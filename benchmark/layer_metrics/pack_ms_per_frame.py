"""host pack: growth of `sparse_unpack + unflatten + pack` (GOP waves) and
`sfe` (the split-frame path's per-frame unpack + band-slice pack) over
the window / frames. Host thread time summed over the pack pool."""

from tvtbench import evidence


def read(ev):
    return evidence.per_frame(ev, "sparse_unpack", "unflatten", "pack", "sfe")
