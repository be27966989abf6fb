"""Checks of a run that are not a decode: the give-way scan and the
comparison with the XLA mirror."""

import re

from . import mp4box

#: any of these in the daemon's log or an activity feed means the path
#: looked healthy by giving way somewhere (chip_smoke.GIVE_WAY)
GIVE_WAY = [re.compile(p) for p in (
    r"copy_to_host_async rejected",
    r"falling back to threaded pack",
    r"pack sidecar pool broke",
    r"native packer unavailable",
    r"replanning frames",
    r"attempt \d+ failed, retrying",
    r"device metrics unavailable",
    r"cannot count devices",
)]



def give_way_lines(lines):
    return [ln.strip()[:300] for ln in lines
            if any(p.search(ln) for p in GIVE_WAY)]


def mirror_equal(output, mirror_nals):
    """True when the first slice NALs of `output` (an MP4) are, byte
    for byte, the ones the XLA mirror child wrote to `mirror_nals`."""
    try:
        with open(mirror_nals, "rb") as fp:
            want = mp4box.vcl_nals(memoryview(fp.read()), 1 << 30)
    except FileNotFoundError:
        return False
    with open(output, "rb") as fp:
        have = mp4box.vcl_nals(mp4box.mdat(fp.read()), len(want))
    return bool(want) and have == want
