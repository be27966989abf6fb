"""deblock kernel: the least time its calls could take / the time they
took, in the traced job's profile. One call per frame; the bound is the
HBM one (roofline_deblock.deblock_bytes over the chip's bytes/s): no
integer VPU peak is published. Not measured where the profile holds no
op of that name (a program without the kernel)."""

from tvtbench import evidence, roofline, roofline_deblock, scope_reduce

KERNEL = "tvt_deblock_wavefront"


def read(ev):
    got = scope_reduce.scopes_of(ev)
    job = evidence.traced_job(ev)
    if got is None or job is None:
        return None
    took_s = sum(row[0] for row in got["ops"] if KERNEL in row[2])
    if not took_s:
        return None
    least_s = job["frames"] * roofline_deblock.deblock_bytes(
        ev["height"], ev["width"]) \
        / roofline.peak(ev["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / took_s
