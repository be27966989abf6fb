"""ME kernel: the least time its calls could take / the time they took.
The bound is the HBM one (roofline.me_search_bytes over the chip's
bytes/s): no integer VPU peak is published. A split frame searches one
band per chip."""

from tvtbench import roofline


def read(ev):
    prof = ev["profile"]
    if not prof or not prof["me"]:
        return None
    bands = int(ev["job_settings"].get("sfe_bands", 0) or 1)
    per_call = roofline.me_search_bytes(-(-ev["height"] // bands),
                                        ev["width"])
    least_s = prof["me"]["events"] * per_call \
        / roofline.peak(ev["device"]["kind"], "hbm_bytes_per_s")
    took_s = prof["me"]["seconds"] * len(prof["device_planes"])
    return 100.0 * least_s / took_s
