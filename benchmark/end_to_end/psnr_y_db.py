"""Mean luma PSNR of one output per distinct source, decoded by
libavcodec, against the generated source; outside the window."""


def read(ev):
    values = ev["psnr_y_db"]
    return sum(values) / len(values) if values else None
