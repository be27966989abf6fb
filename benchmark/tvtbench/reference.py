"""The plain reference of a transcode: an independent decoder.

An H.264 encoder is correct when a decoder that shares no code with it
(libavcodec, through cv2.VideoCapture) turns its output back into the
source's frames: as many of them, and as close as the operating point
promises (PSNR-Y against the generated source, `psnr_floor_db` in the
configuration). The arithmetic is `tools/metrics.psnr`, copied."""

import numpy as np

from . import sources


def psnr(ref, dist, peak=255.0):
    """Peak signal-to-noise ratio in dB of two uint8 planes (inf for
    identical ones). The squared error is summed in integers, which is
    exact and several times faster than the float64 original."""
    diff = ref.astype(np.int32) - dist
    mse = int(np.einsum("ij,ij->", diff, diff, dtype=np.int64)) / diff.size
    if mse == 0:
        return float("inf")
    return float(10.0 * np.log10(peak * peak / mse))


def decode_and_compare(output, source, frames, width, height):
    """(frames libavcodec decodes from `output`, mean PSNR-Y over the
    frames both sides have). The capture hands back the decoder's own
    planes (CONVERT_RGB off): a BGR round trip would rescale luma by
    the colour range it assumes."""
    import cv2

    # (raw mode warns "yuv420p ... treated as 8UC1" once per frame)
    cv2.utils.logging.setLogLevel(cv2.utils.logging.LOG_LEVEL_ERROR)
    cap = cv2.VideoCapture(output)
    cap.set(cv2.CAP_PROP_CONVERT_RGB, 0)
    n, per_frame = 0, []
    try:
        while True:
            ok, raw = cap.read()
            if not ok:
                break
            if n < frames:     # luma leads the planes
                per_frame.append(psnr(
                    sources.luma(source, n, width, height),
                    raw.reshape(-1, width)[:height]))
            n += 1
    finally:
        cap.release()
    return n, (sum(per_frame) / len(per_frame) if per_frame else 0.0)
