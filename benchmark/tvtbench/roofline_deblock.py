"""Bytes of the in-loop filter's kernel, from shapes.

The kernel (`codecs/h264/jaxdeblock._scan_kernel`, the custom call
`tvt_deblock_wavefront`) is integer compare-and-select work on the
VPU, one call per frame, 254 sequential wavefronts at 1080p. No
integer VPU peak of the v5e is published (peaks.json), so the bound
that can be stated is the HBM one, as for the motion search: the bytes
one call must move over the chip's bytes per second."""


def deblock_bytes(height, width):
    """Bytes one filtering of a `height` x `width` picture must move:
    every int16 sample of Y, U and V read once and written once. The
    packed edge parameters, the lanes a 128-wide register leaves blank
    and the two flush blocks are what the kernel adds to this, not
    what the filter needs."""
    h = -(-height // 16) * 16
    w = -(-width // 16) * 16
    samples = h * w + 2 * (h // 2) * (w // 2)
    return 2 * 2 * samples
