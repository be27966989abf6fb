"""Operations and bytes of the kernels, from shapes; peaks by device.

The motion search (`codecs/h264/jaxme._me_pallas`) is integer SAD work
on the VPU with one small f32 matmul per block sum; no integer VPU
peak of the v5e is published, so the bound that can be stated is the
HBM one: the bytes one call must move over the chip's bytes per
second."""

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peak(device_kind, key):
    """A peak of the device from peaks.json; an unknown device is an
    error, not a default."""
    with open(_PEAKS, encoding="utf-8") as fp:
        table = json.load(fp)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}")
    return float(table[device_kind][key])


def me_search_bytes(height, width):
    """Bytes one motion search over a `height` x `width` picture (or
    band) must move, as the kernel holds its planes: int16 samples in,
    int16 predictions out, one int32 half-pel vector pair per
    macroblock. Reads: current luma, reference Y, U, V, each once.
    Writes: predicted Y, U, V and the vectors. Halo rows, lane padding
    and the three search centres' re-reads are what the kernel adds to
    this, not what the search needs."""
    h = -(-height // 16) * 16
    w = -(-width // 16) * 16
    luma = h * w
    chroma = 2 * (h // 2) * (w // 2)
    vectors = (h // 16) * (w // 16) * 2 * 4
    return 2 * (luma + luma + chroma) + 2 * (luma + chroma) + vectors
