"""JAX compute path and native packer must match the numpy/Python reference
bit-exactly — these are the "same bits, different engine" guarantees that
let production run the fast paths while conformance is proven on the slow ones."""

import numpy as np
import pytest

from thinvids_tpu import native
from thinvids_tpu.codecs.h264.encoder import encode_frame_arrays, pack_slice
from thinvids_tpu.codecs.h264.headers import PPS, SPS
from thinvids_tpu.codecs.h264.jaxcore import encode_intra_jax


def _planes(w, h, seed=7):
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    y = np.clip(((xx * 2 + yy) % 256) + rng.integers(-8, 8, (h, w)),
                0, 255).astype(np.uint8)
    u = np.clip(128 + rng.integers(-20, 20, (h // 2, w // 2)), 0, 255).astype(np.uint8)
    v = np.clip(128 + rng.integers(-20, 20, (h // 2, w // 2)), 0, 255).astype(np.uint8)
    return y, u, v


class TestJaxCore:
    @pytest.mark.parametrize("size", [(64, 48), (96, 32), (16, 16)])
    @pytest.mark.parametrize("qp", [10, 27, 40])
    def test_bit_exact_vs_numpy(self, size, qp):
        w, h = size
        y, u, v = _planes(w, h)
        ref, _ = encode_frame_arrays(y, u, v, qp)
        jx = encode_intra_jax(y, u, v, qp)
        for name in ("luma_dc", "luma_ac", "chroma_dc", "chroma_ac",
                     "luma_mode", "chroma_mode"):
            assert np.array_equal(getattr(ref, name), getattr(jx, name)), name


@pytest.mark.skipif(not native.available(), reason="no C++ toolchain")
class TestNativePacker:
    @pytest.mark.parametrize("qp", [8, 20, 27, 40])
    def test_bit_identical_vs_python(self, qp):
        w, h = 96, 64
        y, u, v = _planes(w, h)
        sps, pps = SPS(width=w, height=h), PPS(init_qp=qp)
        levels, _ = encode_frame_arrays(y, u, v, qp)
        py = pack_slice(levels, w // 16, h // 16, sps, pps, qp, native=False)
        nat = pack_slice(levels, w // 16, h // 16, sps, pps, qp, native=True)
        assert py == nat

    def test_noise_worst_case(self):
        # pure noise maximizes coefficient density / table coverage
        rng = np.random.default_rng(0)
        w, h = 64, 32
        y = rng.integers(0, 256, (h, w), dtype=np.uint8)
        u = rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8)
        v = rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8)
        for qp in (4, 16, 30):
            sps, pps = SPS(width=w, height=h), PPS(init_qp=qp)
            levels, _ = encode_frame_arrays(y, u, v, qp)
            py = pack_slice(levels, w // 16, h // 16, sps, pps, qp, native=False)
            nat = pack_slice(levels, w // 16, h // 16, sps, pps, qp, native=True)
            assert py == nat
