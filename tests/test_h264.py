"""H.264 codec tests: transforms, CAVLC, headers, encoder↔decoder, oracle.

Conformance strategy (SURVEY.md §4): golden/structural unit tests per
stage, an in-repo independent decoder cross-check, and a libavcodec
external-oracle bit-exactness test of encoder reconstruction.
"""

import numpy as np
import pytest

from thinvids_tpu.codecs.h264 import cavlc, tables
from thinvids_tpu.codecs.h264.decoder import decode_annexb
from thinvids_tpu.codecs.h264.encoder import (
    H264Encoder,
    encode_frame_arrays,
    encode_frames,
)
from thinvids_tpu.codecs.h264.headers import PPS, SPS
from thinvids_tpu.codecs.h264.transform import (
    MF_TABLE,
    V_TABLE,
    chroma_qp,
    dequant_4x4,
    forward_4x4,
    inverse_4x4,
    inverse_zigzag,
    quant_4x4,
    zigzag,
)
from thinvids_tpu.core.types import Frame, VideoMeta
from thinvids_tpu.io.bits import BitReader, BitWriter
from thinvids_tpu.tools import oracle


def synthetic_frame(w, h, seed=7, flat=False):
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    if flat:
        y = np.full((h, w), 128, np.uint8)
    else:
        y = np.clip(((xx * 2 + yy) % 256).astype(int)
                    + rng.integers(-8, 8, (h, w)), 0, 255).astype(np.uint8)
    u = np.clip(128 + (xx[::2, ::2] // 2) - 30
                + rng.integers(-5, 5, (h // 2, w // 2)), 0, 255).astype(np.uint8)
    v = np.clip(128 - (yy[::2, ::2] // 2)
                + rng.integers(-5, 5, (h // 2, w // 2)), 0, 255).astype(np.uint8)
    return Frame(y, u, v)


def psnr(a, b):
    mse = np.mean((a.astype(np.float64) - b.astype(np.float64)) ** 2)
    return 10 * np.log10(255**2 / max(mse, 1e-12))


class TestTransform:
    def test_qp0_near_lossless(self):
        # The integer transform pair is only an identity THROUGH the
        # quant/dequant scaling matrices; at qp=0 (finest step) the full
        # loop must reconstruct residuals to within +-1.
        rng = np.random.default_rng(0)
        x = rng.integers(-255, 256, (32, 4, 4)).astype(np.int32)
        w = forward_4x4(x)
        r = (inverse_4x4(dequant_4x4(quant_4x4(w, 0), 0)) + 32) >> 6
        assert np.abs(r - x).max() <= 1

    def test_quant_dequant_monotone(self):
        rng = np.random.default_rng(1)
        x = rng.integers(-200, 200, (16, 4, 4)).astype(np.int32)
        w = forward_4x4(x)
        errs = []
        for qp in (0, 10, 20, 30, 40, 50):
            z = quant_4x4(w, qp)
            d = dequant_4x4(z, qp)
            r = (inverse_4x4(d) + 32) >> 6
            errs.append(np.abs(r - x).mean())
        assert errs == sorted(errs)  # coarser qp → larger error

    def test_zigzag_roundtrip(self):
        x = np.arange(16, dtype=np.int32).reshape(4, 4)
        assert np.array_equal(inverse_zigzag(zigzag(x)), x)
        # spec order: second element is (0,1), third is (1,0)
        assert zigzag(x)[1] == x[0, 1]
        assert zigzag(x)[2] == x[1, 0]

    def test_table_classes(self):
        # position-class values from the spec: (0,0)=class0, (1,1) largest V
        assert MF_TABLE[0][0, 0] == 13107
        assert V_TABLE[0][0, 0] == 10
        assert V_TABLE[0][1, 1] == 16
        assert V_TABLE[0][0, 1] == 13

    def test_chroma_qp_mapping(self):
        assert chroma_qp(0) == 0
        assert chroma_qp(29) == 29
        assert chroma_qp(30) == 29
        assert chroma_qp(51) == 39


class TestCavlcTables:
    @pytest.mark.parametrize("ctx", range(4))
    def test_coeff_token_prefix_free(self, ctx):
        codes = list(tables.COEFF_TOKEN[ctx].values())
        assert tables.check_prefix_free(codes) == []

    def test_chroma_dc_complete(self):
        codes = list(tables.CHROMA_DC_COEFF_TOKEN.values())
        assert tables.check_prefix_free(codes) == []
        assert tables.kraft_sum(codes) == 1.0

    def test_total_zeros_complete(self):
        for tc, codes in tables.TOTAL_ZEROS_4x4.items():
            assert tables.check_prefix_free(codes) == [], tc
            expected = 1.0 if tc != 1 else 1.0 - 2.0**-9
            assert abs(tables.kraft_sum(codes) - expected) < 1e-12, tc
        for tc, codes in tables.TOTAL_ZEROS_CHROMA_DC.items():
            assert tables.kraft_sum(codes) == 1.0

    def test_run_before_complete(self):
        for zl, codes in tables.RUN_BEFORE.items():
            assert tables.check_prefix_free(codes) == [], zl


class TestCavlcRoundtrip:
    @pytest.mark.parametrize("seed", range(3))
    def test_fuzz(self, seed):
        rng = np.random.default_rng(seed)
        for _ in range(2000):
            max_coeff = int(rng.choice([16, 15, 4]))
            nc = -1 if max_coeff == 4 else int(rng.choice([0, 1, 2, 3, 5, 8, 20]))
            coeffs = [0] * max_coeff
            density = rng.uniform(0, 1)
            for i in range(max_coeff):
                if rng.uniform() < density:
                    coeffs[i] = int(rng.choice([1, 1, 2, 3, 5, 9, 200])) * \
                        (1 if rng.uniform() < 0.5 else -1)
            bw = BitWriter()
            cavlc.encode_residual(bw, coeffs, nc)
            bw.byte_align()
            out = cavlc.decode_residual(BitReader(bw.getvalue()), nc, max_coeff)
            assert out == coeffs


class TestHeaders:
    def test_sps_roundtrip(self):
        sps = SPS(width=1920, height=1080, fps_num=30000, fps_den=1001)
        parsed = SPS.parse_rbsp(sps.to_rbsp())
        assert parsed.width == 1920 and parsed.height == 1080
        assert parsed.fps_num == 30000 and parsed.fps_den == 1001

    def test_pps_roundtrip(self):
        pps = PPS(init_qp=33)
        parsed = PPS.parse_rbsp(pps.to_rbsp())
        assert parsed.init_qp == 33
        assert parsed.deblocking_control_present


class TestEncoderDecoder:
    @pytest.mark.parametrize("qp", [10, 27, 40])
    def test_own_decoder_matches_recon(self, qp):
        frame = synthetic_frame(64, 48)
        meta = VideoMeta(width=64, height=48)
        stream = H264Encoder(meta, qp=qp).encode_frame(frame)
        padded = frame.padded(16)
        _, (ry, ru, rv) = encode_frame_arrays(padded.y, padded.u, padded.v, qp)
        dec = decode_annexb(stream)
        assert np.array_equal(dec.frames[0].y, ry[:48, :64])
        assert np.array_equal(dec.frames[0].u, ru[:24, :32])
        assert np.array_equal(dec.frames[0].v, rv[:24, :32])

    def test_cropped_dimensions(self):
        frame = synthetic_frame(36, 20)
        meta = VideoMeta(width=36, height=20)
        stream = H264Encoder(meta, qp=27).encode_frame(frame)
        dec = decode_annexb(stream)
        assert dec.frames[0].y.shape == (20, 36)
        assert dec.meta.width == 36 and dec.meta.height == 20

    def test_multi_frame_stream(self):
        meta = VideoMeta(width=32, height=32)
        frames = [synthetic_frame(32, 32, seed=s) for s in range(3)]
        stream = encode_frames(frames, meta, qp=30)
        dec = decode_annexb(stream)
        assert len(dec.frames) == 3

    def test_quality_improves_with_lower_qp(self):
        frame = synthetic_frame(64, 48)
        meta = VideoMeta(width=64, height=48)
        vals = []
        for qp in (40, 27, 10):
            stream = H264Encoder(meta, qp=qp).encode_frame(frame)
            dec = decode_annexb(stream)
            vals.append(psnr(dec.frames[0].y, frame.y))
        assert vals == sorted(vals)
        assert vals[-1] > 45  # qp=10 should be high fidelity


@pytest.mark.skipif(not oracle.oracle_available(), reason="libavcodec missing")
class TestConformanceOracle:
    @pytest.mark.parametrize("qp", [4, 10, 20, 27, 34, 40, 48])
    def test_bit_exact_vs_libavcodec(self, qp):
        frame = synthetic_frame(64, 48)
        meta = VideoMeta(width=64, height=48)
        stream = H264Encoder(meta, qp=qp).encode_frame(frame)
        padded = frame.padded(16)
        _, (ry, ru, rv) = encode_frame_arrays(padded.y, padded.u, padded.v, qp)
        oy, ou, ov = oracle.decode_h264(stream)[0]
        assert np.array_equal(oy, ry[:48, :64])
        assert np.array_equal(ou, ru[:24, :32])
        assert np.array_equal(ov, rv[:24, :32])

    def test_multi_frame_and_crop(self):
        meta = VideoMeta(width=36, height=20)
        frames = [synthetic_frame(36, 20, seed=s) for s in range(4)]
        stream = encode_frames(frames, meta, qp=24)
        decoded = oracle.decode_h264(stream)
        assert len(decoded) == 4
        assert decoded[0][0].shape == (20, 36)
        # every frame individually bit-exact vs own decoder
        own = decode_annexb(stream)
        for (oy, ou, ov), f in zip(decoded, own.frames):
            assert np.array_equal(oy, f.y)
            assert np.array_equal(ou, f.u)
            assert np.array_equal(ov, f.v)

    def test_flat_frame_minimal_stream(self):
        frame = synthetic_frame(32, 32, flat=True)
        meta = VideoMeta(width=32, height=32)
        stream = H264Encoder(meta, qp=30).encode_frame(frame)
        (oy, ou, ov) = oracle.decode_h264(stream)[0]
        assert np.array_equal(oy, np.full((32, 32), 128))


class TestGuards:
    def test_odd_dimensions_rejected(self):
        with pytest.raises(ValueError, match="odd dimensions"):
            SPS(width=33, height=48).to_rbsp()
        with pytest.raises(ValueError, match="odd dimensions"):
            SPS(width=64, height=47).to_rbsp()

    def test_non_420_input_rejected(self):
        meta = VideoMeta(width=32, height=32)
        enc = H264Encoder(meta, qp=27)
        f422 = Frame(
            y=np.zeros((32, 32), np.uint8),
            u=np.zeros((32, 16), np.uint8),   # full-height chroma: 4:2:2
            v=np.zeros((32, 16), np.uint8),
        )
        with pytest.raises(ValueError, match="4:2:0"):
            enc.encode_frame(f422)

    def test_malformed_chroma_plane_rejected(self):
        f = Frame(
            y=np.zeros((64, 64), np.uint8),
            u=np.zeros((16, 16), np.uint8),   # neither 32 nor 64
            v=np.zeros((16, 16), np.uint8),
        )
        with pytest.raises(ValueError, match="chroma"):
            f.padded(16)

    def test_native_escape_overflow_matches_python(self):
        # A level too large for the baseline CAVLC 12-bit escape must
        # raise in BOTH packers (the native path previously emitted a
        # corrupt stream silently).
        from thinvids_tpu import native
        from thinvids_tpu.codecs.h264.encoder import FrameLevels, pack_slice

        if not native.available():
            pytest.skip("no compiler")
        nmb = 1
        levels = FrameLevels(
            luma_mode=np.zeros(nmb, np.int32),
            chroma_mode=np.zeros(nmb, np.int32),
            luma_dc=np.zeros((nmb, 16), np.int32),
            luma_ac=np.zeros((nmb, 16, 15), np.int32),
            chroma_dc=np.zeros((nmb, 2, 4), np.int32),
            chroma_ac=np.zeros((nmb, 2, 4, 15), np.int32),
        )
        levels.luma_ac[0, 0, 0] = 3000   # level_code far beyond 12-bit escape
        sps = SPS(width=16, height=16)
        pps = PPS(init_qp=27)
        with pytest.raises(ValueError, match="too large"):
            pack_slice(levels, 1, 1, sps, pps, 27, native=True)
        with pytest.raises(ValueError, match="too large"):
            pack_slice(levels, 1, 1, sps, pps, 27, native=False)

    def test_native_int16_islice_matches_int32_and_python(self):
        # The int16 entry (cavlc_pack_islice16, fed by the transfer
        # layout's zero-copy views) must emit the exact bits of the
        # int32 entry and of the pure-Python packer.
        from thinvids_tpu import native
        from thinvids_tpu.codecs.h264.encoder import FrameLevels, pack_slice

        if not native.available():
            pytest.skip("no compiler")
        rng = np.random.default_rng(3)
        nmb = 12
        arrs = {
            "luma_dc": rng.integers(-200, 201, (nmb, 16)),
            "luma_ac": (rng.integers(-8, 9, (nmb, 16, 15))
                        * (rng.random((nmb, 16, 15)) < 0.2)),
            "chroma_dc": rng.integers(-150, 151, (nmb, 2, 4)),
            "chroma_ac": (rng.integers(-5, 6, (nmb, 2, 4, 15))
                          * (rng.random((nmb, 2, 4, 15)) < 0.15)),
        }

        def levels(dtype):
            return FrameLevels(
                luma_mode=np.zeros(nmb, np.int32),
                chroma_mode=np.zeros(nmb, np.int32),
                **{k: v.astype(dtype) for k, v in arrs.items()})

        sps = SPS(width=64, height=48)
        pps = PPS(init_qp=27)
        a32 = pack_slice(levels(np.int32), 4, 3, sps, pps, 27, native=True)
        a16 = pack_slice(levels(np.int16), 4, 3, sps, pps, 27, native=True)
        py = pack_slice(levels(np.int32), 4, 3, sps, pps, 27, native=False)
        assert a16 == a32 == py
        # escape overflow propagates from the int16 entry too (the
        # largest int16 level exceeds the 12-bit escape budget)
        bad = levels(np.int16)
        bad.luma_ac[0, 0, 0] = 3000
        with pytest.raises(ValueError, match="too large"):
            pack_slice(bad, 4, 3, sps, pps, 27, native=True)


def test_native_artifact_is_named_by_source_content(tmp_path, monkeypatch):
    """The .so's name carries a hash of cavlc_pack.cpp's CONTENT (no
    mtime comparison): an artifact built from any other source — stale,
    or copied in from another tree — is never the one loaded."""
    import hashlib
    import os

    from thinvids_tpu import native

    with open(native._SRC, "rb") as fp:
        source = fp.read()
    assert native.source_hash() == hashlib.sha256(source).hexdigest()[:16]
    real = native._so_path("")
    assert os.path.basename(real) == \
        f"cavlc_pack.{native.source_hash()}.so"
    assert native._so_path("asan").endswith(
        f".{native.source_hash()}.asan.so")

    edited = tmp_path / "cavlc_pack.cpp"
    edited.write_bytes(source + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", str(edited))
    assert native._so_path("") != real
