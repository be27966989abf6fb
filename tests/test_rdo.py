"""Rate-distortion features: per-MB intra mode decision, perceptual
AQ (mb_qp_delta), P_Skip bias — device/reference parity and
conformance (encoder recon == independent in-repo decode, plus the
libavcodec oracle when present).
"""

import numpy as np
import pytest

from thinvids_tpu.codecs.h264 import decoder as dec_mod
from thinvids_tpu.codecs.h264 import encoder as enc_mod
from thinvids_tpu.codecs.h264 import jaxcore, rdo
from thinvids_tpu.codecs.h264.rdo import RD_OFF, RdConfig
from thinvids_tpu.core.types import VideoMeta
from thinvids_tpu.tools.pan import make_frames


RD_ALL = RdConfig(mode_decision=True, pskip=True, deblock=True,
                  aq_q=rdo.aq_from_strength(1.0))


def _meta(w, h, n):
    return VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                     num_frames=n)


class TestRdConfig:
    def test_defaults_off_and_hashable(self):
        assert RD_OFF == RdConfig()
        assert not (RD_OFF.mode_decision or RD_OFF.pskip
                    or RD_OFF.deblock or RD_OFF.aq)
        hash(RD_ALL)                  # usable as a jit static
        assert not RD_OFF.ships_modes
        assert RdConfig(mode_decision=True).ships_modes
        assert RdConfig(aq_q=4).ships_modes

    def test_aq_quantization(self):
        assert rdo.aq_from_strength(0.0) == 0
        assert rdo.aq_from_strength(1.0) == rdo.AQ_QUANT
        assert rdo.aq_from_strength(10.0) == 3 * rdo.AQ_QUANT

    def test_rd_from_settings(self):
        from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings

        snap = Settings(values=dict(DEFAULT_SETTINGS))
        assert rdo.rd_from_settings(snap) == RD_OFF
        snap = Settings(values=dict(DEFAULT_SETTINGS, mode_decision=True,
                                    pskip=True, deblock=True,
                                    aq_strength=1.0))
        rd = rdo.rd_from_settings(snap)
        assert rd.mode_decision and rd.pskip and rd.deblock
        assert rd.aq_q == rdo.AQ_QUANT


class TestIntraParity:
    """jaxcore._intra_core and the numpy reference must agree bit for
    bit — levels, recon, modes, qp map — for every feature combo."""

    @pytest.mark.parametrize("rd", [
        RD_OFF,
        RdConfig(mode_decision=True),
        RdConfig(aq_q=4),
        RdConfig(mode_decision=True, aq_q=6),
    ])
    def test_numpy_vs_jax(self, rd):
        f = make_frames(1, 144, 112, seed=3)[0].padded(16)
        lev_np, _ = enc_mod.encode_frame_arrays(f.y, f.u, f.v, 27, rd=rd)
        lev_jx = jaxcore.encode_intra_jax(f.y, f.u, f.v, 27, rd)
        for k in ("luma_dc", "luma_ac", "chroma_dc", "chroma_ac",
                  "luma_mode", "chroma_mode"):
            np.testing.assert_array_equal(
                np.asarray(getattr(lev_np, k), np.int32),
                np.asarray(getattr(lev_jx, k), np.int32), err_msg=k)
        if rd.aq:
            np.testing.assert_array_equal(lev_np.qp_delta,
                                          lev_jx.qp_delta)

    def test_mode_decision_actually_decides(self):
        f = make_frames(1, 160, 128, seed=5)[0].padded(16)
        lev, _ = enc_mod.encode_frame_arrays(f.y, f.u, f.v, 27,
                                             rd=RdConfig(mode_decision=True))
        # all three luma modes in play on textured content
        assert set(np.unique(lev.luma_mode)) >= {0, 1, 2}

    def test_greedy_constraint_no_adjacent_switches(self):
        """A switched MB's left neighbor must have kept vertical —
        otherwise its H/DC prediction read a stale recon."""
        f = make_frames(1, 160, 128, seed=5)[0].padded(16)
        lev, _ = enc_mod.encode_frame_arrays(f.y, f.u, f.v, 27,
                                             rd=RdConfig(mode_decision=True))
        mbw = 160 // 16
        modes = np.asarray(lev.luma_mode).reshape(-1, mbw)
        cmodes = np.asarray(lev.chroma_mode).reshape(-1, mbw)
        for r in range(1, modes.shape[0]):
            switched = (modes[r] != 0) | (cmodes[r] != 2)
            assert not (switched[1:] & switched[:-1]).any()

    def test_aq_offsets_zero_mean_and_clamped(self):
        y = make_frames(1, 320, 256, seed=8)[0].y
        off = rdo.aq_offsets_np(y, rdo.AQ_QUANT, 320 // 16, 256 // 16)
        assert abs(float(off.mean())) < 1.0
        assert off.max() <= rdo.AQ_MAX_DELTA
        assert off.min() >= -rdo.AQ_MAX_DELTA
        # flat frame: no modulation
        flat = np.full((256, 320), 128, np.uint8)
        assert not rdo.aq_offsets_np(flat, rdo.AQ_QUANT, 20, 16).any()

    def test_satd_matches_direct_hadamard(self):
        rng = np.random.default_rng(0)
        r = rng.integers(-200, 200, (16, 16)).astype(np.int32)
        import jax.numpy as jnp

        got = int(np.asarray(jaxcore._satd16(jnp.asarray(r)[None]))[0])
        assert got == rdo.satd16_np(r)


class TestStreamConformance:
    """Full GOP encode with features on: the emitted stream must decode
    (in-repo decoder) to exactly the encoder's recon — skip runs,
    mb_qp_delta chains and deblocked references included."""

    @pytest.mark.parametrize("rd", [
        RdConfig(pskip=True, deblock=True),
        RdConfig(mode_decision=True, aq_q=4),
        RD_ALL,
    ])
    def test_decode_matches_recon(self, rd):
        w, h, n = 96, 80, 4
        frames = make_frames(n, w, h)
        stream, recons = enc_mod.encode_gop(frames, _meta(w, h, n),
                                            qp=27, return_recon=True,
                                            rd=rd)
        dec = dec_mod.decode_annexb(stream)
        assert len(dec.frames) == n
        for i in range(n):
            np.testing.assert_array_equal(
                dec.frames[i].y, np.asarray(recons[0])[i][:h, :w])
            np.testing.assert_array_equal(
                dec.frames[i].u, np.asarray(recons[1])[i][:h // 2, :w // 2])
            np.testing.assert_array_equal(
                dec.frames[i].v, np.asarray(recons[2])[i][:h // 2, :w // 2])

    def test_pskip_reduces_bits_and_emits_skips(self):
        # reuses the (pskip, deblock) program compiled above
        w, h, n = 96, 80, 4
        frames = make_frames(n, w, h)
        base, _ = enc_mod.encode_gop(frames, _meta(w, h, n), qp=27,
                                     return_recon=True)
        biased, _ = enc_mod.encode_gop(frames, _meta(w, h, n), qp=27,
                                       return_recon=True,
                                       rd=RdConfig(pskip=True,
                                                   deblock=True))
        assert len(biased) < len(base)

    def test_deblock_signaled_in_headers(self):
        from thinvids_tpu.codecs.h264.headers import (SPS, PPS,
                                                      SliceHeader)
        from thinvids_tpu.io.bits import BitReader, split_annexb

        w, h, n = 96, 80, 4
        frames = make_frames(n, w, h)
        stream, _ = enc_mod.encode_gop(frames, _meta(w, h, n), qp=27,
                                       return_recon=True,
                                       rd=RdConfig(pskip=True,
                                                   deblock=True))
        sps = pps = None
        idcs = []
        for ref_idc, typ, rbsp in split_annexb(stream):
            if typ == 7:
                sps = SPS.parse_rbsp(rbsp)
            elif typ == 8:
                pps = PPS.parse_rbsp(rbsp)
            elif typ in (1, 5):
                hdr = SliceHeader.parse(BitReader(rbsp), sps, pps, typ,
                                        ref_idc)
                idcs.append(hdr.deblock_idc)
        assert idcs and all(i == 0 for i in idcs)

    def test_aq_qp_delta_roundtrip(self):
        """AQ streams carry chained mb_qp_delta: nonzero offsets must
        reach the bitstream and decode cleanly (jit-free: numpy
        reference + python packer + in-repo decoder)."""
        w, h = 144, 112
        f0 = make_frames(1, w, h)[0].padded(16)
        rd = RdConfig(aq_q=rdo.AQ_QUANT)
        lev, _ = enc_mod.encode_frame_arrays(f0.y, f0.u, f0.v, 27, rd=rd)
        assert lev.qp_delta is not None and np.ptp(lev.qp_delta) > 0
        from thinvids_tpu.codecs.h264.headers import PPS, SPS

        sps, pps = SPS(width=w, height=h), PPS(init_qp=27)
        nal = enc_mod.pack_slice(lev, w // 16, h // 16, sps, pps, 27,
                                 native=False)
        _, recons = enc_mod.encode_frame_arrays(f0.y, f0.u, f0.v, 27,
                                                rd=rd)
        dec = dec_mod.decode_annexb(sps.to_nal() + pps.to_nal() + nal)
        # the decoder's running mb_qp_delta chain reproduces the
        # per-MB map: its output equals the reference recon bit-exact
        np.testing.assert_array_equal(dec.frames[0].y, recons[0][:h, :w])

    def test_python_and_native_packers_agree_with_features(self):
        from thinvids_tpu import native

        if not native.available():
            pytest.skip("no compiler for the native packer")
        w, h = 144, 112
        f = make_frames(1, w, h, seed=2)[0].padded(16)
        rd = RdConfig(mode_decision=True, aq_q=4)
        lev, _ = enc_mod.encode_frame_arrays(f.y, f.u, f.v, 27, rd=rd)
        from thinvids_tpu.codecs.h264.headers import PPS, SPS

        sps = SPS(width=w, height=h)
        pps = PPS(init_qp=27)
        a = enc_mod.pack_slice(lev, w // 16, h // 16, sps, pps, 27,
                               native=False)
        b = enc_mod.pack_slice(lev, w // 16, h // 16, sps, pps, 27,
                               native=True)
        assert a == b

    def test_oracle_decodes_feature_streams(self):
        from thinvids_tpu.tools import oracle

        if not oracle.oracle_available():
            pytest.skip("libavcodec oracle not available")
        w, h, n = 96, 80, 4
        frames = make_frames(n, w, h)
        # skip + mode decision + AQ are bit-exact against the oracle
        # (deblock has its own bounded parity test in test_deblock)
        rd = RdConfig(mode_decision=True, aq_q=4)
        stream, recons = enc_mod.encode_gop(frames, _meta(w, h, n),
                                            qp=27, return_recon=True,
                                            rd=rd)
        decoded = oracle.decode_h264(stream)
        assert len(decoded) == n
        ry = np.asarray(recons[0])
        for i, (oy, _u, _v) in enumerate(decoded):
            np.testing.assert_array_equal(oy, ry[i][:h, :w])


class TestShardedPaths:
    """The sharded transfer paths (modes/dqp side channel, pskip,
    deblock recon carry) must produce byte-identical streams to the
    blocked single-GOP program, wave after wave."""

    @pytest.mark.parametrize("rd", [RD_ALL])
    def test_gop_shard_encoder_matches_encode_gop(self, rd):
        import jax
        from jax.sharding import Mesh

        from thinvids_tpu.core.types import concat_segments
        from thinvids_tpu.parallel.dispatch import GopShardEncoder

        w, h, n, gop = 96, 80, 8, 4
        frames = make_frames(n, w, h)
        meta = _meta(w, h, n)
        # one-device mesh: the plan keeps 4-frame GOPs, so the direct
        # per-GOP encode below describes the same segments
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("gop",))
        enc = GopShardEncoder(meta, qp=27, gop_frames=gop, rd=rd,
                              mesh=mesh)
        sharded = concat_segments(enc.encode_waves(
            enc.stage_waves(frames)))
        direct = b"".join(
            enc_mod.encode_gop(frames[g:g + gop], meta, qp=27,
                               idr_pic_id=g // gop,
                               with_headers=True, rd=rd,
                               # reuse the conformance tests' compiled
                               # emit_recon program instead of building
                               # a second XLA program for this shape
                               return_recon=True)[0]
            for g in range(0, n, gop))
        assert sharded == direct

    def test_served_encoder_at_the_serving_point(self):
        """The served path's door (`make_shard_encoder`, settings as
        the daemon reads them: QP 25, mode decision, P_Skip, in-loop
        filter, AQ 1.0 — benchmark/configs/serving-1080p.json) on a
        CPU mesh against the repo's reference encode of a GOP
        (`encoder.encode_gop`, the blocked single-GOP program whose
        recon the oracle tests hold to libavcodec): the same bytes.
        Integer-exact codec, so equality is the only tolerance."""
        import jax
        from jax.sharding import Mesh

        from thinvids_tpu.core.config import (get_settings,
                                              reset_live_settings,
                                              update_live_settings)
        from thinvids_tpu.core.types import concat_segments
        from thinvids_tpu.parallel.dispatch import make_shard_encoder

        w, h, n, gop = 96, 80, 8, 4
        frames = make_frames(n, w, h)
        meta = _meta(w, h, n)
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("gop",))
        try:
            update_live_settings({
                "qp": 25, "gop_frames": gop, "mode_decision": True,
                "pskip": True, "deblock": True, "aq_strength": 1.0})
            enc = make_shard_encoder(meta, get_settings(), mesh)
        finally:
            reset_live_settings()
        assert enc.rd == RD_ALL
        served = concat_segments(enc.encode_waves(
            enc.stage_waves(frames)))
        direct = b"".join(
            enc_mod.encode_gop(frames[g:g + gop], meta, qp=25,
                               idr_pic_id=g // gop, with_headers=True,
                               rd=RD_ALL, return_recon=True)[0]
            for g in range(0, n, gop))
        assert served == direct

    def test_rd_resolves_from_settings(self):
        from thinvids_tpu.core.config import (reset_live_settings,
                                              update_live_settings)
        from thinvids_tpu.parallel.dispatch import GopShardEncoder

        try:
            update_live_settings({"pskip": True, "deblock": True})
            enc = GopShardEncoder(_meta(64, 48, 2), qp=27)
            assert enc.rd.pskip and enc.rd.deblock
        finally:
            reset_live_settings()
