"""Intra4x4 macroblocks in IDR pictures (H.264 §7.3.5 I_NxN, §8.3.1): the
setting `intra4x4` (ISSUE 49).

- the nine predictors, the predicted mode and the availability rules of
  `codecs/h264/intra.py` (numpy) and `jaxcore._i4_predictions` (the
  device's) against `tools/intra4x4_plain.py`, at every availability
  pattern;
- device levels, modes and reconstruction == numpy encoder;
- native packer == Python packer, byte for byte; what either refuses;
- `decoder.py` and libavcodec decode the stream to the encoder's
  reconstruction with `deblock` and AQ on, an Intra4x4 macroblock with
  no level between two QPs among them;
- the compact payload with its ranged unpack, and the dense fallback,
  carry the kind and the modes; `scenecut`'s bounded loop, `subpel`
  quarter and `p_intra` beside it;
- off, every GOP program is the parent's (jaxpr);
- the setting, its refusals at admission, ladder rungs, the remote
  plan's signature and shard tag;
- `tools/screen` = `benchmark/generators/screen`, byte for byte.
"""

import functools
import hashlib
import importlib.util
import itertools
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu import native
from thinvids_tpu.cluster import Coordinator, WorkerRegistry
from thinvids_tpu.cluster.executor import LocalExecutor
from thinvids_tpu.cluster.policy import evaluate_job_policy
from thinvids_tpu.codecs.h264 import (encoder, intra, jaxcore, jaxinter,
                                      layout, rdo)
from thinvids_tpu.codecs.h264.decoder import decode_annexb
from thinvids_tpu.codecs.h264.encoder import (encode_frame_arrays,
                                              encode_gop, pack_slice)
from thinvids_tpu.codecs.h264.headers import PPS, SPS
from thinvids_tpu.codecs.h264.rdo import RD_OFF, RdConfig, rd_from_settings
from thinvids_tpu.core import config
from thinvids_tpu.core.config import (DEFAULT_SETTINGS, Settings,
                                      overlay_job_settings,
                                      reset_live_settings,
                                      update_live_settings)
from thinvids_tpu.core.status import Status
from thinvids_tpu.core.types import VideoMeta, concat_segments
from thinvids_tpu.io.mp4 import read_mp4
from thinvids_tpu.io.y4m import write_y4m
from thinvids_tpu.parallel import dispatch
from thinvids_tpu.parallel.dispatch import (GopShardEncoder, SfeShardEncoder,
                                            default_mesh)
from thinvids_tpu.parallel.planner import plan_segments
from thinvids_tpu.tools import fuzz_native, intra4x4_plain as plain
from thinvids_tpu.tools import screen
from thinvids_tpu.tools.metrics import psnr

# the mesh of one device, the decoders' comparison with the encoder's
# reconstruction and the Python-packed encoder: tests/test_p_intra.py's
from test_p_intra import (PARENT_JAXPR, _decoders_agree, _numpy_encoder,
                          _one_chip)

SERVING = dict(mode_decision=True, pskip=True, deblock=True, aq_q=4)
#: (qp, RdConfig fields) of the two operating points
POINTS = {"library": (27, {}), "serving": (25, SERVING)}


def _rd(point, **over):
    return RdConfig(**{**POINTS[point][1], **over}, intra4x4=True)


def _meta(w, h, n):
    return VideoMeta(width=w, height=h, fps_num=30, fps_den=1, num_frames=n)


def _no_level(lev):
    return ~(lev.luma_dc.any(1) | lev.luma_ac.any((1, 2))
             | lev.chroma_dc.any((1, 2)) | lev.chroma_ac.any((1, 2, 3)))


# ---------------------------------------------------------------------------
# §8.3.1.2, §8.3.1.1, §6.4.11.4 against the plain reference
# ---------------------------------------------------------------------------

#: which of the neighbouring blocks A (left), B (above), C (above
#: right), D (above left) a block has: every pattern a slice of whole
#: macroblock rows can show (C and D need B; D with A and B)
PATTERNS = [dict(zip("ABCD", bits)) for bits in itertools.product(
    (False, True), repeat=4)
    if (not bits[2] or bits[1]) and bits[3] == (bits[0] and bits[1])]


def _pattern_id(has):
    return "".join(k for k in "ABCD" if has[k]) or "none"


def _block_in_plane(rng):
    """A 12x12 plane of random samples with a 4x4 block at (4, 4)."""
    return rng.integers(0, 256, (12, 12)).astype(np.uint8)


class TestAgainstThePlainReference:
    @pytest.mark.parametrize("has", PATTERNS, ids=_pattern_id)
    def test_the_nine_predictors(self, has):
        """numpy's and the device's predictions of a block equal the
        standard's equations, sample for sample, for every mode the
        pattern allows and on extreme and random neighbours."""
        rng = np.random.default_rng([int(has[k]) for k in "ABCD"])
        planes = [_block_in_plane(rng) for _ in range(6)]
        planes.append(np.full((12, 12), 255, np.uint8))
        planes.append(np.zeros((12, 12), np.uint8))
        allowed = intra.i4_modes_allowed(has["B"], has["A"])
        want_allowed = None
        tops, lefts, corners, wants = [], [], [], []
        for plane in planes:
            p = plain.samples(plane.tolist(), 4, 4, has)
            usable = tuple(m for m in range(9) if plain.usable(m, p))
            assert want_allowed in (None, usable)
            want_allowed = usable
            nb = intra.i4_neighbours(plane, 1, 1, has["B"], has["A"],
                                     has["C"])
            assert (nb[2] is not None) == has["D"]
            for m in usable:
                assert intra.predict_luma4(m, *nb).tolist() \
                    == plain.predict(m, p), (m, has)
            tops.append(nb[0] if nb[0] is not None else np.zeros(8))
            lefts.append(nb[1] if nb[1] is not None else np.zeros(4))
            corners.append(nb[2] if nb[2] is not None else 0)
            wants.append({m: plain.predict(m, p) for m in usable})
        assert allowed == want_allowed
        n = len(planes)
        got = np.asarray(jaxcore._i4_predictions(
            jnp.asarray(np.array(tops, np.int32).T),
            jnp.asarray(np.array(lefts, np.int32).T),
            jnp.asarray(np.array(corners, np.int32)),
            jnp.full(n, has["B"]), jnp.full(n, has["A"])))
        assert got.shape == (9, 4, 4, n)
        for i, want in enumerate(wants):
            for m, pred in want.items():
                assert got[m, :, :, i].tolist() == pred, (m, has, i)

    def test_the_predicted_mode(self):
        """§8.3.1.1 over every pair of neighbour states: not available,
        Intra16x16, or one of the nine modes."""
        states = [None, "not_i4", *range(9)]
        for a, b in itertools.product(states, repeat=2):
            ours = intra.i4_pred_mode(
                *(None if s is None else (intra.I4_DC if s == "not_i4"
                                          else s) for s in (a, b)))
            want = plain.predicted_mode(a, b)
            assert ours == want, (a, b)
            for mode in range(9):
                flag, rem = plain.coded_mode(mode, want)
                assert plain.decoded_mode(flag, rem, want) == mode
                # the packers' arithmetic: rem = mode - (mode > pred)
                assert rem in (None, mode - (mode > want))

    def test_neighbours_in_decoding_order(self):
        """§6.4.11.4: the z-scan, and which blocks lack the block above
        and to the right whatever the macroblocks round them."""
        assert [plain.block_xy(b) for b in range(16)] \
            == [(4 * x, 4 * y) for x, y in intra.LUMA_BLOCK_ORDER]
        every = dict.fromkeys("ABCD", True)
        assert {b for b in range(16)
                if plain.neighbour(b, "C", every) is None} \
            == set(intra.I4_NO_TOP_RIGHT)
        no_c = dict(every, C=False)
        assert {b for b in range(16)
                if plain.neighbour(b, "C", no_c) is None} \
            == set(intra.I4_NO_TOP_RIGHT) | {5}
        assert intra.I4_DC == plain.DC
        assert intra.LUMA_I4X4 == 4

    def test_the_intra_cbp_table_is_a_permutation_with_known_ends(self):
        assert sorted(encoder.CODE_TO_CBP_INTRA) == list(range(48))
        assert encoder.CODE_TO_CBP_INTRA[:4] == (47, 31, 15, 0)
        assert encoder.CBP_INTRA_TO_CODE[0] == 3
        assert encoder.CBP_INTRA_TO_CODE[47] == 0

    def test_a_coded_picture_is_the_plain_prediction_plus_residual(self):
        """Every Intra4x4 block of a decoded picture whose levels are
        all zero IS the plain reference's prediction from the picture
        itself (block by block, in the picture's final samples — no
        filter here)."""
        w, h, qp = 128, 128, 42
        f = screen.make_frames(1, w, h, 5)[0]
        rd = RdConfig(mode_decision=True, aq_q=4, intra4x4=True)
        lev, (ry, _, _) = encode_frame_arrays(f.y, f.u, f.v, qp, rd=rd)
        mbw = w // 16
        rows = ry.tolist()
        checked = 0
        for mi in np.flatnonzero(lev.luma_mode == intra.LUMA_I4X4):
            my, mx = divmod(int(mi), mbw)
            for blk in range(16):
                if lev.luma_dc[mi, blk] or lev.luma_ac[mi, blk].any():
                    continue
                x, y = plain.block_xy(blk)
                want = plain.block_prediction(
                    rows, mx, my, blk, int(lev.i4_modes[mi, blk]), mbw)
                got = ry[16 * my + y:16 * my + y + 4,
                         16 * mx + x:16 * mx + x + 4]
                assert got.tolist() == want, (mi, blk)
                checked += 1
        assert checked > 50


# ---------------------------------------------------------------------------
# device == numpy encoder
# ---------------------------------------------------------------------------

#: (width, height, qp, seed, RdConfig fields)
DEVICE_CASES = [
    (96, 64, 25, 3, dict()),
    (96, 64, 25, 4, dict(mode_decision=True)),
    (128, 128, 42, 5, dict(mode_decision=True, aq_q=4)),
    (160, 96, 30, 6, dict(aq_q=8)),
]


def _device_core(y, u, v, qp, rd):
    mbh, mbw = y.shape[0] // 16, y.shape[1] // 16
    return jax.device_get(jax.jit(functools.partial(
        jaxcore._intra_core, mbw=mbw, mbh=mbh, rd=rd))(
            jnp.asarray(y), jnp.asarray(u), jnp.asarray(v), jnp.asarray(qp)))


class TestTheDeviceIsTheNumpyEncoder:
    @pytest.mark.parametrize("w,h,qp,seed,fields", DEVICE_CASES)
    def test_levels_modes_and_reconstruction(self, w, h, qp, seed, fields):
        f = screen.make_frames(1, w, h, seed)[0]
        rd = RdConfig(**fields, intra4x4=True)
        lev, (ry, ru, rv) = encode_frame_arrays(f.y, f.u, f.v, qp, rd=rd)
        out = _device_core(f.y, f.u, f.v, qp, rd)
        want = (lev.luma_dc, lev.luma_ac, lev.chroma_dc, lev.chroma_ac,
                ry, ru, rv, lev.luma_mode, lev.chroma_mode, lev.qp_delta,
                lev.i4_modes)
        assert len(out) == 11
        for name, a, b in zip("ldc lac cdc cac y u v mode cmode dqp i4".split(),
                              out, want):
            assert np.array_equal(np.asarray(a).reshape(b.shape), b), name
        kinds = lev.luma_mode == intra.LUMA_I4X4
        assert 0.2 < kinds.mean() <= 1.0
        # chroma is the Intra16x16 stage's, untouched
        base, (_, bu, bv) = encode_frame_arrays(
            f.y, f.u, f.v, qp, rd=RdConfig(**fields))
        assert np.array_equal(base.chroma_ac, lev.chroma_ac)
        assert np.array_equal(base.chroma_mode, lev.chroma_mode)
        assert np.array_equal(bu, ru) and np.array_equal(bv, rv)
        # an Intra16x16 macroblock's blocks read DC, and more than one
        # Intra4x4 mode is in use
        assert (lev.i4_modes[~kinds] == intra.I4_DC).all()
        assert len(np.unique(lev.i4_modes[kinds])) >= 5
        # the transfer's side channel brings the same levels home
        got = jaxcore.encode_intra_jax(f.y, f.u, f.v, qp, rd)
        assert np.array_equal(got.i4_modes, lev.i4_modes)
        assert np.array_equal(got.luma_mode, lev.luma_mode)
        assert np.array_equal(got.luma_ac, lev.luma_ac)
        assert np.array_equal(got.qp_delta, lev.qp_delta)

    def test_a_macroblock_with_no_level_takes_the_qp_before_it(self):
        """§7.3.5 / §7.4.5: no coded_block_pattern bit, no
        mb_qp_delta. With AQ on the numpy encoder and the device both
        say so in `qp_delta`: such a macroblock reads its raster
        predecessor's offset, whatever the AQ map gave it."""
        w, h, qp, seed, fields = DEVICE_CASES[2]
        f = screen.make_frames(1, w, h, seed)[0]
        rd = RdConfig(**fields, intra4x4=True)
        lev, _ = encode_frame_arrays(f.y, f.u, f.v, qp, rd=rd)
        held = (lev.luma_mode == intra.LUMA_I4X4) & _no_level(lev)
        aq = rdo.aq_offsets_np(f.y, rd.aq_q, w // 16, h // 16)
        moved = held & (lev.qp_delta != aq)
        assert moved.sum() >= 1                 # between two QPs
        for mi in np.flatnonzero(held):
            assert lev.qp_delta[mi] == (lev.qp_delta[mi - 1] if mi else 0)
        assert np.array_equal(lev.qp_delta[~held], aq[~held])

    def test_the_kind_word_and_the_mode_words_round_trip(self):
        rng = np.random.default_rng(0)
        modes = rng.integers(0, 9, (37, 16))
        modes[0] = 8                            # every nibble's sign bit
        words = encoder.pack_i4_modes(modes)
        assert words.shape == (37, 4) and words.dtype == np.int16
        assert np.array_equal(encoder.unpack_i4_modes(words), modes)
        tail = np.asarray(jaxcore._mode_tail(
            jnp.full(37, 4), jnp.arange(37) % 3, jnp.arange(37) - 18,
            jnp.asarray(modes)))
        assert tail.dtype == np.int16 and tail.shape == (37 * 6,)
        luma, chroma = encoder.unpack_mode16(tail[:37])
        assert (luma == 4).all() and np.array_equal(chroma,
                                                    np.arange(37) % 3)
        assert np.array_equal(tail[74:].reshape(37, 4), words)


# ---------------------------------------------------------------------------
# the packers
# ---------------------------------------------------------------------------

def _levels_of(w, h, qp, seed, **fields):
    f = screen.make_frames(1, w, h, seed)[0]
    rd = RdConfig(**fields, intra4x4=True)
    return encode_frame_arrays(f.y, f.u, f.v, qp, rd=rd)[0]


def _pack_both(lev, w, h, qp):
    sps = SPS(width=w, height=h, fps_num=30, fps_den=1)
    pps = PPS(init_qp=qp)
    return [pack_slice(lev, w // 16, h // 16, sps, pps, qp, native=n)
            for n in (False, True)]


@pytest.mark.skipif(not native.available(), reason="no native packer here")
class TestThePackers:
    @pytest.mark.parametrize("w,h,qp,seed,fields", DEVICE_CASES)
    def test_native_is_python_byte_for_byte(self, w, h, qp, seed, fields):
        lev = _levels_of(w, h, qp, seed, **fields)
        py, nat = _pack_both(lev, w, h, qp)
        assert py == nat
        # int16 views (the transfer's) pack to the same bytes
        lev16 = encoder.FrameLevels(**{
            k: (v.astype(np.int16) if k.startswith(("luma_", "chroma_"))
                and not k.endswith("mode") else v)
            for k, v in vars(lev).items()})
        assert _pack_both(lev16, w, h, qp)[1] == py

    def test_random_kinds_modes_and_levels(self):
        """The fuzz case: random kinds, modes, sparse levels and QP
        offsets that obey §7.3.5 (a macroblock without a level keeps
        its predecessor's)."""
        for seed in range(6):
            case = fuzz_native.random_islice_case(seed)
            assert fuzz_native.islice_packers_agree(case), seed

    def test_what_both_refuse(self):
        w, h, qp, seed, fields = DEVICE_CASES[2]
        lev = _levels_of(w, h, qp, seed, **fields)
        held = np.flatnonzero((lev.luma_mode == intra.LUMA_I4X4)
                              & _no_level(lev))
        assert held.size
        bad = encoder.FrameLevels(**vars(lev))
        bad.qp_delta = lev.qp_delta.copy()
        bad.qp_delta[held[0]] += 1              # a QP it cannot signal
        sps = SPS(width=w, height=h, fps_num=30, fps_den=1)
        for use_native in (False, True):
            with pytest.raises(ValueError):
                pack_slice(bad, w // 16, h // 16, sps, PPS(init_qp=qp), qp,
                           native=use_native)
        bad = encoder.FrameLevels(**vars(lev))
        bad.i4_modes = None                     # a kind without modes
        with pytest.raises(ValueError):
            pack_slice(bad, w // 16, h // 16, sps, PPS(init_qp=qp), qp,
                       native=True)
        bad.i4_modes = lev.i4_modes.copy()
        bad.i4_modes[np.flatnonzero(lev.luma_mode == 4)[0], 3] = 9
        with pytest.raises(ValueError):
            pack_slice(bad, w // 16, h // 16, sps, PPS(init_qp=qp), qp,
                       native=True)


# ---------------------------------------------------------------------------
# encoder = in-repo decoder = libavcodec, filter and AQ on
# ---------------------------------------------------------------------------

N = GOP = 4
#: (width, height, point, qp or None for the point's, more RdConfig
#: fields): the serving point at its QP and at one where macroblocks
#: lose every level, the library point, and the two settings beside it
GOP_CASES = [
    (160, 96, "serving", None, {}),
    (128, 128, "serving", 42, {}),
    (128, 128, "library", None, {}),
    (160, 96, "serving", None, dict(subpel="quarter")),
    (160, 96, "serving", None, dict(p_intra=True)),
]


def _gop_id(case):
    w, h, point, qp, more = case
    return "-".join([f"{w}x{h}", point, str(qp or "qp")]
                    + [str(v) for v in more.values()])


class TestBothDecoders:
    @pytest.mark.parametrize("case", GOP_CASES, ids=_gop_id)
    def test_served_bytes_numpy_encoder_and_both_decoders(
            self, monkeypatch, case):
        """One GOP of the screen clip through GopShardEncoder (compact
        payload and ranged unpack where the budgets hold): its bytes
        are the numpy-packed encoder's, and both decoders rebuild the
        encoder's reconstruction, in-loop filter and AQ included, with
        Intra4x4 macroblocks in the IDR."""
        if not native.available():
            pytest.skip("native packer not buildable here")
        w, h, point, qp, more = case
        qp = qp or POINTS[point][0]
        rd = _rd(point, **more)
        frames, meta = screen.make_frames(N, w, h, seed=3), _meta(w, h, N)
        enc = GopShardEncoder(meta, qp=qp, gop_frames=GOP, rd=rd,
                              mesh=_one_chip())
        (seg,) = enc.encode(frames)
        snap = enc.stages.snapshot()
        nmb = (w // 16) * (h // 16)
        assert snap["i_mbs_coded"] == nmb
        assert 0 < snap["i_mbs_4x4"] <= nmb
        if snap["dense_fallback_waves"] == 0:
            assert snap["unpack_ranges"] == 2 + (N - 1) * (
                6 if rd.p_intra else 5)
        stream, recon = _numpy_encoder(monkeypatch, frames, meta, qp, rd,
                                       return_recon=True)
        assert seg.payload == stream
        own = _decoders_agree(stream, recon, N, h, w)
        assert own.i4_mbs[0].sum() == snap["i_mbs_4x4"]
        assert not any(m.any() for m in own.i4_mbs[1:])
        assert min(psnr(f.y, o.y) for f, o in zip(frames, own.frames)) \
            > (24 if qp > 40 else 30)

    def test_no_level_between_two_qps_through_the_filter(self, monkeypatch):
        """The IDR of GOP_CASES[1] holds Intra4x4 macroblocks with no
        level whose AQ offset is not their predecessor's: the stream
        codes no delta there, the decoder's QP map (what §8.7 averages
        over an edge) holds the predecessor's QP, and libavcodec's
        filtered picture is the encoder's (the case above); with the
        AQ map's own QP there the filter's output differs."""
        from thinvids_tpu.codecs.h264 import decoder as dec_mod
        from thinvids_tpu.codecs.h264.deblock import deblock_frame

        w, h, _point, qp, _ = GOP_CASES[1]
        rd = _rd("serving")
        f = screen.make_frames(1, w, h, seed=3)[0]
        lev, (ry, ru, rv) = encode_frame_arrays(f.y, f.u, f.v, qp, rd=rd)
        held = (lev.luma_mode == intra.LUMA_I4X4) & _no_level(lev)
        aq = rdo.aq_offsets_np(f.y, rd.aq_q, w // 16, h // 16)
        assert (held & (aq != lev.qp_delta)).any()
        seen = {}
        real = dec_mod._Picture.deblock_edges

        def spy(pic):
            seen["qp_mb"] = pic.qp_mb.copy()
            return real(pic)

        monkeypatch.setattr(dec_mod._Picture, "deblock_edges", spy)
        stream = encode_gop([f], _meta(w, h, 1), qp=qp, rd=rd)
        decode_annexb(stream)
        assert np.array_equal(seen["qp_mb"].reshape(-1), qp + lev.qp_delta)
        right = deblock_frame(ry, ru, rv, (qp + lev.qp_delta).reshape(
            h // 16, w // 16), intra=True)
        wrong = deblock_frame(ry, ru, rv, (qp + aq).reshape(
            h // 16, w // 16), intra=True)
        assert not np.array_equal(right[0], wrong[0])

    def test_the_dense_fallback_carries_the_modes(self, monkeypatch):
        """A wave that leaves the sparse budgets ships its whole level
        vector: the kind and the mode words ride at its end
        (layout.unflatten_gop) and the bytes are the sparse path's."""
        if not native.available():
            pytest.skip("native packer not buildable here")
        w, h = 160, 96
        qp, rd = POINTS["serving"][0], _rd("serving")
        frames, meta = screen.make_frames(N, w, h, seed=3), _meta(w, h, N)
        sparse = GopShardEncoder(meta, qp=qp, gop_frames=GOP, rd=rd,
                                 mesh=_one_chip())
        (want,) = sparse.encode(frames)
        assert sparse.stages.snapshot()["dense_fallback_waves"] == 0
        monkeypatch.setattr(jaxcore, "block_sparse2_fits",
                            lambda *a, **k: False)
        dense = GopShardEncoder(meta, qp=qp, gop_frames=GOP, rd=rd,
                                mesh=_one_chip())
        (got,) = dense.encode(frames)
        snap = dense.stages.snapshot()
        assert snap["dense_fallback_waves"] == 1
        assert snap["unpack_ranges"] == 0 and snap["i_mbs_4x4"] > 0
        assert got.payload == want.payload

    def test_the_numpy_unpack_carries_them_too(self, monkeypatch):
        """Without the native library the compact payload is unpacked
        whole by numpy and the Python packers write the same bytes."""
        w, h = 96, 64
        qp, rd = POINTS["serving"][0], _rd("serving")
        frames, meta = screen.make_frames(2, w, h, seed=4), _meta(w, h, 2)
        want = encode_gop(frames, meta, qp=qp, rd=rd)
        monkeypatch.setattr(native, "available", lambda: False)
        enc = GopShardEncoder(meta, qp=qp, gop_frames=2, rd=rd,
                              mesh=_one_chip())
        (seg,) = enc.encode(frames)
        assert seg.payload == want
        assert enc.stages.snapshot()["i_mbs_4x4"] > 0

    def test_through_the_bounded_loop(self):
        """A plan made on scene cuts (GOPs of 3 and 1 frames staged to
        4, the P-frame loop stopped at each GOP's length) writes what
        the one-GOP program writes for each GOP."""
        w, h = 160, 96
        qp, rd = POINTS["serving"][0], _rd("serving")
        frames, meta = screen.make_frames(N, w, h, seed=3), _meta(w, h, N)
        enc = GopShardEncoder(meta, qp=qp, gop_frames=GOP, rd=rd,
                              mesh=_one_chip())
        enc.plan_override = plan_segments(N, GOP, 1, cuts=(3,))
        assert enc.plan_override.pin_frames
        segs = enc.encode(frames)
        assert [s.gop.num_frames for s in segs] == [3, 1]
        assert enc.stages.snapshot()["i_mbs_coded"] == 2 * 60
        recon = [[], [], []]
        for seg in segs:
            a, b = seg.gop.start_frame, seg.gop.end_frame
            stream, planes = encode_gop(frames[a:b], meta, qp=qp,
                                        idr_pic_id=seg.gop.index,
                                        return_recon=True, rd=rd)
            assert seg.payload == stream
            for acc, p in zip(recon, planes):
                acc.extend(np.asarray(p))
        own = _decoders_agree(concat_segments(segs), recon, N, h, w)
        assert own.i4_mbs[0].any() and own.i4_mbs[3].any()

    def test_a_wave_over_two_devices_is_the_one_device_wave(self):
        """Under shard_map (`_encode_wave_gop`: a GOP a device) the
        wavefront's carries vary over the mesh like its inputs, and the
        bytes are the single-device program's."""
        w, h = 160, 96
        qp, rd = POINTS["serving"][0], _rd("serving")
        frames = screen.make_frames(2 * GOP, w, h, seed=3)
        meta = _meta(w, h, 2 * GOP)
        got = [GopShardEncoder(meta, qp=qp, gop_frames=GOP, rd=rd,
                               mesh=default_mesh(jax.devices()[:k])
                               ).encode(frames) for k in (2, 1)]
        assert [s.payload for s in got[0]] == [s.payload for s in got[1]]
        assert len(got[0]) == 2

    def test_what_it_buys_on_the_screen_clip(self):
        """On text the setting saves bits at no lower PSNR; off, it is
        the parent's encoder (the jaxpr test below)."""
        w, h = 160, 96
        frames, meta = screen.make_frames(2, w, h, seed=3), _meta(w, h, 2)
        qp, fields = POINTS["serving"]
        got = {}
        for on in (False, True):
            stream, recon = encode_gop(
                frames, meta, qp=qp, return_recon=True,
                rd=RdConfig(**fields, intra4x4=on))
            got[on] = (len(stream), psnr(
                frames[0].y, np.asarray(recon[0][0])[:h, :w]
                .astype(np.uint8)))
        assert got[True][0] < 0.95 * got[False][0]
        assert got[True][1] > got[False][1] - 0.1


# ---------------------------------------------------------------------------
# intra4x4 off: the parent's programs
# ---------------------------------------------------------------------------

class TestOffIsTheParentsProgram:
    @pytest.mark.parametrize("point", sorted(POINTS))
    def test_jaxpr_of_the_gop_program(self, point):
        """With the setting off the GOP programs (scan and bounded
        form) are equation for equation the ones tests/test_p_intra.py
        records (PARENT_JAXPR: the parent commit's); on, they are
        others."""
        H, W, G, F = 64, 96, 2, 4
        c = (G, F, H // 2, W // 2)
        args = [jax.ShapeDtypeStruct((G, F, H, W), jnp.uint8),
                jax.ShapeDtypeStruct(c, jnp.uint8),
                jax.ShapeDtypeStruct(c, jnp.uint8),
                jax.ShapeDtypeStruct((G,), jnp.int32)]

        def sha(rd, *more):
            fn = functools.partial(dispatch._encode_gop_single,
                                   mbw=W // 16, mbh=H // 16, rd=rd)
            return hashlib.sha256(str(jax.make_jaxpr(fn)(
                *args, *more)).encode()).hexdigest()[:16]

        off = RdConfig(**POINTS[point][1], intra4x4=False)
        got = (sha(off), sha(off, args[3]))
        assert got == PARENT_JAXPR[point]
        on = RdConfig(**POINTS[point][1], intra4x4=True)
        assert sha(on) != got[0] and sha(on, args[3]) != got[1]

    def test_off_is_the_default_and_the_same_static_argument(self):
        assert RD_OFF.intra4x4 is False
        assert RdConfig(intra4x4=False) == RD_OFF
        assert hash(RdConfig(intra4x4=False)) == hash(RD_OFF)
        assert RdConfig(intra4x4=True) != RD_OFF
        assert "intra4x4=True" in repr(RdConfig(intra4x4=True))
        # the setting ships the side channel whatever mode_decision says
        assert RdConfig(intra4x4=True).ships_modes
        assert RdConfig(intra4x4=True).intra_tail_mb == 6
        assert RdConfig(mode_decision=True).intra_tail_mb == 2
        assert RD_OFF.intra_tail_mb == 0


# ---------------------------------------------------------------------------
# the setting
# ---------------------------------------------------------------------------

class TestTheSetting:
    def teardown_method(self):
        reset_live_settings()

    def test_default_clamp_env_and_job_key(self, monkeypatch):
        assert DEFAULT_SETTINGS["intra4x4"] is False
        assert rd_from_settings(Settings(values=DEFAULT_SETTINGS)
                                ).intra4x4 is False
        base = Settings(values=dict(DEFAULT_SETTINGS))
        for raw, want in (("1", True), ("true", True), (0, False),
                          ("off", False), ("nonsense", False)):
            assert overlay_job_settings(
                base, {"intra4x4": raw}).intra4x4 is want
        assert rd_from_settings(overlay_job_settings(
            base, {"intra4x4": 1})).intra4x4 is True
        monkeypatch.setenv("TVT_INTRA4X4", "1")
        assert config.get_settings(refresh=True).intra4x4 is True
        monkeypatch.delenv("TVT_INTRA4X4")
        assert config.get_settings(refresh=True).intra4x4 is False
        update_live_settings({"intra4x4": "yes"})
        assert config.get_settings().intra4x4 is True

    def test_the_constants(self):
        assert rdo.I4X4_MODE_BITS == (1, 4)
        assert 0 <= rdo.I4X4_BITS <= 64
        assert "intra4x4" in jaxinter.stage.__globals__["STAGES"]
        weights = jaxcore._I4_WEIGHTS.reshape(9, 16, 14)
        assert (weights.sum(-1) == 4).all()
        assert (weights[intra.I4_DC, :, 13] == 4).all()
        assert not np.delete(weights, intra.I4_DC, 0)[..., 13].any()

    def test_the_counters_are_in_the_snapshot_and_the_registry(self):
        from thinvids_tpu.obs import metrics as obs_metrics

        snap = dispatch.stage_snapshot()
        assert {"i_mbs_coded", "i_mbs_4x4"} <= set(snap)
        assert {"i_mbs_coded", "i_mbs_4x4"} <= set(
            obs_metrics.STAGE_COUNTER_TOTALS)

    def test_a_band_encoder_refuses_it(self):
        with pytest.raises(ValueError, match="intra4x4 is not supported"):
            SfeShardEncoder(_meta(160, 96, 4), qp=27, gop_frames=4, bands=2,
                            mesh=default_mesh(jax.devices()[:2]),
                            rd=RdConfig(intra4x4=True))

    def test_the_transfer_layout_grows_by_four_words_a_macroblock(self):
        assert layout.intra_tail_mb(False) == 0
        assert layout.intra_tail_mb(True) == 2
        assert layout.intra_tail_mb(True, True) == 6
        nmb, F = 6, 2
        n = nmb * 384 + (F - 1) * nmb * 392 + nmb * 6
        flat = np.arange(n, dtype=np.int16)
        intra_t, planes = layout.unflatten_gop(
            flat, np.zeros((1, nmb, 2)), F, 3, 2, ships_modes=True,
            intra4x4=True)
        assert len(intra_t) == 7 and len(planes) == 6
        assert intra_t[4][0] == n - 6 * nmb and intra_t[5][0] == n - 5 * nmb
        assert intra_t[6].shape == (nmb, 4) and intra_t[6][0, 0] == n - 4 * nmb
        dense = np.arange(nmb * (24 + 6), dtype=np.int16)
        il_dc, ic_dc, tail = layout.split_dense_dc(dense, nmb, True)
        assert len(tail) == 3 and tail[2].shape == (nmb, 4)
        assert len(layout.split_dense_dc(dense[:nmb * 26], nmb, True)[2]) == 2
        assert jaxcore.intra_flat_len(nmb, RdConfig(intra4x4=True)) \
            == nmb * 390


# ---------------------------------------------------------------------------
# through the coordinator
# ---------------------------------------------------------------------------

JW, JH, JN, JGOP = 160, 96, 8, 4
JMETA = VideoMeta(width=JW, height=JH, fps_num=30, fps_den=1, num_frames=JN)


def _settings(**over):
    return Settings(values=dict(DEFAULT_SETTINGS, heartbeat_throttle_s=0.0,
                                gop_frames=JGOP, qp=27, **over))


def _run(tmp_path, name, path, job_settings=None, mesh=None, meta=JMETA,
         **settings):
    """One job through a coordinator whose settings are `settings`, the
    daemon's LIVE settings (where an encoder reads its RdConfig) having
    the same `intra4x4`."""
    snap = _settings(**settings)
    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(coord, output_dir=str(tmp_path / name), sync=True,
                          mesh=mesh or _one_chip())
    coord._launcher = execu.launch
    before = dispatch.stage_snapshot()
    update_live_settings({"intra4x4": settings.get("intra4x4", False)})
    try:
        job = coord.add_job(path, meta, settings=job_settings)
    finally:
        reset_live_settings()
    after = dispatch.stage_snapshot()
    return coord.store.get(job.id), {k: after[k] - before.get(k, 0)
                                     for k in after}


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("screen")
    frames = screen.make_frames(JN, JW, JH, seed=3)
    path = tmp / "clip.y4m"
    write_y4m(path, JMETA, frames)
    return tmp, frames, str(path)


def _decoded(job):
    media = read_mp4(job.output_path)
    return decode_annexb(media.annexb_for(0, media.num_frames))


class TestThroughTheCoordinator:
    def test_intra4x4_alone_and_off(self, source):
        tmp, frames, path = source
        job, grew = _run(tmp, "on", path, intra4x4=True)
        assert job.status is Status.DONE, job.failure_reason
        own = _decoded(job)
        assert len(own.frames) == JN
        idrs = [i for i in range(JN) if own.mvs[i] is None]
        assert idrs == [0, JGOP]
        assert all(own.i4_mbs[i].mean() > 0.2 for i in idrs)
        assert grew["i_mbs_coded"] == 2 * 60
        assert 0 < grew["i_mbs_4x4"] <= grew["i_mbs_coded"]
        assert min(psnr(f.y, o.y) for f, o in zip(frames, own.frames)) > 30
        size_on = os.path.getsize(job.output_path)
        job, grew = _run(tmp, "off", path)
        assert job.status is Status.DONE
        assert grew["i_mbs_coded"] == grew["i_mbs_4x4"] == 0
        assert not any(m.any() for m in _decoded(job).i4_mbs)
        assert size_on < os.path.getsize(job.output_path)

    def test_with_scenecut(self, source):
        """The bounded GOP program under intra4x4: the plan is made on
        cuts (none in this clip: the balanced GOPs, pinned)."""
        tmp, _frames, path = source
        job, grew = _run(tmp, "cuts", path, intra4x4=True, scenecut=40)
        assert job.status is Status.DONE, job.failure_reason
        assert grew["i_mbs_4x4"] > 0
        assert _decoded(job).i4_mbs[0].any()

    def test_a_per_job_value_the_daemon_cannot_apply_is_refused(
            self, source):
        tmp, _frames, path = source
        job, grew = _run(tmp, "refused", path,
                         job_settings={"intra4x4": True})
        assert job.status is Status.REJECTED
        assert "intra4x4" in job.reject_reason \
            and "daemon-wide" in job.reject_reason
        assert grew["waves"] == 0
        job, _grew = _run(tmp, "refused-off", path, intra4x4=True,
                          job_settings={"intra4x4": False})
        assert job.status is Status.REJECTED
        # the daemon's own value, asked again per job, is no override
        job, grew = _run(tmp, "same", path, intra4x4=True,
                         job_settings={"intra4x4": "1"})
        assert job.status is Status.DONE and grew["i_mbs_4x4"] > 0

    def test_a_band_shape_job_is_refused_at_admission(self, source):
        """Split-frame band steps code their IDR bands Intra16x16
        alone: the job is refused with the reason, never encoded
        without the tool."""
        tmp, _frames, path = source
        for where in ("job", "daemon"):
            job_settings = {"sfe_bands": 2} if where == "job" else None
            extra = {} if where == "job" else {"sfe_bands": 2}
            job, grew = _run(tmp, f"bands-{where}", path, intra4x4=True,
                             job_settings=job_settings, **extra)
            assert job.status is Status.REJECTED
            assert "sfe_bands" in job.reject_reason \
                and "intra4x4" in job.reject_reason
            assert grew["waves"] == 0 and grew["sfe_frames"] == 0
        decision = evaluate_job_policy(JMETA, _settings(sfe_bands=2))
        assert decision.accepted            # bands alone: as ever

    def test_as_a_ladder(self, source):
        """Ladder rungs are GOP-shape encoders: every rung's IDRs hold
        Intra4x4 macroblocks."""
        tmp, _frames, path = source
        job, grew = _run(tmp, "ladder", path, intra4x4=True,
                         job_settings={"ladder_rungs": "96,48"},
                         job_type="ladder")
        assert job.status is Status.DONE, job.failure_reason
        assert grew["i_mbs_4x4"] > 0
        assert grew["i_mbs_coded"] > 2 * 60         # both rungs counted

    def test_on_the_remote_backend(self, source):
        """The plan's signature and every shard's descriptor carry the
        setting: workers whose own daemon runs without it encode the
        job with it."""
        from thinvids_tpu.cluster import remote
        from thinvids_tpu.cluster.jobs import Job
        from thinvids_tpu.ingest.decode import read_video

        tmp, _frames, path = source
        sig = remote.RemoteExecutor._plan_signature
        probe = Job(id="j" * 12, input_path=path)
        assert sig(probe, _settings()) == sig(probe,
                                              _settings(intra4x4=False))
        assert sig(probe, _settings()) != sig(probe,
                                              _settings(intra4x4=True))
        assert sig(probe, _settings(p_intra=True)) \
            != sig(probe, _settings(intra4x4=True))
        snap = _settings(intra4x4=True, remote_plan_devices=1,
                         remote_shard_gops=1, remote_no_worker_grace_s=10.0)
        reg = WorkerRegistry()
        for i in range(8):
            reg.heartbeat(f"w{i:02d}", metrics={"worker": True})
        coord = Coordinator(registry=reg, settings_fn=lambda: snap)
        execu = remote.RemoteExecutor(
            coord, output_dir=str(tmp / "farm"), sync=True, poll_s=0.02)
        coord._launcher = execu.launch
        stop, descs = threading.Event(), []
        clip = read_video(path)[1]
        mesh = _one_chip()

        def worker(host):
            while not stop.is_set():
                desc = execu.board.claim(host)
                if desc is None:
                    time.sleep(0.01)
                    continue
                descs.append(desc)
                execu.board.submit_part(
                    desc["id"], host,
                    remote.encode_shard(desc, clip, mesh=mesh))

        for i in range(2):
            threading.Thread(target=worker, args=(f"w{i:02d}",),
                             daemon=True).start()
        try:
            job = coord.store.get(coord.add_job(path, JMETA).id)
        finally:
            stop.set()
        assert job.status is Status.DONE, job.failure_reason
        assert len(descs) == JN // JGOP
        assert all(d["shape"] == "gop/half/intra4x4" for d in descs)
        assert _decoded(job).i4_mbs[0].any()

    def test_a_worker_from_before_the_setting_refuses_the_shard(self):
        """The setting rides in the shard's SHAPE tag, after the vector
        precision and `p_intra`: PR 45's worker (remote._shard_rd as it
        was, spelt out below) finds a tag part it does not know and
        answers `unsupported`; older ones know fewer parts still. No
        shard of an intra4x4 plan is encoded Intra16x16 alone under
        the plan's signature. A shard without the setting has the wire
        form it had."""
        from thinvids_tpu.cluster import remote
        from thinvids_tpu.core.config import SUBPELS

        def shard(**more):
            return remote.Shard(
                id="j-0", key="0", job_id="j", input_path="x", meta=JMETA,
                gops=plan_segments(JN, JGOP, 1).gops[:1], qp=27,
                gop_frames=JGOP, timeout_s=1.0, **more)

        def pr45_worker_takes(desc):
            shape, *rest = str(desc.get("shape", "gop") or "gop").split("/")
            return shape in ("gop", "band") \
                and (rest[0] if rest else "half") in SUBPELS \
                and not set(rest[1:]) - {"p_intra"}

        assert "shape" not in shard().descriptor()
        assert shard(p_intra=True).descriptor()["shape"] == "gop/half/p_intra"
        assert pr45_worker_takes(shard(p_intra=True).descriptor())
        for subpel, p_intra in itertools.product(SUBPELS, (False, True)):
            desc = shard(subpel=subpel, p_intra=p_intra,
                         intra4x4=True).descriptor()
            assert desc["shape"] == f"gop/{subpel}" \
                + "/p_intra" * p_intra + "/intra4x4"
            assert "intra4x4" not in desc
            assert not pr45_worker_takes(desc)
            assert remote.wire_shape(desc) == ("gop", subpel)
            rd = remote._shard_rd(desc)
            assert rd.intra4x4 is True and rd.p_intra is p_intra \
                and rd.subpel == subpel
        assert remote._shard_rd(shard().descriptor()).intra4x4 is False


# ---------------------------------------------------------------------------
# the content
# ---------------------------------------------------------------------------

def _harness_screen():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "harness_screen",
        os.path.join(root, "benchmark", "generators", "screen.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("n,w,h,seed,params", [
    (5, 128, 128, 7, {}),
    (3, 322, 182, 2**31 + 5, {"pane_pan": 1, "windows": 3}),
    (2, 640, 368, 0, {"type_every": 1, "scroll_px": 2}),
])
def test_screen_harness_copy_is_the_same_generator(n, w, h, seed, params):
    ours = screen.make_frames(n, w, h, seed=seed, **params)
    theirs = list(_harness_screen().planes(n, w, h, seed, **params))
    assert len(ours) == len(theirs) == n
    for frame, planes in zip(ours, theirs):
        for mine, other in zip((frame.y, frame.u, frame.v), planes):
            assert mine.dtype == other.dtype == np.uint8
            assert mine.tobytes() == other.tobytes()
    assert ours[0].y.shape == (h, w) and ours[0].u.shape == (h // 2, w // 2)


def test_screen_is_a_prefix_the_seed_draws_glyphs_and_things_happen():
    long = screen.make_frames(70, 640, 368, seed=2**31 + 9)
    short = screen.make_frames(3, 640, 368, seed=2**31 + 9)
    for a, b in zip(short, long):
        assert np.array_equal(a.y, b.y) and np.array_equal(a.u, b.u)
    other = screen.make_frames(1, 640, 368, seed=1)[0]
    # the seed draws which glyphs: the same samples are text (differ
    # from paper) under either seed, give or take the glyphs' own ink
    assert not np.array_equal(other.y, long[0].y)
    assert np.array_equal(other.u, long[0].u)
    differ = other.y != long[0].y
    assert 0.005 < differ.mean() < 0.2
    # no grain: the desktop is the same ramp, sample for sample
    assert (other.y[:8] == long[0].y[:8]).all()
    # windows are never on the macroblock grid, and overlap
    wins = [screen.window(k, 1920, 1080) for k in range(5)]
    assert all(x % 16 and y % 16 for x, y, *_ in wins)
    assert all(480 <= w <= 1100 and 300 <= h <= 700 for _, _, w, h, _ in wins)
    # things move: typing and the pane every frame, the scroll in its
    # frames alone
    changed = [float((a.y != b.y).mean()) for a, b in zip(long, long[1:])]
    assert all(c > 0 for c in changed)
    assert screen.scrolled(39, 4) == 0 and screen.scrolled(40, 4) == 4
    assert screen.scrolled(57, 4) == screen.scrolled(63, 4) == 72
    assert screen.scrolled(64 + 40, 4) == 76
    assert np.mean(changed[39:57]) > 1.5 * np.mean(changed[:39])
