"""Intra macroblocks in P pictures (ISSUE 45, the `p_intra` setting).

With `p_intra` every macroblock of a P picture is coded inter, as
without it, or Intra16x16 (H.264 §7.3.5 / Table 7-13: mb_type 5..30 in
a P slice; §8.3.3), whichever costs less. This file holds the setting
end to end, at small sizes on the CPU mirror:

- the device path (GopShardEncoder: plane layout, sparse wire, compact
  payload or dense fallback, the native plane packer) writes the bytes
  of the numpy encoder (the pure-Python packers on the blocked arrays)
  on `tools/crossing` content, at two sizes, both `subpel` values, the
  RD tools on and off, through the scan and the bounded P-frame loop;
- the in-repo decoder = libavcodec = the encoder's reconstruction,
  sample for sample, with the in-loop filter on (mixed bS edges);
- `predict_mvs` and the P_Skip inference next to intra neighbours
  against hand-worked §8.4.1.3 / §8.4.1.1 cases, in the packer and in
  the decoder; the packers' new branch against each other on random
  kinds, modes and levels (tools/fuzz_native.py);
- the Intra16x16 residual on tiles against jaxcore's blocked arithmetic
  at the extremes, every QP;
- with `p_intra` off the GOP programs are the parent commit's, jaxpr
  text for jaxpr text;
- the setting's plumbing; through the coordinator: a job under
  `p_intra`, with `scenecut`, as a ladder and on the remote backend;
  what is refused at admission (a per-job value the daemon cannot
  apply, a band-shape job), and a worker from before the setting
  refusing the shard;
- `tools/crossing` = `benchmark/generators/crossing`, byte for byte.
"""

import functools
import hashlib
import importlib.util
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
from thinvids_tpu.codecs.h264 import decoder, inter, jaxcore, jaxinter, rdo
from thinvids_tpu.codecs.h264.decoder import decode_annexb
from thinvids_tpu.codecs.h264.encoder import encode_gop
from thinvids_tpu.codecs.h264.headers import SPS
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
from thinvids_tpu.tools import crossing, fuzz_native, oracle
from thinvids_tpu.tools.metrics import psnr

N = GOP = 8
SERVING = dict(mode_decision=True, pskip=True, deblock=True, aq_q=4)
#: (qp, RdConfig fields) of the two operating points
POINTS = {"library": (27, {}), "serving": (25, SERVING)}
#: (width, height, subpel, point): each size, each precision and each
#: point with each other value once
CASES = [(160, 96, "half", "library"), (160, 96, "quarter", "serving"),
         (128, 128, "half", "serving"), (128, 128, "quarter", "library")]


def _clip(w, h, n=N, sprites=36, seed=3):
    """`tools/crossing` at a test's size: every 6th frame of the clip
    with a 1-pixel pan, so that the small sprites of a small picture
    still move 6 pixels a frame, beyond the search's windows."""
    return crossing.make_frames(6 * n, w, h, seed=seed, pan=1,
                                sprites=sprites)[::6]


def _meta(w, h, n=N):
    return VideoMeta(width=w, height=h, fps_num=30, fps_den=1, num_frames=n)


def _rd(point, subpel="half", **over):
    return RdConfig(**{**POINTS[point][1], **over}, subpel=subpel,
                    p_intra=True)


def _one_chip():
    return default_mesh(jax.devices()[:1])


def _same_planes(frame, planes, h, w):
    y, u, v = (np.asarray(p) for p in planes)
    return (np.array_equal(frame.y[:h, :w], y[:h, :w])
            and np.array_equal(frame.u[:h // 2, :w // 2],
                               u[:h // 2, :w // 2])
            and np.array_equal(frame.v[:h // 2, :w // 2],
                               v[:h // 2, :w // 2]))


def _decoders_agree(stream, recon, n, h, w):
    """The in-repo decoder and libavcodec against the encoder's own
    reconstruction (`recon`: (ys, us, vs) stacked over frames), sample
    for sample; returns the in-repo decode."""
    own = decode_annexb(stream)
    assert len(own.frames) == n
    for i, frame in enumerate(own.frames):
        assert _same_planes(frame, [p[i] for p in recon], h, w), i
    if oracle.oracle_available():
        theirs = oracle.decode_h264(stream)
        assert len(theirs) == n
        for frame, planes in zip(own.frames, theirs):
            assert _same_planes(frame, planes, h, w)
    return own


def _intra_share(own):
    maps = [m for m in own.intra_mbs if m is not None]
    return float(np.mean([m.mean() for m in maps]))


def _numpy_encoder(monkeypatch, frames, meta, qp, rd, **kw):
    """`encode_gop` with both slice packers in pure Python."""
    with monkeypatch.context() as patch:
        patch.setattr(native, "available", lambda: False)
        return encode_gop(frames, meta, qp=qp, rd=rd, **kw)


# ---------------------------------------------------------------------------
# device path = numpy encoder; encoder = in-repo decoder = libavcodec
# ---------------------------------------------------------------------------

class TestTheMixedPicture:
    @pytest.mark.parametrize("w,h,subpel,point", CASES)
    def test_device_path_numpy_encoder_and_both_decoders(
            self, monkeypatch, w, h, subpel, point):
        """One GOP of the crossing clip: the served path's bytes (the
        dense fallback at this size and QP) are the numpy encoder's,
        and what they decode to — in this repo's decoder and in
        libavcodec — is the encoder's reconstruction, filter and all,
        with a fair share of the P macroblocks intra."""
        if not native.available():
            pytest.skip("native packer not buildable here")
        qp, rd = POINTS[point][0], _rd(point, subpel)
        frames, meta = _clip(w, h), _meta(w, h)
        enc = GopShardEncoder(meta, qp=qp, gop_frames=GOP, rd=rd,
                              mesh=_one_chip())
        (seg,) = enc.encode(frames)
        snap = enc.stages.snapshot()
        assert snap["dense_fallback_waves"] == 1
        assert snap["p_mbs_coded"] == (N - 1) * (w // 16) * (h // 16)
        stream, recon = _numpy_encoder(monkeypatch, frames, meta, qp, rd,
                                       return_recon=True)
        assert seg.payload == stream
        own = _decoders_agree(stream, recon, N, h, w)
        assert own.intra_mbs[0] is None
        share = _intra_share(own)
        assert 0.1 < share < 0.7
        assert snap["p_mbs_intra"] == round(share * snap["p_mbs_coded"])
        # an intra macroblock has no vector
        for mv, kinds in zip(own.mvs[1:], own.intra_mbs[1:]):
            assert not mv[kinds].any()
        assert min(psnr(f.y, o.y) for f, o in zip(frames, own.frames)) > 30

    def test_the_sparse_wire_and_the_compact_payload(self, monkeypatch):
        """Fewer sprites at a coarser QP hold the sparse budgets: the
        kind channel and the Intra16x16 levels cross as the compact
        payload, and the bytes are the numpy encoder's."""
        if not native.available():
            pytest.skip("native packer not buildable here")
        w, h, qp, rd = 160, 96, 38, _rd("library")
        frames, meta = _clip(w, h, sprites=12), _meta(w, h)
        enc = GopShardEncoder(meta, qp=qp, gop_frames=GOP, rd=rd,
                              mesh=_one_chip())
        (seg,) = enc.encode(frames)
        snap = enc.stages.snapshot()
        assert snap["dense_fallback_waves"] == 0
        assert snap["p_mbs_intra"] > 0.05 * snap["p_mbs_coded"]
        stream, recon = _numpy_encoder(monkeypatch, frames, meta, qp, rd,
                                       return_recon=True)
        assert seg.payload == stream
        _decoders_agree(stream, recon, N, h, w)

    def test_through_the_bounded_loop(self):
        """A plan made on scene cuts (GOPs of 5 and 3 frames staged to
        8, the P-frame loop stopped at each GOP's length) writes what
        the one-GOP program writes for each GOP."""
        w, h = 160, 96
        qp, rd = POINTS["serving"][0], _rd("serving", "quarter")
        frames, meta = _clip(w, h), _meta(w, h)
        enc = GopShardEncoder(meta, qp=qp, gop_frames=GOP, rd=rd,
                              mesh=_one_chip())
        enc.plan_override = plan_segments(N, GOP, 1, cuts=(5,))
        assert enc.plan_override.pin_frames
        segs = enc.encode(frames)
        assert [s.gop.num_frames for s in segs] == [5, 3]
        assert enc.stages.snapshot()["p_mbs_coded"] == 6 * 60
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
        assert _intra_share(own) > 0.1

    def test_what_it_buys_on_the_crossing_clip(self):
        """On content whose objects move beyond the search's reach the
        setting saves bits at no lower PSNR; off, it is the parent's
        encoder (the jaxpr test below) and its bytes differ."""
        w, h = 160, 96
        frames, meta = _clip(w, h), _meta(w, h)
        qp, fields = POINTS["serving"]
        got = {}
        for on in (False, True):
            stream, recon = encode_gop(
                frames, meta, qp=qp, return_recon=True,
                rd=RdConfig(**fields, p_intra=on))
            got[on] = (len(stream), np.mean(
                [psnr(f.y, np.asarray(r)[:h, :w].astype(np.uint8))
                 for f, r in zip(frames[1:], recon[0][1:])]))
        assert got[True][0] < 0.97 * got[False][0]
        assert got[True][1] > got[False][1] - 0.1


# ---------------------------------------------------------------------------
# §8.4.1.3 / §8.4.1.1 next to intra neighbours
# ---------------------------------------------------------------------------

#: a 3 x 2 picture; the macroblock under test is (my, mx) = (1, 1), its
#: neighbours A = (1, 0), B = (0, 1), C = (0, 2), D = (0, 0).
#: (vectors of A, B, C, D; which of them are intra; mvp; skip vector)
HAND_WORKED = {
    # one neighbour intra: refIdx -1, vector 0 in the median
    "a_intra": (((9, 9), (4, 2), (8, 6), (1, 1)), "A", (4, 2), (4, 2)),
    # two intra: the ONE neighbour that refers to picture 0 is the
    # prediction, not the median of it and two zeros
    "a_b_intra": (((9, 9), (7, 7), (8, 6), (1, 1)), "AB", (8, 6), (8, 6)),
    # all three intra: the median of three zeros; both neighbours are
    # available and neither is "refIdx 0 with a zero vector", so the
    # skip vector is the prediction (0 here, by the median)
    "all_intra": (((9, 9), (7, 7), (5, 5), (1, 1)), "ABC", (0, 0), (0, 0)),
    # an intra C is AVAILABLE: no fallback to D
    "c_intra": (((2, 2), (4, 4), (9, 9), (6, 6)), "C", (2, 2), (2, 2)),
    # an inter A with a zero vector makes a P_Skip's vector zero ...
    "a_zero_inter": (((0, 0), (4, 2), (8, 6), (1, 1)), "", (4, 2), (0, 0)),
    # ... an intra A (whose vector reads zero) does not
    "a_zero_intra": (((0, 0), (4, 2), (8, 6), (1, 1)), "A", (4, 2), (4, 2)),
    "b_zero_intra": (((4, 2), (0, 0), (8, 6), (1, 1)), "B", (4, 2), (4, 2)),
}
_PLACE = {"A": (1, 0), "B": (0, 1), "C": (0, 2), "D": (0, 0)}


def _hand_worked(case):
    vectors, kinds, mvp, skip = HAND_WORKED[case]
    mv = np.zeros((2, 3, 2), np.int32)
    intra = np.zeros((2, 3), bool)
    for name, vec in zip("ABCD", vectors):
        mv[_PLACE[name]] = vec
    for name in kinds:
        intra[_PLACE[name]] = True
    return mv, intra, np.asarray(mvp), np.asarray(skip)


class TestVectorPredictionNextToIntra:
    @pytest.mark.parametrize("case", sorted(HAND_WORKED))
    def test_the_packers_prediction(self, case):
        mv, intra, mvp, skip = _hand_worked(case)
        got_mvp, got_skip = inter.predict_mvs(mv.reshape(-1, 2), 3, 2,
                                              intra=intra.reshape(-1))
        assert np.array_equal(got_mvp[4], mvp)
        assert np.array_equal(got_skip[4], skip)

    @pytest.mark.parametrize("case", sorted(HAND_WORKED))
    def test_the_decoders_prediction(self, case):
        mv, intra, mvp, skip = _hand_worked(case)
        pic = decoder._Picture(SPS(width=48, height=32))
        pic.mv[:], pic.intra_mb[:] = mv, intra
        got_mvp, got_skip = decoder._mvp_and_skip(pic, 1, 1, 0)
        assert np.array_equal(got_mvp, mvp)
        assert np.array_equal(got_skip, skip)

    def test_without_intra_neighbours_it_is_the_rule_it_was(self):
        rng = np.random.default_rng(3)
        mv = rng.integers(-6, 7, (20, 2)).astype(np.int32)
        mv[rng.random(20) < 0.3] = 0
        plain = inter.predict_mvs(mv, 5, 4)
        flagged = inter.predict_mvs(mv, 5, 4, intra=np.zeros(20, bool))
        assert all(np.array_equal(a, b) for a, b in zip(plain, flagged))
        # the first row and column: A alone, B and C alone, nothing
        assert np.array_equal(plain[0][1], mv[0])
        assert not plain[0][0].any() and not plain[1][:5].any()

    def test_python_and_native_packers_on_random_kinds(self):
        """tools/fuzz_native.py's P case: both native packers write the
        pure-Python packer's bytes on random vectors, levels, kinds and
        modes, or all reject the levels."""
        if not native.available():
            pytest.skip("native packer not buildable here")
        rng = np.random.default_rng(45)
        for _ in range(150):
            fuzz_native.fuzz_pack_p(native, rng)

    def test_a_skip_run_ends_at_an_intra_macroblock(self):
        """Seven macroblocks with no level and the inferred vector: one
        run of 7 all inter; with the fourth intra, a run of 3, the
        intra macroblock, a run of 3 — and the decoder reads it so."""
        from thinvids_tpu.codecs.h264.headers import PPS

        mbw, mbh = 7, 1
        sps = SPS(width=16 * mbw, height=16 * mbh, fps_num=30, fps_den=1)
        zeros = (np.zeros((7, 2), np.int32), np.zeros((7, 16, 16), np.int32),
                 np.zeros((7, 2, 4), np.int32),
                 np.zeros((7, 2, 4, 15), np.int32))
        pmode = np.zeros(7, np.int16)
        pmode[3] = rdo.pmode_word(2, 0)          # DC, chroma DC
        for use_native in (False, True):
            if use_native and not native.available():
                continue
            runs = inter.pack_p_slice(*zeros, mbw, mbh, sps, PPS(init_qp=27),
                                      27, 1, native=use_native)
            mixed = inter.pack_p_slice(*zeros, mbw, mbh, sps,
                                       PPS(init_qp=27), 27, 1,
                                       native=use_native, pmode=pmode)
            assert len(mixed) > len(runs)
        frames = [crossing.make_frames(1, 16 * mbw, 16, seed=1)[0]] * 2
        idr = encode_gop(frames[:1], _meta(16 * mbw, 16, 1), qp=27)
        own = decode_annexb(idr + mixed)
        assert own.intra_mbs[1].tolist() == [[False] * 3 + [True]
                                             + [False] * 3]
        # the skipped ones copy the IDR; the intra one is DC of its left
        assert np.array_equal(own.frames[1].y[:, :48], own.frames[0].y[:, :48])
        assert len(set(own.frames[1].y[:, 48:64].reshape(-1).tolist())) == 1


# ---------------------------------------------------------------------------
# the Intra16x16 residual on tiles = jaxcore's blocked arithmetic
# ---------------------------------------------------------------------------

class TestIntraResidualOnTiles:
    @pytest.mark.parametrize("qp", [0, 11, 25, 35, 36, 44, 51])
    def test_luma_and_chroma_at_the_extremes(self, qp):
        """Checkerboards of 0 / 255 against every prediction, noise and
        flat blocks: levels (the Hadamard-domain DC at the DC
        positions) and reconstruction equal `_luma_mb_batch` /
        `_chroma_mb_batch` macroblock for macroblock."""
        rng = np.random.default_rng(qp)
        mbw, mbh = 9, 2                     # 144 wide: two tiles, one cut
        H, W = 16 * mbh, 16 * mbw
        yy, xx = np.mgrid[0:H, 0:W]
        src = np.where((yy + xx) % 2 == 0, 255, 0)
        src[:, 48:96] = rng.integers(0, 256, (H, 48))
        src[:, 96:] = 255
        pred = np.where((yy // 4 + xx // 4) % 2 == 0, 0, 255)
        pred[16:, :48] = rng.integers(0, 256, (16, 48))

        def mbs(plane, mb):
            r, c = plane.shape[0] // mb, plane.shape[1] // mb
            return jnp.asarray(plane.reshape(r, mb, c, mb).transpose(
                0, 2, 1, 3).reshape(r * c, mb, mb), jnp.int32)

        def plane_of(blocks, mb, shape):
            r, c = shape[0] // mb, shape[1] // mb
            return np.asarray(blocks).reshape(r, c, mb, mb).transpose(
                0, 2, 1, 3).reshape(shape)

        to_t = lambda a: jaxinter._to_tiles(jnp.asarray(a, jnp.int32))
        z, rec = jaxinter._intra16_luma(to_t(src), to_t(pred),
                                        jnp.int32(qp))
        z = np.asarray(jaxinter._from_tiles(z, W))
        dc, ac, want = jaxcore._luma_mb_batch(mbs(src, 16), mbs(pred, 16),
                                              jnp.int32(qp))
        assert np.array_equal(np.asarray(jaxinter._from_tiles(rec, W)),
                              plane_of(want, 16, (H, W)))
        blocks = np.asarray(jaxinter._luma_plane_to_blocks(
            jnp.asarray(z), mbw, mbh))                  # (n, 16, 16)
        assert np.array_equal(blocks[:, :, 1:], np.asarray(ac))
        order = np.argsort([4 * by + bx for bx, by
                            in inter.LUMA_BLOCK_ORDER])
        from thinvids_tpu.codecs.h264.transform import ZIGZAG_4x4
        assert np.array_equal(blocks[:, order, 0][:, ZIGZAG_4x4],
                              np.asarray(dc))

        qpc = jaxcore._QPC[qp]
        csrc, cpred = src[:H // 2, :W // 2], pred[:H // 2, :W // 2]
        zc, crec = jaxinter._intra_chroma(to_t(csrc), to_t(cpred), qpc)
        cdc, cac, cwant = jaxcore._chroma_mb_batch(
            mbs(csrc, 8), mbs(cpred, 8), qpc)
        assert np.array_equal(np.asarray(jaxinter._from_tiles(crec, W // 2)),
                              plane_of(cwant, 8, (H // 2, W // 2)))
        assert np.array_equal(
            np.asarray(jaxinter._chroma_dc_levels(zc, mbw)).reshape(-1, 4),
            np.asarray(cdc))
        zc = np.asarray(jaxinter._from_tiles(zc, W // 2))
        assert np.array_equal(np.asarray(jaxinter._chroma_plane_to_blocks(
            jnp.asarray(zc), mbw, H // 16))[..., 1:], np.asarray(cac))


# ---------------------------------------------------------------------------
# p_intra off: the parent's programs
# ---------------------------------------------------------------------------

#: sha256[:16] of str(jax.make_jaxpr(dispatch._encode_gop_single)), 2
#: GOPs of 4 frames of 96x64, scan form and bounded form: with
#: `p_intra` off (the default) the setting adds no equation to any GOP
#: program — PR 45 recorded them at its parent commit (20e3ab8:
#: a90f8e4d8ccbbf61 / bf7bea49157f8076 library, fe577b42d339bd61 /
#: b89ccc80a38b1b9b serving). A PR that changes those programs on
#: purpose records its own (the loop below prints them); these are
#: PR 47's, whose pack appends chunks (jaxcore._append_blocks).
PARENT_JAXPR = {
    "library": ("ecf2adff318bd32a", "8e7c99422a697e43"),
    "serving": ("8133ebc0ff357abd", "fb0fba2f45ee10a0"),
}


class TestOffIsTheParentsProgram:
    @pytest.mark.parametrize("point", sorted(PARENT_JAXPR))
    def test_jaxpr_of_the_gop_program(self, point):
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

        off = RdConfig(**POINTS[point][1])
        got = (sha(off), sha(off, args[3]))
        print(point, got)
        assert got == PARENT_JAXPR[point]
        assert sha(RdConfig(**POINTS[point][1], p_intra=True)) != got[0]

    def test_off_is_the_default_and_the_same_static_argument(self):
        assert RD_OFF.p_intra is False
        assert RdConfig(p_intra=False) == RD_OFF
        assert hash(RdConfig(p_intra=False)) == hash(RD_OFF)
        assert RdConfig(p_intra=True) != RD_OFF
        assert "p_intra=True" in repr(RdConfig(p_intra=True))


# ---------------------------------------------------------------------------
# the setting
# ---------------------------------------------------------------------------

class TestTheSetting:
    def teardown_method(self):
        reset_live_settings()

    def test_default_clamp_env_and_job_key(self, monkeypatch):
        assert DEFAULT_SETTINGS["p_intra"] is False
        assert rd_from_settings(Settings(values=DEFAULT_SETTINGS)
                                ).p_intra is False
        base = Settings(values=dict(DEFAULT_SETTINGS))
        for raw, want in (("1", True), ("true", True), (0, False),
                          ("off", False), ("nonsense", False)):
            assert overlay_job_settings(
                base, {"p_intra": raw}).p_intra is want
        assert rd_from_settings(overlay_job_settings(
            base, {"p_intra": 1})).p_intra is True
        monkeypatch.setenv("TVT_P_INTRA", "1")
        assert config.get_settings(refresh=True).p_intra is True
        monkeypatch.delenv("TVT_P_INTRA")
        assert config.get_settings(refresh=True).p_intra is False
        update_live_settings({"p_intra": "yes"})
        assert config.get_settings().p_intra is True

    def test_the_constants(self):
        assert rdo.P_INTRA_PASSES >= 2 and rdo.P_INTRA_BITS > 0
        assert len(rdo.P_INTRA_LAMBDA) == 52
        assert rdo.P_INTRA_LAMBDA[25] == 4 and rdo.P_INTRA_LAMBDA[0] == 1
        word = rdo.pmode_word(np.array([0, 1, 2]), np.array([2, 1, 0]))
        flag, luma, chroma = rdo.pmode_fields(np.append(word, 0))
        assert flag.tolist() == [True, True, True, False]
        assert luma[:3].tolist() == [0, 1, 2]
        assert chroma[:3].tolist() == [2, 1, 0]
        assert "p_intra" in jaxinter.stage.__globals__["STAGES"]

    def test_the_counters_are_in_the_snapshot_and_the_registry(self):
        from thinvids_tpu.obs import metrics as obs_metrics

        snap = dispatch.stage_snapshot()
        assert {"p_mbs_coded", "p_mbs_intra"} <= set(snap)
        assert {"p_mbs_coded", "p_mbs_intra"} <= set(
            obs_metrics.STAGE_COUNTER_TOTALS)

    def test_a_band_encoder_refuses_it(self):
        with pytest.raises(ValueError, match="p_intra is not supported"):
            SfeShardEncoder(_meta(160, 96), qp=27, gop_frames=GOP, bands=2,
                            mesh=default_mesh(jax.devices()[:2]),
                            rd=RdConfig(p_intra=True))

    def test_the_transfer_layout_grows_by_one_word_a_macroblock(self):
        from thinvids_tpu.codecs.h264 import layout

        assert layout.p_flat_mb() == layout._P_FLAT_MB == 392
        assert layout.p_flat_mb(True) == 393
        nmb, F = 6, 3
        seg = np.arange((F - 1) * nmb * 393, dtype=np.int16)
        planes = layout.unflatten_p_planes(seg, np.zeros((2, nmb, 2)), F,
                                           3, 2, p_intra=True)
        assert len(planes) == 7 and planes[6].shape == (F - 1, nmb)
        assert planes[6][0, 0] == (F - 1) * nmb * 392
        assert len(layout.unflatten_p_planes(
            seg[:(F - 1) * nmb * 392], np.zeros((2, nmb, 2)), F, 3, 2)) == 6


# ---------------------------------------------------------------------------
# through the coordinator
# ---------------------------------------------------------------------------

JW, JH, JN, JGOP = 160, 96, 16, 8
JMETA = VideoMeta(width=JW, height=JH, fps_num=30, fps_den=1, num_frames=JN)


def _settings(**over):
    return Settings(values=dict(DEFAULT_SETTINGS, heartbeat_throttle_s=0.0,
                                gop_frames=JGOP, qp=27, **over))


def _run(tmp_path, name, path, job_settings=None, mesh=None, live=None,
         meta=JMETA, **settings):
    """One job through a coordinator whose settings are `settings`, the
    daemon's LIVE settings (where an encoder reads its RdConfig) being
    `live` (default: the same `p_intra`)."""
    snap = _settings(**settings)
    reg = WorkerRegistry()
    for i in range(8):
        reg.heartbeat(f"w{i:02d}")
    coord = Coordinator(registry=reg, settings_fn=lambda: snap)
    execu = LocalExecutor(coord, output_dir=str(tmp_path / name), sync=True,
                          mesh=mesh or _one_chip())
    coord._launcher = execu.launch
    before = dispatch.stage_snapshot()
    update_live_settings(live if live is not None else
                         {"p_intra": settings.get("p_intra", False)})
    try:
        job = coord.add_job(path, meta, settings=job_settings)
    finally:
        reset_live_settings()
    after = dispatch.stage_snapshot()
    return coord.store.get(job.id), {k: after[k] - before.get(k, 0)
                                     for k in after}


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("crossing")
    frames = _clip(JW, JH, JN)
    path = tmp / "clip.y4m"
    write_y4m(path, JMETA, frames)
    return tmp, frames, str(path)


def _decoded(job):
    media = read_mp4(job.output_path)
    return decode_annexb(media.annexb_for(0, media.num_frames))


class TestThroughTheCoordinator:
    def test_p_intra_alone_and_off(self, source):
        tmp, frames, path = source
        job, grew = _run(tmp, "on", path, p_intra=True)
        assert job.status is Status.DONE, job.failure_reason
        own = _decoded(job)
        assert len(own.frames) == JN and _intra_share(own) > 0.1
        assert grew["p_mbs_coded"] == (JN - 2) * 60
        assert 0 < grew["p_mbs_intra"] < grew["p_mbs_coded"]
        assert min(psnr(f.y, o.y) for f, o in zip(frames, own.frames)) > 30
        job, grew = _run(tmp, "off", path)
        assert job.status is Status.DONE
        assert grew["p_mbs_coded"] == grew["p_mbs_intra"] == 0
        assert _intra_share(_decoded(job)) == 0.0

    def test_with_scenecut(self, source):
        """The bounded GOP program under p_intra: the plan is made on
        cuts (none in this clip: the balanced GOPs, pinned)."""
        tmp, _frames, path = source
        job, grew = _run(tmp, "cuts", path, p_intra=True, scenecut=40)
        assert job.status is Status.DONE, job.failure_reason
        assert grew["p_mbs_intra"] > 0
        assert _intra_share(_decoded(job)) > 0.1

    def test_a_per_job_value_the_daemon_cannot_apply_is_refused(
            self, source):
        tmp, _frames, path = source
        job, grew = _run(tmp, "refused", path,
                         job_settings={"p_intra": True})
        assert job.status is Status.REJECTED
        assert "p_intra" in job.reject_reason \
            and "daemon-wide" in job.reject_reason
        assert grew["waves"] == 0
        job, _grew = _run(tmp, "refused-off", path, p_intra=True,
                          job_settings={"p_intra": False})
        assert job.status is Status.REJECTED
        # the daemon's own value, asked again per job, is no override
        job, grew = _run(tmp, "same", path, p_intra=True,
                         job_settings={"p_intra": "1"})
        assert job.status is Status.DONE and grew["p_mbs_intra"] > 0

    def test_a_band_shape_job_is_refused_at_admission(self, source):
        """Split-frame band steps have no intra / inter decision: the
        job is refused with the reason, never encoded all-inter."""
        tmp, _frames, path = source
        for where in ("job", "daemon"):
            job_settings = {"sfe_bands": 2} if where == "job" else None
            extra = {} if where == "job" else {"sfe_bands": 2}
            job, grew = _run(tmp, f"bands-{where}", path, p_intra=True,
                             job_settings=job_settings, **extra)
            assert job.status is Status.REJECTED
            assert "sfe_bands" in job.reject_reason \
                and "p_intra" in job.reject_reason
            assert grew["waves"] == 0 and grew["sfe_frames"] == 0
        decision = evaluate_job_policy(JMETA, _settings(sfe_bands=2))
        assert decision.accepted            # bands alone: as ever

    def test_as_a_ladder(self, source):
        """Ladder rungs are GOP-shape encoders: every rung codes intra
        macroblocks in its P pictures."""
        tmp, _frames, path = source
        job, grew = _run(tmp, "ladder", path, p_intra=True,
                         job_settings={"ladder_rungs": "96,48"},
                         job_type="ladder")
        assert job.status is Status.DONE, job.failure_reason
        assert grew["p_mbs_intra"] > 0
        assert grew["p_mbs_coded"] > (JN - 2) * 60   # both rungs counted

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
        assert sig(probe, _settings()) == sig(probe, _settings(p_intra=False))
        assert sig(probe, _settings()) != sig(probe, _settings(p_intra=True))
        snap = _settings(p_intra=True, remote_plan_devices=1,
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
        assert all(d["shape"] == "gop/half/p_intra" for d in descs)
        assert _intra_share(_decoded(job)) > 0.1

    def test_a_worker_from_before_the_setting_refuses_the_shard(self):
        """The setting rides in the shard's SHAPE tag, after the vector
        precision: a worker from before it (remote._shard_rd of PR 41,
        spelt out below) reads "half/p_intra" as a precision it does
        not know and answers `unsupported`; one from before PR 41
        knows only "gop" and "band". No shard of a p_intra plan is
        encoded all-inter under the plan's signature. A shard without
        the setting has the wire form it had."""
        from thinvids_tpu.cluster import remote
        from thinvids_tpu.core.config import SUBPELS

        def shard(**more):
            return remote.Shard(
                id="j-0", key="0", job_id="j", input_path="x", meta=JMETA,
                gops=plan_segments(JN, JGOP, 1).gops[:1], qp=27,
                gop_frames=JGOP, timeout_s=1.0, **more)

        def pr41_worker_takes(desc):
            shape, _, subpel = str(desc.get("shape", "gop") or "gop"
                                   ).partition("/")
            return shape in ("gop", "band") and (subpel or "half") in SUBPELS

        def older_worker_takes(desc):
            return str(desc.get("shape", "gop") or "gop") in ("gop", "band")

        assert "shape" not in shard().descriptor()
        assert shard(subpel="quarter").descriptor()["shape"] == "gop/quarter"
        assert pr41_worker_takes(shard(subpel="quarter").descriptor())
        for subpel in SUBPELS:
            desc = shard(subpel=subpel, p_intra=True).descriptor()
            assert desc["shape"] == f"gop/{subpel}/p_intra"
            assert "p_intra" not in desc
            assert not pr41_worker_takes(desc)
            assert not older_worker_takes(desc)
            assert remote.wire_shape(desc) == ("gop", subpel)
            rd = remote._shard_rd(desc)
            assert rd.p_intra is True and rd.subpel == subpel
        assert remote._shard_rd(shard().descriptor()).p_intra is False
        # this worker, handed a tag part of a later one
        desc = shard(p_intra=True).descriptor()
        desc["shape"] = "gop/half/p_intra/b_frames"
        with pytest.raises(remote.UnsupportedShardShape, match="b_frames"):
            remote.encode_shard(desc, [])


# ---------------------------------------------------------------------------
# the content
# ---------------------------------------------------------------------------

def _harness_crossing():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_gen_crossing",
        os.path.join(root, "benchmark", "generators", "crossing.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("n,w,h,seed,params", [
    (5, 128, 128, 7, {}),
    (3, 322, 182, 2**31 + 5, {"pan": 2, "sprites": 12}),
    (2, 640, 368, 0, {"sprites": 36}),
])
def test_crossing_harness_copy_is_the_same_generator(n, w, h, seed, params):
    ours = crossing.make_frames(n, w, h, seed=seed, **params)
    theirs = list(_harness_crossing().planes(n, w, h, seed, **params))
    assert len(ours) == len(theirs) == n
    for frame, planes in zip(ours, theirs):
        for mine, other in zip((frame.y, frame.u, frame.v), planes):
            assert mine.dtype == other.dtype == np.uint8
            assert mine.tobytes() == other.tobytes()
    assert ours[0].y.shape == (h, w) and ours[0].u.shape == (h // 2, w // 2)


def test_crossing_is_a_prefix_the_seed_draws_grain_and_sprites_cross():
    long = crossing.make_frames(7, 192, 128, seed=9)
    short = crossing.make_frames(3, 192, 128, seed=9)
    for a, b in zip(short, long):
        assert all(np.array_equal(getattr(a, p), getattr(b, p))
                   for p in "yuv")
    other = crossing.make_frames(1, 192, 128, seed=10)[0]
    assert not np.array_equal(other.y, long[0].y)       # the grain
    assert np.array_equal(other.u, long[0].u)           # not the scene
    # a sprite is a function of its index but for its grain, no
    # multiple of 16 wide or high at 1920, fast ones beyond the search
    for k in range(24):
        body, cu, cv, start, (vx, vy) = crossing.sprite(k, 1920, 1)
        again = crossing.sprite(k, 1920, 2)
        assert body.shape == again[0].shape and (cu, cv) == again[1:3]
        assert again[3:] == (start, (vx, vy))
        assert not np.array_equal(body, again[0])
        h, w = body.shape
        assert 96 <= w <= 385 and 96 <= h <= 385 and w % 16 and h % 16
        if k % 2 == 0:
            assert 7 <= abs(vx) <= 13 and abs(vy) == abs(vx) // 2
        else:
            assert 1 <= abs(vx) <= 4 and 1 <= abs(vy) <= 4
    # without sprites it is the panned background
    plain = crossing.make_frames(2, 192, 128, seed=9, sprites=0)
    assert np.array_equal(plain[1].y[:-3, :-3], plain[0].y[3:, 3:])
    covered = np.mean(plain[0].y != long[0].y)
    assert 0.2 < covered < 0.8
