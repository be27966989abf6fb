"""Film-grain content across the sparse-transfer cliff (ISSUE 30).

`tools/pan.make_frames(..., grain=sigma)` adds white grain that is new
on every frame: no motion search predicts it, every P frame carries a
dense residual, and from sigma ~3.4 at QP 27 a GOP leaves the sparse
transfer's block budget and ships its whole int16 levels instead — the
last output of the wave's one program, which the host fetches only
then (`GopShardEncoder.start_fetch`, ISSUE 31: no second program).
Held here, at small sizes on the CPU:

- the served encoder's bytes equal the plain encoder's (`encode_gop`:
  no budgets, no transfer format) for clips in which every GOP falls
  back, none does, and the two mix, with the RD tools off and with the
  serving set on — where libavcodec's decode also equals the encoder's
  reconstruction sample for sample;
- where the cliff is: block and value fill of sigma 0 / 3 / 5 content
  against both budgets, so a later change to budgets or quantiser that
  moves it is seen;
- the counters and stages that say so (`sparse_*`, `dense_retry` =
  `dense_fetch`; `dense_reencode` stays a key and reads 0);
- a wave that leaves the budgets runs ONE encode program, a wave inside
  them drops the levels before the next is dispatched and fetches not
  a byte more, and `dispatch_wave` starts no copy of the levels;
- the levels of a wave that leaves the budgets cross as int32 words
  (ISSUE 37: `_levels_as_words`, one small program per such wave and
  none for any other), and the words viewed as int16 on the host are
  the program's levels element for element;
- the benchmark's own copy of the generator gives the same planes.
"""

import contextlib
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu.codecs.h264 import jaxcore
from thinvids_tpu.codecs.h264.encoder import encode_gop
from thinvids_tpu.codecs.h264.rdo import RdConfig, aq_from_strength
from thinvids_tpu.core.types import VideoMeta, concat_segments
from thinvids_tpu.parallel import dispatch
from thinvids_tpu.parallel.dispatch import GopShardEncoder, default_mesh
from thinvids_tpu.tools.pan import make_frames

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RD_SERVING = dict(mode_decision=True, pskip=True, deblock=True,
                  aq_q=aq_from_strength(1.0))

W, H, GOP = 96, 64, 8
#: per-GOP grain of the three clips: under the budgets (sigma <= 3),
#: over them (sigma >= 4), and both in one clip
CLIPS = {"all_sparse": (0.0, 2.0, 0.0), "all_dense": (5.0, 6.0, 5.0),
         "mixed": (0.0, 6.0, 2.0, 5.0)}


def _clip(sigmas, seed=3, w=W, h=H, gop=GOP):
    per_frame = np.repeat(np.asarray(sigmas, np.float64), gop)
    return make_frames(len(per_frame), w, h, seed=seed, grain=per_frame)


def _one_chip():
    """The served one-chip shape: `_encode_gop_single`, one GOP a
    wave."""
    return default_mesh(jax.devices()[:1])


def _served(frames, qp, rd=None, mesh=None, w=W, h=H, gop=GOP):
    meta = VideoMeta(width=w, height=h, num_frames=len(frames))
    enc = GopShardEncoder(meta, qp=qp, gop_frames=gop, rd=rd,
                          mesh=mesh if mesh is not None else _one_chip())
    return enc, enc.encode(frames)


def _plain(frames, segments, qp, rd=None, w=W, h=H, **kw):
    meta = VideoMeta(width=w, height=h, num_frames=len(frames))
    return [encode_gop(frames[s.gop.start_frame:s.gop.end_frame], meta,
                       qp=qp, idr_pic_id=s.gop.index, rd=rd, **kw)
            for s in segments]


#: `d2h_bytes` of the clip `_clip((2.0, 0.0))` at QP 27 on one chip, as
#: the parent of ISSUE 31 (9267aa0) fetched it: counts, MVs, dense DC
#: prefix and the payloads' used prefixes, two waves
D2H_BYTES_OF_2_0 = 10518


def _words(L):
    """int32 words the L levels of a GOP cross the
    link as: pairs, in whole rows of `dispatch._WORD_ROW` levels."""
    row = dispatch._WORD_ROW
    return -(-L // row) * (row // 2)


def _went_dense(sigmas):
    return [s >= 4.0 for s in sigmas]


class TestServedEqualsPlainAcrossTheCliff:
    @pytest.mark.parametrize("qp", [27, 22])
    @pytest.mark.parametrize("clip", sorted(CLIPS))
    def test_rd_tools_off(self, clip, qp):
        sigmas = CLIPS[clip]
        frames = _clip(sigmas)
        enc, segs = _served(frames, qp)
        assert [s.payload for s in segs] == _plain(frames, segs, qp)
        snap = enc.stages.snapshot()
        assert snap["waves"] == len(sigmas)
        if qp == 27:    # the clips are cut for the budgets at QP 27
            assert snap["dense_fallback_waves"] == sum(_went_dense(sigmas))

    @pytest.mark.parametrize("qp", [25, 30])
    @pytest.mark.parametrize("clip", sorted(CLIPS))
    def test_serving_set_on_and_libavcodec_agrees(self, clip, qp):
        """mode_decision + pskip + deblock + AQ 1.0 through the dense
        fallback: same bytes as the plain encoder, and an independent
        decoder reproduces the encoder's reconstruction."""
        from thinvids_tpu.tools import oracle

        rd = RdConfig(**RD_SERVING)
        sigmas = CLIPS[clip]
        frames = _clip(sigmas)
        enc, segs = _served(frames, qp, rd=rd)
        plain = _plain(frames, segs, qp, rd=rd, return_recon=True)
        assert [s.payload for s in segs] == [p[0] for p in plain]
        if clip != "all_sparse":
            assert enc.stages.snapshot()["dense_fallback_waves"] >= 1
        if not oracle.oracle_available():
            pytest.skip("libavcodec oracle not available")
        decoded = oracle.decode_h264(concat_segments(segs))
        assert len(decoded) == len(frames)
        for seg, (_stream, recons) in zip(segs, plain):
            for i in range(seg.gop.num_frames):
                planes = decoded[seg.gop.start_frame + i]
                for name, got, rec, k in zip("yuv", planes, recons,
                                             (1, 2, 2)):
                    want = np.asarray(rec)[i][:H // k, :W // k]
                    assert np.array_equal(got, want), \
                        f"gop {seg.gop.index} frame {i} plane {name}"

    def test_sharded_wave_goes_dense_as_a_whole(self):
        """On a mesh a wave is one GOP per device and the budgets are
        judged on the fullest GOP: one grainy GOP takes the whole
        wave's levels across dense, same bytes."""
        sigmas = (0.0, 6.0, 0.0, 0.0)
        frames = _clip(sigmas)
        enc, segs = _served(frames, 27,
                            mesh=default_mesh(jax.devices()[:4]))
        assert [s.payload for s in segs] == _plain(frames, segs, 27)
        snap = enc.stages.snapshot()
        assert snap["waves"] == 1 and snap["dense_fallback_waves"] == 1


def _true_fill(frames, qp, w, h):
    """(blocks with a level, non-zero values) as shares of one GOP's
    sparse remainder, counted on the whole levels the one program
    leaves on the device: what the budgets are set against, with no
    budget in the way."""
    pads = [f.padded(16) for f in frames]
    mbw, mbh = pads[0].y.shape[1] // 16, pads[0].y.shape[0] // 16
    nmb = mbw * mbh
    stack = [jnp.asarray(np.stack([getattr(p, k) for p in pads]))[None]
             for k in "yuv"]
    flat = np.asarray(dispatch._encode_gop_single(
        *stack, jnp.asarray([qp], jnp.int32), mbw=mbw, mbh=mbh)[-1])[0]
    assert flat.dtype == np.int16
    ndc, nlac, ncdc = nmb * 16, nmb * 240, nmb * 8
    rest = np.concatenate([flat[ndc:ndc + nlac], flat[ndc + nlac + ncdc:]])
    nb = -(-rest.size // 16)
    blocks = np.pad(rest, (0, nb * 16 - rest.size)).reshape(nb, 16)
    return (np.any(blocks != 0, axis=1).sum() / nb,
            np.count_nonzero(rest) / rest.size)


class TestWhereTheCliffIs:
    """ISSUE 30's calibration (320x192, GOP 16, QP 27, default
    budgets: 25 % of the blocks, 4.17 % of the values). The value
    column is counted on the dense levels: the issue's own table took
    the device's `nval`, which stops counting at the block budget and
    read 3.51 % at sigma 5 where 6.26 % of the values are non-zero."""

    W, H, GOP = 320, 192, 16
    BLOCK_BUDGET = 1 / jaxcore._BLOCK_BUDGET_DIV
    VALUE_BUDGET = 1 / jaxcore._VAL_BUDGET_DIV

    @pytest.mark.parametrize("sigma,blocks,values,dense", [
        (0.0, 0.078, 0.0095, False),
        (3.0, 0.201, 0.0205, False),
        (5.0, 0.488, 0.0626, True),
    ])
    def test_fill_lands_on_its_side_of_each_budget(self, sigma, blocks,
                                                   values, dense):
        frames = make_frames(self.GOP, self.W, self.H, seed=1, grain=sigma)
        got_b, got_v = _true_fill(frames, 27, self.W, self.H)
        assert got_b == pytest.approx(blocks, abs=0.01)
        assert got_v == pytest.approx(values, abs=0.002)
        assert (got_b > self.BLOCK_BUDGET) == dense
        assert (got_v > self.VALUE_BUDGET) == dense
        # the block budget is the one that goes first: at every grain
        # the blocks are the fuller of the two
        assert got_b / self.BLOCK_BUDGET > got_v / self.VALUE_BUDGET

        # and the served encoder's counters say the same of the blocks
        # (exact), and of the values while the blocks fit (past that
        # the device counts the values of the blocks it kept)
        enc, _segs = _served(frames, 27, w=self.W, h=self.H, gop=self.GOP)
        snap = enc.stages.snapshot()
        assert snap["dense_fallback_waves"] == int(dense)
        fill_b = snap["sparse_blocks_used"] / snap["sparse_blocks_budget"]
        fill_v = snap["sparse_values_used"] / snap["sparse_values_budget"]
        assert fill_b == pytest.approx(got_b / self.BLOCK_BUDGET, rel=1e-3)
        if dense:
            assert fill_b > 1.0
            assert fill_v < got_v / self.VALUE_BUDGET
        else:
            assert fill_v == pytest.approx(got_v / self.VALUE_BUDGET,
                                           rel=1e-3)

    def test_budgets_helper_is_what_fits_judges_by(self):
        L = 16 * 1000 + 8
        blocks, values = jaxcore.block_sparse2_budgets(L)
        assert (blocks, values) == (1001 // 4, L // 24)
        assert jaxcore.block_sparse2_fits(blocks, values, 0, L)
        assert not jaxcore.block_sparse2_fits(blocks + 1, values, 0, L)
        assert not jaxcore.block_sparse2_fits(blocks, values + 1, 0, L)
        assert not jaxcore.block_sparse2_fits(blocks, values, 1, L)
        assert jaxcore.block_sparse2_budgets(L, 1, 1) == (1001, L)


class _Spans:
    """A span recorder that keeps (name, seconds)."""

    enabled = True

    def __init__(self):
        self.spans = []

    def record(self, name, t0, dur, **tags):
        self.spans.append((name, dur))

    def span(self, name, **tags):
        return contextlib.nullcontext()


def _count_programs(monkeypatch):
    """Count the calls of every jitted program of `parallel/dispatch`
    from here on; returns the list the names are appended to."""
    calls = []
    for name, program in list(vars(dispatch).items()):
        if callable(program) and hasattr(program, "lower"):
            def counted(*a, _name=name, _program=program, **kw):
                calls.append(_name)
                return _program(*a, **kw)
            monkeypatch.setattr(dispatch, name, counted)
    return calls


class _CopySpy:
    """Stands for one output of a wave's program: records whether a
    copy to the host was started on it."""

    def __init__(self, array):
        self.array, self.copied = array, False

    def copy_to_host_async(self):
        self.copied = True


class TestOneProgramPerWave:
    """ISSUE 31: the levels a wave's program computed are its dense
    fallback; nothing is encoded twice, and a wave inside the budgets
    pays nothing across the link for them."""

    @pytest.mark.parametrize("devices", [1, 4])
    def test_a_wave_that_leaves_the_budgets_runs_one_program(
            self, devices, monkeypatch):
        sigmas = (5.0,) + (0.0,) * (devices - 1)
        frames = _clip(sigmas)
        meta = VideoMeta(width=W, height=H, num_frames=len(frames))
        enc = GopShardEncoder(meta, qp=27, gop_frames=GOP,
                              mesh=default_mesh(jax.devices()[:devices]))
        (staged,) = enc.stage_waves(frames)
        calls = _count_programs(monkeypatch)
        handle = enc.dispatch_wave(staged)
        enc.start_fetch(handle)
        fetch = handle[-1]
        assert not fetch.sparse_ok and fetch.payload is None  # no slice
        segs = enc.collect_wave(handle)
        # one encode program; the re-wording of its levels (ISSUE 37)
        # is the one other thing the wave puts on the compute queue
        assert calls == ["_encode_gop_single" if devices == 1
                         else "_encode_wave_gop", "_levels_as_words"]
        assert enc.stages.snapshot()["dense_fallback_waves"] == 1
        assert [s.payload for s in segs] == _plain(frames, segs, 27)

    def test_a_wave_inside_the_budgets_drops_the_levels_unfetched(self):
        frames = _clip((2.0, 0.0))
        meta = VideoMeta(width=W, height=H, num_frames=len(frames))
        enc = GopShardEncoder(meta, qp=27, gop_frames=GOP, mesh=_one_chip())
        first, second = enc.stage_waves(frames)
        handle = enc.dispatch_wave(first)
        levels = handle[-1].dense
        L, _Lr = enc._level_sizes(GOP, (W // 16) * (H // 16))
        assert levels.shape == (1, L) and levels.dtype == jnp.int16
        enc.start_fetch(handle)
        # before the next wave is dispatched the reference is gone
        assert handle[-1].sparse_ok and handle[-1].dense is None
        nxt = enc.dispatch_wave(second)
        segs = enc.collect_wave(handle) + enc.collect_wave(nxt)
        assert [s.payload for s in segs] == _plain(frames, segs, 27)
        snap = enc.stages.snapshot()
        assert snap["dense_fallback_waves"] == 0
        assert snap["dense_retry"] == 0 and snap["dense_fetch"] == 0
        # the levels never crossed: the parent (9267aa0) fetched the
        # same bytes for this clip, 7 % of one GOP's levels
        assert snap["d2h_bytes"] == D2H_BYTES_OF_2_0
        assert snap["d2h_bytes"] < levels.nbytes

    def test_dispatch_starts_no_copy_of_the_levels(self, monkeypatch):
        """`dispatch_wave` prefetches every small output and neither the
        budget-padded compact payload nor the whole levels: 199 MB per
        1080p GOP would cross on every wave of every cell."""
        frames = _clip((0.0,))
        meta = VideoMeta(width=W, height=H, num_frames=len(frames))
        enc = GopShardEncoder(meta, qp=27, gop_frames=GOP, mesh=_one_chip())
        (staged,) = enc.stage_waves(frames)
        program = dispatch._encode_gop_single
        monkeypatch.setattr(
            dispatch, "_encode_gop_single",
            lambda *a, **kw: tuple(_CopySpy(x) for x in program(*a, **kw)))
        handle = enc.dispatch_wave(staged)
        out, levels = handle[7], handle[-1].dense
        assert isinstance(levels, _CopySpy) and not levels.copied
        assert levels.array.dtype == jnp.int16
        assert levels not in out
        assert [spy.copied for spy in out] == [
            i != 6 for i in range(len(out))]
        assert len(out) == 7


class TestLevelsCrossAsWords:
    """ISSUE 37: what crosses the link for a wave that left the sparse
    budgets is `_levels_as_words` of its program's last output — the
    same bytes as 32-bit words — and the host's int16 view of the
    words is that output, element for element."""

    #: name -> (encoder arguments, devices, frames, GOPs in the wave)
    WAVES = {
        "one_gop": (dict(), 1, lambda: _clip((5.0,)), 1),
        "two_gops_a_device": (dict(gops_per_wave=2), 1,
                              lambda: _clip((5.0, 6.0)), 2),
        "serving_set": (dict(rd=RdConfig(**RD_SERVING)), 1,
                        lambda: _clip((6.0,)), 1),
        "mesh_of_two": (dict(), 2, lambda: _clip((0.0, 6.0)), 2),
    }

    @pytest.mark.parametrize("wave", sorted(WAVES))
    def test_words_viewed_as_int16_are_the_programs_levels(self, wave):
        kwargs, devices, make, G = self.WAVES[wave]
        frames = make()
        qp = 25 if "rd" in kwargs else 27
        meta = VideoMeta(width=W, height=H, num_frames=len(frames))
        enc = GopShardEncoder(
            meta, qp=qp, gop_frames=GOP,
            mesh=default_mesh(jax.devices()[:devices]), **kwargs)
        (staged,) = enc.stage_waves(frames)
        handle = enc.dispatch_wave(staged)
        fetch = handle[-1]
        levels = fetch.dense
        L, _Lr = enc._level_sizes(GOP, (W // 16) * (H // 16))
        assert enc.rd.ships_modes == ("rd" in kwargs)
        assert levels.dtype == jnp.int16
        assert levels.shape == (G, L)
        enc.start_fetch(handle)
        assert not fetch.sparse_ok
        words = fetch.dense
        assert words.dtype == jnp.int32
        assert words.shape == levels.shape[:-1] + (_words(L),)
        # a wave sharded over `gop` stays sharded
        assert words.sharding.is_equivalent_to(levels.sharding, words.ndim)
        assert len(words.sharding.device_set) == devices
        host = np.asarray(words).view(np.int16)
        assert np.array_equal(host[..., :L], np.asarray(levels))
        assert not host[..., L:].any()          # the last row's padding
        for half in (0, 1):     # both halves carry both signs
            part = host[..., half:L:2]
            assert part.min() < 0 < part.max()
        segs = enc.collect_wave(handle)
        assert fetch.dense is None              # released once fetched
        assert enc.stages.snapshot()["dense_fallback_waves"] == 1
        assert [s.payload for s in segs] == _plain(
            frames, segs, qp, rd=kwargs.get("rd"))

    @pytest.mark.parametrize("shape", [(1, 2), (1, 254), (1, 255),
                                       (2, 258), (2, 3, 770), (3, 769),
                                       (1, 1 << 17)])
    def test_every_int16_value_survives_in_either_half(self, shape):
        """The program alone, on every int16 value in the low and in
        the high half of a word (the levels pass through f32 and a
        matmul on the way: exact, or this fails), for lengths that do
        and do not fill their last row — an odd one too (the transfer
        layout has none: every MB has an even count), which the row
        padding carries in the same one format."""
        every = np.arange(-32768, 32768).astype(np.int16)
        levels = np.resize(np.concatenate([np.repeat(every, 2), every]),
                           shape)
        words = np.asarray(dispatch._levels_as_words(jnp.asarray(levels)))
        assert words.dtype == np.int32
        assert words.shape == shape[:-1] + (_words(shape[-1]),)
        host = words.view(np.int16)
        assert np.array_equal(host[..., :shape[-1]], levels)
        assert not host[..., shape[-1]:].any()

    @pytest.mark.parametrize("clip", sorted(CLIPS))
    def test_one_rewording_per_dense_wave_and_none_for_a_sparse_one(
            self, clip, monkeypatch):
        sigmas = CLIPS[clip]
        frames = _clip(sigmas)
        meta = VideoMeta(width=W, height=H, num_frames=len(frames))
        enc = GopShardEncoder(meta, qp=27, gop_frames=GOP, mesh=_one_chip())
        calls = _count_programs(monkeypatch)
        segs = enc.encode(frames)
        assert [s.payload for s in segs] == _plain(frames, segs, 27)
        snap = enc.stages.snapshot()
        assert calls.count("_encode_gop_single") == snap["waves"] \
            == len(sigmas)
        assert calls.count("_levels_as_words") \
            == snap["dense_fallback_waves"] == sum(_went_dense(sigmas))
        assert set(calls) <= {"_encode_gop_single", "_levels_as_words"}


class TestTheRecordSaysWhichHalfCosts:
    def test_dense_retry_is_the_sum_of_its_halves_and_both_are_spans(self):
        """Since ISSUE 31 there is one half: `dense_fetch`. The key
        `dense_reencode` stays registered (the benchmark's files read
        it) and reads 0; no span of that name is recorded."""
        frames = _clip(CLIPS["mixed"])
        meta = VideoMeta(width=W, height=H, num_frames=len(frames))
        enc = GopShardEncoder(meta, qp=27, gop_frames=GOP, mesh=_one_chip())
        rec = _Spans()
        enc.stages.set_tracer(rec)
        enc.encode(frames)
        snap = enc.stages.snapshot()
        assert snap["dense_fallback_waves"] == 2
        assert snap["dense_reencode"] == 0 and snap["dense_fetch"] > 0
        assert snap["dense_retry"] == pytest.approx(
            snap["dense_reencode"] + snap["dense_fetch"], abs=0.02)
        names = [n for n, _d in rec.spans]
        assert names.count("dense_reencode") == 0
        assert names.count("dense_fetch") == 2
        # the sparse waves' fetch is not in the retry's number
        assert snap["fetch"] > 0 and names.count("fetch") == 2

    def test_the_levels_are_sent_by_start_fetch_before_the_next_wave(self):
        """The order rule holds for the fallback as for the payload
        slice: the levels are on the wave's handle from dispatch on;
        start_fetch re-words them (int16 pairs as int32 words, the
        same bytes) and starts the copy of the words where the budgets
        gave way, and drops them where they held, in which case the
        wave gets its payload slices. collect_wave then only waits."""
        frames = _clip((5.0, 0.0))
        meta = VideoMeta(width=W, height=H, num_frames=len(frames))
        enc = GopShardEncoder(meta, qp=27, gop_frames=GOP, mesh=_one_chip())
        grainy, clean = enc.stage_waves(frames)
        first = enc.dispatch_wave(grainy)
        levels = first[-1].dense
        assert levels is not None and first[-1].tiny is None
        assert levels.dtype == jnp.int16 and levels.shape[0] == 1
        enc.start_fetch(first)
        words = first[-1].dense
        assert words.dtype == jnp.int32 and not first[-1].sparse_ok
        L = levels.shape[1]
        assert words.shape == (1, _words(L))
        assert np.array_equal(np.asarray(words).view(np.int16)[:, :L],
                              np.asarray(levels))
        assert first[-1].payload is None
        second = enc.dispatch_wave(clean)
        enc.start_fetch(first)                  # idempotent
        assert first[-1].dense is words
        enc.start_fetch(second)
        assert second[-1].sparse_ok and second[-1].dense is None
        assert second[-1].payload is not None
        segs = enc.collect_wave(first) + enc.collect_wave(second)
        assert first[-1].dense is None          # released once fetched
        assert [s.payload for s in segs] == _plain(frames, segs, 27)

    def test_counters_reach_the_process_totals_and_the_registry(self):
        from thinvids_tpu.obs import metrics as obs_metrics

        names = ("sparse_blocks_used", "sparse_blocks_budget",
                 "sparse_values_used", "sparse_values_budget")
        assert set(names) <= set(dispatch.STAGE_COUNTERS)
        assert set(names) <= set(obs_metrics.STAGE_COUNTER_TOTALS)
        before = dispatch.stage_snapshot()
        enc, _segs = _served(_clip((2.0,)), 27)
        snap, after = enc.stages.snapshot(), dispatch.stage_snapshot()
        _L, Lr = enc._level_sizes(GOP, (W // 16) * (H // 16))
        assert (snap["sparse_blocks_budget"], snap["sparse_values_budget"]) \
            == jaxcore.block_sparse2_budgets(Lr)
        for name in names:
            assert snap[name] > 0
            assert after[name] - before.get(name, 0) == snap[name]

    def test_split_frame_bands_count_against_unit_budgets(self):
        from thinvids_tpu.parallel.dispatch import SfeShardEncoder

        frames = _clip((5.0,), gop=4)
        meta = VideoMeta(width=W, height=H, num_frames=len(frames))
        enc = SfeShardEncoder(meta, qp=27, gop_frames=4, bands=2,
                              mesh=default_mesh(jax.devices()[:2]))
        enc.encode(frames)
        snap = enc.stages.snapshot()
        assert snap["dense_fallback_waves"] == 0    # only an escape would
        assert 0 < snap["sparse_blocks_used"] < snap["sparse_blocks_budget"]
        assert 0 < snap["sparse_values_used"] < snap["sparse_values_budget"]


def _bench_generator(name):
    path = os.path.join(ROOT, "benchmark", "generators", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_gen_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _same_planes(frames, planes):
    planes = list(planes)
    return len(frames) == len(planes) and all(
        np.array_equal(f.y, y) and np.array_equal(f.u, u)
        and np.array_equal(f.v, v) for f, (y, u, v) in zip(frames, planes))


class TestTheHarnessCopyIsTheSameGenerator:
    @pytest.mark.parametrize("sigma", [0.0, 3.0, 5.0])
    @pytest.mark.parametrize("n,w,h,seed,pan", [
        (5, 64, 48, 7, 3), (3, 130, 70, 2**31 + 11, 2)])
    def test_same_planes_for_the_same_arguments(self, n, w, h, seed, pan,
                                                sigma):
        grain = _bench_generator("grain")
        frames = make_frames(n, w, h, seed=seed, pan=pan, grain=sigma)
        assert _same_planes(frames, grain.planes(n, w, h, seed, pan=pan,
                                                 sigma=sigma))
        assert all(f.y.dtype == np.uint8 and f.y.shape == (h, w)
                   and f.u.shape == (h // 2, w // 2) for f in frames)

    def test_sigma_zero_is_the_pan_to_the_byte(self):
        grain, pan = _bench_generator("grain"), _bench_generator("pan")
        want = list(pan.planes(6, 80, 48, 9, pan=3))
        assert _same_planes(make_frames(6, 80, 48, seed=9), want)
        assert _same_planes(make_frames(6, 80, 48, seed=9, grain=0.0), want)
        got = list(grain.planes(6, 80, 48, 9, pan=3, sigma=0.0))
        assert all(np.array_equal(a, b) for g, p in zip(got, want)
                   for a, b in zip(g, p))

    def test_grain_is_new_on_every_frame_and_half_as_strong_in_chroma(self):
        still = make_frames(6, 256, 128, seed=4, pan=0)
        noisy = make_frames(6, 256, 128, seed=4, pan=0, grain=5.0)
        # (away from 0 and 255, where the clip cuts the grain's tails)
        mid = (still[0].y > 40) & (still[0].y < 215)
        dy = [(n.y.astype(np.int32) - s.y)[mid]
              for n, s in zip(noisy, still)]
        du = [n.u.astype(np.int32) - s.u for n, s in zip(noisy, still)]
        assert np.std(dy) == pytest.approx(5.0, rel=0.05)
        assert np.std(du) == pytest.approx(2.5, rel=0.05)
        assert abs(np.mean(dy)) < 0.1
        # independent from frame to frame: no correlation to speak of
        assert abs(np.corrcoef(dy[0].ravel(), dy[1].ravel())[0, 1]) < 0.02

    def test_a_sequence_gives_each_frame_its_own_sigma(self):
        frames = make_frames(4, 64, 48, seed=5, grain=[0.0, 4.0, 0.0, 4.0])
        clean = make_frames(4, 64, 48, seed=5)
        assert np.array_equal(frames[0].y, clean[0].y)
        assert np.array_equal(frames[2].y, clean[2].y)
        assert not np.array_equal(frames[1].y, clean[1].y)
        assert not np.array_equal(frames[3].y, clean[3].y)
