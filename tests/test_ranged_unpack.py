"""The compact payload unpacked in ranges (ISSUE 48).

A GOP wave's compact payload is no longer unpacked whole by the
collecting thread: one pass validates and indexes it
(`native.index_compact`, numpy twin `layout.index_compact_host`), and
each slice thunk unpacks the runs of the level vector it packs
(`native.unpack_compact_range`, twin `unpack_compact_range_host`) into
frame-sized memory its thread keeps. This file holds, bottom up:

- the ranged unpack against `layout.unpack_compact_host` over random
  partitions of `[0, L)` — cuts inside blocks, ragged tails, nothing
  live, everything live, several index entries, a dirty destination —,
  native and numpy, and the two indexes against each other;
- the index pass rejecting what the whole-vector parser rejects, with
  the same error;
- `layout.rest_spans` against `unflatten_gop_parts`' views for the
  plain, `ships_modes` and `p_intra` layouts, odd macroblock counts;
- `collect_wave` with the pool, inline and without the native library
  against the parent's whole-vector collect, kept here as the oracle;
  the counter `unpack_ranges`; and that no allocation of a sparse
  collect is a GOP's levels long.
"""

import threading
import tracemalloc

import numpy as np
import pytest

import jax

from thinvids_tpu import native
from thinvids_tpu.codecs.h264 import layout
from thinvids_tpu.codecs.h264.encoder import pack_gop_slices_planes
from thinvids_tpu.codecs.h264.rdo import RdConfig, aq_from_strength
from thinvids_tpu.core.types import Frame, VideoMeta
from thinvids_tpu.obs import metrics as obs_metrics
from thinvids_tpu.parallel import dispatch
from thinvids_tpu.parallel.dispatch import GopShardEncoder, default_mesh
from thinvids_tpu.tools import crossing

STRIDE = layout.INDEX_STRIDE


def _pack_host(flat: np.ndarray):
    """Flat levels (|v| <= 127) → (nblk, nval, payload): the compact
    format written by numpy (layout.py's docstring)."""
    L = flat.shape[0]
    NB = -(-L // 16)
    blocks = np.zeros(NB * 16, np.int16)
    blocks[:L] = flat
    blocks = blocks.reshape(NB, 16)
    lanes = blocks != 0
    live = lanes.any(1)
    masks = (lanes[live] << np.arange(16)).sum(1).astype(np.uint16)
    vals = blocks[live][lanes[live]].astype(np.int8)
    payload = np.concatenate([
        np.packbits(live.astype(np.uint8)),
        np.stack([masks & 0xFF, masks >> 8], 1).astype(np.uint8).reshape(-1),
        vals.view(np.uint8)])
    return int(live.sum()), int(vals.shape[0]), payload


def _levels(L: int, fill: float, rng, lanes=(1, 4)) -> np.ndarray:
    """`L` levels, a share `fill` of the blocks holding a few."""
    NB = -(-L // 16)
    flat = np.zeros(NB * 16, np.int16)
    for b in np.flatnonzero(rng.random(NB) < fill):
        at = rng.choice(16, rng.integers(*lanes), replace=False)
        flat[b * 16 + at] = rng.choice([-1, 1], len(at)) \
            * rng.integers(1, 128, len(at))
    return flat[:L]


def _case(name: str):
    """name -> flat levels."""
    rng = np.random.default_rng(len(name) + 48)
    if name == "ragged_tail":                 # L no multiple of 16
        return _levels(16 * 600 + 8, 0.15, rng)
    if name == "nothing_live":                # nblk 0: a still GOP
        return np.zeros(16 * 40 + 3, np.int16)
    if name == "every_block_live":            # the block budget, full
        return _levels(16 * 300, 1.1, rng, lanes=(1, 17))
    if name == "last_level_alone":
        flat = np.zeros(16 * 77 + 11, np.int16)
        flat[-1] = -5
        return flat
    if name == "first_level_alone":
        flat = np.zeros(16 * 77 + 11, np.int16)
        flat[0] = 9
        return flat
    if name == "three_index_entries":         # ranges start past entry 0
        return _levels(16 * (2 * STRIDE + 900) + 5, 0.1, rng)
    if name == "a_whole_stride":              # NB a multiple of the stride
        return _levels(16 * STRIDE, 0.3, rng)
    if name == "one_level":                   # L under a block
        return np.asarray([0, 0, 3], np.int16)
    raise KeyError(name)


CASES = ["ragged_tail", "nothing_live", "every_block_live",
         "last_level_alone", "first_level_alone", "three_index_entries",
         "a_whole_stride", "one_level"]
IMPLS = ["native", "numpy"]


def _impl(name: str):
    """name -> (index, ranged unpack, whole-vector parser), one call
    signature for both: (payload, nblk, nval, L, ...)."""
    if name == "numpy":
        return (layout.index_compact_host,
                layout.unpack_compact_range_host,
                layout.unpack_compact_host)
    if not native.available():
        pytest.skip("no compiler")
    return (lambda p, nblk, nval, L: native.index_compact(nblk, nval, p, L),
            lambda p, nblk, nval, L, *a: native.unpack_compact_range(
                nblk, nval, p, L, *a),
            lambda p, nblk, nval, L: native.unpack_compact(nblk, nval, p, L))


def _partition(L: int, rng) -> list[tuple[int, int]]:
    """Ranges that tile [0, L): random cuts (most inside a block), an
    empty range at either end and one in the middle."""
    cuts = np.unique(rng.integers(0, L + 1, min(24, L + 1)))
    edges = [0, 0, *cuts.tolist(), int(cuts[len(cuts) // 2]), L, L]
    edges.sort()
    return list(zip(edges[:-1], edges[1:]))


class TestRangedUnpack:
    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("case", CASES)
    def test_any_partition_is_the_whole_vector(self, case, impl):
        index_of, unpack_range, _ = _impl(impl)
        flat = _case(case)
        L = flat.shape[0]
        nblk, nval, payload = _pack_host(flat)
        want = layout.unpack_compact_host(payload, nblk, nval, L)
        np.testing.assert_array_equal(want, flat)
        index = index_of(payload, nblk, nval, L)
        assert index.shape == (layout.index_entries(L), 2)
        rng = np.random.default_rng(7)
        for _ in range(3):
            # a destination dirty with other levels: every range
            # zeroes what it does not write
            got = np.full(L, 0x7A7A, np.int16)
            for l0, l1 in _partition(L, rng):
                unpack_range(payload, nblk, nval, L, index, l0, l1,
                             got[l0:l1])
            np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("case", CASES)
    def test_the_two_indexes_are_equal(self, case):
        if not native.available():
            pytest.skip("no compiler")
        flat = _case(case)
        L = flat.shape[0]
        nblk, nval, payload = _pack_host(flat)
        got = native.index_compact(nblk, nval, payload, L)
        want = layout.index_compact_host(payload, nblk, nval, L)
        assert got.dtype == want.dtype == np.int64
        np.testing.assert_array_equal(got, want)
        # an entry: the live blocks and the values before its block
        blocks = np.zeros(-(-L // 16) * 16, np.int16)
        blocks[:L] = flat
        blocks = blocks.reshape(-1, 16)
        for j, (bi, vi) in enumerate(want):
            head = blocks[:j * STRIDE]
            assert bi == (head != 0).any(1).sum()
            assert vi == np.count_nonzero(head)

    def test_bytes_past_the_used_prefix_do_not_matter(self):
        index_of, unpack_range, _ = _impl("native")
        flat = _case("ragged_tail")
        L = flat.shape[0]
        nblk, nval, payload = _pack_host(flat)
        padded = np.concatenate([payload, np.full(99, 0xAB, np.uint8)])
        index = index_of(padded, nblk, nval, L)
        got = np.empty(L, np.int16)
        unpack_range(padded, nblk, nval, L, index, 0, L, got)
        np.testing.assert_array_equal(got, flat)

    @pytest.mark.parametrize("bad", ["range_backwards", "range_past_L",
                                     "range_negative", "short_destination",
                                     "int32_destination",
                                     "strided_destination",
                                     "another_payloads_index",
                                     "read_only_destination"])
    def test_native_range_refuses_a_bad_call(self, bad):
        if not native.available():
            pytest.skip("no compiler")
        flat = _case("three_index_entries")
        L = flat.shape[0]
        nblk, nval, payload = _pack_host(flat)
        index = native.index_compact(nblk, nval, payload, L)
        l0, l1, out = 100, 200, np.empty(100, np.int16)
        if bad == "range_backwards":
            l0, l1, out = 200, 100, np.empty(0, np.int16)
        elif bad == "range_past_L":
            l0, l1 = L - 50, L + 50
        elif bad == "range_negative":
            l0, l1 = -100, 0
        elif bad == "short_destination":
            out = np.empty(99, np.int16)
        elif bad == "int32_destination":
            out = np.empty(100, np.int32)
        elif bad == "strided_destination":
            out = np.empty(200, np.int16)[::2]
        elif bad == "another_payloads_index":
            index = index[:-1]
        elif bad == "read_only_destination":
            out.setflags(write=False)
        with pytest.raises(ValueError):
            native.unpack_compact_range(nblk, nval, payload, L, index,
                                        l0, l1, out)

    def test_ranges_side_by_side_on_threads(self):
        """More threads than cores unpack every slice of one payload at
        once, each into its own dirty scratch: what slice thunks do."""
        if not native.available():
            pytest.skip("no compiler")
        mbw, mbh, F = 5, 3, 9
        nmb = mbw * mbh
        Lr = nmb * 360 + (F - 1) * nmb * layout.p_flat_mb(True)
        rest = _levels(Lr, 0.2, np.random.default_rng(5))
        nblk, nval, payload = _pack_host(rest)
        index = native.index_compact(nblk, nval, payload, Lr)
        intra, frames = layout.rest_spans(F, mbw, mbh, p_intra=True)
        wrong: list = []

        def work(seed):
            rng = np.random.default_rng(seed)
            room = np.empty(nmb * layout.p_flat_mb(True), np.int16)
            for _ in range(40):
                spans = ([intra] + frames)[rng.integers(0, F)]
                room[:] = rng.integers(-9, 9)
                o = 0
                for l0, n, shape in spans:
                    native.unpack_compact_range(
                        nblk, nval, payload, Lr, index, l0, l0 + n,
                        room[o:o + n])
                    if not np.array_equal(room[o:o + n], rest[l0:l0 + n]):
                        wrong.append((seed, l0))
                    o += n

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert not wrong


def _corrupt(name: str, nblk: int, nval: int, payload: np.ndarray, L: int):
    """name -> (nblk, nval, payload, L) the parsers must refuse."""
    NB = -(-L // 16)
    nb8 = (NB + 7) // 8
    p = payload.copy()
    if name == "negative_nblk":
        return -1, nval, p, L
    if name == "negative_nval":
        return nblk, -1, p, L
    if name == "nblk_one_short":              # bitmap has one block more
        return nblk - 1, nval, np.concatenate([p, p[-2:]]), L
    if name == "nblk_one_over":
        return nblk + 1, nval, np.concatenate([p, p[-2:]]), L
    if name == "nval_one_short":              # masks ask for one more
        return nblk, nval - 1, p, L
    if name == "nval_one_over":
        return nblk, nval + 1, np.concatenate([p, p[-1:]]), L
    if name == "padding_bit_set":
        assert NB % 8
        p[nb8 - 1] |= 1
        return nblk, nval, p, L
    if name == "truncated":
        return nblk, nval, p[:-1], L
    if name == "a_bit_after_the_last_live_block":
        dead = NB - 1                         # the cases leave it empty
        assert not p[dead >> 3] & (0x80 >> (dead & 7))
        p[dead >> 3] |= 0x80 >> (dead & 7)
        return nblk, nval, p, L
    if name == "a_live_bit_cleared":
        byte = int(np.flatnonzero(p[:nb8])[0])
        p[byte] &= p[byte] - 1
        return nblk, nval, p, L
    if name == "a_mask_bit_flipped":
        p[nb8] ^= 1
        return nblk, nval, p, L
    if name == "no_levels_at_all":
        return nblk, nval, p, 0
    raise KeyError(name)


class TestIndexPassValidates:
    CORRUPT = ["negative_nblk", "negative_nval", "nblk_one_short",
               "nblk_one_over", "nval_one_short", "nval_one_over",
               "padding_bit_set", "truncated",
               "a_bit_after_the_last_live_block", "a_live_bit_cleared",
               "a_mask_bit_flipped", "no_levels_at_all"]

    @pytest.mark.parametrize("impl", IMPLS)
    @pytest.mark.parametrize("corrupt", CORRUPT)
    def test_rejects_what_the_whole_parser_rejects(self, corrupt, impl):
        index_of, _, whole = _impl(impl)
        rng = np.random.default_rng(3)
        flat = _levels(16 * 333 + 5, 0.2, rng)
        flat[-16:] = 0                        # the last block stays empty
        L = flat.shape[0]
        args = _pack_host(flat) + (L,)
        index_of(args[2], args[0], args[1], L)          # sound: accepted
        nblk, nval, payload, L = _corrupt(corrupt, *args)
        with pytest.raises(ValueError) as whole_err:
            whole(payload, nblk, nval, L)
        with pytest.raises(ValueError) as index_err:
            index_of(payload, nblk, nval, L)
        assert str(index_err.value) == str(whole_err.value)


# ---- the layouts: a slice's spans are the whole vector's views -------------

#: name -> (mbw, mbh, frames, p_intra, ships_modes)
LAYOUTS = {
    "plain_odd_nmb": (5, 3, 4, False, False),     # nmb * 392 no multiple
    "plain_one_mb": (1, 1, 3, False, False),      # of 16 for an odd nmb
    "ships_modes": (4, 3, 3, False, True),
    "p_intra_odd_nmb": (3, 3, 5, True, False),
    "p_intra_ships_modes": (4, 2, 4, True, True),
    "idr_alone": (4, 3, 1, False, False),
}


class TestRestSpans:
    @pytest.mark.parametrize("name", sorted(LAYOUTS))
    def test_spans_tile_the_vector_and_give_the_same_views(self, name):
        mbw, mbh, F, p_intra, ships_modes = LAYOUTS[name]
        nmb = mbw * mbh
        Lr = nmb * 360 + (F - 1) * nmb * layout.p_flat_mb(p_intra)
        rng = np.random.default_rng(F + nmb)
        rest = _levels(Lr, 0.3, rng)
        dense = rng.integers(-50, 50, nmb * 24 + (2 * nmb if ships_modes
                                                  else 0)).astype(np.int16)
        mv8 = rng.integers(-4, 4, (F - 1, nmb, 2)).astype(np.int8)
        want_intra, want_planes = layout.unflatten_gop_parts(
            dense, rest, mv8, F, mbw, mbh, ships_modes=ships_modes,
            p_intra=p_intra)
        intra, frames = layout.rest_spans(F, mbw, mbh, p_intra)
        assert len(frames) == F - 1
        assert all(len(f) == (6 if p_intra else 5) for f in frames)
        # a partition of [0, Lr): every level in one span
        runs = sorted((l0, l0 + n) for spans in [intra] + frames
                      for l0, n, shape in spans)
        assert all(n == np.prod(shape) for spans in [intra] + frames
                   for _l0, n, shape in spans)
        assert runs[0][0] == 0 and runs[-1][1] == Lr
        assert all(a[1] == b[0] for a, b in zip(runs, runs[1:]))
        # a frame's spans fit the scratch, and unpacked into a dirty
        # one they are the whole vector's views, value for value
        nblk, nval, payload = _pack_host(rest)
        index_of, unpack_range, _ = _impl(
            "native" if native.available() else "numpy")
        index = index_of(payload, nblk, nval, Lr)
        room = np.full(nmb * layout.p_flat_mb(p_intra), 0x5555, np.int16)

        def views(spans):
            out, o = [], 0
            for l0, n, shape in spans:
                unpack_range(payload, nblk, nval, Lr, index, l0, l0 + n,
                             room[o:o + n])
                out.append(room[o:o + n].reshape(shape).copy())
                o += n
            assert o <= room.shape[0]
            return out

        il_ac, ic_ac = views(intra)
        np.testing.assert_array_equal(il_ac, want_intra[1])
        np.testing.assert_array_equal(ic_ac, want_intra[3])
        for i, spans in enumerate(frames):
            for got, want in zip(views(spans), want_planes[1:]):
                assert got.shape == want[i].shape
                np.testing.assert_array_equal(got, want[i])
        il_dc, ic_dc, modes = layout.split_dense_dc(dense, nmb, ships_modes)
        for got, want in zip((il_dc, ic_dc) + modes,
                             (want_intra[0], want_intra[2])
                             + want_intra[4:]):
            np.testing.assert_array_equal(got, want)
        assert len(modes) == (2 if ships_modes else 0)


# ---- collect_wave ----------------------------------------------------------

def _smooth_frames(n, w, h):
    yy, xx = np.mgrid[0:h, 0:w]
    return [Frame(
        y=((xx + yy + 5 * i) % 256).astype(np.uint8),
        u=np.full((h // 2, w // 2), 100 + i, np.uint8),
        v=np.full((h // 2, w // 2), 140 - i, np.uint8),
    ) for i in range(n)]


def _noise_frames(n, w, h):
    rng = np.random.default_rng(23)
    return [Frame(
        y=rng.integers(0, 256, (h, w), dtype=np.uint8),
        u=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8),
        v=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8),
    ) for _ in range(n)]


def _crossing_frames(n, w, h):
    return crossing.make_frames(6 * n, w, h, seed=3, pan=1,
                                sprites=12)[::6]


#: name -> (width, height, frames, make frames, encoder arguments): two
#: GOPs a wave, the second short (its tail repeats are not packed)
WAVES = {
    "library_odd_nmb": (80, 48, 7, _smooth_frames,
                        dict(qp=27, gop_frames=4, gops_per_wave=2)),
    "serving_rd": (64, 48, 7, _smooth_frames,
                   dict(qp=25, gop_frames=4, gops_per_wave=2,
                        rd=RdConfig(mode_decision=True, pskip=True,
                                    deblock=True,
                                    aq_q=aq_from_strength(1.0)))),
    "p_intra": (160, 96, 7, _crossing_frames,
                dict(qp=38, gop_frames=4, gops_per_wave=2,
                     rd=RdConfig(p_intra=True))),
}


def _whole_vector_collect(enc, pending) -> list[bytes]:
    """collect_wave's sparse path as the parent commit (e4cd35b) had
    it: each GOP's payload unpacked whole (here by numpy) into one
    array of the GOP's levels, the slices packed from views of it."""
    enc.start_fetch(pending)
    wave, ysd, _usd, _vsd, qpsd, mbw, mbh, out, fetch = pending
    assert fetch.sparse_ok
    F = ysd.shape[1]
    _L, Lr = enc._level_sizes(F, mbw * mbh)
    nblk, nval, _n_esc, used = fetch.tiny
    mv8, dc16 = enc._fetch_bulk(out[0:2])
    rows = enc._gather_payload_rows(fetch.payload)
    qps = np.asarray(qpsd)
    streams = []
    for gi, gop in enumerate(wave):
        rest = layout.unpack_compact_host(
            rows[gi][:int(used[gi])], int(nblk[gi]), int(nval[gi]), Lr)
        intra, planes = layout.unflatten_gop_parts(
            dc16[gi], rest, mv8[gi], F, mbw, mbh,
            ships_modes=enc.rd.ships_modes, p_intra=enc.rd.p_intra)
        streams.append(b"".join(pack_gop_slices_planes(
            intra, planes, gop.num_frames, mbw, mbh, enc.sps, enc.pps,
            int(qps[gi]), idr_pic_id=gop.index, rd=enc.rd)))
    return streams


def _grown(enc, before: dict) -> dict:
    after = enc.stages.snapshot()
    return {k: after[k] - before[k] for k in dispatch.STAGE_COUNTERS}


class TestCollectWave:
    @pytest.fixture(scope="class", params=sorted(WAVES))
    def wave(self, request):
        """(encoder, the dispatched wave's handle, the oracle's bytes,
        the cases' shared notes): collect_wave may be called on one
        handle again and again."""
        w, h, n, make, kwargs = WAVES[request.param]
        frames = make(n, w, h)
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc = GopShardEncoder(meta, mesh=default_mesh(jax.devices()[:1]),
                              **kwargs)
        (staged,) = enc.stage_waves(frames)
        pending = enc.dispatch_wave(staged)
        return enc, pending, _whole_vector_collect(enc, pending), {}

    @pytest.mark.parametrize("how", ["pool", "inline", "no_library"])
    def test_bytes_are_the_whole_vector_paths(self, wave, how,
                                              monkeypatch):
        enc, pending, want, notes = wave
        if not native.available():
            pytest.skip("no compiler")
        if how == "no_library":     # the slice packers go numpy too
            monkeypatch.setattr(native, "available", lambda: False)
        monkeypatch.setattr(enc, "pack_workers", 1 if how == "inline" else 4)
        monkeypatch.setattr(enc, "_pack_pool", enc._new_pack_pool())
        assert (enc._slice_pool() is None) == (how == "inline")
        before = enc.stages.snapshot()
        exported = obs_metrics.STAGE_COUNTER_TOTALS["unpack_ranges"]
        exported_before = exported.get()
        segs = enc.collect_wave(pending)
        assert [s.payload for s in segs] == want
        assert [s.gop.num_frames for s in segs] == [4, 3]
        grew = _grown(enc, before)
        assert grew["dense_fallback_waves"] == 0
        per_p = 6 if enc.rd.p_intra else 5
        ranges = 0 if how == "no_library" else sum(
            2 + (s.gop.num_frames - 1) * per_p for s in segs)
        assert grew["unpack_ranges"] == ranges
        assert exported.get() - exported_before == ranges
        # the kind channel is counted where it is unpacked: the same
        # macroblocks, the same kinds
        nmb = (enc.meta.width // 16) * (enc.meta.height // 16)
        coded = 5 * nmb if enc.rd.p_intra else 0
        assert grew["p_mbs_coded"] == coded
        assert (grew["p_mbs_intra"] > 0) == enc.rd.p_intra
        assert grew["p_mbs_intra"] == notes.setdefault(
            "p_mbs_intra", grew["p_mbs_intra"])

    def test_a_dense_wave_unpacks_no_range(self):
        frames = _noise_frames(4, 64, 48)
        meta = VideoMeta(width=64, height=48, num_frames=4)
        enc = GopShardEncoder(meta, qp=27, gop_frames=2,
                              mesh=default_mesh(jax.devices()[:1]))
        enc.encode(frames)
        snap = enc.stages.snapshot()
        assert snap["dense_fallback_waves"] == 2
        assert snap["unpack_ranges"] == 0

    @pytest.mark.parametrize("library", [True, False],
                             ids=["ranged", "whole_vector"])
    def test_no_allocation_of_a_gops_length(self, library, monkeypatch):
        """The peak of traced memory during a sparse collect stays
        under one GOP's levels where the payload is unpacked in ranges
        (one frame-sized scratch and one slice's output buffer at a
        time, inline) — and does not where it is unpacked whole, as the
        parent did and a host without the library does."""
        if not native.available():
            pytest.skip("no compiler")
        w, h, n = 128, 96, 16
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc = GopShardEncoder(meta, qp=27, gop_frames=n, pack_workers=1,
                              mesh=default_mesh(jax.devices()[:1]))
        (staged,) = enc.stage_waves(_smooth_frames(n, w, h))
        pending = enc.dispatch_wave(staged)
        enc.start_fetch(pending)
        assert pending[-1].sparse_ok
        if not library:
            monkeypatch.setattr(native, "available", lambda: False)
        _L, Lr = enc._level_sizes(n, (w // 16) * (h // 16))
        tracemalloc.start()
        try:
            base, _ = tracemalloc.get_traced_memory()
            tracemalloc.reset_peak()
            (seg,) = enc.collect_wave(pending)
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert len(seg.frame_sizes) == n
        gop_bytes = 2 * Lr
        if library:
            assert peak - base < gop_bytes // 2
        else:
            assert peak - base >= gop_bytes
