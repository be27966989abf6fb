"""Multi-device GOP sharding tests on the 8-device virtual CPU mesh.

These are the "fake cluster" tests (SURVEY.md §4): `shard_map` over a real
`jax.sharding.Mesh` of 8 virtual CPU devices, asserting the sharded encode
is bit-identical to the single-device path.
"""

import numpy as np
import pytest

import jax

from thinvids_tpu.core.types import Frame, VideoMeta, concat_segments
from thinvids_tpu.codecs.h264.encoder import H264Encoder
from thinvids_tpu.parallel.dispatch import (
    STAGE_COUNTERS,
    GopShardEncoder,
    default_mesh,
    encode_clip_sharded,
    stage_snapshot,
)
from thinvids_tpu.parallel.planner import plan_segments


def _make_frames(n, w=64, h=48, seed=0):
    rng = np.random.default_rng(seed)
    frames = []
    for _ in range(n):
        frames.append(Frame(
            y=rng.integers(0, 256, (h, w), dtype=np.uint8),
            u=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8),
            v=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8),
        ))
    return frames


def _reference_stream(frames, meta, qp, gop_frames, num_devices,
                      max_segments=200):
    """Single-device encode emitting SPS/PPS at every GOP head, matching
    the sharded layout (idr_pic_id = global frame index)."""
    plan = plan_segments(len(frames), gop_frames, num_devices, max_segments)
    enc = H264Encoder(meta, qp=qp, use_jax=False)
    out = []
    for gop in plan.gops:
        for fi, i in enumerate(range(gop.start_frame, gop.end_frame)):
            out.append(enc.encode_frame(frames[i], idr_pic_id=i,
                                        with_headers=(fi == 0)))
    return b"".join(out)


class TestPlanner:
    def test_covers_every_frame_once(self):
        plan = plan_segments(100, 10, 8)
        assert plan.gops[0].start_frame == 0
        for a, b in zip(plan.gops, plan.gops[1:]):
            assert b.start_frame == a.end_frame
        assert plan.gops[-1].end_frame == 100

    def test_rounds_up_to_device_multiple(self):
        plan = plan_segments(320, 32, 8)
        # ceil(320/32)=10 -> rounded to 16 (multiple of 8)
        assert plan.num_gops == 16
        assert plan.waves == 2

    def test_no_rounding_when_gops_would_be_empty(self):
        # 5 frames over 8 devices: rounding to 8 would need >= 8 frames.
        plan = plan_segments(5, 2, 8)
        assert plan.num_gops <= 5
        assert all(g.num_frames >= 1 for g in plan.gops)

    def test_max_segments_cap(self):
        plan = plan_segments(10_000, 1, 8, max_segments=200)
        assert plan.num_gops == 200

    def test_n_capped_by_num_frames(self):
        plan = plan_segments(3, 1, 8)
        assert plan.num_gops == 3
        assert [g.num_frames for g in plan.gops] == [1, 1, 1]

    def test_remainder_distribution(self):
        plan = plan_segments(10, 3, 4)
        sizes = [g.num_frames for g in plan.gops]
        assert sum(sizes) == 10
        assert max(sizes) - min(sizes) <= 1

    def test_invalid_args(self):
        with pytest.raises(ValueError):
            plan_segments(0, 8, 8)
        with pytest.raises(ValueError):
            plan_segments(10, 0, 8)
        with pytest.raises(ValueError):
            plan_segments(10, 8, 0)


class TestShardedDispatch:
    def test_mesh_has_8_virtual_devices(self):
        assert len(jax.devices()) == 8

    def test_sparse_pack_roundtrip(self):
        from thinvids_tpu.codecs.h264 import jaxcore
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        L = 3840
        flat = np.zeros(L, np.int32)
        nz = rng.choice(L, size=L // 8, replace=False)
        flat[nz] = rng.integers(-127, 128, size=L // 8)
        flat[nz[0]] = 0   # make one chosen slot zero again
        flat[nz[1]] = 400     # escape: exceeds int8
        flat[nz[2]] = -1900   # escape, negative
        nnz, n_esc, bitmap, vals, esc_pos, esc_val = jax.device_get(
            jaxcore._sparse_pack(jnp.asarray(flat)))
        assert int(n_esc) == 2
        assert jaxcore.sparse_fits(nnz, n_esc, L)
        out = jaxcore._sparse_unpack(int(nnz), int(n_esc), bitmap, vals,
                                     esc_pos, esc_val, L)
        np.testing.assert_array_equal(out, flat)


class TestShardedInterDispatch:
    """Sharded GOP (IDR + P) coding across the virtual mesh."""

    def test_sharded_gop_matches_single_device_encode_gop(self):
        from thinvids_tpu.codecs.h264.encoder import encode_gop

        frames = _make_frames(16, seed=11)
        meta = VideoMeta(width=64, height=48, num_frames=16)
        got = encode_clip_sharded(frames, meta, qp=27, gop_frames=2)
        plan = plan_segments(16, 2, len(jax.devices()))
        parts = []
        for gop in plan.gops:
            parts.append(encode_gop(
                frames[gop.start_frame:gop.end_frame], meta, qp=27,
                idr_pic_id=gop.index))
        assert got == b"".join(parts)

    @pytest.mark.parametrize("max_segments,gop_lengths", [
        (200, [2, 2, 1, 1, 1, 1, 1, 1]),    # tail repeats to F = 2
        (6, [2, 2, 2, 2, 1, 1]),            # and two pad GOPs to 8
    ], ids=["tail_repeats", "pad_gops"])
    def test_uneven_wave_matches_single_device(self, max_segments,
                                               gop_lengths):
        # 10 frames, GOP 3 on 8 devices: GOPs of unequal length share
        # one static F, and a wave of fewer GOPs than devices pads to
        # the mesh; neither the repeats nor the pad GOPs are emitted.
        from thinvids_tpu.codecs.h264.encoder import encode_gop

        frames = _make_frames(10, seed=3)
        meta = VideoMeta(width=64, height=48, num_frames=10)
        enc = GopShardEncoder(meta, qp=30, gop_frames=3,
                              max_segments=max_segments)
        segments = enc.encode(frames)
        assert [s.gop.num_frames for s in segments] == gop_lengths
        assert [len(s.frame_sizes) for s in segments] == gop_lengths
        want = [encode_gop(frames[s.gop.start_frame:s.gop.end_frame], meta,
                           qp=30, idr_pic_id=s.gop.index)
                for s in segments]
        assert [s.payload for s in segments] == want

    def test_low_qp_stays_on_sparse_path(self):
        """Saturated chroma drives intra chroma DC past int8 at QP <= 20
        (measured: |level| up to ~250 at QP 15); with BOTH hadamard DC
        segments shipping dense, a low-QP encode must keep the sparse
        transfer — no wave takes the wave-wide dense fallback, which
        proves the trap is closed — and stay bit-identical to the
        reference."""
        from thinvids_tpu.codecs.h264.encoder import encode_gop
        # smooth luma (sparse residuals fit the block budget even at low
        # QP) + saturated chroma (its hadamard DC escapes int8)
        w, h, n = 64, 48, 8
        yy, xx = np.mgrid[0:h, 0:w]
        frames = [Frame(
            y=np.clip(xx // 4 * 2 + 60 + 2 * i, 0, 255).astype(np.uint8),
            u=np.full((h // 2, w // 2), 235, np.uint8),
            v=np.full((h // 2, w // 2), 20, np.uint8),
        ) for i in range(n)]
        meta = VideoMeta(width=w, height=h, num_frames=n)

        # the trap must actually be armed: intra chroma DC escapes int8
        from thinvids_tpu.codecs.h264 import jaxinter
        import jax.numpy as jnp

        nmb = (w // 16) * (h // 16)
        _mv, flat = jaxinter.encode_gop_planes(
            jnp.asarray(np.stack([f.y for f in frames[:2]])),
            jnp.asarray(np.stack([f.u for f in frames[:2]])),
            jnp.asarray(np.stack([f.v for f in frames[:2]])),
            jnp.asarray(15), mbw=w // 16, mbh=h // 16)
        cdc = np.asarray(flat)[nmb * 256:nmb * 264]
        assert np.abs(cdc).max() > 127
        enc = GopShardEncoder(meta, qp=15, gop_frames=2)
        got = concat_segments(enc.encode(frames))
        assert enc.stages.snapshot()["dense_fallback_waves"] == 0
        plan = plan_segments(n, 2, len(jax.devices()))
        parts = [encode_gop(frames[g.start_frame:g.end_frame], meta,
                            qp=15, idr_pic_id=g.index)
                 for g in plan.gops]
        assert got == b"".join(parts)

    def test_block_sparse2_roundtrip(self):
        # two-tier device pack <-> host unpack over clustered content
        # and a non-multiple-of-16 length
        from thinvids_tpu.codecs.h264 import jaxcore
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        L = 16 * 1000 + 8
        flat = np.zeros(L, np.int32)
        # residual-like content: nonzeros cluster in a few blocks
        # (uniform scatter would blow the block budget by design)
        hot_blocks = rng.choice(200, 120, replace=False)
        for b in hot_blocks:
            lanes = rng.choice(16, rng.integers(1, 6), replace=False)
            flat[b * 16 + lanes] = rng.integers(-120, 121, len(lanes))
        out = jaxcore._block_sparse_pack2(jnp.asarray(flat))
        nblk, nval, n_esc, bitmap, bmask16, vals = \
            [np.asarray(x) for x in out]
        assert jaxcore.block_sparse2_fits(nblk, nval, n_esc, L)
        back = jaxcore._block_sparse_unpack2(
            int(nblk), int(nval), bitmap, bmask16, vals, L)
        np.testing.assert_array_equal(back, flat.astype(np.int16))

    def test_block_sparse2_escape_forces_dense(self):
        # |level| > 127 has no escape side-channel anymore: the pack
        # reports a count and the caller must take the dense fallback
        from thinvids_tpu.codecs.h264 import jaxcore
        import jax.numpy as jnp

        L = 16 * 64
        flat = np.zeros(L, np.int32)
        flat[3] = 300
        nblk, nval, n_esc, *_ = [
            np.asarray(x) for x in
            jaxcore._block_sparse_pack2(jnp.asarray(flat))]
        assert int(n_esc) == 1
        assert not jaxcore.block_sparse2_fits(nblk, nval, n_esc, L)

    def test_sharded_gop_odd_mb_count(self):
        # 80x48 -> 5x3 = 15 MBs (odd): the GOP flat level vector length
        # is then not a multiple of the 16-coeff sparse block, which the
        # block-granular transfer pack must pad (regression: reshape
        # crash in the block-sparse pack for any odd-mb resolution).
        from thinvids_tpu.codecs.h264.encoder import encode_gop

        n, w, h = 8, 80, 48
        frames = _make_frames(n, w=w, h=h, seed=3)
        meta = VideoMeta(width=w, height=h, num_frames=n)
        got = encode_clip_sharded(frames, meta, qp=27, gop_frames=4)
        plan = plan_segments(n, 4, len(jax.devices()))
        parts = [encode_gop(frames[g.start_frame:g.end_frame], meta,
                            qp=27, idr_pic_id=g.index)
                 for g in plan.gops]
        assert got == b"".join(parts)

    def test_sharded_gop_oracle_bit_exact(self):
        from thinvids_tpu.tools import oracle

        if not oracle.oracle_available():
            pytest.skip("libavcodec missing")
        # Low-motion clip: decode the full sharded stream with libavcodec
        # and check frame count + that P frames made it smaller.
        n = 64
        meta = VideoMeta(width=64, height=48, num_frames=n)
        yy, xx = np.mgrid[0:48, 0:64]
        frames = [Frame(
            y=(((xx + 2 * i) % 256)).astype(np.uint8),
            u=np.full((24, 32), 90, np.uint8),
            v=np.full((24, 32), 160, np.uint8),
        ) for i in range(n)]
        inter_stream = encode_clip_sharded(frames, meta, qp=27, gop_frames=8)
        intra_stream = _reference_stream(frames, meta, 27, 8,
                                         len(jax.devices()))
        decoded = oracle.decode_h264(inter_stream)
        assert len(decoded) == n
        # IDR cost dominates on this cheap-intra clip: 8-frame GOPs cap
        # the win well below the gop ratio (the >=3x bar on realistic
        # content is asserted in test_inter.py).
        assert len(inter_stream) < len(intra_stream) / 1.7


def _pack2_reference(flat, budget_div, val_div):
    """The documented transfer format of jaxcore._block_sparse_pack2,
    written out in numpy with boolean indexing only (no positions, no
    scatter): what the device function must return, array for array."""
    L = flat.shape[0]
    NB = -(-L // 16)
    budget, vbudget = NB // budget_div, L // val_div
    blocks = np.zeros(NB * 16, np.int16)
    blocks[:L] = flat
    blocks = blocks.reshape(NB, 16)
    bmask = blocks.any(axis=1)
    kept = blocks[bmask][:budget]          # overflow keeps the prefix
    gathered = np.zeros((budget, 16), np.int16)
    gathered[:kept.shape[0]] = kept
    emask = gathered != 0
    stream = np.clip(gathered[emask], -127, 127)[:vbudget]
    vals = np.zeros(vbudget, np.int8)
    vals[:stream.shape[0]] = stream
    return (np.int32(bmask.sum()), np.int32(emask.sum()),
            np.int32((np.abs(gathered.astype(np.int32)) > 127).sum()),
            np.packbits(bmask),
            (emask << np.arange(16)).sum(axis=1).astype(np.uint16),
            vals)


#: case -> (length, nonzero blocks, most lanes set in one, budget_div,
#: val_div): the wave path's divisors and `_sfe_pack_band`'s unit ones
PACK2_CASES = {
    "gop_divisors": (16 * 1000, 120, 5, 4, 24),
    "unit_divisors": (16 * 500, 200, 16, 1, 1),
    "odd_length": (16 * 1000 + 8, 120, 5, 4, 24),
    "odd_length_unit": (16 * 333 + 5, 150, 16, 1, 1),
    "all_zero": (16 * 64 + 3, 0, 1, 4, 24),
    # 200 blocks of the 250 allowed, half full: far over 666 values
    "nval_over_vbudget": (16 * 1000, 200, 16, 4, 24),
    "nblk_over_budget": (16 * 1000, 600, 2, 4, 24),
    "one_escape": (16 * 64, 10, 4, 4, 24),
}


def _pack2_levels(case):
    """(flat int32 levels, budget_div, val_div) of one named case:
    nonzeros clustered in a few blocks, like residuals."""
    L, hot, max_lanes, budget_div, val_div = PACK2_CASES[case]
    rng = np.random.default_rng(25)
    flat = np.zeros(L, np.int32)
    for b in rng.choice(L // 16, hot, replace=False):
        lanes = rng.choice(16, rng.integers(1, max_lanes + 1),
                           replace=False)
        flat[b * 16 + lanes] = rng.integers(1, 121, len(lanes)) \
            * rng.choice([-1, 1], len(lanes))
    if case == "one_escape":
        flat[3] = 300
    return flat, budget_div, val_div


@pytest.mark.parametrize("case", sorted(PACK2_CASES))
def test_block_sparse_pack2_matches_the_documented_format(case):
    from thinvids_tpu.codecs.h264 import jaxcore
    import jax.numpy as jnp

    flat, budget_div, val_div = _pack2_levels(case)
    want = _pack2_reference(flat, budget_div, val_div)
    got = [np.asarray(x) for x in jaxcore._block_sparse_pack2(
        jnp.asarray(flat), budget_div, val_div)]
    names = ("nblk", "nval", "n_esc", "bitmap", "bmask16", "vals")
    for name, w, g in zip(names, want, got):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g, w, err_msg=name)
    L = flat.shape[0]
    NB = -(-L // 16)
    nblk, nval, n_esc = (int(x) for x in got[:3])
    # the cases are what their names say
    assert (nblk > NB // budget_div) == (case == "nblk_over_budget")
    assert (nval > L // val_div) == (case == "nval_over_vbudget")
    assert n_esc == (case == "one_escape")
    if jaxcore.block_sparse2_fits(nblk, nval, n_esc, L, budget_div,
                                  val_div):
        back = jaxcore._block_sparse_unpack2(nblk, nval, *got[3:], L)
        np.testing.assert_array_equal(back, flat.astype(np.int16))


class TestHostPipeline:
    """Stage-profiled wave pipeline: slice-granular threaded pack, the
    zero-copy int16 unflatten, native sparse unpack, per-GOP QP, and
    the config knobs that size it all."""

    def test_wave_honors_per_gop_qp(self):
        # gop_qp overrides (rate control) reach the device AND the
        # slice headers: every slice of a GOP states its GOP's QP
        # against the PPS base, and the pictures are those of a
        # single-device encode of that GOP at that QP.
        from thinvids_tpu.codecs.h264.decoder import decode_annexb
        from thinvids_tpu.codecs.h264.encoder import encode_gop
        from thinvids_tpu.codecs.h264.headers import (NAL_PPS, NAL_SPS,
                                                      PPS, SPS,
                                                      SliceHeader)
        from thinvids_tpu.io.bits import BitReader, split_annexb

        frames = _make_frames(8, seed=21)
        meta = VideoMeta(width=64, height=48, num_frames=8)
        enc = GopShardEncoder(meta, qp=27, gop_frames=2)
        plan = enc.plan(len(frames))
        qp_map = {g.index: 27 + 3 * (g.index % 3) for g in plan.gops}
        enc.gop_qp = dict(qp_map)
        segments = enc.encode(frames)
        assert len(segments) == plan.num_gops
        for seg in segments:
            qp = qp_map[seg.gop.index]
            slice_qps = []
            for ri, t, rbsp in split_annexb(seg.payload):
                if t == NAL_SPS:
                    sps = SPS.parse_rbsp(rbsp)
                elif t == NAL_PPS:
                    pps = PPS.parse_rbsp(rbsp)
                elif t in (1, 5):
                    slice_qps.append(SliceHeader.parse(
                        BitReader(rbsp), sps, pps, t, ri).qp)
            assert pps.init_qp == 27
            assert slice_qps == [qp] * seg.gop.num_frames
            plain = encode_gop(
                frames[seg.gop.start_frame:seg.gop.end_frame], meta, qp=qp,
                idr_pic_id=seg.gop.index)
            for got, want in zip(decode_annexb(seg.payload).frames,
                                 decode_annexb(plain).frames, strict=True):
                for plane in "yuv":
                    np.testing.assert_array_equal(getattr(got, plane),
                                                  getattr(want, plane))

    def test_threaded_pack_and_int16_paths_bit_identical(self, monkeypatch):
        # Parity matrix over the new pack path: slice pool off/on,
        # native packer vs pure-Python fallback, sparse transfer vs the
        # forced dense (int16 full-layout -> cavlc_pack_islice16) branch.
        frames = _make_frames(12, seed=9)
        meta = VideoMeta(width=64, height=48, num_frames=12)

        def stream(pack_workers):
            enc = GopShardEncoder(meta, qp=27, gop_frames=3,
                                  pack_workers=pack_workers)
            return concat_segments(enc.encode(frames))

        base = stream(1)
        assert stream(8) == base

        from thinvids_tpu import native as native_mod

        monkeypatch.setattr(native_mod, "available", lambda: False)
        assert stream(8) == base
        monkeypatch.undo()

        from thinvids_tpu.codecs.h264 import jaxcore

        monkeypatch.setattr(jaxcore, "block_sparse2_fits",
                            lambda *a, **k: False)
        assert stream(8) == base
        assert stream(1) == base

    def test_native_sparse_unpack_matches_python(self):
        from thinvids_tpu import native as native_mod
        from thinvids_tpu.codecs.h264 import jaxcore
        import jax.numpy as jnp

        if not native_mod.available():
            pytest.skip("no compiler")
        rng = np.random.default_rng(17)
        L = 16 * 777 + 8                  # non-multiple-of-16 tail
        flat = np.zeros(L, np.int32)
        hot = rng.choice(150, 90, replace=False)
        for b in hot:
            lanes = rng.choice(16, rng.integers(1, 7), replace=False)
            flat[b * 16 + lanes] = rng.integers(-120, 121, len(lanes))
        nblk, nval, n_esc, bitmap, bmask16, vals = [
            np.asarray(x) for x in
            jaxcore._block_sparse_pack2(jnp.asarray(flat))]
        assert jaxcore.block_sparse2_fits(nblk, nval, n_esc, L)
        want = jaxcore._block_sparse_unpack2(
            int(nblk), int(nval), bitmap, bmask16, vals, L)
        got = native_mod.block_sparse_unpack2(
            int(nblk), int(nval), bitmap, bmask16, vals, L)
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.int16
        # corrupt counts must raise, not mis-scatter
        with pytest.raises(ValueError, match="inconsistent"):
            native_mod.block_sparse_unpack2(
                int(nblk), int(nval) + 1, bitmap, bmask16, vals, L)
        # a stray set bit AFTER the nblk-th live block (bitmap/count
        # disagreement the other way) must raise too, not decode the
        # block as silent zeros
        NB = -(-L // 16)
        bad_bitmap = bitmap.copy()
        bad_bitmap[(NB - 1) // 8] |= 0x80 >> ((NB - 1) % 8)
        with pytest.raises(ValueError, match="inconsistent"):
            native_mod.block_sparse_unpack2(
                int(nblk), int(nval), bad_bitmap, bmask16, vals, L)

    def test_pack_pool_shuts_down_with_encoder(self):
        import gc

        meta = VideoMeta(width=64, height=48, num_frames=4)
        enc = GopShardEncoder(meta, qp=27, pack_workers=2)
        pool = enc._slice_pool()
        assert pool is not None and enc._slice_pool() is pool
        del enc
        gc.collect()
        assert pool._shutdown      # finalizer retired the pack threads

    def test_stage_profile_records_every_stage(self):
        from thinvids_tpu.parallel import dispatch as dispatch_mod

        frames = _make_frames(8, seed=2)
        meta = VideoMeta(width=64, height=48, num_frames=8)
        enc = GopShardEncoder(meta, qp=27, gop_frames=2)
        concat_segments(enc.encode(frames))
        snap = enc.stages.snapshot()
        for key in dispatch_mod.STAGE_NAMES:
            assert key in snap
        assert snap["waves"] >= 1
        assert snap["pack"] > 0
        assert snap["dispatch"] > 0
        # the process-wide aggregate (the /metrics_snapshot exporter)
        # includes this live encoder
        agg = dispatch_mod.stage_snapshot()
        assert set(dispatch_mod.STAGE_NAMES) <= set(agg)
        assert agg["pack"] >= snap["pack"]

    @pytest.fixture(scope="class")
    def counted_encode(self):
        """(the encoder's snapshot, the process-wide one) after ONE
        encode, shared by the counter cases below."""
        meta = VideoMeta(width=64, height=48, num_frames=8)
        enc = GopShardEncoder(meta, qp=27, gop_frames=2)
        concat_segments(enc.encode(_make_frames(8, seed=2)))
        return enc.stages.snapshot(), stage_snapshot()

    @pytest.mark.parametrize("counter", STAGE_COUNTERS)
    def test_stage_counters_ride_both_snapshots(self, counted_encode,
                                                counter):
        """Each counter /metrics_snapshot exports (the benchmark reads
        the byte, fetch-shard and dense-fallback ones) is in the
        encoder's snapshot and, at least as large, in the process-wide
        one; an encode moves bytes both ways across the boundary."""
        snap, agg = counted_encode
        assert isinstance(snap[counter], int) and snap[counter] >= 0
        assert agg[counter] >= snap[counter]
        if counter in ("h2d_bytes", "d2h_bytes"):
            assert snap[counter] > 0

    def test_pack_knobs_read_from_config_env(self, monkeypatch):
        from thinvids_tpu.core.config import invalidate_settings_cache

        monkeypatch.setenv("TVT_PACK_WORKERS", "3")
        monkeypatch.setenv("TVT_PIPELINE_WINDOW", "7")
        invalidate_settings_cache()
        try:
            meta = VideoMeta(width=64, height=48, num_frames=4)
            enc = GopShardEncoder(meta, qp=27)
            assert enc.pack_workers == 3
            assert enc.pipeline_window == 7
            # explicit constructor args beat the config tier
            enc2 = GopShardEncoder(meta, qp=27, pack_workers=2,
                                   pipeline_window=5)
            assert enc2.pack_workers == 2
            assert enc2.pipeline_window == 5
        finally:
            monkeypatch.delenv("TVT_PACK_WORKERS")
            monkeypatch.delenv("TVT_PIPELINE_WINDOW")
            invalidate_settings_cache()

    def test_pack_gop_slices_planes_matches_thunk_path(self):
        # pack_gop_slices_planes is the serial/pooled convenience entry
        # over the same thunks collect_wave submits; pin them together
        # so the wrapper cannot drift from the live path.
        import concurrent.futures as cf

        import jax.numpy as jnp

        from thinvids_tpu.codecs.h264 import jaxinter
        from thinvids_tpu.codecs.h264.encoder import (
            gop_slice_thunks_planes, pack_gop_slices_planes)
        from thinvids_tpu.codecs.h264.headers import PPS, SPS
        from thinvids_tpu.codecs.h264.layout import unflatten_gop

        w, h, n = 64, 48, 4
        frames = _make_frames(n, seed=5)
        ys = jnp.asarray(np.stack([f.y for f in frames]))
        us = jnp.asarray(np.stack([f.u for f in frames]))
        vs = jnp.asarray(np.stack([f.v for f in frames]))
        mv8, flat = jaxinter.encode_gop_planes(ys, us, vs, jnp.asarray(27),
                                               mbw=4, mbh=3)
        intra, planes = unflatten_gop(np.asarray(flat), np.asarray(mv8),
                                      n, 4, 3)
        sps, pps = SPS(width=w, height=h), PPS(init_qp=27)
        serial = pack_gop_slices_planes(intra, planes, n, 4, 3, sps, pps,
                                        27, idr_pic_id=0)
        thunks = gop_slice_thunks_planes(intra, planes, n, 4, 3, sps, pps,
                                         27, idr_pic_id=0)
        assert serial == [t() for t in thunks]
        with cf.ThreadPoolExecutor(4) as pool:
            pooled = pack_gop_slices_planes(intra, planes, n, 4, 3, sps,
                                            pps, 27, idr_pic_id=0,
                                            pool=pool)
        assert pooled == serial


# ---------------------------------------------------------------------------
# the wave pipeline's unit and order (PR 29)
# ---------------------------------------------------------------------------


class _RecordingGopEncoder(GopShardEncoder):
    """Logs (event, first GOP index of the wave, thread) for every
    dispatch and every return of the fetch-start step, and keeps the
    staged shapes it was handed."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.log: list[tuple] = []
        self.shapes: set = set()

    def _note(self, what: str, wave) -> None:
        import threading

        self.log.append((what, wave[0].index,
                         threading.current_thread().name))

    def dispatch_wave(self, staged):
        self._note("dispatch", staged[0])
        self.shapes.add(tuple(staged[1].shape))
        return super().dispatch_wave(staged)

    def start_fetch(self, pending):
        super().start_fetch(pending)
        # logged on return: the step has run by now, whoever ran it
        self._note("fetch_started", pending[0])


def _smooth_frames(n, w=64, h=48):
    """Content the sparse budgets hold (noise overflows them and takes
    the dense fallback, which enqueues no payload slice)."""
    yy, xx = np.mgrid[0:h, 0:w]
    return [Frame(
        y=((xx * 2 + yy + 7 * i) % 256).astype(np.uint8),
        u=np.full((h // 2, w // 2), 108, np.uint8),
        v=np.full((h // 2, w // 2), 148, np.uint8)) for i in range(n)]


def _first(log, what, index, thread=None):
    for pos, (w, i, t) in enumerate(log):
        if w == what and i == index and thread in (None, t):
            return pos
    raise AssertionError(f"no {what} of wave {index} in {log}")


class TestWavePipeline:
    def _one_device(self):
        return default_mesh(jax.devices()[:1])

    def test_default_wave_is_one_gop_per_device(self):
        meta = VideoMeta(width=64, height=48, num_frames=16)
        enc = GopShardEncoder(meta, qp=27, gop_frames=2,
                              mesh=self._one_device())
        assert enc.gops_per_wave == 1
        frames = _make_frames(16, seed=5)
        waves = [wave for wave, *_ in enc.stage_waves(frames)]
        assert [len(w) for w in waves] == [1] * 8
        # on the 8-device mesh a wave is one GOP on each device
        enc8 = GopShardEncoder(meta, qp=27, gop_frames=2)
        assert [len(wave) for wave, *_ in enc8.stage_waves(frames)] == [8]
        # and the stage snapshot counts them
        enc.encode(frames)
        assert enc.stages.snapshot()["waves"] == 8

    def test_encode_waves_starts_fetch_before_next_dispatch(self):
        """For every wave n the dispatch loop has wave n's fetch
        started (counts in, payload slice enqueued) before it enqueues
        wave n+1's program — on the dispatching thread itself, not by
        the luck of a collector thread."""
        import threading

        meta = VideoMeta(width=64, height=48, num_frames=20)
        enc = _RecordingGopEncoder(meta, qp=27, gop_frames=4,
                                   mesh=self._one_device())
        frames = _smooth_frames(20)
        segs = enc.encode_waves(enc.stage_waves(frames))
        assert len(segs) == 5
        me = threading.current_thread().name
        for n in range(4):
            assert _first(enc.log, "fetch_started", n, me) \
                < _first(enc.log, "dispatch", n + 1, me), enc.log
        # a started fetch is not started twice: the collector thread's
        # own call finds the step done and the counts unchanged
        snap = enc.stages.snapshot()
        assert snap["waves"] == 5 and snap["dense_fallback_waves"] == 0

    def test_start_fetch_is_idempotent_and_optional(self):
        meta = VideoMeta(width=64, height=48, num_frames=4)
        frames = _smooth_frames(4)

        def run(calls: int) -> tuple[bytes, dict]:
            enc = GopShardEncoder(meta, qp=27, gop_frames=4,
                                  mesh=self._one_device())
            (staged,) = list(enc.stage_waves(frames))
            handle = enc.dispatch_wave(staged)
            for _ in range(calls):
                enc.start_fetch(handle)
            out = concat_segments(enc.collect_wave(handle))
            snap = enc.stages.snapshot()
            assert snap["dense_fallback_waves"] == 0
            return out, snap

        base, snap0 = run(0)        # collect_wave performs the step
        for calls in (1, 3):
            out, snap = run(calls)
            assert out == base
            assert snap["d2h_bytes"] == snap0["d2h_bytes"]

    @pytest.mark.parametrize("gops_per_wave", [1, 2, 4])
    def test_uneven_gops_byte_identical_at_any_wave_size(self,
                                                         gops_per_wave):
        """A plan with GOPs of `base` and `base + 1` frames encodes to
        the single-device reference GOP for GOP however the GOPs are
        grouped into waves (closed GOPs; the padded frame is dropped)."""
        from thinvids_tpu.codecs.h264.encoder import encode_gop

        n = 13
        frames = _make_frames(n, seed=8)
        meta = VideoMeta(width=64, height=48, num_frames=n)
        enc = GopShardEncoder(meta, qp=27, gop_frames=4,
                              mesh=self._one_device(),
                              gops_per_wave=gops_per_wave)
        plan = enc.plan(n)
        assert sorted({g.num_frames for g in plan.gops}) == [3, 4]
        segs = enc.encode(frames)
        assert [s.gop.num_frames for s in segs] \
            == [g.num_frames for g in plan.gops]
        for seg, gop in zip(segs, plan.gops):
            assert seg.payload == encode_gop(
                frames[gop.start_frame:gop.end_frame], meta, qp=27,
                idr_pic_id=gop.index), f"GOP {gop.index}"

    def test_uneven_gops_dispatch_one_program_shape(self):
        """F is the plan's longest GOP, not the wave's: one-GOP waves
        of a clip with 3- and 4-frame GOPs all dispatch (1, 4, H, W)."""
        n = 13
        meta = VideoMeta(width=64, height=48, num_frames=n)
        enc = _RecordingGopEncoder(meta, qp=27, gop_frames=4,
                                   mesh=self._one_device())
        enc.encode(_make_frames(n, seed=8))
        assert enc.shapes == {(1, 4, 48, 64)}
        # the analysis pass stages the same static F
        assert {tuple(ys.shape) for _w, ys in enc.stage_luma_waves(
            _make_frames(n, seed=8))} == {(1, 4, 48, 64)}

    def test_sfe_dispatch_order_unchanged(self):
        """The split-frame encoder's start_fetch is empty, so its
        encode_waves still dispatches `window` GOPs ahead: wave 1 is
        dispatched while wave 0's collect has not returned."""
        import threading

        from thinvids_tpu.parallel.dispatch import SfeShardEncoder

        dispatched_1 = threading.Event()
        seen: dict = {}

        class Rec(SfeShardEncoder):
            def dispatch_wave(self, staged):
                out = super().dispatch_wave(staged)
                if staged[0].index == 1:
                    dispatched_1.set()
                return out

            def collect_wave(self, pending):
                if pending[0].index == 0:
                    seen["d1_before_c0_returns"] = dispatched_1.wait(60)
                return super().collect_wave(pending)

        w, h, n = 64, 64, 6
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc = Rec(meta, qp=27, gop_frames=2, bands=2)
        frames = _make_frames(n, w=w, h=h, seed=9)
        handle = enc.dispatch_wave(next(iter(enc.stage_waves(frames))))
        assert enc.start_fetch(handle) is None      # nothing to start
        dispatched_1.clear()
        segs = enc.encode_waves(enc.stage_waves(frames), window=2)
        assert len(segs) == 3
        assert seen["d1_before_c0_returns"]
