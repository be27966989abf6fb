"""Compact device→host level-stream transfer (ISSUE 4).

Covers the three layers of the boundary rework: the device-side payload
compaction (jaxcore._compact_stream + the native/numpy unpack parity),
bit-identity of the compact transfer against the validated sparse2 path
(including the escape-heavy dense-fallback edge), the per-shard
concurrent fetch on the 8-device virtual mesh, the process pack
sidecars (pack_backend=process), the stage-honesty accounting
(dense_retry / dense_fallback_waves / d2h_bytes), and the sync
confinement that keeps blocking `jax.device_get` off the hot path for
good (now enforced tree-wide by `cli.py check`; the test here asserts
the analyzer manifest still encodes this file's contract).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu.codecs.h264 import jaxcore, layout
from thinvids_tpu.core.types import Frame, VideoMeta, concat_segments
from thinvids_tpu.parallel.dispatch import GopShardEncoder


def _smooth_frames(n, w=64, h=48):
    """Pan-style content that stays inside every sparse budget."""
    yy, xx = np.mgrid[0:h, 0:w]
    return [Frame(
        y=((xx + yy + 5 * i) % 256).astype(np.uint8),
        u=np.full((h // 2, w // 2), 100 + i, np.uint8),
        v=np.full((h // 2, w // 2), 140 - i, np.uint8),
    ) for i in range(n)]


def _noise_frames(n, w=64, h=48, seed=0):
    """iid noise: blows the block budget, forcing the dense fallback."""
    rng = np.random.default_rng(seed)
    return [Frame(
        y=rng.integers(0, 256, (h, w), dtype=np.uint8),
        u=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8),
        v=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8),
    ) for _ in range(n)]


def _pack_compact(flat):
    """flat int levels → (nblk, nval, n_esc, used, payload) as numpy."""
    nblk, nval, n_esc, bitmap, bmask16, vals = [
        np.asarray(x) for x in jaxcore._block_sparse_pack2(
            jnp.asarray(flat))]
    used, payload = [np.asarray(x) for x in jaxcore._compact_stream(
        *[jnp.asarray(v) for v in (nblk, nval, bitmap, bmask16, vals)])]
    return (int(nblk), int(nval), int(n_esc), int(used), payload,
            (bitmap, bmask16, vals))


class TestCompactStream:
    def test_roundtrip_across_sparsity_levels(self):
        # from near-empty to just under the value budget (L // 24),
        # clustered like residuals so the block budget holds
        rng = np.random.default_rng(11)
        L = 16 * 600 + 8                   # non-multiple-of-16 tail
        for hot_blocks, max_lanes in ((3, 2), (60, 3), (140, 3)):
            flat = np.zeros(L, np.int32)
            for b in rng.choice(300, hot_blocks, replace=False):
                lanes = rng.choice(16, rng.integers(1, max_lanes + 1),
                                   replace=False)
                flat[b * 16 + lanes] = rng.integers(-120, 121, len(lanes))
            nblk, nval, n_esc, used, payload, _ = _pack_compact(flat)
            assert jaxcore.block_sparse2_fits(nblk, nval, n_esc, L)
            NB = -(-L // 16)
            assert used == (NB + 7) // 8 + 2 * nblk + nval
            # the used prefix alone reconstructs the levels bit-exactly
            got = layout.unpack_compact_host(payload[:used], nblk,
                                             nval, L)
            np.testing.assert_array_equal(got, flat.astype(np.int16))

    def test_payload_used_prefix_is_contiguous(self):
        # bytes past `used` must be irrelevant: corrupting them cannot
        # change the decode (the host fetches only the prefix)
        rng = np.random.default_rng(3)
        L = 16 * 200
        flat = np.zeros(L, np.int32)
        for b in rng.choice(100, 40, replace=False):
            flat[b * 16 + rng.integers(0, 16)] = 7
        nblk, nval, _, used, payload, _ = _pack_compact(flat)
        trashed = payload.copy()
        trashed[used:] = 0xAB
        np.testing.assert_array_equal(
            layout.unpack_compact_host(trashed, nblk, nval, L),
            flat.astype(np.int16))

    def test_native_matches_numpy_and_rejects_corruption(self):
        from thinvids_tpu import native as native_mod

        if not native_mod.available():
            pytest.skip("no compiler")
        rng = np.random.default_rng(17)
        L = 16 * 777 + 8
        flat = np.zeros(L, np.int32)
        for b in rng.choice(150, 90, replace=False):
            lanes = rng.choice(16, rng.integers(1, 7), replace=False)
            flat[b * 16 + lanes] = rng.integers(-120, 121, len(lanes))
        nblk, nval, n_esc, used, payload, streams = _pack_compact(flat)
        want = jaxcore._block_sparse_unpack2(nblk, nval, *streams, L)
        got = native_mod.unpack_compact(nblk, nval, payload[:used], L)
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.int16
        # counts disagreeing with the streams must raise, not
        # mis-scatter (nval - 1: the payload is long enough, but the
        # lane masks demand one more value than the count admits) ...
        with pytest.raises(ValueError, match="inconsistent"):
            native_mod.unpack_compact(nblk, nval - 1, payload[:used], L)
        # ... and a payload shorter than its counts demand must too
        with pytest.raises(ValueError, match="truncated"):
            native_mod.unpack_compact(nblk, nval, payload[:used - 1], L)
        with pytest.raises(ValueError, match="truncated"):
            layout.unpack_compact_host(payload[:used - 1], nblk, nval, L)


class TestCompactTransferParity:
    def test_bit_identical_to_sparse2_and_moves_fewer_bytes(self):
        frames = _smooth_frames(12)
        meta = VideoMeta(width=64, height=48, num_frames=12)

        enc_new = GopShardEncoder(meta, qp=27, gop_frames=3,
                                  compact_transfer=True)
        got = concat_segments(enc_new.encode(frames))
        snap_new = enc_new.stages.snapshot()
        enc_old = GopShardEncoder(meta, qp=27, gop_frames=3,
                                  compact_transfer=False)
        want = concat_segments(enc_old.encode(frames))
        snap_old = enc_old.stages.snapshot()

        assert got == want
        # both stayed on the sparse path...
        assert snap_new["dense_fallback_waves"] == 0
        assert snap_old["dense_fallback_waves"] == 0
        # ...and the compact payload crossed the link in fewer bytes
        # than the three budget-padded arrays
        assert 0 < snap_new["d2h_bytes"] <= snap_old["d2h_bytes"]

    def test_escape_heavy_content_takes_dense_fallback_identically(self):
        # iid noise overflows the block budget: both transfer modes
        # must fall back to the dense wave and still agree bit-for-bit
        frames = _noise_frames(8, seed=23)
        meta = VideoMeta(width=64, height=48, num_frames=8)

        def run(compact):
            enc = GopShardEncoder(meta, qp=27, gop_frames=2,
                                  compact_transfer=compact)
            stream = concat_segments(enc.encode(frames))
            return stream, enc.stages.snapshot()

        got, snap_new = run(True)
        want, snap_old = run(False)
        assert got == want
        assert snap_new["dense_fallback_waves"] >= 1
        assert snap_old["dense_fallback_waves"] >= 1

    def test_dense_retry_is_its_own_stage(self, monkeypatch):
        # Stage honesty: the dense fallback's wide fetch must land in
        # dense_retry, not pollute the fetch number (it used to run
        # inside prof.stage("fetch")).
        monkeypatch.setattr(jaxcore, "block_sparse2_fits",
                            lambda *a, **k: False)
        frames = _smooth_frames(8)
        meta = VideoMeta(width=64, height=48, num_frames=8)
        enc = GopShardEncoder(meta, qp=27, gop_frames=2)
        concat_segments(enc.encode(frames))
        snap = enc.stages.snapshot()
        assert snap["dense_fallback_waves"] >= 1
        assert snap["dense_retry"] > 0
        monkeypatch.undo()
        # parity with the sparse pass of the same clip
        enc2 = GopShardEncoder(meta, qp=27, gop_frames=2)
        base = concat_segments(enc2.encode(frames))
        enc3 = GopShardEncoder(meta, qp=27, gop_frames=2)
        monkeypatch.setattr(jaxcore, "block_sparse2_fits",
                            lambda *a, **k: False)
        assert concat_segments(enc3.encode(frames)) == base


class TestPerShardFetch:
    def test_concurrent_fetch_engages_and_stays_bit_identical(self):
        # 8-device mesh (conftest): the collect path must fetch with
        # one transfer per device shard AND still match the
        # single-device reference byte-for-byte.
        from thinvids_tpu.codecs.h264.encoder import encode_gop
        from thinvids_tpu.parallel.planner import plan_segments

        assert len(jax.devices()) == 8
        frames = _smooth_frames(16)
        meta = VideoMeta(width=64, height=48, num_frames=16)
        enc = GopShardEncoder(meta, qp=27, gop_frames=2)
        assert enc._fetch_pool is not None
        got = concat_segments(enc.encode(frames))
        snap = enc.stages.snapshot()
        assert snap["fetch_shards"] >= len(jax.devices())
        plan = plan_segments(16, 2, len(jax.devices()))
        want = b"".join(
            encode_gop(frames[g.start_frame:g.end_frame], meta, qp=27,
                       idr_pic_id=g.index)
            for g in plan.gops)
        assert got == want

    def test_single_device_path_has_no_fetch_pool(self):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]), ("gop",))
        frames = _smooth_frames(4)
        meta = VideoMeta(width=64, height=48, num_frames=4)
        enc = GopShardEncoder(meta, qp=27, mesh=mesh, gop_frames=2)
        assert enc._fetch_pool is None
        segs = enc.encode(frames)
        assert len(segs) == 2
        assert enc.stages.snapshot()["fetch_shards"] == 0


class TestProcessPackBackend:
    def test_process_and_thread_backends_byte_identical(self):
        frames = _smooth_frames(12)
        meta = VideoMeta(width=64, height=48, num_frames=12)
        enc_t = GopShardEncoder(meta, qp=27, gop_frames=3,
                                pack_workers=2)
        base = concat_segments(enc_t.encode(frames))
        enc_p = GopShardEncoder(meta, qp=27, gop_frames=3,
                                pack_workers=2, pack_backend="process")
        if enc_p._proc_pool is None:
            pytest.skip("platform cannot spawn a process pool")
        got = concat_segments(enc_p.encode(frames))
        assert got == base
        # the sidecars actually took the GOPs (not a silent thread
        # fallback)
        assert enc_p.stages.snapshot()["proc_pack_gops"] >= 4

    def test_process_backend_dense_fallback_uses_threads(self):
        # GOPs that leave the compact path (dense wave) must still pack
        # correctly on the thread pool under pack_backend=process
        frames = _noise_frames(8, seed=5)
        meta = VideoMeta(width=64, height=48, num_frames=8)
        enc_t = GopShardEncoder(meta, qp=27, gop_frames=2)
        base = concat_segments(enc_t.encode(frames))
        enc_p = GopShardEncoder(meta, qp=27, gop_frames=2,
                                pack_backend="process")
        if enc_p._proc_pool is None:
            pytest.skip("platform cannot spawn a process pool")
        assert concat_segments(enc_p.encode(frames)) == base
        snap = enc_p.stages.snapshot()
        assert snap["dense_fallback_waves"] >= 1
        assert snap["proc_pack_gops"] == 0

    def test_broken_pool_degrades_to_inline_pack(self):
        # A sidecar pool that breaks mid-job must not fail the encode:
        # the spool bytes re-pack in-process, the pool is retired, and
        # the output stays bit-identical. No shared-memory blocks may
        # outlive the wave either way.
        from concurrent.futures import Future
        from concurrent.futures.process import BrokenProcessPool

        frames = _smooth_frames(12)
        meta = VideoMeta(width=64, height=48, num_frames=12)
        enc_t = GopShardEncoder(meta, qp=27, gop_frames=3)
        base = concat_segments(enc_t.encode(frames))

        class BrokenPool:
            def submit(self, fn, *args):
                fut = Future()
                fut.set_exception(BrokenProcessPool("child died"))
                return fut

        enc = GopShardEncoder(meta, qp=27, gop_frames=3,
                              pack_backend="process")
        enc._proc_pool = BrokenPool()
        assert concat_segments(enc.encode(frames)) == base
        assert enc._proc_pool is None       # retired after first break

    def test_pack_backend_knobs(self, monkeypatch):
        from thinvids_tpu.core.config import (get_settings,
                                              invalidate_settings_cache,
                                              update_live_settings)

        meta = VideoMeta(width=64, height=48, num_frames=4)
        monkeypatch.setenv("TVT_PACK_BACKEND", "process")
        monkeypatch.setenv("TVT_COMPACT_TRANSFER", "0")
        invalidate_settings_cache()
        try:
            enc = GopShardEncoder(meta, qp=27)
            assert enc.pack_backend == "process"
            assert enc.compact_transfer is False
            # constructor args beat the config tier
            enc2 = GopShardEncoder(meta, qp=27, pack_backend="thread",
                                   compact_transfer=True)
            assert enc2.pack_backend == "thread"
            assert enc2.compact_transfer is True
        finally:
            monkeypatch.delenv("TVT_PACK_BACKEND")
            monkeypatch.delenv("TVT_COMPACT_TRANSFER")
            invalidate_settings_cache()
        # the live tier clamps unknown backends back to "thread"
        update_live_settings({"pack_backend": "bogus"})
        try:
            assert get_settings(refresh=True).pack_backend == "thread"
        finally:
            from thinvids_tpu.core.config import reset_live_settings

            reset_live_settings()

    def test_packproc_imports_without_jax(self):
        # Pool children (spawn) import packproc fresh; dragging jax in
        # would initialize a device backend per pack worker. Run in a
        # clean interpreter so this process's imports don't mask it.
        code = ("import sys; import thinvids_tpu.parallel.packproc; "
                "assert 'jax' not in sys.modules, 'packproc pulled jax in'")
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        subprocess.run([sys.executable, "-c", code], check=True, env=env,
                       timeout=120)


class TestSyncConfinement:
    """The device_get guard, migrated to the analyzer (tree-wide
    enforcement lives in `cli.py check` / tests/test_analysis.py; this
    asserts the manifest still encodes THIS subsystem's contract, so
    deleting the allowlist entry fails here, next to the code it
    protects)."""

    def test_manifest_owns_the_boundary(self):
        from thinvids_tpu.analysis import default_manifest
        from thinvids_tpu.analysis.astutil import matches_any

        m = default_manifest()
        # the wave dispatcher owns the boundary (tiny count barriers +
        # dense retry); tools/ is offline; the two codec entries are
        # single-frame/GOP reference paths off the wave hot path
        for mod in ("thinvids_tpu.parallel.dispatch",
                    "thinvids_tpu.codecs.h264.jaxcore",
                    "thinvids_tpu.codecs.h264.encoder",
                    "thinvids_tpu.tools.oracle"):
            assert matches_any(mod, m.sync_allowlist), mod
        assert "device_get" in m.sync_calls
        assert "block_until_ready" in m.sync_calls

    def test_sync_pass_clean_on_head(self, analysis_ctx):
        """A blocking `jax.device_get` outside the allowlist
        reintroduces a serialized fetch on the hot path — route
        transfers through GopShardEncoder._fetch_bulk instead."""
        from thinvids_tpu.analysis import syncs

        m, tree = analysis_ctx
        open_ = [f for f in syncs.run(tree, m)
                 if f.key not in m.waivers]
        assert not open_, "\n".join(f.format() for f in open_)


class TestProcPoolThreadSafety:
    def test_disable_proc_pool_single_shot_across_threads(self, caplog):
        """Regression (cli.py check TVT-T001): several collector
        threads can hit a broken sidecar pool in the same wave window;
        the swap-under-_proc_lock retires it exactly once (one warning,
        no double-disable, never an exception)."""
        import logging
        import threading

        enc = object.__new__(GopShardEncoder)
        enc._proc_lock = threading.Lock()
        enc._proc_pool = object()
        barrier = threading.Barrier(8)

        def hit():
            barrier.wait()
            enc._disable_proc_pool(RuntimeError("boom"))

        workers = [threading.Thread(target=hit) for _ in range(8)]
        with caplog.at_level(logging.WARNING,
                             logger="thinvids_tpu.parallel.dispatch"):
            for t in workers:
                t.start()
            for t in workers:
                t.join(5)
        assert enc._proc_pool is None
        retired = [r for r in caplog.records
                   if "pack sidecar pool broke" in r.getMessage()]
        assert len(retired) == 1
