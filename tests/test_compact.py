"""Compact device→host level-stream transfer (ISSUE 4).

Covers the layers of the boundary: the device-side payload
compaction (jaxcore._compact_stream + the native/numpy unpack parity),
bit-identity of the wave pipeline against the single-device reference
(including the escape-heavy dense-fallback edge), the per-shard
concurrent fetch on the 8-device virtual mesh, the shape of the one
path (no mode parameter, two pinned settings), the stage-honesty
accounting (dense_retry / dense_fallback_waves / d2h_bytes), and the sync
confinement that keeps blocking `jax.device_get` off the hot path for
good (now enforced tree-wide by `cli.py check`; the test here asserts
the analyzer manifest still encodes this file's contract).
"""

import importlib
import inspect
import json
import os
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu.codecs.h264 import jaxcore, layout
from thinvids_tpu.codecs.h264.encoder import encode_gop
from thinvids_tpu.codecs.h264.rdo import RdConfig, aq_from_strength
from thinvids_tpu.core.types import Frame, VideoMeta, concat_segments
from thinvids_tpu.parallel.dispatch import (GopShardEncoder, default_mesh,
                                            encode_clip_sharded)


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


def _gather_pack2(flat, budget_div=jaxcore._BLOCK_BUDGET_DIV,
                  val_div=jaxcore._VAL_BUDGET_DIV):
    """`jaxcore._block_sparse_pack2` as it stood before ISSUE 47 — tier 1
    a row gather, `jnp.take(blocks, blist)`, one dynamic address a
    budget slot — kept here as the oracle of the chunk append that
    took its place."""
    L = flat.shape[0]
    NB = -(-L // 16)
    flat = jnp.concatenate([flat.astype(jnp.int16),
                            jnp.zeros(NB * 16 - L, jnp.int16)])
    budget = NB // budget_div
    vbudget = L // val_div
    blocks = flat.reshape(NB, 16)
    bmask = jnp.any(blocks != 0, axis=1)
    nblk = jnp.sum(bmask.astype(jnp.int32))
    pos = jnp.cumsum(bmask.astype(jnp.int32)) - 1
    (came,) = jaxcore._compact_left(
        jnp.where(bmask, jnp.arange(NB, dtype=jnp.int32) - pos, 0))
    slot = jnp.arange(budget, dtype=jnp.int32)
    live = slot < nblk
    blist = jnp.where(live, slot + came[:budget], 0)
    gathered = jnp.take(blocks, blist, axis=0)
    gathered = jnp.where(live[:, None], gathered, 0)
    bitmap = jnp.sum(
        jaxcore._pad8(bmask).reshape(-1, 8).astype(jnp.uint8)
        * jaxcore._BIT_WEIGHTS, axis=-1).astype(jnp.uint8)
    emask = gathered != 0
    lanes = jnp.asarray([1 << k for k in range(16)], jnp.int32)
    bmask16 = jnp.sum(emask.astype(jnp.int32) * lanes,
                      axis=1).astype(jnp.uint16)
    counts = jnp.sum(emask.astype(jnp.int32), axis=1)
    offs = jnp.cumsum(counts) - counts
    within = jnp.cumsum(emask.astype(jnp.int32), axis=1) - 1
    nval = jnp.sum(counts)
    at = jnp.arange(budget * 16, dtype=jnp.int32).reshape(emask.shape)
    shift = jnp.where(emask, at - (offs[:, None] + within), 0)
    clipped = jnp.clip(gathered, -127, 127).astype(jnp.int8)
    _, vals = jaxcore._compact_left(shift.reshape(-1), clipped.reshape(-1))
    vals = jnp.pad(vals, (0, max(vbudget - vals.shape[0], 0)))[:vbudget]
    n_esc = jnp.sum((jnp.abs(gathered) > 127).astype(jnp.int32))
    return (nblk, nval, n_esc, bitmap, bmask16, vals)


class TestChunkAppend:
    """ISSUE 47: tier 1 of `_block_sparse_pack2` appends chunks of
    `_APPEND_CHUNK` blocks where it gathered rows. All six outputs
    equal the gather form's (`_gather_pack2`) for every input, past
    the budgets too; the Pallas kernel, run by the interpreter, equals
    its XLA mirror on the same cases."""

    CHUNK = jaxcore._APPEND_CHUNK
    #: blocks of the common case: three chunks and a part of a fourth
    NB = 3 * CHUNK + 100
    #: and of the cases whose kept blocks fill several chunks of the
    #: output (a 1080p GOP fills about 60 of its 383)
    BIG = 16 * CHUNK + 100

    @staticmethod
    def _levels(nb, L, at, rng, big=None):
        """`L` levels in `nb` blocks, those of index `at` holding one
        to three of them (`big`: one level of that size in the first)."""
        flat = np.zeros(nb * 16, np.int32)
        for b in at:
            lanes = rng.choice(16, rng.integers(1, 4), replace=False)
            flat[b * 16 + lanes] = rng.choice([-1, 1], len(lanes)) \
                * rng.integers(1, 120, len(lanes))
        if big is not None:
            flat[at[0] * 16 + 3] = big
        return flat[:L]

    def _case(self, name):
        """name -> (flat levels, budget_div, val_div)."""
        rng = np.random.default_rng(len(name))
        nb = self.NB
        budget = nb // 4

        def some(count, lo=0, hi=None):
            return np.sort(rng.choice(np.arange(lo, hi or nb), count,
                                      replace=False))

        L = nb * 16 - 5                     # not a multiple of 16
        div = (jaxcore._BLOCK_BUDGET_DIV, jaxcore._VAL_BUDGET_DIV)
        if name == "fill_0pct":
            return np.zeros(L, np.int32), *div
        if name == "one_block":
            return self._levels(nb, L, [self.CHUNK + 7], rng), *div
        if name == "the_last_block":        # the partial one: 11 levels
            flat = np.zeros(L, np.int32)
            flat[-1] = -3
            return flat, *div
        fills = {"fill_15pct": 0.15, "exactly_the_budget": 1.0,
                 "fill_101pct": 1.01, "fill_200pct": 2.0}
        if name in fills:
            count = int(round(fills[name] * budget))
            return self._levels(nb, L, some(count), rng), *div
        if name == "all_in_the_last_chunk":
            return self._levels(nb, L, some(90, 3 * self.CHUNK), rng), *div
        if name == "whole_chunks":          # NB a multiple of the chunk
            nb = 2 * self.CHUNK
            return self._levels(nb, nb * 16, some(700, 0, nb), rng), *div
        if name == "unit_divisors":         # the split-frame form
            nb = self.CHUNK + 900
            return self._levels(nb, nb * 16 - 9, some(3000, 0, nb),
                                rng), 1, 1
        if name == "escape":
            return self._levels(nb, L, some(200), rng, big=-300), *div
        # the output's chunks beyond the first: a chunk's live blocks
        # pass the end of one and open the next
        nb = self.BIG
        L = nb * 16 - 5
        many = {"four_chunks_out": (0.9, div),
                "past_the_last_chunk_out": (2.0, div),
                "sixteen_chunks_out": (0.7, (1, 1))}
        if name in many:
            fill, divs = many[name]
            return self._levels(nb, L, some(int(fill * (nb // divs[0]))),
                                rng), *divs
        if name == "chunks_end_on_the_outputs_edges":
            # live blocks a chunk: the second ends on the output's
            # first edge, a full one lies edge to edge, an empty one
            # stores nothing, the next two end on the third edge
            at = [c * self.CHUNK + rng.choice(self.CHUNK, n, replace=False)
                  for c, n in enumerate(
                      [1500, 2596, 4096, 0, 3000, 1096, 5, 4091, 17])]
            return self._levels(nb, L, np.sort(np.concatenate(at)),
                                rng), *div
        raise KeyError(name)

    CASES = ["fill_0pct", "one_block", "the_last_block", "fill_15pct",
             "exactly_the_budget", "fill_101pct", "fill_200pct",
             "all_in_the_last_chunk", "whole_chunks", "unit_divisors",
             "escape", "four_chunks_out", "past_the_last_chunk_out",
             "sixteen_chunks_out", "chunks_end_on_the_outputs_edges"]

    @pytest.mark.parametrize("form", ["mirror", "kernel"])
    @pytest.mark.parametrize("case", CASES)
    def test_all_six_outputs_equal_the_gather_forms(self, case, form,
                                                    monkeypatch):
        from thinvids_tpu.codecs.h264 import jaxme

        if form == "kernel":
            monkeypatch.setattr(jaxme, "use_pallas", lambda: True)
            kernel = jaxcore._append_kernel
            monkeypatch.setattr(
                jaxcore, "_append_kernel",
                lambda *a: kernel(*a, interpret=True))
        flat, budget_div, val_div = self._case(case)
        L = flat.shape[0]
        want = [np.asarray(x) for x in jax.jit(
            _gather_pack2, static_argnums=(1, 2))(
                jnp.asarray(flat), budget_div, val_div)]
        got = [np.asarray(x) for x in jax.jit(
            jaxcore._block_sparse_pack2, static_argnums=(1, 2))(
                jnp.asarray(flat), budget_div, val_div)]
        for name, g, w in zip(("nblk", "nval", "n_esc", "bitmap",
                               "bmask16", "vals"), got, want):
            assert g.dtype == w.dtype and g.shape == w.shape, name
            np.testing.assert_array_equal(g, w, err_msg=name)
        nblk, nval, n_esc, bitmap, bmask16, vals = got
        assert int(nblk) == len(np.unique(np.nonzero(flat)[0] // 16))
        fits = jaxcore.block_sparse2_fits(nblk, nval, n_esc, L,
                                          budget_div, val_div)
        assert fits == (case not in ("fill_101pct", "fill_200pct",
                                     "escape", "past_the_last_chunk_out"))
        assert (int(n_esc) > 0) == (case == "escape")
        if fits:
            np.testing.assert_array_equal(
                layout.block_sparse_unpack2_host(
                    int(nblk), int(nval), bitmap, bmask16, vals, L),
                flat.astype(np.int16))


class TestCompactTransferParity:
    """The wave pipeline against the single-device reference
    (encoder.encode_gop), GOP for GOP: the compact wire where the
    sparse budgets hold, the levels as words where they do not."""

    #: name -> (frames, encoder arguments, devices (None: the whole
    #: mesh), whether the clip leaves the sparse budgets)
    CASES = {
        "smooth": (lambda: _smooth_frames(12), dict(gop_frames=3), None,
                   False),
        "smooth_one_device": (lambda: _smooth_frames(12),
                              dict(gop_frames=3), 1, False),
        "noise": (lambda: _noise_frames(8, seed=23), dict(gop_frames=2),
                  None, True),
        "noise_one_device": (lambda: _noise_frames(8, seed=5),
                             dict(gop_frames=2), 1, True),
        "serving_rd": (
            lambda: _smooth_frames(8),
            dict(gop_frames=4, qp=25, rd=RdConfig(
                mode_decision=True, pskip=True, deblock=True,
                aq_q=aq_from_strength(1.0))), 1, False),
        "quarter_subpel": (lambda: _smooth_frames(8),
                           dict(gop_frames=4, rd=RdConfig(subpel="quarter")),
                           1, False),
        "mesh_of_two": (lambda: _smooth_frames(12), dict(gop_frames=3), 2,
                        False),
        "mesh_of_two_noise": (lambda: _noise_frames(8, seed=23),
                              dict(gop_frames=2), 2, True),
        "two_gops_a_device": (lambda: _smooth_frames(12),
                              dict(gop_frames=3, gops_per_wave=2), 1,
                              False),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_wave_bytes_are_the_single_device_reference(self, case):
        make, kwargs, devices, goes_dense = self.CASES[case]
        frames = make()
        kwargs = dict(qp=27) | kwargs
        meta = VideoMeta(width=64, height=48, num_frames=len(frames))
        mesh = None if devices is None \
            else default_mesh(jax.devices()[:devices])
        enc = GopShardEncoder(meta, mesh=mesh, **kwargs)
        segs = enc.encode(frames)
        assert [g.gop.index for g in segs] == list(range(len(segs)))
        assert segs[-1].gop.end_frame == len(frames)
        want = [encode_gop(frames[s.gop.start_frame:s.gop.end_frame], meta,
                           qp=kwargs["qp"], idr_pic_id=s.gop.index,
                           rd=kwargs.get("rd"))
                for s in segs]
        assert [s.payload for s in segs] == want
        snap = enc.stages.snapshot()
        if goes_dense:
            assert snap["dense_fallback_waves"] >= 1
            return
        assert snap["dense_fallback_waves"] == 0
        # the payloads' used prefixes crossed, not the levels
        L, _Lr = enc._level_sizes(max(s.gop.num_frames for s in segs),
                                  (64 // 16) * (48 // 16))
        assert 0 < snap["d2h_bytes"] < len(segs) * L * 2

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


class TestOnePath:
    """The GOP-wave encoder has one device program per wave, one wire
    and one pack backend: no parameter selects another, the sidecar
    module is gone, and the two settings that named the choices are
    constants that report what the benchmark's configs expect
    (core/config._PINNED)."""

    @pytest.mark.parametrize("param", ["inter", "compact_transfer",
                                       "pack_backend"])
    @pytest.mark.parametrize("fn", [GopShardEncoder.__init__,
                                    encode_clip_sharded],
                             ids=["GopShardEncoder", "encode_clip_sharded"])
    def test_no_mode_parameter(self, fn, param):
        assert param not in inspect.signature(fn).parameters

    def test_packproc_is_gone(self):
        with pytest.raises(ModuleNotFoundError):
            importlib.import_module("thinvids_tpu.parallel.packproc")

    @pytest.mark.parametrize("how", ["env", "post_settings",
                                     "update_live_settings"])
    @pytest.mark.parametrize("key,asked,reported", [
        ("compact_transfer", "0", True),
        ("pack_backend", "process", "thread")])
    def test_pinned_setting_reports_the_configs_value(
            self, key, asked, reported, how, monkeypatch):
        from thinvids_tpu.core import config

        # what every benchmark config states in `expect_settings`
        cfg_dir = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs")
        for name in sorted(os.listdir(cfg_dir)):
            with open(os.path.join(cfg_dir, name)) as fh:
                assert json.load(fh)["expect_settings"][key] == reported
        config.reset_live_settings()
        try:
            if how == "env":
                monkeypatch.setenv("TVT_" + key.upper(), asked)
                config.invalidate_settings_cache()
            elif how == "update_live_settings":
                applied = config.update_live_settings({key: asked})
                assert applied == {key: reported}
            else:
                from thinvids_tpu.api import ApiServer
                from thinvids_tpu.cluster.coordinator import Coordinator

                server = ApiServer(Coordinator()).start()
                try:
                    req = urllib.request.Request(
                        server.url + "/settings", method="POST",
                        data=json.dumps({key: asked}).encode(),
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=10) as resp:
                        assert resp.status == 200
                    with urllib.request.urlopen(server.url + "/settings",
                                                timeout=10) as resp:
                        served = json.loads(resp.read())["settings"]
                finally:
                    server.stop()
                assert served[key] == reported
            got = config.get_settings(refresh=True).get(key)
            assert got == reported and type(got) is type(reported)
        finally:
            monkeypatch.delenv("TVT_" + key.upper(), raising=False)
            config.reset_live_settings()


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
