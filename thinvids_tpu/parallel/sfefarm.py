"""Farm split-frame encoding: one frame's band layout spread across
WORKER HOSTS (the cross-host form of PR 9's SfeShardEncoder).

Each band shard (cluster/remote.py, shape="band") owns a contiguous
slice [band_lo, band_hi) of the job's pinned GLOBAL band layout and
steps the SAME fixed GOP grid in lockstep with its peers. Within the
slice the device mesh still runs the PR 9 banded programs
(ppermute/psum over the local axis); ACROSS slices the three
collective flows move to the host and ride the coordinator-relayed
halo route (cluster/halo.py):

- neighbor reference rows: after each frame's step the slice's
  boundary recon rows ship to the adjacent groups and come back as
  injected halo inputs for the next frame's search;
- global-motion probe: a per-host partial-cost program
  (dispatch._sfe_probe_step) + cross-host int32 sum + host argmin —
  bit-identical to the full-mesh psum+argmin;
- temporal median: the per-host histogram partial leaves the device
  with the level streams, sums across hosts, and the host-side
  cumsum/argmax (jaxme.median_from_counts) feeds the next frame's
  search center.

Because every cross-host reduction is an integer sum and the injected
halo rows are exactly the bytes ppermute would have delivered, a farm
of N single-band hosts emits THE SAME band slices a local N-band mesh
would — the coordinator's per-frame zip of the groups' slices is
byte-identical to the local-mesh SFE stream (the hermetic 2-worker
test proves it end to end).

The GOP walk is synchronous here (a frame's step needs the previous
frame's exchange), so a "wave" = one GOP, fully encoded inside
dispatch_wave; escapes fall back to a host-LOCAL dense replay fed by
the cached per-frame injected inputs — peers never notice (recon,
halo and histogram flows are identical either way).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.types import EncodedSegment, VideoMeta
from .dispatch import (SfeShardEncoder, _sfe_intra_step_dense,
                       _sfe_p_step_farm, _sfe_p_step_farm_dense,
                       _sfe_probe_step)


class FarmBandEncoder(SfeShardEncoder):
    """SfeShardEncoder over a SLICE of a cross-host band layout."""

    def __init__(self, meta: VideoMeta, qp: int = 27,
                 mesh: Mesh | None = None, gop_frames: int = 32,
                 max_segments: int = 200, total_bands: int = 0,
                 band_range: tuple[int, int] | None = None,
                 halo_rows: int | None = None, session=None,
                 pack_workers: int | None = None, rd=None):
        super().__init__(meta, qp=qp, mesh=mesh, gop_frames=gop_frames,
                         max_segments=max_segments, halo_rows=halo_rows,
                         pack_workers=pack_workers,
                         # synchronous GOP walk: the exchange serializes
                         # frames anyway, and window 1 bounds retained
                         # staged GOPs on worker hosts
                         pipeline_window=1,
                         total_bands=total_bands, band_range=band_range,
                         rd=rd)
        #: cluster/halo.HaloSession (or None for a single-group layout
        #: covering the whole frame — no peers to talk to)
        self.session = session
        self.edge_top = self.band_lo == 0
        self.edge_bot = self.band_hi == self.global_band_plan.num_bands
        #: traced (2,) bool the farm steps take as an INPUT — a
        #: re-claim of a different slice position must reuse the same
        #: compiled programs, not recompile per edge-flag combination
        self._edges = jnp.asarray([self.edge_top, self.edge_bot],
                                  bool)
        if session is None and not (self.edge_top and self.edge_bot):
            raise ValueError(
                "a band SLICE (neighbors exist) needs a halo session")

    # -- host<->device glue for the injected halo ----------------------

    def _ext_device(self, top, bot, rows: int, width: int):
        """(top, bot) host arrays → the band-sharded injected-ext
        inputs of the farm steps: only the first band's block of `top`
        and the last band's block of `bot` are ever read."""
        B = self.band_plan.num_bands
        t = np.zeros((B * rows, width), np.int16)
        b = np.zeros((B * rows, width), np.int16)
        if top is not None:
            t[:rows] = top
        if bot is not None:
            b[(B - 1) * rows:] = bot
        if self._step_mesh() is None:
            return jnp.asarray(t), jnp.asarray(b)
        shard = NamedSharding(self.mesh, P("band"))
        return jax.device_put(t, shard), jax.device_put(b, shard)

    def _ext_triplet(self, top_in, bot_in):
        halo = self.halo_rows
        W = self.band_plan.mb_width * 16
        ty, by = self._ext_device(
            top_in["y"] if top_in else None,
            bot_in["y"] if bot_in else None, halo, W)
        tu, bu = self._ext_device(
            top_in["u"] if top_in else None,
            bot_in["u"] if bot_in else None, halo // 2, W // 2)
        tv, bv = self._ext_device(
            top_in["v"] if top_in else None,
            bot_in["v"] if bot_in else None, halo // 2, W // 2)
        return ty, by, tu, bu, tv, bv

    def _edge_rows(self, carry3):
        """This slice's boundary recon rows (frame just stepped): what
        the neighbor groups splice in as their halo. None at true
        frame edges (nobody consumes them)."""
        ry, ru, rv = carry3
        halo = self.halo_rows
        hc = halo // 2
        top = bot = None
        if not self.edge_top:
            with self.stages.stage("fetch"):
                top = {"y": np.asarray(jax.device_get(ry[:halo]),
                                       np.int16),
                       "u": np.asarray(jax.device_get(ru[:hc]), np.int16),
                       "v": np.asarray(jax.device_get(rv[:hc]),
                                       np.int16)}
        if not self.edge_bot:
            with self.stages.stage("fetch"):
                bot = {"y": np.asarray(jax.device_get(ry[-halo:]),
                                       np.int16),
                       "u": np.asarray(jax.device_get(ru[-hc:]),
                                       np.int16),
                       "v": np.asarray(jax.device_get(rv[-hc:]),
                                       np.int16)}
        return top, bot

    # -- cross-host reductions -----------------------------------------

    def _global_probe(self, seq: int, cur_y, ref_y, ty, by) -> np.ndarray:
        from ..codecs.h264 import jaxme

        bp = self.band_plan
        with self.stages.stage("dispatch"):
            cost = _sfe_probe_step(cur_y, ref_y, self._real_rows, ty,
                                   by, self._edges,
                                   mesh=self._step_mesh(),
                                   num_bands=bp.num_bands)
        with self.stages.stage("device_wait"):
            cost_h = np.asarray(jax.device_get(cost))[0]
        if self.session is not None:
            with self.stages.stage("halo"):
                cost_h = self.session.sum_probe(seq, cost_h)
        return jaxme.probe_center_from_cost(cost_h)

    def _global_median(self, seq: int, hist_local) -> np.ndarray:
        from ..codecs.h264 import jaxme

        cnt = np.asarray(hist_local[0], np.int32)
        n = int(hist_local[1])
        if self.session is not None:
            with self.stages.stage("halo"):
                peers = self.session.gather_hists(seq)
            for h in peers:
                cnt = (cnt + np.asarray(h["cnt"], np.int32)) \
                    .astype(np.int32)
                n += int(np.asarray(h["n"]).reshape(-1)[0])
        return jaxme.median_from_counts(
            cnt, n, self.rd.mv_per_pel * jaxme.SEARCH_RANGE)

    # -- the lockstep GOP walk -----------------------------------------

    def dispatch_wave(self, staged: tuple) -> tuple:
        """Encode ONE GOP of this band slice, frame by frame in
        lockstep with the peer groups. Returns (global GopSpec,
        per-frame NAL bytes) — collect_wave only assembles the
        segment."""
        import dataclasses as _dc

        gop, ys, us, vs, qp = staged
        bp = self.band_plan
        mesh = self._step_mesh()
        sess = self.session
        qpj = jnp.asarray(qp, jnp.int32)
        gop_g = _dc.replace(gop, index=gop.index + self.gop_index_offset,
                            start_frame=(gop.start_frame
                                         + self.frame_offset))
        idr_pic_id = gop_g.index % 65536
        F = gop.num_frames
        nals: list[bytes] = []
        #: cached per-P-frame injected inputs — the dense replay's feed
        replay: list[tuple] = []
        dense_from: int | None = None
        hist_local = None
        carry3 = None
        pred = np.zeros(2, np.int32)
        for fi in range(F):
            seq = gop_g.start_frame + fi
            if fi == 0:
                with self.stages.stage("dispatch"):
                    r = self._intra_step(ys[0], us[0], vs[0], qpj)
                outs, carry3 = r[:6], r[6:9]
                hist_local = None
            else:
                with self.stages.stage("halo"):
                    top_in, bot_in = sess.gather_edges(seq - 1) \
                        if sess is not None else (None, None)
                pred = self._global_median(seq - 1, hist_local) \
                    if fi >= 2 else np.zeros(2, np.int32)
                ty, by, tu, bu, tv, bv = self._ext_triplet(top_in, bot_in)
                probe = self._global_probe(seq, ys[fi], carry3[0], ty, by)
                with self.stages.stage("dispatch"):
                    r = _sfe_p_step_farm(
                        ys[fi], us[fi], vs[fi], *carry3,
                        jnp.asarray(pred), jnp.asarray(probe),
                        ty, by, tu, bu, tv, bv, qpj, self._real_rows,
                        self._edges, mbw=bp.mb_width,
                        mbh_band=bp.band_mb_rows, mesh=mesh,
                        halo_rows=self.halo_rows,
                        num_bands=bp.num_bands, rd=self.rd)
                outs, carry3 = r[:6], r[8:11]
                with self.stages.stage("device_wait"):
                    cnt_h, n_h = jax.device_get([r[6], r[7]])
                hist_local = (np.asarray(cnt_h)[0].astype(np.int32),
                              int(np.asarray(n_h).reshape(-1)[0]))
                replay.append((pred, probe, top_in, bot_in))
            # unblock the peers FIRST: their next frame's search waits
            # on these rows, while our own pack work below is local
            if sess is not None and fi < F - 1:
                top_out, bot_out = self._edge_rows(carry3)
                hist_blob = None
                if hist_local is not None:
                    hist_blob = {
                        "cnt": hist_local[0],
                        "n": np.asarray([hist_local[1]], np.int64)}
                with self.stages.stage("halo"):
                    sess.publish_state(seq, top=top_out, bot=bot_out,
                                       hist=hist_blob)
            head, nblk, nval, n_esc, used, payload = outs
            with self.stages.stage("device_wait"):
                tiny = jax.device_get([nblk, nval, n_esc, used])
            self.stages.bump("d2h_bytes",
                             sum(int(a.nbytes) for a in tiny))
            nblk_h, nval_h, nesc_h, used_h = tiny
            if dense_from is None \
                    and int(np.asarray(nesc_h).max()) > 0:
                dense_from = fi     # escape: this slice replays dense
                                    # LOCALLY after the walk — the
                                    # exchange flows above continue
                                    # untouched (identical either way)
            if dense_from is not None:
                continue
            _, L = self._band_sizes(intra=(fi == 0))
            self._note_sparse_fill(nblk_h, nval_h, L, 1, 1)
            with self.stages.stage("fetch"):
                (head_h,) = self._fetch_bulk([head])
                rows = self._fetch_payload_rows(payload, used_h)
            with self.stages.stage("sfe"):
                nals.append(self._pack_band_frame(
                    fi, head_h, rows, nblk_h, nval_h, used_h, L, qp,
                    idr_pic_id))
            self._note_frame_done(seq)
        if dense_from is not None:
            nals = self._replay_dense(gop_g, staged, nals, dense_from,
                                      replay)
        return (gop_g, nals)

    def _pack_band_frame(self, fi: int, head_h, rows, nblk_h, nval_h,
                         used_h, L: int, qp: int,
                         idr_pic_id: int) -> bytes:
        bp = self.band_plan
        thunks = []
        for bi in range(bp.num_bands):
            rest = functools.partial(
                self._unpack_compact, rows[bi], int(nblk_h[bi]),
                int(nval_h[bi]), int(used_h[bi]), L)
            if fi == 0:
                thunks.append(functools.partial(
                    lambda r, b: self._pack_intra_band(
                        head_h[b], r(), b, qp, idr_pic_id), rest, bi))
            else:
                thunks.append(functools.partial(
                    lambda r, b, fn: self._pack_p_band(
                        head_h[b], r(), b, qp, fn), rest, bi, fi % 256))
        frame_nal = b"".join(self._gather_frame(thunks))
        if fi == 0 and self.emit_parameter_sets:
            frame_nal = self.sps.to_nal() + self.pps.to_nal() + frame_nal
        return frame_nal

    def _replay_dense(self, gop_g, staged: tuple, nals: list[bytes],
                      dense_from: int, replay: list[tuple]
                      ) -> list[bytes]:
        """Escape fallback, host-LOCAL: rerun this slice's GOP through
        the dense-transfer farm steps, feeding the CACHED per-frame
        injected inputs (pred, probe, neighbor rows) — no re-exchange,
        bit-identical levels (the wave path's fallback contract)."""
        prof = self.stages
        bp = self.band_plan
        _, ys, us, vs, qp = staged
        qpj = jnp.asarray(qp, jnp.int32)
        mesh = self._step_mesh()
        idr_pic_id = gop_g.index % 65536
        prof.bump("dense_fallback_waves")
        with prof.stage("dense_retry"):
            carry3 = None
            for fi in range(gop_g.num_frames):
                if fi == 0:
                    r = _sfe_intra_step_dense(
                        ys[0], us[0], vs[0], qpj, self._real_rows,
                        mbw=bp.mb_width, mbh_band=bp.band_mb_rows,
                        mesh=mesh, rd=self.rd,
                        total_mb_rows=self._total_mb_rows)
                    head, flat, carry3 = None, r[0], r[1:4]
                else:
                    pred, probe, top_in, bot_in = replay[fi - 1]
                    ty, by, tu, bu, tv, bv = self._ext_triplet(top_in,
                                                               bot_in)
                    r = _sfe_p_step_farm_dense(
                        ys[fi], us[fi], vs[fi], *carry3,
                        jnp.asarray(pred), jnp.asarray(probe),
                        ty, by, tu, bu, tv, bv, qpj, self._real_rows,
                        self._edges, mbw=bp.mb_width,
                        mbh_band=bp.band_mb_rows, mesh=mesh,
                        halo_rows=self.halo_rows,
                        num_bands=bp.num_bands, rd=self.rd)
                    head, flat, carry3 = r[0], r[1], r[2:5]
                if fi < dense_from:
                    continue        # already packed from sparse
                if head is None:
                    flat_h = self._fetch_bulk([flat])[0]
                    head_h = None
                else:
                    head_h, flat_h = self._fetch_bulk([head, flat])
                thunks = []
                for bi in range(bp.num_bands):
                    if fi == 0:
                        thunks.append(functools.partial(
                            lambda b, f: self._pack_intra_band_dense(
                                f[b], b, qp, idr_pic_id), bi, flat_h))
                    else:
                        thunks.append(functools.partial(
                            lambda b, m, f, fn: self._pack_p_band(
                                m[b], f[b], b, qp, fn),
                            bi, head_h, flat_h, fi % 256))
                frame_nal = b"".join(self._gather_frame(thunks))
                if fi == 0 and self.emit_parameter_sets:
                    frame_nal = self.sps.to_nal() + self.pps.to_nal() \
                        + frame_nal
                nals.append(frame_nal)
                self._note_frame_done(gop_g.start_frame + fi)
        return nals

    def collect_wave(self, pending: tuple) -> list[EncodedSegment]:
        gop_g, nals = pending
        with self.stages.stage("concat"):
            seg = EncodedSegment(gop=gop_g, payload=b"".join(nals),
                                 frame_sizes=tuple(len(n) for n in nals))
        self.stages.count_wave()
        return [seg]
