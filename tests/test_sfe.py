"""Split-frame encoding (SFE): shard one frame across the mesh.

Covers the whole stack on the 8-device virtual CPU mesh:

- band planner math (MB-aligned, pinned, shrink-to-real-rows);
- banded motion search bit-IDENTITY against full-frame `me_search`
  when the halo covers the candidate reach (halo exchange via
  lax.ppermute + psum'd global probe/median), and the DOCUMENTED
  vertical clamp when it doesn't (bounded divergence, not drift);
- multi-slice entropy: per-band `first_mb_in_slice`, per-slice
  qp delta, idr_pic_id agreement, access-unit grouping in the MP4
  mux and the libavcodec oracle's AU splitter;
- conformance: the in-repo decoder (now multi-slice + P-capable)
  reconstructs SFE streams bit-exactly to the device recon carry,
  including the partial last band, the thin-band clamped halo, and
  the int8-escape dense fallback; the libavcodec oracle re-checks
  when present;
- executor wiring: `sfe_bands` selects the mode (0 = the GOP-wave
  encoder, byte-identical current behavior).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from thinvids_tpu.codecs.h264 import jaxme
from thinvids_tpu.codecs.h264.decoder import decode_annexb
from thinvids_tpu.codecs.h264.encoder import encode_gop
from thinvids_tpu.core.types import Frame, VideoMeta, concat_segments
from thinvids_tpu.parallel.dispatch import SfeShardEncoder
from thinvids_tpu.parallel.planner import plan_bands, plan_fixed_segments

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="SFE multi-band tests need >= 2 devices "
           "(force_cpu_devices in conftest provides 8)")


def _start_positions(au: bytes) -> list:
    import re

    return [m.start() for m in re.finditer(b"\x00\x00\x01", au)]


def clip(w, h, n, step=3, seed=0, vstep=0):
    """Pan over a textured scene; `vstep` adds vertical motion (the
    halo-clamp tests need true motion past the clamp)."""
    rng = np.random.default_rng(seed)
    pad = (abs(step) + abs(vstep)) * n + 2
    yy, xx = np.mgrid[0:h + 2 * pad, 0:w + 2 * pad]
    scene = np.clip((xx * 3 + yy * 2) % 256
                    + rng.normal(0, 2.0, yy.shape), 0, 255).astype(np.uint8)
    frames = []
    for i in range(n):
        dy, dx = pad + vstep * i, pad + step * i
        y = scene[dy:dy + h, dx:dx + w]
        u = np.clip(128 + 20 * np.sin(xx[:h // 2, :w // 2] * 0.1 + i),
                    0, 255).astype(np.uint8)
        v = np.clip(128 + 20 * np.cos(yy[:h // 2, :w // 2] * 0.1 + i),
                    0, 255).astype(np.uint8)
        frames.append(Frame(np.ascontiguousarray(y), u, v))
    return frames


def encode_sfe(frames, meta, qp=27, gop_frames=4, bands=2, halo_rows=32,
               **kw):
    enc = SfeShardEncoder(meta, qp=qp, gop_frames=gop_frames, bands=bands,
                          halo_rows=halo_rows, **kw)
    enc.keep_recon = True
    segs = enc.encode(frames)
    return enc, concat_segments(segs)


def assert_decode_parity(enc, stream, n):
    """The in-repo decoder's output must equal the device recon carry
    frame by frame — the conformance contract (closed-loop recon IS
    what a conformant decoder reconstructs)."""
    dec = decode_annexb(stream)
    assert len(dec.frames) == n
    for i in range(n):
        ry, ru, rv = enc.recon_frames[i]
        np.testing.assert_array_equal(dec.frames[i].y, ry,
                                      err_msg=f"frame {i} y")
        np.testing.assert_array_equal(dec.frames[i].u, ru,
                                      err_msg=f"frame {i} u")
        np.testing.assert_array_equal(dec.frames[i].v, rv,
                                      err_msg=f"frame {i} v")
    return dec


class TestBandPlan:
    def test_divisible(self):
        bp = plan_bands(16, 4, 8)
        assert bp.num_bands == 8 and bp.band_mb_rows == 2
        assert [(b.start_mb_row, b.mb_rows) for b in bp.bands] == \
            [(2 * i, 2) for i in range(8)]
        assert bp.padded_mb_height == 16

    def test_partial_last_band(self):
        bp = plan_bands(135, 240, 8)        # 2160p on an 8-chip mesh
        assert bp.band_mb_rows == 17
        assert [b.mb_rows for b in bp.bands] == [17] * 7 + [16]
        assert bp.bands[-1].end_mb_row == 135
        assert bp.padded_mb_height == 136

    def test_shrinks_to_real_rows(self):
        # 6 MB rows over 8 requested bands: a fully-padded band has no
        # real edge row to source halos from — the plan shrinks
        bp = plan_bands(6, 4, 8)
        assert bp.num_bands == 6 and bp.band_mb_rows == 1

    def test_pinned_pure_function(self):
        assert plan_bands(135, 240, 8) == plan_bands(135, 240, 8)

    def test_validation(self):
        with pytest.raises(ValueError):
            plan_bands(0, 4, 2)
        with pytest.raises(ValueError):
            plan_bands(4, 4, 0)

    def test_fixed_segments(self):
        plan = plan_fixed_segments(10, 4)
        assert [(g.start_frame, g.num_frames) for g in plan.gops] == \
            [(0, 4), (4, 4), (8, 2)]
        with pytest.raises(ValueError):
            plan_fixed_segments(0, 4)

    def test_sfe_plan_honors_max_segments(self):
        meta = VideoMeta(width=64, height=96, num_frames=1000)
        enc = SfeShardEncoder(meta, gop_frames=4, max_segments=50,
                              bands=1)
        plan = enc.plan(1000)
        assert plan.num_gops <= 50
        # still a pure fixed grid: every GOP the same grown length
        assert len({g.num_frames for g in plan.gops[:-1]}) == 1


def _mixed_motion(w, h, seed=0):
    rng = np.random.default_rng(seed)
    pad = 24
    scene = rng.integers(0, 255, (h + 2 * pad, w + 2 * pad)).astype(np.uint8)
    ref = scene[pad:pad + h, pad:pad + w]
    cur = np.empty_like(ref)
    cur[:h // 2] = scene[pad + 9:pad + 9 + h // 2, pad + 5:pad + 5 + w]
    cur[h // 2:] = scene[pad - 7:pad - 7 + h // 2, pad - 3:pad - 3 + w]
    ru = rng.integers(0, 255, (h // 2, w // 2)).astype(np.uint8)
    rv = rng.integers(0, 255, (h // 2, w // 2)).astype(np.uint8)
    return cur, ref, ru, rv


def _banded_me(cur, ref, ru, rv, pmv, qp, bands, halo):
    """shard_map harness running the production banded search over
    `bands` devices of the virtual mesh."""
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    H = cur.shape[0]
    Hb = H // bands
    mesh = Mesh(np.array(jax.devices()[:bands]), ("band",))
    real = jnp.full((bands, 1), Hb, jnp.int32)

    def per_band(cy, ry, ru_, rv_, real_b):
        mv, py, pu, pv, med = jaxme.me_search_banded(
            cy, ry, ru_, rv_, pmv, qp, halo_rows=halo, num_bands=bands,
            axis_name="band", real_rows=real_b[0, 0])
        return mv, py, pu, pv, med[None]

    f = shard_map(per_band, mesh=mesh, in_specs=(P("band"),) * 5,
                  out_specs=(P("band"),) * 5)
    return jax.device_get(jax.jit(f)(
        jnp.asarray(cur, jnp.int16), jnp.asarray(ref, jnp.int16),
        jnp.asarray(ru, jnp.int16), jnp.asarray(rv, jnp.int16), real))


@multi_device
class TestBandedMotionSearch:
    def test_bit_identical_when_halo_covers_search(self):
        """4 bands + 32-row halo: (mv, pred, median) must equal the
        full-frame search BIT-EXACTLY — the halo covers the whole
        candidate reach and the probe/median psums reproduce the
        global centers."""
        cur, ref, ru, rv = _mixed_motion(128, 256)
        pmv = jnp.asarray([2, -3], jnp.int32)
        qp = jnp.asarray(27, jnp.int32)
        full = jax.device_get(jaxme.me_search(
            jnp.asarray(cur, jnp.int16), jnp.asarray(ref, jnp.int16),
            jnp.asarray(ru, jnp.int16), jnp.asarray(rv, jnp.int16),
            pmv, qp))
        banded = _banded_me(cur, ref, ru, rv, pmv, qp, bands=4, halo=32)
        names = ["mv", "pred_y", "pred_u", "pred_v"]
        for name, a, b in zip(names, banded, full):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"banded ME diverges from full-frame: {name}")
        assert (np.asarray(banded[4]) == np.asarray(full[4])).all(), \
            "per-band medians disagree with the global median"
        # the content really does split per-MB decisions
        assert len({tuple(v) for v in full[0].reshape(-1, 2)}) > 1

    def test_small_halo_clamps_vertical_search(self):
        """halo=16 clamps vertical centers to halo_clamp(16)=8 pel:
        vertical motion past the clamp yields BOUNDED divergence —
        |mvy| never exceeds 2*(clamp + window) half-pel — instead of
        out-of-halo reads or silent drift."""
        assert jaxme.halo_clamp(32) == 12       # full range (== _CLIM)
        assert jaxme.halo_clamp(16) == 8
        h, w = 128, 128
        rng = np.random.default_rng(3)
        pad = 20
        scene = rng.integers(0, 255, (h + 2 * pad, w + 2 * pad)
                             ).astype(np.uint8)
        ref = scene[pad:pad + h, pad:pad + w]
        cur = scene[pad + 16:pad + 16 + h, pad:pad + w]   # 16 px down
        ru = rng.integers(0, 255, (h // 2, w // 2)).astype(np.uint8)
        rv = rng.integers(0, 255, (h // 2, w // 2)).astype(np.uint8)
        pmv = jnp.zeros(2, jnp.int32)
        qp = jnp.asarray(27, jnp.int32)
        full = jax.device_get(jaxme.me_search(
            jnp.asarray(cur, jnp.int16), jnp.asarray(ref, jnp.int16),
            jnp.asarray(ru, jnp.int16), jnp.asarray(rv, jnp.int16),
            pmv, qp))
        banded = _banded_me(cur, ref, ru, rv, pmv, qp, bands=2, halo=16)
        # full-frame finds the true 16-pel (32 half-unit) motion...
        assert int(np.abs(full[0][..., 0]).max()) == 32
        # ...the clamped band search stays within its documented bound
        bound = 2 * (jaxme.halo_clamp(16) + 4)
        assert int(np.abs(banded[0][..., 0]).max()) <= bound


@multi_device
class TestSfeConformance:
    def test_multi_band_decode_parity(self):
        w, h, n = 64, 128, 6
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc, stream = encode_sfe(clip(w, h, n), meta, gop_frames=3,
                                 bands=4)
        assert enc.num_bands == 4
        assert_decode_parity(enc, stream, n)

    def test_partial_last_band(self):
        # 7 MB rows over 4 bands: the last band carries a padding row
        # that is computed but never entropy-coded
        w, h, n = 64, 112, 4
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc, stream = encode_sfe(clip(w, h, n), meta, bands=4)
        assert [b.mb_rows for b in enc.band_plan.bands] == [2, 2, 2, 1]
        assert_decode_parity(enc, stream, n)

    def test_thin_bands_clamped_halo(self):
        # 1-MB-row bands force the halo down to the band height (16):
        # vertically-clamped search, still conformant
        w, h, n = 64, 96, 4
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc, stream = encode_sfe(clip(w, h, n, vstep=2), meta, bands=6,
                                 halo_rows=32)
        assert enc.halo_rows == 16
        assert_decode_parity(enc, stream, n)

    def test_escape_dense_fallback(self):
        # qp 4 noise: levels exceed int8, every GOP reruns through the
        # dense transfer — levels identical, stream still conformant
        rng = np.random.default_rng(7)
        w, h, n = 64, 128, 4
        frames = [Frame(
            y=rng.integers(0, 256, (h, w), dtype=np.uint8),
            u=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8),
            v=rng.integers(0, 256, (h // 2, w // 2), dtype=np.uint8))
            for _ in range(n)]
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc, stream = encode_sfe(frames, meta, qp=4, gop_frames=4,
                                 bands=4)
        snap = enc.stages.snapshot()
        assert snap["dense_fallback_waves"] >= 1
        assert snap["sfe_frames"] == n
        assert_decode_parity(enc, stream, n)

    def test_cropped_display_dimensions(self):
        # non-MB-multiple display dims: band slices + frame cropping
        w, h, n = 70, 110, 4
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc, stream = encode_sfe(clip(w, h, n), meta, bands=3)
        assert_decode_parity(enc, stream, n)

    def test_single_band_byte_identical_to_gop_encoder(self):
        """bands=1 degrades to one slice per frame: the stream must be
        BYTE-identical to the existing single-device GOP encode — SFE
        introduces no bitstream change until it actually shards."""
        w, h, n = 64, 128, 3
        frames = clip(w, h, n)
        meta = VideoMeta(width=w, height=h, num_frames=n)
        _, stream = encode_sfe(frames, meta, gop_frames=3, bands=1)
        want = encode_gop(frames, meta, qp=27, idr_pic_id=0)
        assert stream == want

    def test_per_frame_latency_recorded(self):
        from thinvids_tpu.parallel.dispatch import (
            _SFE_LAT_MS, frame_latency_percentiles)

        w, h, n = 64, 96, 6
        meta = VideoMeta(width=w, height=h, num_frames=n)
        before = frame_latency_percentiles().get("count", 0)
        enc, _ = encode_sfe(clip(w, h, n), meta, gop_frames=3, bands=2)
        snap = enc.stages.snapshot()
        assert snap["sfe_frames"] == n and snap["sfe"] > 0
        # every frame but the pass's first adds its gap to the ring
        pct = frame_latency_percentiles()
        assert pct["count"] == min(before + n - 1, _SFE_LAT_MS.maxlen)
        assert pct["p99_ms"] >= pct["p50_ms"] >= 0

    def test_oracle_decode_parity(self):
        from thinvids_tpu.tools import oracle

        if not oracle.oracle_available():
            pytest.skip("libavcodec missing")
        w, h, n = 64, 128, 5
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc, stream = encode_sfe(clip(w, h, n), meta, gop_frames=5,
                                 bands=4)
        decoded = oracle.decode_h264(stream)
        assert len(decoded) == n
        for i, (oy, ou, ov) in enumerate(decoded):
            ry, ru, rv = enc.recon_frames[i]
            for name, got, want in (("y", oy, ry), ("u", ou, ru),
                                    ("v", ov, rv)):
                np.testing.assert_array_equal(
                    got, want[:got.shape[0], :got.shape[1]],
                    err_msg=f"frame {i} {name}")


@multi_device
class TestMultiSliceBitstream:
    def _stream(self, qp=27, gop_qp=None):
        w, h, n = 64, 128, 2
        frames = clip(w, h, n)
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc = SfeShardEncoder(meta, qp=qp, gop_frames=2, bands=4,
                              halo_rows=32)
        if gop_qp:
            enc.gop_qp.update(gop_qp)
        return enc, concat_segments(enc.encode(frames))

    def _slice_headers(self, stream):
        from thinvids_tpu.codecs.h264.headers import (NAL_PPS, NAL_SPS,
                                                      PPS, SPS,
                                                      SliceHeader)
        from thinvids_tpu.io.bits import BitReader, split_annexb

        sps = pps = None
        headers = []
        for ri, t, rbsp in split_annexb(stream):
            if t == NAL_SPS:
                sps = SPS.parse_rbsp(rbsp)
            elif t == NAL_PPS:
                pps = PPS.parse_rbsp(rbsp)
            elif t in (1, 5):
                headers.append(SliceHeader.parse(
                    BitReader(rbsp), sps, pps, t, ri))
        return sps, headers

    def test_first_mb_covers_picture_without_overlap(self):
        enc, stream = self._stream()
        sps, headers = self._slice_headers(stream)
        mbw = sps.mb_width
        per_frame = [headers[i:i + 4] for i in range(0, len(headers), 4)]
        assert len(per_frame) == 2
        for hs in per_frame:
            assert [h.first_mb for h in hs] == \
                [b.start_mb_row * mbw for b in enc.band_plan.bands]
            # same picture: one frame_num, and all IDR slices share
            # idr_pic_id (§7.4.3)
            assert len({h.frame_num for h in hs}) == 1
            if hs[0].idr:
                assert len({h.idr_pic_id for h in hs}) == 1

    def test_slice_qp_delta_per_band_slice(self):
        # per-GOP QP override: EVERY band slice of the GOP must carry
        # the override against the PPS base
        enc, stream = self._stream(qp=27, gop_qp={0: 33})
        _, headers = self._slice_headers(stream)
        assert all(h.qp == 33 for h in headers)

    def test_mp4_mux_groups_band_slices_per_picture(self):
        from thinvids_tpu.io.mp4 import annexb_to_samples, mux_mp4

        enc, stream = self._stream()
        _, _, samples, keys = annexb_to_samples(stream)
        assert len(samples) == 2            # one sample per PICTURE
        assert keys == [True, False]
        meta = VideoMeta(width=64, height=128, num_frames=2)
        assert mux_mp4(stream, meta)        # muxes without error

    def test_oracle_au_splitter_groups_band_slices(self):
        from thinvids_tpu.tools.oracle import split_access_units

        _, stream = self._stream()
        aus = split_access_units(stream)
        assert len(aus) == 2                # one AU per picture

    def test_oracle_au_splitter_keeps_param_sets_with_next_idr(self):
        # two GOPs: the second GOP's SPS/PPS must open ITS access unit,
        # not ride on the tail of the previous picture's AU
        from thinvids_tpu.tools.oracle import split_access_units

        w, h, n = 64, 128, 4
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc = SfeShardEncoder(meta, qp=27, gop_frames=2, bands=4,
                              halo_rows=32)
        stream = concat_segments(enc.encode(clip(w, h, n)))
        aus = split_access_units(stream)
        assert len(aus) == n
        # AU 2 (second GOP's IDR) begins with the re-emitted SPS NAL
        start = aus[2].find(b"\x00\x00\x01") + 3
        assert aus[2][start] & 0x1F == 7    # NAL_SPS
        # AU 1 (last P of GOP 0) carries no parameter sets
        assert all((nal & 0x1F) not in (7, 8) for nal in
                   [aus[1][m + 3] for m in
                    _start_positions(aus[1])])

    def test_slice_first_mb_helper(self):
        from thinvids_tpu.io.bits import slice_first_mb
        from thinvids_tpu.io.mp4 import split_annexb as raw_nals

        _, stream = self._stream()
        firsts = [slice_first_mb(n) for n in raw_nals(stream)
                  if n[0] & 0x1F in (1, 5)]
        assert firsts[:4] == sorted(firsts[:4]) and firsts[0] == 0
        assert firsts[1] > 0


class TestDecoderInter:
    """The decoder's P-slice support, validated against the encoder's
    closed-loop recon on SINGLE-slice streams (whose bit-exactness vs
    libavcodec is already established by tests/test_inter.py) — the
    in-container conformance bar when no oracle is installed."""

    @pytest.mark.parametrize("qp,step", [(27, 3), (20, 12), (35, 2)])
    def test_p_decode_matches_recon(self, qp, step):
        w, h, n = 64, 48, 5
        frames = clip(w, h, n, step=step)
        meta = VideoMeta(width=w, height=h, num_frames=n)
        stream, recons = encode_gop(frames, meta, qp=qp,
                                    return_recon=True)
        dec = decode_annexb(stream)
        assert len(dec.frames) == n
        ry, ru, rv = recons
        for i, f in enumerate(dec.frames):
            for name, got, want in (("y", f.y, ry[i]), ("u", f.u, ru[i]),
                                    ("v", f.v, rv[i])):
                want = np.asarray(want).astype(np.uint8)
                np.testing.assert_array_equal(
                    got, want[:got.shape[0], :got.shape[1]],
                    err_msg=f"frame {i} {name}")

    def test_skip_runs_decode(self):
        yy, xx = np.mgrid[0:64, 0:96]
        y = ((xx + yy) % 256).astype(np.uint8)
        frames = [Frame(y.copy(), np.full((32, 48), 100, np.uint8),
                        np.full((32, 48), 150, np.uint8))
                  for _ in range(6)]
        meta = VideoMeta(width=96, height=64, num_frames=6)
        stream, recons = encode_gop(frames, meta, qp=27,
                                    return_recon=True)
        dec = decode_annexb(stream)
        for i, f in enumerate(dec.frames):
            np.testing.assert_array_equal(
                f.y, np.asarray(recons[0][i]).astype(np.uint8)[:64, :96])


class TestExecutorWiring:
    def test_sfe_bands_selects_encoder(self):
        from thinvids_tpu.cluster.executor import LocalExecutor
        from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
        from thinvids_tpu.parallel.dispatch import GopShardEncoder

        meta = VideoMeta(width=64, height=96, num_frames=4)
        on = Settings(values=dict(DEFAULT_SETTINGS, sfe_bands=2))
        off = Settings(values=dict(DEFAULT_SETTINGS))
        enc_on = LocalExecutor._default_encoder(meta, on, None)
        enc_off = LocalExecutor._default_encoder(meta, off, None)
        assert isinstance(enc_on, SfeShardEncoder)
        assert enc_on.num_bands == 2
        assert type(enc_off) is GopShardEncoder

    def test_settings_clamps(self):
        from thinvids_tpu.core.config import _validate_setting

        assert _validate_setting("sfe_bands", -3) == 0
        assert _validate_setting("sfe_bands", "999") == 64
        assert _validate_setting("sfe_halo_rows", 40) == 32   # 16-align
        assert _validate_setting("sfe_halo_rows", 7) == 16
        assert _validate_setting("sfe_halo_rows", 1000) == 128

    @multi_device
    def test_executor_job_to_done_with_sfe(self, tmp_path):
        """Full data plane with sfe_bands set: Job → SFE encode →
        multi-slice MP4 → DONE, and the output decodes to the right
        frame count via the in-repo decoder."""
        from thinvids_tpu.cluster import Coordinator, WorkerRegistry
        from thinvids_tpu.cluster.executor import LocalExecutor
        from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings
        from thinvids_tpu.core.status import Status
        from thinvids_tpu.io.mp4 import read_mp4
        from thinvids_tpu.io.y4m import write_y4m

        w, h, n = 64, 96, 8
        meta = VideoMeta(width=w, height=h, fps_num=30, fps_den=1,
                         num_frames=n)
        path = tmp_path / "clip.y4m"
        write_y4m(path, meta, clip(w, h, n))
        snap = Settings(values=dict(
            DEFAULT_SETTINGS, gop_frames=4, qp=30, sfe_bands=3,
            heartbeat_throttle_s=0.0))
        reg = WorkerRegistry()
        for i in range(8):
            reg.heartbeat(f"w{i:02d}")
        coord = Coordinator(registry=reg, settings_fn=lambda: snap)
        execu = LocalExecutor(coord, output_dir=str(tmp_path / "lib"),
                              sync=True)
        coord._launcher = execu.launch
        job = coord.add_job(str(path), meta)   # sync launcher runs it
        st = coord.store.get(job.id)
        assert st.status is Status.DONE, st.failure_reason
        assert st.parts_done == st.parts_total == 2   # fixed GOP grid
        media = read_mp4(st.output_path)
        dec = decode_annexb(media.annexb)
        assert len(dec.frames) == n


class TestSfeRdFeatures:
    """Split-frame encoding with the RD features on: band slices must
    stay conformant (recon == independent decode) for every band
    count, the in-loop filter must run SLICE-locally (every band
    slice signals disable_deblocking_filter_idc 2 and filters its own
    rows, §8.7's order ends at a slice's first row), and the per-band
    mode decision must stay SLICE-local too."""

    RD_ON = None     # set lazily (rdo import inside jax-ready process)

    @classmethod
    def _rd_on(cls):
        from thinvids_tpu.codecs.h264.rdo import RdConfig

        return RdConfig(mode_decision=True, pskip=True, deblock=True)

    @multi_device
    def test_bands_decode_parity_features_on(self):
        # 7 MB rows across 3 uneven bands: the last band carries
        # padding rows, so one case covers bands > 1 conformance AND
        # the deblock row masks stopping at the picture's real rows
        w, h, n = 96, 112, 4
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc, stream = encode_sfe(clip(w, h, n), meta, bands=3,
                                 rd=self._rd_on())
        assert_decode_parity(enc, stream, n)

    @multi_device
    def test_band_slices_signal_idc2_and_libavcodec_agrees(self):
        """A two-slice deblocked picture: every slice header carries
        idc 2, and libavcodec — which then filters no edge between the
        two slices — decodes the encoder's recon, sample for sample
        (tolerance zero: the recon is the next frame's reference).
        The in-repo decoder reads the same idc and agrees too."""
        from thinvids_tpu.codecs.h264.headers import (PPS, SPS,
                                                      SliceHeader)
        from thinvids_tpu.io.bits import BitReader, split_annexb
        from thinvids_tpu.tools import oracle

        w, h, n = 96, 112, 4
        meta = VideoMeta(width=w, height=h, num_frames=n)
        enc, stream = encode_sfe(clip(w, h, n), meta, bands=2, qp=30,
                                 rd=self._rd_on())
        sps = pps = None
        idcs = []
        for ref_idc, typ, rbsp in split_annexb(stream):
            if typ == 7:
                sps = SPS.parse_rbsp(rbsp)
            elif typ == 8:
                pps = PPS.parse_rbsp(rbsp)
            elif typ in (1, 5):
                idcs.append(SliceHeader.parse(
                    BitReader(rbsp), sps, pps, typ, ref_idc).deblock_idc)
        assert idcs == [2] * (2 * n)
        dec = assert_decode_parity(enc, stream, n)
        if not oracle.oracle_available():
            pytest.skip("libavcodec oracle not available")
        decoded = oracle.decode_h264(stream)
        assert len(decoded) == n
        for i, planes in enumerate(decoded):
            for got, want, ours in zip(planes, enc.recon_frames[i],
                                       (dec.frames[i].y, dec.frames[i].u,
                                        dec.frames[i].v)):
                np.testing.assert_array_equal(got, want,
                                              err_msg=f"frame {i}")
                np.testing.assert_array_equal(ours, got)

    @multi_device
    @pytest.mark.slow
    def test_single_band_features_match_gop_encoder(self):
        """bands=1 with features on stays byte-identical to the
        single-device GOP encode with the same RdConfig."""
        w, h, n = 64, 128, 3
        frames = clip(w, h, n)
        meta = VideoMeta(width=w, height=h, num_frames=n)
        _, stream = encode_sfe(frames, meta, gop_frames=3, bands=1,
                               rd=self._rd_on())
        want = encode_gop(frames, meta, qp=27, idr_pic_id=0,
                          rd=self._rd_on())
        assert stream == want

    @multi_device
    def test_band_mode_decision_is_slice_local(self):
        """Regression (slice-relative row 0): every band slice's FIRST
        MB row must never pick vertical prediction — the MBs above
        live in another slice and are unavailable to a conformant
        decoder. Checked at the device output, for the mode-decision
        path and the fixed fallback policy alike."""
        import jax.numpy as jnp

        from thinvids_tpu.codecs.h264 import jaxinter
        from thinvids_tpu.codecs.h264.intra import LUMA_V
        from thinvids_tpu.codecs.h264.rdo import RD_OFF, RdConfig

        w, h = 96, 64
        f = clip(w, h, 1)[0].padded(16)
        mbw, band_rows = w // 16, 2        # a 2-MB-row band slice
        for rd in (RD_OFF, RdConfig(mode_decision=True)):
            out = jaxinter._intra_core(
                jnp.asarray(f.y[:16 * band_rows]),
                jnp.asarray(f.u[:8 * band_rows]),
                jnp.asarray(f.v[:8 * band_rows]),
                jnp.asarray(27), mbw=mbw, mbh=band_rows, rd=rd)
            modes = np.asarray(out[7]).reshape(band_rows, mbw)
            assert (modes[0] != LUMA_V).all(), rd

    @multi_device
    def test_sfe_strips_aq(self):
        """Perceptual AQ is frame-global (the activity mean); the
        banded encoder must strip it instead of encoding a map that
        depends on the band count."""
        from thinvids_tpu.codecs.h264.rdo import RdConfig

        meta = VideoMeta(width=64, height=96, num_frames=2)
        enc = SfeShardEncoder(meta, qp=27, bands=2,
                              rd=RdConfig(aq_q=4, pskip=True))
        assert enc.rd.aq_q == 0 and enc.rd.pskip

    def test_farm_band_slice_rejects_deblock(self):
        """Cross-host band SLICES have never run with the in-loop
        filter; construction must refuse (the remote planner keeps GOP
        shards for deblock jobs)."""
        from thinvids_tpu.codecs.h264.rdo import RdConfig

        meta = VideoMeta(width=64, height=192, num_frames=2)
        with pytest.raises(ValueError, match="deblock"):
            SfeShardEncoder(meta, qp=27, total_bands=3,
                            band_range=(0, 1),
                            rd=RdConfig(deblock=True))

    def test_remote_planner_gate(self):
        """deblock-enabled jobs keep GOP-range shards on the farm."""
        from thinvids_tpu.cluster.remote import RemoteExecutor
        from thinvids_tpu.core.config import DEFAULT_SETTINGS, Settings

        class _Job:
            job_type = "transcode"

        on = Settings(values=dict(DEFAULT_SETTINGS, sfe_bands=4,
                                  deblock=True))
        off = Settings(values=dict(DEFAULT_SETTINGS, sfe_bands=4))
        assert RemoteExecutor._band_shape(_Job(), off)
        assert not RemoteExecutor._band_shape(_Job(), on)
