"""Tests for thinvids_tpu.core: status, config layering, events, types."""

import numpy as np
import pytest

from thinvids_tpu.core import (
    ActivityLog,
    DEFAULT_SETTINGS,
    Frame,
    GopSpec,
    EncodedSegment,
    Status,
    get_settings,
)
from thinvids_tpu.core.config import (
    as_bool,
    as_int,
    invalidate_settings_cache,
    overlay_job_settings,
    reset_live_settings,
    update_live_settings,
)
from thinvids_tpu.core.types import concat_segments, pad_to_multiple, pad_to_shape


class TestStatus:
    def test_parse_lenient(self):
        assert Status.parse("RUNNING") is Status.RUNNING
        assert Status.parse("  done \n") is Status.DONE
        assert Status.parse(Status.FAILED) is Status.FAILED

    def test_parse_unknown_raises(self):
        # Matches the reference (common.py:95-97): corrupted status must not
        # silently become schedulable.
        with pytest.raises(ValueError):
            Status.parse("garbage")
        with pytest.raises(ValueError):
            Status.parse(None)
        assert Status.parse("garbage", default=Status.FAILED) is Status.FAILED

    def test_active_terminal(self):
        assert Status.RUNNING.is_active
        assert Status.STARTING.is_active
        assert not Status.WAITING.is_active
        assert Status.DONE.is_terminal
        assert not Status.RUNNING.is_terminal


class TestConfig:
    def setup_method(self):
        reset_live_settings()

    def teardown_method(self):
        reset_live_settings()

    def test_invalidate_keeps_live_overrides(self):
        update_live_settings({"qp": 30})
        invalidate_settings_cache()
        assert get_settings().qp == 30

    def test_job_settings_overlay(self):
        s = get_settings(refresh=True)
        j = overlay_job_settings(s, {"qp": "99", "unknown": 1, "gop_frames": 8})
        assert j.qp == 51 and j.gop_frames == 8
        assert "unknown" not in j.values
        assert get_settings().qp == DEFAULT_SETTINGS["qp"]  # base untouched

    def test_defaults(self):
        s = get_settings(refresh=True)
        assert s.qp == DEFAULT_SETTINGS["qp"]
        assert s.gop_frames == 32

    def test_live_override_and_clamp(self):
        update_live_settings({"qp": "99", "gop_frames": 16, "bogus_key": 1})
        s = get_settings(refresh=True)
        assert s.qp == 51  # clamped
        assert s.gop_frames == 16
        assert "bogus_key" not in s.values

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("TVT_QP", "33")
        s = get_settings(refresh=True)
        assert s.qp == 33

    def test_effective_max_active_jobs(self):
        update_live_settings({"pipeline_worker_count": 10, "max_active_jobs": 0})
        s = get_settings(refresh=True)
        assert s.effective_max_active_jobs() == 5
        update_live_settings({"max_active_jobs": 3})
        assert get_settings(refresh=True).effective_max_active_jobs() == 3

    def test_coercions(self):
        assert as_bool("yes") and as_bool("1") and not as_bool("off")
        assert as_int("12.7") == 12
        assert as_int("junk", 5) == 5


class TestActivityLog:
    def test_emit_fetch_labels(self):
        log = ActivityLog(cap=4)
        log.emit("encode_part", "part finished", job_id="j1", part=3, elapsed_ms=120)
        log.emit("job_failed", "boom", job_id="j1")
        events = log.fetch()
        assert events[0]["label"] == "ERROR"
        assert events[1]["label"] == "ENCODE"
        lines = log.fetch_job("j1")
        assert len(lines) == 2
        assert "part=3" in lines[0]

    def test_cap(self):
        log = ActivityLog(cap=2)
        for i in range(5):
            log.emit("start", f"e{i}")
        assert len(log.fetch()) == 2


class TestTypes:
    def test_pad_to_multiple(self):
        p = np.arange(20, dtype=np.uint8).reshape(4, 5)
        out = pad_to_multiple(p, 16)
        assert out.shape == (16, 16)
        assert (out[:4, :5] == p).all()
        assert out[3, 10] == p[3, 4]  # edge replication

    def test_frame_padded_chroma(self):
        y = np.zeros((30, 50), np.uint8)
        u = np.zeros((15, 25), np.uint8)
        v = np.zeros((15, 25), np.uint8)
        f = Frame(y, u, v).padded(16)
        assert f.y.shape == (32, 64)
        assert f.u.shape == (16, 32)

    def test_frame_padded_422(self):
        # ADVICE.md repro: 4:2:2 h=40 → luma pads to 48 rows, chroma must too.
        y = np.zeros((40, 64), np.uint8)
        u = np.zeros((40, 32), np.uint8)
        f = Frame(y, u, u.copy()).padded(16)
        assert f.y.shape == (48, 64)
        assert f.u.shape == (48, 32)

    def test_frame_padded_odd_420(self):
        # ADVICE.md repro: w=33 (chroma 17) → luma 48 cols, chroma 24 cols.
        y = np.zeros((32, 33), np.uint8)
        u = np.zeros((16, 17), np.uint8)
        f = Frame(y, u, u.copy()).padded(16)
        assert f.y.shape == (32, 48)
        assert f.u.shape == (16, 24)

    def test_frame_chroma_classification(self):
        y = np.zeros((32, 64), np.uint8)
        c420 = np.zeros((16, 32), np.uint8)
        c422 = np.zeros((32, 32), np.uint8)
        c444 = np.zeros((32, 64), np.uint8)
        from thinvids_tpu.core import ChromaFormat
        assert Frame(y, c420, c420).chroma is ChromaFormat.YUV420
        assert Frame(y, c422, c422).chroma is ChromaFormat.YUV422
        assert Frame(y, c444, c444).chroma is ChromaFormat.YUV444
        assert Frame(y).chroma is ChromaFormat.YUV400
        c440 = np.zeros((16, 64), np.uint8)
        with pytest.raises(ValueError, match="4:4:0"):
            Frame(y, c440, c440).chroma

    def test_frame_missing_v_raises(self):
        y = np.zeros((16, 16), np.uint8)
        u = np.zeros((8, 8), np.uint8)
        with pytest.raises(ValueError):
            Frame(y, u, None).padded(16)

    def test_pad_to_shape(self):
        p = np.arange(6, dtype=np.uint8).reshape(2, 3)
        out = pad_to_shape(p, 4, 4)
        assert out.shape == (4, 4) and out[3, 3] == p[1, 2]
        with pytest.raises(ValueError):
            pad_to_shape(p, 1, 3)

    def test_concat_order_and_missing(self):
        segs = [
            EncodedSegment(GopSpec(1, 32, 32), b"b"),
            EncodedSegment(GopSpec(0, 0, 32), b"a"),
        ]
        assert concat_segments(segs) == b"ab"
        with pytest.raises(ValueError, match="missing"):
            concat_segments([EncodedSegment(GopSpec(1, 32, 32), b"b")])

    def test_concat_duplicate_reports_duplicate(self):
        # Retry re-dispatch produces duplicates; the error must say so.
        segs = [
            EncodedSegment(GopSpec(0, 0, 32), b"a"),
            EncodedSegment(GopSpec(0, 0, 32), b"a2"),
        ]
        with pytest.raises(ValueError, match="duplicate"):
            concat_segments(segs)


class TestCompileCache:
    """core.devices.configure_compile_cache: the environment places the
    cache; the code only supplies the fixed in-checkout default."""

    @pytest.fixture
    def cache_config(self):
        import jax

        before = jax.config.jax_compilation_cache_dir
        yield jax.config
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_set_code_sets_nothing(self, monkeypatch, cache_config):
        from thinvids_tpu.core import devices

        before = cache_config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert devices.configure_compile_cache() == "/somewhere/else"
        assert cache_config.jax_compilation_cache_dir == before

    def test_env_unset_uses_fixed_checkout_path(self, monkeypatch,
                                                cache_config):
        import os

        from thinvids_tpu.core import devices

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert devices.DEFAULT_COMPILE_CACHE == want
        assert devices.configure_compile_cache() == want
        assert cache_config.jax_compilation_cache_dir == want
