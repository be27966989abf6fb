"""The metric arithmetic on canned job records, spans and snapshots."""

import pytest

from tvtbench import evidence, roofline
from tvtbench.spec import load_module


def job(name, submit, done, created, started, finished, frames=32,
        video_bytes=600_000, spans=None, wrapped=False, status="done"):
    out = {"name": name, "frames": frames, "submit_t": submit,
           "done_t": done, "video_bytes": video_bytes, "settings": {},
           "record": {"status": status, "created_at": created,
                      "started_at": started, "finished_at": finished,
                      "elapsed_s": max(0.0, finished - started)}}
    if spans is not None:
        out["trace"] = {"wrapped": wrapped, "spans": [
            {"name": n, "t0": t0, "dur": d} for n, t0, d in spans]}
    return out


@pytest.fixture
def ev():
    return {
        "cell": "c", "chips": 1, "width": 1920, "height": 1080,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "job_settings": {}, "setup_s": 31.5, "psnr_y_db": [35.5, 36.5],
        "window": {"t_first_submit": 100.0, "t_last_done": 110.0},
        "jobs": [
            job("w1", 100.0, 103.0, 100.1, 100.2, 102.9,
                spans=[("decode", 100.5, 0.1), ("wave_collect", 100.6, 2.0)]),
            job("w2", 103.0, 106.5, 103.1, 103.4, 106.4,
                spans=[("decode", 103.9, 0.1), ("pack", 104.0, 2.2)]),
            job("w3", 106.5, 110.0, 106.6, 106.7, 109.9, wrapped=True,
                spans=[("pack", 107.0, 2.0)]),
            job("w4", 110.0, 111.0, 110.1, 110.2, 0.0, status="failed"),
        ],
        "snapshot": {
            "before": {"decode": 10.0, "pack": 100.0, "sparse_unpack": 5.0,
                       "unflatten": 1.0, "sfe": 0.0, "d2h_bytes": 1000,
                       "dense_fallback_waves": 1},
            "after": {"decode": 106.0, "pack": 1060.0, "sparse_unpack": 53.0,
                      "unflatten": 49.0, "sfe": 0.0, "d2h_bytes": 961000,
                      "dense_fallback_waves": 1}},
        "traced_job": "w2",
        "profile": {
            "device_planes": [{"name": "/device:TPU:0"}],
            "busy_s": 1.6, "window_s": 3.2, "t0_epoch_s": 103.5,
            "ops": [["custom-call.3", 1.0, 31], ["fusion.9", 0.4, 62]],
            "me": {"seconds": 0.8, "events": 31},
            "collectives": {"seconds": 0.0, "exposed_seconds": 0.0,
                            "events": 0},
            "gaps": [[0.5, 0.3], [1.0, 0.2], [2.8, 0.1]],
            "gaps_small_s": 0.05},
    }


def read(kind, name, ev):
    return load_module(kind, name).read(ev)


def test_end_to_end_arithmetic(ev):
    assert read("end_to_end", "frames_per_s", ev) == pytest.approx(96 / 10.0)
    assert read("end_to_end", "job_p50_s", ev) == pytest.approx(3.5)
    assert read("end_to_end", "kbit_per_frame", ev) == \
        pytest.approx(3 * 600_000 * 8 / 1000 / 96)
    assert read("end_to_end", "psnr_y_db", ev) == pytest.approx(36.0)
    assert read("end_to_end", "setup_s", ev) == 31.5


def test_job_record_and_span_metrics(ev):
    assert read("layer_metrics", "queue_wait_ms", ev) == pytest.approx(100.0)
    # w1: 2.7 - 2.1 = 0.6 s; w2: 3.0 - 2.3 = 0.7 s; w3 wrapped: left out
    assert read("layer_metrics", "job_fixed_ms", ev) == pytest.approx(650.0)
    # w1: 102.9 - 102.6; w2: 106.4 - 106.2
    assert read("layer_metrics", "mux_ms_per_job", ev) == pytest.approx(250.0)
    # one job's ring wrapped: the share of the window is not measured
    assert read("layer_metrics", "encode_stage_share_pct", ev) is None
    ev["jobs"][2]["trace"]["wrapped"] = False
    # the profiled job (w2, 3.0 s) is taken out of both sides
    assert read("layer_metrics", "encode_stage_share_pct", ev) == \
        pytest.approx(100 * (2.1 + 2.0) / (10.0 - 3.0))


def test_snapshot_deltas_are_per_done_frame(ev):
    assert read("layer_metrics", "decode_ms_per_frame", ev) == \
        pytest.approx(1.0)
    assert read("layer_metrics", "pack_ms_per_frame", ev) == \
        pytest.approx((960 + 48 + 48) / 96)
    assert read("layer_metrics", "d2h_bytes_per_frame", ev) == \
        pytest.approx(10_000)
    assert read("layer_metrics", "dense_fallback_waves", ev) == 0


def test_device_metrics_from_the_reduced_profile(ev):
    assert read("layer_metrics", "device_busy_ms_per_frame", ev) == \
        pytest.approx(50.0)
    assert read("layer_metrics", "device_idle_pct", ev) == pytest.approx(50.0)
    assert read("layer_metrics", "me_kernel_share_pct", ev) == \
        pytest.approx(50.0)
    least = 31 * roofline.me_search_bytes(1080, 1920) / 819e9
    assert read("layer_metrics", "me_kernel_roofline", ev) == \
        pytest.approx(100 * least / 0.8)
    assert read("layer_metrics", "collective_ms_per_frame", ev) is None


def test_without_a_profile_device_metrics_are_not_measured(ev):
    ev["profile"] = None
    for name in ("device_busy_ms_per_frame", "device_idle_pct",
                 "me_kernel_share_pct", "me_kernel_roofline",
                 "collective_ms_per_frame",
                 "collective_exposed_ms_per_frame"):
        assert read("layer_metrics", name, ev) is None


def test_me_bytes_from_shapes():
    # 1088 x 1920 padded: 2 B x (cur Y + ref YUV) + 2 B x pred YUV + MVs
    luma, chroma = 1088 * 1920, 2 * 544 * 960
    assert roofline.me_search_bytes(1080, 1920) == \
        2 * (2 * luma + chroma) + 2 * (luma + chroma) + 68 * 120 * 8


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peak("TPU v9", "hbm_bytes_per_s")


def test_breakdown_attributes_gaps_to_the_innermost_host_span(ev):
    out = evidence.breakdown(ev)
    assert out["device_ops"] == [["custom-call.3", 1.0], ["fusion.9", 0.4]]
    gaps = dict(out["idle_gaps"])
    # gap 1 at 104.0-104.3 and gap 2 at 104.5-104.7 lie in `pack`,
    # gap 3 at 106.3-106.4 in nothing of the job
    assert gaps["pack"] == pytest.approx(0.5)
    assert gaps["no host span"] == pytest.approx(0.1)
    assert gaps["gaps under 0.1 ms"] == pytest.approx(0.05)
    ev["profile"]["t0_epoch_s"] = 5.0       # not this job's clock
    assert [k for k, _v in evidence.breakdown(ev)["idle_gaps"]][0] \
        .startswith("unattributed")
