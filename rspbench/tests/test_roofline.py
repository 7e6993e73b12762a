"""Byte counts, roofline shares and the per-layer readers' arithmetic."""

import os

import pytest
import tiny

import harness
from yardstick import peaks, roofline
from yardstick.trace import OPS_LINE, WINDOW, Event

V5E = peaks.peaks("TPU v5 lite")


def test_byte_counts():
    assert roofline.fold_bytes(86016, 29) == 86016 * 29 * 4 == 9_977_856
    assert roofline.shuffle_bytes(38912, 19) == 2 * 38912 * 19 * 4


def test_least_time_and_share():
    assert roofline.least_seconds(819e9, V5E["hbm_bytes_per_s"]) == pytest.approx(1.0)
    assert roofline.share_pct(1.0, 4.0) == 25.0
    assert roofline.share_pct(1.0, 0.0) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def reader(name):
    return harness.load_module(os.path.join(tiny.BENCH, "metrics", f"{name}.py"), name)


def layer(**kw):
    ops = {"/device:TPU:0": [
        Event("/device:TPU:0", OPS_LINE, "%k = (f32[5,29]) custom-call(f32[86016,29] %x)", 0, 1e6),
        Event("/device:TPU:0", OPS_LINE, "%s = f32[38912,19] custom-call(f32[38912,19] %y)", 0, 2e6),
    ]}
    base = dict(
        obs={
            "rsp_kernel_runs_total": [
                ({"kernel": "block_sketch", "impl": "pallas"}, 10.0),
                ({"kernel": "plan", "impl": "np"}, 5.0),
                ({"kernel": "rsp_shuffle", "impl": "pallas"}, 4.0),
            ],
            "rsp_engine_fetch_total": [({"outcome": "hit"}, 1.0), ({"outcome": "miss"}, 3.0)],
            "rsp_engine_fetch_seconds": [({}, 4, 0.2)],
        },
        service={"rsp_serve_step_seconds": [({}, 2, 0.5)],
                 "rsp_serve_admission_wait_seconds": [({}, 0, 0.0)]},
        answers=[{"kind": "filtered", "blocks": 8, "in_window": True, "ms": 30.0},
                 {"kind": "sketch", "blocks": 0, "in_window": True, "ms": 1.0},
                 {"kind": "grouped", "blocks": 4, "in_window": False, "ms": 90.0,
                  "step_at": [0.5, 1.5, 2.5, 3.5]}],
        trace={"ops": ops, "lo": 0.0, "hi": 1e9, "window_s": 1.0, "busy_s": 0.25,
               "events": [Event("/host:CPU", "", WINDOW, 0, 1e9)]},
        facts={"block_rows": 86016, "columns": 29, "block_bytes": 9_977_856,
               "shuffle_rows": 38912},
        peaks=V5E, window_s=2.0, stretch=(1.0, 3.0),
    )
    base.update(kw)
    return harness.Layer(**base)


def test_fold_roofline_counts_device_calls_over_their_device_time():
    value = reader("fold_roofline").read(layer())
    assert value == pytest.approx(100 * 10 * 9_977_856 / 819e9 / 1e-3)


def test_fold_peak_counts_every_fold_and_grouped_blocks_in_the_stretch():
    # 10 pallas + 5 np dispatches, and the grouped answer's 2 folds at 1.5
    # and 2.5 inside the stretch [1, 3)
    value = reader("kernel.fold_peak_pct").read(layer())
    assert value == pytest.approx(100 * (10 + 5 + 2) * 9_977_856 / 2.0 / 819e9)


def test_shuffle_roofline():
    facts = {"shuffle_rows": 38912, "columns": 19}
    value = reader("shuffle_roofline").read(layer(facts=facts))
    assert value == pytest.approx(100 * 4 * 2 * 38912 * 19 * 4 / 819e9 / 2e-3)


def test_readers_with_nothing_to_read_return_none():
    empty = layer(obs={}, service={}, answers=[],
                  trace={"ops": {}, "lo": 0.0, "hi": 1.0, "window_s": 1.0, "busy_s": 0.0},
                  facts={"block_rows": 86016, "columns": 29, "block_bytes": 9_977_856,
                         "shuffle_rows": 38912})
    for name in ("fold_roofline", "kernel.fold_peak_pct",
                 "shuffle_roofline", "engine.hit_pct", "engine.fetch_ms",
                 "serve.step_ms", "query_p95_ms"):
        assert reader(name).read(empty) is None, name


def test_simple_readers():
    lay = layer()
    assert reader("engine.hit_pct").read(lay) == 25.0
    assert reader("engine.fetch_ms").read(lay) == pytest.approx(50.0)
    assert reader("serve.step_ms").read(lay) == 250.0
    assert reader("query_p95_ms").read(lay) == pytest.approx(84.0)
    assert reader("device.idle_pct.query").read(lay) == 75.0
    assert reader("device.idle_pct.ingest").read(lay) == 75.0
