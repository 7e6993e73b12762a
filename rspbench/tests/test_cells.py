"""Whole runs of every cell, cut to a size the CPU runs in seconds.

A sound run is correct; the control (the reference in the program's place,
one precision below the configuration's) is not; and a fault planted in
the timed path underneath the harness turns ``correct`` false, once for
each fault the cell can have.  The cells run on one chip, so there is no
exchange between chips to leave out."""

import dataclasses
import os

import numpy as np
import pytest
import tiny

import harness

BENCH = harness.load_json(os.path.join(tiny.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
QUERY_CELLS = [c for c in CELLS if harness.load_cell(c, BENCH).traffic["driver"] == "closed_loop"]
INGEST_CELLS = [c for c in CELLS if harness.load_cell(c, BENCH).traffic["driver"] == "ingest"]


def failed_checks(res: dict) -> list[str]:
    return [k for k, c in res["checks"].items()
            if c["value"] is not None
            and not (isinstance(c["value"], (int, float)) and c["value"] <= c["limit"])]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, tmp_path):
    res = tiny.run_tiny(name, tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    cell = harness.load_cell(name, BENCH)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_non_finite_number_keeps_the_result_strict_json(tmp_path, monkeypatch):
    import json

    from yardstick import store_check

    monkeypatch.setattr(store_check, "read_sketches", lambda path: [])
    res = tiny.run_tiny(INGEST_CELLS[0], tmp_path)
    assert not res["correct"] and res["checks"]["sketch_gap"]["value"] == "inf"
    json.loads(json.dumps(res), parse_constant=lambda c: pytest.fail(f"non-JSON constant {c}"))


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reads_the_layers(name, tmp_path):
    res = tiny.run_tiny(name, tmp_path, "--trace", "1")
    assert res["correct"], res["checks"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    cell = harness.load_cell(name, BENCH)
    wanted = {m["name"] for m in cell.per_layer}
    # the CPU has no device plane, so the device readers and the device
    # folds find nothing there; the serving layer's and the executor's
    # readers do
    on_cpu = wanted & {"serve.step_ms", "query_p95_ms", "engine.hit_pct",
                       "kernel.fold_peak_pct"}
    assert on_cpu <= set(res["metrics"]) <= wanted


@pytest.mark.parametrize("name,control", [(c, "bf16") for c in CELLS]
                         + [(c, k) for c in INGEST_CELLS for k in ("bf16_sketch", "chunked")])
def test_control_is_not_correct(name, control, tmp_path):
    res = tiny.run_tiny(name, tmp_path, "--control", control)
    assert not res["correct"]
    assert failed_checks(res)


# -- faults planted in the timed path -------------------------------------------

def _every_other(method):
    """``method`` that leaves its state unchanged on every second call."""
    calls = {}

    def wrapped(self, *a, **kw):
        calls[id(self)] = calls.get(id(self), 0) + 1
        if calls[id(self)] % 2 == 0:
            return None
        return method(self, *a, **kw)

    return wrapped


def _first_half(fn):
    def wrapped(block, *a, **kw):
        block = np.asarray(block)
        return fn(block[: block.shape[0] // 2], *a, **kw)

    return wrapped


def _altered_result(method):
    def wrapped(self):
        r = method(self)
        if r.kind != "mean":
            return r
        est = np.asarray(r.estimate, np.float64)
        return dataclasses.replace(r, estimate=est + 1e-3 * (1.0 + np.abs(est)))

    return wrapped


def plant_query_fault(monkeypatch, fault: str) -> None:
    from repro.rsp import query

    if fault == "state_unchanged":
        monkeypatch.setattr(query._MomentAgg, "update", _every_other(query._MomentAgg.update))
        monkeypatch.setattr(query._HistAgg, "update", _every_other(query._HistAgg.update))
    elif fault == "half_batch":
        monkeypatch.setattr(query, "plan_sketch", _first_half(query.plan_sketch))
        monkeypatch.setattr(query, "block_sketch", _first_half(query.block_sketch))
    elif fault == "answer_altered":
        monkeypatch.setattr(query._MomentAgg, "result", _altered_result(query._MomentAgg.result))


def _every_second_row(method):
    def wrapped(self, rows, *a, **kw):
        return method(self, np.asarray(rows)[::2], *a, **kw)

    return wrapped


def plant_ingest_fault(monkeypatch, fault: str) -> None:
    """Breaks what ``rsp.partition(..., out=)`` writes: the sketches as they
    are folded, or the store after it was written."""
    import json

    from repro import rsp
    from repro.rsp import sketch

    if fault == "sketch_subsampled":        # quantile and distinct sketches fold half the rows
        monkeypatch.setattr(sketch.KLLSketch, "update", _every_second_row(sketch.KLLSketch.update))
        monkeypatch.setattr(sketch.DistinctSketch, "update",
                            _every_second_row(sketch.DistinctSketch.update))
        return
    real = rsp.partition

    def partition(data, *, blocks, out, **kw):
        ds = real(data, blocks=blocks, out=out, **kw)
        n = data.shape[0] // blocks
        for k in range(blocks):
            path = os.path.join(out, f"block_{k:05d}.npy")
            b = np.load(path)
            if fault == "state_unchanged":      # the shuffle returned its input
                b = np.asarray(data[k * n:(k + 1) * n])
            elif fault == "half_batch":         # half of every block left out
                b[n // 2:] = b[: n - n // 2]
            elif fault == "answer_altered" and k == 0:
                b[0, 0] += np.float32(1e-3)
            np.save(path, b)
        if fault == "sketch_skipped":        # only the moments and label counts written
            path = os.path.join(out, "sketches.json")
            with open(path) as f:
                doc = json.load(f)
            for suite in doc["summaries"]:
                suite["sketches"] = {k: v for k, v in suite["sketches"].items()
                                     if k in ("moments", "labels")}
            with open(path, "w") as f:
                json.dump(doc, f)
        return ds

    monkeypatch.setattr(rsp, "partition", partition)


FAULTS = ("state_unchanged", "half_batch", "answer_altered")
INGEST_FAULTS = FAULTS + ("sketch_skipped", "sketch_subsampled")


@pytest.mark.parametrize("name,fault", [(c, f) for c in QUERY_CELLS for f in FAULTS]
                         + [(c, f) for c in INGEST_CELLS for f in INGEST_FAULTS])
def test_fault_in_the_timed_path_is_not_correct(name, fault, tmp_path, monkeypatch):
    if name in QUERY_CELLS:
        plant_query_fault(monkeypatch, fault)
    else:
        plant_ingest_fault(monkeypatch, fault)
    res = tiny.run_tiny(name, tmp_path)
    assert not res["correct"]
    assert failed_checks(res) or res["failed"] > 0
