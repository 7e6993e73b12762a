"""Shared tile autotuner: off-mode determinism, measured selection,
persistence round-trips, and interpret-mode exclusion."""

import json
import os

import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import Autotuner, Candidate


def _times(table):
    """A measure() stub returning fixed seconds per candidate label, and a
    call log so tests can assert what was (not) measured."""
    calls = []

    def measure(c):
        calls.append(c.label)
        return table[c.label]

    return measure, calls


@pytest.fixture
def tuner(tmp_path):
    return Autotuner(path=str(tmp_path / "autotune.json"))


CANDS = [Candidate("np", 8192), Candidate("np", 32768), Candidate("ref")]
DEFAULT = Candidate("np", 16384)


def test_off_mode_returns_default_without_measuring(tuner, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert not autotune.enabled()
    measure, calls = _times({})
    got = tuner.choose("k", "r1024xf8:float32", CANDS, measure, default=DEFAULT)
    assert got == DEFAULT
    assert calls == [] and tuner.measurements == 0
    assert not os.path.exists(tuner._file())  # touches no files


@pytest.mark.parametrize("value", ["0", "false", "no", "OFF"])
def test_off_spellings(value, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", value)
    assert not autotune.enabled()


def test_measured_winner_and_cache_hit(tuner, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    measure, calls = _times({"np:8192": 3e-3, "np:32768": 1e-3, "ref": 2e-3})
    got = tuner.choose("k", "key", CANDS, measure, default=DEFAULT, repeats=2)
    assert got == Candidate("np", 32768)
    assert tuner.measurements == 1
    assert calls.count("np:8192") == 2  # best-of-repeats per candidate

    # second call: cached winner, measure never invoked again
    calls.clear()
    again = tuner.choose("k", "key", CANDS, measure, default=DEFAULT)
    assert again == Candidate("np", 32768)
    assert calls == [] and tuner.measurements == 1


def test_persistence_round_trip(tuner, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    measure, _ = _times({"np:8192": 2e-3, "np:32768": 1e-3, "ref": 3e-3})
    tuner.choose("k", "key", CANDS, measure, default=DEFAULT)

    with open(tuner._file()) as f:
        disk = json.load(f)
    (rec,) = disk.values()
    assert rec["impl"] == "np" and rec["tile_rows"] == 32768
    assert not rec["fallback"]
    assert rec["measured_us"]["np:32768"] == pytest.approx(1e3)

    # a fresh process (new Autotuner on the same path) reuses the winner
    fresh = Autotuner(path=tuner._file())
    measure2, calls2 = _times({})
    got = fresh.choose("k", "key", CANDS, measure2, default=DEFAULT)
    assert got == Candidate("np", 32768)
    assert calls2 == [] and fresh.measurements == 0
    assert fresh.lookup("k", "key") == Candidate("np", 32768)
    assert fresh.lookup("k", "other") is None


def test_interpret_mode_candidates_never_win(tuner, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    cands = [Candidate("pallas", t, interpreted=True) for t in (128, 256)]
    cands.append(Candidate("np", 8192))
    # the interpreter "wins" the raw timing -- it must still be excluded
    measure, calls = _times({"pallas:128": 1e-6, "pallas:256": 1e-6, "np:8192": 1e-3})
    got = tuner.choose("k", "key", cands, measure, default=DEFAULT)
    assert got == Candidate("np", 8192)
    assert all(not c.startswith("pallas") for c in calls)
    with open(tuner._file()) as f:
        (rec,) = json.load(f).values()
    assert "pallas:128 (interpret)" in rec["excluded"]


def test_all_excluded_falls_back_to_default(tuner, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    cands = [Candidate("pallas", 128, interpreted=True)]
    measure, calls = _times({})
    got = tuner.choose("k", "key", cands, measure, default=DEFAULT)
    assert got == DEFAULT and calls == []
    with open(tuner._file()) as f:
        (rec,) = json.load(f).values()
    assert rec["fallback"] and rec["us"] is None


def test_failing_candidate_is_disqualified(tuner, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")

    def measure(c):
        if c.impl == "ref":
            raise RuntimeError("boom")
        return 1e-3

    got = tuner.choose("k", "key", CANDS, measure, default=DEFAULT)
    assert got.impl == "np"
    with open(tuner._file()) as f:
        (rec,) = json.load(f).values()
    assert "ref (error)" in rec["excluded"]


def test_compiled_pallas_failure_propagates_on_tpu(tuner, monkeypatch):
    """On a TPU a Pallas candidate that raises is a kernel the chip's compiler
    refused: the error surfaces instead of the tuner quietly picking numpy."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    monkeypatch.setattr(autotune, "_device", lambda: "tpu")

    def measure(c):
        if c.impl == "pallas":
            raise RuntimeError("Mosaic refused the kernel")
        return 1e-3

    cands = [Candidate("np", 8192), Candidate("pallas", 128)]
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        tuner.choose("k", "key", cands, measure, default=DEFAULT)
    assert not os.path.exists(tuner._file())  # nothing recorded


def test_clear_forgets_disk_and_memory(tuner, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    measure, _ = _times({"np:8192": 1e-3, "np:32768": 2e-3, "ref": 3e-3})
    tuner.choose("k", "key", CANDS, measure, default=DEFAULT)
    assert os.path.exists(tuner._file())
    tuner.clear()
    assert not os.path.exists(tuner._file())
    assert tuner.lookup("k", "key") is None


def test_cache_path_env_override(tmp_path, monkeypatch):
    target = str(tmp_path / "elsewhere.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", target)
    assert autotune.cache_path() == target
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE")
    assert autotune.cache_path().endswith(os.path.join("results", "bench", "autotune.json"))


def test_shape_key_buckets_rows():
    # rows bucket to the next power of two; features/dtype exact
    assert autotune.shape_key(600, 8) == autotune.shape_key(1024, 8) == "r1024xf8:float32"
    assert autotune.shape_key(1025, 8) == "r2048xf8:float32"
    assert autotune.shape_key(1024, 9) != autotune.shape_key(1024, 8)
    assert autotune.shape_key(1024, 8, "float64") != autotune.shape_key(1024, 8)


def test_candidate_labels():
    assert Candidate("ref").label == "ref"
    assert Candidate("np", 8192).label == "np:8192"


def test_auto_paths_deterministic_with_tuning_off(monkeypatch):
    """conftest pins REPRO_AUTOTUNE=off: impl="auto" entry points must not
    run measurements (tier-1 never depends on machine-local timings)."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    from repro.kernels.block_sketch import block_sketch
    from repro.kernels.plan import QueryPlan, plan_sketch
    from repro.kernels.rsp_shuffle import ops as rs_ops

    before = autotune.get_tuner().measurements
    x = np.random.default_rng(0).normal(size=(512, 4)).astype(np.float32)

    a = block_sketch(x, bins=8, lo=-4.0, hi=4.0, impl="auto")
    b = block_sketch(x, bins=8, lo=-4.0, hi=4.0, impl="ref")
    np.testing.assert_allclose(a.mean, b.mean, rtol=1e-5, atol=1e-6)

    plan = QueryPlan(predicates="c0 > 0.0")
    r = plan_sketch(x, plan, impl="auto")
    np.testing.assert_allclose(
        r.sketches[0].mean, plan_sketch(x, plan, impl="ref").sketches[0].mean,
        rtol=1e-5, atol=1e-5,
    )

    import jax

    key = jax.random.PRNGKey(0)
    s1 = np.asarray(rs_ops.rsp_randomize_block(x, key))
    s2 = np.asarray(rs_ops.rsp_randomize_block(x, key))
    np.testing.assert_array_equal(s1, s2)  # tile default is pinned

    assert autotune.get_tuner().measurements == before
