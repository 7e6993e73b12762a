"""The fold paths of the KLL and KMV sketch members.

A fresh KLL column is filled from one sort of the column (positions found by
replaying the compaction schedule); KMV selects each column's smallest new
hashes in O(n).  Both must give exactly what the compactor stack and the
full hash union give: every test compares bits, not tolerances."""

import json

import numpy as np
import pytest

from repro import obs
from repro.kernels.block_sketch import block_sketch_ref
from repro.rsp.sketch import (
    DistinctSketch,
    KLLSketch,
    MomentsSketch,
    SketchSuite,
    _hash_values,
    _KLLColumn,
)
from repro.rsp.summaries import summarize_block


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    yield
    obs.reset()


def _kll_folds() -> dict[str, float]:
    snap = obs.get_registry().snapshot().get("rsp_kll_folds_total", {"series": []})
    return {s["labels"]["path"]: s["value"] for s in snap["series"]}


def _data(kind: str, n: int, f: int = 3, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "gauss":
        return rng.normal(size=(n, f))
    if kind == "ties":
        return rng.integers(0, 4, size=(n, f)).astype(np.float64)
    if kind == "signed_zeros":
        return rng.choice([-0.0, 0.0, -1.5, 2.0], size=(n, f))
    x = rng.normal(size=(n, f))
    nan = rng.random((n, f)) < 0.2
    if kind == "nan":
        x[nan] = np.nan
    else:  # "nan_payloads": two NaN bit patterns in one column
        x[nan] = np.where(rng.random(nan.sum()) < 0.5, np.nan, -np.nan)
    return x


def _compactor_column(values: np.ndarray, k: int, seed: int) -> _KLLColumn:
    """The compactor stack's own fold of ``values`` into a fresh column."""
    col = _KLLColumn(k, seed)
    col.levels[0] = np.array(values, dtype=np.float64)
    col.n = values.size
    col._compress()
    return col


def _mixed_ties(values: np.ndarray) -> bool:
    zeros = np.signbit(values[values == 0.0])
    payloads = np.unique(values[np.isnan(values)].view(np.uint64))
    return (zeros.any() and not zeros.all()) or payloads.size > 1


def _bits(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, dtype=np.float64).view(np.uint64)


_N = [1, 2, "k-1", "k", "k+1", 1000, 7919, 38_912, 86_016]   # 7919 is prime


@pytest.mark.parametrize("kind", ["gauss", "ties", "signed_zeros", "nan", "nan_payloads"])
@pytest.mark.parametrize("k", [8, 160])
@pytest.mark.parametrize("n", _N, ids=str)
def test_kll_one_sort_matches_compactor(n, k, kind):
    n = {"k-1": k - 1, "k": k, "k+1": k + 1}.get(n, n)
    x = _data(kind, n, seed=n + k)
    obs.enable()
    sk = KLLSketch(k, seed=3).update(x)
    # ties whose bits differ (zeros of both signs, NaNs of two payloads where
    # the sort keeps payloads) leave their order to the sort, so where a
    # compaction sorts, such a column keeps the compactor
    mixed = sum(n > k and _mixed_ties(np.sort(x[:, j])) for j in range(x.shape[1]))
    assert mixed == 0 or kind in ("signed_zeros", "nan_payloads")
    want = {"one_sort": 3 - mixed, "compactor": mixed}
    assert _kll_folds() == {path: c for path, c in want.items() if c}
    for j, col in enumerate(sk._columns):
        ref = _compactor_column(x[:, j], k, col.seed)
        assert col.n == ref.n == n
        assert col.compactions == ref.compactions
        assert len(col.levels) == len(ref.levels)
        for mine, want in zip(col.levels, ref.levels):
            np.testing.assert_array_equal(_bits(mine), _bits(want))
        qs = np.linspace(0.0, 1.0, 11)
        np.testing.assert_array_equal(_bits(col.quantile(qs)), _bits(ref.quantile(qs)))
        for v in (-1.0, 0.0, 0.5, 2.0):
            assert col.rank(v) == ref.rank(v)


def _union_reference(existing: list[np.ndarray], x: np.ndarray, k: int) -> list[np.ndarray]:
    return [np.union1d(e, _hash_values(x[:, j]))[:k] for j, e in enumerate(existing)]


@pytest.mark.parametrize(
    "case", ["fewer_than_k", "duplicates", "existing", "signed_zeros", "single_row"]
)
def test_kmv_selection_matches_full_union(case):
    k = 64
    rng = np.random.default_rng(11)
    if case == "fewer_than_k":
        x = rng.integers(0, 20, size=(5000, 3)).astype(np.float64)
    elif case == "duplicates":
        # 2k smallest hashes hold fewer than k distinct: the full-unique fallback
        x = np.repeat(rng.normal(size=(100, 3)), 200, axis=0)
    elif case == "signed_zeros":
        x = rng.choice([-0.0, 0.0], size=(3000, 3))
        x[::7] = rng.normal(size=x[::7].shape)
    elif case == "single_row":
        x = rng.normal(size=(1, 3))
    else:
        x = rng.normal(size=(4000, 3))
    sk = DistinctSketch(k)
    existing = [np.empty(0, dtype=np.uint64)] * 3
    if case == "existing":
        first = rng.normal(size=(3000, 3))
        sk.update(first)
        existing = _union_reference(existing, first, k)
        x = np.concatenate([x, first[:500]])       # overlaps the kept hashes
    sk.update(x)
    want = _union_reference(existing, x, k)
    for got, ref in zip(sk._columns, want):
        assert got.dtype == np.uint64
        np.testing.assert_array_equal(got, ref)


def test_summarize_block_equals_compactor_suite():
    rng = np.random.default_rng(5)
    block = rng.normal(size=(38_912, 19)).astype(np.float32)
    block[:, -1] = rng.integers(0, 2, size=block.shape[0])
    got = summarize_block(block, 7, label_column=18, num_classes=2)

    x = np.asarray(block, dtype=np.float64)
    want = SketchSuite.create(7, label_column=18, num_classes=2)
    want.sketches["moments"] = MomentsSketch.wrap(block_sketch_ref(x))
    kll = want.sketches["kll"]
    kll._columns = [
        _compactor_column(x[:, j], kll.k, (kll.seed << 8) + j) for j in range(x.shape[1])
    ]
    kmv = want.sketches["distinct"]
    kmv._columns = _union_reference([np.empty(0, dtype=np.uint64)] * x.shape[1], x, kmv.k)
    want.sketches["labels"].update(x)
    assert json.dumps(got.to_dict()) == json.dumps(want.to_dict())


def test_kll_fold_counter_counts_each_path():
    rng = np.random.default_rng(2)
    block = rng.normal(size=(2000, 19))
    summarize_block(block, 0)            # telemetry off: nothing counted
    assert _kll_folds() == {}
    obs.enable()
    suite = summarize_block(block, 1)
    assert _kll_folds() == {"one_sort": 19}
    suite.sketches["kll"].update(block[:500])
    assert _kll_folds() == {"one_sort": 19, "compactor": 19}
