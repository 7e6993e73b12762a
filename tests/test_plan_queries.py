"""End-to-end ``where=`` / ``columns=`` queries through the plan-compiled
fused kernels: predicate filtering with honest selectivity-aware CIs, the
sketch fast path declining filtered queries, column projection, grouped
filtered aggregates, weighted policies, and the serve path."""

import dataclasses

import numpy as np
import pytest

from repro import rsp


@pytest.fixture(scope="module")
def plain_ds():
    rng = np.random.default_rng(5)
    data = rng.normal(1.5, 2.0, size=(20000, 4)).astype(np.float32)
    return rsp.partition(data, blocks=50, seed=3), data


@pytest.fixture(scope="module")
def labelled_ds():
    rng = np.random.default_rng(1)
    n, k = 24000, 40
    x = rng.normal(1.5, 2.0, size=(n, 3)).astype(np.float32)
    y = rng.integers(0, 2, size=(n, 1)).astype(np.float32)
    data = np.concatenate([x, y], axis=1)
    return rsp.partition(data, blocks=k, seed=7, num_classes=2), data


@pytest.mark.parametrize("impl", ["ref", "np", "jax", "pallas"])
def test_unfiltered_and_grouped_folds_are_block_sketches(impl, monkeypatch):
    """An unfiltered query folds each block through a plan with no
    predicates, a grouped one through a plan grouped by label: the payload
    is the sketch of the whole block, and of each class's rows, with a
    class the block lacks as an empty sketch."""
    from repro.kernels.block_sketch import block_sketch_ref
    from repro.rsp.query import QueryExecutor

    rng = np.random.default_rng(11)
    x = rng.normal(1.5, 2.0, size=(1600, 3)).astype(np.float32)
    y = rng.integers(0, 2, size=(1600, 1)).astype(np.float32)  # class 2 never occurs
    ds = rsp.partition(np.concatenate([x, y], axis=1), blocks=4, seed=2, num_classes=3)
    seen = []
    source = QueryExecutor._payload_source

    def spy(self, ids, fold):
        for bid, payload in source(self, ids, fold):
            seen.append((bid, fold, payload))
            yield bid, payload

    monkeypatch.setattr(QueryExecutor, "_payload_source", spy)
    grouped_p50 = rsp.Aggregate("quantile", q=0.5, by_label=True)
    ds.query(["mean", "p50", grouped_p50], use_sketches=False, max_blocks=2,
             sketch_impl=impl, seed=1)
    assert len(seen) == 2
    for bid, fold, payload in seen:
        block = np.asarray(ds.block(bid))
        kw = dict(bins=fold.bins, lo=fold.lo, hi=fold.hi)
        wants = [("whole", payload["whole"], block_sketch_ref(block, **kw))]
        for c in range(2):
            rows = block[block[:, -1] == c]
            wants.append((f"class {c}", payload["per_class"][c], block_sketch_ref(rows, **kw)))
        for name, got, want in wants:
            assert got.count == want.count, name
            np.testing.assert_allclose(got.mean, want.mean, rtol=1e-5, atol=1e-5, err_msg=name)
            np.testing.assert_allclose(got.m2, want.m2, rtol=1e-4, atol=1e-3, err_msg=name)
            np.testing.assert_allclose(got.min, want.min, rtol=1e-6, err_msg=name)
            np.testing.assert_allclose(got.max, want.max, rtol=1e-6, err_msg=name)
            # the bin-edge caveat: float32 and float64 binning may put a
            # value on an edge in adjacent bins, never off the feature
            np.testing.assert_array_equal(got.hist.sum(-1), want.hist.sum(-1), err_msg=name)
            assert np.abs(got.hist - want.hist).sum() <= 2, name
        empty = payload["per_class"][2]
        assert empty.count == 0 and not empty.hist.any()
        assert np.all(empty.min == np.inf) and np.all(empty.max == -np.inf)
        assert payload["rows_total"] == payload["rows_selected"] == block.shape[0]


def _masked(data, col, thresh):
    return data[data[:, col] > np.float32(thresh)].astype(np.float64)


def test_where_filtered_mean(plain_ds):
    ds, data = plain_ds
    res = ds.query("mean", where="c0 > 1.5", seed=3)
    truth = _masked(data, 0, 1.5).mean(0)
    assert not res.from_sketches
    assert res.blocks_read > 0
    # roughly half the rows pass (threshold at the distribution mean)
    assert 0.3 < res.selectivity < 0.7
    np.testing.assert_allclose(res["mean"].estimate, truth, atol=0.05)


def test_where_full_scan_is_exact(plain_ds):
    ds, data = plain_ds
    res = ds.query(
        ["mean", "count", "sum"], where="c1 < 1.0", min_blocks=50, seed=0
    )
    sel = data[data[:, 1] < np.float32(1.0)].astype(np.float64)
    assert res.blocks_read == res.total_blocks
    assert res.selectivity == pytest.approx(sel.shape[0] / data.shape[0])
    assert res["count"].estimate == pytest.approx(sel.shape[0], rel=1e-6)
    np.testing.assert_allclose(res["mean"].estimate, sel.mean(0), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(res["sum"].estimate, sel.sum(0), rtol=1e-4)


def test_where_conjunction_and_tuple_specs(plain_ds):
    ds, data = plain_ds
    res = ds.query("count", where=["c0 > 1.5", (2, "<", 2.0)], min_blocks=50)
    m = (data[:, 0] > np.float32(1.5)) & (data[:, 2] < np.float32(2.0))
    assert res["count"].estimate == pytest.approx(int(m.sum()), rel=1e-6)
    assert res.selectivity == pytest.approx(m.mean())


def test_where_ci_covers_truth(plain_ds):
    ds, data = plain_ds
    res = ds.query(
        rsp.Aggregate("mean", feature=1), where="c0 > 1.5", seed=11, max_blocks=15
    )
    truth = _masked(data, 0, 1.5).mean(0)[1]
    agg = res.aggregates[0]
    assert res.blocks_read == 15
    assert agg.ci_lo < truth < agg.ci_hi


def test_unfiltered_has_no_selectivity(plain_ds):
    ds, _ = plain_ds
    assert ds.query("mean").selectivity is None
    assert ds.query("median", use_sketches=False, max_blocks=5).selectivity is None


def test_where_declines_sketch_fast_path(plain_ds):
    ds, _ = plain_ds
    # the same aggregates WITHOUT a predicate take the zero-read fast path
    assert ds.query(["mean", "count"]).from_sketches
    res = ds.query(["mean", "count"], where="c0 > 1.5")
    assert not res.from_sketches and res.blocks_read > 0
    # forcing sketches on a filtered query is an error naming the culprit
    with pytest.raises(ValueError, match="where"):
        ds.query("mean", where="c0 > 1.5", use_sketches=True)


def test_columns_projection_stays_sketch_eligible(plain_ds):
    ds, data = plain_ds
    res = ds.query(["mean", "var"], columns=(2, 0))
    assert res.from_sketches  # projection alone needs no block reads
    full = data.astype(np.float64)
    np.testing.assert_allclose(
        res["mean"].estimate, full.mean(0)[[2, 0]], rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        res["var"].estimate, full.var(0, ddof=1)[[2, 0]], rtol=1e-3
    )


def test_where_with_columns_and_feature(plain_ds):
    ds, data = plain_ds
    # feature= indexes the projected axis: columns=(3, 1) -> feature 1 is c1
    res = ds.query(
        rsp.Aggregate("mean", feature=1),
        where="c0 > 1.5", columns=(3, 1), min_blocks=50,
    )
    truth = _masked(data, 0, 1.5).mean(0)[1]
    assert res.aggregates[0].estimate == pytest.approx(truth, abs=1e-4)


def test_where_quantile(plain_ds):
    ds, data = plain_ds
    res = ds.query(
        rsp.Aggregate("quantile", q=0.5, feature=0),
        where="c0 > 1.5", seed=2, min_blocks=50,
    )
    truth = np.median(_masked(data, 0, 1.5)[:, 0])
    assert res.aggregates[0].estimate == pytest.approx(truth, abs=0.05)


def test_grouped_filtered_mean(labelled_ds):
    ds, data = labelled_ds
    res = ds.query(
        rsp.Aggregate("mean", by_label=True),
        where="c0 > 1.5", columns=(0, 1, 2), min_blocks=40,
    )
    sel = data[data[:, 0] > np.float32(1.5)].astype(np.float64)
    est = np.asarray(res.aggregates[0].estimate)
    assert est.shape == (2, 3)
    for c in range(2):
        truth = sel[sel[:, 3] == c][:, :3].mean(0)
        np.testing.assert_allclose(est[c], truth, rtol=1e-4, atol=1e-4)


def test_weighted_policy_filtered_mean_is_honest(plain_ds):
    ds, data = plain_ds
    # non-uniform block sampling: the filtered mean must use the Hajek ratio
    # (HT sum / HT count), not the HH-over-N expansion
    res = ds.query(
        "mean", where="c0 > 1.5", policy="weighted", seed=13, min_blocks=20
    )
    truth = _masked(data, 0, 1.5).mean(0)
    np.testing.assert_allclose(res["mean"].estimate, truth, atol=0.08)


def test_where_spec_normalization():
    q = rsp.Query(
        aggregates=(rsp.Aggregate("mean"),), where="c0 > 0.5", columns=[2, 0]
    )
    assert q.where == (rsp.Predicate(0, "gt", 0.5),)
    assert q.columns == (2, 0)
    # dataclasses.replace re-runs normalization on already-normalized specs
    q2 = dataclasses.replace(q, seed=9)
    assert q2.where == q.where
    with pytest.raises(ValueError):
        rsp.Query(aggregates=(rsp.Aggregate("mean"),), columns=())


def test_stream_reports_selectivity_progressively(plain_ds):
    ds, _ = plain_ds
    seen = 0
    for res in ds.query_stream("mean", where="c0 > 1.5", max_blocks=5, seed=1):
        seen += 1
        assert 0.0 < res.selectivity < 1.0
        assert not res.from_sketches
    assert seen == 5


def test_serve_where_query(plain_ds):
    ds, data = plain_ds
    truth = _masked(data, 0, 1.5).mean(0)
    with ds.serve(capacity=4, workers=2, seed=3) as svc:
        t_plain = svc.submit(["mean", "count"])
        t_where = svc.submit("mean", where="c0 > 1.5")
        plain = svc.result(t_plain, timeout=60)
        res = svc.result(t_where, timeout=60)
    assert plain.from_sketches  # unfiltered stays on the zero-read fast path
    assert not res.from_sketches
    assert 0.3 < res.selectivity < 0.7
    np.testing.assert_allclose(res["mean"].estimate, truth, atol=0.05)
