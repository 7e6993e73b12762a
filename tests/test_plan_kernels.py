"""Plan-compiled fused query kernels: predicate parsing, three-way parity
(ref / fused numpy / jax / Pallas-interpret), the plan-keyed compile cache,
and a property test against plain boolean-mask numpy aggregates."""

import numpy as np
import pytest

from repro.kernels.plan import (
    Predicate,
    QueryPlan,
    as_predicates,
    parse_predicate,
    plan_sketch,
    plan_sketch_ref,
)
from repro.kernels.plan import ops as plan_ops

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# Predicate / plan construction
# ---------------------------------------------------------------------------


def test_parse_predicate_forms():
    p = parse_predicate("c3 > 0.5")
    assert p == Predicate(3, "gt", 0.5)
    assert parse_predicate("col2 <= -1e-2") == Predicate(2, "le", -0.01)
    assert parse_predicate("0 != 4") == Predicate(0, "ne", 4.0)
    assert parse_predicate((1, "<", 2.0)) == Predicate(1, "lt", 2.0)
    assert parse_predicate(p) is p
    with pytest.raises(ValueError):
        parse_predicate("c3 ~ 0.5")
    with pytest.raises(TypeError):
        parse_predicate(7)


def test_predicate_symbol_normalization():
    # symbols and names are the same predicate -- and the same cache key
    assert Predicate(0, ">", 1.0) == Predicate(0, "gt", 1.0)
    assert str(Predicate(2, "le", 0.25)) == "c2 <= 0.25"
    with pytest.raises(ValueError):
        Predicate(0, "gtt", 1.0)
    with pytest.raises(ValueError):
        Predicate(-1, "gt", 1.0)


def test_as_predicates_shapes():
    assert as_predicates(None) == ()
    assert as_predicates("c0 > 1") == (Predicate(0, "gt", 1.0),)
    assert as_predicates((0, ">", 1.0)) == (Predicate(0, "gt", 1.0),)
    two = as_predicates(["c0 > 1", (2, "<", 3.0)])
    assert two == (Predicate(0, "gt", 1.0), Predicate(2, "lt", 3.0))


def test_plan_validation():
    with pytest.raises(ValueError):
        QueryPlan(columns=())
    with pytest.raises(ValueError):
        QueryPlan(num_classes=3)  # num_classes without group_by
    plan = QueryPlan(columns=(0, -1))
    assert plan.resolve_columns(4) == (0, 3)
    assert not plan.filtered
    assert QueryPlan(predicates="c0 > 1").filtered


# ---------------------------------------------------------------------------
# Three-way parity: every impl must agree with the two-pass reference
# ---------------------------------------------------------------------------


def _data(n=4000, f=6, classes=0, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(1.5, 2.0, size=(n, f)).astype(np.float32)
    if classes:
        x[:, f - 1] = rng.integers(0, classes, size=n)
    return x


PLANS = {
    "filter": (QueryPlan(predicates="c0 > 1.0"), 0),
    "conjunction": (QueryPlan(predicates=["c0 > 1.0", "c2 < 2.5"]), 0),
    "empty_selection": (QueryPlan(predicates="c0 > 1e9"), 0),
    "all_pass": (QueryPlan(predicates="c0 > -1e9"), 0),
    "projection": (QueryPlan(columns=(0, 2, 4)), 0),
    "filter_project": (QueryPlan(predicates="c1 < 2.0", columns=(3, 1)), 0),
    "grouped_filter": (
        QueryPlan(predicates="c0 > 1.0", columns=(0, 1, 2), group_by=5, num_classes=3),
        3,
    ),
    # the plans an unfiltered query folds through: the whole block, and one
    # sketch per class (the block lacks class 3 in the last, grouped as the
    # executor groups, by the label column counted from the end)
    "whole": (QueryPlan(), 0),
    "grouped": (QueryPlan(group_by=5, num_classes=3), 3),
    "grouped_absent_class": (QueryPlan(group_by=-1, num_classes=4), 3),
}


def _assert_matches(res, ref, *, hist_exact=False):
    """Moment parity at 1e-5 (the acceptance bar); histograms must agree on
    mass always and bin-for-bin when hist_exact (same f32 binning path)."""
    assert res.rows_total == ref.rows_total
    assert res.rows_selected == ref.rows_selected
    assert res.selectivity == pytest.approx(ref.selectivity)
    assert len(res.sketches) == len(ref.sketches)
    for got, want in zip(res.sketches, ref.sketches):
        assert got.count == want.count
        if want.count == 0:
            assert np.all(np.isinf(got.min)) and np.all(np.isinf(got.max))
            continue
        np.testing.assert_allclose(got.mean, want.mean, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got.min, want.min, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got.max, want.max, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got.m2, want.m2, rtol=1e-4, atol=1e-3)
        if want.hist is not None:
            assert got.hist is not None
            # bin-edge caveat: f32 vs f64 binning may shift edge values one
            # bin, but never changes per-feature mass
            np.testing.assert_array_equal(got.hist.sum(-1), want.hist.sum(-1))
            if hist_exact:
                np.testing.assert_array_equal(got.hist, want.hist)


@pytest.mark.parametrize("impl", ["np", "jax", "pallas"])
@pytest.mark.parametrize("name", sorted(PLANS))
def test_plan_parity_with_hist(impl, name):
    plan, classes = PLANS[name]
    x = _data(classes=classes)
    kw = dict(bins=16, lo=-8.0, hi=12.0)
    ref = plan_sketch(x, plan, impl="ref", **kw)
    res = plan_sketch(x, plan, impl=impl, tile_rows=512, **kw)
    _assert_matches(res, ref)
    # the fused paths share one f32 binning rule -- exact hist agreement
    base = plan_sketch(x, plan, impl="np", tile_rows=512, **kw)
    _assert_matches(res, base, hist_exact=True)


@pytest.mark.parametrize("impl", ["np", "jax", "pallas"])
def test_plan_parity_no_hist(impl):
    # bins=0 skips histograms (pallas falls back to the jit path)
    plan = QueryPlan(predicates=["c0 > 1.0", "c3 >= 0.0"])
    x = _data()
    ref = plan_sketch(x, plan, impl="ref")
    _assert_matches(plan_sketch(x, plan, impl=impl, tile_rows=256), ref)


@pytest.mark.parametrize("impl", ["ref", "np", "jax", "pallas"])
def test_plan_out_of_range_mass_clipped(impl):
    """Mass outside the grid clips into the edge bins: a histogram always
    sums to the rows selected, per feature."""
    rng = np.random.default_rng(13)
    x = rng.normal(0.0, 5.0, size=(400, 2)).astype(np.float32)
    sk = plan_sketch(x, QueryPlan(), bins=8, lo=-1.0, hi=1.0, impl=impl).sketches[0]
    np.testing.assert_array_equal(sk.hist.sum(axis=1), [400, 400])
    # bins are 0.25 wide: the first holds every value below -0.75, the last
    # every value from 0.75 up
    np.testing.assert_array_equal(sk.hist[:, 0], (x < -0.75).sum(0))
    np.testing.assert_array_equal(sk.hist[:, -1], (x >= 0.75).sum(0))


@pytest.mark.parametrize("impl", ["ref", "np", "jax", "pallas"])
def test_plan_constant_feature_and_moments_only(impl):
    x = np.concatenate(
        [np.full((256, 1), 3.0, np.float32),
         np.random.default_rng(14).normal(size=(256, 1)).astype(np.float32)],
        axis=1,
    )
    sk = plan_sketch(x, QueryPlan(), bins=4, lo=x.min(0), hi=x.max(0), impl=impl).sketches[0]
    assert sk.hist[0].tolist() == [256, 0, 0, 0]  # constant -> all mass bin 0
    assert sk.hist[1].sum() == 256
    # bins=0: moments only (the pallas kernel always histograms, so it runs jax)
    m = plan_sketch(x, QueryPlan(), impl=impl).sketches[0]
    assert m.hist is None
    np.testing.assert_allclose(m.mean, x.mean(0), rtol=1e-6, atol=1e-6)
    assert m.m2[0] == 0.0


def test_plan_ragged_tiles():
    # n not divisible by the tile: the tail tile and Pallas padding must not
    # leak phantom rows into any aggregate
    plan = QueryPlan(predicates="c1 > 1.5")
    x = _data(n=3001)
    ref = plan_sketch(x, plan, impl="ref", bins=8, lo=-8.0, hi=12.0)
    for impl in ("np", "jax", "pallas"):
        res = plan_sketch(x, plan, impl=impl, tile_rows=128, bins=8, lo=-8.0, hi=12.0)
        _assert_matches(res, ref)


def test_plan_sketch_ref_is_mask_then_sketch():
    plan = QueryPlan(predicates="c0 > 1.0", columns=(2,))
    x = _data(n=500)
    res = plan_sketch_ref(x, plan)
    sel = x[x[:, 0] > np.float32(1.0)][:, 2]
    assert res.rows_selected == sel.shape[0]
    np.testing.assert_allclose(res.sketches[0].mean, [sel.astype(np.float64).mean()], rtol=1e-6)


def test_auto_impl_matches_ref():
    # REPRO_AUTOTUNE=off (conftest): auto pins the deterministic default
    plan = QueryPlan(predicates="c2 < 2.0")
    x = _data(n=2000)
    ref = plan_sketch(x, plan, impl="ref", bins=8, lo=-8.0, hi=12.0)
    _assert_matches(plan_sketch(x, plan, bins=8, lo=-8.0, hi=12.0), ref)


def test_unknown_impl_rejected():
    with pytest.raises(ValueError):
        plan_sketch(_data(n=10), QueryPlan(), impl="cuda")


# ---------------------------------------------------------------------------
# Plan-keyed compile cache
# ---------------------------------------------------------------------------


def test_compile_cache_hits_and_misses():
    plan_ops.cache_clear()
    plan = QueryPlan(predicates="c0 > 0.5")
    fn = plan_ops.compile_plan(plan, num_features=4, bins=8, impl="np")
    assert plan_ops.cache_info() == {"hits": 0, "misses": 1, "size": 1}

    # identical plan (fresh object, symbol spelling) -> cache hit, same fn
    again = plan_ops.compile_plan(
        QueryPlan(predicates=(Predicate(0, ">", 0.5),)),
        num_features=4, bins=8, impl="np",
    )
    assert again is fn
    assert plan_ops.cache_info()["hits"] == 1

    # changing the predicate value recompiles
    plan_ops.compile_plan(
        QueryPlan(predicates="c0 > 0.25"), num_features=4, bins=8, impl="np"
    )
    # ...as does any other key component (shape, bins, impl, tile)
    plan_ops.compile_plan(plan, num_features=5, bins=8, impl="np")
    plan_ops.compile_plan(plan, num_features=4, bins=16, impl="np")
    plan_ops.compile_plan(plan, num_features=4, bins=8, impl="ref")
    plan_ops.compile_plan(plan, num_features=4, bins=8, impl="np", tile_rows=8192)
    info = plan_ops.cache_info()
    assert info["misses"] == 6 and info["size"] == 6


def test_plan_key_identity():
    a = QueryPlan(predicates="c0 > 0.5", columns=(1, 2))
    b = QueryPlan(predicates=(0, ">", 0.5), columns=[1, 2])
    assert a.key() == b.key()
    assert a.key() != QueryPlan(predicates="c0 > 0.6", columns=(1, 2)).key()
    assert a.key() != QueryPlan(predicates="c0 >= 0.5", columns=(1, 2)).key()
    assert a.key() != QueryPlan(predicates="c0 > 0.5").key()


def test_compile_plan_rejects_auto():
    with pytest.raises(ValueError):
        plan_ops.compile_plan(QueryPlan(), num_features=3, impl="auto")


# ---------------------------------------------------------------------------
# Property test: fused filtered aggregates == boolean-mask numpy aggregates
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        col=st.integers(0, 2),
        op=st.sampled_from(["lt", "le", "gt", "ge"]),
        thresh=st.floats(-2.0, 2.0, allow_nan=False),
        impl=st.sampled_from(["np", "jax"]),
    )
    def test_fused_filter_matches_boolean_mask(seed, col, op, thresh, impl):
        rng = np.random.default_rng(seed)
        x = rng.normal(0.0, 1.0, size=(257, 3)).astype(np.float32)
        plan = QueryPlan(predicates=(Predicate(col, op, thresh),))
        res = plan_sketch(x, plan, impl=impl, tile_rows=64)
        sel = x[plan.mask(x)].astype(np.float64)
        assert res.rows_total == 257
        assert res.rows_selected == sel.shape[0]
        sk = res.sketches[0]
        assert sk.count == sel.shape[0]
        if sel.shape[0] == 0:
            return
        np.testing.assert_allclose(sk.mean, sel.mean(0), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(sk.min, sel.min(0), rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(sk.max, sel.max(0), rtol=1e-6, atol=1e-7)
        m2 = ((sel - sel.mean(0)) ** 2).sum(0)
        np.testing.assert_allclose(sk.m2, m2, rtol=1e-3, atol=1e-3)
