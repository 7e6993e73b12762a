"""TPC-H ``lineitem`` on the typed path: schema, store, int32 shuffle,
sketches, typed plans in every implementation, and Q1 / Q6 through
``ds.serve`` against the plain reference of ``repro.data.tpch``."""

import datetime

import jax
import numpy as np
import pytest

from repro import rsp
from repro.core.schema import Column, Schema, date_code
from repro.data import tpch
from repro.kernels.plan import as_where, bind_plan, plan_totals
from repro.kernels.rsp_shuffle.ops import make_permutations, rsp_shuffle
from repro.kernels.rsp_shuffle.ref import rsp_shuffle_ref
from repro.rsp import Aggregate, Query

N, K = 65_536, 8
UNITS = [u for _, u in tpch.Q1_TOTALS]


@pytest.fixture(scope="module")
def table():
    t = tpch.lineitem(N, seed=11)
    # orderkeys past 2**24, where float32 stops holding every integer
    t[:, tpch.C["l_orderkey"]] += 2**24 + 3
    return t


@pytest.fixture(scope="module")
def store(table, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("lineitem") / "store")
    rsp.partition(table, blocks=K, seed=5, out=path, schema=tpch.lineitem_schema()).close()
    return path


def blocks_of(path, ids):
    return np.stack([np.load(f"{path}/block_{k:05d}.npy") for k in ids])


def q1_query(delta, **kw):
    aggs = tuple(Aggregate(kind, expr=e, name=n) for n, kind, e in tpch.Q1)
    return Query(aggs, where=tpch.q1_where(delta), group_by=tpch.Q1_GROUP_BY, **kw)


def q6_query(date, **kw):
    aggs = tuple(Aggregate(kind, expr=e, name=n) for n, kind, e in tpch.Q6)
    return Query(aggs, where=tpch.q6_where(date, 0.06, 24), **kw)


# -- the table and its schema ------------------------------------------------------

def test_lineitem_follows_clause_4_2_3(table):
    t = tpch.lineitem(N, seed=11)
    c = tpch.C
    assert t.dtype == np.int32 and t.shape == (N, 16)
    assert np.all(np.diff(t[:, c["l_orderkey"]]) >= 0)               # dbgen's order
    assert set(np.unique((t[:, c["l_orderkey"]] - 1) % 32)) == set(range(8))  # sparse keys
    assert t[:, c["l_linenumber"]].min() == 1 and t[:, c["l_linenumber"]].max() == 7
    assert t[:, c["l_quantity"]].min() == 1 and t[:, c["l_quantity"]].max() == 50
    assert t[:, c["l_discount"]].max() == 10 and t[:, c["l_tax"]].max() == 8
    price = t[:, c["l_extendedprice"]]
    assert np.array_equal(price, t[:, c["l_quantity"]] * tpch.retail_cents(t[:, c["l_partkey"]]))
    assert price.max() <= 50 * 209_900 < 2**24
    ship, receipt = t[:, c["l_shipdate"]], t[:, c["l_receiptdate"]]
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    flag, status = t[:, c["l_returnflag"]], t[:, c["l_linestatus"]]
    assert np.all((flag == 1) == (receipt > tpch.CURRENT))           # "N" after CURRENTDATE
    assert np.all((status == 1) == (ship > tpch.CURRENT))            # "O"
    groups = {(f, s) for f, s in zip(flag, status)}
    assert groups == {(0, 0), (1, 0), (1, 1), (2, 0)}                 # 4 of Q1's 6 keys occur
    assert np.array_equal(t, tpch.lineitem(N, seed=11))


def test_schema_encodes_literals_in_their_units():
    price = Column("p", "decimal", scale=2)
    assert price.bound("ge", 0.05) == ("ge", 5)
    assert price.bound("lt", 0.055) == ("le", 5)       # between two codes: inclusive bound
    assert price.bound("gt", 0.055) == ("ge", 6)
    with pytest.raises(ValueError):
        price.bound("eq", 0.055)
    flag = Column("f", "dict", dictionary=("A", "N", "R"))
    assert flag.bound("eq", "R") == ("eq", 2)
    assert Column("d", "date").bound("le", "1998-09-02") == ("le", date_code("1998-09-02"))
    assert date_code(datetime.date(1970, 2, 1)) == 31
    s = tpch.lineitem_schema()
    assert Schema.from_list(s.to_list()) == s
    with pytest.raises(ValueError):
        Column("x", "int", dictionary=("a",))


def test_typed_store_round_trip_is_bit_exact(table, store):
    ds = rsp.open(store)
    assert ds.spec.schema == tpch.lineitem_schema() and ds.spec.dtype == "int32"
    got = blocks_of(store, range(K)).reshape(-1, 16)
    assert got.dtype == np.int32
    order = lambda a: a[np.lexsort(a.T[::-1])]  # noqa: E731
    assert np.array_equal(order(got), order(table))
    assert got[:, tpch.C["l_orderkey"]].max() > 2**24
    # a float store still opens with no schema, as columns c0..cF-1
    assert rsp.RSPSpec.from_json(rsp.RSPSpec(64, 4, 4, (2,)).to_json()).schema is None


def test_int32_shuffle_is_bit_exact_in_interpret_mode():
    rng = np.random.default_rng(0)
    x = rng.integers(-(2**31), 2**31, size=(512, 16), dtype=np.int64).astype(np.int32)
    x[:64] = rng.integers(0, 51, size=(64, 16))   # small codes: denormals as float bits
    x[64:72] = [-1, 0, 1, -(2**31), 2**31 - 1, 65535, 65536, -65536] * 2
    tile_perm, intra = make_permutations(jax.random.PRNGKey(3), 8, 64)
    got = np.asarray(rsp_shuffle(x, tile_perm, intra, tile_rows=64))
    assert got.dtype == np.int32
    assert np.array_equal(got, np.asarray(rsp_shuffle_ref(x, tile_perm, intra, tile_rows=64)))


@pytest.fixture(scope="module")
def pallas_blocks(table):
    return rsp.partition(table, blocks=K, seed=2, backend="pallas", summaries=False,
                         schema=tpch.lineitem_schema()).stacked()


def test_pallas_backend_partitions_a_typed_table(table, pallas_blocks):
    spec = rsp.RSPSpec(N, K, K, (16,), "int32", 0, tpch.lineitem_schema())
    assert rsp.get_backend("pallas").supports(rsp.PartitionRequest(data=table, spec=spec)) is None
    flat = pallas_blocks.reshape(-1, 16)
    assert flat.dtype == np.int32
    assert np.array_equal(np.sort(flat, axis=0), np.sort(table, axis=0))


def test_pallas_backend_blocks_are_random_samples_of_a_key_ordered_table(table, pallas_blocks):
    # the table lies in l_orderkey order: dealing runs of consecutive rows
    # would put one key range of each original block into each RSP block,
    # a KS distance of (1 / P)(1 - 1 / K) = 0.109 from the table's keys
    keys = np.sort(table[:, tpch.C["l_orderkey"]])
    for block in pallas_blocks:
        b = np.sort(block[:, tpch.C["l_orderkey"]])
        v = np.unique(b)
        gap = np.abs(np.searchsorted(b, v, "right") / len(b)
                     - np.searchsorted(keys, v, "right") / len(keys))
        assert gap.max() < 0.02


def test_sketches_fold_the_exact_integers(table, store):
    ds = rsp.open(store)
    exact = len(np.unique(table[:, tpch.C["l_orderkey"]]))
    res = ds.query(Aggregate("distinct", feature=tpch.C["l_orderkey"]))
    assert res.from_sketches
    assert abs(res.aggregates[0].estimate / exact - 1) < 3 * (1 / np.sqrt(254))
    qty = table[:, tpch.C["l_quantity"]].astype(np.float64)
    m = ds.moments()
    assert m.count == N and m.mean[tpch.C["l_quantity"]] == pytest.approx(qty.mean(), rel=1e-12)
    assert m.min[tpch.C["l_orderkey"]] == table[:, tpch.C["l_orderkey"]].min()


# -- typed plans --------------------------------------------------------------------

PLANS = {
    "q1": (tpch.q1_where(90), [e for _, _, e in tpch.Q1 if e], tpch.Q1_GROUP_BY),
    "q6": (tpch.q6_where("1994-01-01", 0.06, 24), ["l_extendedprice * l_discount"], ()),
    "all_rows": ((), ["l_tax"], ("l_shipmode",)),
}


@pytest.mark.parametrize("impl", ("np", "jax", "pallas"))
@pytest.mark.parametrize("name", sorted(PLANS))
def test_typed_plans_agree_across_impls(table, name, impl):
    where, exprs, group_by = PLANS[name]
    plan = bind_plan(tpch.lineitem_schema(), as_where(where), exprs, group_by)
    block = table[: N // K]
    ref = plan_totals(block, plan, impl="ref")
    got = plan_totals(block, plan, impl=impl, tile_rows=None if impl == "jax" else 1024)
    assert got.rows_total == ref.rows_total and got.rows_selected == ref.rows_selected
    assert ref.rows_selected > 0
    np.testing.assert_array_equal(got.counts, ref.counts)
    np.testing.assert_allclose(got.sums, ref.sums, rtol=1e-5)


def test_typed_plan_matches_the_exact_reference(table):
    plan = bind_plan(tpch.lineitem_schema(), as_where(tpch.q1_where(60)),
                     [e for _, _, e in tpch.Q1 if e], tpch.Q1_GROUP_BY)
    got = plan_totals(table, plan, impl="np")
    exact = tpch.q1_totals(table, 60)
    np.testing.assert_array_equal(got.counts, exact[:, 0])
    # measures in plan order: qty, price, disc_price, charge, qty, price, disc
    units = np.array(UNITS, np.float64)
    want = exact[:, [1, 2, 3, 4, 5]] / units[[1, 2, 3, 4, 5]]
    np.testing.assert_allclose(got.sums[:, [0, 1, 2, 3, 6]], want, rtol=1e-6)


def test_typed_query_errors_are_plain():
    ds = rsp.partition(np.zeros((64, 2), np.float32), blocks=4)
    with pytest.raises(ValueError, match="typed table"):
        ds.query(Aggregate("sum", expr="c0"), where="x > 1")
    with pytest.raises(ValueError, match="avg"):
        Aggregate("avg")


# -- Q1 and Q6 through ds.serve ---------------------------------------------------------

def test_q1_q6_through_serve_agree_with_the_reference(store):
    ds = rsp.open(store)
    with ds.serve(seed=7, workers=2) as svc:
        t1 = svc.submit(q1_query(90, target_rel_err=0.01, min_blocks=3, max_blocks=6))
        t6 = svc.submit(q6_query("1995-01-01", target_rel_err=0.01, min_blocks=3,
                                 max_blocks=6))
        r1, r6 = svc.result(t1), svc.result(t6)
    for res, qname in ((r1, "q1"), (r6, "q6")):
        ids = [s.block_id for s in res.trace.steps[: res.blocks_read]]
        assert len(set(ids)) == res.blocks_read >= 3
        blocks = blocks_of(store, ids)
        if qname == "q1":
            totals = np.stack([tpch.q1_totals(b, 90) for b in blocks])
            ref = tpch.ht_answer(totals, UNITS, K)
            assert res.groups == tuple(tpch.Q1_GROUPS[g] for g in ref["seen"])
            source = tpch.Q1_FROM
        else:
            totals = np.stack([tpch.q6_totals(b, "1995-01-01", 0.06, 24) for b in blocks])
            ref = tpch.ht_answer(totals, [1, 10**4], K, grouped=False)
            source = tpch.Q6_FROM
        for name, (j, form) in source.items():
            a = res[name]
            want, half = ref[form][..., j], ref[form + "_half"][..., j]
            np.testing.assert_allclose(np.atleast_1d(a.estimate), np.ravel(want), rtol=1e-5)
            got_half = (np.atleast_1d(a.ci_hi) - np.atleast_1d(a.ci_lo)) / 2
            # the executor's Student-t is a Cornish-Fisher expansion
            np.testing.assert_allclose(got_half, np.ravel(half), rtol=2e-3)


@pytest.mark.parametrize("qname", ("q1", "q6"))
def test_reading_every_block_gives_the_exact_answer(table, store, qname):
    ds = rsp.open(store)
    q = q1_query(120) if qname == "q1" else q6_query("1996-01-01")
    res = ds.query(q)
    assert res.blocks_read == K
    if qname == "q1":
        exact, units, source = tpch.q1_totals(table, 120), UNITS, tpch.Q1_FROM
    else:
        exact, units, source = tpch.q6_totals(table, "1996-01-01", 0.06, 24), [1, 10**4], \
            tpch.Q6_FROM
    seen = np.flatnonzero(exact[:, 0] > 0)
    for name, (j, form) in source.items():
        a = res[name]
        total = exact[seen, j] / units[j]
        want = total if form == "total" else total / exact[seen, 0]
        np.testing.assert_allclose(np.atleast_1d(a.estimate), want, rtol=1e-6)
        assert np.all(np.atleast_1d(a.ci_lo) == np.atleast_1d(a.ci_hi))    # zero width
    if qname == "q1":
        assert np.array_equal(res["count_order"].estimate, exact[seen, 0])


def test_typed_fold_counts_rows_and_runs_under_its_span(store):
    from repro import obs

    ds = rsp.open(store)
    obs.enable(sample_rate=0.0)
    try:
        before = obs.get_registry().snapshot().get("rsp_plan_rows_total", {"series": []})
        ds.query(q6_query("1994-01-01", max_blocks=2, min_blocks=2))
        after = obs.get_registry().snapshot()["rsp_plan_rows_total"]["series"]
    finally:
        obs.disable()
    got = {s["labels"]["outcome"]: s["value"] for s in after}
    was = {s["labels"]["outcome"]: s["value"] for s in before["series"]}
    assert got["scanned"] - was.get("scanned", 0) == 2 * (N // K)
    assert 0 < got["selected"] - was.get("selected", 0) < 0.05 * 2 * (N // K)
