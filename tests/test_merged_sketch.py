"""The corpus-level merged sketch of an opened dataset.

``RSPDataset.merged_sketch(kind)`` merges the stored per-block suites once per
summaries list and kind, and every later query reads that one object: the
progressive quantile's grid and the sketch-only quantile and distinct answers.
The merge runs in block order from a deep copy of the first suite, so the
shared sketch must be bit for bit the merge each query used to run for
itself, and no query may change it."""

import numpy as np
import pytest

from repro import obs, rsp
from repro.distributed import LocalTransport, run_local_hosts
from repro.rsp.query import QueryExecutor, as_query
from repro.rsp.sketch import sketch_from_dict

P95 = dict(use_sketches=False, target_rel_err=0.01, min_blocks=4, max_blocks=6, seed=9)


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset()
    yield
    obs.reset()


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """A saved store with partition-time suites: 32 blocks of 1,024 rows, so
    each block's KLL columns have compacted and the merge compacts again."""
    rng = np.random.default_rng(5)
    data = rng.normal(1.0, 2.0, size=(32 * 1024, 4)).astype(np.float32)
    data[:, 1] = rng.gamma(2.0, 1.0, size=32 * 1024).astype(np.float32)
    path = str(tmp_path_factory.mktemp("merged") / "corpus.rsp")
    rsp.partition(data, blocks=32, seed=2, out=path)
    return path


def _uncached_merge(summaries, kind):
    """The merge each query ran for itself before the memo: a deep copy of the
    first block's sketch, then every other block's merged in, in order."""
    acc = None
    for s in summaries:
        sk = s.get(kind)
        if acc is None:
            acc = sketch_from_dict(sk.to_dict())
        else:
            acc.merge(sk)
    return acc


def _bits(sketch) -> list:
    """Every number the sketch holds, as bytes: a KLL column's ``n``,
    ``compactions`` and level arrays; a KMV column's kept hashes."""
    if sketch.kind == "kll":
        return [
            (c.n, c.compactions, [(lv.dtype.str, lv.tobytes()) for lv in c.levels])
            for c in sketch._columns
        ]
    return [(c.dtype.str, c.tobytes()) for c in sketch._columns]


def _merges() -> dict:
    snap = obs.get_registry().snapshot().get("rsp_merged_sketch_total", {"series": []})
    return {(s["labels"]["kind"], s["labels"]["outcome"]): s["value"] for s in snap["series"]}


@pytest.mark.parametrize("kind", ["kll", "distinct"])
def test_shared_sketch_is_the_uncached_merge_bit_for_bit(store, kind):
    ds = rsp.open(store)
    shared = ds.merged_sketch(kind)
    ref = _uncached_merge(ds.summaries, kind)
    assert _bits(shared) == _bits(ref)
    assert shared.k == ref.k
    if kind == "kll":
        assert shared.seed == ref.seed
        assert shared.n == 32 * 1024
        # the merge itself compacted, beyond the first block's compactions
        first = ds.summaries[0].get("kll")._columns[0].compactions
        assert shared._columns[0].compactions > first
    assert ds.merged_sketch(kind) is shared


def test_p95_grids_and_answers_match_the_uncached_merge(store, monkeypatch):
    ds = rsp.open(store)
    grids = [QueryExecutor(ds, as_query("p95", **P95))._grid() for _ in range(2)]
    answers = [ds.query("p95", **P95) for _ in range(2)]

    before = rsp.open(store)
    monkeypatch.setattr(
        before, "merged_sketch", lambda kind: _uncached_merge(before.summaries, kind)
    )
    ref_lo, ref_hi = QueryExecutor(before, as_query("p95", **P95))._grid()
    ref = before.query("p95", **P95)

    for lo, hi in grids:
        assert lo.tobytes() == ref_lo.tobytes() and hi.tobytes() == ref_hi.tobytes()
    for res in answers:
        assert not res.from_sketches and res.blocks_read == ref.blocks_read
        (a,), (b,) = res.aggregates, ref.aggregates
        for x, y in ((a.estimate, b.estimate), (a.ci_lo, b.ci_lo), (a.ci_hi, b.ci_hi)):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


def test_served_p95s_merge_once(store):
    ds = rsp.open(store)
    obs.enable()
    n = 8
    with ds.serve(capacity=64, workers=4) as svc:
        tickets = [svc.submit("p95", **{**P95, "seed": i}) for i in range(n)]
        results = [svc.result(t, timeout=120) for t in tickets]
    assert all(r.blocks_read >= 4 and not r.from_sketches for r in results)
    assert _merges() == {("kll", "built"): 1.0, ("kll", "reused"): float(n - 1)}


def test_queries_leave_the_shared_sketch_as_built(store):
    ds = rsp.open(store)
    kll, kmv = ds.merged_sketch("kll"), ds.merged_sketch("distinct")
    kll_before, kmv_before = kll.to_dict(), kmv.to_dict()
    stored_before = ds.summaries[0].to_dict()

    fast = ds.query(["p95", "distinct"], use_sketches=True)
    slow = ds.query("p95", **P95)
    assert fast.from_sketches and not slow.from_sketches

    assert ds.merged_sketch("kll") is kll and ds.merged_sketch("distinct") is kmv
    assert kll.to_dict() == kll_before and kmv.to_dict() == kmv_before
    assert ds.summaries[0].to_dict() == stored_before


def test_replaced_summaries_build_anew(store):
    ds = rsp.open(store)
    obs.enable()
    first = ds.merged_sketch("kll")
    ds._summaries = list(ds.summaries)  # the same suites, a new list
    second = ds.merged_sketch("kll")
    assert second is not first and _bits(second) == _bits(first)
    assert ds.merged_sketch("kll") is second
    assert _merges() == {("kll", "built"): 2.0, ("kll", "reused"): 1.0}


def test_lazy_sketch_pass_builds_from_its_summaries():
    rng = np.random.default_rng(1)
    data = rng.normal(size=(8 * 512, 3)).astype(np.float32)
    ds = rsp.partition(data, blocks=8, seed=4, summaries=False)
    assert not ds.has_summaries
    obs.enable()
    ds.query("p95", **{**P95, "min_blocks": 2, "max_blocks": 2})
    assert ds.has_summaries
    assert _bits(ds.merged_sketch("kll")) == _bits(_uncached_merge(ds.summaries, "kll"))
    assert _merges() == {("kll", "built"): 1.0, ("kll", "reused"): 1.0}


def test_distributed_query_reads_the_dataset_memo(store):
    ds = rsp.open(store)
    obs.enable()

    def run(t):
        dds = ds.distribute(t, straggler_grace=5.0, poll_interval=0.01)
        return dds.query("p95", **P95).aggregates[0].estimate

    estimates = run_local_hosts(LocalTransport.group(2), run)
    assert np.asarray(estimates[0]).tobytes() == np.asarray(estimates[1]).tobytes()
    assert _merges() == {("kll", "built"): 1.0, ("kll", "reused"): 1.0}
    assert _bits(ds.merged_sketch("kll")) == _bits(_uncached_merge(ds.summaries, "kll"))
