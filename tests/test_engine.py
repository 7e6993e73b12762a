"""Streaming block-execution engine tests: fetcher round-trips, prefetch
pipeline equivalence + exception propagation, LRU cache, sampling policies
(HT unbiasedness on skewed data), and the similarity self-inclusion fix."""

import threading
import time

import numpy as np
import pytest

from repro import rsp
from repro.core import RSPSpec, RSPStore
from repro.core.sampler import (
    StratifiedPolicy,
    UniformPolicy,
    WeightedPolicy,
    make_policy,
)
from repro.rsp.engine import (
    BlockExecutor,
    CallerStats,
    MemoryFetcher,
    MmapFetcher,
    StoreFetcher,
    as_fetcher,
)
from repro.rsp.summaries import combine_summaries, summarize_blocks


def _blocks(k=6, n=32, f=3, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(k, n, f)).astype(np.float32)


@pytest.fixture()
def store(tmp_path):
    blocks = _blocks(k=8, n=64, f=4)
    spec = RSPSpec(
        num_records=8 * 64, num_blocks=8, num_original_blocks=1, record_shape=(4,)
    )
    s = RSPStore(str(tmp_path / "rsp"))
    s.write_partition(blocks, spec)
    return s, blocks


# ---------------------------------------------------------------------------
# Executor primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefetch", [0, 3])
def test_map_blocks_ordered_and_equivalent(store, prefetch):
    s, blocks = store
    with BlockExecutor(StoreFetcher(s), prefetch=prefetch) as ex:
        got = list(ex.map_blocks(None, [5, 1, 6, 2, 2]))
    for g, k in zip(got, [5, 1, 6, 2, 2]):
        np.testing.assert_array_equal(np.asarray(g), blocks[k])


def test_map_blocks_fn_and_with_ids(store):
    s, blocks = store
    with BlockExecutor(StoreFetcher(s), prefetch=2) as ex:
        got = list(ex.map_blocks(lambda b: b.sum(), [0, 3], with_ids=True))
    assert [bid for bid, _ in got] == [0, 3]
    for bid, v in got:
        np.testing.assert_allclose(v, blocks[bid].sum(), rtol=1e-6)


def test_take_matches_blocks(store):
    s, blocks = store
    with BlockExecutor(StoreFetcher(s), prefetch=4) as ex:
        np.testing.assert_array_equal(ex.take([2, 0, 7]), blocks[[2, 0, 7]])


@pytest.mark.parametrize("prefetch", [0, 2])
def test_stream_batches_cover_records(store, prefetch):
    s, blocks = store
    with BlockExecutor(StoreFetcher(s), prefetch=prefetch) as ex:
        batches = list(ex.stream_batches(range(8), 96, drop_last=False))
    assert all(b.shape[0] == 96 for b in batches[:-1])
    got = np.concatenate(batches)
    np.testing.assert_array_equal(got, blocks.reshape(-1, 4))


def test_stream_batches_prepare_runs_per_block(store):
    s, blocks = store
    with BlockExecutor(StoreFetcher(s), prefetch=2) as ex:
        batches = list(
            ex.stream_batches(
                range(8), 64, prepare=lambda bid, b: b + bid, drop_last=False
            )
        )
    got = np.concatenate(batches)
    want = np.concatenate([blocks[k] + k for k in range(8)]).reshape(-1, 4)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("prefetch", [0, 3])
def test_worker_exception_propagates(prefetch):
    class Flaky:
        num_blocks = 5

        def fetch(self, k):
            if k == 3:
                raise RuntimeError("disk on fire")
            return np.zeros((4, 2), np.float32)

    with BlockExecutor(Flaky(), prefetch=prefetch) as ex:
        it = ex.map_blocks(None, range(5))
        for _ in range(3):
            next(it)
        with pytest.raises(RuntimeError, match="disk on fire"):
            next(it)


def test_fetched_blocks_are_read_only(store, tmp_path):
    # blocks are shared between the LRU cache and consumers: in-place writes
    # must fail loudly instead of silently corrupting later reads
    s, blocks = store
    with BlockExecutor(StoreFetcher(s), prefetch=0, cache_blocks=4) as ex:
        b = ex.fetch(1)
        with pytest.raises(ValueError):
            b[0, 0] = 99.0
        np.testing.assert_array_equal(np.asarray(ex.fetch(1)), blocks[1])
    ds = rsp.RSPDataset(s.spec(), store=s)
    with pytest.raises(ValueError):
        ds.block(0)[0, 0] = 99.0


def test_loader_uses_dataset_fetcher(tmp_path):
    # ds.loader() must train on what the dataset's fetcher serves, not on
    # raw store bytes behind a custom fetcher's back
    data = _blocks(k=4, n=64, f=3).reshape(-1, 3)
    ds = rsp.partition(data, blocks=4, seed=0, backend="np").save(str(tmp_path / "c"))

    class ScalingFetcher:
        def __init__(self, store):
            self.inner = StoreFetcher(store)

        @property
        def num_blocks(self):
            return self.inner.num_blocks

        def fetch(self, k):
            return self.inner.fetch(k) * 10.0

    custom = rsp.RSPDataset(ds.spec, store=ds.store, fetcher=ScalingFetcher(ds.store))
    batch = custom.loader(batch_size=32, seed=1).next_batch()
    plain = rsp.open(str(tmp_path / "c")).loader(batch_size=32, seed=1).next_batch()
    np.testing.assert_allclose(batch, plain * 10.0, rtol=1e-6)


def test_lru_cache_hits_and_evicts():
    calls: list[int] = []

    class Counting:
        num_blocks = 6

        def fetch(self, k):
            calls.append(k)
            return np.full((2, 2), k, np.float32)

    ex = BlockExecutor(Counting(), prefetch=0, cache_blocks=2)
    ex.fetch(0), ex.fetch(0), ex.fetch(0)
    assert calls == [0]  # cached
    ex.fetch(1), ex.fetch(0)  # both resident (cap 2)
    assert calls == [0, 1]
    ex.fetch(2)  # evicts 1 (LRU order: 0 was touched last)
    ex.fetch(1)
    assert calls == [0, 1, 2, 1]


# ---------------------------------------------------------------------------
# Fetchers
# ---------------------------------------------------------------------------

def test_mmap_fetcher_roundtrip(store):
    s, blocks = store
    f = MmapFetcher(s)
    assert f.num_blocks == 8
    for k in range(8):
        got = f.fetch(k)
        assert isinstance(got, np.memmap)  # streamed, not materialized
        np.testing.assert_array_equal(np.asarray(got), np.asarray(s.load_block(k, mmap=False)))
    with BlockExecutor(f, prefetch=2) as ex:
        np.testing.assert_array_equal(ex.take(range(8)), blocks)


def test_as_fetcher_adapters(store, tmp_path):
    s, blocks = store
    assert isinstance(as_fetcher(blocks), MemoryFetcher)
    assert isinstance(as_fetcher(s), StoreFetcher)
    assert isinstance(as_fetcher(s, mode="mmap"), MmapFetcher)
    ds = rsp.RSPDataset(s.spec(), store=s)
    adapted = as_fetcher(ds)
    np.testing.assert_array_equal(np.asarray(adapted.fetch(3)), blocks[3])
    assert adapted.num_blocks == 8
    with pytest.raises(TypeError):
        as_fetcher(object())


def test_dataset_fetcher_modes(tmp_path):
    data = _blocks(k=4, n=64, f=3).reshape(-1, 3)
    ds = rsp.partition(data, blocks=4, seed=0, backend="np").save(str(tmp_path / "c"))
    for mode in ("auto", "memory", "store", "mmap"):
        got = rsp.open(str(tmp_path / "c"), fetcher=mode)
        np.testing.assert_array_equal(np.asarray(got.block(2)), np.asarray(ds.block(2)))
        np.testing.assert_array_equal(got.stacked(), ds.stacked())
    with pytest.raises(ValueError, match="unknown fetcher"):
        rsp.open(str(tmp_path / "c"), fetcher="carrier-pigeon").block(0)


# ---------------------------------------------------------------------------
# Sampling policies + HT reweighting
# ---------------------------------------------------------------------------

def _skewed_sketches(k=32, n=128, seed=1):
    rng = np.random.default_rng(seed)
    x = np.sort(rng.lognormal(mean=1.0, sigma=1.2, size=k * n))
    blocks = x.reshape(k, n, 1)
    return summarize_blocks(blocks), x.mean(), k * n


def test_uniform_policy_matches_block_sampler():
    from repro.core import BlockSampler

    pol = UniformPolicy(16, seed=5)
    ref = BlockSampler(16, seed=5)
    assert pol.sample(6) == ref.sample(6)
    state = pol.state_dict()
    pol2 = UniformPolicy(16, seed=0)
    pol2.load_state_dict(state)
    assert pol2.sample(4) == ref.sample(4)


def test_weighted_policy_ht_unbiased_and_beats_uniform():
    sketches, truth, n = _skewed_sketches()
    g, uni_err, w_err, w_est = 6, [], [], []
    for s in range(150):
        up = UniformPolicy(len(sketches), seed=s)
        ids = up.sample(g)
        uni_err.append(abs(combine_summaries([sketches[k] for k in ids]).mean[0] - truth))
        wp = WeightedPolicy(len(sketches), sketches, seed=s)
        ids = wp.sample(g)
        est = combine_summaries(
            [sketches[k] for k in ids], weights=wp.weights(ids), total_count=n
        ).mean[0]
        w_est.append(est)
        w_err.append(abs(est - truth))
    # unbiased: the average of HT estimates lands on the truth
    assert abs(np.mean(w_est) - truth) < 0.05 * truth
    # and on skewed (non-RSP) blocks, sketch-weighted selection wins clearly
    assert np.mean(w_err) < 0.5 * np.mean(uni_err)


def test_weighted_policy_determinism_and_state():
    sketches, _, _ = _skewed_sketches(k=8)
    a = WeightedPolicy(8, sketches, seed=3)
    b = WeightedPolicy(8, sketches, seed=3)
    assert a.sample(4) == b.sample(4)
    state = a.state_dict()
    c = WeightedPolicy(8, sketches, seed=0)
    c.load_state_dict(state)
    assert c.sample(4) == b.sample(4)


def test_stratified_policy_allocation_and_weights():
    # 6 blocks: 4 dominated by label 0, 2 by label 1
    blocks = np.zeros((6, 32, 2), np.float32)
    blocks[4:, :, 1] = 1.0
    sketches = summarize_blocks(blocks, label_column=1, num_classes=2)
    pol = StratifiedPolicy(6, sketches, seed=0)
    ids = pol.sample(3)
    assert len(ids) == 3 and len(set(ids)) == 3
    strata = {k: (0 if k < 4 else 1) for k in range(6)}
    drawn = [strata[i] for i in ids]
    assert drawn.count(0) == 2 and drawn.count(1) == 1  # proportional 4:2
    w = pol.weights(ids)
    np.testing.assert_allclose(w, [2.0, 2.0, 2.0])  # 4/2 and 2/1


def test_stratified_single_draw_stream_visits_all_strata():
    # regression: deterministic largest-remainder allocation starved small
    # strata at g=1 (the loader's refill pattern) -- remainder draws are now
    # randomized in proportion, so a g=1 stream covers every stratum
    blocks = np.zeros((10, 16, 2), np.float32)
    blocks[6:9, :, 1] = 1.0   # stratum sizes 6 / 3 / 1
    blocks[9:, :, 1] = 2.0
    sketches = summarize_blocks(blocks, label_column=1, num_classes=3)
    pol = StratifiedPolicy(10, sketches, seed=0)
    drawn = {pol.sample(1)[0] for _ in range(200)}
    assert 9 in drawn            # the single-block stratum is reachable
    assert drawn & set(range(6)) and drawn & {6, 7, 8}


def test_stratified_policy_requires_label_hists():
    sketches = summarize_blocks(_blocks(k=4))
    with pytest.raises(ValueError, match="label histograms"):
        StratifiedPolicy(4, sketches)


def test_make_policy_errors():
    with pytest.raises(ValueError, match="unknown sampling policy"):
        make_policy("thompson", 8)
    with pytest.raises(ValueError, match="summaries"):
        make_policy("weighted", 8)
    with pytest.raises(ValueError, match="summaries"):
        make_policy("stratified", 8)


def test_combine_summaries_weighted_exact_on_full_population():
    blocks = _blocks(k=5, n=16, f=2, seed=3)
    sketches = summarize_blocks(blocks)
    plain = combine_summaries(sketches)
    ht = combine_summaries(
        sketches, weights=np.ones(5), total_count=int(plain.count)
    )
    np.testing.assert_allclose(ht.mean, plain.mean, rtol=1e-9)
    np.testing.assert_allclose(ht.m2, plain.m2, rtol=1e-9, atol=1e-9)
    assert ht.count == plain.count


def test_combine_summaries_weight_validation():
    sketches = summarize_blocks(_blocks(k=3))
    with pytest.raises(ValueError, match="weights"):
        combine_summaries(sketches, weights=np.ones(2))
    with pytest.raises(ValueError, match="weights"):
        combine_summaries(sketches, weights=np.array([1.0, -1.0, 1.0]))


# ---------------------------------------------------------------------------
# Dataset surface: sample/moments/estimate with policies
# ---------------------------------------------------------------------------

def _labelled_dataset(n=2048, k=8, seed=2):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    data = np.concatenate([x, y[:, None]], axis=1)
    return rsp.partition(data, blocks=k, seed=seed, backend="np", num_classes=2), data


def test_dataset_policy_surface(tmp_path):
    ds, data = _labelled_dataset()
    for policy in ("uniform", "weighted", "stratified"):
        ids = ds.sample(4, seed=1, policy=policy)
        assert len(ids) == 4 and all(0 <= i < 8 for i in ids)
        m = ds.moments(g=4, seed=1, policy=policy)
        assert np.abs(m.mean - data.astype(np.float64).mean(0)).max() < 0.5
    est = ds.estimate(lambda b: b.mean(0), g=4, seed=1, policy="weighted")
    assert np.abs(est - data.mean(0)).max() < 0.5
    with pytest.raises(ValueError, match="need g"):
        ds.moments(policy="weighted")
    with pytest.raises(ValueError, match="ids or a non-uniform policy"):
        ds.moments(ids=[0, 1], policy="weighted")  # no silent unweighted combine
    # store-backed too (sketches come from the manifest)
    ds.save(str(tmp_path / "c"))
    got = rsp.open(str(tmp_path / "c"))
    m = got.moments(g=4, seed=1, policy="stratified")
    assert np.isfinite(m.mean).all()


def test_dataset_estimator_streams_through_executor(tmp_path):
    ds, data = _labelled_dataset()
    ds.save(str(tmp_path / "c"))
    got = rsp.open(str(tmp_path / "c"), prefetch=3)
    est = got.estimator(g=6, seed=0)
    assert est.blocks_seen == 6
    ref = ds.estimator(g=6, seed=0)
    np.testing.assert_allclose(est.stats.mean, ref.stats.mean, rtol=1e-6)


# ---------------------------------------------------------------------------
# Similarity: the probed block must not ride in its own reference sample
# ---------------------------------------------------------------------------

def test_corpus_reference_excludes_probe(tmp_path):
    # constant-valued blocks make self-inclusion visible in the reference
    k, n = 4, 64
    blocks = np.stack([np.full((n, 1), float(i), np.float32) for i in range(k)])
    spec = RSPSpec(num_records=k * n, num_blocks=k, num_original_blocks=1, record_shape=(1,))
    store = RSPStore(str(tmp_path / "c"))
    store.write_partition(blocks, spec)
    ds = rsp.RSPDataset(spec, store=store)
    for probe in range(k):
        ref = ds._corpus_reference(4096, seed=0, exclude=probe)
        assert float(probe) not in set(np.unique(ref))
        assert ref.shape[0] >= n  # still a usable reference


def test_similarity_detects_outlier_block(tmp_path):
    ds, data = _labelled_dataset(n=2048, k=8)
    ds.save(str(tmp_path / "c"))
    got = rsp.open(str(tmp_path / "c"))
    # corrupt one stored block far away from the corpus
    bad = np.asarray(got.block(5)) + 50.0
    np.save(store_path := str(tmp_path / "c" / "block_00005.npy"), bad)
    got2 = rsp.open(str(tmp_path / "c"))
    sane = got2.similarity(1, metric="mmd", seed=0)
    outlier = got2.similarity(5, metric="mmd", seed=0)
    assert outlier > sane + 0.1


# ---------------------------------------------------------------------------
# Cache / prefetch instrumentation (ExecutorStats)
# ---------------------------------------------------------------------------

def test_stats_hits_misses_evictions():
    blocks = _blocks(k=6)
    with BlockExecutor(MemoryFetcher(blocks), prefetch=0, cache_blocks=2) as ex:
        assert ex.stats() == rsp.ExecutorStats()
        ex.fetch(0)          # miss
        ex.fetch(0)          # hit
        ex.fetch(1)          # miss (cache {0, 1})
        ex.fetch(2)          # miss -> evicts 0
        ex.fetch(0)          # miss -> evicts 1
        s = ex.stats()
    assert (s.hits, s.misses, s.evictions) == (1, 4, 2)
    assert s.blocks_fetched == 4


def test_stats_cache_disabled_counts_every_fetch_as_miss():
    blocks = _blocks(k=4)
    with BlockExecutor(MemoryFetcher(blocks), prefetch=0, cache_blocks=0) as ex:
        for _ in range(3):
            ex.fetch(1)
        s = ex.stats()
    assert (s.hits, s.misses, s.evictions) == (0, 3, 0)


def test_stats_snapshot_subtraction_meters_a_window():
    blocks = _blocks(k=5)
    with BlockExecutor(MemoryFetcher(blocks), prefetch=0, cache_blocks=8) as ex:
        ex.fetch(0)
        before = ex.stats()
        ex.fetch(0)  # hit
        ex.fetch(1)  # miss
        window = ex.stats() - before
    assert (window.hits, window.misses) == (1, 1)
    assert window.blocks_fetched == 1


def test_stats_under_prefetch_pipeline():
    blocks = _blocks(k=8)
    with BlockExecutor(MemoryFetcher(blocks), prefetch=3, cache_blocks=8) as ex:
        list(ex.map_blocks(None, [0, 1, 2, 3, 0, 1]))
        s = ex.stats()
    assert s.hits + s.misses == 6
    assert s.misses >= 4  # at least the four distinct blocks were fetched


def test_closing_a_stream_waits_for_its_running_fetches():
    """A stream closed early (a query that converged) leaves none of its
    fetches running, so its counter is final once ``close()`` returns."""
    started = threading.Event()

    class Slow(MemoryFetcher):
        def fetch(self, block_id):
            if block_id:
                started.set()
                time.sleep(0.2)
            return super().fetch(block_id)

    counter = CallerStats()
    with BlockExecutor(Slow(_blocks(k=8)), prefetch=3, cache_blocks=0) as ex:
        stream = ex.map_blocks(None, range(8), counter=counter)
        next(stream)
        assert started.wait(5.0)
        stream.close()
        closed = counter.stats()
        time.sleep(0.5)
        assert counter.stats() == closed
    assert closed.misses >= 2  # block 0 and a prefetch that was running


def test_reset_stats():
    blocks = _blocks(k=3)
    with BlockExecutor(MemoryFetcher(blocks), prefetch=0) as ex:
        ex.fetch(0)
        ex.reset_stats()
        assert ex.stats() == rsp.ExecutorStats()


def test_stats_consistent_under_concurrent_hammering():
    """``stats()`` must be an atomic snapshot: with 8 threads fetching
    concurrently, every observed snapshot satisfies the conservation law
    ``accesses == hits + misses`` and counters never run backwards."""
    import threading

    blocks = _blocks(k=16)
    stop = threading.Event()
    bad: list[str] = []

    with BlockExecutor(MemoryFetcher(blocks), prefetch=0, cache_blocks=4) as ex:
        def hammer(seed: int) -> None:
            rng = np.random.default_rng(seed)
            while not stop.is_set():
                ex.fetch(int(rng.integers(0, 16)))

        def watch() -> None:
            prev = ex.stats()
            while not stop.is_set():
                s = ex.stats()
                total = s.hits + s.misses
                if s.blocks_fetched != s.misses:
                    bad.append(f"blocks_fetched {s.blocks_fetched} != misses {s.misses}")
                if s.hits < prev.hits or s.misses < prev.misses or total < (
                    prev.hits + prev.misses
                ):
                    bad.append(f"counters ran backwards: {prev} -> {s}")
                prev = s

        threads = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
        threads += [threading.Thread(target=watch) for _ in range(2)]
        for t in threads:
            t.start()
        import time as _time

        _time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join()
        final = ex.stats()

    assert not bad, bad[:5]
    assert final.hits + final.misses > 0
    assert final.blocks_fetched == final.misses
