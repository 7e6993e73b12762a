"""Streaming-engine benchmark: fetch-path throughput + sketch-guided selection.

Two measurements, mirroring what ``repro.rsp.engine`` is for:

1. **Fetch paths** -- records/sec for block-level estimation over a
   store-backed corpus through the three fetch paths: synchronous loads
   (``prefetch=0``), the prefetch pipeline, and memory-mapped streaming.
   The store fetcher is additionally wrapped with an emulated per-block
   I/O latency (``--latency``, default 8 ms) modelling the paper's setting
   of blocks served by a cluster file system rather than a warm local page
   cache; raw local-disk numbers are reported alongside.

2. **Selection policies** -- moment-estimation error vs. ``g`` for uniform
   block selection against sketch-weighted (HT-reweighted) selection on a
   *skewed, contiguously-chunked* corpus -- the non-RSP layout where
   uniform block sampling is at its worst and summary-statistics-driven
   selection (Rong et al., 2020) pays off.

Usage::

    PYTHONPATH=src python -m benchmarks.engine_bench            # full sizes
    PYTHONPATH=src python -m benchmarks.engine_bench --smoke    # CI gate

``--smoke`` uses small sizes and exits non-zero unless (a) the prefetched
path is >= 1.5x the synchronous path and (b) weighted selection beats
uniform on the skewed corpus -- so perf-path regressions fail loudly.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import numpy as np

from repro.core.registry import RSPStore
from repro.core.sampler import UniformPolicy, WeightedPolicy
from repro.core.types import RSPSpec
from repro.rsp.engine import BlockExecutor, MmapFetcher, StoreFetcher
from repro.rsp.summaries import combine_summaries, summarize_block, summarize_blocks
from repro.runtime import use_compile_cache


class LatencyFetcher:
    """Emulates remote-store latency: ``delay`` seconds per block fetch on
    top of the wrapped fetcher (sleeps release the GIL, like real I/O)."""

    def __init__(self, inner, delay: float):
        self.inner = inner
        self.delay = delay

    @property
    def num_blocks(self) -> int:
        return self.inner.num_blocks

    def fetch(self, block_id: int) -> np.ndarray:
        time.sleep(self.delay)
        return self.inner.fetch(block_id)


def _build_store(root: str, num_blocks: int, block_records: int, features: int) -> RSPStore:
    rng = np.random.default_rng(0)
    n = num_blocks * block_records
    data = rng.normal(size=(n, features)).astype(np.float32)
    spec = RSPSpec(
        num_records=n,
        num_blocks=num_blocks,
        num_original_blocks=1,  # layout metadata only; the bench writes blocks directly
        record_shape=(features,),
        dtype="float32",
    )
    store = RSPStore(root)
    store.write_partition(data.reshape(num_blocks, block_records, features), spec)
    return store


def _estimate(executor: BlockExecutor, num_blocks: int) -> None:
    """One full estimation sweep: sketch every block (``fn`` runs on the
    executor's workers, overlapping fetch and compute) and combine.
    Moments only -- this bench gates the engine's fetch/compute overlap,
    and the full suite's KLL/KMV folding would drown the fetch latency the
    prefetch pipeline is hiding."""
    sketches = executor.map_blocks(
        lambda b: summarize_block(b, 0, kinds=("moments",)), range(num_blocks)
    )
    combine_summaries(list(sketches))


def _throughput(executor: BlockExecutor, num_blocks: int, block_records: int) -> float:
    """records/sec for a full block-level estimation sweep."""
    t0 = time.perf_counter()
    _estimate(executor, num_blocks)
    return num_blocks * block_records / (time.perf_counter() - t0)


def bench_fetch_paths(
    *,
    num_blocks: int,
    block_records: int,
    features: int,
    latency: float,
    prefetch: int,
) -> dict[str, float]:
    out: dict[str, float] = {}
    with tempfile.TemporaryDirectory() as tmp:
        store = _build_store(os.path.join(tmp, "corpus"), num_blocks, block_records, features)
        # warm once so page-cache effects hit every path equally
        _estimate(BlockExecutor(StoreFetcher(store), prefetch=0), num_blocks)

        paths = {
            "sync": BlockExecutor(
                LatencyFetcher(StoreFetcher(store), latency), prefetch=0, cache_blocks=0
            ),
            "prefetch": BlockExecutor(
                LatencyFetcher(StoreFetcher(store), latency),
                prefetch=prefetch,
                cache_blocks=0,
            ),
            "sync_local": BlockExecutor(StoreFetcher(store), prefetch=0, cache_blocks=0),
            "prefetch_local": BlockExecutor(
                StoreFetcher(store), prefetch=prefetch, cache_blocks=0
            ),
            "mmap": BlockExecutor(MmapFetcher(store), prefetch=prefetch, cache_blocks=0),
        }
        for name, executor in paths.items():
            with executor:
                out[name] = _throughput(executor, num_blocks, block_records)
    return out


def bench_selection(
    *, num_blocks: int, block_records: int, gs: tuple[int, ...], trials: int
) -> list[tuple[int, float, float]]:
    """(g, uniform mean-abs-err, weighted mean-abs-err) on a skewed,
    contiguously chunked (non-RSP) corpus."""
    rng = np.random.default_rng(7)
    n = num_blocks * block_records
    x = np.sort(rng.lognormal(mean=1.0, sigma=1.2, size=n))
    blocks = x.reshape(num_blocks, block_records, 1)
    sketches = summarize_blocks(blocks)
    truth = x.mean()
    rows = []
    for g in gs:
        uni, wgt = [], []
        for s in range(trials):
            up = UniformPolicy(num_blocks, seed=s)
            ids = up.sample(g)
            uni.append(abs(combine_summaries([sketches[k] for k in ids]).mean[0] - truth))
            wp = WeightedPolicy(num_blocks, sketches, seed=s)
            ids = wp.sample(g)
            est = combine_summaries(
                [sketches[k] for k in ids], weights=wp.weights(ids), total_count=n
            ).mean[0]
            wgt.append(abs(est - truth))
        rows.append((g, float(np.mean(uni)), float(np.mean(wgt))))
    return rows


def engine_rows(smoke: bool = False, latency: float = 8e-3) -> list[tuple[str, float, str]]:
    """``benchmarks.run``-style rows: (name, value, derived)."""
    if smoke:
        fetch_kw = dict(num_blocks=32, block_records=8192, features=32)
        sel_kw = dict(num_blocks=32, block_records=128, gs=(4, 8), trials=60)
    else:
        fetch_kw = dict(num_blocks=96, block_records=16384, features=32)
        sel_kw = dict(num_blocks=64, block_records=1024, gs=(2, 4, 8, 16), trials=200)
    rows: list[tuple[str, float, str]] = []
    tp = bench_fetch_paths(latency=latency, prefetch=4, **fetch_kw)
    speedup = tp["prefetch"] / tp["sync"]
    for name, rps in tp.items():
        derived = f"records_per_s={rps:,.0f}"
        if name == "prefetch":
            derived += f" speedup_vs_sync={speedup:.2f}x"
        rows.append((f"engine_fetch_{name}", rps, derived))
    for g, uerr, werr in bench_selection(**sel_kw):
        # row value is the uniform/weighted error ratio (>1 == weighted wins):
        # it stays legible under the harness's fixed-point value formatting,
        # unlike the raw ~1e-2 error magnitudes kept in the derived column
        rows.append(
            (
                f"engine_policy_g{g}",
                uerr / max(werr, 1e-12),
                f"uniform_err={uerr:.4f} weighted_err={werr:.4f} "
                f"ratio={uerr / max(werr, 1e-12):.2f}x",
            )
        )
    return rows


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small sizes + hard pass/fail gate")
    ap.add_argument("--latency", type=float, default=8e-3,
                    help="emulated per-block store latency in seconds (default 8ms)")
    args = ap.parse_args()

    rows = engine_rows(smoke=args.smoke, latency=args.latency)
    print("name,value,derived")
    for name, value, derived in rows:
        print(f"{name},{value:.1f},{derived}")

    if args.smoke:
        by_name = {name: (value, derived) for name, value, derived in rows}
        speedup = by_name["engine_fetch_prefetch"][0] / by_name["engine_fetch_sync"][0]
        policy_rows = [(n, d) for n, (v, d) in by_name.items() if n.startswith("engine_policy")]
        weighted_wins = all(
            float(d.split("ratio=")[1].rstrip("x")) > 1.0 for _, d in policy_rows
        )
        ok = True
        if speedup < 1.5:
            print(f"SMOKE FAIL: prefetch speedup {speedup:.2f}x < 1.5x", file=sys.stderr)
            ok = False
        if not weighted_wins:
            print("SMOKE FAIL: weighted selection did not beat uniform", file=sys.stderr)
            ok = False
        if not ok:
            sys.exit(1)
        print(f"SMOKE OK: prefetch {speedup:.2f}x, weighted beats uniform at all g")


if __name__ == "__main__":
    main()
