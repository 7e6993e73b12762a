"""Progressive-query benchmark: sketch fast path + early stopping + fused sketch.

Three measurements, mirroring what ``repro.rsp.query`` is for:

1. **Sketch fast path** -- latency of moment/count queries answered from the
   partition-time sketches alone, with the block-fetch count asserted to be
   exactly zero (the executor's stats are the witness).

2. **Progressive early stopping** -- a quantile query at 1% target relative
   error over a store-backed corpus: how many of the K blocks the anytime CI
   loop actually reads before the interval is tight enough, and the speedup
   versus scanning every block.

3. **Fused sketch kernel** -- records/sec of the fused moments+histogram
   sketch (``repro.kernels.block_sketch``, ``impl="auto"``) against the
   two-pass equivalent (separate moments and histogram sweeps) it replaces.
   On CPU these are plumbing numbers (both paths are RAM-resident); the
   single-HBM-pass win is the Pallas kernel's TPU story, like the other
   interpret-mode kernel benchmarks.

Usage::

    PYTHONPATH=src python -m benchmarks.query_bench            # full sizes
    PYTHONPATH=src python -m benchmarks.query_bench --smoke    # CI gate

``--smoke`` uses small sizes and exits non-zero unless (a) sketch-only
queries read 0 blocks and (b) the progressive quantile query stops at <50%
of the blocks at 1% target error -- so regressions in the query layer's
whole point (few blocks, zero-read fast paths) fail loudly.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import numpy as np

from repro import rsp
from repro.core.estimators import block_histogram, block_moments
from repro.kernels.block_sketch import block_sketch
from repro.runtime import use_compile_cache


def _build(num_blocks: int, block_records: int, features: int, *, shift: float = 5.0):
    """A shifted-normal corpus (non-zero median, so relative stopping is
    well-posed) partitioned in memory."""
    rng = np.random.default_rng(0)
    n = num_blocks * block_records
    data = rng.normal(shift, 1.0, size=(n, features)).astype(np.float32)
    return rsp.partition(data, blocks=num_blocks, seed=1), data


def bench_sketch_path(ds, repeats: int = 20) -> tuple[float, int]:
    """(us per sketch-only query, blocks fetched across all repeats)."""
    before = ds.executor.stats()
    t0 = time.perf_counter()
    for _ in range(repeats):
        res = ds.query(["mean", "var", "sum", "count"])
        assert res.from_sketches
    us = (time.perf_counter() - t0) / repeats * 1e6
    fetched = (ds.executor.stats() - before).blocks_fetched
    return us, fetched


def bench_progressive_quantile(
    ds_path: str, *, target: float = 0.01, seed: int = 0
) -> tuple[int, int, float, float]:
    """(blocks_read, total_blocks, speedup_vs_full_scan, rows_per_s) for a
    p50 query at ``target`` relative error on a store-backed dataset."""
    ds = rsp.open(ds_path, cache_blocks=0)
    t0 = time.perf_counter()
    res = ds.query(
        "median", target_rel_err=target, use_sketches=False, seed=seed
    )
    t_query = time.perf_counter() - t0
    assert res.executor_stats.blocks_fetched >= res.blocks_read  # honest I/O count
    rows_per_s = res.executor_stats.rows_fetched / max(t_query, 1e-9)
    t0 = time.perf_counter()
    full = rsp.open(ds_path, cache_blocks=0)
    full.query("median", use_sketches=False, target_rel_err=None, seed=seed)
    t_full = time.perf_counter() - t0
    ds.close()
    full.close()
    return res.blocks_read, res.total_blocks, t_full / max(t_query, 1e-9), rows_per_s


def bench_fused_sketch(block: np.ndarray, *, bins: int = 128, repeats: int = 10):
    """records/sec: fused one-pass sketch vs separate moments + histogram."""
    lo, hi = block.min(0), block.max(0)
    n = block.shape[0]

    def timed(fn):
        fn()
        t0 = time.perf_counter()
        for _ in range(repeats):
            fn()
        return n * repeats / (time.perf_counter() - t0)

    fused = timed(lambda: block_sketch(block, bins=bins, lo=lo, hi=hi, impl="auto"))
    two_pass = timed(
        lambda: (block_moments(block), block_histogram(block, bins=bins, lo=-8, hi=8))
    )
    return fused, two_pass


def query_rows(smoke: bool = False) -> list[tuple]:
    """``benchmarks.run``-style rows ``(name, value, derived, metrics)``
    with per-row rows/s throughput in the metrics dict."""
    if smoke:
        # block_records must divide by num_blocks (Algorithm 1's delta slices)
        kw = dict(num_blocks=48, block_records=2304, features=8)
    else:
        kw = dict(num_blocks=96, block_records=9216, features=16)
    rows: list[tuple] = []
    ds, _ = _build(**kw)

    us, fetched = bench_sketch_path(ds)
    rows.append(
        (
            "query_sketch_only",
            us,
            f"us_per_query={us:.0f} blocks_fetched={fetched}",
            {"rows_per_s": 0.0, "queries_per_s": 1e6 / max(us, 1e-9)},
        )
    )

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "corpus.rsp")
        ds.save(path)
        read, total, speedup, rows_per_s = bench_progressive_quantile(path)
        rows.append(
            (
                "query_progressive_p50",
                read,
                f"blocks={read}/{total} frac={read / total:.2f}"
                f" speedup_vs_full={speedup:.1f}x rows_per_s={rows_per_s:,.0f}",
                {"rows_per_s": rows_per_s},
            )
        )
    block = np.asarray(ds.block(0))
    fused, two_pass = bench_fused_sketch(block)
    ds.close()
    rows.append(
        (
            "query_fused_sketch",
            fused,
            f"records_per_s={fused:,.0f} two_pass={two_pass:,.0f}"
            f" ratio={fused / max(two_pass, 1e-9):.2f}x",
            {"rows_per_s": fused, "two_pass_rows_per_s": two_pass},
        )
    )
    return rows


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small sizes + hard pass/fail gate")
    args = ap.parse_args()

    rows = query_rows(smoke=args.smoke)
    print("name,value,derived")
    for row in rows:
        print(f"{row[0]},{row[1]:.1f},{row[2]}")

    if args.smoke:
        by_name = {row[0]: row[2] for row in rows}
        ok = True
        fetched = int(by_name["query_sketch_only"].split("blocks_fetched=")[1])
        if fetched != 0:
            print(f"SMOKE FAIL: sketch-only queries fetched {fetched} blocks", file=sys.stderr)
            ok = False
        frac = float(by_name["query_progressive_p50"].split("frac=")[1].split()[0])
        if frac >= 0.5:
            print(
                f"SMOKE FAIL: progressive p50 read {frac:.0%} of blocks (>= 50%)",
                file=sys.stderr,
            )
            ok = False
        if not ok:
            sys.exit(1)
        print(
            f"SMOKE OK: sketch-only reads 0 blocks; progressive p50 stopped at"
            f" {frac:.0%} of blocks at 1% target error"
        )


if __name__ == "__main__":
    main()
