"""Sketch-suite benchmark: KLL accuracy, sketch-only quantiles, and the
query-aware block-selection race.

The corpus is deliberately *skewed at the block level* -- a minority of
"rich" blocks holds almost all rows matching the benchmark predicate, the
way a time- or source-ordered corpus looks before RSP randomization.  That
is exactly the regime where block selection matters, and three claims of the
sketch subsystem are measured against it:

1. **KLL rank error** -- the merged per-column KLL sketch answers p50/p95
   within its analytic rank-error bound ``kll_rank_error_bound(k)`` against
   the exact sorted corpus.

2. **Sketch-only quantiles** -- ``query(["p50", "p95"], use_sketches=True)``
   answers with *zero* block fetches (the executor's counter is the
   witness) and every estimate falls inside the true value band
   ``[Q(q - eps), Q(q + eps)]``.

3. **Query-aware beats dispersion-PPS** -- a filtered progressive quantile
   query at 1% target relative error reads strictly fewer blocks under
   ``policy="query_aware"`` (predicate selectivity from the per-block KLL
   sketches) than under ``policy="weighted"`` (dispersion-only PPS), for
   p50 *and* p95, averaged over several selection seeds.

Usage::

    PYTHONPATH=src python -m benchmarks.sketch_bench            # full sizes
    PYTHONPATH=src python -m benchmarks.sketch_bench --smoke    # CI gate

``--smoke`` uses small sizes and exits non-zero unless all three gates
hold, so regressions in the sketch path fail loudly.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from benchmarks.artifact import write_artifact
from repro.core.types import RSPSpec
from repro.rsp.dataset import RSPDataset
from repro.rsp.sketch import kll_rank_error_bound, merge_suites
from repro.rsp.summaries import summarize_blocks
from repro.runtime import use_compile_cache

PREDICATE = "c0 > 1.0"
QUANTILES = {"p50": 0.5, "p95": 0.95}


def build_skewed(num_blocks: int, block_records: int, features: int, *, seed: int = 0):
    """Block-skewed corpus: every 4th block is "rich" (its column 0 sits
    around +2, so most rows pass ``c0 > 1.0``); the rest are "poor" (column
    0 around -2, essentially nothing passes).  The *other* columns are
    i.i.d. shifted normals everywhere, so the filtered quantile answer
    itself is block-invariant -- only where the matching rows live is
    skewed."""
    rng = np.random.default_rng(seed)
    blocks = np.empty((num_blocks, block_records, features), dtype=np.float32)
    for k in range(num_blocks):
        # shifted normal: non-zero quantiles, so 1% *relative* error is a
        # well-posed target for p50 and p95 alike
        x = rng.normal(5.0, 1.0, size=(block_records, features))
        loc = 2.0 if k % 4 == 0 else -2.0
        x[:, 0] = rng.normal(loc, 0.8, size=block_records)
        blocks[k] = x
    n = num_blocks * block_records
    spec = RSPSpec(
        num_records=n,
        num_blocks=num_blocks,
        num_original_blocks=num_blocks,
        record_shape=(features,),
    )
    ds = RSPDataset(spec, blocks=blocks, summaries=summarize_blocks(blocks))
    return ds, blocks.reshape(n, features).astype(np.float64)


def measured_rank_error(ds, data: np.ndarray) -> float:
    """Worst empirical rank error of the merged KLL over both gate
    quantiles and every feature."""
    kll = merge_suites(ds.summaries).get("kll")
    worst = 0.0
    srt = np.sort(data, axis=0)
    n = data.shape[0]
    for q in QUANTILES.values():
        est = kll.quantile([q])[:, 0]
        for j in range(data.shape[1]):
            rank = np.searchsorted(srt[:, j], est[j], side="right") / n
            worst = max(worst, abs(rank - q))
    return worst


def sketch_only_quantiles(ds, data: np.ndarray):
    """(blocks_fetched, within_band) for a forced sketch-only p50/p95."""
    before = ds.executor.stats()
    res = ds.query(list(QUANTILES), use_sketches=True)
    fetched = (ds.executor.stats() - before).blocks_fetched
    eps = kll_rank_error_bound(merge_suites(ds.summaries).get("kll").k)
    srt = np.sort(data, axis=0)
    n = data.shape[0]
    within = bool(res.from_sketches)
    for name, q in QUANTILES.items():
        lo = srt[max(int(np.floor((q - eps) * n)), 0)]
        hi = srt[min(int(np.ceil((q + eps) * n)), n - 1)]
        est = np.asarray(res[name].estimate, dtype=np.float64)
        within = within and bool(np.all(est >= lo) and np.all(est <= hi))
    return int(fetched), within


def policy_race(
    ds, *, target: float = 0.01, seeds=(0, 1, 2)
) -> dict[str, dict[str, float]]:
    """Mean blocks_read per policy for each filtered progressive quantile,
    averaged over selection seeds (same seeds for both policies)."""
    out: dict[str, dict[str, float]] = {name: {} for name in QUANTILES}
    for name in QUANTILES:
        for policy in ("weighted", "query_aware"):
            reads = []
            for seed in seeds:
                res = ds.query(
                    name,
                    where=PREDICATE,
                    target_rel_err=target,
                    use_sketches=False,
                    policy=policy,
                    seed=seed,
                )
                reads.append(res.blocks_read)
            out[name][policy] = float(np.mean(reads))
    return out


SMOKE_SIZES = dict(num_blocks=48, block_records=960, features=4)
FULL_SIZES = dict(num_blocks=96, block_records=4800, features=8)


def sketch_rows(smoke: bool = False) -> list[tuple]:
    """``benchmarks.run``-style rows ``(name, value, derived)``."""
    ds, data = build_skewed(**(SMOKE_SIZES if smoke else FULL_SIZES))
    try:
        eps = kll_rank_error_bound(merge_suites(ds.summaries).get("kll").k)
        rank_err = measured_rank_error(ds, data)
        fetched, within = sketch_only_quantiles(ds, data)
        race = policy_race(ds)
    finally:
        ds.close()
    rows = [
        (
            "sketch_kll_rank_error",
            rank_err,
            f"measured={rank_err:.4f} bound={eps:.4f} "
            f"ok={rank_err <= eps}",
        ),
        (
            "sketch_only_quantiles",
            fetched,
            f"blocks_fetched={fetched} within_band={within}",
        ),
    ]
    for name, reads in race.items():
        qa, wt = reads["query_aware"], reads["weighted"]
        rows.append(
            (
                f"sketch_query_aware_{name}",
                qa,
                f"query_aware={qa:.1f} weighted={wt:.1f} "
                f"saved={(1 - qa / max(wt, 1e-9)):.0%} ok={qa < wt}",
            )
        )
    return rows


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + hard pass/fail gate")
    args = ap.parse_args()

    rows = sketch_rows(smoke=args.smoke)
    print("name,value,derived")
    for name, value, derived in rows:
        print(f"{name},{value:.3f},{derived}")
    write_artifact("sketch", rows, extra={"smoke": args.smoke})

    if args.smoke:
        ok = True
        for name, _, derived in rows:
            if "ok=False" in derived:
                print(f"SMOKE FAIL: {name}: {derived}", file=sys.stderr)
                ok = False
            if name == "sketch_only_quantiles" and "blocks_fetched=0" not in derived:
                print(f"SMOKE FAIL: {name} read block data: {derived}", file=sys.stderr)
                ok = False
        if not ok:
            sys.exit(1)
        print(
            "SMOKE OK: KLL within analytic rank bound; p50/p95 answered"
            " sketch-only with 0 block reads; query_aware beat dispersion-PPS"
            " on p50 and p95"
        )


if __name__ == "__main__":
    main()
