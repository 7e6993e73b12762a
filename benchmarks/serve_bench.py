"""Concurrent query-serving benchmark: thousands of progressive queries over
one shared executor, with admission control and anytime deadlines.

Four tenant classes are interleaved against one store-backed RSP dataset
through ``RSPDataset.serve()``:

* **sketch** -- moment/count queries answered from the partition-time
  sketches (the zero-I/O fast path; never queued, never scheduled);
* **converged** -- median queries with an achievable target relative error
  (the progressive bread-and-butter: read a few blocks, stop early);
* **truncated** -- mean queries capped at ``max_blocks=4``: they exhaust
  their block budget without converging, so their answer is an *anytime*
  estimate whose CI must cover the full-scan answer;
* **deadline** -- mean queries chasing an unreachable target under a tight
  ``deadline_ms`` (PPS-with-replacement selection, so they can neither
  converge nor exhaust): the deadline is the only way out, and the service
  must return their current anytime estimate at it.

Reported rows: service QPS + latency percentiles, shared-cache hit rate vs
an isolated-executor baseline (same query mix, one fresh executor per
query), sketch fast-path latency, anytime CI coverage, and admission
behavior.  ``results/bench/BENCH_serve.json`` is written on every run.

Usage::

    PYTHONPATH=src python -m benchmarks.serve_bench            # full sizes
    PYTHONPATH=src python -m benchmarks.serve_bench --smoke    # CI gate

``--smoke`` runs >= 1000 concurrent progressive queries and exits non-zero
unless: sketch-only queries fetch exactly 0 blocks; the shared cache's hit
rate is strictly above the isolated baseline; every class's p99 latency is
within its deadline budget (+ a fixed enforcement slack); every anytime
(truncated / deadline) result's CI covers the full-scan answer; and at
least one deadline result carries a real partial estimate (>= 1 block).
Anytime classes use mean aggregates at 99.9999% confidence on purpose:
across hundreds of queries x 8 features of jointly gated intervals, only a
far-tail confidence makes "every CI covers" a correctness property rather
than a coin flip (nominal 95% intervals *should* miss ~5% of the time).
"""

from __future__ import annotations

import argparse
import math
import os
import sys
import tempfile
import time

import numpy as np

from benchmarks.artifact import write_artifact
from repro import rsp
from repro.rsp.query import derive_seed
from repro.runtime import use_compile_cache
from repro.serve import AdmissionRejected

# latency slack added on top of a query's deadline budget before the p99
# gate trips: deadline enforcement is exact by construction (worker pre-step
# checks + result() waiters), the slack only absorbs host scheduling jitter
SLACK_MS = 250.0


def _build(tmp: str, *, num_blocks: int, block_records: int, features: int):
    rng = np.random.default_rng(0)
    n = num_blocks * block_records
    data = rng.normal(5.0, 1.0, size=(n, features)).astype(np.float32)
    ds = rsp.partition(data, blocks=num_blocks, seed=1)
    path = os.path.join(tmp, "corpus.rsp")
    ds.save(path)
    ds.close()
    return path, data


def _plan(counts: dict[str, int]) -> list[str]:
    """Round-robin interleave of the tenant classes (multi-tenant mix, not
    class-by-class waves -- admission sees all classes competing at once)."""
    pools = {c: n for c, n in counts.items()}
    out: list[str] = []
    while any(pools.values()):
        for c in counts:
            if pools[c] > 0:
                pools[c] -= 1
                out.append(c)
    return out


def _submit(svc, cls: str, *, tight_ms: float, wide_ms: float):
    if cls == "sketch":
        return svc.submit(["mean", "var", "count"], deadline_ms=wide_ms)
    if cls == "converged":
        return svc.submit(
            "median", target_rel_err=0.05, use_sketches=False, deadline_ms=wide_ms
        )
    if cls == "truncated":
        return svc.submit(
            "mean", use_sketches=False, max_blocks=4, confidence=0.999999,
            deadline_ms=wide_ms,
        )
    if cls == "deadline":
        return svc.submit(
            "mean", use_sketches=False, target_rel_err=1e-12, policy="weighted",
            max_blocks=10**7, confidence=0.999999, deadline_ms=tight_ms,
        )
    raise ValueError(cls)


def _covers(agg, truth: np.ndarray) -> bool:
    lo = -math.inf if agg.ci_lo is None else np.asarray(agg.ci_lo, np.float64)
    hi = math.inf if agg.ci_hi is None else np.asarray(agg.ci_hi, np.float64)
    return bool(np.all(lo <= truth) and np.all(truth <= hi))


def _p99(latencies_ms: list[float]) -> float:
    if not latencies_ms:
        return math.nan
    s = sorted(latencies_ms)
    return s[min(len(s) - 1, max(0, math.ceil(0.99 * len(s)) - 1))]


def _isolated_hit_rate(path: str, *, cache_blocks: int, n: int) -> float:
    """The no-sharing baseline: the same progressive query mix, each query on
    its own freshly opened dataset (private executor + private cache).  Only
    a query's *own* re-picks (the with-replacement class) can hit."""
    total = rsp.ExecutorStats()
    for i in range(n):
        ds = rsp.open(path, cache_blocks=cache_blocks)
        seed = derive_seed(7, i)
        if i % 3 == 0:
            ds.query(
                "mean", use_sketches=False, target_rel_err=1e-12,
                policy="weighted", max_blocks=12, seed=seed,
            )
        elif i % 3 == 1:
            ds.query("median", target_rel_err=0.05, use_sketches=False, seed=seed)
        else:
            ds.query("mean", use_sketches=False, max_blocks=4, seed=seed)
        total = total + ds.executor.stats()
        ds.close()
    return total.hit_rate


def _bench_reject(path: str) -> int:
    """Deterministic saturation scenario: capacity 1, no queue -> the second
    concurrent progressive query must be rejected, not silently queued."""
    ds = rsp.open(path, cache_blocks=4)
    rejected = 0
    with ds.serve(capacity=1, max_queue=0, workers=1, seed=9) as svc:
        hog = svc.submit(
            "mean", use_sketches=False, target_rel_err=1e-12,
            policy="weighted", max_blocks=10**7,
        )
        try:
            svc.submit("median", use_sketches=False)
        except AdmissionRejected:
            rejected += 1
        svc.cancel(hog)
    ds.close()
    return rejected


def serve_bench(smoke: bool = False):
    """Run the serving workload; returns (rows, gates) where ``gates`` holds
    everything the smoke verdict needs."""
    if smoke:
        shape = dict(num_blocks=64, block_records=1024, features=8)
        counts = {"sketch": 256, "converged": 640, "truncated": 200, "deadline": 200}
        capacity, workers, cache_blocks = 64, 8, 64
        iso_n = 30
    else:
        shape = dict(num_blocks=128, block_records=2048, features=8)
        counts = {"sketch": 500, "converged": 1200, "truncated": 300, "deadline": 300}
        capacity, workers, cache_blocks = 128, 8, 128
        iso_n = 45
    tight_ms, wide_ms = 1200.0, 10_000.0

    with tempfile.TemporaryDirectory() as tmp:
        path, data = _build(tmp, **shape)
        truth = data.astype(np.float64).mean(axis=0)

        ds = rsp.open(path, cache_blocks=cache_blocks)
        t0 = time.perf_counter()
        with ds.serve(capacity=capacity, workers=workers, seed=7) as svc:
            tickets = [
                (cls, _submit(svc, cls, tight_ms=tight_ms, wide_ms=wide_ms))
                for cls in _plan(counts)
            ]
            results = [(cls, t, svc.result(t, timeout=120)) for cls, t in tickets]
            metrics = svc.metrics()
        wall_s = time.perf_counter() - t0
        ds.close()

        shared_rate = metrics.cache_hit_rate
        isolated_rate = _isolated_hit_rate(path, cache_blocks=cache_blocks, n=iso_n)
        rejected_when_full = _bench_reject(path)

    by_cls: dict[str, list] = {c: [] for c in counts}
    for cls, t, res in results:
        by_cls[cls].append((t, res))

    sketch_lat = [t.latency_ms for t, _ in by_cls["sketch"]]
    sketch_io = max(r.executor_stats.blocks_fetched for _, r in by_cls["sketch"])
    anytime = [(t, r) for c in ("truncated", "deadline") for t, r in by_cls[c]]
    covered = sum(_covers(r["mean"], truth) for _, r in anytime)
    deadline_blocks = [r.blocks_read for _, r in by_cls["deadline"]]
    p99_by_cls = {c: _p99([t.latency_ms for t, _ in by_cls[c]]) for c in counts}
    budget = {c: (tight_ms if c == "deadline" else wide_ms) for c in counts}
    progressive = sum(n for c, n in counts.items() if c != "sketch")
    conv_frac = sum(
        t.outcome in ("converged", "exhausted") for t, _ in by_cls["converged"]
    ) / counts["converged"]

    fetched_rows_per_s = metrics.executor.rows_fetched / max(wall_s, 1e-9)
    scanned_rows_per_s = (
        metrics.executor.accesses * shape["block_records"] / max(wall_s, 1e-9)
    )
    rows = [
        (
            "serve_throughput",
            metrics.qps,
            f"queries={metrics.submitted} progressive={progressive}"
            f" wall_s={wall_s:.2f} p50_ms={metrics.latency_p50_ms:.1f}"
            f" p99_ms={metrics.latency_p99_ms:.1f}"
            f" rows_per_s={scanned_rows_per_s:,.0f}",
            # scanned = every block pass (cache hits included); fetched =
            # rows that actually crossed the fetcher (cache misses)
            {"rows_per_s": scanned_rows_per_s,
             "fetched_rows_per_s": fetched_rows_per_s},
        ),
        (
            "serve_cache_sharing",
            shared_rate,
            f"shared={shared_rate:.3f} isolated={isolated_rate:.3f}"
            f" hits={metrics.executor.hits} misses={metrics.executor.misses}",
            {"rows_fetched": metrics.executor.rows_fetched},
        ),
        (
            "serve_sketch_fast_path",
            float(np.mean(sketch_lat) * 1e3),
            f"us_per_query={np.mean(sketch_lat) * 1e3:.0f}"
            f" blocks_fetched={sketch_io} n={counts['sketch']}",
        ),
        (
            "serve_anytime",
            len(anytime),
            f"ci_covered={covered}/{len(anytime)}"
            f" deadline_hits={metrics.deadline_hits}"
            f" deadline_p99_ms={p99_by_cls['deadline']:.0f}"
            f" max_partial_blocks={max(deadline_blocks)}",
        ),
        (
            "serve_admission",
            float(metrics.admission.admitted_total),
            f"admitted={metrics.admission.admitted_total}"
            f" rejected_when_full={rejected_when_full}"
            f" converged_frac={conv_frac:.2f}"
            f" blocks_per_query={metrics.blocks_per_query:.2f}",
        ),
    ]
    gates = {
        "progressive_queries": progressive,
        "sketch_blocks_fetched": int(sketch_io),
        "shared_hit_rate": shared_rate,
        "isolated_hit_rate": isolated_rate,
        "anytime_total": len(anytime),
        "anytime_ci_covered": int(covered),
        "deadline_max_partial_blocks": int(max(deadline_blocks)),
        "rejected_when_full": rejected_when_full,
        "p99_ms_by_class": p99_by_cls,
        "deadline_budget_ms_by_class": budget,
        "slack_ms": SLACK_MS,
    }
    return rows, gates


def serve_rows(smoke: bool = False) -> list[tuple]:
    """``benchmarks.run``-style rows ``(name, value, derived[, metrics])``."""
    return serve_bench(smoke=smoke)[0]


def _verdict(gates: dict) -> list[str]:
    failures = []
    if gates["progressive_queries"] < 1000:
        failures.append(
            f"only {gates['progressive_queries']} concurrent progressive queries (< 1000)"
        )
    if gates["sketch_blocks_fetched"] != 0:
        failures.append(
            f"sketch-only queries fetched {gates['sketch_blocks_fetched']} blocks"
        )
    if not gates["shared_hit_rate"] > gates["isolated_hit_rate"]:
        failures.append(
            f"shared cache hit rate {gates['shared_hit_rate']:.3f} not above"
            f" isolated baseline {gates['isolated_hit_rate']:.3f}"
        )
    if gates["anytime_ci_covered"] != gates["anytime_total"]:
        failures.append(
            f"only {gates['anytime_ci_covered']}/{gates['anytime_total']}"
            f" anytime CIs cover the full-scan answer"
        )
    if gates["deadline_max_partial_blocks"] < 1:
        failures.append("no deadline query returned a partial (>= 1 block) estimate")
    if gates["rejected_when_full"] != 1:
        failures.append("saturated capacity-1/queue-0 service did not reject")
    for cls, p99 in gates["p99_ms_by_class"].items():
        cap = gates["deadline_budget_ms_by_class"][cls] + gates["slack_ms"]
        if not p99 <= cap:
            failures.append(f"{cls} p99 {p99:.0f}ms exceeds budget {cap:.0f}ms")
    return failures


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true", help="CI sizes + hard pass/fail gate"
    )
    args = ap.parse_args()

    rows, gates = serve_bench(smoke=args.smoke)
    print("name,value,derived")
    for row in rows:
        print(f"{row[0]},{row[1]:.1f},{row[2]}")
    path = write_artifact("serve", rows, extra={"gates": gates, "smoke": args.smoke})
    print(f"wrote {path}")

    if args.smoke:
        failures = _verdict(gates)
        for msg in failures:
            print(f"SMOKE FAIL: {msg}", file=sys.stderr)
        if failures:
            sys.exit(1)
        print(
            f"SMOKE OK: {gates['progressive_queries']} progressive queries;"
            f" shared hit rate {gates['shared_hit_rate']:.3f} >"
            f" isolated {gates['isolated_hit_rate']:.3f}; sketch I/O 0;"
            f" {gates['anytime_ci_covered']}/{gates['anytime_total']}"
            f" anytime CIs cover; per-class p99 within budget"
        )


if __name__ == "__main__":
    main()
