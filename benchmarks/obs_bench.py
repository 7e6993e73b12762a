"""Observability benchmark: telemetry overhead gate + trace/convergence smoke.

Telemetry that distorts what it measures is worse than none, so this bench
holds ``repro.obs`` to three promises:

1. **Overhead** -- the progressive-query hot path (store-backed p50 at a
   target relative error) is timed best-of-N with telemetry fully off and
   again with metrics + tracing enabled at ``sample_rate=1.0``.  The
   enabled/disabled ratio is the headline number; ``--smoke`` fails if the
   overhead exceeds 5%.

2. **Trace integrity** -- a concurrent serve workload (progressive
   quantile queries with deadlines over one shared executor) runs with
   tracing on; the Chrome trace exported to
   ``results/bench/TRACE_serve_smoke.json`` must be valid Perfetto input
   with spans from >= 3 distinct threads, every child span belonging to
   some query's trace -- cross-thread context propagation, witnessed.

3. **Convergence honesty** -- ``explain=True`` query traces must record
   strictly increasing block counts, and the last step's half-widths must
   equal the final result's CI half-widths exactly: the trace is the
   computation's own numbers, not a reconstruction.

Usage::

    PYTHONPATH=src python -m benchmarks.obs_bench            # full sizes
    PYTHONPATH=src python -m benchmarks.obs_bench --smoke    # CI gate
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

from benchmarks.artifact import default_out_dir, write_artifact
from repro import obs, rsp
from repro.runtime import use_compile_cache

OVERHEAD_LIMIT = 0.05  # --smoke gate: enabled/disabled - 1 must stay below


def _build(num_blocks: int, block_records: int, features: int):
    rng = np.random.default_rng(0)
    n = num_blocks * block_records
    data = rng.lognormal(0.0, 1.0, size=(n, features)).astype(np.float32)
    return rsp.partition(data, blocks=num_blocks, seed=1)


def _time_progressive(path: str, *, repeats: int, target: float) -> float:
    """Best-of-``repeats`` seconds for a store-backed progressive p50 query.
    A fresh uncached dataset per repeat keeps the I/O identical across the
    off/on passes -- only the telemetry differs."""
    best = math.inf
    for _ in range(repeats):
        ds = rsp.open(path, cache_blocks=0)
        t0 = time.perf_counter()
        ds.query("median", target_rel_err=target, use_sketches=False, seed=7)
        best = min(best, time.perf_counter() - t0)
        ds.close()
    return best


def bench_overhead(
    *, num_blocks: int, block_records: int, features: int, repeats: int
) -> tuple[float, float, float]:
    """(seconds_off, seconds_on, overhead_fraction) for the progressive path."""
    ds = _build(num_blocks, block_records, features)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "corpus.rsp")
        ds.save(path)
        ds.close()
        obs.disable()
        _time_progressive(path, repeats=1, target=0.02)  # warm compile caches
        t_off = _time_progressive(path, repeats=repeats, target=0.02)
        obs.enable(sample_rate=1.0)
        try:
            t_on = _time_progressive(path, repeats=repeats, target=0.02)
        finally:
            obs.disable()
    return t_off, t_on, t_on / max(t_off, 1e-12) - 1.0


def run_serve_smoke(
    *, num_blocks: int, block_records: int, features: int, queries: int,
    trace_path: str,
) -> dict:
    """Concurrent traced serve workload; exports the Chrome trace and returns
    the integrity report ``{"events", "threads", "query_spans", "orphans"}``."""
    obs.reset()
    obs.enable(sample_rate=1.0)
    try:
        ds = _build(num_blocks, block_records, features)
        with ds.serve(capacity=32, workers=4, seed=3) as svc:
            tickets = [
                svc.submit(
                    "median", target_rel_err=0.02, use_sketches=False,
                    deadline_ms=10_000,
                )
                for _ in range(queries)
            ]
            for t in tickets:
                svc.result(t)
        ds.close()
        os.makedirs(os.path.dirname(trace_path), exist_ok=True)
        obs.get_tracer().export_chrome(trace_path)
    finally:
        obs.disable()
    return validate_trace(trace_path)


def validate_trace(trace_path: str) -> dict:
    """Parse a Chrome trace and check span parenting across threads."""
    with open(trace_path) as f:
        payload = json.load(f)
    events = payload["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    roots = [e for e in spans if e["name"] == "query"]
    root_traces = {e["args"]["trace_id"] for e in roots}
    children = [e for e in spans if "parent_id" in e["args"]]
    orphans = [e for e in children if e["args"]["trace_id"] not in root_traces]
    return {
        "events": len(spans),
        "threads": len({e["tid"] for e in spans}),
        "query_spans": len(roots),
        "children": len(children),
        "orphans": len(orphans),
        "names": sorted({e["name"] for e in spans}),
    }


def run_convergence_check(
    *, num_blocks: int, block_records: int, features: int
) -> dict:
    """``explain=True`` trace vs the final result it narrates."""
    ds = _build(num_blocks, block_records, features)
    res = ds.query("median", target_rel_err=0.03, use_sketches=False, seed=5, explain=True)
    ds.close()
    trace = res.trace
    assert trace is not None and len(trace) > 0, "explain=True produced no trace"
    blocks = trace.blocks
    monotone = all(b1 < b2 for b1, b2 in zip(blocks, blocks[1:]))
    last = trace.steps[-1]
    final_hw = {}
    for r in res.aggregates:
        if r.ci_lo is None or r.ci_hi is None:
            continue
        half = (np.asarray(r.ci_hi, dtype=float) - np.asarray(r.ci_lo, dtype=float)) / 2.0
        # mirror the trace's reduction: worst (max) half-width across features
        final_hw[r.name] = (
            float(np.nanmax(half)) if np.any(~np.isnan(half)) else math.nan
        )
    consistent = last.blocks_read == res.blocks_read and all(
        math.isclose(last.half_widths[k], v, rel_tol=1e-9, abs_tol=1e-12)
        or (math.isnan(last.half_widths[k]) and math.isnan(v))
        for k, v in final_hw.items()
    )
    return {
        "steps": len(trace),
        "blocks_read": res.blocks_read,
        "monotone": monotone,
        "consistent_with_final_ci": consistent,
        "final_rel_err": last.max_rel_err,
    }


def obs_rows(smoke: bool = False) -> list[tuple]:
    """``benchmarks.run``-style rows ``(name, value, derived, metrics)``."""
    if smoke:
        kw = dict(num_blocks=48, block_records=2304, features=8)
        repeats, queries = 5, 8
    else:
        kw = dict(num_blocks=96, block_records=9216, features=8)
        repeats, queries = 7, 16
    rows: list[tuple] = []

    t_off, t_on, overhead = bench_overhead(repeats=repeats, **kw)
    rows.append((
        "obs_overhead_progressive_p50",
        overhead * 100,
        f"off_ms={t_off * 1e3:.1f} on_ms={t_on * 1e3:.1f}"
        f" overhead={overhead:+.1%} limit={OVERHEAD_LIMIT:.0%}",
        {"seconds_off": t_off, "seconds_on": t_on, "overhead": overhead},
    ))

    trace_path = os.path.join(default_out_dir(), "TRACE_serve_smoke.json")
    report = run_serve_smoke(queries=queries, trace_path=trace_path, **kw)
    rows.append((
        "obs_serve_trace",
        report["events"],
        f"spans={report['events']} threads={report['threads']}"
        f" queries={report['query_spans']} orphans={report['orphans']}",
        report,
    ))

    conv = run_convergence_check(**kw)
    rows.append((
        "obs_convergence_trace",
        conv["steps"],
        f"steps={conv['steps']} blocks={conv['blocks_read']}"
        f" monotone={conv['monotone']}"
        f" ci_consistent={conv['consistent_with_final_ci']}",
        conv,
    ))
    return rows


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="small sizes + hard pass/fail gate")
    args = ap.parse_args()

    rows = obs_rows(smoke=args.smoke)
    print("name,value,derived")
    for row in rows:
        print(f"{row[0]},{row[1]:.1f},{row[2]}")
    write_artifact("obs", rows, extra={"smoke": args.smoke})

    if args.smoke:
        by_name = {row[0]: row[3] for row in rows}
        ok = True
        overhead = by_name["obs_overhead_progressive_p50"]["overhead"]
        if overhead > OVERHEAD_LIMIT:
            print(
                f"SMOKE FAIL: telemetry overhead {overhead:.1%} exceeds"
                f" {OVERHEAD_LIMIT:.0%} on the progressive query path",
                file=sys.stderr,
            )
            ok = False
        tr = by_name["obs_serve_trace"]
        if tr["threads"] < 3:
            print(
                f"SMOKE FAIL: serve trace has spans from only {tr['threads']}"
                " threads (< 3)", file=sys.stderr,
            )
            ok = False
        if tr["query_spans"] == 0 or tr["children"] == 0 or tr["orphans"]:
            print(
                f"SMOKE FAIL: trace parenting broken (queries={tr['query_spans']}"
                f" children={tr['children']} orphans={tr['orphans']})",
                file=sys.stderr,
            )
            ok = False
        conv = by_name["obs_convergence_trace"]
        if not (conv["monotone"] and conv["consistent_with_final_ci"]):
            print(
                f"SMOKE FAIL: convergence trace dishonest (monotone="
                f"{conv['monotone']} ci_consistent={conv['consistent_with_final_ci']})",
                file=sys.stderr,
            )
            ok = False
        if not ok:
            sys.exit(1)
        print(
            f"SMOKE OK: overhead {overhead:+.1%} <= {OVERHEAD_LIMIT:.0%};"
            f" trace spans {tr['events']} across {tr['threads']} threads,"
            f" 0 orphans; convergence trace monotone and CI-consistent"
        )


if __name__ == "__main__":
    main()
