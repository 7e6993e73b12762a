"""Benchmark harness: one function per paper table/figure plus kernel and
dry-run/roofline tables.  Prints ``name,us_per_call,derived`` CSV and writes
one machine-readable ``results/bench/BENCH_<suite>.json`` artifact per suite
executed (see :mod:`benchmarks.artifact`).

    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run fig6 kernels
"""

from __future__ import annotations

import json
import os
import sys
import traceback

from benchmarks.artifact import write_artifact
from repro.runtime import use_compile_cache


def _roofline_rows() -> list[tuple[str, float, str]]:
    """Summarize results/dryrun/*.json (if the dry-run sweep has run)."""
    rows: list[tuple[str, float, str]] = []
    root = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    if not os.path.isdir(root):
        return [("roofline_table", 0.0, "results/dryrun missing -- run repro.launch.dryrun --all")]
    from repro.configs import ARCHS, SHAPES
    from repro.launch.roofline import summarize_cell

    for name in sorted(os.listdir(root)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(root, name)) as f:
            r = json.load(f)
        if "skipped" in r or "analysis" not in r:
            continue
        if r.get("arch") not in ARCHS:
            continue
        cfg, cell = ARCHS[r["arch"]], SHAPES[r["shape"]]
        t = summarize_cell(r, cfg, cell)
        rows.append((
            f"roofline_{name[:-5]}",
            t["step_time_s"] * 1e6,
            f"dom={t['dominant']} frac={t['roofline_fraction']:.3f} "
            f"useful={t['useful_ratio']:.2f}",
        ))
    return rows


SUITES = {}


def _register_suites():
    from benchmarks.paper_figs import ALL_FIGS
    from benchmarks.kernel_bench import ALL_KERNELS
    from benchmarks.distributed_bench import distributed_rows
    from benchmarks.engine_bench import engine_rows
    from benchmarks.ingest_bench import ingest_rows
    from benchmarks.obs_bench import obs_rows
    from benchmarks.query_bench import query_rows
    from benchmarks.serve_bench import serve_rows
    from benchmarks.sketch_bench import sketch_rows

    SUITES.update({
        "distributed": [distributed_rows],
        "engine": [engine_rows],
        "ingest": [ingest_rows],
        "obs": [obs_rows],
        "query": [query_rows],
        "serve": [serve_rows],
        "sketch": [sketch_rows],
        "fig1": [ALL_FIGS[0]],
        "fig2": [ALL_FIGS[1]],
        "fig34": [ALL_FIGS[2]],
        "fig6": [ALL_FIGS[3]],
        "fig7": [ALL_FIGS[4]],
        "paper": ALL_FIGS,
        "kernels": ALL_KERNELS,
        "roofline": [_roofline_rows],
    })


def main() -> None:
    use_compile_cache()
    _register_suites()
    which = sys.argv[1:] or ["paper", "kernels", "roofline"]
    for w in which:
        if w not in SUITES:
            print(f"unknown suite {w}; choices: {sorted(SUITES)}", file=sys.stderr)
            sys.exit(2)
    print("name,us_per_call,derived")
    failed = False
    for suite in which:
        rows: list[tuple] = []
        errors = 0
        for fn in SUITES[suite]:
            try:
                for row in fn():
                    name, us, derived = row[0], row[1], row[2]
                    print(f"{name},{us:.1f},{derived}")
                    rows.append(row)
            except Exception:
                failed = True
                errors += 1
                print(f"{fn.__name__},NaN,ERROR", flush=True)
                traceback.print_exc()
        write_artifact(suite, rows, extra={"errors": errors})
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
