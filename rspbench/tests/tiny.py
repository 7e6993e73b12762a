"""Cells of the benchmark cut to a size the CPU runs in seconds, for tests."""

from __future__ import annotations

import dataclasses
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("REPRO_AUTOTUNE", "off")  # deterministic impls, no timing

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

# 16 blocks of 16 slices of 256 records (Algorithm 1's N % (P K) == 0
# holds): a progressive answer reads 8 of the 16 blocks, as many as at the
# cells' own size, over rows enough that a quantile's rank resolves to 1e-4
TINY = {"num_records": 16 * 16 * 256, "blocks": 16, "original_blocks": 16}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cfg = dict(cell.config, **TINY)
    traffic = dict(cell.traffic)
    if "clients" in traffic:
        traffic["clients"] = 2
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def run_tiny(name: str, tmp_path, *args: str, seconds: float = 1.0) -> dict:
    """One CPU run of a tiny cell; returns the result line as a dict."""
    import io
    import time
    import json
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = harness.main(["--workload", name, "--seed", "3", "--seconds", str(seconds),
                           *args], t0=time.perf_counter(), require_tpu=False, cell=tiny_cell(name),
                          work_dir=str(tmp_path), peaks_of="TPU v5 lite")
    assert rc == 0, buf.getvalue()
    return json.loads(buf.getvalue().strip().splitlines()[-1])
