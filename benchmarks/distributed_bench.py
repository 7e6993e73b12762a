"""Distributed-query benchmark: block-parallel fan-out and straggler
tolerance over an emulated multi-host mesh.

An in-process ``LocalTransport`` mesh (one thread per host, one shared KV
plane -- the same protocol code a real ``jax.distributed`` mesh runs)
answers a progressive weighted query over a store whose fetches carry an
emulated per-block I/O latency.  Reported rows:

* **distributed_fanout** -- wall-clock speedup of a 4-host mesh over the
  1-host run of the identical query: each host streams only its owned
  blocks, so block I/O overlaps across the mesh while every host still
  folds the full payload sequence.
* **distributed_straggler** -- a host is fault-injected dead mid-query;
  survivors steal its leases after the grace deadline.  The row records
  whether the surviving hosts' answer is *bit-identical* to the single-host
  reference (Theorem 1: re-assigning exchangeable blocks is statistically
  free, so a death may cost time but never accuracy).

``results/bench/BENCH_distributed.json`` is written on every run.

Usage::

    PYTHONPATH=src python -m benchmarks.distributed_bench            # full sizes
    PYTHONPATH=src python -m benchmarks.distributed_bench --smoke    # CI gate

``--smoke`` exits non-zero unless the 4-host fan-out beats the 1-host
wall-clock by >= 1.5x on the emulated-latency store and the killed
straggler changes no estimate bit (estimates, CI endpoints, stopping
point all exactly equal).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from benchmarks.artifact import write_artifact
from repro.distributed import LocalTransport, run_local_hosts
from repro.rsp.dataset import RSPDataset
from repro.runtime import use_compile_cache

SPEEDUP_GATE = 1.5


class _SlowFetcher:
    """Fetcher wrapper emulating per-block store latency (remote object
    store / cold disk): every fetch sleeps before delegating."""

    def __init__(self, inner, delay_s: float):
        self._inner = inner
        self._delay_s = delay_s

    @property
    def num_blocks(self) -> int:
        return self._inner.num_blocks

    def fetch(self, block_id: int) -> np.ndarray:
        time.sleep(self._delay_s)
        return self._inner.fetch(block_id)


def _make_ds(n: int, blocks: int, *, delay_s: float) -> RSPDataset:
    rng = np.random.default_rng(7)
    data = rng.normal(size=(n, 4)).astype(np.float32)
    data[:, 2] = rng.gamma(2.0, 1.0, size=n).astype(np.float32)
    ds = RSPDataset.partition(data, blocks, seed=3)
    inner_factory = ds._make_fetcher
    ds._make_fetcher = lambda: _SlowFetcher(inner_factory(), delay_s)  # type: ignore[method-assign]
    return ds


def _sig(r) -> str:
    return json.dumps(
        {
            "est": {a.name: np.asarray(a.estimate).ravel().tolist() for a in r.aggregates},
            "lo": {
                a.name: None if a.ci_lo is None else np.asarray(a.ci_lo).ravel().tolist()
                for a in r.aggregates
            },
            "hi": {
                a.name: None if a.ci_hi is None else np.asarray(a.ci_hi).ravel().tolist()
                for a in r.aggregates
            },
            "blocks_read": r.blocks_read,
            "converged": r.converged,
        },
        sort_keys=True,
    )


def _mesh_query(ds, num_hosts: int, query: dict, *, kill: tuple[int, int] | None = None):
    """Run the query on an emulated ``num_hosts`` mesh; returns (signatures
    of surviving hosts' results, wall seconds)."""
    transports = LocalTransport.group(num_hosts)
    if kill is not None:
        transports[kill[0]].kill_after_puts(kill[1])

    def run(t):
        dds = ds.distribute(t, straggler_grace=1.0, poll_interval=0.005)
        return _sig(dds.query(**query))

    t0 = time.perf_counter()
    results = run_local_hosts(transports, run)
    wall = time.perf_counter() - t0
    return [r for r in results if r is not None], wall


def distributed_bench(smoke: bool = False):
    """Returns (rows, gates)."""
    # delay emulates a remote object store / cold disk; it must dominate the
    # (GIL-serialized) fold CPU for fan-out to show -- that is the regime
    # block-parallel distribution targets
    if smoke:
        n, blocks, delay_s = 32768, 32, 0.15
    else:
        n, blocks, delay_s = 131072, 64, 0.15
    query = dict(
        aggregates=["mean", "p95"], target_rel_err=1e-6, seed=11,
        policy="weighted", where="c2 > 0.5", max_blocks=blocks,
    )

    ds = _make_ds(n, blocks, delay_s=delay_s)
    ref = _sig(ds.query(**query))

    solo_sigs, solo_wall = _mesh_query(ds, 1, query)
    fan_sigs, fan_wall = _mesh_query(ds, 4, query)
    speedup = solo_wall / max(fan_wall, 1e-9)
    fanout_identical = all(s == ref for s in solo_sigs + fan_sigs)

    # straggler: host 3 dies after publishing 2 payloads; survivors steal
    surv_sigs, surv_wall = _mesh_query(ds, 4, query, kill=(3, 2))
    straggler_identical = len(surv_sigs) == 3 and all(s == ref for s in surv_sigs)

    rows = [
        (
            "distributed_fanout",
            speedup,
            f"hosts=4 blocks={blocks} delay_ms={delay_s * 1e3:.0f}"
            f" solo_s={solo_wall:.2f} mesh_s={fan_wall:.2f}"
            f" bit_identical={fanout_identical}",
            {"solo_wall_s": solo_wall, "mesh_wall_s": fan_wall},
        ),
        (
            "distributed_straggler",
            float(straggler_identical),
            f"killed_host=3 survivors={len(surv_sigs)}"
            f" wall_s={surv_wall:.2f} bit_identical={straggler_identical}",
            {"survivor_wall_s": surv_wall},
        ),
    ]
    gates = {
        "speedup": speedup,
        "speedup_gate": SPEEDUP_GATE,
        "fanout_bit_identical": bool(fanout_identical),
        "straggler_survivors": len(surv_sigs),
        "straggler_bit_identical": bool(straggler_identical),
    }
    return rows, gates


def distributed_rows(smoke: bool = False) -> list[tuple]:
    """``benchmarks.run``-style rows ``(name, value, derived[, metrics])``."""
    return distributed_bench(smoke=smoke)[0]


def _verdict(gates: dict) -> list[str]:
    failures = []
    if not gates["speedup"] >= gates["speedup_gate"]:
        failures.append(
            f"4-host fan-out speedup {gates['speedup']:.2f}x below"
            f" {gates['speedup_gate']:.1f}x gate"
        )
    if not gates["fanout_bit_identical"]:
        failures.append("mesh answer differs from the single-host reference")
    if gates["straggler_survivors"] != 3:
        failures.append(
            f"{gates['straggler_survivors']} survivors after one injected death (want 3)"
        )
    if not gates["straggler_bit_identical"]:
        failures.append("killed straggler changed an estimate bit")
    return failures


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true", help="CI sizes + hard pass/fail gate"
    )
    args = ap.parse_args()

    rows, gates = distributed_bench(smoke=args.smoke)
    print("name,value,derived")
    for row in rows:
        print(f"{row[0]},{row[1]:.2f},{row[2]}")
    path = write_artifact(
        "distributed", rows, extra={"gates": gates, "smoke": args.smoke}
    )
    print(f"wrote {path}")

    if args.smoke:
        failures = _verdict(gates)
        for msg in failures:
            print(f"SMOKE FAIL: {msg}", file=sys.stderr)
        if failures:
            sys.exit(1)
        print(
            f"SMOKE OK: 4-host fan-out {gates['speedup']:.2f}x >="
            f" {gates['speedup_gate']:.1f}x; killed straggler changed no"
            f" estimate bit ({gates['straggler_survivors']} survivors)"
        )


if __name__ == "__main__":
    main()
