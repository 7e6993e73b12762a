"""Back-to-back ingest of one in-memory corpus into fresh stores.

Set-up makes the class-sorted corpus from the seed and ingests it once into
a store it then deletes, so every program the ingest runs is compiled before
the window.  In the window, iteration ``i`` runs ``rsp.partition(corpus,
blocks=K, num_classes=C, seed=seed + 1 + i, out=<store i>)`` -- a fresh
partition seed and path each time -- until the window has closed; the
iteration under way then finishes.  ``partition_rows_per_s`` is the records
ingested by the iterations completed over their elapsed time.

After the window ``check_stores`` of the stores written (the last one and
others drawn from the seed) are compared with ``yardstick.store_check``;
then every store is deleted.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from yardstick.corpus import make_corpus
from yardstick.store_check import CorpusFacts, check_store, control_store, gaps


def run(run) -> dict:
    from repro import rsp

    cfg, tr = run.cfg, run.traffic
    K, C = cfg["blocks"], cfg["num_classes"]
    corpus = make_corpus(cfg, run.seed)
    base = os.path.join(run.work_dir, "stores")
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)

    def ingest(i: int, path: str) -> str:
        ds = rsp.partition(corpus, blocks=K, num_classes=C, seed=run.seed + 1 + i, out=path)
        backend = ds.backend
        ds.close()
        return backend

    backend = ingest(-1, os.path.join(base, "warm"))
    shutil.rmtree(os.path.join(base, "warm"))
    run.log(f"partition backend {backend}")
    run.setup_done()

    stores: list[tuple[str, float, float]] = []
    begin = run.window_begin()
    while time.perf_counter() < begin + run.seconds:
        path = os.path.join(base, f"store_{len(stores)}")
        t0 = time.perf_counter()
        ingest(len(stores), path)
        stores.append((path, t0, time.perf_counter()))
    run.window_end()
    elapsed = sum(t1 - t0 for _, t0, t1 in stores)
    rows_per_s = corpus.shape[0] * len(stores) / elapsed
    run.log(f"iterations {len(stores)}, elapsed {elapsed:.3f} s, rows/s {rows_per_s:.6g}")

    facts = CorpusFacts(corpus)
    rng = np.random.default_rng([run.seed, 0x1D])
    picks = [len(stores) - 1] + list(rng.permutation(len(stores) - 1))
    picks = sorted(picks[: tr["check_stores"]])
    checks: dict[str, float] = {}
    for i in picks:
        if run.control is None:
            g = check_store(stores[i][0], K, facts, C)
        else:
            blocks, sketches = control_store(corpus, K, run.seed + 1 + i, run.control, C)
            g = gaps(blocks, sketches, facts, C)
        run.log(f"store {i}: " + ", ".join(f"{k} {v:.6g}" for k, v in g.items()))
        for k, v in g.items():
            checks[k] = max(checks.get(k, 0.0), v)
    shutil.rmtree(base, ignore_errors=True)
    R = corpus.shape[0] // cfg["original_blocks"]
    return {
        "end_to_end": {"partition_rows_per_s": rows_per_s},
        "attempted": len(stores), "failed": 0, "checks": checks,
        "facts": {"shuffle_rows": R, "columns": corpus.shape[1]},
    }
