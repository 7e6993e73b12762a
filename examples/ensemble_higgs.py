"""End-to-end driver: asymptotic ensemble learning on a HIGGS-like corpus
(the paper's Fig. 6/7 experiment) with an on-disk RSP store, through the
``repro.rsp`` facade.

The corpus is materialized as an RSP once (``ds.save``); analysis then
touches only the sampled blocks -- including after a simulated node failure,
where the lost host's blocks are re-dealt to the survivors (Theorem 1).

    PYTHONPATH=src python examples/ensemble_higgs.py [--records 100000]
"""

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import rsp
from repro.data import make_higgs_like
from repro.runtime import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=100_000)
    ap.add_argument("--blocks", type=int, default=50)
    ap.add_argument("--batch-blocks", type=int, default=5)
    ap.add_argument("--learner", choices=("logreg", "mlp"), default="logreg")
    args = ap.parse_args()

    N, K = args.records, args.blocks
    x, y = make_higgs_like(N + 10_000, seed=2, class_sep=1.5)
    xe, ye = jnp.asarray(x[N:]), jnp.asarray(y[N:])
    data = np.concatenate([x[:N], y[:N, None].astype(np.float32)], axis=1)

    # --- create + store the RSP (done once per corpus) ---------------------
    t0 = time.time()
    root = tempfile.mkdtemp(prefix="rsp_")
    rsp.partition(data, blocks=K, seed=1, num_classes=2).save(root)
    ds = rsp.open(root)   # lazy, store-backed from here on
    print(f"[partition+store] {N} records -> {K} blocks "
          f"(backend={ds.backend!r}) in {time.time() - t0:.2f}s at {root}")

    # --- deal blocks to 4 hosts, then lose one -----------------------------
    assign = ds.deal(num_hosts=4, seed=3).redistribute([2])
    print(f"[elastic] host 2 failed; survivors now hold "
          f"{[len(assign.blocks_for(h)) for h in (0, 1, 3)]} blocks")

    # --- Algorithm 2 over the stored RSP -----------------------------------
    F = x.shape[1]
    learner = (rsp.make_logreg(F, 2, steps=200, lr=0.5) if args.learner == "logreg"
               else rsp.make_mlp(F, 2, hidden=32, steps=300, lr=0.05))
    t0 = time.time()
    ensemble, hist = ds.ensemble(
        learner, eval_x=xe, eval_y=ye, g=args.batch_blocks, seed=9,
        improvement_tol=1e-3, patience=2,
    )
    ens_s = time.time() - t0
    for used, acc in zip(hist.blocks_used, hist.accuracy):
        print(f"  ensemble acc {acc:.4f} ({used}/{K} blocks)")
    print(f"[ensemble] trained in {ens_s:.1f}s, loading only sampled blocks")
    best = max(hist.accuracy)

    # --- the full-data single model for comparison (Fig. 6 dotted line) ----
    t0 = time.time()
    params = learner.fit(
        learner.init(jax.random.PRNGKey(1)),
        jnp.asarray(data[:, :-1]), jnp.asarray(data[:, -1].astype(np.int32)),
    )
    jax.block_until_ready(params)
    single = float((jnp.argmax(learner.predict_proba(params, xe), -1) == ye).mean())
    print(f"[single model, all data] acc {single:.4f} in {time.time() - t0:.1f}s")
    print(f"[result] ensemble {best:.4f} vs single {single:.4f} "
          f"using {ensemble.num_models}/{K} blocks")


if __name__ == "__main__":
    main()
