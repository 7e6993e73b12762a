"""Quickstart: the RSP data model in ~40 lines, through the ``repro.rsp``
facade.

Creates an RSP from a (deliberately class-sorted!) synthetic data set,
draws a block-level sample, estimates statistics from the partition-time
block sketches, and trains a small ensemble -- the paper's workflow end
to end via one object.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro import rsp
from repro.data import make_nonrandom_higgs_like
from repro.runtime import use_compile_cache


def main():
    use_compile_cache()
    # 1. a "big" data set, stored in the worst possible order (sorted by class)
    # delta = N / K^2 = 24 records per slice: a whole number of the TPU's
    # 8-row sublane tiles, so on a TPU host "auto" can pick the Pallas kernel
    N, K = 38_400, 40
    x, y = make_nonrandom_higgs_like(N + 8_000, seed=0, class_sep=1.5)
    xe, ye = x[N:], y[N:].astype(np.int32)
    data = np.concatenate([x[:N], y[:N, None].astype(np.float32)], axis=1)

    # 2. two-stage partitioning (Algorithm 1): every block becomes a random
    #    sample.  backend="auto" dispatches through the registry (shard_map
    #    with a mesh, the Pallas kernel on TPU, numpy streaming otherwise).
    ds = rsp.partition(data, blocks=K, seed=1, backend="auto", num_classes=2)
    print(f"RSP created: {ds.num_blocks} blocks x {ds.block_size} records "
          f"via backend={ds.backend!r}; worst label divergence "
          f"{ds.label_divergence():.4f} (sequential chunking: 0.50)")

    # 3. block-level sampling (Definition 4): no scan, no shuffle
    sample = ds.sample(5, seed=7)
    print(f"block-level sample: {sample}")

    # 4. estimate statistics from the sample alone (Sec. 8) -- the moments
    #    combine partition-time block sketches, touching no block data
    stats = ds.moments(ids=sample)
    err = float(np.abs(stats.mean[:-1] - data[:, :-1].mean(0)).max())
    print(f"mean estimated from 5/{K} blocks; max abs error {err:.5f}")

    # 5. asymptotic ensemble learning (Algorithm 2)
    learner = rsp.make_logreg(data.shape[1] - 1, 2, steps=200, lr=0.5)
    ens, hist = ds.ensemble(learner, eval_x=xe, eval_y=ye, g=5, seed=0)
    print("ensemble accuracy per batch:", [round(a, 4) for a in hist.accuracy])
    print(f"final: {hist.accuracy[-1]:.4f} using {ens.num_models}/{K} blocks")


if __name__ == "__main__":
    main()
