"""Block-level statistics estimation (paper Sec. 8, Figs. 3/4) through the
``repro.rsp`` facade: watch the estimates converge to the full-data truth as
blocks are added, with the plateau detector stopping the scan early.

    PYTHONPATH=src python examples/estimate_stats.py
"""

import numpy as np

from repro import rsp
from repro.core import block_histogram, quantile_from_histogram
from repro.data import make_higgs_like
from repro.runtime import use_compile_cache


def main():
    use_compile_cache()
    N, K = 200_000, 100
    x, y = make_higgs_like(N, seed=4)
    data = np.concatenate([x, y[:, None].astype(np.float32)], axis=1)
    ds = rsp.partition(data, blocks=K, seed=7, num_classes=2)
    truth_mean = data.mean(0)
    truth_std = data.std(0, ddof=1)

    # streaming fold over a block-level sample, with convergence history
    est = rsp.BlockLevelEstimator()
    order = ds.sample(K, seed=0)
    print("blocks  max|mean err|  max|std err|  converged?")
    for g, k in enumerate(order, start=1):
        est.update(ds[k])
        conv = est.converged(rel_tol=1e-3)
        if g in (1, 2, 5, 10, 20) or conv:
            em = np.abs(est.stats.mean - truth_mean).max()
            es = np.abs(est.stats.std - truth_std).max()
            print(f"{g:6d}  {em:13.6f}  {es:12.6f}  {conv}")
        if conv:
            print(f"-> plateau after {g}/{K} blocks ({100 * g / K:.0f}% of the data)")
            break

    # the same estimate from the partition-time sketches: no block reads at all
    sk = ds.moments(g=20, seed=0)
    print(f"sketch-only moments from 20 blocks: "
          f"max|mean err| {np.abs(sk.mean - truth_mean).max():.6f}")

    # distribution-level checks on one block (Sec. 7 toolkit)
    mmd = ds.similarity(3, metric="mmd", seed=0)
    ks = ds.similarity(3, metric="ks", feature=0)
    print(f"block 3 vs data: MMD^2={mmd:.2e}, KS={ks:.4f}")

    # quantiles via combinable histograms
    h = sum(block_histogram(ds[k], bins=256, lo=-8, hi=8) for k in range(5))
    q = quantile_from_histogram(h, [0.5], lo=-8, hi=8)[:, 0]
    true_q = np.quantile(data, 0.5, axis=0)
    print(f"median from 5 blocks: max abs err {np.abs(q - true_q).max():.4f}")

    # progressive declarative query (repro.rsp.query): ask for feature 0's
    # 90th percentile at 2% relative error and watch the anytime CI narrow
    # block by block until the stopping rule fires -- the paper's "few
    # blocks" loop made explicit.  (Relative-error stopping needs a
    # statistic away from zero; p90 here, unlike the near-zero medians.)
    print("\nprogressive query: p90[feature 0] @ 2% target relative error")
    print("blocks  p90[0]      95% CI            rel_err")
    for res in ds.query_stream(
        rsp.Aggregate("quantile", q=0.9, feature=0),
        target_rel_err=0.02,
        use_sketches=False,
        seed=0,
    ):
        a = res["p90[0]"]
        lo_w = "-inf" if np.isneginf(a.ci_lo) else f"{a.ci_lo:.4f}"
        hi_w = "+inf" if np.isposinf(a.ci_hi) else f"{a.ci_hi:.4f}"
        print(f"{res.blocks_read:6d}  {a.estimate:8.4f}  [{lo_w}, {hi_w}]"
              f"  {res.max_rel_err:8.4f}")
        if res.converged:
            st = res.executor_stats
            print(f"-> converged after {res.blocks_read}/{res.total_blocks} blocks"
                  f" ({st.blocks_fetched} fetched, {st.hits} cache hits)")

    # the same machinery answers moment-only queries from the partition-time
    # sketches alone: zero block reads, exact corpus statistics
    res = ds.query(["mean", "var", "count"])
    print(f"sketch-only query: from_sketches={res.from_sketches}, "
          f"blocks_fetched={res.executor_stats.blocks_fetched}, "
          f"count={res['count'].estimate:.0f}")

    # out-of-core ingest: partition a chunked on-disk corpus into a stored
    # RSP without ever loading it whole -- chunks scatter straight to their
    # destination offsets and the sketches fold during the write, so the
    # finished store answers moment queries with zero block reads
    import os
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        chunk_dir = os.path.join(tmp, "chunks")
        os.makedirs(chunk_dir)
        for c in range(8):  # the "distributed data set": 8 chunk files
            np.save(os.path.join(chunk_dir, f"part_{c:03d}.npy"),
                    data[c * (N // 8) : (c + 1) * (N // 8)])
        ds_stream = rsp.from_source(chunk_dir, blocks=K, seed=7,
                                    out=os.path.join(tmp, "corpus.rsp"))
        res = ds_stream.query(["mean", "count"])
        print(f"\nstreamed ingest of {len(os.listdir(chunk_dir))} chunk files -> "
              f"store-backed RSP ({ds_stream.backend}): "
              f"max|mean err| {np.abs(res['mean'].estimate - truth_mean).max():.2e}, "
              f"blocks read {res.executor_stats.blocks_fetched}")
        ds_stream.close()

    # sketch-guided selection: on a *skewed, contiguously-chunked* corpus
    # (NOT an RSP -- the pathological storage order), uniform block sampling
    # is at its worst; weighted PPS selection + Horvitz-Thompson reweighting
    # recovers the corpus mean from the same number of blocks
    rng = np.random.default_rng(0)
    skewed = np.sort(rng.lognormal(mean=1.0, sigma=1.2, size=64 * 512))
    chunked = rsp.RSPDataset(
        rsp.RSPSpec(num_records=64 * 512, num_blocks=64, num_original_blocks=1,
                    record_shape=(1,)),
        blocks=skewed.reshape(64, 512, 1).astype(np.float32),
    )
    truth = skewed.mean()
    uni = chunked.moments(g=8, seed=1).mean[0]
    wgt = chunked.moments(g=8, seed=1, policy="weighted").mean[0]
    print(f"skewed chunked corpus, g=8: true mean {truth:.3f}, "
          f"uniform {uni:.3f}, weighted+HT {wgt:.3f}")


if __name__ == "__main__":
    main()
