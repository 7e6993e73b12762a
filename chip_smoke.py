"""Bring-up smoke of the RSP main path on a TPU chip.

Drives partition -> store -> query -> serve once, through the entry points a
user calls, at the size of the paper's HIGGS workload: a class-sorted corpus
of N = 11,010,048 records x (28 features + label) float32 (1.28 GB), made
from ``--seed``, cut by Algorithm 1 into P = K = 128 blocks (delta = 672
records per slice).  Every answer is checked against an exact float64 numpy
full scan of the same corpus; any failed check raises and the script exits
non-zero.

    python chip_smoke.py [--seed S]     # one chip: every phase
    python chip_smoke.py --chips 4      # only the shard_map partition, 4 chips

Lines before the last are one run's output (phase wall times, which impl,
tile and Pallas mode each kernel ran with, the autotuner's records); they
are not measurements.  The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero and prints no result.  One process
owns the chip: run nothing else on it meanwhile.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs, rsp  # noqa: E402
from repro.core.partition import distributed_rsp_partition  # noqa: E402
from repro.data import make_nonrandom_higgs_like  # noqa: E402
from repro.kernels import autotune  # noqa: E402
from repro.rsp.query import Aggregate  # noqa: E402
from repro.runtime import use_compile_cache  # noqa: E402

N = 128 * 128 * 672          # 11,010,048 HIGGS records
FEATURES = 28                # + 1 label column
BLOCKS = 128                 # P = K = 128, delta = 672
LABEL = FEATURES             # column index of the class label
WHERE = "c0 > 1.0"
# Mean CIs are checked one by one against the full scan, some 400 of them,
# so a far-tail confidence keeps a correct estimator's joint miss rate
# negligible.  Quantile CIs come from 200 bootstrap replicates, which cannot
# resolve so far a tail, so quantile answers are checked by exact rank.
CONFIDENCE = 0.999999
RANK_SDS = 6.0


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Prints one phase's wall time and the kernel dispatches it made."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.k0 = kernel_runs()
        log(f"== {self.name}")
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            self.runs = {k: v - self.k0.get(k, 0) for k, v in kernel_runs().items()}
            self.runs = {k: v for k, v in self.runs.items() if v}
            for (kernel, impl, tile, interp), n in sorted(self.runs.items()):
                log(f"   kernel {kernel}: impl={impl} tile={tile} interpret={interp} x{n}")
            log(f"   wall {time.perf_counter() - self.t0:.3f} s (this run)")
        return False

    def ran(self, kernel: str, impl: str) -> bool:
        return any(k[0] == kernel and k[1] == impl for k in self.runs)


def kernel_runs() -> dict[tuple, int]:
    snap = obs.get_registry().snapshot().get("rsp_kernel_runs_total", {"series": []})
    out = {}
    for s in snap["series"]:
        lab = s["labels"]
        out[(lab["kernel"], lab["impl"], lab["tile"], lab["interpret"])] = int(s["value"])
    return out


def make_corpus(n: int, seed: int) -> np.ndarray:
    """HIGGS-shaped records, stored sorted by class: [n, 28 features + label]."""
    x, y = make_nonrandom_higgs_like(n, seed=seed)
    return np.concatenate([x, y[:, None].astype(np.float32)], axis=1)


def row_hashes(a: np.ndarray) -> np.ndarray:
    """Sorted 64-bit FNV-1a hashes of whole records: two arrays hold the same
    multiset of rows iff these agree (up to hash collisions), at O(N log N)
    on one key per row instead of a lexicographic sort of 11M rows."""
    words = np.ascontiguousarray(a).reshape(a.shape[0], -1).view(np.uint32)
    h = np.full(words.shape[0], 0xCBF29CE484222325, np.uint64)
    for c in range(words.shape[1]):
        h ^= words[:, c].astype(np.uint64)
        h *= np.uint64(0x100000001B3)
    return np.sort(h)


def check_partition(blocks: np.ndarray, data: np.ndarray, k: int) -> None:
    """Definition 2: the K blocks are disjoint, equal-sized and together hold
    exactly the input's records."""
    n, f = data.shape
    assert blocks.shape == (k, n // k, f), blocks.shape
    assert blocks.dtype == data.dtype, blocks.dtype
    same = np.array_equal(row_hashes(blocks.reshape(n, f)), row_hashes(data))
    assert same, "blocks are not a partition of the input records"


def exact_reference(data: np.ndarray) -> dict:
    """Exact float64 full scan: every mean, variance and count the queries
    estimate, plus the corpus and the row subsets that quantile answers are
    ranked against (their exact quantiles: :func:`exact_quantiles`)."""
    n, f = data.shape
    where = data[:, 0] > np.float32(1.0)
    classes = [data[:, LABEL] == c for c in (0, 1)]
    ref = {
        "data": data, "where": where, "classes": classes,
        "count": float(n), "mean": np.empty(f), "var": np.empty(f),
        "where_mean": np.empty(f), "class_mean": np.empty((2, f)),
    }
    for c in range(f):
        col = data[:, c].astype(np.float64)
        ref["mean"][c] = col.mean()
        ref["var"][c] = col.var(ddof=1)
        ref["where_mean"][c] = col[where].mean()
        for g in (0, 1):
            ref["class_mean"][g, c] = col[classes[g]].mean()
    return ref


def exact_quantiles(ref: dict) -> None:
    """The exact feature quantiles the quantile queries estimate."""
    data, where, classes = ref["data"], ref["where"], ref["classes"]
    ref["p95"] = np.quantile(data[:, :FEATURES].astype(np.float64), 0.95, axis=0)[None]
    ref["where_p50"] = np.quantile(data[where, :FEATURES].astype(np.float64), 0.5, axis=0)[None]
    ref["class_p50"] = np.stack(
        [np.quantile(data[m, :FEATURES].astype(np.float64), 0.5, axis=0) for m in classes]
    )


def check_close(name: str, est, truth, scale, rtol: float) -> None:
    err = float(np.max(np.abs(np.asarray(est) - truth) / scale))
    log(f"   {name}: max error / scale {err:.3e} (limit {rtol:g})")
    assert err <= rtol, f"{name}: {err} > {rtol}"


def check_covers(name: str, agg, truth) -> None:
    """Every CI of a mean aggregate covers the exact full-scan value."""
    lo, hi = np.asarray(agg.ci_lo), np.asarray(agg.ci_hi)
    covered = (lo <= truth) & (truth <= hi)
    log(f"   {name}: {int(covered.sum())}/{covered.size} CIs cover the full-scan value")
    assert covered.all(), f"{name}: CI misses the full-scan value at {np.argwhere(~covered)}"


def check_rank(name: str, res, agg: str, q: float, ref: dict, exact: str, masks) -> None:
    """A quantile answer is right when its rank in the exact full scan of
    its rows is ``q`` within ``RANK_SDS`` sampling standard deviations,
    sqrt(q (1 - q) / m) for the m rows the answer was estimated from.  The
    label is a 0/1 code, not a quantile target, so features only."""
    a = res[agg]
    est = np.atleast_2d(np.asarray(a.estimate))[:, :FEATURES]
    covered = (np.atleast_2d(a.ci_lo)[:, :FEATURES] <= ref[exact]) & (
        ref[exact] <= np.atleast_2d(a.ci_hi)[:, :FEATURES]
    )
    data, worst = ref["data"], 0.0
    for g, mask in enumerate(masks):
        rows = data[:, :FEATURES] if mask is None else data[mask, :FEATURES]
        m = res.blocks_read * len(data) / res.total_blocks * len(rows) / len(data)
        sd = np.sqrt(q * (1 - q) / m)
        ranks = np.count_nonzero(rows <= est[g], axis=0) / len(rows)
        worst = max(worst, float(np.max(np.abs(ranks - q)) / sd))
    log(f"   {name}: worst rank error {worst:.2f} sampling SDs (limit {RANK_SDS});"
        f" {int(covered.sum())}/{covered.size} bootstrap CIs cover the exact quantile")
    assert worst <= RANK_SDS, f"{name}: rank error {worst} SDs > {RANK_SDS}"


def check_sketch_answer(res, ref) -> None:
    assert res.from_sketches and res.blocks_read == 0, res
    assert res.executor_stats is None or res.executor_stats.blocks_fetched == 0
    scale = np.maximum(np.abs(ref["mean"]), np.sqrt(ref["var"]))
    check_close("sketch mean", res["mean"].estimate, ref["mean"], scale, 1e-6)
    check_close("sketch var", res["var"].estimate, ref["var"], ref["var"], 1e-6)
    assert res["count"].estimate == ref["count"], res["count"].estimate


def run_queries(ds, ref: dict, impl: str, seed: int) -> None:
    """The four query shapes of the main path, each checked."""
    stream = dict(use_sketches=False, confidence=CONFIDENCE, seed=seed, sketch_impl=impl)
    with Phase(f"query sketch-only mean/var/count (sketch_impl={impl})"):
        check_sketch_answer(ds.query(["mean", "var", "count"], sketch_impl=impl), ref)

    with Phase(f"query progressive p95, target 1% (sketch_impl={impl})") as ph:
        res = ds.query("p95", target_rel_err=0.01, min_blocks=8, **stream)
        log(f"   {res}")
        assert res.converged, res
        check_rank("p95", res, "p95", 0.95, ref, "p95", [None])
    if impl == "pallas":
        assert ph.ran("plan", "pallas"), ph.runs

    with Phase(f"query mean+p50 where {WHERE!r} (sketch_impl={impl})") as ph:
        res = ds.query(["mean", "p50"], where=WHERE, max_blocks=BLOCKS // 4, **stream)
        log(f"   {res} selectivity={res.selectivity:.4f}")
        check_covers("filtered mean", res["mean"], ref["where_mean"])
        check_rank("filtered p50", res, "p50", 0.5, ref, "where_p50", [ref["where"]])
    if impl == "pallas":
        assert ph.ran("plan", "pallas"), ph.runs

    with Phase(f"query grouped p50 by label (sketch_impl={impl})") as ph:
        res = ds.query(
            Aggregate("quantile", q=0.5, by_label=True), max_blocks=BLOCKS // 4, **stream
        )
        log(f"   {res}")
        check_rank("grouped p50", res, "p50/label", 0.5, ref, "class_p50", ref["classes"])
    if impl == "pallas":
        assert ph.ran("plan", "pallas"), ph.runs


def run_service(ds, ref: dict) -> None:
    """16 concurrent mixed queries through ds.serve, some with deadlines."""
    p = dict(use_sketches=False, confidence=CONFIDENCE)
    part = BLOCKS // 8
    mix = (
        # first, so they are admitted at once and the deadline cuts a scan
        [("deadline", "mean",
          dict(target_rel_err=1e-9, max_blocks=BLOCKS // 2, deadline_ms=500, **p))] * 2
        + [("sketch", ["mean", "var", "count"], {})] * 4
        + [("mean", "mean", dict(target_rel_err=0.01, max_blocks=part, **p))] * 4
        + [("p95", "p95", dict(target_rel_err=0.01, min_blocks=8, **p))] * 2
        + [("where", ["mean", "p50"], dict(where=WHERE, max_blocks=part, **p))] * 2
        + [("class", Aggregate("mean", by_label=True), dict(max_blocks=part, **p))] * 2
    )
    with ds.serve(capacity=16, workers=4, seed=7) as svc:
        tickets = [(tag, svc.submit(aggs, **kw)) for tag, aggs, kw in mix]
        outcomes: dict[str, int] = {}
        for tag, t in tickets:
            res = svc.result(t, timeout=600)
            outcomes[t.outcome] = outcomes.get(t.outcome, 0) + 1
            assert t.outcome not in ("failed", "rejected", "cancelled"), (tag, t)
            if tag == "sketch":
                check_sketch_answer(res, ref)
            elif tag in ("mean", "deadline"):
                check_covers(f"served {tag} ({t.outcome}, {res.blocks_read} blocks)",
                             res["mean"], ref["mean"])
            elif tag == "p95":
                check_rank("served p95", res, "p95", 0.95, ref, "p95", [None])
            elif tag == "where":
                check_covers("served filtered mean", res["mean"], ref["where_mean"])
                check_rank("served filtered p50", res, "p50", 0.5, ref, "where_p50",
                           [ref["where"]])
            else:
                check_covers("served class mean", res["mean/label"], ref["class_mean"])
        m = svc.metrics()
    log(f"   outcomes {outcomes}; completed={m.completed}")
    assert sum(outcomes.values()) == len(mix)


def tuner_records() -> None:
    recs = {k: v for k, v in autotune.get_tuner().records().items() if k.endswith("|tpu")}
    for key, rec in sorted(recs.items()):
        log(f"   autotune {key}: winner {rec['impl']}:{rec['tile_rows']}"
            f" excluded={rec['excluded']} measured_us={rec['measured_us']}")
        assert not any("(error)" in e for e in rec["excluded"]), (key, rec)


def one_chip(seed: int) -> None:
    with Phase("make corpus"):
        data = make_corpus(N, seed)
        log(f"   {data.shape[0]} records x {data.shape[1]} columns, {data.nbytes / 1e9:.2f} GB")

    with Phase("partition (backend=auto)") as ph:
        ds = rsp.partition(data, blocks=BLOCKS, backend="auto", num_classes=2, seed=seed)
        log(f"   backend={ds.backend} blocks={ds.num_blocks} x {ds.block_size}")
        assert ds.backend == "pallas", ds.backend
    assert ph.ran("rsp_shuffle", "pallas"), ph.runs

    with Phase("check partition (Definition 2) and label balance"):
        check_partition(ds.stacked(), data, BLOCKS)
        div = ds.label_divergence()
        log(f"   worst block label divergence {div:.5f} (sequential chunking: 0.5)")
        assert div < 0.02, div

    with Phase("exact float64 full scan (reference)"):
        ref = exact_reference(data)
        exact_quantiles(ref)

    with tempfile.TemporaryDirectory(prefix="rsp_smoke_") as tmp:
        with Phase("save + open"):
            path = os.path.join(tmp, "higgs.rsp")
            ds.save(path)
            ds.close()
            ds = rsp.open(path)
            assert ds.num_blocks == BLOCKS and ds.has_summaries
        for impl in ("pallas", "auto"):
            run_queries(ds, ref, impl, seed)
        with Phase("serve 16 concurrent mixed queries"):
            run_service(ds, ref)
        ds.close()

    log("== autotuner records")
    tuner_records()
    interpreted = [k for k in kernel_runs() if k[3] == "true"]
    assert not interpreted, f"kernels ran in interpret mode: {interpreted}"


def four_chips(seed: int) -> None:
    """Algorithm 1 as one shard_map program over four chips (P = K = 4)."""
    devices = jax.devices()
    mesh = jax.sharding.Mesh(np.array(devices), ("data",))
    with Phase("make corpus"):
        data = make_corpus(N, seed)

    with Phase("partition (backend=auto, 4-chip mesh)"):
        ds = rsp.partition(
            data, blocks=len(devices), backend="auto", mesh=mesh, num_classes=2, seed=seed
        )
        log(f"   backend={ds.backend} blocks={ds.num_blocks} x {ds.block_size}")
        assert ds.backend == "shard_map", ds.backend

    with Phase("check residency: one block per device"):
        spec = jax.sharding.PartitionSpec("data", None)
        placed = jax.device_put(data, jax.sharding.NamedSharding(mesh, spec))
        out = distributed_rsp_partition(placed, jax.random.PRNGKey(seed), mesh)
        for k, dev in enumerate(devices):
            (shard,) = [s for s in out.addressable_shards if s.device == dev]
            assert shard.index[0] == slice(k, k + 1, None), (dev, shard.index)
            log(f"   block {k} on {dev}")
        assert np.array_equal(np.asarray(out), ds.stacked())

    with Phase("check partition (Definition 2) and exact corpus moments"):
        check_partition(ds.stacked(), data, len(devices))
        ref = exact_reference(data)
        check_sketch_answer(ds.query(["mean", "var", "count"]), ref)
    ds.close()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    log(f"device: {device}")
    if dev.platform != "tpu":
        print("no TPU found: this smoke runs on the chip only", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    log(f"compile cache: {use_compile_cache()}")
    obs.enable(sample_rate=0.0)  # metrics (the kernel-run counter), no spans
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
