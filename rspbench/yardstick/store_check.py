"""Plain reference for the ingest cell: is a written store an RSP of the corpus?

Reads the store's block files and its sketch sidecar as data and checks
against the corpus made from the seed:

* ``rows_gap``: Definition 2 -- the blocks hold exactly the corpus's records
  (a multiset of row hashes), every block the same size;
* ``label_gap``: every block is a random sample -- its class-1 share lies
  near the corpus's, where sequential chunking of the class-sorted corpus
  gives blocks of one class;
* ``sketch_gap``: the partition-time moments and label counts of every
  block equal the float64 ones of its rows;
* ``kll_items_gap``: every block's quantile sketch (KLL) summarises all of
  its rows and nothing else -- per column, the count and the total weight
  of the retained items equal the block's rows, and every retained item is
  one of the column's values (a KLL compactor only ever keeps input items);
* ``kll_rank_gap``: the 99th percentile, over blocks, feature columns and
  ``KLL_QS``, of ``|share of the block's rows at or below the sketch's
  q-quantile - q|``, the sketch's rank error against exact ranks;
* ``kmv_gap``: the mean, over blocks and columns, of the relative error of
  the distinct-count sketch's (KMV) estimate against the exact count of
  distinct values in the column.

Every sketch kind the partition writes by default (moments, KLL, KMV,
labels) has to be there: a missing one reads ``inf``.

The controls are partitions made here in the program's place:
``bf16`` deals a random permutation of the corpus with every record rounded
to bfloat16 and sketches it in float32 (the step below the configuration's
float32 records); ``bf16_sketch`` stores the exact records of such a deal
but computes every sketch from them rounded to bfloat16 (a sketch suite
moved to lower precision); ``chunked`` cuts the class-sorted corpus into
consecutive blocks without shuffling (the storage the paper warns about).
A control's quantile sketch keeps items at evenly spaced ranks, its
distinct-count sketch the smallest hashes of its values (``splitmix64`` of
the float64 bit pattern).
"""

from __future__ import annotations

import json
import os

import numpy as np

from yardstick.reference import as_precision, load_blocks, partition_gap, row_hashes

CONTROLS = ("bf16", "bf16_sketch", "chunked")
KLL_QS = (0.05, 0.25, 0.5, 0.75, 0.95)
KLL_K = 160   # the controls' quantile-sketch size, the program's default
KMV_K = 256   # the controls' distinct-count sketch size, the program's default
INF = float("inf")


class CorpusFacts:
    """What the checks need of the corpus, computed once per run."""

    def __init__(self, corpus: np.ndarray):
        self.hashes = row_hashes(corpus)
        self.label_share = float(corpus[:, -1].astype(np.float64).mean())
        mean = corpus.mean(axis=0, dtype=np.float64)
        self.scale = np.maximum(np.abs(mean), corpus.std(axis=0, dtype=np.float64))
        self.num_records = corpus.shape[0]


def read_sketches(path: str) -> list[dict]:
    """Per-block sketches from a store's sketch sidecar, in block order: the
    moments, label counts, KLL columns (``n`` and ``levels``, level ``h``
    holding items of weight ``2**h``) and KMV columns (sorted hashes, with
    its ``k``); a kind the store lacks is ``None``."""
    with open(os.path.join(path, "sketches.json")) as f:
        suites = json.load(f)["summaries"]
    out = []
    for s in sorted(suites, key=lambda s: s.get("block_id", 0)):
        sk = s["sketches"]
        m, labels, kll, kmv = (sk.get(k) for k in ("moments", "labels", "kll", "distinct"))
        out.append({
            "count": float(m["count"]),
            "mean": np.asarray(m["mean"], np.float64),
            "m2": np.asarray(m["m2"], np.float64),
            "min": np.asarray(m["min"], np.float64),
            "max": np.asarray(m["max"], np.float64),
            "labels": None if labels is None else np.asarray(labels["hist"], np.float64),
            "kll": None if kll is None or kll.get("columns") is None else [
                {"n": int(c["n"]), "levels": [np.asarray(lv, np.float64) for lv in c["levels"]]}
                for c in kll["columns"]
            ],
            "kmv": None if kmv is None or kmv.get("columns") is None else {
                "k": int(kmv["k"]),
                "columns": [np.asarray(c, np.uint64) for c in kmv["columns"]],
            },
        })
    return out


def splitmix64(x: np.ndarray) -> np.ndarray:
    """The splitmix64 finaliser over uint64 (Steele, Lea, Flood 2014)."""
    x = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        z = x + np.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def control_kll(sorted_col: np.ndarray, k: int = KLL_K) -> dict:
    """A quantile sketch of one sorted column: items at evenly spaced ranks,
    each of weight ``2**h`` (about ``n / k``), and the rows left over at
    weight 1, so the weights sum to the rows."""
    n = sorted_col.shape[0]
    h = max(int(np.floor(np.log2(max(n / k, 1.0)))), 0)
    w = 1 << h
    m = n // w
    items = sorted_col[np.arange(m) * w + w // 2].astype(np.float64)
    rest = sorted_col[m * w:].astype(np.float64)
    levels = [rest] + [np.empty(0)] * (h - 1) + [items] if h else [np.concatenate([rest, items])]
    return {"n": n, "levels": levels}


def control_kmv(col: np.ndarray, k: int = KMV_K) -> np.ndarray:
    """The ``k`` smallest hashes of a column's distinct values."""
    v = np.asarray(col, np.float64).copy()
    v[v == 0.0] = 0.0
    return np.unique(splitmix64(v.view(np.uint64)))[:k]


def moments_of(block: np.ndarray, num_classes: int, precision: str = "f64") -> dict:
    """A block's moments and label counts as ``precision`` computes them."""
    x = as_precision(block, precision)
    acc = np.float64 if precision == "f64" else np.float32
    mean = x.mean(axis=0, dtype=acc)
    return {
        "count": float(x.shape[0]),
        "mean": mean.astype(np.float64),
        "m2": ((x - mean) ** 2).sum(axis=0, dtype=acc).astype(np.float64),
        "min": x.min(axis=0).astype(np.float64),
        "max": x.max(axis=0).astype(np.float64),
        "labels": np.bincount(x[:, -1].astype(np.int64), minlength=num_classes).astype(
            np.float64
        ),
    }


def sketch_of(block: np.ndarray, num_classes: int, precision: str = "f64") -> dict:
    """A control's sketches of a block, as ``precision`` computes them."""
    x = as_precision(block, precision)
    xs = np.sort(x, axis=0)
    return dict(
        moments_of(block, num_classes, precision),
        kll=[control_kll(xs[:, j]) for j in range(x.shape[1])],
        kmv={"k": KMV_K, "columns": [control_kmv(x[:, j]) for j in range(x.shape[1])]},
    )


def kll_quantile(levels: list[np.ndarray], q: float) -> float:
    """The smallest retained item whose cumulative weight reaches ``q`` of
    the total."""
    v = np.concatenate(levels)
    w = np.concatenate([np.full(lv.size, float(1 << h)) for h, lv in enumerate(levels)])
    order = np.argsort(v, kind="stable")
    cum = np.cumsum(w[order])
    i = min(int(np.searchsorted(cum, q * cum[-1], side="left")), v.size - 1)
    return float(v[order][i])


def kmv_estimate(hashes: np.ndarray, k: int) -> float:
    """KMV's distinct-count estimate: exact below ``k`` hashes, else
    ``(k - 1) / r_k`` with ``r_k`` the k-th smallest hash over 2**64."""
    if hashes.size < k:
        return float(hashes.size)
    return (k - 1) / ((float(hashes[k - 1]) + 1.0) / 2.0**64)


def block_sketch_gaps(block: np.ndarray, s: dict, num_classes: int,
                      scale: np.ndarray) -> tuple[float, float, list[float], list[float]]:
    """One block against its sketches: the moments-and-labels gap, the KLL
    items gap, the KLL rank errors and the KMV relative errors."""
    n, F = block.shape
    ref = moments_of(block, num_classes)
    moments = max(
        abs(s["count"] - ref["count"]) / n,
        float(np.max(np.abs(s["mean"] - ref["mean"]) / scale)),
        float(np.max(np.abs(s["m2"] - ref["m2"]) / np.maximum(ref["m2"], 1e-300))),
        float(np.max(np.abs(s["min"] - ref["min"]) / scale)),
        float(np.max(np.abs(s["max"] - ref["max"]) / scale)),
        INF if s["labels"] is None else float(np.max(np.abs(s["labels"] - ref["labels"]))) / n,
    )
    xs = np.sort(block, axis=0)
    items, ranks, rel = 0.0, [], []
    if s["kll"] is None or len(s["kll"]) != F:
        items = INF
    else:
        for j, col in enumerate(s["kll"]):
            lv = [np.asarray(v, np.float64) for v in col["levels"]]
            weight = sum(v.size << h for h, v in enumerate(lv))
            flat = np.concatenate(lv) if lv else np.empty(0)
            sc = xs[:, j].astype(np.float64)
            at = np.minimum(np.searchsorted(sc, flat), n - 1)
            missing = np.count_nonzero(sc[at] != flat) / max(flat.size, 1)
            items = max(items, abs(col["n"] - n) / n, abs(weight - n) / n, missing)
            if j < F - 1 and flat.size:   # the last column is the class label
                for q in KLL_QS:
                    x = kll_quantile(lv, q)
                    ranks.append(abs(np.searchsorted(sc, x, side="right") / n - q))
    if s["kmv"] is None or len(s["kmv"]["columns"]) != F:
        rel.append(INF)
    else:
        distinct = 1 + np.count_nonzero(np.diff(xs, axis=0) != 0, axis=0)
        for j, h in enumerate(s["kmv"]["columns"]):
            est = kmv_estimate(np.sort(h), s["kmv"]["k"])
            rel.append(abs(est - distinct[j]) / distinct[j])
    return moments, items, ranks, rel


def gaps(blocks: np.ndarray, sketches: list[dict], facts: CorpusFacts,
         num_classes: int) -> dict[str, float]:
    """The numbers compared for one store."""
    K, n, _ = blocks.shape
    label_gap = float(np.max(np.abs(blocks[:, :, -1].mean(axis=1, dtype=np.float64)
                                    - facts.label_share)))
    whole = len(sketches) == K
    moments, items, ranks, rel = (0.0, 0.0, [], []) if whole else (INF, INF, [INF], [INF])
    for k, s in enumerate(sketches[:K]):
        m, i, r, e = block_sketch_gaps(blocks[k], s, num_classes, facts.scale)
        moments, items = max(moments, m), max(items, i)
        ranks += r
        rel += e

    def finite(v: float) -> float:
        return float(v) if np.isfinite(v) else INF

    return {
        "rows_gap": partition_gap(blocks, facts.hashes),
        "label_gap": label_gap,
        "sketch_gap": finite(moments),
        "kll_items_gap": finite(items),
        "kll_rank_gap": finite(np.percentile(ranks, 99)) if ranks else INF,
        "kmv_gap": finite(np.mean(rel)) if rel else INF,
    }


def check_store(path: str, num_blocks: int, facts: CorpusFacts, num_classes: int) -> dict:
    blocks = load_blocks(path, num_blocks)
    return gaps(blocks, read_sketches(path), facts, num_classes)


def control_store(corpus: np.ndarray, num_blocks: int, seed: int, control: str,
                  num_classes: int) -> tuple[np.ndarray, list[dict]]:
    """Blocks and sketches of a control partition (see the module doc)."""
    n = corpus.shape[0] // num_blocks
    if control == "bf16":
        perm = np.random.default_rng(seed).permutation(corpus.shape[0])
        blocks = as_precision(corpus[perm], "bf16").reshape(num_blocks, n, -1)
        sketches = [sketch_of(b, num_classes, "bf16") for b in blocks]
    elif control == "bf16_sketch":
        perm = np.random.default_rng(seed).permutation(corpus.shape[0])
        blocks = corpus[perm].reshape(num_blocks, n, -1)
        sketches = [sketch_of(b, num_classes, "bf16") for b in blocks]
    elif control == "chunked":
        blocks = corpus.reshape(num_blocks, n, -1)
        sketches = [sketch_of(b, num_classes) for b in blocks]
    else:
        raise ValueError(f"unknown control {control!r} (one of {CONTROLS})")
    return blocks.astype(np.float32), sketches
