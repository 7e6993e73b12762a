"""Plain float64 reference for the query cells, and the comparison that
decides ``correct``.

Independent of the program: it reads the store's block files with
``numpy.load`` as data, first checks that they hold exactly the corpus's
records (Definition 2: the blocks are a partition of the corpus, by a
multiset of row hashes), and then recomputes, from the blocks each answer
says it read (``QueryResult.trace``), what that answer must be:

* a filtered mean is the mean of the rows passing ``c > v`` over the blocks
  read, its interval the Student-t interval over per-block means with the
  finite-population correction, its selectivity the passing share of rows;
* a quantile over the extrema grid (filtered ``p50``, grouped ``p50``) is
  the quantile of the merged fixed-grid histogram, linearly interpolated in
  the covering bin, over the grid spanned by the corpus's column extrema;
* a ``p95`` answer, whose grid the program tightens with its own KLL
  sketches, is judged by its rank among the rows read;
* a sketch answer is the corpus's exact mean, variance and count.

``QueryReference(..., precision="bf16")`` is the control: the same
computation with every record rounded to bfloat16 and arithmetic in
float32, the step below the configuration's float32 records.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np
from scipy import stats

PRECISIONS = ("f64", "bf16")


def load_blocks(path: str, num_blocks: int) -> np.ndarray:
    """Every block file of a stored RSP, stacked ``[K, n, F]``."""
    blocks = [
        np.load(os.path.join(path, f"block_{k:05d}.npy"), allow_pickle=False)
        for k in range(num_blocks)
    ]
    return np.stack(blocks)


def row_hashes(a: np.ndarray) -> np.ndarray:
    """Sorted 64-bit FNV-1a hashes of whole records: two arrays hold the same
    multiset of rows iff these agree (up to hash collisions)."""
    words = np.ascontiguousarray(a).reshape(a.shape[0], -1).view(np.uint32)
    h = np.full(words.shape[0], 0xCBF29CE484222325, np.uint64)
    for c in range(words.shape[1]):
        h ^= words[:, c].astype(np.uint64)
        h *= np.uint64(0x100000001B3)
    return np.sort(h)


def partition_gap(blocks: np.ndarray, corpus_hashes: np.ndarray) -> float:
    """Share of sorted row hashes that differ between the blocks and the
    corpus: 0 exactly when the blocks are a partition of the corpus."""
    rows = blocks.reshape(-1, blocks.shape[-1])
    if rows.shape[0] != corpus_hashes.shape[0]:
        return 1.0
    return float(np.count_nonzero(row_hashes(rows) != corpus_hashes)) / rows.shape[0]


def as_precision(x: np.ndarray, precision: str) -> np.ndarray:
    """Records as the reference (float64) or the control (bfloat16 values
    held in float32) computes with them."""
    if precision == "f64":
        return np.asarray(x, np.float64)
    if precision == "bf16":
        import ml_dtypes

        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r} (one of {PRECISIONS})")


def hist_quantile(hist: np.ndarray, q: float, lo: np.ndarray, width: np.ndarray) -> np.ndarray:
    """Quantile ``q`` of fixed-grid histograms ``[..., F, bins]``, linearly
    interpolated inside the bin where the cumulative count reaches it."""
    hist = np.asarray(hist, np.float64)
    cdf = np.cumsum(hist, axis=-1)
    target = q * np.maximum(cdf[..., -1:], 1.0)
    idx = np.argmax(cdf >= target, axis=-1)[..., None]
    below = np.where(idx > 0, np.take_along_axis(cdf, np.maximum(idx - 1, 0), -1), 0.0)
    in_bin = np.take_along_axis(hist, idx, -1)
    frac = np.clip((target - below) / np.maximum(in_bin, 1e-300), 0.0, 1.0)
    return (lo + (idx[..., 0] + frac[..., 0]) * width)


class QueryReference:
    """Per-block statistics of every block, from which each answer's exact
    value follows.  ``values`` are the filter thresholds the traffic uses on
    column ``where_column``; ``label_column`` holds the class."""

    def __init__(
        self,
        corpus: np.ndarray,
        blocks: np.ndarray,
        *,
        where_column: int,
        values: list[float],
        num_classes: int,
        confidence: float,
        bins: int,
        precision: str = "f64",
        threads: int = 8,
    ):
        self.precision = precision
        self.acc = np.float64 if precision == "f64" else np.float32
        self.K, self.n, self.F = blocks.shape
        self.blocks = blocks
        self.where_column = where_column
        self.values = [float(v) for v in values]
        self.num_classes = num_classes
        self.confidence = confidence
        self.bins = bins
        self.N = corpus.shape[0]
        # corpus-level moments (sketch answers) and the extrema grid
        c = as_precision(corpus, precision)
        self.mean = c.mean(axis=0, dtype=self.acc).astype(np.float64)
        self.var = c.var(axis=0, ddof=1, dtype=self.acc).astype(np.float64)
        self.count = float(self.acc(corpus.shape[0]))
        del c
        ref_mean = corpus.mean(axis=0, dtype=np.float64)
        ref_sd = corpus.std(axis=0, dtype=np.float64)
        self.scale = np.maximum(np.abs(ref_mean), ref_sd)
        lo = corpus.min(axis=0).astype(np.float64)
        hi = corpus.max(axis=0).astype(np.float64)
        pad = np.maximum(1e-9, 1e-9 * (hi - lo))
        self.lo, hi = lo - pad, hi + pad
        self.width = (hi - self.lo) / bins
        nv = len(self.values)
        self.f_count = np.zeros((self.K, nv))
        self.f_sum = np.zeros((self.K, nv, self.F))
        self.f_hist = np.zeros((self.K, nv, self.F, bins))
        self.c_hist = np.zeros((self.K, num_classes, self.F, bins))
        with cf.ThreadPoolExecutor(threads) as pool:
            list(pool.map(self._block_stats, range(self.K)))

    def _block_stats(self, k: int) -> None:
        x = as_precision(self.blocks[k], self.precision)
        lo, width = self.lo.astype(self.acc), self.width.astype(self.acc)
        idx = np.clip(np.floor((x - lo) / width), 0, self.bins - 1).astype(np.int64)
        idx += np.arange(self.F) * self.bins
        size = self.F * self.bins
        col = x[:, self.where_column]
        for j, v in enumerate(self.values):
            keep = col > self.acc(np.float32(v))
            self.f_count[k, j] = np.count_nonzero(keep)
            self.f_sum[k, j] = x[keep].sum(axis=0, dtype=self.acc)
            self.f_hist[k, j] = np.bincount(idx[keep].ravel(), minlength=size).reshape(
                self.F, self.bins
            )
        labels = x[:, -1].astype(np.int64)
        for c in range(self.num_classes):
            self.c_hist[k, c] = np.bincount(
                idx[labels == c].ravel(), minlength=size
            ).reshape(self.F, self.bins)

    # -- the answers each query shape must give --------------------------------
    def filtered(self, v: float, ids: list[int]) -> dict:
        j = self.values.index(float(v))
        ids = np.asarray(ids)
        cnt, sums = self.f_count[ids, j], self.f_sum[ids, j]
        b = len(ids)
        mean = sums.sum(axis=0) / max(cnt.sum(), 1.0)
        seen = cnt > 0
        per_block = sums[seen] / cnt[seen][:, None]
        half = np.full(self.F, np.inf)
        if per_block.shape[0] >= 2:
            m = per_block.shape[0]
            t = stats.t.ppf(0.5 + self.confidence / 2.0, m - 1)
            fpc = np.sqrt(max(self.K - m, 0) / (self.K - 1))
            half = t * fpc * per_block.std(axis=0, ddof=1) / np.sqrt(m)
        sel = float(cnt.sum() / (b * self.n))
        p50 = hist_quantile(self.f_hist[ids, j].sum(axis=0), 0.5, self.lo, self.width)
        return {"mean": mean, "half": half, "sel": sel, "p50": p50}

    def grouped(self, q: float, ids: list[int]) -> np.ndarray:
        return hist_quantile(self.c_hist[np.asarray(ids)].sum(axis=0), q, self.lo, self.width)

    def rank_gap(self, q: float, ids: list[int], est: np.ndarray) -> float:
        """Worst ``|share of rows read at or below the estimate - q|`` over
        the feature columns (the label is a class code, not a quantile)."""
        f = self.F - 1
        below = np.zeros(f)
        for k in ids:
            below += np.count_nonzero(self.blocks[k][:, :f] <= est[None, :f], axis=0)
        return float(np.max(np.abs(below / (len(ids) * self.n) - q)))

    def quantile(self, q: float, ids: list[int]) -> np.ndarray:
        """The control's answer to a quantile query: the exact quantile of
        the rows read, as its precision holds them."""
        rows = as_precision(self.blocks[np.asarray(ids)].reshape(-1, self.F), self.precision)
        return np.quantile(rows, q, axis=0)

    def sketch(self) -> dict:
        return {"mean": self.mean, "var": self.var, "count": self.count}

    # -- the control: the reference put in the program's place ----------------
    def answer(self, a: dict) -> dict:
        """This reference's own answer to the query that produced ``a``."""
        out = dict(a)
        if a["kind"] == "filtered":
            r = self.filtered(a["v"], a["ids"])
            out.update(mean=r["mean"], lo=r["mean"] - r["half"], hi=r["mean"] + r["half"],
                       sel=r["sel"], p50=r["p50"])
        elif a["kind"] == "grouped":
            out["est"] = self.grouped(a["q"], a["ids"])
        elif a["kind"] == "quantile":
            out["est"] = self.quantile(a["q"], a["ids"])
        elif a["kind"] == "sketch":
            out.update(self.sketch())
        return out


def compare(answers: list[dict], ref: QueryReference) -> dict[str, float]:
    """The numbers compared, each the worst over the answers that carry it."""
    worst: dict[str, float] = {}

    def put(name: str, value: float) -> None:
        # a NaN (an answer with no value) is never within a limit
        value = float(value) if np.isfinite(value) else float("inf")
        worst[name] = max(worst.get(name, 0.0), value)

    for a in answers:
        kind = a["kind"]
        if kind == "filtered":
            r = ref.filtered(a["v"], a["ids"])
            put("mean_gap", np.max(np.abs(a["mean"] - r["mean"]) / ref.scale))
            put("sel_gap", abs(a["sel"] - r["sel"]))
            put("hist_q_gap", np.max(np.abs(a["p50"] - r["p50"]) / ref.width))
            if len(a["ids"]) >= 2:
                half = (np.asarray(a["hi"]) - np.asarray(a["lo"])) / 2.0
                put("ci_gap", np.max(np.abs(half - r["half"]) / r["half"]))
        elif kind == "grouped":
            r = ref.grouped(a["q"], a["ids"])
            put("hist_q_gap", np.max(np.abs(np.asarray(a["est"]) - r) / ref.width))
        elif kind == "quantile":
            put("p95_rank_gap", ref.rank_gap(a["q"], a["ids"], np.asarray(a["est"])))
        elif kind == "sketch":
            s = ref.sketch()
            put("sketch_gap", max(
                np.max(np.abs(a["mean"] - s["mean"]) / ref.scale),
                np.max(np.abs(a["var"] - s["var"]) / s["var"]),
                abs(a["count"] - s["count"]) / s["count"],
            ))
    return worst
