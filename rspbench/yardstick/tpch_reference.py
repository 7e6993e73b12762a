"""Plain reference for the TPC-H cell, and the comparison that decides
``correct``.

Independent of the program: it reads the store's block files as data and
recomputes, in int64, Q1's and Q6's totals over each block (every sum an
integer in hundredths, or their products in 1e-4 and 1e-6), then each
answer from the blocks its convergence trace names: per group, the
Horvitz-Thompson total ``K / b * sum_k t_k`` with its Student-t interval and
the finite-population correction ``sqrt((K - b) / (K - 1))``, and each
average as the ratio of two totals with its linearized interval.

Checks, each the worst over the answers that carry it:

* ``store_rows_gap`` (``yardstick.reference.partition_gap``): the blocks
  hold exactly the table's rows;
* ``orderkey_ks_gap``: the largest Kolmogorov-Smirnov distance of a block's
  ``l_orderkey`` from the table's, times the square root of the block's
  rows (Kolmogorov's statistic, whose scale does not move with the block's
  size), the RSP property (Definition 2) on the column the table is stored
  in order of.  Algorithm 1 draws the same number of rows from each
  original block, which reads below a simple random sample's (about 0.9);
  a block made of runs of consecutive rows, one from each of P original
  blocks, reads about ``sqrt(n) / P``;
* ``sum_gap``, ``avg_gap``: relative gaps of sums and averages;
* ``count_gap``: the largest gap of a count (counts are exact);
* ``ci_gap``: relative gap of the interval half-widths;
* ``groups_gap``: groups answered that have no row among the blocks read,
  or groups with rows that were not answered.

Controls (in the program's place): ``bf16``, each decoded column value
rounded to bfloat16 before the expressions and the fold, which are in
float32, the step below the configuration's float32 measures; ``chunked``, a
store of consecutive blocks, whose answers agree with themselves but whose
blocks are no random samples.
"""

from __future__ import annotations

import concurrent.futures as cf
import datetime

import numpy as np
from scipy import stats

from yardstick.lineitem import C, day

PRECISIONS = ("exact", "bf16")
FLAGS, STATUS = ("A", "N", "R"), ("F", "O")
Q1_GROUPS = tuple((f, s) for f in FLAGS for s in STATUS)
#: Q1's totals per group: count, quantity, price, price (1 - disc),
#: price (1 - disc)(1 + tax), discount; the divisor of each int64 sum
Q1_UNITS = (1, 1, 100, 10**4, 10**6, 100)
#: each aggregate as (total column, "total" or "ratio")
Q1_FROM = {
    "sum_qty": (1, "total"), "sum_base_price": (2, "total"), "sum_disc_price": (3, "total"),
    "sum_charge": (4, "total"), "avg_qty": (1, "ratio"), "avg_price": (2, "ratio"),
    "avg_disc": (5, "ratio"), "count_order": (0, "total"),
}
Q6_UNITS = (1, 10**4)
Q6_FROM = {"revenue": (1, "total")}


def _bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def q1_totals(rows: np.ndarray, delta: int, precision: str = "exact") -> np.ndarray:
    """``[6, 6]``: per (returnflag, linestatus) group, Q1's totals over the
    rows shipped on or before 1998-12-01 less ``delta`` days; int64 in the
    units of :data:`Q1_UNITS`, or under ``bf16`` float64 in those units of
    float32 arithmetic on bfloat16 values."""
    r = rows[rows[:, C["l_shipdate"]] <= day("1998-12-01") - delta]
    price, disc, tax = (r[:, C[n]].astype(np.int64) for n in
                        ("l_extendedprice", "l_discount", "l_tax"))
    qty = r[:, C["l_quantity"]].astype(np.int64)
    if precision == "exact":
        cols = (np.ones(len(r), np.int64), qty, price, price * (100 - disc),
                price * (100 - disc) * (100 + tax), disc)
    else:
        p, d, t, q = (_bf16(v / 100.0) for v in (price, disc, tax, qty * 100))
        units = np.asarray(Q1_UNITS, np.float32)
        cols = (np.ones(len(r), np.float32), q, p * units[2], p * (1 - d) * units[3],
                p * (1 - d) * (1 + t) * units[4], d * units[5])
    group = r[:, C["l_returnflag"]] * len(STATUS) + r[:, C["l_linestatus"]]
    out = np.zeros((len(Q1_GROUPS), len(cols)), np.int64 if precision == "exact" else np.float64)
    for g in range(len(Q1_GROUPS)):
        rows_g = group == g
        out[g] = [v[rows_g].sum() for v in cols]
    return out


def q6_year(date: str) -> tuple[int, int]:
    start = datetime.date.fromisoformat(date)
    return day(date), day(start.replace(year=start.year + 1).isoformat())


def q6_totals(rows: np.ndarray, date: str, discount: float, quantity: int,
              precision: str = "exact") -> np.ndarray:
    """``[1, 2]``: Q6's selected-row count and its revenue in 1e-4."""
    lo, hi = q6_year(date)
    d = round(discount * 100)
    ship, disc = rows[:, C["l_shipdate"]], rows[:, C["l_discount"]]
    keep = ((ship >= lo) & (ship < hi) & (disc >= d - 1) & (disc <= d + 1)
            & (rows[:, C["l_quantity"]] < quantity))
    r = rows[keep]
    price, disc = r[:, C["l_extendedprice"]].astype(np.int64), r[:, C["l_discount"]]
    if precision == "exact":
        return np.array([[len(r), int(np.sum(price * disc))]], np.int64)
    revenue = np.sum(_bf16(price / 100.0) * _bf16(disc / 100.0) * np.float32(10**4))
    return np.array([[len(r), revenue]], np.float64)


def ht_answer(totals: np.ndarray, units, num_blocks: int, confidence: float,
              grouped: bool = True) -> dict:
    """Per group with a row among the ``b`` blocks (``seen``): each total
    column's Horvitz-Thompson total and half-width, and its ratio to the
    count (column 0) and the ratio's half-width."""
    b, K = totals.shape[0], num_blocks
    seen = np.flatnonzero(totals[:, :, 0].sum(axis=0) > 0) if grouped else np.arange(1)
    u = np.asarray(units, np.float64)
    t = totals[:, seen].astype(np.float64) / u
    total = K * t.sum(axis=0) / b
    total[:, 0] = K * totals[:, seen, 0].sum(axis=0) / b
    with np.errstate(invalid="ignore", divide="ignore"):   # an average of no rows is NaN
        ratio = t.sum(axis=0) / t[:, :, :1].sum(axis=0)
    if b < 2:
        inf = np.full(total.shape, np.inf)
        return {"seen": seen, "total": total, "total_half": inf, "ratio": ratio,
                "ratio_half": inf}
    scale = stats.t.ppf(0.5 + confidence / 2.0, b - 1) * np.sqrt(max(K - b, 0) / (K - 1))
    resid = t - ratio[None] * t[:, :, :1]
    return {"seen": seen, "total": total,
            "total_half": scale * K * t.std(axis=0, ddof=1) / np.sqrt(b),
            "ratio": ratio,
            "ratio_half": scale * resid.std(axis=0, ddof=1)
            / (np.sqrt(b) * t[:, :, :1].mean(axis=0))}


class TpchReference:
    """Each block's totals under every query of the traffic; from them,
    every answer.  ``precision="bf16"`` is the control."""

    def __init__(self, blocks: np.ndarray, traffic: dict, *, confidence: float,
                 precision: str = "exact", threads: int = 8):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r} (one of {PRECISIONS})")
        self.K = blocks.shape[0]
        self.confidence = confidence
        self.queries = {}
        for shape in traffic["shapes"]:
            if shape["kind"] == "q1":
                for delta in shape["deltas"]:
                    self.queries[("q1", delta)] = (
                        lambda b, delta=delta: q1_totals(b, delta, precision), Q1_UNITS)
            else:
                for date in shape["dates"]:
                    self.queries[("q6", date)] = (
                        lambda b, date=date, s=shape: q6_totals(
                            b, date, s["discount"], s["quantity"], precision), Q6_UNITS)
        with cf.ThreadPoolExecutor(threads) as pool:
            self.totals = {key: np.stack(list(pool.map(fn, blocks)))
                           for key, (fn, _) in self.queries.items()}

    def answer(self, a: dict) -> dict:
        """This reference's answer to the query behind answer ``a``:
        ``groups`` answered and each aggregate's ``est`` and ``half``."""
        key = (a["kind"], a["param"])
        got = ht_answer(self.totals[key][np.asarray(a["ids"])], self.queries[key][1], self.K,
                        self.confidence, grouped=a["kind"] == "q1")
        source = Q1_FROM if a["kind"] == "q1" else Q6_FROM
        out = dict(a)
        out["groups"] = ([list(Q1_GROUPS[g]) for g in got["seen"]]
                         if a["kind"] == "q1" else None)
        out["est"] = {n: np.ravel(got[form][:, j]) for n, (j, form) in source.items()}
        out["half"] = {n: np.ravel(got[form + "_half"][:, j]) for n, (j, form) in source.items()}
        return out


def ks_gap(blocks: np.ndarray, column: int) -> float:
    """The largest KS distance of one block's ``column`` from the whole
    table's (the blocks together), times ``sqrt`` of the block's rows."""
    table = np.sort(blocks[:, :, column].ravel())
    worst = 0.0
    for b in blocks:
        s = np.sort(b[:, column])
        v = np.unique(s)
        fb = np.searchsorted(s, v, "right") / len(s)
        fb_prev = np.concatenate([[0.0], fb[:-1]])
        ft = np.searchsorted(table, v, "right") / len(table)
        ft_left = np.searchsorted(table, v, "left") / len(table)
        worst = max(worst, float(np.max(np.abs(fb - ft))),
                    float(np.max(np.abs(ft_left - fb_prev))))
    return worst * np.sqrt(blocks.shape[1])


def compare(answers: list[dict], ref: TpchReference) -> dict[str, float]:
    """The numbers compared, each the worst over the answers that carry it."""
    worst: dict[str, float] = {}

    def put(name: str, value: float) -> None:
        value = float(value) if np.isfinite(value) else float("inf")
        worst[name] = max(worst.get(name, 0.0), value)

    for a in answers:
        r = ref.answer(a)
        if a["kind"] == "q1":
            put("groups_gap", len({tuple(g) for g in a["groups"]}
                                  ^ {tuple(g) for g in r["groups"]}))
            if a["groups"] != r["groups"]:
                continue
        for name, want in r["est"].items():
            got = np.asarray(a["est"][name], np.float64)
            if got.shape != want.shape:
                put("groups_gap", float("inf"))
                continue
            if name.startswith("count"):
                put("count_gap", np.max(np.abs(got - want)))
            else:
                gap = np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-300))
                put("avg_gap" if name.startswith("avg") else "sum_gap", gap)
            half, ref_half = np.asarray(a["half"][name], np.float64), r["half"][name]
            ok = np.isfinite(ref_half) & (ref_half > 0)
            if ok.any():
                put("ci_gap", np.max(np.abs(half[ok] - ref_half[ok]) / ref_half[ok]))
    return worst
