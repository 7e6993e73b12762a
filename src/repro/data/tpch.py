"""TPC-H ``lineitem``, and a plain reference for its queries Q1 and Q6.

``lineitem(n, seed=...)`` draws the first ``n`` rows of the ``LINEITEM``
table of the TPC-H Standard Specification at scale factor ``scale_factor``,
as Clause 4.2.3 generates it, in ``l_orderkey`` order (as dbgen writes it),
as ``[n, 16]`` int32 codes under :func:`lineitem_schema`: keys and
integers as they are, decimals in hundredths, dates in days since
1970-01-01, the 1-character and short-string columns as dictionary codes.

Departures from the specification:

* numpy's generator in place of dbgen's, so a seed does not reproduce dbgen
  row for row, only the distributions of Clause 4.2.3;
* the table is cut after ``n`` rows, so its last order may hold fewer lines
  than it drew;
* ``l_comment`` is text, and a record holds numbers: it is stored as a
  32-bit id of its text, drawn uniformly (neither query reads it);
* ``O_ORDERDATE``, which only the lines' dates read, is drawn here per order
  and not stored.

The reference computes Q1 (Clause 2.4.1) and Q6 (Clause 2.4.6) exactly over
any rows: every total is an int64 sum of integer codes (hundredths, their
products in 1e-4 and 1e-6), so ``exact`` is exact.  :func:`ht_answer`
turns per-block totals into what an approximate answer over ``b`` of ``K``
random-sample blocks must be: Horvitz-Thompson totals with Student-t
intervals and the finite-population correction, averages as ratios of
totals.  It imports nothing of the system's kernels or query executor.
"""

from __future__ import annotations

import datetime

import numpy as np

from repro.core.schema import Column, Schema, date_code

START = date_code("1992-01-01")
CURRENT = date_code("1995-06-17")
END = date_code("1998-12-31")
RETURNFLAGS = ("A", "N", "R")
LINESTATUS = ("F", "O")
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")

COLUMNS = (
    Column("l_orderkey", "key"),
    Column("l_partkey", "key"),
    Column("l_suppkey", "key"),
    Column("l_linenumber", "int"),
    Column("l_quantity", "int"),
    Column("l_extendedprice", "decimal", scale=2),
    Column("l_discount", "decimal", scale=2),
    Column("l_tax", "decimal", scale=2),
    Column("l_returnflag", "dict", dictionary=RETURNFLAGS),
    Column("l_linestatus", "dict", dictionary=LINESTATUS),
    Column("l_shipdate", "date"),
    Column("l_commitdate", "date"),
    Column("l_receiptdate", "date"),
    Column("l_shipinstruct", "dict", dictionary=INSTRUCTIONS),
    Column("l_shipmode", "dict", dictionary=MODES),
    Column("l_comment", "id"),
)
C = {c.name: i for i, c in enumerate(COLUMNS)}


def lineitem_schema() -> Schema:
    return Schema(COLUMNS)


def order_keys(i: np.ndarray) -> np.ndarray:
    """``O_ORDERKEY`` of the ``i``-th order (0-based): the first 8 of every
    32 keys, the specification's sparse keys."""
    return (i // 8) * 32 + i % 8 + 1


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """``P_RETAILPRICE`` of a part, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def lineitem(num_records: int, *, seed: int = 0, scale_factor: int = 10) -> np.ndarray:
    """The first ``num_records`` rows of ``LINEITEM`` at ``scale_factor``,
    ``[num_records, 16]`` int32 under :func:`lineitem_schema`."""
    rng = np.random.default_rng(seed)
    n = int(num_records)
    lines = rng.integers(1, 8, size=n // 4 + 64)            # 1..7 lines an order
    while lines.sum() < n:
        lines = np.concatenate([lines, rng.integers(1, 8, size=n // 4 + 64)])
    last = int(np.searchsorted(np.cumsum(lines), n)) + 1
    lines = lines[:last]
    orderdate = rng.integers(START, END - 151 + 1, size=last)
    order = np.repeat(np.arange(last), lines)[:n]
    first = np.repeat(np.cumsum(lines) - lines, lines)[:n]
    out = np.empty((n, len(COLUMNS)), np.int64)
    out[:, C["l_orderkey"]] = order_keys(order)
    out[:, C["l_linenumber"]] = np.arange(n) - first + 1
    part = rng.integers(1, scale_factor * 200_000 + 1, size=n)
    s = scale_factor * 10_000
    supplier = rng.integers(0, 4, size=n)
    out[:, C["l_partkey"]] = part
    out[:, C["l_suppkey"]] = (part + supplier * (s // 4 + (part - 1) // s)) % s + 1
    qty = rng.integers(1, 51, size=n)
    out[:, C["l_quantity"]] = qty
    out[:, C["l_extendedprice"]] = qty * retail_cents(part)
    out[:, C["l_discount"]] = rng.integers(0, 11, size=n)
    out[:, C["l_tax"]] = rng.integers(0, 9, size=n)
    od = orderdate[order]
    ship = od + rng.integers(1, 122, size=n)
    receipt = ship + rng.integers(1, 31, size=n)
    out[:, C["l_shipdate"]] = ship
    out[:, C["l_commitdate"]] = od + rng.integers(30, 91, size=n)
    out[:, C["l_receiptdate"]] = receipt
    a_or_r = np.where(rng.integers(0, 2, size=n) == 0, 0, 2)  # "A" or "R"
    out[:, C["l_returnflag"]] = np.where(receipt <= CURRENT, a_or_r, 1)
    out[:, C["l_linestatus"]] = (ship > CURRENT).astype(np.int64)  # "O" after CURRENTDATE
    out[:, C["l_shipinstruct"]] = rng.integers(0, len(INSTRUCTIONS), size=n)
    out[:, C["l_shipmode"]] = rng.integers(0, len(MODES), size=n)
    out[:, C["l_comment"]] = rng.integers(-(2**31), 2**31, size=n)
    return out.astype(np.int32)


# ---------------------------------------------------------------------------
# Q1 and Q6 as the query layer is asked them: (name, kind, expression)
# ---------------------------------------------------------------------------

Q1 = (
    ("sum_qty", "sum", "l_quantity"),
    ("sum_base_price", "sum", "l_extendedprice"),
    ("sum_disc_price", "sum", "l_extendedprice * (1 - l_discount)"),
    ("sum_charge", "sum", "l_extendedprice * (1 - l_discount) * (1 + l_tax)"),
    ("avg_qty", "avg", "l_quantity"),
    ("avg_price", "avg", "l_extendedprice"),
    ("avg_disc", "avg", "l_discount"),
    ("count_order", "count", None),
)
Q1_GROUP_BY = ("l_returnflag", "l_linestatus")
Q6 = (("revenue", "sum", "l_extendedprice * l_discount"),)


def _day(code: int) -> str:
    return (datetime.date(1970, 1, 1) + datetime.timedelta(days=int(code))).isoformat()


def q1_where(delta: int) -> tuple[str, ...]:
    """``l_shipdate <= date '1998-12-01' - interval 'DELTA' day``."""
    return (f"l_shipdate <= date '{_day(date_code('1998-12-01') - delta)}'",)


def q6_where(date: str, discount: float, quantity: int) -> tuple[str, ...]:
    """Q6's filter: one year from ``date``, the discount within 0.01 of
    ``discount``, the quantity under ``quantity``."""
    year = datetime.date.fromisoformat(date)
    return (
        f"l_shipdate >= date '{year.isoformat()}'",
        f"l_shipdate < date '{year.replace(year=year.year + 1).isoformat()}'",
        f"l_discount >= {discount - 0.01:.2f}",
        f"l_discount <= {discount + 0.01:.2f}",
        f"l_quantity < {quantity}",
    )


# ---------------------------------------------------------------------------
# The exact reference
# ---------------------------------------------------------------------------

#: Q1's totals per group: count, then sums in these units (the divisor of
#: each int64 sum)
Q1_TOTALS = (("count", 1), ("qty", 1), ("price", 100), ("disc_price", 10**4),
             ("charge", 10**6), ("disc", 100))
Q1_GROUPS = tuple((f, s) for f in RETURNFLAGS for s in LINESTATUS)


def q1_totals(rows: np.ndarray, delta: int) -> np.ndarray:
    """``[6, 6]`` int64: for each (returnflag, linestatus) of
    :data:`Q1_GROUPS`, the totals of :data:`Q1_TOTALS` over the rows that
    ship on or before 1998-12-01 minus ``delta`` days."""
    r = rows[rows[:, C["l_shipdate"]] <= date_code("1998-12-01") - delta].astype(np.int64)
    price = r[:, C["l_extendedprice"]]
    disc = r[:, C["l_discount"]]
    cols = (
        np.ones(len(r), np.int64),
        r[:, C["l_quantity"]],
        price,
        price * (100 - disc),
        price * (100 - disc) * (100 + r[:, C["l_tax"]]),
        disc,
    )
    group = r[:, C["l_returnflag"]] * len(LINESTATUS) + r[:, C["l_linestatus"]]
    out = np.zeros((len(Q1_GROUPS), len(cols)), np.int64)
    for g in range(len(Q1_GROUPS)):
        rows_g = group == g
        out[g] = [int(v[rows_g].sum()) for v in cols]
    return out


def q6_totals(rows: np.ndarray, date: str, discount: float, quantity: int) -> np.ndarray:
    """``[1, 2]`` int64: Q6's selected-row count and its revenue in 1e-4."""
    lo = date_code(date)
    year = datetime.date.fromisoformat(date)
    hi = date_code(year.replace(year=year.year + 1))
    d = round(discount * 100)
    ship, disc = rows[:, C["l_shipdate"]], rows[:, C["l_discount"]]
    keep = ((ship >= lo) & (ship < hi) & (disc >= d - 1) & (disc <= d + 1)
            & (rows[:, C["l_quantity"]] < quantity))
    r = rows[keep].astype(np.int64)
    revenue = int(np.sum(r[:, C["l_extendedprice"]] * r[:, C["l_discount"]]))
    return np.array([[len(r), revenue]], np.int64)


def ht_answer(totals: np.ndarray, units, num_blocks: int, confidence: float = 0.95,
              grouped: bool = True) -> dict:
    """The answer over ``b`` blocks with block totals ``totals [b, G, T]``
    (int64, column ``j`` in units of ``1 / units[j]``; column 0 counts rows):
    per group seen, each column's Horvitz-Thompson total ``K / b * sum`` and
    its half-width, and each column's ratio to the count (an average) and
    its linearized half-width.  Groups with no row in the blocks are left
    out (``seen``, the indices kept) where ``grouped``; an ungrouped answer
    keeps its one group."""
    b, K = totals.shape[0], num_blocks
    seen = np.flatnonzero(totals[:, :, 0].sum(axis=0) > 0) if grouped else np.arange(1)
    t = totals[:, seen].astype(np.float64) / np.asarray(units, np.float64)
    total = K * totals[:, seen].sum(axis=0) / np.asarray(units, np.float64) / b
    total[:, 0] = K * totals[:, seen, 0].sum(axis=0) / b   # the count, exactly as counted
    with np.errstate(invalid="ignore", divide="ignore"):   # an average of no rows is NaN
        ratio = t.sum(axis=0) / t[:, :, :1].sum(axis=0)
    if b < 2:
        inf = np.full(total.shape, np.inf)
        return {"seen": seen, "total": total, "total_half": inf, "ratio": ratio,
                "ratio_half": inf}
    from scipy import stats

    scale = stats.t.ppf(0.5 + confidence / 2.0, b - 1) * np.sqrt(max(K - b, 0) / (K - 1))
    total_half = scale * K * t.std(axis=0, ddof=1) / np.sqrt(b)
    resid = t - ratio[None] * t[:, :, :1]
    ratio_half = scale * resid.std(axis=0, ddof=1) / (np.sqrt(b) * t[:, :, :1].mean(axis=0))
    return {"seen": seen, "total": total, "total_half": total_half, "ratio": ratio,
            "ratio_half": ratio_half}


#: each Q1 aggregate as (column of Q1_TOTALS, "total" or "ratio")
Q1_FROM = {
    "sum_qty": (1, "total"), "sum_base_price": (2, "total"), "sum_disc_price": (3, "total"),
    "sum_charge": (4, "total"), "avg_qty": (1, "ratio"), "avg_price": (2, "ratio"),
    "avg_disc": (5, "ratio"), "count_order": (0, "total"),
}
Q6_FROM = {"revenue": (1, "total")}
