"""The benchmark's own TPC-H ``lineitem`` generator, drawn on the device
from a seed.

The first ``num_records`` rows of ``LINEITEM`` at ``scale_factor``, as
Clause 4.2.3 of the TPC-H Standard Specification generates it, in
``l_orderkey`` order as dbgen writes it, as ``[n, 16]`` int32 codes under
the configuration's ``columns``: keys and integers as they are, decimals in
hundredths, dates in days since 1970-01-01, the 1-character and
short-string columns as dictionary codes, ``l_comment`` as a 32-bit id of
its text.  Orders draw 1-7 lines; keys are sparse (the first 8 of every 32);
order dates are uniform over 1992-01-01 .. 1998-12-31 less 151 days; a line
ships 1-121 days after its order, commits 30-90 days after it, is received
1-30 days after shipping; quantity 1-50, discount 0.00-0.10, tax
0.00-0.08; price is quantity times the part's retail price; the return
flag is "N" after CURRENTDATE (1995-06-17), else "A" or "R" at random; the
line status "O" for lines shipped after it, else "F".

One jitted call draws the table and one copy brings it to the host, so
set-up pays seconds of generation, not minutes of ``numpy.random``.  The
same seed gives the same table on every run and every device of one kind;
it does not reproduce ``repro.data.tpch`` draw for draw.
"""

from __future__ import annotations

import datetime
import functools

import jax
import jax.numpy as jnp
import numpy as np

from yardstick.corpus import key_from_seed

EPOCH = datetime.date(1970, 1, 1)


def day(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


START, CURRENT, END = day("1992-01-01"), day("1995-06-17"), day("1998-12-31")
NAMES = ("l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity",
         "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus",
         "l_shipdate", "l_commitdate", "l_receiptdate", "l_shipinstruct", "l_shipmode",
         "l_comment")
C = {n: i for i, n in enumerate(NAMES)}


def orders_for(num_records: int) -> int:
    """Orders enough that their 1-7 lines (4 on average, 2 the spread of
    one order) cover ``num_records`` lines by a wide margin."""
    return num_records // 4 + num_records // 50 + 1024


@functools.partial(jax.jit, static_argnames=("num_records", "scale_factor"))
def _draw(key, *, num_records, scale_factor):
    n = num_records
    orders = orders_for(n)
    ks = jax.random.split(key, 14)

    def ints(k, lo, hi, size=n):
        return jax.random.randint(ks[k], (size,), lo, hi, jnp.int32)

    lines = ints(0, 1, 8, orders)
    orderdate = ints(1, START, END - 151 + 1, orders)
    order = jnp.repeat(jnp.arange(orders, dtype=jnp.int32), lines, total_repeat_length=n)
    first = jnp.cumsum(lines) - lines
    linenumber = jnp.arange(n, dtype=jnp.int32) - first[order] + 1
    orderkey = (order // 8) * 32 + order % 8 + 1
    part = ints(2, 1, scale_factor * 200_000 + 1)
    s = scale_factor * 10_000
    supp = (part + ints(3, 0, 4) * (s // 4 + (part - 1) // s)) % s + 1
    qty = ints(4, 1, 51)
    retail = 90_000 + (part // 10) % 20_001 + 100 * (part % 1000)       # cents
    od = orderdate[order]
    ship = od + ints(5, 1, 122)
    commit = od + ints(6, 30, 91)
    receipt = ship + ints(7, 1, 31)
    a_or_r = ints(8, 0, 2) * 2                                          # "A" or "R"
    flag = jnp.where(receipt <= CURRENT, a_or_r, 1)                     # "N"
    status = (ship > CURRENT).astype(jnp.int32)                         # "O", else "F"
    comment = jax.random.bits(ks[9], (n,), jnp.uint32).view(jnp.int32)
    cols = (orderkey, part, supp, linenumber, qty, qty * retail, ints(10, 0, 11),
            ints(11, 0, 9), flag, status, ship, commit, receipt, ints(12, 0, 4),
            ints(13, 0, 7), comment)
    return jnp.stack(cols, axis=1), lines.sum()


def make_lineitem(cfg: dict, seed: int) -> np.ndarray:
    """``[num_records, 16]`` int32 in ``l_orderkey`` order."""
    table, drawn = _draw(key_from_seed(seed), num_records=int(cfg["num_records"]),
                         scale_factor=int(cfg["scale_factor"]))
    if int(drawn) < int(cfg["num_records"]):
        raise RuntimeError(f"drew {int(drawn)} lines for {cfg['num_records']} records")
    return np.asarray(table)
