"""Closed-loop TPC-H Q1 and Q6 against one stored typed RSP of ``lineitem``,
through ``ds.serve``.

Set-up first checks that the program has typed tables (a column schema,
typed plans, ``Query(group_by=)``) and stops at once, with a message, where
it does not.  It then draws the table from the seed on the device
(``yardstick.lineitem``, stored in ``l_orderkey`` order), partitions it into
a store with ``rsp.partition(..., out=, schema=)``, reopens it with
``rsp.open`` (engine settings from the mix's ``store``), opens the service
(``service``), and sends each query of the mix (every DELTA of Q1, every
DATE of Q6) once, so that every plan compiles and every tuner shape is
measured before the window.

In the window, ``clients`` threads each send a query, wait for its answer
and send the next.  Queries come from one shared sequence: periods of the
mix's ``shapes`` (each with its ``count`` per period; Q1's DELTA and Q6's
DATE taken in turn), every period shuffled from the seed.  Every query
holds each sum and count to ``stopping``'s ``target_rel_err``, between its
``min_blocks`` and ``max_blocks``.

``queries_per_s`` counts the answers returned inside the window, over the
window; ``query_p50_ms`` / ``query_p95_ms`` are over every query sent in it.
Then every answer is compared with ``yardstick.tpch_reference``, and the
store is deleted.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import threading
import time

import numpy as np

from drivers.closed_loop import DONE, LATE_S
from yardstick import tpch_reference, typed_fold
from yardstick.lineitem import C, make_lineitem
from yardstick.reference import load_blocks, partition_gap, row_hashes

CONTROLS = ("bf16", "chunked")
Q1_AGGREGATES = (
    ("sum_qty", "sum", "l_quantity"),
    ("sum_base_price", "sum", "l_extendedprice"),
    ("sum_disc_price", "sum", "l_extendedprice * (1 - l_discount)"),
    ("sum_charge", "sum", "l_extendedprice * (1 - l_discount) * (1 + l_tax)"),
    ("avg_qty", "avg", "l_quantity"),
    ("avg_price", "avg", "l_extendedprice"),
    ("avg_disc", "avg", "l_discount"),
    ("count_order", "count", None),
)
Q6_AGGREGATES = (("revenue", "sum", "l_extendedprice * l_discount"),)


def check_program() -> None:
    """Stops the run where the program has no typed tables."""
    try:
        from repro.core.schema import Schema  # noqa: F401
        from repro.kernels.plan import plan_totals  # noqa: F401
        from repro.rsp import Aggregate, Query

        fields = {f.name for f in dataclasses.fields(Query)}
        if "group_by" not in fields or "expr" not in {f.name for f in dataclasses.fields(Aggregate)}:
            raise ImportError("Query has no group_by, or Aggregate no expr")
    except ImportError as e:
        raise SystemExit(f"the program has no typed tables (column schema, typed plans): {e}")


def schedule(traffic: dict, seed: int):
    """The endless query sequence: seed-shuffled periods of the mix, each
    shape's parameter taken in turn."""
    rng = np.random.default_rng([seed, 0x7C4])
    period = [s for s in traffic["shapes"] for _ in range(s["count"])]
    turn = {s["kind"]: 0 for s in traffic["shapes"]}
    while True:
        for i in rng.permutation(len(period)):
            shape = period[i]
            values = shape["deltas"] if shape["kind"] == "q1" else shape["dates"]
            yield dict(shape, param=values[turn[shape["kind"]] % len(values)])
            turn[shape["kind"]] += 1


def day_before(days: int) -> str:
    import datetime

    return (datetime.date(1998, 12, 1) - datetime.timedelta(days=days)).isoformat()


def make_query(shape: dict, traffic: dict, warm: bool = False):
    """The ``Query`` of one shape; ``warm`` reads two blocks (same plan,
    same tuner shape)."""
    import datetime

    from repro.rsp import Aggregate, Query

    stop = dict(traffic["stopping"])
    if warm:
        stop.update(min_blocks=2, max_blocks=2)
    if shape["kind"] == "q1":
        aggs, group_by = Q1_AGGREGATES, ("l_returnflag", "l_linestatus")
        where = (f"l_shipdate <= date '{day_before(shape['param'])}'",)
    else:
        aggs, group_by = Q6_AGGREGATES, ()
        start = datetime.date.fromisoformat(shape["param"])
        d = shape["discount"]
        where = (f"l_shipdate >= date '{start.isoformat()}'",
                 f"l_shipdate < date '{start.replace(year=start.year + 1).isoformat()}'",
                 f"l_discount >= {d - 0.01:.2f}", f"l_discount <= {d + 0.01:.2f}",
                 f"l_quantity < {shape['quantity']}")
    return Query(tuple(Aggregate(kind, expr=e, name=n) for n, kind, e in aggs), where=where,
                 group_by=group_by, confidence=traffic["confidence"], **stop)


def answer_record(shape: dict, res, sent: float) -> dict:
    """What the reference needs of one answer, as plain arrays."""
    steps = res.trace.steps if res.trace is not None else []
    a = {"kind": shape["kind"], "param": shape["param"], "blocks": res.blocks_read,
         "ids": [int(s.block_id) for s in steps[: res.blocks_read]],
         "step_at": [sent + s.elapsed_s for s in steps[: res.blocks_read]],
         "groups": None if res.groups is None else [list(g) for g in res.groups],
         "est": {}, "half": {}}
    if len(a["ids"]) != res.blocks_read:
        raise RuntimeError(f"answer reports {res.blocks_read} blocks, trace has {len(steps)}")
    for r in res.aggregates:
        a["est"][r.name] = np.atleast_1d(np.asarray(r.estimate, np.float64))
        a["half"][r.name] = (np.atleast_1d(np.asarray(r.ci_hi, np.float64))
                             - np.atleast_1d(np.asarray(r.ci_lo, np.float64))) / 2.0
    return a


def run(run) -> dict:
    check_program()
    from repro import rsp

    cfg, tr = run.cfg, run.traffic
    if run.control is not None and run.control not in CONTROLS:
        raise SystemExit(f"unknown control {run.control!r} (one of {CONTROLS})")
    table = make_lineitem(cfg, run.seed)
    path = os.path.join(run.work_dir, "store")
    shutil.rmtree(path, ignore_errors=True)
    ds = rsp.partition(table, blocks=cfg["blocks"], original_blocks=cfg["original_blocks"],
                       seed=run.seed, out=path, schema=cfg["columns"])
    run.log(f"partition backend {ds.backend}: {ds.num_blocks} blocks x {ds.block_size} records")
    ds.close()
    del ds
    ds = rsp.open(path, **tr["store"])
    svc = ds.serve(seed=run.seed, **tr["service"])
    for shape in tr["shapes"]:
        for v in shape["deltas"] if shape["kind"] == "q1" else shape["dates"]:
            svc.result(svc.submit(make_query(dict(shape, param=v), tr, warm=True)))
    run.setup_done()

    queries = schedule(tr, run.seed)
    lock = threading.Lock()
    stop = threading.Event()
    records: list[tuple] = []

    def client() -> None:
        while not stop.is_set():
            with lock:
                shape = next(queries)
            q = make_query(shape, tr)
            sent = time.perf_counter()
            try:
                ticket = svc.submit(q)
                res = svc.result(ticket, timeout=LATE_S + run.seconds)
                outcome = ticket.outcome
            except Exception as e:  # noqa: BLE001 -- a failed query is counted, not fatal
                res, outcome = None, f"error: {e!r}"
            got = time.perf_counter()
            with lock:
                records.append((shape, sent, got, outcome, res))

    run.watch("service", svc.registry)
    threads = [threading.Thread(target=client, daemon=True) for _ in range(tr["clients"])]
    begin = run.window_begin()
    for t in threads:
        t.start()
    time.sleep(max(0.0, begin + run.seconds - time.perf_counter()))
    stop.set()
    close = run.window_end()
    deadline = close + LATE_S
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.perf_counter()))
    never = sum(t.is_alive() for t in threads)
    with lock:
        done = list(records)
    svc.close()
    ds.close()
    del svc, ds

    sent = [r for r in done if r[1] < close]
    lat_ms = np.array([(r[2] - r[1]) * 1e3 for r in sent])
    ok = [r for r in sent if r[3] in DONE]
    failed = len(sent) - len(ok) + never
    for r in sent:
        if r[3] not in DONE:
            run.log(f"query {r[0]['kind']} outcome {r[3]}")
    in_window = [r for r in ok if r[2] <= close]
    answers = [answer_record(r[0], r[4], r[1]) for r in ok]
    for a, r in zip(answers, ok):
        a["in_window"] = r[2] <= close
        a["ms"] = (r[2] - r[1]) * 1e3
    end_to_end = {"queries_per_s": len(in_window) / (close - begin)}
    for q in (50, 95):
        end_to_end[f"query_p{q}_ms"] = (float(np.percentile(lat_ms, q)) if len(lat_ms)
                                        else float("inf"))
    blocks_read = [a["blocks"] for a in answers]
    run.log(f"answers {len(ok)} ({len(in_window)} in the window), sent {len(sent)}, "
            f"failed {failed}; blocks per answer "
            f"{np.mean(blocks_read) if blocks_read else float('nan'):.2f}; "
            + ", ".join(f"{k} {v:.6g}" for k, v in end_to_end.items()))

    K = cfg["blocks"]
    blocks = load_blocks(path, K)
    if run.control == "chunked":
        blocks = table.reshape(blocks.shape)    # consecutive blocks in the store's place
    _, n, F = blocks.shape
    facts = {"typed": True, "block_rows": n, "columns": F,
             "block_bytes": typed_fold.fold_bytes(n, F)}
    checks = {"store_rows_gap": partition_gap(blocks, row_hashes(table)),
              "orderkey_ks_gap": tpch_reference.ks_gap(blocks, C["l_orderkey"])}
    kw = dict(confidence=tr["confidence"])
    ref = tpch_reference.TpchReference(blocks, tr, **kw)
    subject = answers
    if run.control is not None:
        ctrl = ref if run.control == "chunked" else tpch_reference.TpchReference(
            blocks, tr, precision=run.control, **kw)
        subject = [ctrl.answer(a) for a in answers]
    checks.update(tpch_reference.compare(subject, ref))
    shutil.rmtree(path, ignore_errors=True)
    return {"end_to_end": end_to_end, "attempted": len(sent), "failed": failed,
            "checks": checks, "answers": answers, "facts": facts}
