"""Closed-loop analyst queries against one stored RSP, through ``ds.serve``.

Set-up makes the corpus from the seed, partitions it into a store with
``rsp.partition(..., out=)``, reopens it with ``rsp.open`` (engine settings
from the mix's ``store``), opens the service (``service``), and sends every
query shape and predicate value of the mix once, so that every plan compiles
and every tuner shape is measured before the window.

In the window, ``clients`` threads each send a query, wait for its answer
and send the next.  Queries come from one shared sequence: periods of the
mix's ``shapes`` (each with its ``count`` per period, filter values taken in
turn), every period shuffled from the seed, so every seed sends the same
shares of the same shapes in another order.  When the window closes, no
client sends again; answers still due are awaited (up to ``late_s``).

``queries_per_s`` counts the answers returned inside the window, over the
window; ``query_p50_ms`` and ``query_p95_ms`` are over every query sent in
it, from send to answer, across all shapes.  Then every answer is compared
with ``yardstick.reference``, and the store is deleted.
"""

from __future__ import annotations

import os
import shutil
import threading
import time

import numpy as np

from yardstick.corpus import make_corpus
from yardstick.reference import QueryReference, compare, load_blocks, partition_gap, row_hashes
from yardstick.roofline import fold_bytes

LATE_S = 60.0
DONE = ("sketch", "converged", "exhausted")


def schedule(traffic: dict, seed: int):
    """The endless query sequence: seed-shuffled periods of the mix, with
    the filter values of the ``filtered`` shape taken in turn."""
    rng = np.random.default_rng([seed, 0x51])
    period = [s for s in traffic["shapes"] for _ in range(s["count"])]
    turn = 0
    while True:
        for i in rng.permutation(len(period)):
            shape = period[i]
            values = shape.get("where_values")
            if values:
                shape = dict(shape, v=values[turn % len(values)])
                turn += 1
            yield shape


def submit_args(shape: dict, confidence: float, warm: bool = False):
    """``svc.submit`` arguments of one query; ``warm`` shortens progressive
    queries to two blocks (same programs, same tuner shapes)."""
    from repro.rsp import Aggregate

    kind = shape["kind"]
    if kind == "sketch":
        return list(shape["aggregates"]), {}
    kw = dict(confidence=confidence)
    if kind == "filtered":
        aggs = list(shape["aggregates"])
        kw.update(where=f"c{shape['where_column']} > {shape['v']!r}",
                  max_blocks=shape["max_blocks"])
    elif kind == "grouped":
        aggs = Aggregate("quantile", q=shape["q"], by_label=True)
        kw.update(max_blocks=shape["max_blocks"])
    elif kind == "quantile":
        aggs = f"p{round(shape['q'] * 100)}"
        kw.update(target_rel_err=shape["target_rel_err"], min_blocks=shape["min_blocks"],
                  use_sketches=False)
    else:
        raise ValueError(f"unknown query shape {kind!r}")
    if warm:
        kw.update(max_blocks=2, min_blocks=2)
    return aggs, kw


def answer_record(shape: dict, res, sent: float) -> dict:
    """What the reference needs of one answer, as plain arrays; and when
    (host ``perf_counter``) each of its blocks was folded, from its
    convergence trace."""
    kind = shape["kind"]
    a = {"kind": kind, "blocks": res.blocks_read}
    if kind == "sketch":
        a.update(mean=np.asarray(res["mean"].estimate, np.float64),
                 var=np.asarray(res["var"].estimate, np.float64),
                 count=float(res["count"].estimate))
        return a
    steps = res.trace.steps if res.trace is not None else []
    a["ids"] = [int(s.block_id) for s in steps[: res.blocks_read]]
    a["step_at"] = [sent + s.elapsed_s for s in steps[: res.blocks_read]]
    if len(a["ids"]) != res.blocks_read:
        raise RuntimeError(f"answer reports {res.blocks_read} blocks, trace has {len(steps)}")
    if kind == "filtered":
        m = res["mean"]
        a.update(v=shape["v"], mean=np.asarray(m.estimate, np.float64),
                 lo=np.asarray(m.ci_lo, np.float64), hi=np.asarray(m.ci_hi, np.float64),
                 sel=float(res.selectivity), p50=np.asarray(res["p50"].estimate, np.float64))
    else:
        a.update(q=shape["q"], est=np.asarray(res.aggregates[0].estimate, np.float64))
    return a


def run(run) -> dict:
    from repro import rsp

    cfg, tr = run.cfg, run.traffic
    corpus = make_corpus(cfg, run.seed)
    path = os.path.join(run.work_dir, "store")
    shutil.rmtree(path, ignore_errors=True)
    ds = rsp.partition(corpus, blocks=cfg["blocks"], num_classes=cfg["num_classes"],
                       seed=run.seed, out=path)
    run.log(f"partition backend {ds.backend}: {ds.num_blocks} blocks x {ds.block_size} records")
    ds.close()
    del ds
    ds = rsp.open(path, **tr["store"])
    svc = ds.serve(seed=run.seed, **tr["service"])
    confidence = tr["confidence"]
    warm = []
    for shape in tr["shapes"]:
        for v in shape.get("where_values", [None]):
            warm.append(dict(shape, v=v) if v is not None else shape)
    for shape in warm:
        aggs, kw = submit_args(shape, confidence, warm=True)
        svc.result(svc.submit(aggs, **kw))
    run.setup_done()

    queries = schedule(tr, run.seed)
    lock = threading.Lock()
    stop = threading.Event()
    records: list[tuple] = []

    def client() -> None:
        while not stop.is_set():
            with lock:
                shape = next(queries)
            aggs, kw = submit_args(shape, confidence)
            sent = time.perf_counter()
            try:
                ticket = svc.submit(aggs, **kw)
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
    for q in (50, 90, 95):
        end_to_end[f"query_p{q}_ms"] = (float(np.percentile(lat_ms, q)) if len(lat_ms)
                                        else float("inf"))
    run.log(f"answers {len(ok)} ({len(in_window)} in the window), sent {len(sent)}, "
            f"failed {failed}; " + ", ".join(f"{k} {v:.6g}" for k, v in end_to_end.items()))

    blocks = load_blocks(path, cfg["blocks"])
    K, n, F = blocks.shape
    facts = {"block_rows": n, "columns": F, "block_bytes": fold_bytes(n, F, blocks.itemsize)}
    filt = [s for s in tr["shapes"] if s["kind"] == "filtered"]
    where_column = filt[0]["where_column"] if filt else 0
    values = filt[0]["where_values"] if filt else []
    kw = dict(where_column=where_column, values=values, num_classes=cfg["num_classes"],
              confidence=confidence, bins=tr["bins"])
    checks = {"store_rows_gap": partition_gap(blocks, row_hashes(corpus))}
    ref = QueryReference(corpus, blocks, **kw)
    subject = answers
    if run.control is not None:
        ctrl = QueryReference(corpus, blocks, precision=run.control, **kw)
        subject = [ctrl.answer(a) for a in answers]
    checks.update(compare(subject, ref))
    shutil.rmtree(path, ignore_errors=True)
    return {"end_to_end": end_to_end, "attempted": len(sent), "failed": failed,
            "checks": checks, "answers": answers, "facts": facts}
