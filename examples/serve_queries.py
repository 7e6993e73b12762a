"""Multi-tenant query serving over one stored RSP dataset.

Four tenants share one ``QueryService`` (one ``BlockExecutor`` block cache):
a dashboard refreshing exact moments from the sketches, an analyst's
progressive median, a batch job capped at a block budget, and an impatient
tenant whose unreachable accuracy target is cut off by a deadline -- who
still gets an *anytime* answer (estimate + CI + blocks consumed), not an
error.  A final saturation demo shows admission control rejecting instead
of queueing forever.

    PYTHONPATH=src python examples/serve_queries.py
"""

import os
import tempfile

import numpy as np

from repro import rsp
from repro.runtime import use_compile_cache
from repro.serve import AdmissionRejected


def show(tag, ticket, res):
    a = res[res.aggregates[0].name]
    print(f"{tag:>10}: outcome={ticket.outcome:<10} blocks={res.blocks_read:<3}"
          f" latency={ticket.latency_ms:6.1f}ms  {a.name}={np.round(a.estimate, 4)}")


def main():
    use_compile_cache()
    rng = np.random.default_rng(0)
    data = rng.normal(5.0, 1.0, size=(64 * 1024, 4)).astype(np.float32)

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "corpus.rsp")
        ds = rsp.partition(data, blocks=64, seed=1)
        ds.save(path)
        ds.close()

        ds = rsp.open(path, cache_blocks=64)
        with ds.serve(capacity=16, workers=4, seed=7) as svc:
            # four tenants submit concurrently; nobody waits for anybody
            dashboard = svc.submit(["mean", "var", "count"])          # sketches
            analyst = svc.submit("median", target_rel_err=0.02,
                                 use_sketches=False, explain=True)
            batch = svc.submit("mean", max_blocks=4, use_sketches=False,
                               confidence=0.999)
            impatient = svc.submit("mean", target_rel_err=1e-12,
                                   policy="weighted", max_blocks=10**7,
                                   use_sketches=False, deadline_ms=300,
                                   explain=True)

            show("dashboard", dashboard, svc.result(dashboard))
            analyst_res = svc.result(analyst)
            show("analyst", analyst, analyst_res)
            show("batch", batch, svc.result(batch))
            res = svc.result(impatient)  # anytime answer AT the deadline
            show("impatient", impatient, res)
            a = res["mean"]
            truth = data.astype(np.float64).mean(0)
            covered = bool(np.all(a.ci_lo <= truth) & np.all(truth <= a.ci_hi))
            print(f"            anytime CI covers the full-scan mean: {covered}")

            # per-tenant convergence report: each explain=True tenant gets the
            # paper's error-vs-blocks trajectory for ITS OWN query, straight
            # off QueryResult.trace -- why did my answer stop when it did?
            for tag, r in [("analyst", analyst_res), ("impatient", res)]:
                if r.trace is not None:
                    print(f"\n[{tag}] {r.trace.report()}")

            m = svc.metrics()
            print(f"\nservice: {m.completed} completed, qps={m.qps:.0f}, "
                  f"p99={m.latency_p99_ms:.0f}ms, cache hit rate "
                  f"{m.cache_hit_rate:.2f}, blocks/query={m.blocks_per_query:.1f}")

        # saturation: capacity 1, no queue -> the second progressive query
        # is rejected up front instead of silently piling onto a busy service
        with ds.serve(capacity=1, max_queue=0, workers=1, seed=9) as svc:
            hog = svc.submit("mean", target_rel_err=1e-12, policy="weighted",
                             max_blocks=10**7, use_sketches=False)
            try:
                svc.submit("median", use_sketches=False)
            except AdmissionRejected as e:
                print(f"\nsaturated service rejected the second tenant: {e}")
            svc.cancel(hog)
        ds.close()


if __name__ == "__main__":
    main()
