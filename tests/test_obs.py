"""``repro.obs`` tests: metrics registry semantics, tracer sampling and
Chrome export, convergence traces, cross-thread trace-context propagation
through engine/query/serve (including the deadline sweeper), and the
``QueryService.metrics()`` <-> registry reconciliation."""

import json
import math
import threading

import numpy as np
import pytest

from repro import obs, rsp
from repro.obs.convergence import ConvergenceStep, ConvergenceTrace
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import DROPPED, Tracer
from repro.rsp.engine import BlockExecutor, MemoryFetcher


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with telemetry off and empty."""
    obs.reset()
    yield
    obs.reset()


def _data(blocks=16, n=512, f=3, seed=0):
    rng = np.random.default_rng(seed)
    return rng.lognormal(0.0, 1.0, size=(blocks * n, f)).astype(np.float32)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", route="a")
    c.inc()
    c.inc(2)
    assert c.value == 3
    assert reg.counter("c_total", route="a") is c  # stable handle
    assert reg.counter("c_total", route="b").value == 0  # sibling label set
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(5)
    g.add(-2)
    assert g.value == 3


def test_kind_mismatch_rejected():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_histogram_buckets_and_quantile():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", start=1e-3, factor=2.0, buckets=10)
    for v in [0.001, 0.002, 0.004, 0.1]:
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(0.107)
    assert h.mean == pytest.approx(0.107 / 4)
    assert h.quantile(0.5) <= h.quantile(1.0)
    snap = h.snapshot()
    assert sum(snap["buckets"].values()) == 4
    assert math.inf in snap["buckets"]  # overflow bucket always present


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("hits_total", "cache hits", kind="memory").inc(3)
    h = reg.histogram("fetch_seconds", "latency", start=1e-3, buckets=4)
    h.observe(0.002)
    h.observe(100.0)  # overflow
    text = reg.to_prometheus()
    assert '# TYPE hits_total counter' in text
    assert 'hits_total{kind="memory"} 3.0' in text
    assert '# TYPE fetch_seconds histogram' in text
    assert 'le="+Inf"' in text
    assert "fetch_seconds_count 2" in text
    # buckets are cumulative: the +Inf series equals the count
    inf_line = [ln for ln in text.splitlines() if 'le="+Inf"' in ln][0]
    assert inf_line.endswith(" 2")


def test_registry_json_roundtrips():
    reg = MetricsRegistry()
    reg.counter("a_total").inc()
    reg.histogram("b_seconds").observe(0.5)
    parsed = json.loads(reg.to_json())
    assert parsed["a_total"]["series"][0]["value"] == 1.0
    assert parsed["b_seconds"]["kind"] == "histogram"


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_span_parenting_and_chrome_events():
    tr = Tracer()
    root = tr.start_span("root", attrs={"q": 1})
    child = tr.start_span("child", parent=root.ctx)
    child.end()
    child.end()  # idempotent: must not double-record
    root.end()
    assert len(tr) == 2
    events = tr.chrome_events()
    xs = [e for e in events if e["ph"] == "X"]
    by_name = {e["name"]: e for e in xs}
    assert by_name["child"]["args"]["trace_id"] == by_name["root"]["args"]["trace_id"]
    assert by_name["child"]["args"]["parent_id"] == by_name["root"]["args"]["span_id"]
    assert by_name["root"]["args"]["q"] == 1
    assert all(e["dur"] >= 1 for e in xs)
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)


def test_unsampled_root_suppresses_children():
    tr = Tracer(sample_rate=0.0)
    root = tr.start_span("root")
    child = tr.start_span("child", parent=root.ctx)
    assert root.ctx is DROPPED and child.ctx is DROPPED
    root.end()
    child.end()
    assert len(tr) == 0


def test_bounded_buffer_counts_drops():
    tr = Tracer(max_events=4)
    for i in range(6):
        tr.start_span(f"s{i}").end()
    assert len(tr) == 4
    assert tr.dropped == 2


def test_export_chrome_is_loadable(tmp_path):
    tr = Tracer()
    tr.start_span("op", attrs={"k": "v"}).end()
    path = tmp_path / "trace.json"
    n = tr.export_chrome(path)
    payload = json.loads(path.read_text())
    assert len(payload["traceEvents"]) == n
    assert any(e["name"] == "op" for e in payload["traceEvents"])


# ---------------------------------------------------------------------------
# Convergence traces
# ---------------------------------------------------------------------------

def test_convergence_trace_report_and_dict():
    trace = ConvergenceTrace(confidence=0.95, target_rel_err=0.05)
    for b, err in [(1, 0.5), (2, 0.1), (3, 0.04)]:
        trace.record(ConvergenceStep(
            blocks_read=b, block_id=b - 1, max_rel_err=err,
            estimates={"mean": 1.0}, half_widths={"mean": err},
            cum_fetch_s=0.01 * b, elapsed_s=0.02 * b,
        ))
    assert len(trace) == 3
    assert trace.blocks == [1, 2, 3]
    assert trace.half_widths("mean") == [0.5, 0.1, 0.04]
    d = trace.to_dict()
    assert d["steps"][2]["max_rel_err"] == 0.04
    rep = trace.report()
    assert "3 steps" in rep and "<- target met" in rep


# ---------------------------------------------------------------------------
# Global toggle
# ---------------------------------------------------------------------------

def test_disabled_by_default_and_hot_paths_stay_silent():
    assert not obs.enabled()
    ds = rsp.partition(_data(blocks=8), blocks=8, seed=0)
    ds.query("median", target_rel_err=0.2, use_sketches=False, seed=1)
    ds.close()
    assert obs.get_registry().snapshot() == {}
    assert len(obs.get_tracer()) == 0


def test_env_init(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "on")
    monkeypatch.setenv("REPRO_OBS_SAMPLE", "0.25")
    obs._init_from_env()
    assert obs.enabled()
    assert obs.get_tracer().sample_rate == 0.25


# ---------------------------------------------------------------------------
# Trace-context propagation (tentpole wiring)
# ---------------------------------------------------------------------------

def test_engine_fetch_metrics_by_outcome():
    obs.enable()
    blocks = np.random.default_rng(0).normal(size=(4, 32, 3)).astype(np.float32)
    with BlockExecutor(MemoryFetcher(blocks), prefetch=0, cache_blocks=4) as ex:
        ex.fetch(0)  # miss
        ex.fetch(0)  # hit
        ex.fetch(1)  # miss
    snap = obs.get_registry().snapshot()
    series = {
        dict(s["labels"])["outcome"]: s["value"]
        for s in snap["rsp_engine_fetch_total"]["series"]
    }
    assert series == {"hit": 1.0, "miss": 2.0}
    assert snap["rsp_engine_rows_fetched_total"]["series"][0]["value"] == 64.0


def test_query_spans_propagate_to_engine_workers():
    obs.enable()
    ds = rsp.partition(_data(blocks=16), blocks=16, seed=0)
    res = ds.query("median", target_rel_err=0.02, use_sketches=False, seed=1)
    ds.close()
    assert res.blocks_read > 0
    xs = [e for e in obs.get_tracer().chrome_events() if e["ph"] == "X"]
    roots = [e for e in xs if e["name"] == "query"]
    fetches = [e for e in xs if e["name"] == "engine.fetch"]
    assert len(roots) == 1 and fetches
    root = roots[0]
    assert all(f["args"]["trace_id"] == root["args"]["trace_id"] for f in fetches)
    assert all(f["args"]["parent_id"] == root["args"]["span_id"] for f in fetches)
    # the dataset executor prefetches: fetch spans run on pool threads
    assert any(f["tid"] != root["tid"] for f in fetches)


class _GatedFetcher:
    """MemoryFetcher whose reads wait for ``gate``: the serve workers stay
    inside their step until the test opens it, so only the deadline
    sweeper can finalize a ticket meanwhile."""

    def __init__(self, blocks, gate: threading.Event):
        self._inner = MemoryFetcher(blocks)
        self._gate = gate

    @property
    def num_blocks(self) -> int:
        return self._inner.num_blocks

    def fetch(self, block_id: int):
        self._gate.wait()
        return self._inner.fetch(block_id)


def test_deadline_sweeper_span_parents_under_query():
    obs.enable()
    ds = rsp.partition(_data(blocks=32), blocks=32, seed=0)
    gate = threading.Event()
    ds._executor = BlockExecutor(
        _GatedFetcher(ds._blocks, gate), prefetch=2, cache_blocks=64
    )
    with ds.serve(workers=2, seed=0) as svc:
        t = svc.submit(
            "median", target_rel_err=1e-9, use_sketches=False, deadline_ms=50
        )
        # wait on the ticket (NOT svc.result): only the sweeper thread can
        # finalize it, which is exactly the cross-thread hop under test
        try:
            assert t.wait(30.0)
            assert t.outcome == "deadline"
        finally:
            gate.set()  # only now may the worker's step read its block
    ds.close()
    xs = [e for e in obs.get_tracer().chrome_events() if e["ph"] == "X"]
    roots = [e for e in xs if e["name"] == "query"]
    deadlines = [e for e in xs if e["name"] == "serve.deadline"]
    assert len(roots) == 1 and len(deadlines) == 1
    root, dl = roots[0], deadlines[0]
    assert dl["args"]["trace_id"] == root["args"]["trace_id"]
    assert dl["args"]["parent_id"] == root["args"]["span_id"]
    assert dl["tid"] != root["tid"]  # recorded from the sweeper thread


def test_mixed_serve_workload_trace_is_well_formed(tmp_path):
    obs.enable()
    ds = rsp.partition(_data(blocks=32, n=256), blocks=32, seed=0)
    tickets: list = []
    with ds.serve(capacity=64, workers=8, seed=1) as svc:
        def tenant(i: int) -> None:
            for j in range(2):
                if (i + j) % 3 == 0:
                    tickets.append(svc.submit("mean"))  # sketch fast path
                else:
                    tickets.append(svc.submit(
                        "median", target_rel_err=0.05, use_sketches=False,
                        deadline_ms=5000,
                    ))

        submitters = [threading.Thread(target=tenant, args=(i,)) for i in range(12)]
        for th in submitters:
            th.start()
        for th in submitters:
            th.join()
        for t in list(tickets):
            t.wait(30.0)
    ds.close()

    path = tmp_path / "trace.json"
    n = obs.get_tracer().export_chrome(path)
    payload = json.loads(path.read_text())
    events = payload["traceEvents"]
    assert len(events) == n
    xs = [e for e in events if e["ph"] == "X"]
    for e in xs:  # every span event fully formed
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int) and e["dur"] >= 1
        assert {"trace_id", "span_id"} <= e["args"].keys()
    root_traces = {e["args"]["trace_id"] for e in xs if e["name"] == "query"}
    children = [e for e in xs if "parent_id" in e["args"]]
    assert children
    assert all(c["args"]["trace_id"] in root_traces for c in children)
    assert len({e["tid"] for e in xs}) >= 3  # submitters, workers, engine pool


# ---------------------------------------------------------------------------
# Profiler sink: spans in the JAX profiler's trace
# ---------------------------------------------------------------------------

#: Every span the program opens at a layer boundary of its timed paths.
SPANS = (
    "serve.step", "engine.wait", "engine.fetch", "query.fold", "kernel.h2d",
    "kernel.readback", "query.ci", "partition.shuffle", "partition.sketch",
    "sketch.block", "store.write", "store.block", "store.sketch", "query.setup",
    "shuffle.block",
)


def _profiled(log_dir, fn):
    """Run ``fn`` inside a profiler session; return its result and the
    host events of the trace as ``(line, name, start_ns, end_ns, stats)``,
    ``line`` naming one thread."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(log_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                events.append(((p, i), e.name, e.start_ns, e.start_ns + e.duration_ns,
                               dict(e.stats)))
    return out, events


def test_spans_reach_the_profiler_trace(tmp_path):
    # metrics on and every trace dropped, as a traced benchmark run has it:
    # the profiler sink does not depend on the tracer's sampling
    obs.enable(sample_rate=0.0)
    data = _data(blocks=8, n=64, f=3)
    f = data.shape[1]

    def work():
        ds = rsp.partition(data, blocks=8, backend="pallas", seed=0,
                           out=str(tmp_path / "store"))
        with ds.serve(workers=2, seed=0) as svc:
            tickets = [
                svc.submit("median", use_sketches=False, max_blocks=3, sketch_impl="jax")
                for _ in range(2)
            ]
            tickets.append(svc.submit("mean", where="c0 > 1.0", max_blocks=2,
                                      sketch_impl="jax"))
            for t in tickets:
                assert t.wait(60.0) and t.result is not None
        ds.close()
        return ds, tickets

    h2d = obs.get_registry().counter("rsp_h2d_bytes_total", kernel="plan")
    (ds, tickets), events = _profiled(tmp_path / "trace", work)
    names = {e[1] for e in events}
    assert set(SPANS) <= names, sorted(set(SPANS) - names)

    # the pool's fetches run on engine worker threads, never on a step's
    steps = [e for e in events if e[1] == "serve.step"]
    fetch_lines = {e[0] for e in events if e[1] == "engine.fetch"}
    assert fetch_lines and not fetch_lines & {e[0] for e in steps}

    # a query's set-up, each block's fold and interval update nest inside a
    # step, on its thread
    for e in events:
        if e[1] in ("query.setup", "query.fold", "query.ci"):
            assert any(s[0] == e[0] and s[2] <= e[2] and e[3] <= s[3] for s in steps), e

    # attributes are the events' stats; the copy names its kernel and bytes
    block_bytes = ds.block_size * f * 4
    copies = [e for e in events if e[1] == "kernel.h2d" and e[4].get("kernel") == "plan"]
    assert copies and all(e[4]["bytes"] == block_bytes for e in copies)

    # bytes counted: one block per block folded by a device impl
    reg = obs.get_registry()
    folded = sum(t.result.blocks_read for t in tickets)
    assert h2d.value > 0 and h2d.value == folded * block_bytes
    # the partition copied each original block to the device once
    assert reg.counter("rsp_h2d_bytes_total", kernel="rsp_shuffle").value == data.nbytes
    assert len(obs.get_tracer()) == 0  # the tracer sampled nothing


def test_span_off_path_builds_no_annotation(monkeypatch, tmp_path):
    from repro.obs import trace as obs_trace

    built = []

    class Counting(obs_trace.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Counting)
    assert not obs.enabled()
    ds = rsp.partition(_data(blocks=8, n=64), blocks=8, backend="pallas", seed=0,
                       out=str(tmp_path / "store"))
    with ds.serve(workers=2, seed=0) as svc:
        t = svc.submit("median", use_sketches=False, max_blocks=2, sketch_impl="jax")
        assert t.wait(60.0)
    ds.close()
    assert built == []
    assert obs.span("x") is obs.span("y")  # one shared no-op
    # the same call builds one once a session collects

    def probe():
        with obs.span("probe", k=1):
            pass

    _, events = _profiled(tmp_path / "trace", probe)
    assert built == ["probe"]
    assert any(e[1] == "probe" and e[4] == {"k": 1} for e in events)


# ---------------------------------------------------------------------------
# Convergence traces on live queries
# ---------------------------------------------------------------------------

def test_explain_records_per_block_trace():
    ds = rsp.partition(_data(blocks=16), blocks=16, seed=0)
    # 4% target: the KLL-seeded bootstrap grid resolves the quantile CI
    # honestly (no coarse-bin smoothing), which sits just above 3% here
    res = ds.query("median", target_rel_err=0.04, use_sketches=False,
                   seed=2, explain=True)
    ds.close()
    trace = res.trace
    assert trace is not None and len(trace) == res.blocks_read
    assert trace.blocks == list(range(1, res.blocks_read + 1))
    last = trace.steps[-1]
    r = res.aggregates[0]
    half = (np.asarray(r.ci_hi, float) - np.asarray(r.ci_lo, float)) / 2.0
    want = float(np.nanmax(half)) if np.any(~np.isnan(half)) else math.nan
    assert last.half_widths[r.name] == pytest.approx(want, rel=1e-12)
    assert last.max_rel_err <= 0.04  # it converged and the trace shows it
    assert "<- target met" in trace.report()


def test_sketch_answer_has_zero_block_trace():
    ds = rsp.partition(_data(blocks=8), blocks=8, seed=0)
    res = ds.query("mean", explain=True)
    ds.close()
    assert res.from_sketches
    assert res.trace is not None and len(res.trace) == 1
    step = res.trace.steps[0]
    assert step.blocks_read == 0 and step.cum_fetch_s == 0.0


# ---------------------------------------------------------------------------
# QueryService.metrics() as a registry view (satellite 3)
# ---------------------------------------------------------------------------

def test_service_metrics_reconcile_with_registry_and_callers():
    ds = rsp.partition(_data(blocks=16), blocks=16, seed=0)
    # prefetch=0: fetches run inline during steps, so per-caller counts are
    # settled the instant a ticket finalizes -- exact reconciliation below
    ds._executor = BlockExecutor(
        MemoryFetcher(ds._blocks), prefetch=0, cache_blocks=32
    )
    with ds.serve(capacity=2, max_queue=0, workers=2, seed=0) as svc:
        sketch = [svc.submit("mean") for _ in range(3)]
        prog, rejected = [], []
        for _ in range(6):
            t = svc.submit("median", target_rel_err=0.05, use_sketches=False,
                           on_reject="ticket")
            (rejected if t.outcome == "rejected" else prog).append(t)
        for t in sketch + prog:
            t.wait(30.0)
        m = svc.metrics()
        snap = svc.registry.snapshot()

    submitted = snap["rsp_serve_submitted_total"]["series"][0]["value"]
    outcomes = {
        dict(s["labels"])["outcome"]: s["value"]
        for s in snap["rsp_serve_queries_total"]["series"]
    }
    assert m.submitted == submitted == 3 + len(prog) + len(rejected)
    assert sum(outcomes.values()) == m.submitted  # every ticket is terminal
    assert m.rejected == len(rejected)
    assert m.sketch_answers == outcomes.get("sketch", 0) == 3
    assert m.completed == m.submitted - m.rejected

    # blocks: the registry counter, metrics(), and the per-caller stats on
    # the tickets' own results are the same number -- one book of record
    blocks_counter = snap["rsp_serve_blocks_fetched_total"]["series"][0]["value"]
    per_caller = sum(
        t.result.executor_stats.blocks_fetched
        for t in sketch + prog
        if t.result is not None
    )
    assert m.blocks_fetched == blocks_counter == per_caller

    prom = svc.registry.to_prometheus()
    assert "rsp_serve_submitted_total" in prom
    assert 'rsp_serve_queries_total{outcome="sketch"} 3.0' in prom
    ds.close()
