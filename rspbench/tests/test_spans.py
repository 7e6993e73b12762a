"""The reductions of the program's spans and the readers built on them, on
hand-made events, and the span readers of a traced run on the CPU."""

import os

import pytest
import tiny

import harness
from yardstick import spans
from yardstick import trace as tr
from yardstick.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def host(name, start, dur, line="python3"):
    return Event(HOST, line, name, float(start), float(dur))


def op(start, dur):
    return Event(DEV, tr.OPS_LINE, "%k = f32[8] fusion(f32[8] %x)", float(start), float(dur))


def reduced(events, lo=0.0, hi=1000.0):
    """The parts of a reduced trace that the span reductions read."""
    return {"events": events, "ops": tr.device_ops(events), "lo": lo, "hi": hi}


def layer(trace, obs=None):
    return harness.Layer(obs=obs or {}, service={}, answers=[], trace=trace, facts={},
                         peaks={}, window_s=1.0)


def reader(name):
    return harness.load_module(os.path.join(tiny.BENCH, "metrics", f"{name}.py"),
                               "test_reader_" + name.replace(".", "_"))


def test_inside_leaves_out_spans_cut_by_the_window():
    ev = [host("query.fold", -50, 100), host("query.fold", 100, 20),
          host("query.fold", 300, 40), host("query.fold", 980, 40), host("query.ci", 10, 5)]
    got, mean = spans.inside(reduced(ev), "query.fold")
    assert [e.start_ns for e in got] == [100.0, 300.0]
    assert mean == pytest.approx(30e-6)  # (20 + 40) / 2 ns, in ms


def test_no_events_read_none():
    t = reduced([host("other", 10, 10)])
    assert spans.inside(t, "query.fold") == ([], None)
    for name in ("query.fold_ms", "query.ci_ms", "engine.wait_ms", "sketch.block_ms",
                 "store.block_ms", "kernel.h2d_gbps", "device.idle_named_pct.query",
                 "device.idle_named_pct.ingest"):
        assert reader(name).read(layer(t)) is None, name


def test_idle_by_span_unions_over_threads():
    # device busy [100, 200] and [600, 700]: idle 800 ns of the 1000 ns window
    ev = [op(100, 100), op(600, 100),
          # two threads in one span at once: [0, 150] and [50, 300] -> [0, 300],
          # of which [0, 100] and [200, 300] are idle
          host("query.fold", 0, 150, line="a"), host("query.fold", 50, 250, line="b"),
          # [650, 900]: [700, 900] idle; overlaps query.fold nowhere
          host("query.ci", 650, 250, line="a"),
          # nested in query.fold: adds to its own name, not to the union of all
          host("kernel.h2d", 60, 20, line="a")]
    got = spans.idle_by_span(reduced(ev), ["query.fold", "query.ci", "kernel.h2d", "absent"])
    assert got["idle_s"] == pytest.approx(800e-9)
    assert got["spans"]["query.fold"] == pytest.approx(200e-9)
    assert got["spans"]["query.ci"] == pytest.approx(200e-9)
    assert got["spans"]["kernel.h2d"] == pytest.approx(20e-9)
    assert got["spans"]["absent"] == 0.0
    assert got["any_s"] == pytest.approx(400e-9)
    assert got["none_s"] == pytest.approx(400e-9)


def test_idle_by_span_without_a_device_plane_counts_the_whole_window():
    got = spans.idle_by_span(reduced([host("store.block", -10, 60)], hi=100.0), ["store.block"])
    assert got["idle_s"] == pytest.approx(100e-9)
    assert got["spans"]["store.block"] == pytest.approx(50e-9)  # clipped at the window's start


@pytest.mark.parametrize("name", ["device.idle_named_pct.query", "device.idle_named_pct.ingest"])
def test_named_idle_share_reads_program_spans_only(name):
    # idle [0, 100] and [200, 1000]: 900 ns; program spans cover [0, 50] and
    # [500, 700] of it, a host event that is not the program's [700, 1000]
    ev = [op(100, 100), host("serve.step", 0, 50), host("sketch.block", 500, 200, line="b"),
          host("store.block", 600, 50), host("PjitFunction(f)", 700, 300)]
    assert reader(name).read(layer(reduced(ev))) == pytest.approx(100.0 * 250 / 900)
    assert reader(name).read(layer(reduced([op(0, 1000), host("serve.step", 0, 10)]))) is None


def test_program_spans_name_every_span_the_program_writes():
    import re

    src = os.path.join(tiny.ROOT, "src", "repro")
    written = set()
    for d, _, files in os.walk(src):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    written |= set(re.findall(r'obs\.span\(\s*"([\w.]+)"', fh.read()))
    assert written == set(spans.PROGRAM_SPANS)


@pytest.mark.parametrize("name,span", [
    ("query.fold_ms", "query.fold"), ("query.ci_ms", "query.ci"),
    ("engine.wait_ms", "engine.wait"), ("sketch.block_ms", "sketch.block"),
    ("store.block_ms", "store.block"),
])
def test_mean_readers(name, span):
    t = reduced([host(span, 100, 2e6), host(span, 3e6, 4e6), host(span, 5e8, 1e9)], hi=1e9)
    assert reader(name).read(layer(t)) == pytest.approx(3.0)  # ms; the cut one left out


def test_h2d_rate_reads_counted_bytes_over_span_seconds():
    # the counter counts a copy at its span's end: the first span (begun before
    # the window) is counted and timed whole, the last (ending after it) neither
    t = reduced([host("kernel.h2d", -1e6, 2e6), host("kernel.h2d", 1e7, 3e6),
                 host("kernel.h2d", 9.9e8, 1e8)], hi=1e9)
    rows = [({"kernel": "plan"}, 3e6), ({"kernel": "block_sketch"}, 2e6)]
    got = reader("kernel.h2d_gbps").read(layer(t, obs={"rsp_h2d_bytes_total": rows}))
    assert got == pytest.approx(5e6 / 5e-3 / 1e9)  # 5 MB in 5 ms: 1 GB/s
    assert reader("kernel.h2d_gbps").read(layer(t)) is None  # no bytes counted


def test_traced_query_run_reads_the_executor_and_engine_spans(tmp_path):
    res = tiny.run_tiny("higgs.query8", tmp_path, "--trace", "1")
    assert res["correct"], res["checks"]
    for name in ("query.fold_ms", "query.ci_ms", "engine.wait_ms"):
        assert res["metrics"][name]["value"] > 0, name
    # no device plane on the CPU: the whole window is idle, mostly in steps
    assert 50.0 < res["metrics"]["device.idle_named_pct.query"]["value"] <= 100.0
    import span_table

    got = span_table.table(str(tmp_path), platform="cpu")
    assert got["named_pct"] == pytest.approx(res["metrics"]["device.idle_named_pct.query"]["value"])
    assert got["spans_s"]["serve.step"] > 0 and got["mean"]["query.fold"]["count"] > 0
    assert got["any_s"] + got["none_s"] == pytest.approx(got["idle_s"])
