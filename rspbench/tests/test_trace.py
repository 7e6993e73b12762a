"""The trace reduction: busy union, idle share, gaps, kernels by operand."""

import os

import numpy as np
import tiny  # noqa: F401 -- puts rspbench on the path

from yardstick import trace as tr
from yardstick.trace import Event

DEV = "/device:TPU:0"


def op(name, start, dur, plane=DEV):
    return Event(plane, tr.OPS_LINE, name, float(start), float(dur))


def recorded():
    """A small trace: a 1000 ns window, three overlapping device ops, one
    op outside the window, and host events."""
    return [
        Event("/host:CPU", "python3", tr.WINDOW, 0, 1000),
        op("%k.1 = (f32[5,29]) custom-call(f32[64,29]{1,0} %copy)", 100, 200),
        op("%copy = f32[64,29]{1,0} copy(f32[64,29]{0,1} %x)", 250, 100),
        op("%fusion = f32[8] fusion(f32[8] %y)", 600, 100),
        op("%late = f32[8] fusion(f32[8] %y)", 1200, 100),
        Event("/host:CPU", "pjrt", "TransferToDevice", 360, 230),
        Event("/host:CPU", "pjrt", "Tiny", 710, 10),
    ]


def test_window_busy_and_idle_share():
    ev = recorded()
    lo, hi = tr.window(ev)
    assert (lo, hi) == (0.0, 1000.0)
    ops = tr.device_ops(ev)
    # union of [100,300], [250,350], [600,700] inside [0,1000] = 350 ns
    assert tr.busy_seconds(ops, lo, hi) == 350e-9
    assert tr.union([(1, 3), (2, 4), (6, 7)]) == [(1, 4), (6, 7)]
    assert tr.gaps([(1, 4), (6, 7)], 0, 10) == [(0, 1), (4, 6), (7, 10)]


def test_idle_gaps_named_by_host_event_covering_half():
    ev = recorded()
    lo, hi = tr.window(ev)
    gaps = tr.idle_gaps(ev, tr.device_ops(ev), lo, hi)
    # gaps: [0,100] 100, [350,600] 250, [700,1000] 300 -- longest first
    assert [round(g[1] * 1e9) for g in gaps] == [300, 250, 100]
    assert gaps[1][0] == "TransferToDevice"          # covers 230 of 250 ns
    assert gaps[0][0] == "no host event over half of it"


def test_kernel_found_by_operand_without_layout_copies():
    ev = recorded()
    ops = tr.device_ops(ev)
    assert tr.op_seconds(ops, "f32[64,29]", 0, 1000) == 200e-9
    assert tr.op_seconds(ops, "f32[65,29]", 0, 1000) == 0.0
    top = tr.top_ops(ops, 0, 1000)
    assert top[0] == ["%k.1 = (f32[5,29]) custom-call(f32[64,29]{1,0} %copy)", 200e-9]


def test_busy_is_averaged_over_devices():
    ev = [Event("/host:CPU", "", tr.WINDOW, 0, 100), op("a", 0, 100),
          op("b", 0, 50, plane="/device:TPU:1")]
    assert tr.busy_seconds(tr.device_ops(ev), 0, 100) == 75e-9


def test_load_reads_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum(0))
    x = jnp.ones((256, 29))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=tr.capture_options())
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tr.load(tr.latest_xplane(str(tmp_path)))
    lo, hi = tr.window(ev)
    assert hi > lo
    # the CPU has no device plane: nothing is busy, everything is one gap
    assert tr.device_ops(ev) == {}
    assert tr.busy_seconds({}, lo, hi) == 0.0
    assert any(e.module.startswith("jit_") for e in ev)  # the program's ops carry their module
    assert os.path.exists(tr.latest_xplane(str(tmp_path)))
    assert np.isfinite((hi - lo) / 1e9)
