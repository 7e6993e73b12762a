"""The program's spans in the device trace.

The program writes its spans (``repro.obs.span``) into the JAX profiler's
trace as host events, on the device ops' clock, one line per thread.  Two
reductions over the events of a reduced trace (``trace["events"]`` inside
the window ``trace["lo"]``-``trace["hi"]``, see ``yardstick.trace``):

* :func:`inside`: the events of one span name that lie wholly inside the
  window, and their mean duration; a span cut by the window's edge is
  left out, so a mean is never of a part.
* :func:`idle_by_span`: the device-idle time of the window that each span
  name covers, its intervals merged over every thread, so that time two
  threads spend in one span at once counts once.
* :func:`named_idle_pct`: the share of that idle time that some span of
  the program's (:data:`PROGRAM_SPANS`) covers.
"""

from __future__ import annotations

from yardstick.trace import Event, clip, gaps, union

#: The program's span names (``repro.obs.span``), from the entry point down.
PROGRAM_SPANS = (
    "serve.step", "serve.deadline", "query.setup", "query.fold", "query.ci",
    "engine.wait", "engine.fetch", "kernel.h2d", "kernel.readback",
    "partition.shuffle", "shuffle.block", "partition.sketch", "sketch.block",
    "store.write", "store.block", "store.sketch",
)


def inside(trace: dict, name: str) -> tuple[list[Event], float | None]:
    """The events named ``name`` wholly inside the window, and their mean
    duration in ms (None when there are none)."""
    lo, hi = trace["lo"], trace["hi"]
    evs = [e for e in trace["events"] if e.name == name and e.start_ns >= lo and e.end_ns <= hi]
    return evs, (1e-6 * sum(e.dur_ns for e in evs) / len(evs) if evs else None)


def _overlap_ns(a: list[tuple[float, float]], b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_by_span(trace: dict, names) -> dict:
    """Seconds of the first device's idle time in the window covered by each
    span name of ``names`` (``spans``), by any of them (``any_s``) and by
    none (``none_s``), beside the idle total (``idle_s``).  With no device
    plane the whole window is idle."""
    lo, hi = trace["lo"], trace["hi"]
    ops = trace["ops"]
    busy = union(clip([(e.start_ns, e.end_ns) for e in ops[sorted(ops)[0]]], lo, hi)) if ops else []
    idle = gaps(busy, lo, hi)
    idle_ns = sum(b - a for a, b in idle)
    names = list(names)
    by_name = {n: [] for n in names}
    for e in trace["events"]:
        if e.name in by_name and not e.plane.startswith("/device:"):
            by_name[e.name].append((e.start_ns, e.end_ns))
    spans = {n: _overlap_ns(union(clip(iv, lo, hi)), idle) / 1e9 for n, iv in by_name.items()}
    every = union(clip([iv for ivs in by_name.values() for iv in ivs], lo, hi))
    any_ns = _overlap_ns(every, idle)
    return {"idle_s": idle_ns / 1e9, "spans": spans, "any_s": any_ns / 1e9,
            "none_s": (idle_ns - any_ns) / 1e9}


def named_idle_pct(trace: dict) -> float | None:
    """Percent of the window's device-idle time that some program span
    covers; None where the trace holds no program span (a program that does
    not write them) or the device never idles."""
    got = idle_by_span(trace, PROGRAM_SPANS)
    if got["any_s"] == 0 or got["idle_s"] == 0:
        return None
    return 100.0 * got["any_s"] / got["idle_s"]
