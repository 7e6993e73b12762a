"""Reduction of a JAX profiler trace to device busy time, idle gaps and
per-kernel device time.

``capture_options()`` is how the harness records a trace (host annotations
on, Python tracer off: the host path is Python and tracing every call would
slow it severalfold),
``load(path)`` flattens its planes into :class:`Event` rows, and the rest
are pure functions over those rows, so the tests drive them with a small
recorded or hand-made trace.

* Device events are those on a device plane's ``XLA Ops`` line (a device
  plane whose name starts with ``/device:<PLATFORM>``).  Busy time is the
  union of their intervals inside the window, averaged over the devices.
* The window is the host annotation :data:`WINDOW` that the harness puts
  around its measured window.
* An idle gap is a stretch of the window in which no device op ran; it is
  named by the host event that covers most of it (other than the window
  itself), where one covers at least half of it.
* A device op event is named by its HLO instruction text
  (``%name = f32[...] custom-call(f32[86016,29]... %x)``), so a kernel is
  found by the array it reads (:func:`op_seconds`).
"""

from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "rspbench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def capture_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def latest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> list[Event]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                module = ""
                for key, value in e.stats:
                    if key == "hlo_module":
                        module = str(value)
                        break
                out.append(Event(plane.name, line.name, e.name, float(e.start_ns),
                                 float(e.duration_ns), module))
    return out


def window(events: list[Event]) -> tuple[float, float]:
    """(start, end) of the harness's window annotation, in trace ns."""
    spans = [e for e in events if e.name == WINDOW]
    if not spans:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    return min(e.start_ns for e in spans), max(e.end_ns for e in spans)


def device_ops(events: list[Event], platform: str = "TPU") -> dict[str, list[Event]]:
    """Device ops by device plane."""
    prefix = f"/device:{platform.upper()}"
    out: dict[str, list[Event]] = {}
    for e in events:
        if e.plane.startswith(prefix) and e.line == OPS_LINE:
            out.setdefault(e.plane, []).append(e)
    return out


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, disjoint intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(ops: dict[str, list[Event]], lo: float, hi: float) -> float:
    """Union of device-op intervals inside [lo, hi], averaged over devices."""
    if not ops:
        return 0.0
    total = 0.0
    for evs in ops.values():
        total += sum(b - a for a, b in union(clip([(e.start_ns, e.end_ns) for e in evs], lo, hi)))
    return total / len(ops) / 1e9


def gaps(busy: list[tuple[float, float]], lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that ``busy`` (merged) leaves uncovered."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def idle_gaps(events: list[Event], ops: dict[str, list[Event]], lo: float, hi: float,
              top: int = 10) -> list[list]:
    """The longest idle gaps of the first device, each named by the host
    event that overlaps it most: ``[[name, seconds], ...]``."""
    if not ops:
        return []
    first = sorted(ops)[0]
    busy = union(clip([(e.start_ns, e.end_ns) for e in ops[first]], lo, hi))
    host = [e for e in events if not e.plane.startswith("/device:") and e.name != WINDOW
            and e.dur_ns > 0 and e.end_ns > lo and e.start_ns < hi]
    longest = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    out = []
    for a, b in longest:
        best, name = 0.0, "no host event"
        for e in host:
            overlap = min(b, e.end_ns) - max(a, e.start_ns)
            if overlap > best:
                best, name = overlap, e.name
        if best < 0.5 * (b - a):
            name = "no host event over half of it"
        out.append([name, (b - a) / 1e9])
    return out


def top_ops(ops: dict[str, list[Event]], lo: float, hi: float, top: int = 10) -> list[list]:
    """Device ops that took most time in the window, summed over devices
    and divided by the device count: ``[[name, seconds], ...]``."""
    if not ops:
        return []
    total: dict[str, float] = {}
    for evs in ops.values():
        for e in evs:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                key = f"{e.module}/{e.name}" if e.module else e.name
                total[key] = total.get(key, 0.0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v / len(ops) / 1e9] for k, v in ranked]


def host_totals(events: list[Event], lo: float, hi: float, top: int = 30) -> list[list]:
    """Host events by name: seconds inside [lo, hi] summed over threads,
    and their count, the largest first: ``[[name, seconds, count], ...]``.
    Nested events each count, so the totals overlap."""
    total: dict[str, list[float]] = {}
    for e in events:
        if e.plane.startswith("/device:") or e.name == WINDOW:
            continue
        d = min(e.end_ns, hi) - max(e.start_ns, lo)
        if d > 0:
            t = total.setdefault(e.name, [0.0, 0])
            t[0] += d / 1e9
            t[1] += 1
    ranked = sorted(total.items(), key=lambda kv: -kv[1][0])[:top]
    return [[k, v[0], v[1]] for k, v in ranked]


def op_counts(ops: dict[str, list[Event]], lo: float, hi: float, top: int = 10) -> list[list]:
    """How many times each device op ran inside [lo, hi], over all devices:
    ``[[name, count], ...]``, the most frequent first."""
    count: dict[str, int] = {}
    for evs in ops.values():
        for e in evs:
            if e.end_ns > lo and e.start_ns < hi:
                count[e.name] = count.get(e.name, 0) + 1
    return [[k, v] for k, v in sorted(count.items(), key=lambda kv: -kv[1])[:top]]


def is_layout_op(e: Event) -> bool:
    """Copies and bitcasts that only move or relabel an operand."""
    return any(f" {op}(" in e.name for op in ("copy", "copy-start", "copy-done", "bitcast"))


def op_seconds(ops: dict[str, list[Event]], operand: str, lo: float, hi: float) -> float:
    """Device seconds inside [lo, hi] of the ops that read an array of the
    HLO type ``operand`` (such as ``f32[86016,29]``) other than layout
    copies, averaged over the devices.  Device op events are named by
    their HLO instruction text, so this finds a kernel by what it reads,
    whatever the program names it."""
    secs = 0.0
    for evs in ops.values():
        for e in evs:
            if operand not in e.name or is_layout_op(e):
                continue
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                secs += d
    return secs / max(len(ops), 1) / 1e9
