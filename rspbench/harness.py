"""The benchmark harness: finds a cell's pieces by name, runs it once, and
prints the result line.

Everything belonging to one cell, configuration, traffic mix or per-layer
metric lives in a file of its own and is found by the name in
``BENCHMARK.json``:

* ``BENCHMARK.json`` ``workloads[]``: the cell's configuration, traffic and
  chips;
* ``rspbench/configs/<config>.json``: the deployment (the entry's ``file``);
* ``rspbench/traffic/<traffic>.json``: the traffic mix; its ``driver`` key
  names the general generator ``rspbench/drivers/<driver>.py`` that reads it;
* ``rspbench/workloads/<cell>.json``: the limits of the numbers that decide
  the cell's ``correct``;
* ``rspbench/metrics/<metric>.py``: one per-layer metric's reader,
  ``read(layer) -> float | None``.

A driver exposes ``run(run: Run) -> dict`` and calls ``run.setup_done()``,
``run.window_begin()`` and ``run.window_end()`` at the edges of its set-up
and measured window; it returns the end-to-end metrics, the counts of
attempted and failed operations, the compared numbers and what the
per-layer readers read (see :class:`Layer`).
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, "results", "rspbench")
CACHE_DIR = os.path.join(HERE, ".jax_cache")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    """One cell with every file it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def driver_path(self) -> str:
        return os.path.join(HERE, "drivers", f"{self.traffic['driver']}.py")


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w = by_name(bench["workloads"], name, "workload")
    c = by_name(bench["configs"], w["config"], "config")
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in moved else [])]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(os.path.join(ROOT, c["file"])),
        traffic=load_json(os.path.join(HERE, "traffic", f"{w['traffic']}.json")),
        limits=load_json(os.path.join(HERE, "workloads", f"{name}.json"))["limits"],
        end_to_end=e2e,
        per_layer=layer,
    )


@dataclasses.dataclass
class Layer:
    """What per-layer readers read.

    Over the traced stretch (``window_s`` seconds, ``stretch`` on the
    host's ``perf_counter`` clock): ``obs``, the deltas of
    the process-global ``repro.obs`` registry; ``service``, those of the
    query service's own registry (both map a metric name to
    ``[(labels, value)]`` for counters and ``[(labels, count, sum)]`` for
    histograms); ``trace``, the reduced device trace.  Over the whole
    window: ``answers``, the driver's answer records.  ``facts``: sizes
    the readers need (block bytes, rows, columns), and ``peaks`` the
    chip's published peaks."""

    obs: dict
    service: dict
    answers: list
    trace: dict
    facts: dict
    peaks: dict
    window_s: float
    stretch: tuple = (0.0, 0.0)


def registry_delta(before: dict, after: dict) -> dict:
    """Counter and histogram deltas between two ``MetricsRegistry.snapshot()``s."""
    out: dict = {}
    for name, fam in after.items():
        old = {json.dumps(s["labels"], sort_keys=True): s for s in before.get(name, {}).get("series", [])}
        rows = []
        for s in fam["series"]:
            o = old.get(json.dumps(s["labels"], sort_keys=True))
            if fam["kind"] == "histogram":
                c0, s0 = (o["count"], o["sum"]) if o else (0, 0.0)
                rows.append((s["labels"], s["count"] - c0, s["sum"] - s0))
            else:
                rows.append((s["labels"], s["value"] - (o["value"] if o else 0.0)))
        out[name] = rows
    return out


TRACE_S = 10.0   # the traced stretch of a --trace 1 run's window
TRACE_AT = 20.0  # its start, seconds into the window


class Run:
    """One run of one cell: its inputs, and the hooks that time it.

    With ``trace``, ``repro.obs`` metrics are on for the whole window, and
    a stretch of it (``TRACE_S`` seconds from ``TRACE_AT``, both shortened
    to fit a short window) is traced by the JAX profiler; the per-layer
    readers see the registries' deltas over that same stretch."""

    def __init__(self, cell: Cell, *, seed: int, seconds: float, trace: bool,
                 control: str | None, t0: float, work_dir: str | None = None):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.control = control
        self.t0 = t0
        self.work_dir = work_dir or os.path.join(WORK, cell.name)
        self.setup_s: float | None = None
        self.window_s: float | None = None
        self.traced_s: float | None = None
        self.stretch = (0.0, 0.0)
        self.memory_peak_bytes = 0
        self.compiles = {"traced": 0, "compiled": 0}
        self._counting = False
        self._registries: dict = {}
        self.deltas: dict = {}
        self._tracer: threading.Thread | None = None
        self.trace_dir = os.path.join(self.work_dir, "trace")
        self._listen()

    def _listen(self) -> None:
        import jax

        def on_duration(event: str, duration: float, **kw) -> None:
            if not self._counting:
                return
            if event == "/jax/core/compile/jaxpr_trace_duration":
                self.compiles["traced"] += 1
            elif event == "/jax/core/compile/backend_compile_duration":
                self.compiles["compiled"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def log(self, msg: str) -> None:
        print(msg, flush=True)

    def watch(self, name: str, registry) -> None:
        """Have the traced stretch take ``registry``'s deltas as ``name``."""
        self._registries[name] = registry

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t0
        self.log(f"setup_s {self.setup_s:.3f}")

    def _traced_stretch(self, at: float, seconds: float) -> None:
        import jax

        from yardstick.trace import WINDOW, capture_options

        time.sleep(max(0.0, at - time.perf_counter()))
        jax.profiler.start_trace(self.trace_dir, profiler_options=capture_options())
        # the registries' deltas and the host clock's stretch are taken
        # inside the window annotation, so that counts and trace cover the
        # same seconds; starting and stopping the profiler take seconds
        with jax.profiler.TraceAnnotation(WINDOW):
            before = {k: r.snapshot() for k, r in self._registries.items()}
            t = time.perf_counter()
            time.sleep(seconds)
            self.stretch = (t, time.perf_counter())
            after = {k: r.snapshot() for k, r in self._registries.items()}
        self.traced_s = self.stretch[1] - t
        jax.profiler.stop_trace()
        self.deltas = {k: registry_delta(before[k], after[k]) for k in self._registries}

    def window_begin(self) -> float:
        """Starts the measured window (and, traced, the stretch that the
        profiler records); returns its start on the host clock."""
        if self.trace:
            from repro import obs

            obs.enable(sample_rate=0.0)  # metrics on, spans off
            self.watch("obs", obs.get_registry())
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        self._counting = True
        self._begin = time.perf_counter()
        if self.trace:
            span = min(TRACE_S, self.seconds / 2)
            at = self._begin + min(TRACE_AT, (self.seconds - span) / 2)
            self._tracer = threading.Thread(target=self._traced_stretch, args=(at, span),
                                            daemon=True)
            self._tracer.start()
        return self._begin

    def window_end(self) -> float:
        """Closes the window; reads the device's peak memory."""
        end = time.perf_counter()
        self.window_s = end - self._begin
        if self._tracer is not None:
            self._tracer.join()
        self._counting = False
        self.memory_peak_bytes = peak_memory()
        log_tuner_winners(self.log)
        self.log(f"window_s {self.window_s:.3f}; compiles in window: "
                 f"{self.compiles['traced'] + self.compiles['compiled']} "
                 f"(jaxpr traces {self.compiles['traced']}, backend compiles "
                 f"{self.compiles['compiled']})")
        return end


def log_tuner_winners(log) -> None:
    """The program's autotuner winners this run used, one line each."""
    from repro.kernels import autotune

    for key, rec in sorted(autotune.get_tuner().records().items()):
        tile = rec.get("tile_rows")
        log(f"tuner {key}: {rec['impl']}" + (f":{tile}" if tile else "")
            + f" ({rec.get('us', float('nan')):.1f} us)")


def peak_memory() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def reduce_trace(run: Run, platform: str) -> dict:
    """Busy time, window, top ops and idle gaps from the run's trace."""
    from yardstick import trace as tr

    events = tr.load(tr.latest_xplane(run.trace_dir))
    lo, hi = tr.window(events)
    ops = tr.device_ops(events, platform)
    out = {
        "events": events, "ops": ops, "lo": lo, "hi": hi,
        "window_s": (hi - lo) / 1e9,
        "busy_s": tr.busy_seconds(ops, lo, hi),
        "device_ops": tr.top_ops(ops, lo, hi),
        "idle_gaps": tr.idle_gaps(events, ops, lo, hi),
    }
    summary = {
        "planes": sorted({(e.plane, e.line) for e in events}),
        "device_ops": out["device_ops"],
        "idle_gaps": out["idle_gaps"],
        "modules": sorted({e.module for evs in ops.values() for e in evs}),
        "host_events": tr.host_totals(events, lo, hi),
        "op_counts": tr.op_counts(ops, lo, hi),
    }
    with open(os.path.join(run.work_dir, "trace_summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    return out


def read_layer(cell: Cell, layer: Layer) -> dict:
    """Each per-layer metric of this cell whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        reader = load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                             "rspbench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(layer)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def use_compile_cache() -> str:
    """JAX's persistent compilation cache, always at one fixed path inside
    the checkout (the path is part of the cache key), whatever the
    environment names: two checkouts never share a cache."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return CACHE_DIR


def parse(argv: list[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="compare a control in the program's place (see PERF.md);"
                         " never used by the benchmark's own runs")
    return ap.parse_args(argv)


def result_line(cell: Cell, out: dict, device: dict, metrics: dict,
                breakdown: dict | None) -> tuple[dict, list[str]]:
    """The result and the lines that give each compared number beside its
    limit.  A number that no answer of the run carried (a query shape the
    window happened not to send) is shown as ``null`` and decides nothing;
    a run with no number compared at all, or a failed operation, is not
    correct."""
    checks, lines = {}, []
    compared = 0
    correct = out["failed"] == 0 and out["attempted"] > 0
    for name, limit in cell.limits.items():
        value = out["checks"].get(name)
        if value is None:
            lines.append(f"check {name}: not compared, no answer carried it (limit {limit!r})")
        else:
            compared += 1
            ok = math.isfinite(value) and value <= limit
            correct = correct and ok
            lines.append(f"check {name}: {value!r} (limit {limit!r})" + ("" if ok else "  FAILED"))
        # the result line stays strict JSON: a non-finite number is given as text
        shown = value if value is None or math.isfinite(value) else str(value)
        checks[name] = {"value": shown, "limit": limit}
    correct = correct and compared > 0
    lines.append(f"check failed: {out['failed']} of {out['attempted']} (limit 0)")
    res = {"correct": bool(correct), "attempted": int(out["attempted"]),
           "failed": int(out["failed"]), "metrics": metrics, "device": device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["checks"] = checks
    return res, lines


def main(argv: list[str], *, t0: float, require_tpu: bool = True,
         cell: Cell | None = None, work_dir: str | None = None,
         peaks_of: str | None = None) -> int:
    """Runs one cell once.  Tests drive a run on the CPU with
    ``require_tpu=False``, a cut ``cell`` and ``peaks_of`` naming the chip
    whose peaks the per-layer arithmetic uses."""
    args = parse(argv)
    cell = cell or load_cell(args.workload)
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < cell.chips):
        print(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from yardstick.peaks import peaks

    run = Run(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              control=args.control, t0=t0, work_dir=work_dir)
    cache = use_compile_cache() if require_tpu else "off"
    run.log(f"cell {cell.name} seed {args.seed} seconds {args.seconds} trace {args.trace}"
            f" control {args.control}; compile cache {cache}")
    os.makedirs(run.work_dir, exist_ok=True)
    driver = load_module(cell.driver_path, "rspbench_driver_" + cell.traffic["driver"])
    out = driver.run(run)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    breakdown = None
    if run.trace:
        reduced = reduce_trace(run, dev.platform)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        layer = Layer(obs=run.deltas.get("obs", {}), service=run.deltas.get("service", {}),
                      answers=out.get("answers", []), trace=reduced,
                      facts=out["facts"], peaks=peaks(peaks_of or dev.device_kind),
                      window_s=run.traced_s, stretch=run.stretch)
        metrics = read_layer(cell, layer)
    else:
        metrics = {m["name"]: {"value": float(out["end_to_end"][m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": float(run.setup_s), "unit": "s"}
    res, lines = result_line(cell, out, device, metrics, breakdown)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0
