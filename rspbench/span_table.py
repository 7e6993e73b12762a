"""Where the device's idle time went in a traced run, by program span.

    python3 rspbench/span_table.py results/rspbench/<cell> [...]

Reads the newest profile under each run directory's ``trace/`` (what
``run.py --trace 1`` leaves there) and prints one JSON line per run: the
traced window's device-idle seconds, the seconds of it that each program
span covers (its intervals merged over threads; nested spans each count, so
rows overlap), the seconds any span covers and none covers, the window's
seconds before the first program span starts and after the last one ends
(a span open when the profiler starts or stops is not recorded), and each
span's count and mean duration over the spans wholly inside the window.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from yardstick import spans  # noqa: E402
from yardstick import trace as tr  # noqa: E402


def table(run_dir: str, platform: str = "TPU") -> dict:
    events = tr.load(tr.latest_xplane(os.path.join(run_dir, "trace")))
    lo, hi = tr.window(events)
    ops = tr.device_ops(events, platform)
    t = {"events": events, "ops": ops, "lo": lo, "hi": hi}
    idle = spans.idle_by_span(t, spans.PROGRAM_SPANS)
    named = [(e.start_ns, e.end_ns) for e in events
             if e.name in spans.PROGRAM_SPANS and not e.plane.startswith("/device:")]
    first = min((a for a, _ in named), default=hi)
    last = max((b for _, b in named), default=lo)
    mean = {}
    for name in spans.PROGRAM_SPANS:
        evs, ms = spans.inside(t, name)
        if evs:
            mean[name] = {"count": len(evs), "mean_ms": ms}
    return {
        "run": run_dir,
        "window_s": (hi - lo) / 1e9,
        "idle_s": idle["idle_s"],
        "spans_s": {k: v for k, v in idle["spans"].items() if v > 0},
        "any_s": idle["any_s"],
        "none_s": idle["none_s"],
        "named_pct": spans.named_idle_pct(t),
        "before_first_span_s": max(0.0, min(first, hi) - lo) / 1e9,
        "after_last_span_s": max(0.0, hi - max(last, lo)) / 1e9,
        "mean": mean,
    }


if __name__ == "__main__":
    for d in sys.argv[1:]:
        print(json.dumps(table(d)), flush=True)
