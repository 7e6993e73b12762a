"""Small helpers the per-layer readers share over registry deltas."""

from __future__ import annotations


def matches(labels: dict, want: dict) -> bool:
    return all(labels.get(k) in (v if isinstance(v, tuple) else (v,)) for k, v in want.items())


def counter(rows: list, **want) -> float:
    """Sum of a counter's deltas over the series whose labels match."""
    return float(sum(r[1] for r in rows if matches(r[0], want)))


def hist_mean(rows: list, **want) -> float | None:
    """Mean observation of a histogram's deltas, or None if none landed."""
    n = sum(r[1] for r in rows if matches(r[0], want))
    s = sum(r[2] for r in rows if matches(r[0], want))
    return s / n if n > 0 else None


def idle_pct(trace: dict) -> float | None:
    """Share of the traced window in which no op ran on the device, in percent."""
    w = trace["window_s"]
    return None if w <= 0 else 100.0 * (1.0 - trace["busy_s"] / w)
