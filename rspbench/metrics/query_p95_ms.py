"""95th percentile of the time from send to answer of every query sent in
the window, across all shapes, in ms (host clock)."""
import numpy as np


def read(layer):
    ms = [a["ms"] for a in layer.answers]
    return float(np.percentile(ms, 95)) if ms else None
