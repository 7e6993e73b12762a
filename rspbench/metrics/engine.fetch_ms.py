"""Mean latency of one fetcher read on a cache miss, in ms (repro.obs)."""
from yardstick.layer import hist_mean


def read(layer):
    m = hist_mean(layer.obs.get("rsp_engine_fetch_seconds", []))
    return None if m is None else 1e3 * m
