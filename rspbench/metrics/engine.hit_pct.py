"""Block accesses served by the engine's cache, in percent (repro.obs)."""
from yardstick.layer import counter


def read(layer):
    rows = layer.obs.get("rsp_engine_fetch_total", [])
    hits, misses = counter(rows, outcome="hit"), counter(rows, outcome="miss")
    return 100.0 * hits / (hits + misses) if hits + misses > 0 else None
