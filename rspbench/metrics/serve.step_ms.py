"""Mean latency of one progressive step (fetch, fold, interval update), in ms."""
from yardstick.layer import hist_mean


def read(layer):
    m = hist_mean(layer.service.get("rsp_serve_step_seconds", []))
    return None if m is None else 1e3 * m
