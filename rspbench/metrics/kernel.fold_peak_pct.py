"""Bytes that every fold in the traced stretch read, whatever impl ran it,
over the stretch, as a share of the chip's HBM peak, in percent.

Fold calls through the dispatcher, host impls included, are counted by
``rsp_kernel_runs_total``; the grouped path's per-class host folds are not
dispatched through it, so each block a grouped answer folded inside the
stretch (by its convergence trace) adds one block read."""
from yardstick import kernels
from yardstick.layer import counter


def read(layer):
    calls = counter(layer.obs.get("rsp_kernel_runs_total", []), kernel=kernels.FOLD_KERNELS)
    lo, hi = layer.stretch
    calls += sum(lo <= t < hi for a in layer.answers if a["kind"] == "grouped"
                 for t in a["step_at"])
    if calls == 0 or not layer.window_s:
        return None
    rate = calls * layer.facts["block_bytes"] / layer.window_s
    return 100.0 * rate / layer.peaks["hbm_bytes_per_s"]
