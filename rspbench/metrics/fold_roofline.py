"""Device fold calls' share of their HBM roofline, in percent.

Bytes: one block read per fold call that ran on the device (block_sketch
and plan, impls pallas and jax, counted by ``rsp_kernel_runs_total``).
Time: the device durations of the ops that read a block, from the trace.
Nothing to read when no fold ran on the device."""
from yardstick import kernels, trace
from yardstick.layer import counter
from yardstick.roofline import least_seconds, share_pct


def read(layer):
    calls = counter(layer.obs.get("rsp_kernel_runs_total", []),
                    kernel=kernels.FOLD_KERNELS, impl=kernels.DEVICE_IMPLS)
    f = layer.facts
    secs = trace.op_seconds(layer.trace["ops"], kernels.operand(f["block_rows"], f["columns"]),
                            layer.trace["lo"], layer.trace["hi"])
    if calls == 0 or secs == 0:
        return None
    return share_pct(least_seconds(calls * f["block_bytes"], layer.peaks["hbm_bytes_per_s"]), secs)
