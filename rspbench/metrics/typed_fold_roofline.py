"""The typed fold's share of its HBM roofline, in percent.

Bytes: one int32 block (``block_rows x columns x 4``) per typed fold call
that ran on the device (``rsp_kernel_runs_total{kernel="plan"}``, impls
pallas and jax).  Time: the device durations of the ops that read an int32
block, from the trace.  Nothing to read when no typed fold ran on the
device."""
from yardstick import kernels, trace, typed_fold
from yardstick.layer import counter
from yardstick.roofline import least_seconds, share_pct


def read(layer):
    f = layer.facts
    if not f.get("typed"):
        return None
    calls = counter(layer.obs.get("rsp_kernel_runs_total", []), kernel=typed_fold.KERNEL,
                    impl=kernels.DEVICE_IMPLS)
    secs = trace.op_seconds(layer.trace["ops"], typed_fold.operand(f["block_rows"], f["columns"]),
                            layer.trace["lo"], layer.trace["hi"])
    if calls == 0 or secs == 0:
        return None
    nbytes = calls * typed_fold.fold_bytes(f["block_rows"], f["columns"])
    return share_pct(least_seconds(nbytes, layer.peaks["hbm_bytes_per_s"]), secs)
