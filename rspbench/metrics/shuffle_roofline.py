"""rsp_shuffle's share of its HBM roofline, in percent: each original block
read once and written once, over the device time of the ops that read an
original block, from the trace."""
from yardstick import kernels, trace
from yardstick.layer import counter
from yardstick.roofline import least_seconds, share_pct, shuffle_bytes


def read(layer):
    calls = counter(layer.obs.get("rsp_kernel_runs_total", []), kernel=kernels.SHUFFLE_KERNEL)
    f = layer.facts
    secs = trace.op_seconds(layer.trace["ops"], kernels.operand(f["shuffle_rows"], f["columns"]),
                            layer.trace["lo"], layer.trace["hi"])
    if calls == 0 or secs == 0:
        return None
    nbytes = calls * shuffle_bytes(f["shuffle_rows"], f["columns"])
    return share_pct(least_seconds(nbytes, layer.peaks["hbm_bytes_per_s"]), secs)
