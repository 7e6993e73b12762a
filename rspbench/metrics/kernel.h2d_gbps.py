"""Rate of the host's part of the kernels' copies to the device, in GB/s:
the bytes counted by ``rsp_h2d_bytes_total`` over the traced stretch, over
the summed durations of the ``kernel.h2d`` spans that end inside the traced
window (the host's ``jnp.asarray`` of each array, layout included, waited
for).  The counter counts a copy when its span ends, so both sides hold the
same copies; a copy begun before the profiler collected is counted but not
traced, at most one per thread, and reads the rate high by its bytes."""
from yardstick.layer import counter


def read(layer):
    t = layer.trace
    nbytes = counter(layer.obs.get("rsp_h2d_bytes_total", []))
    secs = sum(e.dur_ns for e in t["events"]
               if e.name == "kernel.h2d" and t["lo"] <= e.end_ns <= t["hi"]) / 1e9
    if nbytes == 0 or secs == 0:
        return None
    return nbytes / secs / 1e9
