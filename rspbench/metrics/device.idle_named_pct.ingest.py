"""Share of the traced window's device-idle time that some program span
covers (each span name's intervals merged over threads), in percent, in the
cells that report ``partition_rows_per_s``: how much of the chip's idle time the trace
can put down to a layer of the program."""
from yardstick.spans import named_idle_pct


def read(layer):
    return named_idle_pct(layer.trace)
