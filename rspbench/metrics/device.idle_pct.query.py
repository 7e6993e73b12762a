"""Share of the traced window in which no op ran on the device, in percent,
in the cells that report ``queries_per_s``."""
from yardstick.layer import idle_pct


def read(layer):
    return idle_pct(layer.trace)
