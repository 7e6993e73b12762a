"""Mean time of one step's totals in a typed query, in ms: the
``query.totals`` span (every group's Horvitz-Thompson totals, ratios and
intervals), over the spans wholly inside the traced window."""
from yardstick.spans import inside


def read(layer):
    return inside(layer.trace, "query.totals")[1]
