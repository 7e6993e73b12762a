"""Mean time of one step's interval update in the query executor, in ms:
the ``query.ci`` span (every aggregate's estimate and interval, bootstrap
included), over the spans wholly inside the traced window."""
from yardstick.spans import inside


def read(layer):
    return inside(layer.trace, "query.ci")[1]
