"""Mean time of one block's fold in the query executor, in ms: the
``query.fold`` span (dispatch, copy to the device, kernel, readback,
per-class host folds, KMV), over the spans wholly inside the traced window."""
from yardstick.spans import inside


def read(layer):
    return inside(layer.trace, "query.fold")[1]
