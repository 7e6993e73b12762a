"""Mean time of one block's write to the store (``np.save``, replace,
checksum), in ms: the ``store.block`` span, over the spans wholly inside
the traced window."""
from yardstick.spans import inside


def read(layer):
    return inside(layer.trace, "store.block")[1]
