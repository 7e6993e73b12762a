"""Mean time of one block's sketch suite at partition time (moments, KLL,
KMV, labels), in ms: the ``sketch.block`` span, over the spans wholly
inside the traced window."""
from yardstick.spans import inside


def read(layer):
    return inside(layer.trace, "sketch.block")[1]
