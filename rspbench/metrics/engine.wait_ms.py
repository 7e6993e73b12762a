"""Mean time the folding thread waits for its prefetched block, in ms: the
``engine.wait`` span, over the spans wholly inside the traced window."""
from yardstick.spans import inside


def read(layer):
    return inside(layer.trace, "engine.wait")[1]
