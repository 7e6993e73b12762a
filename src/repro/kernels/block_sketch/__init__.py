"""The per-block sketch container and its numpy oracle.

:class:`BlockSketch` holds one block's count, per-feature mean / M2 /
extrema and optional fixed-grid histogram; :func:`merge_sketches` combines
two.  :func:`block_sketch_ref` computes one in float64 -- the oracle that
the plan kernels' reference (``repro.kernels.plan.ref``) and the ingest
sketches use.  On the query path blocks fold through the plan kernels
(``repro.kernels.plan``), which return these containers.
"""

from repro.kernels.block_sketch.ref import (
    BlockSketch,
    block_sketch_ref,
    grid_histogram,
    merge_sketches,
)

__all__ = [
    "BlockSketch",
    "block_sketch_ref",
    "grid_histogram",
    "merge_sketches",
]
