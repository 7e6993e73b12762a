"""Pallas TPU kernel for the fused per-block sketch (moments + histogram).

The query layer touches every record of a fetched block exactly once; doing
moments and the quantile histogram in *separate* passes doubles the HBM
traffic of the hot loop.  This kernel fuses them: the grid walks row tiles of
a ``[n, F]`` block, each step computes the tile's stable (mean, M2) moments,
extrema, and a per-feature fixed-grid histogram entirely in VMEM, then folds
them into the running outputs -- moments via the Chan parallel combine
(numerically stable across tiles), histogram by addition, extrema by
min/max.  One pass over HBM, two small resident outputs:

  * ``stats [5, F]``  -- rows (count, mean, M2, min, max)
  * ``hist  [F, B]``  -- per-feature bin counts (out-of-range mass clipped
    into the edge bins, so the histogram always sums to ``n``)

Rows past ``n`` (tile padding) are masked out of every reduction.  The bin
index is ``clip(floor((x - lo) * inv_width), 0, B-1)`` with per-feature
``lo`` / ``inv_width`` carried as ``[1, F]`` inputs; a constant feature
(``inv_width = 0``) lands all its mass in bin 0, matching ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.moments import chan_merge


def _sketch_kernel(
    x_ref, lo_ref, invw_ref, stats_ref, hist_ref, *, valid_rows, tile_rows, bins
):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)                       # [T, F]
    t, f = x.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0) + i * tile_rows
    valid = row < valid_rows                                  # [T, 1]
    cnt = jnp.sum(valid.astype(jnp.float32))
    safe_cnt = jnp.maximum(cnt, 1.0)

    xz = jnp.where(valid, x, 0.0)
    mean_t = xz.sum(axis=0) / safe_cnt                        # [F]
    m2_t = jnp.where(valid, (x - mean_t) ** 2, 0.0).sum(axis=0)
    min_t = jnp.where(valid, x, jnp.inf).min(axis=0)
    max_t = jnp.where(valid, x, -jnp.inf).max(axis=0)

    idx = jnp.clip(
        jnp.floor((x - lo_ref[0]) * invw_ref[0]).astype(jnp.int32), 0, bins - 1
    )                                                         # [T, F]
    onehot = (idx[:, :, None] == jax.lax.broadcasted_iota(jnp.int32, (t, f, bins), 2))
    onehot = jnp.logical_and(onehot, valid[:, :, None])
    hist_t = onehot.astype(jnp.float32).sum(axis=0)           # [F, B]

    @pl.when(i == 0)
    def _init():
        stats_ref[0, :] = jnp.full((f,), cnt, jnp.float32)
        stats_ref[1, :] = mean_t
        stats_ref[2, :] = m2_t
        stats_ref[3, :] = min_t
        stats_ref[4, :] = max_t
        hist_ref[...] = hist_t

    @pl.when(i > 0)
    def _fold():
        # the one shared Chan combine (repro.core.moments), traced with xp=jnp
        n, mean, m2 = chan_merge(
            stats_ref[0, :], stats_ref[1, :], stats_ref[2, :],
            cnt, mean_t, m2_t,
            xp=jnp,
        )
        stats_ref[0, :] = n
        stats_ref[1, :] = mean
        stats_ref[2, :] = m2
        stats_ref[3, :] = jnp.minimum(stats_ref[3, :], min_t)
        stats_ref[4, :] = jnp.maximum(stats_ref[4, :], max_t)
        hist_ref[...] = hist_ref[...] + hist_t


def block_sketch_pallas(
    x: jax.Array,        # [n, F]
    lo: jax.Array,       # [F] per-feature grid lower edge
    inv_width: jax.Array,  # [F] 1 / bin_width (0 for constant features)
    *,
    bins: int,
    tile_rows: int = 128,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Run the fused sketch kernel; returns ``(stats [5, F], hist [F, bins])``.

    ``n`` need not divide ``tile_rows`` -- the input is zero-padded to a tile
    multiple and padded rows are masked inside the kernel.
    """
    if x.ndim != 2:
        raise ValueError(f"block must be [n, F], got shape {x.shape}")
    if bins < 1:
        raise ValueError("the fused kernel needs bins >= 1")
    n, f = x.shape
    n_tiles = max(1, -(-n // tile_rows))
    pad = n_tiles * tile_rows - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))

    kernel = functools.partial(
        _sketch_kernel, valid_rows=n, tile_rows=tile_rows, bins=bins
    )
    stats, hist = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((5, f), lambda i: (0, 0)),
            pl.BlockSpec((f, bins), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((5, f), jnp.float32),
            jax.ShapeDtypeStruct((f, bins), jnp.float32),
        ],
        name="block_sketch",
        interpret=interpret,
    )(
        x.astype(jnp.float32),
        lo.reshape(1, f).astype(jnp.float32),
        inv_width.reshape(1, f).astype(jnp.float32),
    )
    return stats, hist
