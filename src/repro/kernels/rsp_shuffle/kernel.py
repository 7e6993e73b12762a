"""Pallas TPU kernel for RSP block randomization (the paper's partitioning
hot spot, Fig. 1).

TPU adaptation of Algorithm 1's record shuffle: a *hierarchical* permutation
  out_tile[i] = P_i  @  in_tile[tile_perm[i]]
where
  * ``tile_perm`` (scalar-prefetched) drives the BlockSpec index_map -- the
    delta-slice dealing between blocks becomes pure DMA scheduling; rows are
    moved HBM->VMEM tile-by-tile, never row-at-a-time (XLA's gather lowers
    row-at-a-time dynamic slices, which is what makes naive shuffles slow).
  * ``P_i`` is the intra-tile permutation applied as a one-hot matmul on the
    MXU (a [T, T] x [T, D] matmul per tile -- cheap, and avoids unsupported
    in-VMEM vector gathers).

The composition (tile dealing o intra-tile shuffle) is a bijection and is
exactly the structure Algorithm 1 needs: locally randomize, slice into
delta-chunks, deal chunks to output blocks (Lemma 1 applies at slice
granularity).  ``ref.py`` is the equivalent flat row-gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _shuffle_kernel(tile_perm_ref, intra_ref, x_ref, o_ref):
    del tile_perm_ref  # consumed by the index_map
    tile = x_ref[...]                       # [T, D] (gathered tile)
    perm = intra_ref[...]                   # [T, 1] int32
    T = tile.shape[0]
    # one-hot permutation matrix on the MXU: onehot[r, c] = (c == perm[r])
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    onehot = (cols == perm).astype(tile.dtype)
    # HIGHEST keeps the f32 contraction exact: each output row is 1.0 times
    # one input row, so a reduced-precision pass would round the records
    o_ref[...] = jax.lax.dot_general(
        onehot, tile, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def rsp_shuffle_pallas(
    x: jax.Array,           # [R, D]   R = num_tiles * tile_rows
    tile_perm: jax.Array,   # [num_tiles] int32 -- source tile for output tile i
    intra_perm: jax.Array,  # [num_tiles, T] int32 -- row perm within each tile
    *,
    tile_rows: int,
    interpret: bool,
) -> jax.Array:
    R, D = x.shape
    if R % tile_rows:
        raise ValueError(f"rows {R} must be divisible by tile_rows {tile_rows}")
    n_tiles = R // tile_rows

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            # [n_tiles, T, 1]: the block's last two dims are the array's own,
            # which the TPU tiling rule accepts for any T
            pl.BlockSpec((None, tile_rows, 1), lambda i, tp: (i, 0, 0)),
            pl.BlockSpec((tile_rows, D), lambda i, tp: (tp[i], 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, D), lambda i, tp: (i, 0)),
        scratch_shapes=[],
    )
    return pl.pallas_call(
        _shuffle_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), x.dtype),
        name="rsp_shuffle",
        interpret=interpret,
    )(
        tile_perm.astype(jnp.int32),
        intra_perm.astype(jnp.int32).reshape(n_tiles, tile_rows, 1),
        x,
    )
