"""Pallas TPU kernel for plan-compiled fused query execution.

One grid pass over the row tiles of a ``[n, F]`` block does, per tile,
entirely in VMEM:

1. **Predicate mask** -- the plan's conjunctive column comparisons, with
   tile-padding rows masked out alongside the failing rows;
2. **Projection** -- a static one-hot matmul ``x @ P`` onto the plan's
   columns (MXU-friendly; identity plans skip it);
3. **Grouped Chan moments** -- per plan group, masked (count, mean, M2,
   min, max) folded across tiles with the parallel combine;
4. **Histogram scatter** -- the same one-hot-vs-iota trick as
   ``block_sketch.kernel``, weighted by the mask so rejected rows add zero
   mass.

Rows that fail a predicate never leave the tile: there is no second
"apply the mask" pass over HBM, which is the whole point versus the
mask-then-sketch baseline in ``plan.ref``.

Outputs (2D, TPU-friendly):

* ``stats [G * 5, Fp]`` -- per group g, rows ``5g..5g+4`` are (count,
  mean, M2, min, max) over the selected rows of that group;
* ``hist  [G * Fp, B]`` -- per-group per-feature bin counts;
* ``nsel  [1, Fp]``     -- total selected rows (all groups, including rows
  whose group label falls outside ``[0, G)``), broadcast along the row:
  Mosaic stores vectors to VMEM, not scalars.

:func:`plan_totals_pallas` is the typed fold of a
:class:`~repro.kernels.plan.plan.TypedPlan` over int32 blocks: predicates
compared exactly on the codes, each measure decoded to float32 by its scale
and summed per group (the mixed radix of the group-by columns' codes), in
the same single pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.moments import chan_merge
from repro.kernels.plan.plan import QueryPlan, TypedPlan, decode_measures, group_keys

_JNP_OPS = {
    "lt": jnp.less,
    "le": jnp.less_equal,
    "gt": jnp.greater,
    "ge": jnp.greater_equal,
    "eq": jnp.equal,
    "ne": jnp.not_equal,
}


def _plan_kernel(
    *refs, plan: QueryPlan, project: bool, valid_rows, tile_rows, bins,
):
    if project:
        x_ref, lo_ref, invw_ref, proj_ref, stats_ref, hist_ref, nsel_ref = refs
    else:
        x_ref, lo_ref, invw_ref, stats_ref, hist_ref, nsel_ref = refs
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)                        # [T, F]
    t, f = x.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0) + i * tile_rows
    mask = row < valid_rows                                   # [T, 1]
    for p in plan.predicates:
        mask = jnp.logical_and(
            mask, _JNP_OPS[p.op](x[:, p.column : p.column + 1], jnp.float32(p.value))
        )
    nsel_t = jnp.sum(mask.astype(jnp.float32))

    xp = x @ proj_ref[...] if project else x                  # [T, Fp]
    fp = xp.shape[1]
    if plan.group_by is not None:
        lab = x[:, plan.group_by : plan.group_by + 1]         # [T, 1] float labels

    groups = []
    for g in range(plan.groups):
        mg = mask
        if plan.group_by is not None:
            mg = jnp.logical_and(mask, lab == jnp.float32(g))
        cnt = jnp.sum(mg.astype(jnp.float32))
        safe_cnt = jnp.maximum(cnt, 1.0)
        xz = jnp.where(mg, xp, 0.0)
        mean_t = xz.sum(axis=0) / safe_cnt                    # [Fp]
        m2_t = jnp.where(mg, (xp - mean_t) ** 2, 0.0).sum(axis=0)
        min_t = jnp.where(mg, xp, jnp.inf).min(axis=0)
        max_t = jnp.where(mg, xp, -jnp.inf).max(axis=0)
        idx = jnp.clip(
            jnp.floor((xp - lo_ref[0]) * invw_ref[0]).astype(jnp.int32), 0, bins - 1
        )                                                     # [T, Fp]
        onehot = idx[:, :, None] == jax.lax.broadcasted_iota(
            jnp.int32, (t, fp, bins), 2
        )
        onehot = jnp.logical_and(onehot, mg[:, :, None])
        hist_t = onehot.astype(jnp.float32).sum(axis=0)       # [Fp, B]
        groups.append((cnt, mean_t, m2_t, min_t, max_t, hist_t))

    @pl.when(i == 0)
    def _init():
        nsel_ref[0, :] = jnp.full((fp,), nsel_t, jnp.float32)
        for g, (cnt, mean_t, m2_t, min_t, max_t, hist_t) in enumerate(groups):
            stats_ref[5 * g + 0, :] = jnp.full((fp,), cnt, jnp.float32)
            stats_ref[5 * g + 1, :] = mean_t
            stats_ref[5 * g + 2, :] = m2_t
            stats_ref[5 * g + 3, :] = min_t
            stats_ref[5 * g + 4, :] = max_t
            hist_ref[fp * g : fp * (g + 1), :] = hist_t

    @pl.when(i > 0)
    def _fold():
        nsel_ref[0, :] = nsel_ref[0, :] + nsel_t
        for g, (cnt, mean_t, m2_t, min_t, max_t, hist_t) in enumerate(groups):
            # shared Chan combine (repro.core.moments), traced with xp=jnp
            n, mean, m2 = chan_merge(
                stats_ref[5 * g + 0, :],
                stats_ref[5 * g + 1, :],
                stats_ref[5 * g + 2, :],
                cnt, mean_t, m2_t,
                xp=jnp,
            )
            stats_ref[5 * g + 0, :] = n
            stats_ref[5 * g + 1, :] = mean
            stats_ref[5 * g + 2, :] = m2
            stats_ref[5 * g + 3, :] = jnp.minimum(stats_ref[5 * g + 3, :], min_t)
            stats_ref[5 * g + 4, :] = jnp.maximum(stats_ref[5 * g + 4, :], max_t)
            hist_ref[fp * g : fp * (g + 1), :] = (
                hist_ref[fp * g : fp * (g + 1), :] + hist_t
            )


def plan_sketch_pallas(
    x: jax.Array,          # [n, F]
    lo: jax.Array,         # [Fp] projected-grid lower edges
    inv_width: jax.Array,  # [Fp] 1 / bin width (0 for constant features)
    *,
    plan: QueryPlan,
    bins: int,
    tile_rows: int = 128,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run the fused plan kernel; returns ``(stats [G*5, Fp],
    hist [G*Fp, bins], nsel [1, Fp])``.  ``n`` need not divide
    ``tile_rows``; padded rows are masked like failing predicate rows."""
    if x.ndim != 2:
        raise ValueError(f"block must be [n, F], got shape {x.shape}")
    if bins < 1:
        raise ValueError("the fused plan kernel needs bins >= 1")
    n, f = x.shape
    cols = plan.resolve_columns(f)
    proj = None
    if cols != tuple(range(f)):
        proj = np.zeros((f, len(cols)), np.float32)
        proj[list(cols), np.arange(len(cols))] = 1.0
    fp = len(cols)
    g = plan.groups
    n_tiles = max(1, -(-n // tile_rows))
    pad = n_tiles * tile_rows - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))

    kernel = functools.partial(
        _plan_kernel, plan=plan, project=proj is not None, valid_rows=n,
        tile_rows=tile_rows, bins=bins,
    )
    in_specs = [
        pl.BlockSpec((tile_rows, f), lambda i: (i, 0)),
        pl.BlockSpec((1, fp), lambda i: (0, 0)),
        pl.BlockSpec((1, fp), lambda i: (0, 0)),
    ]
    inputs = [
        x.astype(jnp.float32),
        lo.reshape(1, fp).astype(jnp.float32),
        inv_width.reshape(1, fp).astype(jnp.float32),
    ]
    if proj is not None:
        in_specs.append(pl.BlockSpec((f, fp), lambda i: (0, 0)))
        inputs.append(jnp.asarray(proj))
    stats, hist, nsel = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((5 * g, fp), lambda i: (0, 0)),
            pl.BlockSpec((fp * g, bins), lambda i: (0, 0)),
            pl.BlockSpec((1, fp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((5 * g, fp), jnp.float32),
            jax.ShapeDtypeStruct((fp * g, bins), jnp.float32),
            jax.ShapeDtypeStruct((1, fp), jnp.float32),
        ],
        name="plan",
        interpret=interpret,
    )(*inputs)
    return stats, hist, nsel


# ---------------------------------------------------------------------------
# Typed totals: int32 codes, exact predicates, expression sums per group
# ---------------------------------------------------------------------------

LANES = 128  # each total is one broadcast row of the output


def _totals_kernel(x_ref, out_ref, *, plan: TypedPlan, valid_rows, tile_rows):
    i = pl.program_id(0)
    x = x_ref[...]                                            # [T, F] int32
    t = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0) + i * tile_rows
    mask = row < valid_rows                                   # [T, 1]
    column = lambda c: x[:, c : c + 1]  # noqa: E731
    for p in plan.predicates:
        mask = jnp.logical_and(mask, _JNP_OPS[p.op](column(p.column), jnp.int32(p.code)))
    values = decode_measures(column, plan.measures, jnp)     # each [T, 1] float32
    key = group_keys(column, plan.group_by) if plan.group_by else None
    totals = [jnp.sum(mask.astype(jnp.float32))]
    for g in range(plan.groups):
        mg = mask if key is None else jnp.logical_and(mask, key == g)
        totals.append(jnp.sum(mg.astype(jnp.float32)))
        totals += [jnp.sum(jnp.where(mg, v, 0.0)) for v in values]

    @pl.when(i == 0)
    def _init():
        for r, s in enumerate(totals):
            out_ref[r, :] = jnp.full((LANES,), s, jnp.float32)

    @pl.when(i > 0)
    def _fold():
        for r, s in enumerate(totals):
            out_ref[r, :] = out_ref[r, :] + s


def plan_totals_pallas(
    x: jax.Array,   # [n, F] int32
    *,
    plan: TypedPlan,
    tile_rows: int,
    interpret: bool,
) -> jax.Array:
    """Run the typed fold; returns ``[1 + G (1 + M), LANES]`` float32 totals
    (each row broadcast along its lanes): the selected-row count, then per
    group g its count and its M measure sums.  Padded rows are masked like
    rows that fail a predicate; sums accumulate in float32 across tiles."""
    if x.ndim != 2:
        raise ValueError(f"block must be [n, F], got shape {x.shape}")
    n, f = x.shape
    n_tiles = max(1, -(-n // tile_rows))
    pad = n_tiles * tile_rows - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rows = 1 + plan.groups * (1 + len(plan.measures))
    kernel = functools.partial(_totals_kernel, plan=plan, valid_rows=n, tile_rows=tile_rows)
    return pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile_rows, f), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        name="plan",
        interpret=interpret,
    )(x.astype(jnp.int32))
