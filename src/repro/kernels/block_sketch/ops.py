"""jit'd wrappers and the impl dispatcher for the fused block sketch.

Three equivalent paths (``1e-5``-agreeing on the same block; the one caveat
is values lying *exactly on a bin edge* -- discrete/integer columns -- which
the float32 jax/pallas paths and the float64 ref path may assign to adjacent
bins, moving a downstream quantile by at most one bin width):

* ``impl="ref"``    -- plain numpy (float64), the oracle.
* ``impl="jax"``    -- one jit'd fused pass (scatter-add histogram); vmap'd
  batch variant for stacked blocks.
* ``impl="pallas"`` -- the tiled TPU kernel (compiled on a TPU, interpreted
  elsewhere: :func:`repro.runtime.interpret_mode`), moments folded
  Chan-style across row tiles in VMEM.

``impl="auto"`` consults the shared measured autotuner
(:mod:`repro.kernels.autotune`): the first call at a shape benchmarks the
candidate (impl, tile) grid and persists the winner; with
``REPRO_AUTOTUNE=off`` it pins the deterministic default (numpy oracle on
CPU hosts -- XLA's scatter-add histogram lowers poorly there -- and the
jit'd jax path on accelerators).  All paths return the numpy
:class:`~repro.kernels.block_sketch.ref.BlockSketch`.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune
from repro.kernels.autotune import Candidate
from repro.kernels.block_sketch.kernel import block_sketch_pallas
from repro.kernels.block_sketch.ref import BlockSketch, _grid, block_sketch_ref
from repro.runtime import count_kernel_run, interpret_mode

IMPLS = ("auto", "ref", "jax", "pallas")

PALLAS_TILES = (128, 256, 512, 1024)
DEFAULT_TILE = 128  # legacy hardcoded tile; now only the explicit-impl fallback


@functools.partial(jax.jit, static_argnames=("bins",))
def _sketch_jax(x: jax.Array, lo: jax.Array, inv_width: jax.Array, *, bins: int):
    """Fused one-pass sketch of ``x`` [n, F]; returns (mean, m2, min, max,
    hist) with ``hist`` empty when ``bins == 0``."""
    x = x.astype(jnp.float32)
    n, f = x.shape
    mean = x.mean(axis=0)
    m2 = ((x - mean) ** 2).sum(axis=0)
    mn = x.min(axis=0)
    mx = x.max(axis=0)
    if bins == 0:
        return mean, m2, mn, mx, jnp.zeros((f, 0), jnp.float32)
    idx = jnp.clip(jnp.floor((x - lo) * inv_width).astype(jnp.int32), 0, bins - 1)
    flat = idx + jnp.arange(f, dtype=jnp.int32) * bins
    hist = jnp.zeros((f * bins,), jnp.float32).at[flat.ravel()].add(1.0)
    return mean, m2, mn, mx, hist.reshape(f, bins)


@functools.partial(jax.jit, static_argnames=("bins",))
def batched_block_sketch(blocks: jax.Array, lo: jax.Array, inv_width: jax.Array, *, bins: int):
    """vmap'd fused sketch for stacked blocks [g, n, F] -> per-block sketches."""
    return jax.vmap(lambda b: _sketch_jax(b, lo, inv_width, bins=bins))(blocks)


# jitted so the kernel is traced and compiled once per shape, not per call
_sketch_pallas = jax.jit(block_sketch_pallas, static_argnames=("bins", "tile_rows", "interpret"))


def _inv_width(lo: np.ndarray, hi: np.ndarray, bins: int) -> np.ndarray:
    width = (hi - lo) / max(bins, 1)
    return np.where(width > 0, 1.0 / np.where(width > 0, width, 1.0), 0.0)


def _auto_config(block, *, bins, lo, hi) -> Candidate:
    """Tuner-backed (impl, tile) choice for this block's shape bucket."""
    dev = jax.default_backend()
    default = Candidate("ref") if dev == "cpu" else Candidate("jax")
    shape = np.shape(block)
    n = int(shape[0]) if shape else 0
    f = int(np.prod(shape[1:])) if len(shape) > 1 else 1
    cands = [Candidate("ref"), Candidate("jax")]
    if bins >= 1:
        # off-TPU the Pallas kernel runs interpreted; flagged so the tuner
        # never crowns a config from interpret-mode timings
        interpreted = interpret_mode()
        cands += [Candidate("pallas", t, interpreted=interpreted) for t in PALLAS_TILES]

    def measure(c: Candidate) -> float:
        run = lambda: _sketch(block, bins, lo, hi, c.impl, c.tile_rows)  # noqa: E731
        run()  # warm (jit compile / first-touch) outside the timer
        t0 = time.perf_counter()
        run()
        return time.perf_counter() - t0

    key = autotune.shape_key(n, f) + f"|b{bins}"
    return autotune.choose("block_sketch", key, cands, measure, default=default)


def block_sketch(
    block,
    *,
    bins: int = 0,
    lo=0.0,
    hi=1.0,
    impl: str = "auto",
    tile_rows: int | None = None,
) -> BlockSketch:
    """Fused sketch of one block (any shape ``[n, ...]``; features flatten).

    ``bins=0`` skips the histogram (moments-only fast path; ref/jax only --
    the Pallas kernel always produces a histogram, so ``impl="pallas"`` needs
    ``bins >= 1``).  ``lo`` / ``hi`` are scalars or per-feature arrays.
    ``impl="auto"`` routes through the measured autotuner; an explicit
    ``tile_rows`` pins the Pallas tile.
    """
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r} (one of {IMPLS})")
    if impl == "auto":
        cfg = _auto_config(block, bins=bins, lo=lo, hi=hi)
        impl = cfg.impl
        if tile_rows is None:
            tile_rows = cfg.tile_rows
    if impl == "pallas" and tile_rows is None:
        tile_rows = DEFAULT_TILE
    count_kernel_run("block_sketch", impl, tile_rows if impl == "pallas" else None)
    return _sketch(block, bins, lo, hi, impl, tile_rows)


def _sketch(block, bins, lo, hi, impl, tile_rows) -> BlockSketch:
    """Run one concrete impl (the dispatcher and the tuner's timer)."""
    if impl == "ref":
        return block_sketch_ref(block, bins=bins, lo=lo, hi=hi)
    x = np.asarray(block, dtype=np.float32).reshape(np.shape(block)[0], -1)
    glo, ghi = _grid(lo, hi, x.shape[1])
    if impl == "pallas":
        if bins < 1:
            raise ValueError("impl='pallas' needs bins >= 1")
        stats, hist = _sketch_pallas(
            jnp.asarray(x),
            jnp.asarray(glo),
            jnp.asarray(_inv_width(glo, ghi, bins)),
            bins=bins,
            tile_rows=tile_rows,
            interpret=interpret_mode(),
        )
        stats = np.asarray(stats, dtype=np.float64)
        return BlockSketch(
            count=float(stats[0, 0]),
            mean=stats[1],
            m2=stats[2],
            min=stats[3],
            max=stats[4],
            hist=np.asarray(np.rint(np.asarray(hist)), dtype=np.int64),
            lo=glo,
            hi=ghi,
        )
    mean, m2, mn, mx, hist = _sketch_jax(
        jnp.asarray(x),
        jnp.asarray(glo, dtype=jnp.float32),
        jnp.asarray(_inv_width(glo, ghi, bins), dtype=jnp.float32),
        bins=bins,
    )
    return BlockSketch(
        count=float(x.shape[0]),
        mean=np.asarray(mean, dtype=np.float64),
        m2=np.asarray(m2, dtype=np.float64),
        min=np.asarray(mn, dtype=np.float64),
        max=np.asarray(mx, dtype=np.float64),
        hist=None if bins == 0 else np.asarray(np.rint(np.asarray(hist)), np.int64),
        lo=None if bins == 0 else glo,
        hi=None if bins == 0 else ghi,
    )
