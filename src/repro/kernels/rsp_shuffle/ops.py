"""jit'd wrapper: generate the hierarchical permutation from a PRNG key and
apply the kernel.  ``rsp_randomize_block`` is the on-device realization of
Algorithm 1's per-block randomize step.

With ``whole_block=True`` the block's rows are first permuted as a whole (a
device gather), so each tile the kernel deals is a uniformly random subset
of the block, as Algorithm 1 has it; without it a tile is a run of
consecutive rows, shuffled within itself.

``tile_rows=None`` (the default) asks the shared autotuner for the fastest
tile among the divisors of ``R``; passing an explicit tile pins it -- the
partition backends do exactly that (``tile_rows=delta``) because the tile
*is* part of the permutation's definition there, and retuning would change
which rows land in which RSP block."""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.autotune import Candidate
from repro.kernels.rsp_shuffle.kernel import rsp_shuffle_pallas
from repro.runtime import count_kernel_run, interpret_mode

SHUFFLE_TILES = (64, 128, 256, 512, 1024)
DEFAULT_SHUFFLE_TILE = 256


def make_permutations(key: jax.Array, n_tiles: int, tile_rows: int):
    k1, k2 = jax.random.split(key)
    tile_perm = jax.random.permutation(k1, n_tiles).astype(jnp.int32)
    intra = jax.vmap(lambda k: jax.random.permutation(k, tile_rows))(
        jax.random.split(k2, n_tiles)
    ).astype(jnp.int32)
    return tile_perm, intra


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret", "whole_block"))
def _randomize(x: jax.Array, key: jax.Array, *, tile_rows: int, interpret: bool,
               whole_block: bool = False) -> jax.Array:
    R = x.shape[0]
    if R % tile_rows:
        raise ValueError(f"R={R} must be divisible by tile_rows={tile_rows}")
    if whole_block:
        key, k_rows = jax.random.split(key)
        x = jnp.take(x, jax.random.permutation(k_rows, R), axis=0)
    tile_perm, intra = make_permutations(key, R // tile_rows, tile_rows)
    return rsp_shuffle_pallas(x, tile_perm, intra, tile_rows=tile_rows, interpret=interpret)


def _auto_tile(x: jax.Array) -> int:
    """Tuner-backed shuffle tile: fastest divisor of ``R`` at this shape.

    Off-TPU the kernel runs in Pallas interpret mode, so every candidate is
    flagged ``interpreted`` and the tuner falls back to the deterministic
    default instead of crowning a config from interpreter timings."""
    R, d = int(x.shape[0]), int(x.shape[1]) if x.ndim > 1 else 1
    valid = [t for t in SHUFFLE_TILES if R % t == 0]
    if not valid:
        raise ValueError(
            f"no tile in {SHUFFLE_TILES} divides R={R}; pass tile_rows explicitly"
        )
    default_tile = DEFAULT_SHUFFLE_TILE if R % DEFAULT_SHUFFLE_TILE == 0 else valid[-1]
    interpret = interpret_mode()
    cands = [Candidate("pallas", t, interpreted=interpret) for t in valid]

    def measure(c: Candidate) -> float:
        key = jax.random.PRNGKey(0)
        _randomize(x, key, tile_rows=c.tile_rows, interpret=interpret).block_until_ready()
        t0 = time.perf_counter()
        _randomize(x, key, tile_rows=c.tile_rows, interpret=interpret).block_until_ready()
        return time.perf_counter() - t0

    cfg = autotune.choose(
        "rsp_shuffle", autotune.shape_key(R, d), cands, measure,
        default=Candidate("pallas", default_tile),
    )
    return cfg.tile_rows if cfg.tile_rows in valid else default_tile


def rsp_randomize_block(
    x: jax.Array, key: jax.Array, *, tile_rows: int | None = None, whole_block: bool = False
) -> jax.Array:
    """Randomize one original block [R, D] on-device (hierarchical shuffle).

    ``tile_rows=None`` autotunes over the divisors of ``R``; an explicit
    tile is honored verbatim (and is part of the shuffle's definition --
    two calls with different tiles produce different permutations).
    ``whole_block`` permutes all ``R`` rows before the tiles are dealt."""
    if tile_rows is None:
        tile_rows = _auto_tile(x)
    count_kernel_run("rsp_shuffle", "pallas", tile_rows)
    return _randomize(x, key, tile_rows=tile_rows, interpret=interpret_mode(),
                      whole_block=whole_block)


_shuffle = jax.jit(rsp_shuffle_pallas, static_argnames=("tile_rows", "interpret"))


def rsp_shuffle(
    x: jax.Array, tile_perm: jax.Array, intra_perm: jax.Array, *, tile_rows: int
) -> jax.Array:
    """Apply a given hierarchical permutation (``ref.py`` is the oracle)."""
    return _shuffle(x, tile_perm, intra_perm, tile_rows=tile_rows, interpret=interpret_mode())
