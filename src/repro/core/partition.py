"""Two-stage RSP partitioning (Algorithm 1 of the paper) in three forms.

1. ``two_stage_partition_np``  -- faithful out-of-core-style numpy streaming
   implementation (the HDFS/Spark path of the paper, adapted to local files /
   arrays).  This is the *paper-faithful baseline* used by the Fig-1
   benchmark.
2. ``two_stage_partition_jax`` -- jit-able in-memory implementation: the two
   stages become (vmapped per-block permutation) + (transpose/reshape).
3. ``distributed_rsp_partition`` -- the TPU-native adaptation: Algorithm 1 as
   one ``shard_map`` program whose slice-and-recombine stage is a single
   ``jax.lax.all_to_all`` across the mesh.  Each device holds one original
   block; after the collective, device ``k`` holds RSP block ``k``.

All three produce the same statistical object: a partition ``T = {D_1..D_K}``
where each block is a random sample of ``D`` (Lemma 1).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import RSPSpec

Array = jax.Array


# ---------------------------------------------------------------------------
# Stage helpers
# ---------------------------------------------------------------------------

def _np_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


# ---------------------------------------------------------------------------
# 1. Paper-faithful numpy implementation (streaming-friendly)
# ---------------------------------------------------------------------------

def two_stage_partition_np(
    data: np.ndarray,
    spec: RSPSpec,
    *,
    permute_assignment: bool = True,
) -> np.ndarray:
    """Algorithm 1: returns an array of K RSP blocks, shape [K, n, ...].

    Stage 1 (chunking): ``data`` is viewed as P original blocks in storage
    order.  Stage 2 (randomization): each original block is permuted locally,
    sliced into K sub-blocks of ``delta`` records, and RSP block ``k`` is the
    concatenation of one sub-block drawn *without replacement* from each
    original block (``permute_assignment`` randomizes which sub-block each RSP
    block receives, matching the paper's "select one sub-block from D_i
    without replacement").
    """
    if data.shape[0] != spec.num_records:
        raise ValueError(f"data has {data.shape[0]} records, spec says {spec.num_records}")
    P, K = spec.num_original_blocks, spec.num_blocks
    if spec.num_records % (P * K) != 0:
        # RSPSpec validates this at construction; hand-built spec-like objects
        # get a clear message here instead of an opaque reshape error
        # (mirrors the jax path's divisibility check).
        raise ValueError(
            f"spec unsatisfiable: N={spec.num_records} must be divisible by"
            f" P*K={P * K} (P={P} original blocks x K={K} RSP blocks need"
            " uniform sub-blocks of delta = N/(P*K) records)"
        )
    delta = spec.slice_size
    tail = data.shape[1:]

    out = np.empty((K, spec.block_size, *tail), dtype=data.dtype)
    original = data.reshape(P, spec.original_block_size, *tail)
    for i in range(P):
        rng = _np_rng(spec.seed, 0, i)
        block = original[i][rng.permutation(spec.original_block_size)]
        sub = block.reshape(K, delta, *tail)
        if permute_assignment:
            assign = _np_rng(spec.seed, 1, i).permutation(K)
        else:
            assign = np.arange(K)
        # sub-block assign[k] of original block i -> slice i of RSP block k
        out[:, i * delta : (i + 1) * delta] = sub[assign]
    return out


# ---------------------------------------------------------------------------
# 2. jit-able single-device implementation
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_blocks", "num_original_blocks", "permute_assignment"))
def two_stage_partition_jax(
    data: Array,
    key: Array,
    *,
    num_blocks: int,
    num_original_blocks: int,
    permute_assignment: bool = True,
) -> Array:
    """Algorithm 1 in jnp.  Returns [K, n, ...].

    Stage 2's "permute each original block" is a vmapped
    ``jax.random.permutation``; slice+recombine is a transpose/reshape (the
    memory-movement pattern that ``distributed_rsp_partition`` turns into an
    all_to_all when blocks live on different devices).
    """
    N = data.shape[0]
    P, K = num_original_blocks, num_blocks
    tail = data.shape[1:]
    if N % (P * K) != 0:
        raise ValueError(f"N={N} must be divisible by P*K={P * K}")
    delta = N // (P * K)

    original = data.reshape(P, N // P, *tail)
    perm_keys = jax.random.split(jax.random.fold_in(key, 0), P)
    randomized = jax.vmap(lambda k, b: jax.random.permutation(k, b, axis=0))(
        perm_keys, original
    )
    # [P, K, delta, ...]
    sub = randomized.reshape(P, K, delta, *tail)
    if permute_assignment:
        assign_keys = jax.random.split(jax.random.fold_in(key, 1), P)
        assign = jax.vmap(lambda k: jax.random.permutation(k, K))(assign_keys)
        sub = jax.vmap(lambda s, a: s[a])(sub, assign)
    # recombine: RSP block k = concat over i of sub[i, k]  -> [K, P*delta, ...]
    return sub.transpose(1, 0, 2, *range(3, 3 + len(tail))).reshape(K, P * delta, *tail)


def randomize_dataset(data: Array, key: Array) -> Array:
    """Global randomization (for non-randomized sources; paper Sec. 2)."""
    return jax.random.permutation(key, data, axis=0)


# ---------------------------------------------------------------------------
# 3. Distributed shard_map + all_to_all implementation
# ---------------------------------------------------------------------------

def distributed_rsp_partition(
    data: Array,
    key: Array,
    mesh: jax.sharding.Mesh,
    *,
    axis: str = "data",
    permute_assignment: bool = True,
) -> Array:
    """Algorithm 1 as a collective program over one mesh axis.

    ``data`` is [N, ...] sharded (or shardable) over ``axis`` along dim 0 with
    D devices: device ``i`` holds original block ``i`` (P = D).  Each device
    permutes its shard locally, slices it into D sub-blocks, and a single
    ``all_to_all`` transposes (device, sub-block) so device ``k`` ends with
    RSP block ``k`` (K = D).  The HDFS shuffle-read/write of the paper is
    exactly this collective on the ICI mesh.
    """
    D = mesh.shape[axis]
    N = data.shape[0]
    if N % (D * D) != 0:
        raise ValueError(f"N={N} must be divisible by D^2={D * D} (P=K=D, delta=N/D^2)")
    tail = data.shape[1:]

    in_spec = jax.sharding.PartitionSpec(axis, *(None,) * len(tail))

    def local_fn(shard: Array, key: Array) -> Array:
        # shard: [N/D, ...] -- this device's original block.
        idx = jax.lax.axis_index(axis)
        k = jax.random.fold_in(key, idx)
        block = jax.random.permutation(jax.random.fold_in(k, 0), shard, axis=0)
        sub = block.reshape(D, N // (D * D), *tail)          # D sub-blocks
        if permute_assignment:
            assign = jax.random.permutation(jax.random.fold_in(k, 1), D)
            sub = sub[assign]
        # transpose (device, sub-block): after this, slot j holds the
        # sub-block destined for this device from device j.
        sub = jax.lax.all_to_all(sub[None], axis, split_axis=1, concat_axis=0)[:, 0]
        return sub.reshape(N // D, *tail)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(in_spec, jax.sharding.PartitionSpec()),
        out_specs=in_spec,
    )
    out = fn(data, key)
    # [N, ...] where contiguous slabs of n = N/D records are the RSP blocks.
    return out.reshape(D, N // D, *tail)


# ---------------------------------------------------------------------------
# Validation helpers (Definition 2 / Definition 3 empirical checks)
# ---------------------------------------------------------------------------

def _lex_sorted_rows(x: np.ndarray) -> np.ndarray:
    """Rows of ``x`` as a byte matrix, sorted lexicographically as *whole
    rows* -- row (record) identity is preserved, unlike a column-wise sort."""
    x = np.asarray(x)
    n = x.shape[0] if x.ndim else 0
    feat = int(np.prod(x.shape[1:], dtype=np.int64))  # explicit: -1 breaks on n=0
    rows = np.ascontiguousarray(x.reshape(n, feat))
    b = rows.view(np.uint8).reshape(n, -1) if rows.size else rows.view(np.uint8)
    if b.shape[0] <= 1 or b.shape[1] == 0:
        return b  # nothing to sort (and lexsort needs >= 1 key column)
    return b[np.lexsort(b.T[::-1])]


def is_partition(blocks: np.ndarray, data: np.ndarray) -> bool:
    """Definition 2: blocks form a partition of ``data`` (as multisets of
    whole records).  Rows are compared as units: lexicographically sorting
    complete rows keeps record identity, where the per-column sort this
    replaces validated any pair with equal per-column byte multisets."""
    blocks = np.asarray(blocks)
    data = np.asarray(data)
    flat = blocks.reshape(-1, *blocks.shape[2:])
    if flat.shape != data.shape:
        return False
    return bool(np.array_equal(_lex_sorted_rows(flat), _lex_sorted_rows(data)))


def empirical_cdf(x: np.ndarray, thresholds: Sequence[float]) -> np.ndarray:
    """F(t) for each threshold -- used by Lemma-1 style unbiasedness tests."""
    x = np.asarray(x).reshape(-1)
    t = np.asarray(thresholds).reshape(-1, 1)
    return (x[None, :] <= t).mean(axis=1)
