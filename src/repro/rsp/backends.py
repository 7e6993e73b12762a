"""Partition backend registry behind ``rsp.partition(..., backend=...)``.

Each backend runs Algorithm 1 (two-stage RSP partitioning) through a
different execution substrate and declares a *capability predicate* that
says whether it can serve a given request:

    np        -- paper-faithful numpy in-memory path; the fallback for
                 non-float / non-2D array data.
    np_stream -- out-of-core single-pass scatter (``repro.rsp.ingest``):
                 anything ``as_chunk_source`` can adapt (memmapped ``.npy``,
                 chunk-file directories, record-batch iterators, arrays)
                 streams to a stored RSP (``out=``) or an in-RAM assembly
                 with O(chunk) peak memory; bit-identical to ``np``.
    jax       -- jit'd in-memory path (vmapped permutation + reshape).
    shard_map -- one collective program over a device mesh (all_to_all);
                 requires a mesh with P = K = mesh size.
    pallas    -- the ``rsp_shuffle`` TPU kernel: hierarchical tile shuffle
                 per original block with the delta-slice dealing expressed
                 as DMA scheduling; requires 2-D floating-point data and,
                 where it compiles (a TPU), a delta that fills whole
                 sublane tiles.

``backend="auto"`` selects shard_map when a mesh is supplied, Pallas when
the kernel's shape constraints hold *and* a TPU is attached (off-TPU the
kernel would run in interpret mode, slower than numpy), ``np_stream`` for
every non-array source (paths, chunk directories, batch iterators,
memmaps -- the corpora that never fit in RAM) and whenever ``out=`` asks
for a direct-to-store write, and the in-memory numpy path otherwise
(highest ``auto_priority`` whose predicates pass).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.partition import (
    distributed_rsp_partition,
    two_stage_partition_jax,
    two_stage_partition_np,
)
from repro.core.registry import RSPStore
from repro.core.types import RSPSpec
from repro.kernels.rsp_shuffle.ops import rsp_randomize_block
from repro.runtime import interpret_mode, to_device, to_host
from repro.rsp.ingest import (
    is_stream_source,
    maybe_chunk_source,
    resolve_stream_source,
    stream_partition,
)

AUTO = "auto"


@dataclasses.dataclass(frozen=True)
class PartitionRequest:
    """Everything a backend needs to decide eligibility and to run.

    ``data`` is array-like [N, ...] for the in-memory backends, or anything
    ``repro.rsp.ingest.as_chunk_source`` adapts (a ``.npy`` path, a chunk
    directory, a record-batch iterator, a memmap) for ``np_stream``.  The
    streaming fields (``out``, ``with_summaries``, ``num_classes``,
    ``label_column``, ``chunk_records``) are read only by ``np_stream``:
    with ``out`` set its result is the finished :class:`RSPStore` (sketches
    folded during the write land in the manifest) instead of stacked blocks.
    """

    data: Any                                   # array-like [N, ...] or ChunkSource
    spec: RSPSpec
    mesh: jax.sharding.Mesh | None = None
    mesh_axis: str = "data"
    permute_assignment: bool = True
    out: str | None = None
    with_summaries: bool = True
    num_classes: int | None = None
    label_column: int = -1
    chunk_records: int | None = None


@dataclasses.dataclass(frozen=True)
class PartitionBackend:
    """A named Algorithm-1 implementation with a capability predicate.

    ``supports`` returns ``None`` when the backend *can* serve the request
    and a human-readable refusal reason otherwise; it gates explicit
    ``backend=<name>`` dispatch.  ``auto_eligible`` (optional) adds a
    preference predicate consulted only by ``backend="auto"`` -- a backend
    that would run but poorly (e.g. an interpret-mode kernel off-TPU) can
    decline auto-selection while remaining explicitly requestable.  ``run``
    returns the stacked RSP blocks [K, n, ...] as a numpy array, or -- for
    streaming backends writing directly to ``request.out`` -- the finished
    :class:`RSPStore`.
    """

    name: str
    capabilities: frozenset[str]
    supports: Callable[[PartitionRequest], str | None]
    run: Callable[[PartitionRequest], "np.ndarray | RSPStore"]
    auto_priority: int
    auto_eligible: Callable[[PartitionRequest], str | None] | None = None


_REGISTRY: dict[str, PartitionBackend] = {}


def register_backend(backend: PartitionBackend) -> PartitionBackend:
    if backend.name == AUTO:
        raise ValueError(f"'{AUTO}' is reserved for automatic selection")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> PartitionBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def backend_eligibility(request: PartitionRequest) -> dict[str, str | None]:
    """Map backend name -> None (eligible) or the refusal reason."""
    return {name: b.supports(request) for name, b in _REGISTRY.items()}


def select_backend(request: PartitionRequest) -> PartitionBackend:
    """The ``backend="auto"`` rule: highest-priority eligible backend."""
    ranked = sorted(_REGISTRY.values(), key=lambda b: -b.auto_priority)
    reasons: list[str] = []
    for b in ranked:
        reason = b.supports(request)
        if reason is None and b.auto_eligible is not None:
            reason = b.auto_eligible(request)
        if reason is None:
            return b
        reasons.append(f"{b.name}: {reason}")
    raise ValueError("no backend can serve this request; " + "; ".join(reasons))


def run_partition(
    request: PartitionRequest, backend: str = AUTO
) -> tuple["np.ndarray | RSPStore", str]:
    """Dispatch a partition request; returns (result, backend) where the
    result is the stacked blocks [K, n, ...] or, for a streaming backend
    writing to ``request.out``, the finished :class:`RSPStore`."""
    if not isinstance(request.data, np.ndarray):
        # resolve a path/directory/iterator input to its ChunkSource ONCE:
        # every capability predicate and the eventual run then reuse it
        # instead of re-listing directories and re-reading .npy headers
        src = resolve_stream_source(request.data, chunk_records=request.chunk_records)
        if src is not None and src is not request.data:
            request = dataclasses.replace(request, data=src)
    b = select_backend(request) if backend == AUTO else get_backend(backend)
    if backend != AUTO:
        reason = b.supports(request)
        if reason is not None:
            raise ValueError(f"backend {b.name!r} cannot serve this request: {reason}")
    return b.run(request), b.name


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------

def _non_array_source(req: PartitionRequest) -> str | None:
    """Refusal reason the in-memory backends share: they can serve any
    ndarray (memmaps included -- they materialize on use) but not a
    chunk-stream object, which only ``np_stream`` knows how to drain."""
    if not isinstance(req.data, np.ndarray) and is_stream_source(req.data):
        return "streaming ChunkSource input needs backend='np_stream'"
    return None


def _supports_np(req: PartitionRequest) -> str | None:
    reason = _non_array_source(req)
    if reason is not None:
        return reason
    return None  # the in-memory fallback serves every array the spec admits


def _run_np(req: PartitionRequest) -> np.ndarray:
    return two_stage_partition_np(
        np.asarray(req.data), req.spec, permute_assignment=req.permute_assignment
    )


def _supports_np_stream(req: PartitionRequest) -> str | None:
    if maybe_chunk_source(req.data) is None:
        return (
            "input is not chunkable (need an array, a .npy path, a chunk-file"
            " directory, a batch sequence, or a ChunkSource)"
        )
    return None


def _auto_np_stream(req: PartitionRequest) -> str | None:
    # memmaps, paths, directories, and ChunkSources always stream; in-RAM
    # arrays stream only for direct-to-store writes (out=); everything else
    # (plain arrays, ambiguous record lists) keeps the np path, where it is
    # served with the same bits and no scatter bookkeeping.
    if is_stream_source(req.data):
        return None
    if req.out is not None and isinstance(req.data, np.ndarray):
        return None
    return "in-memory input without out= is served by the np path"


def _run_np_stream(req: PartitionRequest) -> np.ndarray | RSPStore:
    # without out= the facade gets stacked in-memory blocks back and computes
    # summaries the same way as every in-memory backend, so folding sketches
    # during the scatter would be duplicated work; with out= the folded
    # sketches ARE the store's manifest summaries (no second corpus scan)
    result, _ = stream_partition(
        req.data,
        req.spec,
        out=req.out,
        permute_assignment=req.permute_assignment,
        with_summaries=req.with_summaries and req.out is not None,
        num_classes=req.num_classes,
        label_column=req.label_column,
        chunk_records=req.chunk_records,
    )
    return result


def _supports_jax(req: PartitionRequest) -> str | None:
    reason = _non_array_source(req)
    if reason is not None:
        return reason
    return None  # in-memory jit path; spec divisibility is validated upstream


def _run_jax(req: PartitionRequest) -> np.ndarray:
    out = two_stage_partition_jax(
        jnp.asarray(req.data),
        jax.random.PRNGKey(req.spec.seed),
        num_blocks=req.spec.num_blocks,
        num_original_blocks=req.spec.num_original_blocks,
        permute_assignment=req.permute_assignment,
    )
    return np.asarray(out)


def _supports_shard_map(req: PartitionRequest) -> str | None:
    reason = _non_array_source(req)
    if reason is not None:
        return reason
    if req.mesh is None:
        return "requires a device mesh"
    if req.mesh_axis not in req.mesh.shape:
        return f"mesh has no axis {req.mesh_axis!r}"
    d = req.mesh.shape[req.mesh_axis]
    if req.spec.num_blocks != d or req.spec.num_original_blocks != d:
        return (
            f"needs P = K = mesh size ({d}), got P={req.spec.num_original_blocks}"
            f" K={req.spec.num_blocks}"
        )
    if req.spec.num_records % (d * d) != 0:
        return f"N={req.spec.num_records} not divisible by mesh_size^2={d * d}"
    return None


def _run_shard_map(req: PartitionRequest) -> np.ndarray:
    # place each original block straight onto its device: jnp.asarray would
    # first copy the whole corpus to device 0
    rank = np.ndim(req.data)
    spec = jax.sharding.PartitionSpec(req.mesh_axis, *(None,) * (rank - 1))
    out = distributed_rsp_partition(
        jax.device_put(req.data, jax.sharding.NamedSharding(req.mesh, spec)),
        jax.random.PRNGKey(req.spec.seed),
        req.mesh,
        axis=req.mesh_axis,
        permute_assignment=req.permute_assignment,
    )
    return np.asarray(out)


def _supports_pallas(req: PartitionRequest) -> str | None:
    reason = _non_array_source(req)
    if reason is not None:
        return reason
    shape = np.shape(req.data)
    if len(shape) != 2:
        return f"kernel needs 2-D [records, features] data, got shape {shape}"
    dtype = getattr(req.data, "dtype", None)
    if dtype is None or not np.issubdtype(np.dtype(dtype), np.floating):
        return f"kernel shuffles via an MXU matmul and needs a float dtype, got {dtype}"
    if not req.permute_assignment:
        return "sub-block assignment permutation is intrinsic to the tile dealing"
    # compiled, the kernel's tile is delta rows of the block, and Mosaic
    # tiles rows in sublanes of 8 32-bit words (16 for 16-bit dtypes)
    sublanes = 32 // min(np.dtype(dtype).itemsize, 4)
    if not interpret_mode() and req.spec.slice_size % sublanes:
        return (
            f"delta={req.spec.slice_size} rows is not a multiple of {sublanes},"
            " the sublane tiling the TPU compiler requires"
        )
    return None


def _auto_pallas(req: PartitionRequest) -> str | None:
    # off-TPU the kernel runs in interpret mode, far slower than the numpy
    # path -- don't win auto-selection there (explicit backend="pallas"
    # still works, e.g. for kernel plumbing tests).
    if interpret_mode():
        return "interpret-mode off-TPU is slower than np (request it explicitly)"
    return None


def _run_pallas(req: PartitionRequest) -> np.ndarray:
    """Algorithm 1 with the randomize step on the ``rsp_shuffle`` kernel.

    Per original block, ``tile_rows = delta`` makes the kernel's tile
    permutation *be* the sub-block dealing: output tile k of block i is the
    (intra-shuffled) sub-block destined for RSP block k.  Lemma 1 applies at
    slice granularity (see kernels.rsp_shuffle.kernel).

    Original blocks stream through the device one at a time, so device
    memory holds one block, not the corpus and its shuffled copies.
    """
    spec = req.spec
    P, K, delta = spec.num_original_blocks, spec.num_blocks, spec.slice_size
    data = np.asarray(req.data)
    R, F = spec.original_block_size, data.shape[1]
    key = jax.random.PRNGKey(spec.seed)
    out = np.empty((K, P * delta, F), jax.dtypes.canonicalize_dtype(data.dtype))
    with obs.span("partition.shuffle", blocks=P):
        for i in range(P):
            with obs.span("shuffle.block", block=i):
                sub = rsp_randomize_block(
                    to_device(data[i * R : (i + 1) * R], "rsp_shuffle"),
                    jax.random.fold_in(key, i), tile_rows=delta,
                )
                # tile k of original block i is its sub-block dealt to RSP block k
                (sub,) = to_host((sub,), "rsp_shuffle")
                out[:, i * delta : (i + 1) * delta] = sub.reshape(K, delta, F)
    return out


register_backend(
    PartitionBackend(
        name="np",
        capabilities=frozenset({"in-memory"}),
        supports=_supports_np,
        run=_run_np,
        auto_priority=20,
    )
)
register_backend(
    PartitionBackend(
        name="np_stream",
        capabilities=frozenset({"streaming", "out-of-core", "direct-to-store"}),
        supports=_supports_np_stream,
        run=_run_np_stream,
        # above np: wins auto for everything chunkable unless auto_eligible
        # hands plain in-RAM arrays back to the np path
        auto_priority=25,
        auto_eligible=_auto_np_stream,
    )
)
register_backend(
    PartitionBackend(
        name="jax",
        capabilities=frozenset({"in-memory", "jit"}),
        supports=_supports_jax,
        run=_run_jax,
        auto_priority=10,
    )
)
register_backend(
    PartitionBackend(
        name="shard_map",
        capabilities=frozenset({"in-memory", "collective", "mesh"}),
        supports=_supports_shard_map,
        run=_run_shard_map,
        auto_priority=40,
    )
)
register_backend(
    PartitionBackend(
        name="pallas",
        capabilities=frozenset({"in-memory", "kernel"}),
        supports=_supports_pallas,
        run=_run_pallas,
        auto_priority=30,
        auto_eligible=_auto_pallas,
    )
)
