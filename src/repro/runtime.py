"""How JAX runs on this machine: Pallas mode and the compile cache.

``interpret_mode()`` is the one rule for every Pallas kernel in the repo:
the kernels are Mosaic (TPU) kernels, so they compile on a TPU and run in
interpret mode on every other backend -- the CPU of the test suite.  No
entry point takes an ``interpret`` argument; the kernels' ops layers ask
this function, so a tuner times the same mode the dispatcher then runs.

``count_kernel_run()`` records, when telemetry is on, which impl, tile
and Pallas mode each kernel dispatch ran with
(``rsp_kernel_runs_total{kernel, impl, tile, interpret}``), so a run on the
chip can show that its kernels ran compiled.

``to_device()`` and ``to_host()`` are the kernels' copies between host
and device: each runs under a span (``kernel.h2d``, ``kernel.readback``)
that a profiler trace shows beside the device ops, and ``to_device()``
counts its bytes (``rsp_h2d_bytes_total{kernel}``) when telemetry is on.

``use_compile_cache()`` gives entry points (``chip_smoke.py``, the
examples and benchmarks) JAX's persistent compilation cache.  Where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
changed; otherwise the cache lives at a fixed path inside the checkout,
because the path is part of the cache key and a moving directory never
hits.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: everywhere but on a TPU."""
    return jax.default_backend() != "tpu"


def count_kernel_run(kernel: str, impl: str, tile_rows: int | None) -> None:
    """Count one dispatch of ``kernel`` (telemetry on only)."""
    if obs.enabled():
        obs.get_registry().counter(
            "rsp_kernel_runs_total", "kernel dispatches by impl, tile and Pallas mode",
            kernel=kernel, impl=impl, tile="-" if tile_rows is None else str(tile_rows),
            interpret=str(impl == "pallas" and interpret_mode()).lower(),
        ).inc()


def to_device(x: np.ndarray, kernel: str) -> jax.Array:
    """``jnp.asarray(x)`` for ``kernel`` under a ``kernel.h2d`` span, waited
    for: on a TPU ``jnp.asarray`` returns before the copy is made (its layout
    transposition runs on the runtime's threads), so the wait keeps the whole
    copy inside the span, traced or not."""
    nbytes = int(x.nbytes)
    with obs.span("kernel.h2d", kernel=kernel, bytes=nbytes):
        out = jnp.asarray(x).block_until_ready()
    if obs.enabled():
        obs.get_registry().counter(
            "rsp_h2d_bytes_total", "bytes copied host to device for a kernel", kernel=kernel,
        ).inc(nbytes)
    return out


def to_host(arrays, kernel: str) -> tuple[np.ndarray, ...]:
    """``np.asarray`` of each of ``kernel``'s outputs under a
    ``kernel.readback`` span, the wait for the device included."""
    with obs.span("kernel.readback", kernel=kernel):
        return tuple(np.asarray(a) for a in arrays)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
