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

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: everywhere but on a TPU."""
    return jax.default_backend() != "tpu"


def count_kernel_run(kernel: str, impl: str, tile_rows: int | None) -> None:
    """Count one dispatch of ``kernel`` (telemetry on only)."""
    from repro import obs

    if obs.enabled():
        obs.get_registry().counter(
            "rsp_kernel_runs_total", "kernel dispatches by impl, tile and Pallas mode",
            kernel=kernel, impl=impl, tile="-" if tile_rows is None else str(tile_rows),
            interpret=str(impl == "pallas" and interpret_mode()).lower(),
        ).inc()


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
