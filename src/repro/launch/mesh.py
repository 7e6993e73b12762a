"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state.  The dry-run launcher
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else (tests, benchmarks) sees the real single device.

Target: TPU v5e, 256 chips per pod (16x16 ICI torus), 2 pods over DCN.
Axes: ("pod",) data-parallel over DCN; ("data",) data-parallel over ICI;
("model",) tensor/expert-parallel over ICI.
"""

from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary small mesh over available devices (tests / examples)."""
    n = int(np.prod(shape))
    devs = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(devs, axes)


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s, 1,600 Gbit/s of interconnect over four links).
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bw": 819e9,            # bytes/s
        "ici_link_bw": 50e9,        # bytes/s per link
        "hbm_bytes": 16 * 1024**3,
    },
}
TARGET_DEVICE_KIND = "TPU v5 lite"  # the chip the production mesh describes


def peaks(device_kind: str) -> dict[str, float]:
    """The peak table row for ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: {sorted(PEAKS)})"
        ) from None
