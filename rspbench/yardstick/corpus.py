"""The benchmark's own corpus generator: HIGGS/SUSY-shaped records from a seed.

A copy of the two-class Gaussian mixture of ``repro.data.synthetic``
(informative features get class-dependent means along one random unit
direction, every feature its own scale in [0.8, 1.4)), stored in class-sorted
order -- the non-random storage the paper starts from -- with the label as the
last float32 column.  It is drawn on the device in one jitted call and copied
to the host once, so set-up pays milliseconds of generation, not seconds of
``numpy.random``.  The same seed gives the same corpus on every run and every
device of one kind; it does not reproduce ``repro.data`` draw for draw.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including seeds above 2**32."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.partial(
    jax.jit, static_argnames=("num_records", "num_features", "num_informative", "class_sep")
)
def _draw(key, *, num_records, num_features, num_informative, class_sep):
    k_dir, k_scale, k_x = jax.random.split(key, 3)
    direction = jax.random.normal(k_dir, (num_informative,), jnp.float32)
    direction = direction / jnp.linalg.norm(direction)
    shift = jnp.zeros((num_features,), jnp.float32).at[:num_informative].set(
        class_sep * direction
    )
    scale = jax.random.uniform(k_scale, (num_features,), jnp.float32, 0.8, 1.4)
    n0 = num_records - num_records // 2
    label = (jnp.arange(num_records) >= n0).astype(jnp.float32)[:, None]
    x = jax.random.normal(k_x, (num_records, num_features), jnp.float32) * scale
    return jnp.concatenate([x + label * shift, label], axis=1)


def make_corpus(cfg: dict, seed: int) -> np.ndarray:
    """``[num_records, num_features + 1]`` float32, class 0 rows first."""
    out = _draw(
        key_from_seed(seed),
        num_records=int(cfg["num_records"]),
        num_features=int(cfg["num_features"]),
        num_informative=int(cfg["num_informative"]),
        class_sep=float(cfg["class_sep"]),
    )
    return np.asarray(out)
