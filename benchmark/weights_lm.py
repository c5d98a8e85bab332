"""The benchmark's own weights for a hybrid state-space decoder: every leaf
from the seed, by its name and shape alone, so that the program and the
plain reference start from the same numbers and neither takes one the other
made.  The rules are the configuration file's ``assumed.initialisation``:

- ``kernel`` and ``embedding`` (dense products, the tied table): normal,
  standard deviation 0.02;
- ``conv_kernel`` (taps, C) and ``conv_bias``: uniform within
  1 / sqrt(taps) of zero, torch's Conv1d default, which the published
  implementation leaves in place;
- ``A_log``: log of a uniform draw in [1, 16];  ``dt_bias``: the inverse
  softplus of a log-uniform step in [1e-3, 0.1];  ``D`` and every norm's
  ``scale``: 1.

One small jitted draw a leaf, keyed by the leaf's place in sorted order (a
dozen distinct shapes, so a dozen programs): a single draw for all 772 M
numbers, cut up afterwards, would hold them twice.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

CONV_TAPS = 4


@functools.partial(jax.jit, static_argnames=("kind", "shape"))
def _draw(key, kind: str, shape: tuple):
    f32 = jnp.float32
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, f32)
    if kind == "conv":
        bound = 1.0 / math.sqrt(CONV_TAPS)
        return jax.random.uniform(key, shape, f32, -bound, bound)
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "one":
        return jnp.ones(shape, f32)
    raise ValueError(kind)


def kind_of(leaf: str) -> str:
    name = leaf.rsplit("/", 1)[-1]
    if name in ("kernel", "embedding"):
        return "normal"
    if name in ("conv_kernel", "conv_bias"):
        return "conv"
    if name in ("A_log", "dt_bias"):
        return name
    if name in ("D", "scale"):
        return "one"
    raise ValueError(f"no rule for leaf {leaf!r}")


def make_leaf(leaf: str, index: int, shape: tuple, seed: int, sharding=None):
    out = _draw(jax.random.fold_in(seed_key(seed), index), kind_of(leaf),
                tuple(shape))
    return out if sharding is None else jax.device_put(out, sharding)
