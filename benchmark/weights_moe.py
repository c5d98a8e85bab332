"""The benchmark's own weights for a gated-conv / attention decoder with
routed experts: every leaf from the seed, by its name and shape alone, so
that the program and the plain reference start from the same numbers and
neither takes one the other made.  The rules are the configuration file's
``assumed.initialisation``:

- ``kernel``, ``embedding``, ``router`` and the experts' ``experts_w1/w3/w2``
  (dense and grouped products, the tied table): normal, standard deviation
  0.02;
- ``conv_kernel`` (taps, C): uniform within 1 / sqrt(taps) of zero, torch's
  Conv1d default for a depthwise conv, which the published implementation
  leaves in place (``weights_lm`` draws the same rule for four taps);
- every norm's ``scale``: 1;
- ``expert_bias`` (state beside the parameters, which the forward pass's
  balancing moves and no gradient does): uniform within ``BIAS_BOUND`` of
  zero.  The published code starts it at zero, where a program that weighed
  by ``score + bias`` would pass; ``BIAS_BOUND`` is wide enough that this
  fault fails and narrow enough that the fullest expert stays under three
  times the mean load at the first step (PERF.md s6, PR 33, has both
  readings).

One small jitted draw a leaf, keyed by the leaf's place in sorted order, as
``weights_lm`` makes them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

BIAS_BOUND = 0.05


@functools.partial(jax.jit, static_argnames=("kind", "shape"))
def _draw(key, kind: str, shape: tuple):
    f32 = jnp.float32
    if kind == "normal":
        return 0.02 * jax.random.normal(key, shape, f32)
    if kind == "conv":
        bound = shape[0] ** -0.5
        return jax.random.uniform(key, shape, f32, -bound, bound)
    if kind == "bias":
        return jax.random.uniform(key, shape, f32, -BIAS_BOUND, BIAS_BOUND)
    if kind == "one":
        return jnp.ones(shape, f32)
    raise ValueError(kind)


def kind_of(leaf: str) -> str:
    name = leaf.rsplit("/", 1)[-1]
    if name in ("kernel", "embedding", "router") or name.startswith("experts_w"):
        return "normal"
    if name == "conv_kernel":
        return "conv"
    if name == "expert_bias":
        return "bias"
    if name == "scale":
        return "one"
    raise ValueError(f"no rule for leaf {leaf!r}")


def make_leaf(leaf: str, index: int, shape: tuple, seed: int, sharding=None):
    out = _draw(jax.random.fold_in(seed_key(seed), index), kind_of(leaf),
                tuple(shape))
    return out if sharding is None else jax.device_put(out, sharding)
