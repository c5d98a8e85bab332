"""The benchmark's own weights: every leaf from the seed, in one jitted call.

The program and the plain reference are both handed these, so neither takes
a number the other made.  The rule goes by a leaf's name and shape alone:

- ``kernel`` of a convolution (4-D, HWIO): normal, variance 2 / fan_out
  (He et al., arXiv:1502.01852, as the upstream recipe initialises);
- ``kernel`` of a dense layer (2-D): normal, variance 1 / fan_in;
- ``scale``: 1, or the value the configuration's ``init_scales`` gives the
  first pattern (``fnmatch``) that matches the leaf;  ``bias``: 0.

``init_scales`` is how a configuration damps the last BatchNorm of every
residual branch (Goyal et al., arXiv:1706.02677 s5.1 start it at 0, as the
zoo does; a small value keeps a gradient in the branch at the first step).
With every scale at 1 a 50-layer BatchNorm net at a random start is chaotic:
bfloat16 rounding alone turns the median leaf's gradient by 40-100%, and no
number separates bfloat16 from fp8 (PR 25's readings, PERF.md s6).
"""

from __future__ import annotations

import fnmatch
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A raw key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                       jnp.uint32)


def _std(leaf: str, shape: tuple) -> float | None:
    name = leaf.rsplit("/", 1)[-1]
    if name == "kernel" and len(shape) == 4:
        return math.sqrt(2.0 / (shape[0] * shape[1] * shape[3]))
    if name == "kernel" and len(shape) == 2:
        return math.sqrt(1.0 / shape[0])
    if name in ("scale", "bias"):
        return None
    raise ValueError(f"no rule for leaf {leaf!r} of shape {shape}")


def _fill(leaf: str, scales: dict | None) -> float:
    if not leaf.endswith("scale"):
        return 0.0
    for pattern, value in (scales or {}).items():
        if fnmatch.fnmatchcase(leaf, pattern):
            return float(value)
    return 1.0


def make(shapes: dict, seed: int, shardings: dict | None = None,
         scales: dict | None = None) -> dict:
    """``shapes``: leaf path -> shape.  Returns leaf path -> float32 array,
    placed as ``shardings`` says where given.  ``scales``: pattern -> the
    value of the ``scale`` leaves it matches."""
    leaves = sorted(shapes)
    stds = {leaf: _std(leaf, tuple(shapes[leaf])) for leaf in leaves}
    total = sum(math.prod(shapes[leaf]) for leaf in leaves
                if stds[leaf] is not None)

    def build(key):
        # one draw for every kernel, cut up in the leaves' sorted order: a
        # draw per leaf costs the TPU's compiler a random-bits program each
        draw = jax.random.normal(key, (total,), jnp.float32)
        out, at = {}, 0
        for leaf in leaves:
            shape = tuple(shapes[leaf])
            if stds[leaf] is None:
                out[leaf] = jnp.full(shape, _fill(leaf, scales), jnp.float32)
            else:
                n = math.prod(shape)
                out[leaf] = stds[leaf] * draw[at:at + n].reshape(shape)
                at += n
        return out

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))
