"""Spatial (context) parallelism: shard ACTIVATIONS over the image height
axis with ring halo exchange.

The reference has no analog — its "big activation" axis is image resolution,
handled only by shrinking batch sizes (SURVEY §5 long-context: OOM notes
ResNet/pytorch/train.py:141-148).  TPU-native answer: treat H like a sequence
axis — a ``spatial`` mesh axis shards rows across chips, convolutions run on
row shards after exchanging ``halo`` boundary rows with ring neighbours via
``lax.ppermute`` (ICI neighbour traffic, the same pattern as ring attention's
block exchange), so images too large for one chip's HBM train without
changing the model.

Composable with data parallelism: mesh {"data": d, "spatial": s}.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deep_vision_tpu.parallel.mesh import SPATIAL_AXIS  # single source


def _same_pad(dim: int, k: int, s: int) -> tuple[int, int]:
    """XLA's SAME padding split (low, high) for one dimension: total
    padding so out = ceil(dim/s), remainder goes to the high side."""
    total = max((-(-dim // s) - 1) * s + k - dim, 0)
    return total // 2, total - total // 2


def halo_exchange(x, halo: int, halo_bottom: int | None = None,
                  axis_name: str = SPATIAL_AXIS, fill_value=0.0):
    """Per-shard (B, H_shard, W, C) → (B, top + H_shard + bottom, W, C).

    ``halo`` rows arrive from the shard above and ``halo_bottom``
    (default: same) from the shard below, via two ring ppermutes; the
    outermost shards get ``fill_value`` rows instead (SAME-padding
    semantics at the true image edge: 0 for convolution, -inf for max
    pooling).  Asymmetric halos are what SAME-under-stride requires
    (XLA puts the odd padding row on the high side).
    """
    top = halo
    bottom = halo if halo_bottom is None else halo_bottom
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    parts = []
    if top:
        bot_rows = x[:, -top:]   # my last rows → neighbour below's top halo
        from_above = jax.lax.ppermute(bot_rows, axis_name, fwd)
        parts.append(jnp.where(idx == 0,
                               jnp.full_like(from_above, fill_value),
                               from_above))
    parts.append(x)
    if bottom:
        top_rows = x[:, :bottom]  # my first rows → neighbour above's bottom
        from_below = jax.lax.ppermute(top_rows, axis_name, bwd)
        parts.append(jnp.where(idx == n - 1,
                               jnp.full_like(from_below, fill_value),
                               from_below))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else x


def _check_row_split(H: int, n_sp: int, sh: int, kh: int):
    """Shared divisibility/halo validation; returns (rows, pad_t, pad_b)."""
    rows = H // n_sp
    if H % n_sp:
        raise ValueError(f"H={H} not divisible by spatial={n_sp}")
    if rows % sh:
        raise ValueError(
            f"rows/shard={rows} not divisible by row stride {sh}: shard "
            f"boundaries would fall between output rows — reshard first")
    pad_top, pad_bottom = _same_pad(H, kh, sh)
    if max(pad_top, pad_bottom) > rows:
        raise ValueError(
            f"halo {max(pad_top, pad_bottom)} exceeds rows/shard={rows}: "
            f"window too tall for this mesh")
    return rows, pad_top, pad_bottom


def spatial_max_pool(x, window=(2, 2), strides=None, *, mesh: Mesh):
    """SAME max-pool with x row-sharded over the ``spatial`` axis — the
    companion to :func:`spatial_conv` (ResNet stem 3×3/2 pool, Hourglass
    2×2/2 downsamples).  Identical to the unsharded ``nn.max_pool(...,
    padding="SAME")``.  Edge halos fill with -inf (the max identity), so
    true-edge windows see exactly XLA's SAME padding.
    """
    wh, ww = tuple(window)
    sh, sw = tuple(strides) if strides is not None else (wh, ww)
    H, W = x.shape[1], x.shape[2]
    _, pad_top, pad_bottom = _check_row_split(H, mesh.shape[SPATIAL_AXIS],
                                              sh, wh)
    pad_w = _same_pad(W, ww, sw)
    neg_inf = jnp.array(-jnp.inf, x.dtype)

    def shard_fn(xs):
        padded = halo_exchange(xs, pad_top, pad_bottom,
                               fill_value=-jnp.inf)
        return jax.lax.reduce_window(
            padded, neg_inf, jax.lax.max, (1, wh, ww, 1), (1, sh, sw, 1),
            ((0, 0), (0, 0), pad_w, (0, 0)))

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=P(None, SPATIAL_AXIS, None, None),
                   out_specs=P(None, SPATIAL_AXIS, None, None))
    x = jax.device_put(x, NamedSharding(mesh, P(None, SPATIAL_AXIS,
                                                None, None)))
    return fn(x)


def spatial_conv(x, kernel, mesh: Mesh, strides=(1, 1)):
    """SAME conv2d with x row-sharded over the ``spatial`` axis.

    x: GLOBAL (B, H, W, Cin) array (sharded or not — it is device_put to
    P(None, "spatial")); kernel: (kh, kw, Cin, Cout) replicated.  Returns
    the global result, identical to an unsharded SAME conv.

    Strides are supported by mapping XLA's asymmetric SAME-under-stride
    padding onto an asymmetric halo: each shard fetches ``pad_top`` rows
    from above and ``pad_bottom`` from below, then runs a VALID strided
    conv on its slab — output rows land exactly on this shard's slice of
    the global output.  Requires the per-shard row count to be a multiple
    of the row stride (so shard boundaries fall on output rows) and each
    halo to fit in one neighbour (max SAME pad side ≤ rows/shard, i.e.
    roughly kh ≤ 2·rows + stride).
    """
    sh, sw = tuple(strides)
    kh, kw = kernel.shape[0], kernel.shape[1]
    H, W = x.shape[1], x.shape[2]
    _, pad_top, pad_bottom = _check_row_split(H, mesh.shape[SPATIAL_AXIS],
                                              sh, kh)
    pad_w = _same_pad(W, kw, sw)

    def shard_fn(xs, ks):
        padded = halo_exchange(xs, pad_top, pad_bottom)
        return jax.lax.conv_general_dilated(
            padded, ks, window_strides=(sh, sw),
            padding=((0, 0), pad_w),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(None, SPATIAL_AXIS, None, None), P()),
                   out_specs=P(None, SPATIAL_AXIS, None, None))
    x = jax.device_put(x, NamedSharding(mesh, P(None, SPATIAL_AXIS,
                                                None, None)))
    kernel = jax.device_put(kernel, NamedSharding(mesh, P()))
    return fn(x, kernel)
