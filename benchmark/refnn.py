"""Plain jax.numpy layers, optimizers and the three-step trainer that the
configurations' references are written from.

Nothing here imports the program.  Everything is float32; matrix products
run at ``Precision.HIGHEST`` (on a TPU a float32 product otherwise runs as
one bfloat16 pass).  ``operands`` names how a conv/dense rounds its two
operands before the product:

- ``"float32"``  the reference itself: no rounding;
- ``"bfloat16"`` both operands rounded to bfloat16 and, on the way back,
  their gradients too, products accumulated in float32: what the
  configurations state; kept for the look at which leaves rounding alone
  moves (``readings.py``), never compared in a run;
- ``"fp8"``      the control: all three products of a layer take fp8
  operands and accumulate in float32.  Forward, each operand is scaled by
  its absolute maximum to the range of float8_e4m3fn (448), rounded to that
  format's 3 mantissa bits and scaled back; backward, the gradient arriving
  at the product's output is rounded the same way to float8_e5m2 (2 bits),
  the format fp8 training keeps gradients in — the precision below the
  bfloat16 the configurations state, the step a later PR would be tempted
  by.  (Rounding the forward operands alone reads like bfloat16 itself at a
  random start: PR 25's readings.)

The roundings work on the integer view of the float: on the TPU XLA drops a
float32 -> narrow -> float32 pair of converts as excess precision (PR 25
read an "fp8" control built from converts at exactly the float32 loss).
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn
F8E5M2_MAX = 57344.0  # largest finite float8_e5m2


def _keep_mantissa(x, bits: int):
    """Round float32 to ``bits`` explicit mantissa bits, to nearest, ties to
    even, on the integer view: XLA may drop a float32 -> narrow -> float32
    pair of converts as "excess precision", it cannot drop this."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = u + ((u >> drop) & 1) + jnp.uint32((1 << (drop - 1)) - 1)
    u = u & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


@jax.custom_vjp
def _through_bfloat16(x):
    return _keep_mantissa(x, 7)


_through_bfloat16.defvjp(lambda x: (_keep_mantissa(x, 7), None),
                         lambda _, g: (_keep_mantissa(g, 7),))


def _to_e4m3(y):
    """|y| <= 448: 3 mantissa bits down to 2**-6, steps of 2**-9 below."""
    small = jnp.round(y * 512.0) / 512.0
    return jnp.where(jnp.abs(y) < 2.0 ** -6, small, _keep_mantissa(y, 3))


def _to_e5m2(y):
    """|y| <= 57344: 2 mantissa bits down to 2**-14, steps of 2**-16 below."""
    small = jnp.round(y * 65536.0) / 65536.0
    return jnp.where(jnp.abs(y) < 2.0 ** -14, small, _keep_mantissa(y, 2))


def _scaled(x, to_format, largest: float):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return to_format(x / scale) * scale


@jax.custom_vjp
def _gradient_through_e5m2(y):
    """Identity forward; backward, the incoming gradient is rounded to
    float8_e5m2 (the format fp8 training keeps gradients in), so both
    backward products of the layer below take an fp8 operand."""
    return y


_gradient_through_e5m2.defvjp(lambda y: (y, None),
                              lambda _, g: (_scaled(g, _to_e5m2, F8E5M2_MAX),))


def product_output(y, operands: str):
    return _gradient_through_e5m2(y) if operands == "fp8" else y


def round_operand(x, operands: str):
    if operands == "float32":
        return x
    if operands == "bfloat16":
        return _through_bfloat16(x)
    if operands == "fp8":
        return x + jax.lax.stop_gradient(_scaled(x, _to_e4m3, F8_MAX) - x)
    raise ValueError(f"unknown operand rounding {operands!r}")


def conv2d(x, kernel, stride: int = 1, padding="SAME", operands="float32"):
    """NHWC x, HWIO kernel.  ``padding``: "SAME", "VALID" or ((lo, hi), (lo, hi))."""
    return product_output(jax.lax.conv_general_dilated(
        round_operand(x, operands), round_operand(kernel, operands),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST), operands)


def dense(x, kernel, bias, operands="float32"):
    return product_output(
        jnp.dot(round_operand(x, operands), round_operand(kernel, operands),
                precision=HIGHEST), operands) + bias


def batchnorm_train(x, scale, bias, eps: float = 1e-5):
    """Batch statistics over every axis but the last, biased variance."""
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axes)
    var = jnp.mean(jnp.square(x - mean), axes)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def max_pool(x, window: int, stride: int, pad: int):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def avg_pool(x, window: int):
    s = jax.lax.reduce_window(x, 0.0, jax.lax.add, (1, window, window, 1),
                              (1, window, window, 1), "VALID")
    return s / float(window * window)


def softmax_xent(logits, labels):
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def sigmoid_xent(logits, targets):
    return (jnp.maximum(logits, 0) - logits * targets
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


# ------------------------------------------------------------- optimizers

def decays(leaf: str) -> bool:
    """Weight decay reaches kernels only, never a bias or a BN scale."""
    return leaf.rsplit("/", 1)[-1] not in ("bias", "scale")


def global_norm(tree: dict):
    return jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in tree.values()))


def clip_global(grads: dict, max_norm):
    if not max_norm:
        return grads
    norm = global_norm(grads)
    factor = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return {k: g * factor for k, g in grads.items()}


def sgd_step(params, moment, grads, hyper):
    """Momentum SGD with L2 decay added to the gradient of every kernel."""
    lr, mom, wd = hyper["learning_rate"], hyper["momentum"], hyper["weight_decay"]
    new_p, new_m = {}, {}
    for k, p in params.items():
        g = grads[k] + (wd * p if wd and decays(k) else 0.0)
        new_m[k] = g + mom * moment[k]
        new_p[k] = p - lr * new_m[k]
    return new_p, new_m


def adam_step(params, state, grads, hyper, t):
    lr, b1, b2, eps = (hyper["learning_rate"], hyper["b1"], hyper["b2"],
                       hyper["eps"])
    mu, nu = state
    new_p, new_mu, new_nu = {}, {}, {}
    for k, p in params.items():
        new_mu[k] = b1 * mu[k] + (1 - b1) * grads[k]
        new_nu[k] = b2 * nu[k] + (1 - b2) * jnp.square(grads[k])
        m_hat = new_mu[k] / (1 - b1 ** t)
        v_hat = new_nu[k] / (1 - b2 ** t)
        new_p[k] = p - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    return new_p, (new_mu, new_nu)


# --------------------------------------------------- the three-step trainer

def make_step(model, hyper: dict, operands: str):
    """One jitted training step of the reference.

    ``model`` is a configuration's reference module: ``prologue(batch, key,
    step)`` gives the float input the program's device-side prologue gives,
    ``forward(params, x, operands)`` the train-mode outputs and
    ``loss(outputs, batch)`` the scalar loss.
    """
    name = hyper["name"]

    def step(params, opt, batch, key, t):
        x = model.prologue(batch, key, t - 1)

        def loss_fn(p):
            return model.loss(model.forward(p, x, operands), batch)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = clip_global(grads, hyper.get("grad_clip_norm"))
        if name == "sgd":
            new_p, new_opt = sgd_step(params, opt, grads, hyper)
        elif name == "adam":
            new_p, new_opt = adam_step(params, opt, grads, hyper,
                                       t.astype(jnp.float32))
        else:
            raise ValueError(f"no reference optimizer {name!r}")
        return loss, grads, new_p, new_opt

    return jax.jit(step, donate_argnums=(0, 1))


_STEPS: dict = {}


def _compiled_step(model, hyper: dict, operands: str):
    """One step function per model, optimizer and rounding: a second seed in
    the same process (``readings.py``) neither traces nor loads it again."""
    key = (id(model), json.dumps(hyper, sort_keys=True), operands)
    if key not in _STEPS:
        _STEPS[key] = make_step(model, hyper, operands)
    return _STEPS[key]


def run_steps(model, hyper: dict, params0: dict, batches: list, key,
              operands: str = "float32", rows: str = "all") -> dict:
    """Follow the first ``len(batches)`` steps from ``params0``.

    Returns each step's loss, each leaf's gradient at step 1 (after the
    clip, before the decay: what the optimizer is handed) and each leaf's
    change over all the steps, as float32 arrays on the host.

    ``rows="half"`` plants the half-batch fault: the second half of every
    batch is left out and the first half stands in its place, so the batch
    statistics, the mean loss and the gradients are those of half the rows,
    at the whole batch's shapes (the step compiled for the reference
    serves; only a prologue that draws per row, as the colour jitter does,
    treats the two copies apart).
    """
    if rows == "half":
        batches = [{k: np.concatenate([v[: len(v) // 2]] * 2)
                    for k, v in batch.items()} for batch in batches]
    elif rows != "all":
        raise ValueError(f"unknown rows {rows!r}")
    step = _compiled_step(model, hyper, operands)
    params = {k: jnp.array(v, jnp.float32) for k, v in params0.items()}
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    opt = zeros if hyper["name"] == "sgd" else (
        zeros, {k: jnp.zeros_like(v) for k, v in params.items()})
    losses, first = [], None
    for i, batch in enumerate(batches):
        loss, grads, params, opt = step(params, opt, batch, key,
                                        jnp.asarray(i + 1, jnp.int32))
        losses.append(loss)
        if i == 0:
            first = jax.device_get(grads)
        del grads
    end = jax.device_get(params)
    return {"loss": [float(v) for v in jax.device_get(losses)],
            "grad": first,
            "delta": {k: end[k] - params0[k] for k in end}}
