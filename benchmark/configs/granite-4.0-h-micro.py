"""Plain reference for ``granite-4.0-h-micro``: the decoder's forward pass,
its loss, gradients and three AdamW steps in float32 ``jax.numpy`` at
``Precision.HIGHEST``, written from the published description and from
nothing under ``deep_vision_tpu``.

    h = E[tokens] * embedding_multiplier
    h = h + residual_multiplier * mixer(RMSNorm(h));  h = h + residual_multiplier * mlp(RMSNorm(h))
    logits = RMSNorm(h) E^T / logits_scaling;  loss = weighted mean cross-entropy

- the state-space recurrence runs **sequentially**, one ``lax.scan`` step a
  token, ``H = exp(dt a) H + dt x (outer) B``, ``y = H C + D x``, with ``H``
  set to zero at a document's first token; the scan is checkpointed in
  blocks of 64 steps so that its backward pass keeps 64 block-start states
  a layer (2 MB each at the published widths) and not one a token;
- the causal conv gathers its taps by index and drops those that fall
  before the row's start or in another document;
- attention is a plain masked softmax (causal, same document, no position
  term, scores times ``attention_multiplier``), computed a block of query
  rows at a time against all keys;
- each layer is rematerialised (``jax.checkpoint``) so that the float32
  activations of 4,096 tokens fit beside parameters and gradients.

Departures from the published model: none in the equations; the depth and
the vocabulary are the configuration file's (``reduced``), the optimizer and
the initialisation are assumed there.

``operands`` rounds the operands of every dense product and of the output
head as ``refnn.round_operand`` says ("fp8": the control).  ``reset_state``
False plants the fault of a state carried across document boundaries,
``rows="half"`` that of a loss over the first half of every row alone (the
second half's weights set to 0: the same program on another batch).

AdamW with a global-norm clip follows the program's optax chain: clip, Adam
moments with bias correction, decoupled decay on every leaf of rank 2 and
more.  The first moment stays on the device, the second is kept on the host
between steps: parameters, gradients and both moments are 12.4 GB at this
size and would leave nothing for the activations.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refnn

HIGHEST = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64
QUERY_BLOCK = 512


def _dense(x, kernel, operands):
    return refnn.product_output(
        jnp.dot(refnn.round_operand(x, operands),
                refnn.round_operand(kernel, operands), precision=HIGHEST),
        operands)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _conv(x, kernel, bias, seg):
    """One row: x (L, C), kernel (taps, C); tap k reads taps-1-k back."""
    taps, t = kernel.shape[0], jnp.arange(x.shape[0])
    out = jnp.zeros_like(x) + bias
    for k in range(taps):
        src = t - (taps - 1 - k)
        at = jnp.clip(src, 0)
        ok = (src >= 0) & (seg[at] == seg)
        out = out + jnp.where(ok[:, None], x[at], 0.0) * kernel[k]
    return out


def _scan(x, dt, a, b, c, first):
    """One row, token by token: x (L, H, P), dt (L, H), a (H,), b, c (L, N),
    first (L,) bool.  Returns y (L, H, P) without the D skip."""
    length = x.shape[0]
    block = min(SCAN_BLOCK, length)

    def step(state, inp):
        x_t, dt_t, b_t, c_t, first_t = inp
        state = jnp.where(first_t, 0.0, state)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    @jax.checkpoint
    def run_block(state, inputs):
        return jax.lax.scan(step, state, inputs)

    inputs = jax.tree_util.tree_map(
        lambda v: v.reshape(length // block, block, *v.shape[1:]),
        (x, dt, b, c, first))
    state = jnp.zeros((x.shape[1], x.shape[2], b.shape[1]), jnp.float32)
    _, y = jax.lax.scan(run_block, state, inputs)
    return y.reshape(x.shape)


def _attention(q, k, v, seg, scale):
    """One row: q (L, Hq, D), k, v (L, Hkv, D)."""
    length, heads = q.shape[0], q.shape[1]
    k = jnp.repeat(k, heads // k.shape[1], axis=1)
    v = jnp.repeat(v, heads // v.shape[1], axis=1)
    t = jnp.arange(length)

    @jax.checkpoint
    def rows(q_rows, t_rows, seg_rows):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k, precision=HIGHEST) * scale
        ok = (t_rows[:, None] >= t[None, :]) & (seg_rows[:, None] == seg[None, :])
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    block = min(QUERY_BLOCK, length)
    return jnp.concatenate([rows(q[i:i + block], t[i:i + block], seg[i:i + block])
                            for i in range(0, length, block)])


class Reference:
    def __init__(self, config: dict):
        self.c = config
        self.types = list(config["layer_types"])[: config["num_hidden_layers"]]
        self._grad_fns: dict = {}  # (operands, reset_state) -> jitted function

    # ---------------------------------------------------------- the model

    def _mamba(self, p, u, seg, operands, reset_state):
        c = self.c
        heads, dim, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
        inner = heads * dim
        zxbcdt = _dense(u, p["in_proj/kernel"], operands)
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * n], axis=-1)
        xbc = jax.nn.silu(_conv(xbc, p["conv_kernel"], p["conv_bias"], seg))
        x, b, cc = jnp.split(xbc, [inner, inner + n], axis=-1)
        x = x.reshape(-1, heads, dim)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
        if not reset_state:
            first = jnp.zeros_like(first)
        y = _scan(x, dt, -jnp.exp(p["A_log"]), b, cc, first)
        y = (y + p["D"][:, None] * x).reshape(-1, inner) * jax.nn.silu(z)
        y = _rmsnorm(y, p["norm/scale"], c["rms_norm_eps"])
        return _dense(y, p["out_proj/kernel"], operands)

    def _attn(self, p, u, seg, operands):
        c = self.c
        dim = c["hidden_size"] // c["num_attention_heads"]
        q, k, v = (_dense(u, p[f"{name}_proj/kernel"], operands).reshape(
            u.shape[0], -1, dim) for name in "qkv")
        out = _attention(q, k, v, seg, c["attention_multiplier"])
        return _dense(out.reshape(u.shape[0], -1), p["o_proj/kernel"], operands)

    def _layer(self, kind, p, h, seg, operands, reset_state):
        c, r = self.c, self.c["residual_multiplier"]
        mixer = {k[len("mixer/"):]: v for k, v in p.items() if k.startswith("mixer/")}
        u = _rmsnorm(h, p["mixer_norm/scale"], c["rms_norm_eps"])
        if kind == "mamba":
            h = h + r * self._mamba(mixer, u, seg, operands, reset_state)
        else:
            h = h + r * self._attn(mixer, u, seg, operands)
        u = _rmsnorm(h, p["ffn_norm/scale"], c["rms_norm_eps"])
        gate, value = jnp.split(_dense(u, p["ffn/in_proj/kernel"], operands), 2, -1)
        return h + r * _dense(jax.nn.silu(gate) * value,
                              p["ffn/out_proj/kernel"], operands)

    def logits(self, params, tokens, segment_ids, operands="float32",
               reset_state=True):
        """params: flat ``layer_3/mixer/in_proj/kernel`` -> array;
        tokens, segment_ids (B, L).  Returns (B, L, vocab) float32."""
        c = self.c

        def row(tok, seg):
            h = params["embedding"][tok] * c["embedding_multiplier"]
            for i, kind in enumerate(self.types):
                pre = f"layer_{i}/"
                p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
                h = jax.checkpoint(
                    functools.partial(self._layer, kind, operands=operands,
                                      reset_state=reset_state))(p, h, seg)
            h = _rmsnorm(h, params["final_norm/scale"], c["rms_norm_eps"])
            return _dense(h, params["embedding"].T, operands) / c["logits_scaling"]

        return jax.vmap(row)(tokens, segment_ids)

    def loss(self, params, batch, operands="float32", reset_state=True):
        logits = self.logits(params, batch["tokens"], batch["segment_ids"],
                             operands, reset_state)
        picked = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
        xent = jax.scipy.special.logsumexp(logits, axis=-1) - picked
        w = batch["loss_weights"]
        return jnp.sum(xent * w) / jnp.maximum(jnp.sum(w), 1.0)

    # ------------------------------------------------------- three steps

    def _grad_fn(self, operands, reset_state):
        key = (operands, reset_state)
        if key not in self._grad_fns:
            self._grad_fns[key] = jax.jit(jax.value_and_grad(functools.partial(
                self.loss, operands=operands, reset_state=reset_state)))
        return self._grad_fns[key]

    def run_steps(self, params0: dict, batches: list, operands="float32",
                  reset_state=True, rows="all") -> dict:
        """Each step's loss, each leaf's gradient at step 1 after the clip
        (what the optimizer is handed) and each leaf's change over the
        steps, float32 on the host."""
        if rows == "half":
            batches = [dict(b, loss_weights=np.where(
                np.arange(b["loss_weights"].shape[1]) < b["loss_weights"].shape[1] // 2,
                b["loss_weights"], 0)) for b in batches]
        hyper = self.c["optimizer"]
        grad_fn = self._grad_fn(operands, reset_state)
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params0.items()}
        mu, nu, losses, first = {}, {}, [], None
        seconds = {"gradient": [], "update": []}
        for t, batch in enumerate(batches, 1):
            t0 = time.perf_counter()
            loss, grads = grad_fn(params, {k: jnp.asarray(v) for k, v in batch.items()})
            losses.append(float(loss))
            t1 = time.perf_counter()
            norm = math.sqrt(sum(float(_sum_squares(g)) for g in grads.values()))
            clip = hyper.get("grad_clip_norm")
            factor = 1.0 if not clip or norm < clip else clip / norm
            if first is None:
                first = {k: np.asarray(g) * np.float32(factor)
                         for k, g in grads.items()}
            for k in sorted(params):
                g = grads.pop(k)
                m = mu[k] if k in mu else jnp.zeros_like(g)
                v = nu.pop(k) if k in nu else jnp.zeros_like(g)
                params[k], mu[k], v = _adamw_leaf(
                    params[k], g, m, v, factor, float(t), hyper["learning_rate"],
                    hyper["b1"], hyper["b2"], hyper["eps"],
                    hyper["weight_decay"] if params[k].ndim >= 2 else 0.0)
                if t < len(batches):
                    nu[k] = np.asarray(v)  # off the device until the next step
            seconds["gradient"].append(round(t1 - t0, 1))
            seconds["update"].append(round(time.perf_counter() - t1, 1))
        delta = {k: np.asarray(params.pop(k)) - params0[k] for k in sorted(params)}
        return {"loss": losses, "grad": first, "delta": delta, "seconds": seconds}


@jax.jit
def _sum_squares(g):
    return jnp.sum(jnp.square(g))


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw_leaf(p, g, mu, nu, factor, t, lr, b1, b2, eps, decay):
    g = g * factor
    mu = b1 * mu + (1.0 - b1) * g
    nu = b2 * nu + (1.0 - b2) * jnp.square(g)
    step = (mu / (1.0 - b1 ** t)) / (jnp.sqrt(nu / (1.0 - b2 ** t)) + eps)
    return p - lr * (step + decay * p), mu, nu
