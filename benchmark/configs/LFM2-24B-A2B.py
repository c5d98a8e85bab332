"""Plain reference for ``LFM2-24B-A2B``: the decoder's forward pass, its
loss, gradients and three AdamW steps in float32 ``jax.numpy`` at
``Precision.HIGHEST``, written from the published description and from
nothing under ``deep_vision_tpu``.  No kernel, no sort, no grouped product.

    h = E[tokens]
    h = h + operator(RMSNorm(h));  h = h + ffn(RMSNorm(h))
    logits = RMSNorm(h) E^T;  loss = weighted mean cross-entropy

- conv operator: ``[B, C, x] = W_in u``, ``y = W_out (C * conv3(B * x))``,
  the depthwise causal conv written as ``conv_L_cache`` shifted products,
  a tap that would read before the document's first token reads zero;
- attention operator: RMSNorm over each head of q and of k, rotary over the
  whole head (halves rotated, ``theta^(-2i/d)``, positions counted from the
  document's first token) written out with ``cos`` and ``sin``, a plain
  masked softmax (causal, same document, scores ``q.k / sqrt(d)``) a block
  of query rows at a time against all keys, the blocks one after another;
- dense block ``W2 (silu(W1 u) * W3 u)``; routed block: ``s = sigmoid(W_r
  u)`` over the router's whole width, an expert is chosen where fewer than
  ``k`` experts have a larger ``s + b`` (ties to the lower index: a count,
  not a sort), ``w = s`` on the chosen, over their sum + 1e-6; **every held
  expert is applied to every token** and its result multiplied by the
  token's weight for it, zero where it was not chosen.  The share (``first``,
  ``count``) is the configuration's: experts outside it add nothing;
- each layer is rematerialised (``jax.checkpoint``), the held experts taken
  one at a time (a ``lax.scan`` over them, each step rematerialised), so
  that the float32 activations of 8,192 tokens fit.

Departures from the published model: none in the equations; the table is
taken as tied, depth, experts held and vocabulary are the configuration
file's (``reduced``), optimizer and initialisation are assumed there.  The
selection bias is a leaf no gradient moves; after each step every expert of
the router's whole width whose load in that step's batch lay over the mean
loses ``expert_bias_update_rate`` and every one under it gains as much
(balancing without an auxiliary loss, assumed in the configuration file).

``operands`` rounds the operands of every dense and expert product and of
the output head as ``refnn.round_operand`` says ("fp8": the control; the
router's product stays float32 in every mode, as the configuration states
it).  The planted faults, each the same code with one thing wrong:
``weigh="biased"`` takes the weights from ``s + b``; ``normalise="held"``
sums the chosen scores over the held experts only; ``capacity=1.25`` gives
each held expert room for that many times the mean load and drops what
arrives later in the row; ``reset=False`` carries conv, positions and mask
across document boundaries; ``rows="half"`` takes the loss over the first
half of every row alone; ``balance=False`` leaves the selection biases
where they started.

AdamW with a global-norm clip follows the program's optax chain as the
granite reference does; the first moment stays on the device, the second is
kept on the host between steps (the compiler counts 7.5 GiB for the
gradient program at this size, parameters and gradients among them).
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
QUERY_BLOCK = 256
FAULTS = {"weigh": "scores", "normalise": "chosen", "capacity": None,
          "reset": True}


def _dense(x, kernel, operands):
    return refnn.product_output(
        jnp.dot(refnn.round_operand(x, operands),
                refnn.round_operand(kernel, operands), precision=HIGHEST),
        operands)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _shift(x, back, same):
    """One row: x (L, C) read ``back`` positions earlier, zero where that
    lies before the row or outside the token's document (``same`` (L, L))."""
    t = jnp.arange(x.shape[0])
    src = t - back
    ok = (src >= 0) & same[t, jnp.clip(src, 0)]
    return jnp.where(ok[:, None], x[jnp.clip(src, 0)], 0.0)


def _conv_operator(p, u, same, operands):
    b, c, x = jnp.split(_dense(u, p["in_proj/kernel"], operands), 3, axis=-1)
    bx, taps = b * x, p["conv_kernel"]
    conv = sum(_shift(bx, taps.shape[0] - 1 - k, same) * taps[k]
               for k in range(taps.shape[0]))
    return _dense(c * conv, p["out_proj/kernel"], operands)


def _rotary(x, positions, theta):
    """x (L, H, D): x cos + rotate_half(x) sin."""
    dim = x.shape[-1]
    rate = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[:, None] * rate[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def _attention(q, k, v, same, scale):
    """One row: q (L, Hq, D), k, v (L, Hkv, D); same (L, L) bool."""
    length, heads = q.shape[0], q.shape[1]
    k = jnp.repeat(k, heads // k.shape[1], axis=1)
    v = jnp.repeat(v, heads // v.shape[1], axis=1)
    t = jnp.arange(length)

    @jax.checkpoint
    def rows(q_rows, t_rows, same_rows):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k, precision=HIGHEST) * scale
        ok = (t_rows[:, None] >= t[None, :]) & same_rows
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    # one block of query rows after another (``lax.map``): left to itself
    # the compiler holds several blocks' scores at once, 256 MB each here
    block = min(QUERY_BLOCK, length)
    cut = lambda a: a.reshape(length // block, block, *a.shape[1:])  # noqa: E731
    out = jax.lax.map(lambda xs: rows(*xs), (cut(q), cut(t), cut(same)))
    return out.reshape(length, heads, -1)


class Reference:
    def __init__(self, config: dict):
        self.c = config
        self.types = list(config["layer_types"])[: config["num_hidden_layers"]]
        self.first = int(config.get("expert_first", 0))
        self.count = int(config["num_experts"])          # the experts held
        self.width = int(config.get("published", {}).get(
            "num_experts", config["num_experts"]))       # the router's
        self._grad_fns: dict = {}

    # ---------------------------------------------------------- the model

    def _attention_operator(self, p, u, same, positions, operands):
        c = self.c
        dim = c["hidden_size"] // c["num_attention_heads"]
        q, k, v = (_dense(u, p[f"{name}_proj/kernel"], operands).reshape(
            u.shape[0], -1, dim) for name in "qkv")
        q = _rotary(_rmsnorm(q, p["q_layernorm/scale"], c["norm_eps"]),
                    positions, c["rope_parameters"]["rope_theta"])
        k = _rotary(_rmsnorm(k, p["k_layernorm/scale"], c["norm_eps"]),
                    positions, c["rope_parameters"]["rope_theta"])
        out = _attention(q, k, v, same, 1.0 / math.sqrt(dim))
        return _dense(out.reshape(u.shape[0], -1), p["out_proj/kernel"], operands)

    def route(self, p, u, weigh="scores", normalise="chosen"):
        """(T, E) weights over the router's whole width, zero off the
        chosen, and the (T, E) mask of the chosen."""
        k = self.c["num_experts_per_tok"]
        s = jax.nn.sigmoid(jnp.dot(u, p["router"], precision=HIGHEST))
        biased = jax.lax.stop_gradient(s + p["expert_bias"])
        ahead = biased[:, None, :] > biased[:, :, None]          # [t, i, j]
        tie = (biased[:, None, :] == biased[:, :, None]) & (
            jnp.arange(self.width)[None, :] < jnp.arange(self.width)[:, None])[None]
        chosen = (ahead | tie).sum(-1) < k
        w = jnp.where(chosen, s + p["expert_bias"] if weigh == "biased" else s, 0.0)
        held = (jnp.arange(self.width) >= self.first) & (
            jnp.arange(self.width) < self.first + self.count)
        total = jnp.where(held, w, 0.0) if normalise == "held" else w
        return w / (total.sum(-1, keepdims=True) + 1e-6), chosen

    def _routed(self, p, u, operands, weigh, normalise, capacity):
        everywhere, chosen = self.route(p, u, weigh, normalise)
        lo = self.first
        mine = chosen[:, lo:lo + self.count]
        dropped = jnp.zeros((), jnp.float32)
        if capacity is not None:
            room = math.ceil(capacity * u.shape[0] * self.c["num_experts_per_tok"]
                             / self.width)
            kept = mine & (jnp.cumsum(mine, axis=0) <= room)
            dropped = jnp.sum(mine & ~kept).astype(jnp.float32)
            mine = kept
        w = jnp.where(mine, everywhere[:, lo:lo + self.count], 0.0)

        @jax.checkpoint
        def expert(w1, w3, w2, weight):
            h = jax.nn.silu(_dense(u, w1, operands)) * _dense(u, w3, operands)
            return weight[:, None] * _dense(h, w2, operands)

        out, _ = jax.lax.scan(
            lambda out, held: (out + expert(*held), None), jnp.zeros_like(u),
            (p["experts_w1"], p["experts_w3"], p["experts_w2"], w.T))
        counters = {
            "assignments": jnp.sum(chosen[:, lo:lo + self.count]).astype(jnp.float32),
            "max_load": jnp.max(jnp.sum(mine, axis=0)).astype(jnp.float32),
            "dropped": dropped,
            # of every expert of the router's width, held here or not
            "loads": jnp.sum(chosen, axis=0).astype(jnp.float32),
            # every chosen expert's bias, held here or not, by its weight
            "bias_lift": jnp.mean(jnp.sum(everywhere * p["expert_bias"], -1))}
        return out, counters

    def _layer(self, index, p, h, same, positions, operands, faults):
        c = self.c
        op = {k[len("operator/"):]: v for k, v in p.items()
              if k.startswith("operator/")}
        ffn = {k[len("feed_forward/"):]: v for k, v in p.items()
               if k.startswith("feed_forward/")}
        u = _rmsnorm(h, p["operator_norm/scale"], c["norm_eps"])
        if self.types[index] == "conv":
            h = h + _conv_operator(op, u, same, operands)
        else:
            h = h + self._attention_operator(op, u, same, positions, operands)
        u = _rmsnorm(h, p["ffn_norm/scale"], c["norm_eps"])
        zero = jnp.zeros((), jnp.float32)
        if index < c["num_dense_layers"]:
            gate = _dense(u, ffn["w1/kernel"], operands)
            value = _dense(u, ffn["w3/kernel"], operands)
            return (h + _dense(jax.nn.silu(gate) * value, ffn["w2/kernel"], operands),
                    {**dict.fromkeys(("assignments", "max_load", "dropped",
                                      "bias_lift"), zero),
                     "loads": jnp.zeros((self.width,), jnp.float32)})
        out, counters = self._routed(ffn, u, operands, faults["weigh"],
                                     faults["normalise"], faults["capacity"])
        return h + out, counters

    def forward(self, params, tokens, segment_ids, operands="float32", **faults):
        """params: flat ``layer_3/operator/in_proj/kernel`` -> array; tokens,
        segment_ids (B, L).  Returns logits (B, L, vocab) float32 and the
        counters, each (B, layers), the loads (B, layers, experts)."""
        c, faults = self.c, {**FAULTS, **faults}

        def row(tok, seg):
            if not faults["reset"]:
                seg = jnp.zeros_like(seg)
            same = seg[:, None] == seg[None, :]
            t = jnp.arange(seg.shape[0])
            first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
            positions = t - jax.lax.cummax(jnp.where(first, t, 0), axis=0)
            h, counters = params["embedding"][tok], []
            for i in range(len(self.types)):
                pre = f"layer_{i}/"
                p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
                h, got = jax.checkpoint(functools.partial(
                    self._layer, i, operands=operands, faults=faults))(
                        p, h, same, positions)
                counters.append(got)
            h = _rmsnorm(h, params["final_norm/scale"], c["norm_eps"])
            stacked = {k: jnp.stack([g[k] for g in counters]) for k in counters[0]}
            return _dense(h, params["embedding"].T, operands), stacked

        return jax.vmap(row)(tokens, segment_ids)

    def logits(self, params, tokens, segment_ids, operands="float32", **faults):
        return self.forward(params, tokens, segment_ids, operands, **faults)[0]

    def loss(self, params, batch, operands="float32", **faults):
        logits, counters = self.forward(params, batch["tokens"],
                                        batch["segment_ids"], operands, **faults)
        picked = jnp.take_along_axis(logits, batch["targets"][..., None], -1)[..., 0]
        xent = jax.scipy.special.logsumexp(logits, axis=-1) - picked
        w = batch["loss_weights"]
        return jnp.sum(xent * w) / jnp.maximum(jnp.sum(w), 1.0), counters

    # ------------------------------------------------------- three steps

    def _grad_fn(self, operands, faults):
        key = (operands, tuple(sorted(faults.items())))
        if key not in self._grad_fns:
            self._grad_fns[key] = jax.jit(jax.value_and_grad(functools.partial(
                self.loss, operands=operands, **faults), has_aux=True))
        return self._grad_fns[key]

    def run_steps(self, params0: dict, batches: list, operands="float32",
                  rows="all", balance=True, **faults) -> dict:
        """Each step's loss, each leaf's gradient at step 1 after the clip
        (what the optimizer is handed), each leaf's change over the steps
        (the selection biases' by the balancing alone: their gradient is
        zero), float32 on the host, and the routing counters of the first step
        (``moe_assignments`` and ``moe_dropped`` summed over rows and layers,
        ``moe_max_load`` the largest, ``moe_bias_lift`` the mean over rows
        and expert layers) with the root mean square of the selection
        biases they are read against."""
        if rows == "half":
            batches = [dict(b, loss_weights=np.where(
                np.arange(b["loss_weights"].shape[1]) < b["loss_weights"].shape[1] // 2,
                b["loss_weights"], 0)) for b in batches]
        hyper = self.c["optimizer"]
        grad_fn = self._grad_fn(operands, faults)
        params = {k: jnp.asarray(v, jnp.float32) for k, v in params0.items()}
        mu, nu, losses, first, counters = {}, {}, [], None, None
        seconds = {"gradient": [], "update": []}
        for t, batch in enumerate(batches, 1):
            t0 = time.perf_counter()
            (loss, got), grads = grad_fn(
                params, {k: jnp.asarray(v) for k, v in batch.items()})
            counters = got if counters is None else counters
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
            if balance:
                loads = jnp.sum(got["loads"], axis=0)        # (layers, experts)
                mean = batch["tokens"].size * self.c["num_experts_per_tok"] / self.width
                for i in range(self.c["num_dense_layers"], len(self.types)):
                    k = f"layer_{i}/feed_forward/expert_bias"
                    params[k] = params[k] + self.c["expert_bias_update_rate"] * jnp.sign(
                        mean - loads[i])
            seconds["gradient"].append(round(t1 - t0, 1))
            seconds["update"].append(round(time.perf_counter() - t1, 1))
        delta = {k: np.asarray(params.pop(k)) - params0[k] for k in sorted(params)}
        return {"loss": losses, "grad": first, "delta": delta, "seconds": seconds,
                "moe_assignments": float(jnp.sum(counters["assignments"])),
                "moe_max_load": float(jnp.max(counters["max_load"])),
                "moe_dropped": float(jnp.sum(counters["dropped"])),
                "moe_bias_lift": float(jnp.mean(
                    counters["bias_lift"][:, self.c["num_dense_layers"]:])),
                "moe_bias_rms": float(np.sqrt(np.mean(np.square(np.concatenate(
                    [np.ravel(v) for k, v in params0.items()
                     if k.endswith("expert_bias")])))))}


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
