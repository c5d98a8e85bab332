"""Plain reference for ``GLM-4.7-Flash``: the decoder's forward pass, its
multi-token-prediction module, both losses, gradients and three AdamW steps
in float32 ``jax.numpy`` at ``Precision.HIGHEST``, written from the
published description and from nothing under ``deep_vision_tpu``.  No
kernel, no sort, no grouped product.

    h = E[tokens]
    h = h + mla(RMSNorm(h));  h = h + ffn(RMSNorm(h))
    logits = RMSNorm(h_L) W_out;  module: logits2 (below)
    loss = CE(logits[t], tokens[t+1]; w) + weight * CE(logits2[t], tokens[t+2]; w2)

- latent attention, product by product: ``c_q = RMSNorm(W_qa u)``, ``q =
  W_qb c_q`` cut into heads of ``nope | rope``; ``W_kva u`` cut into the
  latent ``c_kv`` and ONE rotary key; ``RMSNorm(c_kv)``, ``W_kvb`` of it cut
  into heads of ``nope | v``; rotary (halves rotated, ``theta^(-2i/rope)``,
  positions counted from the document's first token) written out with
  ``cos`` and ``sin`` on the ``rope`` dimensions of ``q`` and on the one
  key, which is then repeated for every head; a plain masked softmax
  (causal, same document, scores ``q.k / sqrt(nope + rope)``) a block of
  query rows at a time against all keys; ``W_o``;
- dense block ``W2 (silu(W1 u) * W3 u)``; routed block: ``s = sigmoid(W_r
  u)`` over the router's whole width, an expert is chosen where fewer than
  ``k`` experts have a larger ``s + b`` (ties to the lower index: a count,
  not a sort), ``w = scale * s`` on the chosen over their sum + 1e-20;
  **every held expert is applied to every token** and its result multiplied
  by the token's weight for it, zero where it was not chosen; the shared
  expert is added for every token.  The share (``first``, ``count``) is the
  configuration's: experts outside it add nothing;
- the module: ``x_t = W_eh [RMSNorm_e(E[next_t]) | RMSNorm_h(h_L[t])]`` with
  ``next_t`` the row's next token where it is the same document's and token
  ``t`` itself elsewhere (positions that carry no second target), one
  routed layer, ``logits2 = RMSNorm_m(y) W_out`` through the model's own
  table and head.  The second target is ``tokens[t + 2]`` where ``t + 2``
  lies in the row and in ``t``'s document (read off the segment ids, not
  off the first loss's weights);
- each layer is rematerialised (``jax.checkpoint``), the held experts taken
  one at a time (a ``lax.scan``, each step rematerialised), each head with
  its cross-entropy rematerialised too, so that the float32 activations of
  8,192 tokens fit.

Departures from the published model: none in the equations; depth, experts
held and vocabulary are the configuration file's (``reduced``); the module's
form, its weight, optimizer and initialisation are assumed there.  The
selection bias is a leaf no gradient moves; after each step every expert of
the router's whole width whose load in that step's batch lay over the mean
loses ``expert_bias_update_rate`` and every one under it gains as much.

``operands`` rounds the operands of every dense and expert product and of
the output head as ``refnn.round_operand`` says ("fp8": the control; the
router's product stays float32 in every mode).  The planted faults, each
the same code with one thing wrong: ``rotate="all"`` turns all ``nope +
rope`` dimensions of queries and keys; ``rope_key="per_head"`` gives every
head a rotary key of its own (the one key rolled by the head's index);
``latent_norm=False`` leaves out the norm on ``c_kv``; ``shared=False``
leaves out the shared expert; ``scale=1.0`` takes the routed weights
without ``routed_scaling_factor``; ``second="across"`` keeps the second
target across a document boundary (``w2 = w``); ``mtp_weight=0.0`` gives
the module's loss no weight; ``balance=False`` leaves the selection biases
where they started.

AdamW with a global-norm clip follows the program's optax chain as the
LFM2 reference does; the first moment stays on the device, the second is
kept on the host between steps.
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
FAULTS = {"rotate": "rope", "rope_key": "shared", "latent_norm": True,
          "shared": True, "scale": None, "second": "document",
          "mtp_weight": None}
COUNTED = ("assignments", "max_load", "bias_lift")


def _dense(x, kernel, operands):
    return refnn.product_output(
        jnp.dot(refnn.round_operand(x, operands),
                refnn.round_operand(kernel, operands), precision=HIGHEST),
        operands)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _swiglu(p, u, operands):
    gate = _dense(u, p["w1/kernel"], operands)
    value = _dense(u, p["w3/kernel"], operands)
    return _dense(jax.nn.silu(gate) * value, p["w2/kernel"], operands)


def _rotary(x, positions, theta):
    """x (L, H, D): x cos + rotate_half(x) sin."""
    dim = x.shape[-1]
    rate = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[:, None] * rate[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    turned = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
    return x * jnp.cos(angle) + turned * jnp.sin(angle)


def _attention(q, k, v, same, scale):
    """One row: q, k (L, H, D), v (L, H, Dv); same (L, L) bool."""
    length, heads = q.shape[0], q.shape[1]
    t = jnp.arange(length)

    @jax.checkpoint
    def rows(q_rows, t_rows, same_rows):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k, precision=HIGHEST) * scale
        ok = (t_rows[:, None] >= t[None, :]) & same_rows
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    # one block of query rows after another (``lax.map``): left to itself
    # the compiler holds several blocks' scores at once
    block = min(QUERY_BLOCK, length)
    cut = lambda a: a.reshape(length // block, block, *a.shape[1:])  # noqa: E731
    out = jax.lax.map(lambda xs: rows(*xs), (cut(q), cut(t), cut(same)))
    return out.reshape(length, heads, -1)


def _under(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


class Reference:
    def __init__(self, config: dict):
        self.c = config
        self.first = int(config.get("expert_first", 0))
        self.count = int(config["n_routed_experts"])          # the experts held
        self.width = int(config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"]))  # the router's
        self.modules = int(config["num_nextn_predict_layers"])
        self._grad_fns: dict = {}

    # ---------------------------------------------------------- the model

    def _latent_attention(self, p, u, same, positions, operands, faults):
        c = self.c
        heads, nope, rope = (c["num_attention_heads"], c["qk_nope_head_dim"],
                             c["qk_rope_head_dim"])
        rank, theta, length = c["kv_lora_rank"], c["rope_theta"], u.shape[0]
        c_q = _rmsnorm(_dense(u, p["q_a/kernel"], operands),
                       p["q_a_norm/scale"], c["rms_norm_eps"])
        q = _dense(c_q, p["q_b/kernel"], operands).reshape(length, heads, nope + rope)
        joint = _dense(u, p["kv_a/kernel"], operands)
        c_kv, k_rope = joint[:, :rank], joint[:, rank:]
        if faults["latent_norm"]:
            c_kv = _rmsnorm(c_kv, p["kv_a_norm/scale"], c["rms_norm_eps"])
        up = _dense(c_kv, p["kv_b/kernel"], operands).reshape(
            length, heads, nope + c["v_head_dim"])
        k_nope, v = up[..., :nope], up[..., nope:]
        if faults["rope_key"] == "per_head":
            k_rope = jnp.stack([jnp.roll(k_rope, n, axis=-1)
                                for n in range(heads)], axis=1)
        else:
            k_rope = jnp.repeat(k_rope[:, None, :], heads, axis=1)
        if faults["rotate"] == "all":
            q = _rotary(q, positions, theta)
            k = _rotary(jnp.concatenate([k_nope, k_rope], -1), positions, theta)
        else:
            q = jnp.concatenate([q[..., :nope],
                                 _rotary(q[..., nope:], positions, theta)], -1)
            k = jnp.concatenate([k_nope, _rotary(k_rope, positions, theta)], -1)
        out = _attention(q, k, v, same, 1.0 / math.sqrt(nope + rope))
        return _dense(out.reshape(length, -1), p["o/kernel"], operands)

    def route(self, p, u, scale=None):
        """(T, E) weights over the router's whole width, zero off the
        chosen, and the (T, E) mask of the chosen."""
        k = self.c["num_experts_per_tok"]
        scale = self.c["routed_scaling_factor"] if scale is None else scale
        s = jax.nn.sigmoid(jnp.dot(u, p["router"], precision=HIGHEST))
        biased = jax.lax.stop_gradient(s + p["expert_bias"])
        ahead = biased[:, None, :] > biased[:, :, None]          # [t, i, j]
        tie = (biased[:, None, :] == biased[:, :, None]) & (
            jnp.arange(self.width)[None, :] < jnp.arange(self.width)[:, None])[None]
        chosen = (ahead | tie).sum(-1) < k
        w = jnp.where(chosen, s, 0.0)
        return scale * w / (w.sum(-1, keepdims=True) + 1e-20), chosen

    def _routed(self, p, u, operands, faults):
        everywhere, chosen = self.route(p, u, faults["scale"])
        lo = self.first
        mine = chosen[:, lo:lo + self.count]
        w = jnp.where(mine, everywhere[:, lo:lo + self.count], 0.0)

        @jax.checkpoint
        def expert(w1, w3, w2, weight):
            h = jax.nn.silu(_dense(u, w1, operands)) * _dense(u, w3, operands)
            return weight[:, None] * _dense(h, w2, operands)

        out, _ = jax.lax.scan(
            lambda out, held: (out + expert(*held), None), jnp.zeros_like(u),
            (p["experts_w1"], p["experts_w3"], p["experts_w2"], w.T))
        if faults["shared"]:
            out = out + _swiglu(_under(p, "shared/"), u, operands)
        counters = {
            "assignments": jnp.sum(mine).astype(jnp.float32),
            "max_load": jnp.max(jnp.sum(mine, axis=0)).astype(jnp.float32),
            # of every expert of the router's width, held here or not
            "loads": jnp.sum(chosen, axis=0).astype(jnp.float32),
            # every chosen expert's bias, held here or not, by its weight
            "bias_lift": jnp.mean(jnp.sum(everywhere * p["expert_bias"], -1))}
        return out, counters

    def _layer(self, routed, p, h, same, positions, operands, faults):
        c = self.c
        u = _rmsnorm(h, p["operator_norm/scale"], c["rms_norm_eps"])
        h = h + self._latent_attention(_under(p, "operator/"), u, same,
                                       positions, operands, faults)
        u = _rmsnorm(h, p["ffn_norm/scale"], c["rms_norm_eps"])
        ffn = _under(p, "feed_forward/")
        if not routed:
            zero = jnp.zeros((), jnp.float32)
            return h + _swiglu(ffn, u, operands), {
                **dict.fromkeys(COUNTED, zero),
                "loads": jnp.zeros((self.width,), jnp.float32)}
        out, counters = self._routed(ffn, u, operands, faults)
        return h + out, counters

    def routed_layers(self) -> list:
        """The leaves' prefix of every layer with a router, the module's last."""
        c = self.c
        return [f"layer_{i}/" for i in range(c["first_k_dense_replace"],
                                             c["num_hidden_layers"])] + [
            "mtp/layer/"] * self.modules

    def forward(self, params, tokens, segment_ids, operands="float32", **faults):
        """params: flat ``layer_3/operator/q_a/kernel`` -> array; tokens,
        segment_ids (B, L).  Returns the normed streams the head reads
        ((B, L, hidden) each, the module's None without one) and the
        counters of the routed layers, each (B, layers), the loads (B,
        layers, experts)."""
        c, faults = self.c, {**FAULTS, **faults}
        layer = lambda routed: jax.checkpoint(functools.partial(  # noqa: E731
            self._layer, routed, operands=operands, faults=faults))

        def row(tok, seg):
            same = seg[:, None] == seg[None, :]
            t = jnp.arange(seg.shape[0])
            first = jnp.concatenate([jnp.ones((1,), bool), seg[1:] != seg[:-1]])
            positions = t - jax.lax.cummax(jnp.where(first, t, 0), axis=0)
            h, counters = params["embedding"][tok], []
            for i in range(c["num_hidden_layers"]):
                routed = i >= c["first_k_dense_replace"]
                h, got = layer(routed)(_under(params, f"layer_{i}/"), h, same,
                                       positions)
                if routed:
                    counters.append(got)
            normed, normed2 = _rmsnorm(h, params["final_norm/scale"],
                                       c["rms_norm_eps"]), None
            if self.modules:
                m = _under(params, "mtp/")
                goes_on = jnp.concatenate([seg[1:] == seg[:-1],
                                           jnp.zeros((1,), bool)])
                after = jnp.where(goes_on, jnp.concatenate([tok[1:], tok[-1:]]), tok)
                x = jnp.concatenate(
                    [_rmsnorm(params["embedding"][after], m["enorm/scale"],
                              c["rms_norm_eps"]),
                     _rmsnorm(h, m["hnorm/scale"], c["rms_norm_eps"])], axis=-1)
                x = _dense(x, m["eh_proj/kernel"], operands)
                y, got = layer(True)(_under(m, "layer/"), x, same, positions)
                counters.append(got)
                normed2 = _rmsnorm(y, m["final_norm/scale"], c["rms_norm_eps"])
            stacked = {k: jnp.stack([g[k] for g in counters]) for k in counters[0]}
            return normed, normed2, stacked

        return jax.vmap(row)(tokens, segment_ids)

    def logits(self, params, tokens, segment_ids, operands="float32", **faults):
        """Both sets of logits (B, L, vocab); the second None without a module."""
        normed, normed2, _ = self.forward(params, tokens, segment_ids, operands,
                                          **faults)
        head = lambda x: None if x is None else _dense(  # noqa: E731
            x, params["lm_head/kernel"], operands)
        return head(normed), head(normed2)

    def second_targets(self, batch, second="document"):
        """``tokens[t + 2]`` and the weight of position ``t``'s second
        prediction: 1 where ``t + 2`` lies in the row and in the document of
        ``t``, read off the segment ids."""
        tokens, seg = batch["tokens"], batch["segment_ids"]
        pad = lambda a, n: jnp.concatenate(  # noqa: E731
            [a[:, n:], jnp.repeat(a[:, -1:], n, axis=1)], axis=1)
        if second == "across":
            return pad(tokens, 2), batch["loss_weights"]
        inside = jnp.arange(seg.shape[1]) + 2 < seg.shape[1]
        return pad(tokens, 2), ((pad(seg, 2) == seg) & inside).astype(jnp.float32)

    def loss(self, params, batch, operands="float32", **faults):
        faults = {**FAULTS, **faults}
        normed, normed2, counters = self.forward(
            params, batch["tokens"], batch["segment_ids"], operands, **faults)

        @jax.checkpoint
        def xent(x, kernel, targets, w):
            logits = _dense(x, kernel, operands)
            picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
            each = jax.scipy.special.logsumexp(logits, axis=-1) - picked
            return jnp.sum(each * w) / jnp.maximum(jnp.sum(w), 1.0)

        loss = xent(normed, params["lm_head/kernel"], batch["targets"],
                    batch["loss_weights"])
        counters = dict(counters, loss=loss)
        if self.modules:
            weight = (self.c["mtp_loss_weight"] if faults["mtp_weight"] is None
                      else faults["mtp_weight"])
            targets2, w2 = self.second_targets(batch, faults["second"])
            counters["mtp_loss"] = xent(normed2, params["lm_head/kernel"],
                                        targets2, w2)
            counters["mtp_targets"] = jnp.sum(w2)
            loss = loss + weight * counters["mtp_loss"]
        return loss, counters

    # ------------------------------------------------------- three steps

    def _grad_fn(self, operands, faults):
        key = (operands, tuple(sorted(faults.items())))
        if key not in self._grad_fns:
            self._grad_fns[key] = jax.jit(jax.value_and_grad(functools.partial(
                self.loss, operands=operands, **faults), has_aux=True))
        return self._grad_fns[key]

    def run_steps(self, params0: dict, batches: list, operands="float32",
                  balance=True, **faults) -> dict:
        """Each step's loss (the weighted sum of the two), each leaf's
        gradient at step 1 after the clip (what the optimizer is handed),
        each leaf's change over the steps (the selection biases' by the
        balancing alone: their gradient is zero), float32 on the host, and
        of the first step: the routing counters (``moe_assignments`` summed
        over rows and routed layers, ``moe_max_load`` the largest,
        ``moe_bias_lift`` the mean) with the root mean square of the
        selection biases they are read against, ``mtp_loss`` and
        ``mtp_targets`` (the positions that carry a second target)."""
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
                for i, prefix in enumerate(self.routed_layers()):
                    k = prefix + "feed_forward/expert_bias"
                    params[k] = params[k] + self.c["expert_bias_update_rate"] * jnp.sign(
                        mean - loads[i])
            seconds["gradient"].append(round(t1 - t0, 1))
            seconds["update"].append(round(time.perf_counter() - t1, 1))
        delta = {k: np.asarray(params.pop(k)) - params0[k] for k in sorted(params)}
        out = {"loss": losses, "grad": first, "delta": delta, "seconds": seconds,
               "moe_assignments": float(jnp.sum(counters["assignments"])),
               "moe_max_load": float(jnp.max(counters["max_load"])),
               "moe_dropped": 0.0,      # every held expert sees every token
               "moe_bias_lift": float(jnp.mean(counters["bias_lift"])),
               "moe_bias_rms": float(np.sqrt(np.mean(np.square(np.concatenate(
                   [np.ravel(v) for k, v in params0.items()
                    if k.endswith("expert_bias")])))))}
        if self.modules:
            out["mtp_loss"] = float(counters["mtp_loss"])
            out["mtp_targets"] = float(counters["mtp_targets"])
        return out


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
