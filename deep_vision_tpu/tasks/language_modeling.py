"""Next-token prediction over packed documents: cross-entropy of
``logits[t]`` against ``targets[t] = tokens[t + 1]`` in float32, averaged
over the positions ``loss_weights`` keeps (``data/text.py`` sets a row's last
position and every position whose successor starts another document to 0).

A model may hand back ``(logits, counters)`` in place of the logits alone:
``counters`` is a dict of scalars computed on the device beside the forward
pass (a routed model's ``moe_assignments``, ``moe_max_load``,
``moe_unrouted_tokens``, ``moe_dropped``, ``moe_bias_lift``), which the loss passes on as the
step's metrics, so that they reach ``metrics.jsonl`` the way
``token_accuracy`` does and without a host read of their own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _token_xent(logits, targets):
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.scipy.special.logsumexp(logits, axis=-1) - picked


class LanguageModelingTask:
    monitor = "token_accuracy"

    @staticmethod
    def model_inputs(batch: dict) -> tuple:
        """What the model is called with (the zoo's image tasks have no such
        method and the trainer hands their model ``batch["image"]``)."""
        return batch["tokens"], batch["segment_ids"]

    @staticmethod
    def batch_counters(batch: dict) -> dict:
        """Host-side counts of one batch for the input block of
        ``metrics.jsonl``: segment ids count up from each row's first."""
        seg = batch["segment_ids"]
        return {"tokens": int(seg.size),
                "documents": int((seg[:, -1] - seg[:, 0] + 1).sum())}

    @staticmethod
    def _split(outputs) -> tuple:
        """Logits and the model's own counters ({} where it has none)."""
        return outputs if isinstance(outputs, tuple) else (outputs, {})

    def _sums(self, logits, batch):
        w = batch["loss_weights"].astype(jnp.float32)
        xent = _token_xent(logits, batch["targets"])
        hit = jnp.argmax(logits, -1) == batch["targets"]
        return (xent * w).sum(), (hit * w).sum(), w.sum()

    def loss(self, outputs, batch):
        logits, counters = self._split(outputs)
        xent, hit, count = self._sums(logits, batch)
        count = jnp.maximum(count, 1.0)
        return xent / count, {"token_accuracy": hit / count, **counters}

    def eval_metrics(self, outputs, batch):
        xent, hit, count = self._sums(self._split(outputs)[0], batch)
        return {"loss": xent, "token_accuracy": hit, "count": count}
