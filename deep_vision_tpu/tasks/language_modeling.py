"""Next-token prediction over packed documents: cross-entropy of
``logits[t]`` against ``targets[t] = tokens[t + 1]`` in float32, averaged
over the positions ``loss_weights`` keeps (``data/text.py`` sets a row's last
position and every position whose successor starts another document to 0).

A model may hand back ``(logits, counters)`` in place of the logits alone:
``counters`` is a dict of scalars computed on the device beside the forward
pass (a routed model's ``moe_assignments``, ``moe_max_load``,
``moe_unrouted_tokens``, ``moe_dropped``, ``moe_buffer_rows``,
``moe_bias_lift``), which the loss passes on as the
step's metrics, so that they reach ``metrics.jsonl`` the way
``token_accuracy`` does and without a host read of their own.

A model with a multi-token-prediction module hands back ``(logits, logits2,
counters)``: ``logits2[t]`` predicts ``tokens[t + 2]``.  Its targets and
weights are derived here, on the device, from the batch's own
(``targets2[t] = targets[t + 1]``, ``w2[t] = w[t] * w[t + 1]``: 0 wherever
``t + 1`` or ``t + 2`` leaves the document or the row), so the loaders stay
as they are.  The loss is the first cross-entropy plus ``mtp_loss_weight``
times the second, each a weighted mean over its own kept positions
(DeepSeek-V3 arXiv:2412.19437 s2.2); ``mtp_loss`` and ``mtp_targets`` (the
positions that carry a second target) join the step's metrics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deep_vision_tpu.ops.attention import visited_blocks


def _token_xent(logits, targets):
    logits = logits.astype(jnp.float32)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jax.scipy.special.logsumexp(logits, axis=-1) - picked


def second_targets(targets, weights):
    """``targets`` and ``weights`` one position on; a row's last position
    has no second target."""
    after = jnp.pad(weights[:, 1:], ((0, 0), (0, 1)))
    return (jnp.concatenate([targets[:, 1:], targets[:, -1:]], axis=1),
            weights * after)


class LanguageModelingTask:
    monitor = "token_accuracy"

    def __init__(self, mtp_loss_weight: float = 0.0):
        self.mtp_loss_weight = float(mtp_loss_weight)

    @staticmethod
    def model_inputs(batch: dict) -> tuple:
        """What the model is called with (the zoo's image tasks have no such
        method and the trainer hands their model ``batch["image"]``)."""
        return batch["tokens"], batch["segment_ids"]

    @staticmethod
    def batch_counters(batch: dict) -> dict:
        """Host-side counts of one batch for the input block of
        ``metrics.jsonl``: segment ids count up from each row's first.
        ``pairs`` are the (query, key) pairs a causal document mask leaves
        visible, what an attention layer's score and value products need;
        ``attn_blocks`` the pairs of blocks ``ops/attention.py``'s kernels
        compute for these rows at the op's default ``block`` (a count a tile
        of key heads a layer), ``attn_blocks_causal`` what they would compute
        were every row one document; neither for rows that block does not
        divide."""
        seg = batch["segment_ids"]
        # a document of n tokens leaves n (n + 1) / 2; every row starts one
        first = np.diff(seg, axis=1, prepend=seg[:, :1] - 1) != 0
        n = np.diff(np.flatnonzero(first.ravel()), append=seg.size).astype(np.int64)
        counts = {"tokens": int(seg.size),
                  "documents": int((seg[:, -1] - seg[:, 0] + 1).sum()),
                  "pairs": int((n * (n + 1) // 2).sum())}
        try:
            counts["attn_blocks"], counts["attn_blocks_causal"] = visited_blocks(seg)
        except ValueError:
            pass
        return counts

    @staticmethod
    def _split(outputs) -> tuple:
        """Logits, the prediction module's logits (None where the model has
        none) and the model's own counters ({} where it has none)."""
        if not isinstance(outputs, tuple):
            return outputs, None, {}
        return outputs if len(outputs) == 3 else (outputs[0], None, outputs[1])

    @staticmethod
    def _sums(logits, targets, weights):
        xent = _token_xent(logits, targets)
        hit = jnp.argmax(logits, -1) == targets
        return (xent * weights).sum(), (hit * weights).sum(), weights.sum()

    def loss(self, outputs, batch):
        logits, logits2, counters = self._split(outputs)
        w = batch["loss_weights"].astype(jnp.float32)
        xent, hit, count = self._sums(logits, batch["targets"], w)
        count = jnp.maximum(count, 1.0)
        loss, metrics = xent / count, {"token_accuracy": hit / count, **counters}
        if logits2 is not None:
            targets2, w2 = second_targets(batch["targets"], w)
            metrics["mtp_targets"] = w2.sum()
            metrics["mtp_loss"] = (_token_xent(logits2, targets2) * w2).sum(
                ) / jnp.maximum(metrics["mtp_targets"], 1.0)
            loss = loss + self.mtp_loss_weight * metrics["mtp_loss"]
        return loss, metrics

    def eval_metrics(self, outputs, batch):
        xent, hit, count = self._sums(
            self._split(outputs)[0], batch["targets"],
            batch["loss_weights"].astype(jnp.float32))
        return {"loss": xent, "token_accuracy": hit, "count": count}
