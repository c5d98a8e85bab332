"""Classification task: softmax cross-entropy + top-k accuracy.

Mirrors the reference's ``nn.CrossEntropyLoss`` + ``accuracy(topk=(1,5))``
(ResNet/pytorch/train.py:358, :524-538) and the Inception multi-head loss
(aux classifiers weighted 0.3 — Inception/pytorch/train.py discounts per the
GoogLeNet paper; model emits (logits, aux1, aux2) in training mode,
Inception/pytorch/models/inception_v1.py:92-113).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def _materialize(logits):
    """f32 logits behind an optimization barrier.

    Without the barrier, XLA on TPU may fuse/rematerialize the (bf16)
    classifier matmul separately into the cross-entropy's max-reduce and
    exp-sum-reduce; the two recomputations can disagree in the last bf16
    bits, so the computed log-normalizer falls BELOW the true-class logit
    and the "cross-entropy" goes negative (observed: −0.04/sample on a
    converged eval whose true loss was 1e-6 — a ~0.04 absolute error
    hiding inside every fused eval loss).  The barrier forces the logits
    to materialize once, making both reductions read the same values.
    """
    return jax.lax.optimization_barrier(logits.astype(jnp.float32))


class ClassificationTask:
    monitor = "top1"

    def __init__(self, num_classes: int, label_smoothing: float = 0.0,
                 aux_weight: float = 0.3):
        self.num_classes = num_classes
        self.label_smoothing = label_smoothing
        self.aux_weight = aux_weight

    def _xent(self, logits, labels):
        logits = _materialize(logits)
        if self.label_smoothing > 0:
            onehot = optax.smooth_labels(
                jnp.eye(self.num_classes)[labels], self.label_smoothing)
            return optax.softmax_cross_entropy(logits, onehot).mean()
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    def loss(self, outputs, batch):
        labels = batch["label"]
        if isinstance(outputs, (tuple, list)):  # main + aux heads (Inception)
            main, *aux = outputs
            loss = self._xent(main, labels)
            for a in aux:
                loss = loss + self.aux_weight * self._xent(a, labels)
            logits = main
        else:
            loss = self._xent(outputs, labels)
            logits = outputs
        top1 = (jnp.argmax(logits, -1) == labels).mean()
        return loss, {"top1": top1}

    def eval_metrics(self, outputs, batch):
        logits = outputs[0] if isinstance(outputs, (tuple, list)) else outputs
        logits = _materialize(logits)
        labels = batch["label"]
        # weight=0 marks padded filler rows from pad_last loaders
        w = batch.get("weight", jnp.ones(labels.shape[0], jnp.float32))
        xent = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        top1 = ((jnp.argmax(logits, -1) == labels) * w).sum()
        k = min(5, logits.shape[-1])
        topk_idx = jnp.argsort(logits, -1)[:, -k:]
        top5 = ((topk_idx == labels[:, None]).any(-1) * w).sum()
        return {"loss": (xent * w).sum(), "top1": top1,
                "top5": top5, "count": w.sum()}
