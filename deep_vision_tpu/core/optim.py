"""Optimizers and LR scheduling.

The reference drives LR two ways: torch/Keras ``ReduceLROnPlateau``
(ResNet/pytorch/train.py:358-372, ResNet/tensorflow/train.py:271-272), and
hand-rolled epoch-table decay (YOLO/tensorflow/train.py:56-68,
Hourglass/tensorflow/train.py:46-58) plus CycleGAN's constant-then-linear
``LinearDecay`` (CycleGAN/tensorflow/utils.py:5-28).

Here the optimizer is built with ``optax.inject_hyperparams`` so the learning
rate lives inside ``opt_state`` as a traced scalar: host-side scheduler objects
(plateau logic needs val metrics, so it *must* run on host) rewrite it between
steps without retracing the jitted train step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax


@dataclasses.dataclass
class OptimizerConfig:
    name: str = "sgd"  # sgd | adam | rmsprop
    learning_rate: float = 0.1
    momentum: float = 0.9
    nesterov: bool = False
    weight_decay: float = 0.0  # decoupled, applied to all non-BN params
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    rms_decay: float = 0.9  # torch RMSprop 'alpha' (MobileNet config uses 0.9)
    grad_clip_norm: float | None = None
    # SGD momentum accumulator storage dtype (None = param dtype, f32).
    # "bfloat16" halves the optimizer-state HBM traffic in the elementwise
    # band of the step — its static memory count is in docs/PERF.md; changes
    # update numerics (~1e-3 relative), so NOT part of the parity recipe.
    momentum_dtype: str | None = None


def _weight_decay_mask(params):
    """Decay kernels only — skip biases and BN scale/bias, matching the
    effective behavior of torch SGD weight_decay on conv/fc layers dominating
    the norm (ResNet/pytorch/train.py:166-184 uses blanket 1e-4; we use the
    modern no-BN-decay recipe required to reach 76% top-1).  No leaf of
    rank under 2 is decayed, whatever its name: a state-space layer's
    ``A_log``, ``D`` and ``dt_bias`` are vectors too."""

    def keep(path, x):
        leaf = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return leaf not in ("bias", "scale") and x.ndim >= 2

    return jax.tree_util.tree_map_with_path(keep, params)


def build_optimizer(cfg: OptimizerConfig) -> optax.GradientTransformation:
    if cfg.momentum_dtype not in (None, "bfloat16"):
        raise ValueError(f"momentum_dtype must be None or 'bfloat16', "
                         f"got {cfg.momentum_dtype!r}")
    if cfg.momentum_dtype is not None and cfg.name != "sgd":
        raise ValueError(
            f"momentum_dtype applies to the sgd momentum accumulator "
            f"only; optimizer is {cfg.name!r}")

    def make(learning_rate):
        txs = []
        if cfg.grad_clip_norm:
            txs.append(optax.clip_by_global_norm(cfg.grad_clip_norm))
        if cfg.name == "sgd":
            if cfg.weight_decay:
                txs.append(
                    optax.add_decayed_weights(cfg.weight_decay, mask=_weight_decay_mask)
                )
            acc_dtype = (jnp.bfloat16 if cfg.momentum_dtype == "bfloat16"
                         else None)
            txs.append(optax.sgd(learning_rate, momentum=cfg.momentum,
                                 nesterov=cfg.nesterov,
                                 accumulator_dtype=acc_dtype))
        elif cfg.name == "adam":
            if cfg.weight_decay:
                txs.append(optax.adamw(learning_rate, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
                                       weight_decay=cfg.weight_decay,
                                       mask=_weight_decay_mask))
            else:
                txs.append(optax.adam(learning_rate, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps))
        elif cfg.name == "rmsprop":
            txs.append(optax.rmsprop(learning_rate, decay=cfg.rms_decay,
                                     momentum=cfg.momentum, eps=cfg.eps))
        else:
            raise ValueError(f"unknown optimizer {cfg.name}")
        return optax.chain(*txs)

    return optax.inject_hyperparams(make)(learning_rate=cfg.learning_rate)


def get_learning_rate(opt_state) -> float:
    return float(opt_state.hyperparams["learning_rate"])


def set_learning_rate(opt_state, lr: float):
    """Functionally rewrite the injected LR.  No retrace: same pytree
    shape AND the same placement as the leaf it replaces — a placed
    leaf's sharding is part of its type, so a fresh unplaced scalar in
    the first call (where every later call sees the step's own placed
    output) made jit trace and compile the whole train step a second
    time (ResNet-50 on the v5e: 40 s + 30 s)."""
    hp = dict(opt_state.hyperparams)
    old = hp["learning_rate"]
    new = np.asarray(lr, jnp.asarray(old).dtype)
    if isinstance(old, jax.Array) and old.committed:
        # also right on a multi-process mesh: every process holds the
        # same scalar and contributes its addressable replicas
        hp["learning_rate"] = jax.make_array_from_process_local_data(
            old.sharding, new)
    else:
        hp["learning_rate"] = jnp.asarray(new)
    return opt_state._replace(hyperparams=hp)


# ---------------------------------------------------------------------------
# Host-side schedulers (stateful, epoch-granularity like the reference's)
# ---------------------------------------------------------------------------


class Scheduler:
    """Base contract: ``epoch_begin(epoch)`` fixes the LR used *during*
    ``epoch`` (1-indexed) — so warmup applies to the very first epoch;
    ``step(epoch, metric)`` runs after validation for metric-driven
    schedules (plateau).  Read ``.lr``."""

    def __init__(self, base_lr: float):
        self.base_lr = base_lr
        self.lr = base_lr

    def epoch_begin(self, epoch: int) -> float:
        return self.lr

    def step(self, epoch: int, metric: float | None = None) -> float:
        return self.lr

    def state_dict(self) -> dict:
        return dict(self.__dict__)

    def load_state_dict(self, d: dict):
        self.__dict__.update(d)


class ConstantSchedule(Scheduler):
    pass


class ReduceLROnPlateau(Scheduler):
    """Mirror of torch's, as configured by the reference
    (mode='max' on val top-1, factor=0.1, patience=10 —
    ResNet/pytorch/train.py:186-195)."""

    def __init__(self, base_lr, mode="max", factor=0.1, patience=10,
                 threshold=1e-4, min_lr=0.0):
        super().__init__(base_lr)
        assert mode in ("min", "max")
        self.mode, self.factor, self.patience = mode, factor, patience
        self.threshold, self.min_lr = threshold, min_lr
        self.best: float | None = None
        self.bad_epochs = 0

    def _improved(self, metric: float) -> bool:
        if self.best is None:
            return True
        if self.mode == "max":
            return metric > self.best * (1 + self.threshold)
        return metric < self.best * (1 - self.threshold)

    def step(self, epoch, metric=None):
        if metric is None:
            return self.lr
        if self._improved(metric):
            self.best = metric
            self.bad_epochs = 0
        else:
            self.bad_epochs += 1
            if self.bad_epochs > self.patience:
                self.lr = max(self.lr * self.factor, self.min_lr)
                self.bad_epochs = 0
        return self.lr


class EpochTableSchedule(Scheduler):
    """Piecewise-constant by epoch boundaries — the YOLO/Hourglass pattern
    (YOLO/tensorflow/train.py:56-68: {0:1e-3, 40:1e-4, ...})."""

    def __init__(self, table: dict[int, float]):
        self.table = {int(k): v for k, v in sorted(table.items())}
        super().__init__(next(iter(self.table.values())))

    def epoch_begin(self, epoch):
        for boundary, lr in sorted(self.table.items()):
            if epoch >= boundary:
                self.lr = lr
        return self.lr

    def load_state_dict(self, d: dict):
        # JSON round-trips stringify int keys; restore them
        d = dict(d)
        d["table"] = {int(k): v for k, v in d["table"].items()}
        self.__dict__.update(d)


class LinearDecay(Scheduler):
    """Constant for ``decay_start`` epochs then linear to 0 at ``total`` —
    CycleGAN/tensorflow/utils.py:5-28."""

    def __init__(self, base_lr, total_epochs: int, decay_start: int):
        super().__init__(base_lr)
        self.total_epochs, self.decay_start = total_epochs, decay_start

    def epoch_begin(self, epoch):
        if epoch <= self.decay_start:
            self.lr = self.base_lr
        else:
            frac = (epoch - 1 - self.decay_start) / max(
                1, self.total_epochs - self.decay_start
            )
            self.lr = self.base_lr * max(0.0, 1.0 - frac)
        return self.lr


class WarmupCosine(Scheduler):
    """Linear warmup + cosine decay (per-epoch granularity): the modern
    large-batch recipe needed for the 76% ResNet-50 target (parity-plus;
    the reference itself only used plateau decay)."""

    def __init__(self, base_lr, total_epochs: int, warmup_epochs: int = 5,
                 final_lr: float = 0.0):
        super().__init__(base_lr)
        self.total_epochs, self.warmup_epochs = total_epochs, warmup_epochs
        self.final_lr = final_lr

    def epoch_begin(self, epoch):
        import math

        if epoch <= self.warmup_epochs:
            # ramp base·(1/w) … base·(w/w) over the first w epochs
            self.lr = self.base_lr * epoch / self.warmup_epochs
        else:
            t = (epoch - 1 - self.warmup_epochs) / max(
                1, self.total_epochs - self.warmup_epochs
            )
            self.lr = self.final_lr + 0.5 * (self.base_lr - self.final_lr) * (
                1 + math.cos(math.pi * min(t, 1.0))
            )
        return self.lr


class StepDecay(Scheduler):
    """torch ``StepLR``: lr = base·gamma^(epoch//step_size) — the reference's
    VGG (step 10, γ=0.5) and MobileNet (step 2, γ=0.94, the Inception-V3
    policy) configs (VGG/pytorch/train.py scheduler_params)."""

    def __init__(self, base_lr, step_size: int, gamma: float):
        super().__init__(base_lr)
        self.step_size, self.gamma = step_size, gamma

    def epoch_begin(self, epoch):
        self.lr = self.base_lr * self.gamma ** ((epoch - 1) // self.step_size)
        return self.lr


class SqrtPolyDecay(Scheduler):
    """The reference's Inception V1 LambdaLR policy
    (Inception/pytorch/train.py scheduler_params): base·(1-e/horizon)^0.5
    until ``horizon``, then fixed small multipliers."""

    def __init__(self, base_lr, horizon: int = 60):
        super().__init__(base_lr)
        self.horizon = horizon

    def epoch_begin(self, epoch):
        e = epoch - 1
        if e < self.horizon:
            mult = (1 - e / self.horizon) ** 0.5
        elif e < self.horizon + 15:
            mult = 0.01
        else:
            mult = 0.001
        self.lr = self.base_lr * mult
        return self.lr


SCHEDULERS = {
    "constant": ConstantSchedule,
    "plateau": ReduceLROnPlateau,
    "epoch_table": EpochTableSchedule,
    "linear_decay": LinearDecay,
    "warmup_cosine": WarmupCosine,
    "step": StepDecay,
    "sqrt_poly": SqrtPolyDecay,
}


def build_scheduler(name: str, base_lr: float, **kwargs) -> Scheduler:
    cls = SCHEDULERS[name]
    if cls is EpochTableSchedule:
        return cls(kwargs["table"])
    return cls(base_lr, **kwargs)
