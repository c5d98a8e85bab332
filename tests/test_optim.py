"""Scheduler semantics tests (epoch_begin fixes the LR used DURING the
epoch — warmup must cover epoch 1; tables must survive JSON resume)."""

import json

from deep_vision_tpu.core.optim import (
    EpochTableSchedule,
    LinearDecay,
    ReduceLROnPlateau,
    WarmupCosine,
    build_scheduler,
)


def test_warmup_covers_first_epoch():
    s = WarmupCosine(0.4, total_epochs=90, warmup_epochs=5)
    ramp = [round(s.epoch_begin(e), 4) for e in range(1, 6)]
    assert ramp == [0.08, 0.16, 0.24, 0.32, 0.4]
    # first post-warmup epoch starts at peak, then decays
    assert s.epoch_begin(6) == 0.4
    assert s.epoch_begin(7) < 0.4
    assert s.epoch_begin(90) < 0.01


def test_epoch_table_survives_json_roundtrip():
    s = EpochTableSchedule({1: 1e-3, 40: 1e-4, 60: 1e-5})
    assert s.epoch_begin(1) == 1e-3
    assert s.epoch_begin(45) == 1e-4
    state = json.loads(json.dumps(s.state_dict()))  # stringifies int keys
    s2 = EpochTableSchedule({1: 0.0})
    s2.load_state_dict(state)
    assert s2.epoch_begin(41) == 1e-4
    assert s2.epoch_begin(61) == 1e-5


def test_linear_decay_reaches_zero():
    s = LinearDecay(2e-4, total_epochs=200, decay_start=100)
    assert s.epoch_begin(1) == 2e-4
    assert s.epoch_begin(100) == 2e-4
    assert s.epoch_begin(101) == 2e-4  # first decayed epoch is still ~base
    assert s.epoch_begin(151) == 1e-4
    assert s.epoch_begin(201) == 0.0


def test_plateau_decays_after_patience():
    s = ReduceLROnPlateau(0.1, mode="max", factor=0.1, patience=2)
    s.step(1, 0.5)
    for e in range(2, 6):
        s.step(e, 0.4)  # no improvement ×4 > patience 2
    assert abs(s.lr - 0.01) < 1e-9


def test_build_scheduler_registry():
    s = build_scheduler("epoch_table", 0.0, table={1: 1e-3})
    assert isinstance(s, EpochTableSchedule)
    s = build_scheduler("warmup_cosine", 0.1, total_epochs=10)
    assert isinstance(s, WarmupCosine)


def test_momentum_dtype_bf16_accumulator():
    """momentum_dtype='bfloat16' stores the SGD trace in bf16 (the
    optimizer-state bandwidth lever, docs/PERF.md) and is rejected
    for anything but sgd / any other dtype string."""
    import jax
    import jax.numpy as jnp
    import pytest

    from deep_vision_tpu.core.optim import OptimizerConfig, build_optimizer

    params = {"w": jnp.ones((4, 4), jnp.float32)}
    tx = build_optimizer(OptimizerConfig(name="sgd", learning_rate=0.1,
                                         momentum=0.9,
                                         momentum_dtype="bfloat16"))
    st = tx.init(params)
    accs = [l for l in jax.tree_util.tree_leaves(st)
            if getattr(l, "shape", None) == (4, 4)]
    assert accs and all(l.dtype == jnp.bfloat16 for l in accs)
    upd, _ = tx.update({"w": jnp.full((4, 4), 0.5)}, st, params)
    assert jnp.isfinite(upd["w"]).all()

    with pytest.raises(ValueError, match="momentum_dtype"):
        build_optimizer(OptimizerConfig(name="sgd", momentum_dtype="bf16"))
    with pytest.raises(ValueError, match="sgd"):
        build_optimizer(OptimizerConfig(name="adam",
                                        momentum_dtype="bfloat16"))
