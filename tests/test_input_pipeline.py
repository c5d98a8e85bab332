"""Train-input pipeline: staged prefetcher, uint8 wire, fused ingest.

Covers the input-side acceptance bar: uint8-vs-float32 wire parity (same
eval metric, 4× smaller image DMA), `train_ingest` interpret-mode parity
vs `jitter_normalize`, staging-buffer reuse bounds, stage timers summing
to wall time, donation safety, and abandoned-epoch cleanup (no leaked
producer thread, no pinned device batches).
"""

import gc
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.data.pipeline import DevicePrefetcher

pytestmark = pytest.mark.input_pipeline


def _batches(n_batches: int, batch: int = 16, size: int = 8,
             dtype=np.uint8, seed: int = 0):
    rng = np.random.default_rng(seed)
    for _ in range(n_batches):
        img = rng.integers(0, 256, size=(batch, size, size, 3))
        yield {"image": img.astype(dtype),
               "label": rng.integers(0, 10, size=batch).astype(np.int32)}


# -- staging pool + prefetcher plumbing --------------------------------------


def test_staging_pool_reuse_bounded(mesh1):
    """N batches must NOT allocate N buffers: steady state holds at most
    depth+2 staging buffers per distinct leaf shape (depth+1 plus one for
    CPU zero-copy deferred release), and a second epoch through the same
    prefetcher reuses the pool instead of growing it."""
    depth = 2
    pf = DevicePrefetcher(mesh1, depth=depth)
    try:
        for b in pf.iterate(_batches(16)):
            jax.block_until_ready(b["image"])
        # 2 pooled leaf shapes (image, label) × at most depth+2 each
        bound = (depth + 2) * 2
        assert pf.pool.allocated <= bound
        del b
        gc.collect()  # return zero-copy-deferred buffers before epoch 2
        for b in pf.iterate(_batches(16)):
            jax.block_until_ready(b["image"])
        st = pf.pool.stats()
        assert st["allocated"] <= bound  # epoch 2 rode the same pool
        assert st["reused"] >= 16  # far more reuse than allocation
    finally:
        pf.close()


def test_h2d_bytes_accounted_per_key(mesh1):
    """uint8 wire carries exactly 1/4 the image bytes of the f32 wire —
    measured on the image key alone, not diluted by labels."""
    def run(dtype):
        pf = DevicePrefetcher(mesh1, depth=1)
        try:
            stream = pf.iterate(_batches(4, dtype=dtype))
            for b in stream:
                jax.block_until_ready(b["image"])
            return stream.stats()["h2d_bytes_by_key"]
        finally:
            pf.close()

    u8, f32 = run(np.uint8), run(np.float32)
    assert f32["image"] == 4 * u8["image"]
    assert f32["label"] == u8["label"]  # labels int32 on both wires


def test_stage_timers_sum_to_wall(mesh1):
    """Consumer-side stall + step spans the whole epoch wall time (the
    Span construction guarantees each side's stages sum exactly); the
    producer reports all four of its stages."""
    import time

    pf = DevicePrefetcher(mesh1, depth=2)
    try:
        t0 = time.perf_counter()
        stream = pf.iterate(_batches(6))
        for b in stream:
            jax.block_until_ready(b["image"])
            time.sleep(0.01)  # a visible "step" so both sides are nonzero
        wall_ms = (time.perf_counter() - t0) * 1e3
        st = stream.stats()
    finally:
        pf.close()
    assert st["batches"] == 6
    assert 0.0 <= st["input_stall_frac"] <= 1.0
    assert st["stall_ms"] + st["step_ms"] == pytest.approx(wall_ms, abs=60)
    for stage in ("prep_wait", "assemble", "h2d", "enqueue"):
        assert st["producer_ms"].get(stage, -1.0) >= 0.0
    assert st["h2d_bytes_per_step"] > 0


def test_consumer_marks_keep_the_sums_and_share_batch_numbers(mesh1):
    """A loop that marks its own stages (as ``Trainer.train_epoch`` does)
    changes no number: ``stall_ms + step_ms`` is still the wall time,
    ``step_ms`` is every stage but ``stall``, and ``input_stall_frac`` is
    what an unmarked loop with the same waits reads.  The intervals of
    both threads carry the batch's number in the epoch."""
    import time

    def epoch(marked: bool):
        pf = DevicePrefetcher(mesh1, depth=2)
        try:
            t0 = time.perf_counter()
            stream = pf.iterate(_batches(5))
            for i, b in enumerate(stream):
                jax.block_until_ready(b["image"])
                time.sleep(0.02)
                if marked:
                    stream.mark("dispatch")
                time.sleep(0.02)
                if marked and i % 2 == 0:
                    stream.mark("fetch")
                    stream.mark("log")
            wall_ms = (time.perf_counter() - t0) * 1e3
            return stream.stats(), stream.intervals(), wall_ms
        finally:
            pf.close()

    plain, _, _ = epoch(marked=False)
    st, ivs, wall_ms = epoch(marked=True)
    assert st["batches"] == 5
    assert st["stall_ms"] + st["step_ms"] == pytest.approx(wall_ms, abs=60)
    assert st["step_ms"] >= 5 * 40 - 1
    assert st["input_stall_frac"] == pytest.approx(
        st["stall_ms"] / (st["stall_ms"] + st["step_ms"]))
    assert st["input_stall_frac"] == pytest.approx(
        plain["input_stall_frac"], abs=0.15)
    cons = [iv for iv in ivs if iv[0] == "consumer"]
    prod = [iv for iv in ivs if iv[0] == "producer"]
    assert {s for _, s, *_ in cons} == {"stall", "dispatch", "fetch", "log",
                                        "step"}
    assert {s for _, s, *_ in prod} == {"prep_wait", "assemble", "h2d",
                                        "enqueue"}
    # each thread's intervals tile its span; the batch number is shared
    for thread in (cons, prod):
        assert all(a[4] == b[3] for a, b in zip(thread, thread[1:]))
    assert [b for _, s, b, *_ in cons if s == "dispatch"] == [0, 1, 2, 3, 4]
    assert [b for _, s, b, *_ in cons if s == "fetch"] == [0, 2, 4]
    assert [b for _, s, b, *_ in prod if s == "h2d"] == [0, 1, 2, 3, 4]
    # the last dequeue finds the end of the stream, not a batch
    assert cons[-1][1:3] == ("stall", 5)


def test_abandoned_epoch_leaks_nothing(mesh1):
    """Abandoning iteration mid-epoch (preemption, divergence abort) must
    not leave a producer thread behind nor device batches pinned in the
    queue."""
    gc.collect()
    base_threads = threading.active_count()
    base_arrays = len(jax.live_arrays())
    for _ in range(5):
        pf = DevicePrefetcher(mesh1, depth=4)
        stream = pf.iterate(_batches(64))
        next(stream)  # consume one batch, then walk away
        pf.close()
        assert not stream.alive
        del pf, stream
    gc.collect()
    assert threading.active_count() == base_threads
    # queued device batches were dropped by close(); nothing stays pinned
    assert len(jax.live_arrays()) <= base_arrays + 2


def test_early_stop_joins_producer_and_errors_propagate(mesh1):
    """A consumer that stops early and closes the prefetcher joins the
    producer thread, and a producer exception surfaces at the consumer."""
    base = threading.active_count()
    pf = DevicePrefetcher(mesh1, depth=2)
    stream = pf.iterate(_batches(64))
    next(stream)
    pf.close()
    assert threading.active_count() == base

    def poisoned():
        yield from _batches(2)
        raise RuntimeError("loader exploded")

    pf = DevicePrefetcher(mesh1, depth=2)
    try:
        with pytest.raises(RuntimeError, match="loader exploded"):
            for _ in pf.iterate(poisoned()):
                pass
    finally:
        pf.close()


def test_donated_batches_stay_correct_across_epochs(mesh1):
    """Device batches are donated into the jitted step (the trainer's
    donate_argnums=(0, 1)); the staging buffers they came from are reused
    every epoch.  Two epochs over identical data must produce identical
    losses — donation must never corrupt a buffer still in the pool."""

    def step(b):
        return jnp.sum(b["image"].astype(jnp.float32)) + jnp.sum(b["label"])

    donating = jax.jit(step, donate_argnums=(0,))

    def losses():
        pf = DevicePrefetcher(mesh1, depth=2)
        try:
            return [float(donating(b)) for b in pf.iterate(_batches(6))]
        finally:
            pf.close()

    assert losses() == losses()


# -- fused train-ingest kernel ------------------------------------------------


def test_train_ingest_interpret_parity():
    """Fused kernel == jitter_normalize at the PR 10 tolerance bar, for
    the production 3-channel shape and a non-square one."""
    from deep_vision_tpu.ops.pallas_ops import (
        train_ingest,
        train_ingest_factors,
    )
    from deep_vision_tpu.ops.preprocess import jitter_normalize

    for shape in ((4, 32, 32, 3), (2, 24, 40, 3)):
        x = jnp.asarray(np.random.default_rng(5).integers(
            0, 256, size=shape, dtype=np.uint8))
        rng = jax.random.PRNGKey(3)
        got = train_ingest(x, train_ingest_factors(x, rng), interpret=True)
        want = jitter_normalize(x, rng, train=True)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_train_ingest_parity_check_and_selection(monkeypatch):
    """The preprocess factory runs the one-batch parity check before it
    bakes the fused kernel in; the XLA path is what ``use_fused=False``
    asks for, never what a failed check falls back to — a kernel that
    diverges raises with the error size."""
    import pytest

    from deep_vision_tpu.ops import pallas_ops
    from deep_vision_tpu.ops.preprocess import (
        jitter_normalize,
        make_imagenet_preprocess,
    )

    shape = (4, 16, 16, 3)
    assert pallas_ops.train_ingest_parity(shape, interpret=True) <= 1e-4

    fn = make_imagenet_preprocess(use_fused=True, fused_shape=shape)
    assert fn.fused
    x = jnp.asarray(np.random.default_rng(2).integers(
        0, 256, size=shape, dtype=np.uint8))
    rng = jax.random.PRNGKey(11)
    got = fn({"image": x}, rng, train=True)["image"]
    want = jitter_normalize(x, rng, train=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    fb = make_imagenet_preprocess(use_fused=False)
    assert not fb.fused
    np.testing.assert_allclose(fb({"image": x}, rng, train=True)["image"],
                               want, rtol=1e-6, atol=1e-7)

    monkeypatch.setattr(pallas_ops, "train_ingest",
                        lambda x, factors, kind, interpret=False:
                        jnp.zeros(x.shape, jnp.float32))
    with pytest.raises(RuntimeError, match="max error"):
        make_imagenet_preprocess(use_fused=True, fused_shape=shape)

    # float batches pass through untouched on both paths
    xf = jnp.ones(shape, jnp.float32)
    assert fn({"image": xf}, rng, train=True)["image"] is xf


# -- uint8 wire end to end ----------------------------------------------------


class _PlainXentTask:
    """Plain cross-entropy task for the wire-parity test: the same math
    as ``ClassificationTask`` without the logits barrier, so both wires
    compile to the simplest comparable program."""

    monitor = "top1"

    def loss(self, outputs, batch):
        import optax

        labels = batch["label"]
        logits = outputs.astype(jnp.float32)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, {"top1": (jnp.argmax(logits, -1) == labels).mean()}

    def eval_metrics(self, outputs, batch):
        import optax

        labels = batch["label"]
        logits = outputs.astype(jnp.float32)
        w = batch.get("weight", jnp.ones(labels.shape[0], jnp.float32))
        xent = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels)
        return {"loss": (xent * w).sum(),
                "top1": ((jnp.argmax(logits, -1) == labels) * w).sum(),
                "count": w.sum()}


def test_uint8_wire_matches_f32_wire_eval_metric(tmp_path, mesh1):
    """Same pixels shipped as uint8 (device normalize) and as
    host-normalized float32 train to the same eval metric — the wire is
    a transport change, not a numerics change."""
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.data.loader import ArrayLoader
    from deep_vision_tpu.data.mnist import MEAN, STD
    from deep_vision_tpu.ops.preprocess import make_mnist_preprocess

    rng = np.random.default_rng(0)
    n = 96
    u8 = rng.integers(0, 256, size=(n, 32, 32, 1)).astype(np.uint8)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    f32 = ((u8.astype(np.float32) / 255.0) - MEAN) / STD

    def run(images, preprocess_fn, workdir):
        cfg = get_config("lenet5")
        cfg.total_epochs = 1
        cfg.batch_size = cfg.eval_batch_size = 32
        trainer = Trainer(cfg, cfg.model(), _PlainXentTask(),
                          mesh=mesh1, workdir=str(workdir),
                          preprocess_fn=preprocess_fn)
        data = {"image": images, "label": labels}
        loader = ArrayLoader(data, 32, seed=cfg.seed)
        val = ArrayLoader(data, 32, shuffle=False)
        state = trainer.fit(loader, val, resume=False)
        metrics = trainer.evaluate(state, val)
        return metrics, trainer

    m_u8, tr = run(u8, make_mnist_preprocess(), tmp_path / "u8")
    m_f32, _ = run(f32, None, tmp_path / "f32")
    assert m_u8["top1"] == pytest.approx(m_f32["top1"], abs=1e-6)
    assert m_u8["loss"] == pytest.approx(m_f32["loss"], rel=1e-4)
    # the trainer logged the input-goodput block for the epoch
    assert tr.logger.latest("input_stall_frac") is not None
    assert tr.logger.latest("input_h2d_bytes_per_step") > 0


def test_gan_uint8_wire_roundtrip():
    """GAN loaders' uint8 wire + traced prologue reproduces the host
    [-1,1] scaling exactly on representable values."""
    from deep_vision_tpu.data.gan import synthetic_unpaired, to_uint8_wire
    from deep_vision_tpu.ops.preprocess import make_gan_preprocess

    a_f, b_f = synthetic_unpaired(8, image_size=16, seed=3)
    a_u8, b_u8 = synthetic_unpaired(8, image_size=16, seed=3,
                                    device_normalize=True)
    assert a_u8.dtype == np.uint8 and b_u8.dtype == np.uint8
    assert np.array_equal(a_u8, to_uint8_wire(a_f))

    fn = make_gan_preprocess()
    out = fn({"image_a": jnp.asarray(a_u8), "image_b": jnp.asarray(b_u8)},
             jax.random.PRNGKey(0), train=True)
    # uint8 quantization is the only delta: within half a pixel step
    np.testing.assert_allclose(np.asarray(out["image_a"]), a_f,
                               atol=1.0 / 255.0)
    # float inputs pass through untouched
    xf = jnp.asarray(a_f)
    assert fn({"image_a": xf}, jax.random.PRNGKey(0), train=True)[
        "image_a"] is xf


def test_mnist_uint8_wire_matches_host_preprocess():
    from deep_vision_tpu.data.mnist import pad_uint8, preprocess
    from deep_vision_tpu.ops.preprocess import serve_normalize

    raw = np.random.default_rng(1).integers(
        0, 256, size=(4, 28, 28)).astype(np.uint8)
    wire = pad_uint8(raw)
    assert wire.dtype == np.uint8 and wire.shape == (4, 32, 32, 1)
    np.testing.assert_allclose(
        np.asarray(serve_normalize(jnp.asarray(wire), "mnist")),
        preprocess(raw), rtol=1e-6, atol=1e-6)
