"""Int8 post-training quantization + fused Pallas ingest (CPU, tier-1).

The int8 serving contract (docs/SERVING.md "Wire format & inference
dtype"): ``--infer-dtype int8`` quantizes conv/dense kernels to
symmetric per-channel int8 AT LOAD (serve/quant.py), keeps them
int8-resident in HBM (~0.26× the f32 footprint — the WeightCache
then admits ~4× more versions per budget), and runs bucket programs
that dequantize in-trace with float32 accumulation and float32
outputs.  On the uint8 wire the serve prologue is a single fused
Pallas pass (ops/pallas_ops.serve_ingest: decode + normalize +
activation-quantize in one VMEM trip), interpret-mode here on CPU,
with the XLA prologue as the always-available fallback — the two
must agree to ≤ 1 quantization step.

Uses LeNet at random init (restore's no-checkpoint fallback), same as
the wire-format suite: the gates are about dtype plumbing and
agreement with the f32 path, not learned accuracy."""

import numpy as np
import pytest

from deep_vision_tpu.serve.engine import BatchingEngine
from deep_vision_tpu.serve.quant import (
    Calibration,
    calibrate,
    dequantize_params,
    load_calibration_dir,
    quantize_params,
    synthetic_calibration_batches,
)
from deep_vision_tpu.serve.registry import ModelRegistry

pytestmark = pytest.mark.serve

MNIST_MEAN, MNIST_STD = 0.1307, 0.3081


@pytest.fixture(scope="module")
def quant_serving(tmp_path_factory):
    """One restore, f32 reference + int8 via both ingest paths."""
    reg = ModelRegistry()
    td = str(tmp_path_factory.mktemp("quant_workdir"))
    sm_f32 = reg.load_checkpoint("lenet5", td, name="lenet_f32q")
    sm_i8 = reg.load_checkpoint("lenet5", td, name="lenet_i8",
                                wire_dtype="uint8", infer_dtype="int8")
    sm_i8_xla = reg.load_checkpoint("lenet5", td, name="lenet_i8_xla",
                                    wire_dtype="uint8",
                                    infer_dtype="int8", ingest="xla")
    return sm_f32, sm_i8, sm_i8_xla


def _raw_images(n, shape=(32, 32, 1)):
    return [np.random.RandomState(i).randint(0, 256, shape, dtype=np.uint8)
            for i in range(n)]


def _host_normalized(raw):
    return [((r.astype(np.float32) / 255.0) - MNIST_MEAN) / MNIST_STD
            for r in raw]


def _serve_all(engine, images, timeout=120):
    from concurrent.futures import wait

    futs = [engine.submit(x) for x in images]
    wait(futs, timeout)
    return [np.asarray(f.result(0)) for f in futs]


# -- weight quantization ---------------------------------------------------


def test_quantize_params_roundtrip():
    """Kernels → int8 + per-channel (cout,) scales with ≤ half-step
    dequant error; 1-D leaves pass through with identity scales."""
    rng = np.random.RandomState(0)
    params = {"conv": {"kernel": rng.randn(3, 3, 4, 8).astype(np.float32),
                       "bias": rng.randn(8).astype(np.float32)},
              "dense": {"kernel": rng.randn(16, 10).astype(np.float32)}}
    q, s = quantize_params(params)
    assert q["conv"]["kernel"].dtype == np.int8
    assert s["conv"]["kernel"].shape == (8,)
    assert q["dense"]["kernel"].dtype == np.int8
    assert s["dense"]["kernel"].shape == (10,)
    # bias untouched, scalar identity scale keeps the trees congruent
    np.testing.assert_array_equal(q["conv"]["bias"],
                                  params["conv"]["bias"])
    assert s["conv"]["bias"].shape == ()
    assert float(s["conv"]["bias"]) == 1.0
    # symmetric round-to-nearest: |deq - w| ≤ scale/2 everywhere
    for key in ("conv", "dense"):
        w = params[key]["kernel"]
        deq = (q[key]["kernel"].astype(np.float32)
               * s[key]["kernel"].astype(np.float32))
        assert np.max(np.abs(deq - w)) <= np.max(s[key]["kernel"]) / 2 + 1e-7
        # absmax channels hit ±127 exactly (symmetric, no zero-point)
        assert np.max(np.abs(q[key]["kernel"])) == 127


def test_quantize_zero_channel_guard():
    """An all-zero output channel gets scale 1.0 and exact-zero int8
    codes instead of a 0/0."""
    w = np.random.RandomState(1).randn(5, 4).astype(np.float32)
    w[:, 2] = 0.0
    q, s = quantize_params({"k": w})
    assert float(s["k"][2]) == 1.0
    np.testing.assert_array_equal(q["k"][:, 2], np.zeros(5, np.int8))
    assert np.isfinite(s["k"]).all()


def test_dequantize_params_traced():
    import jax.numpy as jnp

    w = np.random.RandomState(2).randn(6, 3).astype(np.float32)
    q, s = quantize_params({"k": w, "b": np.ones(3, np.float32)})
    deq = dequantize_params(
        {"k": jnp.asarray(q["k"]), "b": jnp.asarray(q["b"])},
        {"k": jnp.asarray(s["k"]), "b": jnp.asarray(s["b"])})
    assert deq["k"].dtype == jnp.float32
    assert deq["b"].dtype == jnp.float32  # passthrough keeps its dtype
    np.testing.assert_allclose(np.asarray(deq["k"]),
                               q["k"].astype(np.float32) * s["k"],
                               atol=0)


# -- calibration -----------------------------------------------------------


def test_synthetic_calibration_deterministic():
    a = synthetic_calibration_batches((8, 8, 1), n_batches=2, batch_size=4)
    b = synthetic_calibration_batches((8, 8, 1), n_batches=2, batch_size=4)
    assert len(a) == len(b) == 2
    for x, y in zip(a, b):
        assert x.dtype == np.uint8 and x.shape == (4, 8, 8, 1)
        np.testing.assert_array_equal(x, y)


def test_calibrate_is_pure(quant_serving):
    """Same model + same batches → bit-identical scales and ranges (the
    determinism gate: a hot reload recalibrates and must agree)."""
    sm_f32, sm_i8, _ = quant_serving
    batches = synthetic_calibration_batches(sm_f32.input_shape)
    c1 = calibrate(sm_f32._model, sm_f32._variables, batches, "mnist")
    c2 = calibrate(sm_f32._model, sm_f32._variables, batches, "mnist")
    assert isinstance(c1, Calibration)
    assert c1.act_scale == c2.act_scale > 0
    assert c1.act_absmax == c2.act_absmax
    assert c1.ranges and c1.ranges == c2.ranges
    # the registry load calibrated the SAME weights on the SAME
    # synthetic batches — its recorded scale must match too
    assert sm_i8.quant.act_scale == c1.act_scale
    with pytest.raises(ValueError, match="at least one batch"):
        calibrate(sm_f32._model, sm_f32._variables, [], "mnist")


def test_load_calibration_dir(tmp_path):
    rng = np.random.RandomState(3)
    np.save(tmp_path / "a.npy",
            rng.randint(0, 256, (6, 8, 8, 1), dtype=np.uint8))
    np.save(tmp_path / "b.npy",
            rng.randint(0, 256, (8, 8, 1), dtype=np.uint8))  # single HWC
    batches = load_calibration_dir(str(tmp_path), (8, 8, 1),
                                   n_batches=2, batch_size=3)
    assert len(batches) == 2
    assert all(b.shape == (3, 8, 8, 1) and b.dtype == np.uint8
               for b in batches)
    with pytest.raises(FileNotFoundError, match="calibration"):
        load_calibration_dir(str(tmp_path / "empty"), (8, 8, 1))
    bad = tmp_path / "bad"
    bad.mkdir()
    np.save(bad / "x.npy", np.zeros((2, 4, 4, 3), np.uint8))
    with pytest.raises(ValueError, match="expected uint8 images"):
        load_calibration_dir(str(bad), (8, 8, 1))


# -- fused Pallas ingest (interpret mode on CPU) ---------------------------


def test_ingest_decode_normalize_parity():
    """quantize=False mode is serve_normalize's math: decode /255 then
    (x-mean)/std, per family, to the same tolerance the XLA prologue is
    held to against the host path."""
    import jax.numpy as jnp

    from deep_vision_tpu.ops.pallas_ops import serve_ingest
    from deep_vision_tpu.ops.preprocess import serve_normalize

    gray = np.random.RandomState(0).randint(0, 256, (3, 32, 32, 1),
                                            dtype=np.uint8)
    got = np.asarray(serve_ingest(jnp.asarray(gray), "mnist",
                                  quantize=False, interpret=True))
    want = np.asarray(serve_normalize(jnp.asarray(gray), "mnist"))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=1e-6)

    rgb = np.random.RandomState(1).randint(0, 256, (2, 8, 8, 3),
                                           dtype=np.uint8)
    got = np.asarray(serve_ingest(jnp.asarray(rgb), "imagenet",
                                  quantize=False, interpret=True))
    want = np.asarray(serve_normalize(jnp.asarray(rgb), "imagenet"))
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_ingest_quantize_matches_xla_prologue():
    """The fused kernel's int8 activations agree with the two-op XLA
    path (serve_normalize → quantize_activations) to ≤ 1 step — the
    same bar serve_ingest_parity holds the compiled kernel to on TPU."""
    import jax.numpy as jnp

    from deep_vision_tpu.ops.pallas_ops import serve_ingest
    from deep_vision_tpu.ops.preprocess import (
        quantize_activations,
        serve_normalize,
    )

    act_scale = 2.8 / 127.0
    raw = np.random.RandomState(2).randint(0, 256, (4, 32, 32, 1),
                                           dtype=np.uint8)
    got = np.asarray(serve_ingest(jnp.asarray(raw), "mnist",
                                  act_scale=act_scale, interpret=True))
    assert got.dtype == np.int8
    ref = np.asarray(quantize_activations(
        serve_normalize(jnp.asarray(raw), "mnist"), act_scale))
    assert np.max(np.abs(got.astype(np.int32)
                         - ref.astype(np.int32))) <= 1


def test_ingest_parity_check():
    from deep_vision_tpu.ops.pallas_ops import serve_ingest_parity

    assert serve_ingest_parity((8, 32, 32, 1), "mnist", 2.8 / 127.0,
                               interpret=True) <= 1
    assert serve_ingest_parity((2, 8, 8, 3), "imagenet", 3.1 / 127.0,
                               interpret=True) <= 1


# -- the int8 serving path end to end --------------------------------------


def test_int8_top1_agreement(quant_serving, images_with_margin):
    """Acceptance gate: int8 engines return FLOAT32 outputs within
    loose tolerance of the f32 path with top-1 intact (the bf16 bar),
    and the Pallas-ingest and XLA-ingest engines agree with each other
    to the tight tolerance (same quantized weights, ≤1-step ingest
    difference).  The inputs' top-2 f32 logits lie further apart than
    two logits may move inside the loose tolerance, so top-1 is the
    tolerance's to keep."""
    sm_f32, sm_i8, sm_i8_xla = quant_serving
    f32_program = sm_f32.compile_bucket(8)
    raw = images_with_margin(
        lambda r: np.asarray(f32_program(np.stack(_host_normalized(r)))),
        12, margin=0.2)
    kw = dict(buckets=[4, 8], max_wait_ms=150, watchdog_interval_s=0)
    with BatchingEngine(sm_f32, **kw) as eng:
        ref = _serve_all(eng, _host_normalized(raw[:8]))
        ref += _serve_all(eng, _host_normalized(raw[8:]))
    with BatchingEngine(sm_i8, **kw) as eng:
        got = _serve_all(eng, raw[:8])
        got += _serve_all(eng, raw[8:])
        stats = eng.stats()
    assert stats["infer_dtype"] == "int8"
    assert stats["weight_hbm_bytes"] == sm_i8.param_bytes()
    for a, b in zip(ref, got):
        assert b.dtype == np.float32
        np.testing.assert_allclose(a, b, atol=5e-2, rtol=5e-2)
        assert 2 * (5e-2 + 5e-2 * np.abs(b).max()) < 0.2
        assert int(np.argmax(a)) == int(np.argmax(b))
    with BatchingEngine(sm_i8_xla, **kw) as eng:
        got_x = _serve_all(eng, raw[:8])
        got_x += _serve_all(eng, raw[8:])
    for a, b in zip(got, got_x):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
        assert int(np.argmax(a)) == int(np.argmax(b))
    assert sm_i8.ingest_path == "pallas"  # uint8 wire, no TPU veto here
    assert sm_i8_xla.ingest_path == "xla"


def test_int8_weight_footprint_and_describe(quant_serving):
    """Acceptance gate: int8 weight HBM ≤ 0.27× f32 (int8 kernels +
    f32 scales/biases), priced by param_bytes() and surfaced in
    describe()'s quant block."""
    sm_f32, sm_i8, _ = quant_serving
    ratio = sm_i8.param_bytes() / sm_f32.param_bytes()
    assert ratio <= 0.27, f"int8/f32 weight bytes {ratio:.4f} > 0.27"
    d = sm_i8.describe()
    assert d["infer_dtype"] == "int8"
    q = d["quant"]
    assert q["act_scale"] > 0 and q["act_absmax"] > 0
    assert q["calib_source"] == "synthetic"
    assert q["calib_batches"] == 2
    assert q["activation_ranges"] > 0
    assert q["param_bytes"] == sm_i8.param_bytes()
    assert q["ingest"] == "pallas"
    assert "quant" not in sm_f32.describe()


def test_int8_validation_and_stablehlo_rejection():
    reg = ModelRegistry()
    # int8 is an INFER dtype, never a wire format
    with pytest.raises(ValueError, match="wire_dtype"):
        reg.load_checkpoint("lenet5", "/nonexistent", wire_dtype="int8")
    with pytest.raises(ValueError, match="ingest"):
        reg.load_checkpoint("lenet5", "/nonexistent",
                            infer_dtype="int8", ingest="mosaic")
    # exported blobs serve exactly their traced f32 signature — every
    # non-f32 knob names the checkpoint path, checked before any I/O
    for kw in ({"infer_dtype": "int8"}, {"infer_dtype": "bfloat16"},
               {"wire_dtype": "uint8"}):
        with pytest.raises(ValueError,
                           match="f32-wire/f32-compute only"):
            reg.load_exported("lenet5", "/nonexistent.bin",
                              "/nonexistent", **kw)


def test_int8_does_not_recompile_f32_programs(quant_serving):
    """Compiling an int8 bucket must not invalidate a retained f32
    program: the f32 callable compiled BEFORE still serves identical
    outputs AFTER (the no-global-recompile acceptance)."""
    sm_f32, sm_i8, _ = quant_serving
    x = np.stack(_host_normalized(_raw_images(4)))
    call_f32 = sm_f32.compile_bucket(4)
    before = np.asarray(call_f32(x.copy()))
    call_i8 = sm_i8.compile_bucket(4)
    raw4 = np.stack(_raw_images(4))
    out_i8 = np.asarray(call_i8(raw4))
    assert out_i8.dtype == np.float32
    after = np.asarray(call_f32(x.copy()))
    np.testing.assert_array_equal(before, after)


# -- WeightCache density + spill/re-admit ----------------------------------


def test_weight_cache_admits_more_int8_versions(quant_serving,
                                                tmp_path_factory):
    """A budget sized for ONE f32 version holds ≥ 3 int8 versions
    resident simultaneously (the ~4× density win the control plane's
    version retention buys from quantization)."""
    from deep_vision_tpu.serve.models import WeightCache

    sm_f32, _, _ = quant_serving
    reg = ModelRegistry()
    td = str(tmp_path_factory.mktemp("cache_workdir"))
    versions = [reg.load_checkpoint("lenet5", td, name=f"lenet_i8_v{k}",
                                    wire_dtype="uint8",
                                    infer_dtype="int8")
                for k in range(3)]
    cache = WeightCache(budget_bytes=sm_f32.param_bytes())
    for sm in versions:
        cache.register(sm)
    st = cache.stats()
    assert st["evictions"] == 0 and st["over_budget"] == 0
    assert st["resident_bytes"] <= st["budget_bytes"]
    assert sorted(cache.resident_models()) == \
        [f"lenet_i8_v{k}" for k in range(3)]
    # the density claim itself: three int8 trees fit where one f32 did
    assert 3 * versions[0].param_bytes() <= sm_f32.param_bytes()


def test_int8_spill_readmit_bit_identity(tmp_path_factory):
    """Evict→re-admit round-trips the quantized tree leaf-wise: int8
    codes, f32 scales, and batch_stats all come back bit-identical (the
    opaque-pytree contract in serve/quant.py)."""
    import jax

    reg = ModelRegistry()
    td = str(tmp_path_factory.mktemp("spill_workdir"))
    m1 = reg.load_checkpoint("lenet5", td, name="spill_a",
                             wire_dtype="uint8", infer_dtype="int8")
    m2 = reg.load_checkpoint("lenet5", td, name="spill_b",
                             wire_dtype="uint8", infer_dtype="int8")
    pristine = jax.tree_util.tree_map(
        np.array, jax.device_get(m1._variables))
    from deep_vision_tpu.serve.models import WeightCache

    # budget fits exactly one int8 version: registering m2 evicts m1
    cache = WeightCache(budget_bytes=m1.param_bytes())
    cache.register(m1)
    cache.register(m2)
    assert cache.resident_models() == ["spill_b"]
    # hot path re-admits m1 (evicting m2) via one device_put
    live = m1._live_variables()
    assert cache.resident_models() == ["spill_a"]
    assert cache.stats()["misses"] == 1
    flat_p = jax.tree_util.tree_leaves_with_path(pristine)
    flat_l = jax.tree_util.tree_leaves_with_path(
        jax.device_get(live))
    assert len(flat_p) == len(flat_l)
    for (pa, a), (pb, b) in zip(flat_p, flat_l):
        assert pa == pb
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # int8 leaves really are int8 through the round trip
    dtypes = {np.asarray(a).dtype for a in
              jax.tree_util.tree_leaves(jax.device_get(live))}
    assert np.dtype(np.int8) in dtypes
