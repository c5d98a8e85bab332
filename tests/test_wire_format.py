"""Uint8 serving wire + bf16 inference parity suite (CPU, tier-1 fast).

The wire-format contract (docs/SERVING.md "Wire format & inference
dtype"): a uint8-wire engine stages and H2D-transfers raw 0–255 pixels
— 4× fewer bytes per padded batch than float32, asserted here via the
``h2d_bytes`` stat — while the bucket program's traced prologue applies
the SAME normalization math the float32-wire client runs on the host,
so outputs stay allclose (classification top-1 bit-identical) on every
execution mode: single engine at pipeline depths 1/2, ReplicatedEngine
over forced host devices, and the --shard-batches mesh path.  bf16
compute keeps float32 outputs within loose tolerance with the same
top-1.

Uses LeNet at random init (restore's no-checkpoint fallback): wire
parity is about the dtype plumbing, not learned weights."""

import json
import urllib.error
import urllib.request
from concurrent.futures import wait

import numpy as np
import pytest

from deep_vision_tpu.serve.engine import (
    BatchingEngine,
    StagingPool,
    sharded_buckets,
)
from deep_vision_tpu.serve.registry import ModelRegistry

pytestmark = pytest.mark.serve

MNIST_MEAN, MNIST_STD = 0.1307, 0.3081


@pytest.fixture(scope="module")
def wire_serving(tmp_path_factory):
    """One restore, three wire/compute views of the same weights."""
    reg = ModelRegistry()
    td = str(tmp_path_factory.mktemp("wire_workdir"))
    sm_f32 = reg.load_checkpoint("lenet5", td, name="lenet_f32")
    sm_u8 = reg.load_checkpoint("lenet5", td, name="lenet_u8",
                                wire_dtype="uint8")
    sm_bf16 = reg.load_checkpoint("lenet5", td, name="lenet_bf16",
                                  wire_dtype="uint8",
                                  infer_dtype="bfloat16")
    return sm_f32, sm_u8, sm_bf16


def _raw_images(n, shape=(32, 32, 1)):
    return [np.random.RandomState(i).randint(0, 256, shape, dtype=np.uint8)
            for i in range(n)]


def _host_normalized(raw):
    """The float32-wire client's host path (data/mnist.py math)."""
    return [((r.astype(np.float32) / 255.0) - MNIST_MEAN) / MNIST_STD
            for r in raw]


def _serve_all(engine, images, timeout=120):
    futs = [engine.submit(x) for x in images]
    wait(futs, timeout)
    return [np.asarray(f.result(0)) for f in futs]


def _assert_parity(ref, got, atol=1e-5):
    for a, b in zip(ref, got):
        np.testing.assert_allclose(a, b, atol=atol, rtol=1e-5)
        assert int(np.argmax(a)) == int(np.argmax(b))


# -- device-side normalization math --------------------------------------


def test_serve_normalize_matches_host_math():
    """Each normalization family's device prologue is the host path's
    math exactly (same op order) — checked per family without paying a
    model compile."""
    import jax.numpy as jnp

    from deep_vision_tpu.data.transforms import normalize
    from deep_vision_tpu.ops.preprocess import serve_normalize

    rgb = np.random.RandomState(0).randint(0, 256, (2, 8, 8, 3),
                                           dtype=np.uint8)
    got = np.asarray(serve_normalize(jnp.asarray(rgb), "imagenet"))
    want = np.stack([normalize(r) for r in rgb])
    np.testing.assert_allclose(got, want, atol=1e-6)

    gray = np.random.RandomState(1).randint(0, 256, (2, 8, 8, 1),
                                            dtype=np.uint8)
    got = np.asarray(serve_normalize(jnp.asarray(gray), "mnist"))
    want = ((gray.astype(np.float32) / 255.0) - MNIST_MEAN) / MNIST_STD
    np.testing.assert_allclose(got, want, atol=1e-6)

    got = np.asarray(serve_normalize(jnp.asarray(rgb), "unit"))
    np.testing.assert_allclose(got, rgb.astype(np.float32) / 255.0,
                               atol=1e-6)

    with pytest.raises(ValueError, match="unknown serve preprocess"):
        serve_normalize(jnp.asarray(rgb), "nope")


def test_serve_preprocess_kind_derivation():
    from deep_vision_tpu.ops.preprocess import serve_preprocess_kind

    assert serve_preprocess_kind("classification", 3) == "imagenet"
    assert serve_preprocess_kind("classification", 1) == "mnist"
    assert serve_preprocess_kind("detection", 3) == "unit"
    assert serve_preprocess_kind("pose", 3) == "unit"


def test_registry_dtype_validation_and_describe(wire_serving):
    sm_f32, sm_u8, sm_bf16 = wire_serving
    assert sm_f32.describe()["wire_dtype"] == "float32"
    d = sm_bf16.describe()
    assert d["wire_dtype"] == "uint8" and d["infer_dtype"] == "bfloat16"
    assert sm_u8.preprocess_kind == "mnist"
    reg = ModelRegistry()
    with pytest.raises(ValueError, match="wire_dtype"):
        reg.load_checkpoint("lenet5", "/nonexistent", wire_dtype="int8")
    with pytest.raises(ValueError, match="infer_dtype"):
        reg.load_checkpoint("lenet5", "/nonexistent",
                            infer_dtype="float16")


# -- single-engine parity + the 4x H2D win --------------------------------


@pytest.mark.parametrize("depth", [1, 2])
def test_uint8_wire_parity_across_buckets(wire_serving, depth):
    """Uint8-wire outputs allclose to the float32 path (top-1
    identical) with cohorts landing in BOTH buckets, at the synchronous
    and the pipelined depth."""
    sm_f32, sm_u8, _ = wire_serving
    raw = _raw_images(12)
    kw = dict(buckets=[4, 8], max_wait_ms=150, pipeline_depth=depth,
              watchdog_interval_s=0)
    with BatchingEngine(sm_f32, **kw) as eng:
        ref = _serve_all(eng, _host_normalized(raw[:8]))
        ref += _serve_all(eng, _host_normalized(raw[8:]))  # 4-bucket
    with BatchingEngine(sm_u8, **kw) as eng:
        got = _serve_all(eng, raw[:8])
        got += _serve_all(eng, raw[8:])
        assert sorted(eng.stats()["compiled_buckets"]) == [4, 8]
    _assert_parity(ref, got)


def test_h2d_bytes_drop_4x(wire_serving):
    """Acceptance: staged H2D bytes per padded batch drop exactly 4× on
    the uint8 wire — the same request stream through both wires forms
    the same padded buckets, so total and per-bucket bytes divide by
    the dtype width."""
    sm_f32, sm_u8, _ = wire_serving
    raw = _raw_images(8)
    stats = {}
    for key, sm, imgs in (("f32", sm_f32, _host_normalized(raw)),
                          ("u8", sm_u8, raw)):
        with BatchingEngine(sm, buckets=[8], max_wait_ms=250,
                            watchdog_interval_s=0) as eng:
            _serve_all(eng, imgs)
            stats[key] = eng.stats()
    f32, u8 = stats["f32"]["pipeline"], stats["u8"]["pipeline"]
    assert u8["h2d_transfers"] == f32["h2d_transfers"] == 1
    assert u8["h2d_bytes"] == 8 * 32 * 32 * 1          # uint8 batch
    assert f32["h2d_bytes"] == 4 * u8["h2d_bytes"]     # the 4x win
    assert f32["h2d_bytes_by_bucket"][8] \
        == 4 * u8["h2d_bytes_by_bucket"][8]
    assert stats["u8"]["wire_dtype"] == "uint8"
    assert stats["f32"]["wire_dtype"] == "float32"


def test_staging_pool_dtype_reuse():
    """Pooled staging buffers allocate in the wire dtype and are reused
    across acquire/release cycles — no per-batch reallocation and no
    float32 fallback on the uint8 wire."""
    pool = StagingPool((32, 32, 1), np.uint8)
    a = pool.acquire(8)
    assert a.dtype == np.uint8 and a.shape == (8, 32, 32, 1)
    pool.release(8, a)
    b = pool.acquire(8)
    assert b is a  # the SAME buffer came back
    assert pool.allocated == 1 and pool.reused == 1
    assert pool.stats()["dtype"] == "uint8"
    # default stays float32 for wire-f32 engines
    assert StagingPool((32, 32, 1)).acquire(2).dtype == np.float32


def test_bf16_compute_tolerance(wire_serving, images_with_margin):
    """bf16 bucket programs return FLOAT32 outputs within loose
    tolerance of the f32 path, top-1 intact (docs/SERVING.md bf16
    caveats).  The inputs' top-2 f32 logits lie further apart than two
    logits may move inside the tolerance, so top-1 is the tolerance's
    to keep."""
    sm_f32, _, sm_bf16 = wire_serving
    f32_program = sm_f32.compile_bucket(8)
    raw = images_with_margin(
        lambda r: np.asarray(f32_program(np.stack(_host_normalized(r)))),
        8, margin=0.2)
    kw = dict(buckets=[8], max_wait_ms=250, watchdog_interval_s=0)
    with BatchingEngine(sm_f32, **kw) as eng:
        ref = _serve_all(eng, _host_normalized(raw))
    with BatchingEngine(sm_bf16, **kw) as eng:
        got = _serve_all(eng, raw)
        assert eng.stats()["infer_dtype"] == "bfloat16"
    for a, b in zip(ref, got):
        assert b.dtype == np.float32
        np.testing.assert_allclose(a, b, atol=5e-2, rtol=5e-2)
        assert 2 * (5e-2 + 5e-2 * np.abs(b).max()) < 0.2
        assert int(np.argmax(a)) == int(np.argmax(b))


# -- multi-device execution modes -----------------------------------------


def test_replicated_uint8_parity(wire_serving, host_devices):
    """ReplicatedEngine on forced host devices serves the uint8 wire
    allclose to the single-engine float32 reference (per-replica views
    inherit the wire dtype through for_device)."""
    from deep_vision_tpu.serve.replicas import ReplicatedEngine

    sm_f32, sm_u8, _ = wire_serving
    raw = _raw_images(16)
    with BatchingEngine(sm_f32, max_batch=8, max_wait_ms=150,
                        watchdog_interval_s=0) as eng:
        ref = _serve_all(eng, _host_normalized(raw))
    with ReplicatedEngine(sm_u8, devices=host_devices[:2], max_batch=8,
                          max_wait_ms=150) as eng:
        got = _serve_all(eng, raw)
        st = eng.stats()
    assert st["wire_dtype"] == "uint8"
    assert st["pipeline"]["h2d_transfers"] >= 1
    assert st["pipeline"]["h2d_bytes"] \
        == sum(st["pipeline"]["h2d_bytes_by_bucket"].values())
    _assert_parity(ref, got)


def test_shard_batches_uint8_parity(wire_serving, host_devices):
    """The --shard-batches mesh path on the uint8 wire: mega-batches
    laid across a 2-device data axis match the float32 reference."""
    from deep_vision_tpu.parallel.mesh import make_mesh

    sm_f32, sm_u8, _ = wire_serving
    raw = _raw_images(8)
    with BatchingEngine(sm_f32, max_batch=8, max_wait_ms=250,
                        watchdog_interval_s=0) as eng:
        ref = _serve_all(eng, _host_normalized(raw))
    mesh = make_mesh({"data": 2}, devices=host_devices[:2])
    buckets = sharded_buckets(8, 2)
    with BatchingEngine(sm_u8.for_mesh(mesh), buckets=buckets,
                        max_wait_ms=250, watchdog_interval_s=0) as eng:
        got = _serve_all(eng, raw)
        st = eng.stats()
    assert st["wire_dtype"] == "uint8"
    _assert_parity(ref, got)


def test_bf16_sharded_and_replicated_run(wire_serving, host_devices):
    """bf16 + uint8 wire works on both multi-device modes (the
    all-three-execution-modes acceptance for the infer-dtype knob)."""
    from deep_vision_tpu.parallel.mesh import make_mesh
    from deep_vision_tpu.serve.replicas import ReplicatedEngine

    _, _, sm_bf16 = wire_serving
    raw = _raw_images(4)
    with ReplicatedEngine(sm_bf16, devices=host_devices[:2],
                          max_batch=4, max_wait_ms=100) as eng:
        rows = _serve_all(eng, raw)
    assert all(r.dtype == np.float32 for r in rows)
    mesh = make_mesh({"data": 2}, devices=host_devices[:2])
    with BatchingEngine(sm_bf16.for_mesh(mesh),
                        buckets=sharded_buckets(4, 2), max_wait_ms=100,
                        watchdog_interval_s=0) as eng:
        rows2 = _serve_all(eng, raw)
    for a, b in zip(rows, rows2):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# -- HTTP wire contract ----------------------------------------------------


def _post(base, route, payload):
    req = urllib.request.Request(
        base + route, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, json.loads(r.read())


def test_http_uint8_wire_and_nonfinite_rejection(wire_serving):
    """Clients POST raw integer pixels on the uint8 wire; NaN/Inf
    payloads answer 400 on BOTH wires instead of reaching the batcher
    (float64 detour gone: lists decode straight to the wire dtype)."""
    from deep_vision_tpu.serve.http import ServeServer

    sm_f32, sm_u8, _ = wire_serving
    reg = ModelRegistry()
    reg.add(sm_u8)
    reg.add(sm_f32)
    engines = {
        sm.name: BatchingEngine(sm, max_batch=4, max_wait_ms=2.0,
                                watchdog_interval_s=0).start()
        for sm in (sm_u8, sm_f32)}
    srv = ServeServer(reg, engines, port=0).start_background()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        raw = _raw_images(1)[0]
        status, out = _post(base, "/v1/classify",
                            {"pixels": raw[..., 0].tolist(),
                             "model": sm_u8.name})
        assert status == 200 and len(out["top"]) == 5
        # same pixels through the f32 wire (host-normalized): top-1 match
        _, out_f = _post(
            base, "/v1/classify",
            {"pixels": _host_normalized([raw])[0][..., 0].tolist(),
             "model": sm_f32.name})
        assert out["top"][0]["class"] == out_f["top"][0]["class"]
        bad = np.zeros((32, 32), np.float64)
        bad[0, 0] = np.nan
        for model in (sm_u8.name, sm_f32.name):
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(base, "/v1/classify",
                      {"pixels": bad.tolist(), "model": model})
            assert exc.value.code == 400
        # ragged payloads are a 400, not a 500
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(base, "/v1/classify",
                  {"pixels": [[1, 2], [3]], "model": sm_u8.name})
        assert exc.value.code == 400
    finally:
        srv.shutdown()
        for eng in engines.values():
            eng.stop()
