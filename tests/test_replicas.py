"""Multi-device serving (serve/replicas.py) under 8 forced host devices
(conftest pins XLA_FLAGS=--xla_force_host_platform_device_count=8):
routing spreads work over every replica, results stay bit-identical to
the single-engine path, a replica killed mid-load loses zero admitted
requests, and the sharded mega-batch path matches the unsharded
reference.  CPU-only and deterministic — the 8 "devices" share one
host, so these tests verify CORRECTNESS of placement/routing/failover,
not speedup."""

from concurrent.futures import wait

import numpy as np
import pytest

from deep_vision_tpu.serve.admission import AdmissionController, Shed
from deep_vision_tpu.serve.engine import BatchingEngine, sharded_buckets
from deep_vision_tpu.serve.faults import Quarantined
from deep_vision_tpu.serve.registry import ModelRegistry
from deep_vision_tpu.serve.replicas import ReplicatedEngine, local_devices

pytestmark = [pytest.mark.serve, pytest.mark.replicas]


@pytest.fixture(scope="module")
def lenet_serving(tmp_path_factory):
    reg = ModelRegistry()
    # empty workdir fixture → deterministic PRNGKey(0) random init
    sm = reg.load_checkpoint(
        "lenet5", str(tmp_path_factory.mktemp("replica_workdir")))
    return reg, sm


def _images(n, shape=(32, 32, 1)):
    return [np.random.RandomState(i).randn(*shape).astype(np.float32)
            for i in range(n)]


def _serve_all(engine, images, timeout=120):
    futs = [engine.submit(x) for x in images]
    wait(futs, timeout)
    return [f.result(0) for f in futs]


def test_local_devices_validation(host_devices):
    assert len(local_devices()) == len(host_devices)
    assert local_devices(3) == host_devices[:3]
    with pytest.raises(ValueError, match="only"):
        local_devices(len(host_devices) + 1)
    with pytest.raises(ValueError, match="at least 1"):
        local_devices(0)


def test_sharded_buckets_ladder():
    # every bucket a multiple of the device count, topping at max_batch
    assert sharded_buckets(32, 8) == [8, 16, 32]
    assert sharded_buckets(32, 4) == [4, 8, 16, 32]
    assert sharded_buckets(8, 8) == [8]
    assert sharded_buckets(32, 1) == [1, 2, 4, 8, 16, 32]


def test_routing_spreads_across_replicas(lenet_serving, host_devices):
    """8 replicas, mixed sequential + concurrent workload: every replica
    executes at least one batch (the round-robin tie-break keeps an
    idle fleet from piling onto replica 0), and the full response set
    is served."""
    _, sm = lenet_serving
    imgs = _images(48)
    with ReplicatedEngine(sm, devices=host_devices, max_batch=4,
                          max_wait_ms=1.0) as eng:
        # sequential singles — each forms its own batch, ties rotate
        for x in imgs[:16]:
            r = eng.infer(x, timeout=60)
            assert isinstance(r, np.ndarray)
        # then a concurrent burst
        results = _serve_all(eng, imgs[16:])
        assert all(isinstance(r, np.ndarray) for r in results)
        st = eng.stats()
    assert len(st["replicas"]) == 8
    per_replica = [r["batches"] for r in st["replicas"]]
    assert all(n >= 1 for n in per_replica), per_replica
    assert st["served"] == len(imgs)
    assert sum(r["routed_batches"] for r in st["replicas"]) \
        == st["batches"]
    # each replica is pinned to its own device
    assert len({r["device"] for r in st["replicas"]}) == 8


def test_replicated_bit_identical_to_single(lenet_serving, host_devices):
    _, sm = lenet_serving
    imgs = _images(32)
    with BatchingEngine(sm, max_batch=8, max_wait_ms=2.0,
                        watchdog_interval_s=0) as eng:
        ref = _serve_all(eng, imgs)
    with ReplicatedEngine(sm, devices=host_devices[:4], max_batch=8,
                          max_wait_ms=2.0) as eng:
        got = _serve_all(eng, imgs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dead_replica_reroute_serves_all_inflight(lenet_serving,
                                                  host_devices):
    """Kill a replica mid-load: its in-flight cohorts are evacuated and
    bisect-retried on a healthy replica — zero admitted requests are
    lost, routing masks the corpse, healthz stays serveable."""
    _, sm = lenet_serving
    imgs = _images(96)
    eng = ReplicatedEngine(sm, devices=host_devices[:3], max_batch=4,
                          max_wait_ms=5.0, watchdog_interval_s=0.02)
    with eng:
        eng.warmup([4])
        futs = [eng.submit(x) for x in imgs]
        eng.replicas[0].health.force_dead("test kill")
        wait(futs, 120)
        results = [f.result(0) for f in futs]
        st = eng.stats()
        health = eng.health_report()
    lost = [r for r in results
            if not isinstance(r, np.ndarray)
            and not isinstance(r, Quarantined)]
    assert not lost, f"{len(lost)} admitted requests lost: {lost[:3]}"
    assert st["served"] == len(imgs)
    assert st["replicas"][0]["state"] == "dead"
    assert st["routing"]["free_replicas"] == 2
    assert st["admission"]["free_replicas"] == 2
    # one dead replica degrades the fleet but does NOT take it down
    assert health["state"] == "degraded"
    assert health["can_serve"] is True
    assert health["replicas"]["0"]["state"] == "dead"


def test_all_replicas_dead_cannot_serve(lenet_serving, host_devices):
    _, sm = lenet_serving
    with ReplicatedEngine(sm, devices=host_devices[:2], max_batch=4,
                          max_wait_ms=1.0,
                          watchdog_interval_s=0.02) as eng:
        assert eng.infer(_images(1)[0], timeout=60) is not None
        for rep in eng.replicas:
            rep.health.force_dead("test kill")
        health = eng.health_report()
        assert health["state"] == "dead"
        assert health["can_serve"] is False
        # a batch formed with nobody routable sheds, it doesn't hang
        r = eng.infer(_images(1)[0], timeout=60)
        assert isinstance(r, Shed), r


def test_sharded_megabatch_equals_unsharded(lenet_serving, mesh8):
    """--shard-batches: one padded mega-batch laid across the 8-device
    data axis produces the same answers as the default single-device
    engine (allclose — SPMD partitioning may reorder reductions)."""
    _, sm = lenet_serving
    imgs = _images(24)
    smesh = sm.for_mesh(mesh8)
    buckets = sharded_buckets(32, 8)
    with BatchingEngine(smesh, max_batch=32, buckets=buckets,
                        max_wait_ms=20.0, watchdog_interval_s=0) as eng:
        got = _serve_all(eng, imgs)
        st = eng.stats()
    with BatchingEngine(sm, max_batch=32, max_wait_ms=20.0,
                        watchdog_interval_s=0) as eng:
        ref = _serve_all(eng, imgs)
    for a, b in zip(ref, got):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)
    assert st["buckets"] == buckets
    assert "sharded over 8 devices" in smesh.placement_desc()


def test_sharded_bucket_must_divide_mesh(lenet_serving, mesh8):
    _, sm = lenet_serving
    smesh = sm.for_mesh(mesh8)
    with pytest.raises(ValueError, match="not divisible"):
        smesh.compile_bucket(4)  # 4 % 8 != 0


def test_admission_divides_by_free_replicas():
    """The shed estimate's exec term divides by routable replicas (the
    drain window does not), and stats expose the divisor + per-bucket
    EWMAs (satellite: surfaced through /v1/stats)."""
    adm = AdmissionController(max_wait_ms=0.0)
    adm.observe_exec(0.100, bucket=8)
    base = adm.estimated_service_s(bucket=8, inflight=3)
    assert base == pytest.approx(0.4)
    adm.set_free_replicas(4)
    assert adm.estimated_service_s(bucket=8, inflight=3) \
        == pytest.approx(base / 4)
    # a callable divisor follows live replica state, floored at 1
    n = {"free": 0}
    adm.set_free_replicas(lambda: n["free"])
    assert adm.estimated_service_s(bucket=8, inflight=3) \
        == pytest.approx(base)
    n["free"] = 2
    assert adm.estimated_service_s(bucket=8, inflight=3) \
        == pytest.approx(base / 2)
    st = adm.stats()
    assert st["free_replicas"] == 2
    assert st["exec_ewma_ms_by_bucket"] == {"8": 100.0}


def test_replica_views_pin_devices(lenet_serving, host_devices):
    """for_device views: variables live on the view's device, outputs
    land there, and the base model's default placement is untouched."""
    import jax

    _, sm = lenet_serving
    view = sm.for_device(host_devices[3])
    leaf = jax.tree_util.tree_leaves(view._variables)[0]
    assert leaf.devices() == {host_devices[3]}
    fn = view.compile_bucket(2)
    out = fn(np.zeros((2, 32, 32, 1), np.float32))
    assert out.devices() == {host_devices[3]}
    assert sm.placement is None  # base model untouched
    assert str(host_devices[3]) in view.placement_desc()
