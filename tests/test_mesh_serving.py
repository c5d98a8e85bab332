"""2-D ``data × model`` mesh serving under forced host devices (conftest
pins 8 virtual CPU devices): the regex partition-rule engine
(parallel/partition.py) — first-match-wins, strict exactly-one-match,
the first-divisible-axis fallback and its indivisible-trailing-dim fix —
then the serving path end to end: every mesh cell (2×2, 4×1, 1×4) must
produce outputs allclose to the single-device engine with bit-identical
top-1, bucket divisibility errors must name both mesh axes, per-chip
``param_bytes()`` must price one chip's shard (strictly below the
replicated footprint when the model axis is real), and the weight cache
must spill/re-admit a model-sharded view bit-identically with zero
recompiles.  Correctness only — the 8 "devices" share one host."""

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deep_vision_tpu.parallel.mesh import make_mesh
from deep_vision_tpu.parallel.partition import (
    first_divisible_spec,
    leaf_paths,
    match_partition_rules,
    parse_partition_rules,
    RULE_TABLES,
)
from deep_vision_tpu.serve.engine import BatchingEngine, sharded_buckets
from deep_vision_tpu.serve.models import WeightCache
from deep_vision_tpu.serve.registry import ModelRegistry

pytestmark = [pytest.mark.serve, pytest.mark.mesh]

# disjoint (strict-compatible) table for the LeNet fixture: the wide
# leaves shard over ``model``, everything else replicates explicitly
LENET_STRICT_RULES = [
    (r"Conv_2/kernel$", P(None, None, None, "model")),
    (r"Dense_0/kernel$", P(None, "model")),
    (r"(bias|Conv_[01]/kernel|Dense_1/kernel)$", P()),
]


@pytest.fixture(scope="module")
def lenet_serving(tmp_path_factory):
    reg = ModelRegistry()
    # empty workdir fixture → deterministic PRNGKey(0) random init
    sm = reg.load_checkpoint(
        "lenet5", str(tmp_path_factory.mktemp("mesh_workdir")))
    return reg, sm


def _images(n, shape=(32, 32, 1)):
    return [np.random.RandomState(i).randn(*shape).astype(np.float32)
            for i in range(n)]


def _mesh(host_devices, d, m):
    return make_mesh({"data": d, "model": m},
                     devices=host_devices[:d * m])


# -- the rule engine -------------------------------------------------------


def test_match_rules_first_wins_and_unmatched_replicates():
    params = {"params": {"head": {"kernel": np.zeros((8, 4)),
                                  "bias": np.zeros((4,))},
                         "step": np.zeros(())}}
    specs = match_partition_rules(
        [(r"head/kernel$", P(None, "model")),
         (r"head/.*", P("model"))], params)
    assert specs["params"]["head"]["kernel"] == P(None, "model")  # first
    assert specs["params"]["head"]["bias"] == P("model")
    assert specs["params"]["step"] == P()  # scalar: always replicated


def test_strict_rejects_unmatched_and_overlap():
    params = {"head": {"kernel": np.zeros((8, 4)),
                       "bias": np.zeros((4,))}}
    with pytest.raises(ValueError, match="matches no rule"):
        match_partition_rules([(r"kernel$", P(None, "model"))],
                              params, strict=True)
    with pytest.raises(ValueError, match="matches 2 rules"):
        match_partition_rules([(r"kernel$", P(None, "model")),
                               (r".*", P())], params, strict=True)
    # a disjoint table passes
    specs = match_partition_rules([(r"kernel$", P(None, "model")),
                                   (r"bias$", P())], params, strict=True)
    assert specs["head"]["kernel"] == P(None, "model")


def test_builtin_tables_are_first_match_non_strict():
    params = {"params": {"head": {"kernel": np.zeros((128, 1000))}}}
    specs = match_partition_rules(RULE_TABLES["classifier"], params)
    assert specs["params"]["head"]["kernel"] == P(None, "model")
    # the catch-all overlaps every specific rule, so strict (exactly
    # one match) rejects the built-in tables by construction
    with pytest.raises(ValueError, match="matches 2 rules"):
        match_partition_rules(RULE_TABLES["classifier"], params,
                              strict=True)


def test_first_divisible_skips_indivisible_trailing_dim():
    """The silent-replication fix: a leaf whose TRAILING dim is wide
    but indivisible used to replicate wholesale; now an earlier
    divisible dim is sharded instead."""
    # 1002 % 4 != 0 → the old sharder replicated; dim 0 (2048) shards
    assert first_divisible_spec((2048, 1002), 4, min_shard_dim=512) \
        == P("model", None)
    # trailing dim divisible → it keeps priority
    assert first_divisible_spec((2048, 1024), 4, min_shard_dim=512) \
        == P(None, "model")
    # nothing qualifies → replicate
    assert first_divisible_spec((100, 100), 4, min_shard_dim=512) == P()
    assert first_divisible_spec((2048, 1024), 1) == P()  # no model axis


def test_parse_partition_rules_inline_and_table():
    assert parse_partition_rules("classifier") \
        == RULE_TABLES["classifier"]
    rules = parse_partition_rules("head/kernel=-,model;.*=")
    assert rules == [("head/kernel", P(None, "model")), (".*", P())]
    with pytest.raises(ValueError, match="regex=axes"):
        parse_partition_rules("no-equals-sign-here")


def test_leaf_paths_join_with_slash(lenet_serving):
    _, sm = lenet_serving
    names = [n for n, _ in leaf_paths(sm._variables)]
    assert "params/Conv_0/kernel" in names
    assert "params/Dense_1/bias" in names


# -- the serving path ------------------------------------------------------


@pytest.mark.parametrize("d,m", [(2, 2), (4, 1), (1, 4)],
                         ids=["2x2", "4x1", "1x4"])
def test_mesh_cells_match_single_device(lenet_serving, host_devices,
                                        d, m):
    """Every mesh cell serves outputs allclose to the single-device
    engine, with bit-identical top-1 — GSPMD's collectives are a layout
    detail, never a numerics change the client can see."""
    _, sm = lenet_serving
    imgs = _images(8)
    with BatchingEngine(sm, max_batch=4, max_wait_ms=1.0) as ref_eng:
        ref = [np.asarray(ref_eng.infer(x, timeout=60)) for x in imgs]
    view = sm.for_mesh(_mesh(host_devices, d, m), min_shard_dim=64)
    with BatchingEngine(view, max_batch=4, max_wait_ms=1.0,
                        buckets=sharded_buckets(4, d)) as eng:
        got = [np.asarray(eng.infer(x, timeout=60)) for x in imgs]
        st = eng.stats()
    assert st["mesh_shape"] == {"data": d, "model": m}
    for r, g in zip(ref, got):
        np.testing.assert_allclose(r, g, rtol=1e-5, atol=1e-5)
        assert int(np.argmax(r)) == int(np.argmax(g))  # top-1 identical


def test_strict_rules_through_for_mesh(lenet_serving, host_devices):
    _, sm = lenet_serving
    mesh = _mesh(host_devices, 2, 2)
    # a disjoint table passes strict and actually shards
    view = sm.for_mesh(mesh, partition_rules=LENET_STRICT_RULES,
                       strict=True, min_shard_dim=64)
    assert view.param_bytes() < view.param_global_bytes()
    # a table that misses leaves fails loudly at load
    with pytest.raises(ValueError, match="matches no rule"):
        sm.for_mesh(mesh, partition_rules=[
            (r"Dense_0/kernel$", P(None, "model"))], strict=True)


def test_divisibility_error_names_both_axes(lenet_serving,
                                            host_devices):
    _, sm = lenet_serving
    view = sm.for_mesh(_mesh(host_devices, 2, 2), min_shard_dim=64)
    with pytest.raises(ValueError) as e:
        view.compile_bucket(3)
    msg = str(e.value)
    assert "2×2 data×model mesh" in msg
    assert "nearest usable bucket is 4" in msg
    assert "multiples of 2" in msg


def test_per_chip_bytes_below_replicated_on_1x4(lenet_serving,
                                                host_devices):
    """The HBM contract: a real model axis must price each chip at its
    addressable shard, strictly below the replicated footprint, while
    the logical size is unchanged."""
    _, sm = lenet_serving
    replicated = sm.param_bytes()
    view = sm.for_mesh(_mesh(host_devices, 1, 4), min_shard_dim=64)
    assert view.mesh_shape() == {"data": 1, "model": 4}
    assert view.param_bytes() < replicated
    assert view.param_global_bytes() == replicated
    # a pure data mesh replicates params: per-chip == global, as before
    flat = sm.for_mesh(_mesh(host_devices, 4, 1), min_shard_dim=64)
    assert flat.param_bytes() == replicated


def test_cache_spill_readmit_sharded_bit_identical(lenet_serving,
                                                   host_devices):
    """Evict→spill→re-admit of a model-sharded view: the spill gathers
    shards into full host values, re-admit lands them back under the
    view's sharding pytree — outputs bit-identical, zero recompiles,
    and the re-admitted leaves still price per-chip."""
    reg, sm = lenet_serving
    view = sm.for_mesh(_mesh(host_devices, 2, 2), min_shard_dim=64)
    # budget holds exactly one model: registering the view evicts sm
    cache = WeightCache(budget_bytes=sm.param_bytes() + 1)
    cache.register(sm)
    cache.register(view)
    img = _images(1)[0]
    with BatchingEngine(view, max_batch=4, max_wait_ms=1.0,
                        buckets=sharded_buckets(4, 2)) as eng:
        first = np.asarray(eng.infer(img, timeout=60))
        compiles = eng.compiles
        # touching sm admits it, evicting the view (the LRU resident);
        # the spill device_gets every sharded leaf to its full value
        assert cache.variables_for(sm) is not None
        assert not cache._entries[id(view)]["resident"]
        # next batch re-admits through _live_variables: device_put
        # against the sharding pytree, no compile
        again = np.asarray(eng.infer(img, timeout=60))
        assert np.array_equal(first, again)  # bit-identical round trip
        assert eng.compiles == compiles
    assert view.param_bytes() < view.param_global_bytes()
    st = cache.stats()
    assert st["evictions"] >= 1 and st["spilled_bytes_total"] > 0


# -- 2-process pod ---------------------------------------------------------


@pytest.mark.slow
def test_mesh_serving_two_processes(tmp_path):
    """A real 2-process pod (2 virtual devices each) serving over a 2×2
    data×model mesh: every addressable output shard matches a local
    single-device reference on each rank, per-chip bytes price below
    the replicated footprint, and both ranks report identical RESULTs
    (tests/dist_mesh_worker.py)."""
    import os
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(repo, "tests", "dist_mesh_worker.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, worker, coord, str(pid), "2", str(tmp_path)],
        env=env, cwd=repo,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for pid in (0, 1)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    if any("SKIPBACKEND" in out for out in outs):
        pytest.skip("jaxlib CPU backend lacks multiprocess SPMD "
                    "(needs a pod or a collectives-capable backend)")
    results = []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {pid} failed:\n{out}"
        line = [ln for ln in out.splitlines()
                if ln.startswith(f"RESULT pid={pid}")]
        assert line, out
        results.append(line[0].split(f"RESULT pid={pid} ")[1])
    # same weights, same batch → byte-identical payloads across ranks
    assert results[0] == results[1], results
