"""The routed-experts driver end to end on a fixture configuration (the
LFM2-24B-A2B equations at hidden 64, three layers conv / attention / conv,
the second and third routed over 16 experts of which this "chip" holds 4-7,
rows of 64 tokens, float32, through ``Trainer.train_epoch`` on the CPU): the
result line, the counters, the control and every planted fault."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import compare, moe_scopes, run

FIXTURE = os.path.join(run.HERE, "tests", "fixture_moe")
CELL = "lfm2-tiny-train"
FAKE_TRACE = {"busy_s": 0.5, "window_s": 1.0, "chips": 1, "step_executions": 10.0,
              "device_ops": [["fusion.1", 0.3]], "idle_gaps": [["host:wait", 0.2]]}


@pytest.fixture(scope="module")
def bench():
    return run.load_json(os.path.join(FIXTURE, "BENCHMARK.json"))


@pytest.fixture(autouse=True)
def no_staging_reuse(monkeypatch):
    """As ``test_driver_lm_fixture.py``: on the CPU backend a staging buffer
    can be handed back while an asynchronous step still reads it."""
    from deep_vision_tpu.data.pipeline import HostStagingPool

    monkeypatch.setattr(HostStagingPool, "release", lambda self, buf: None)


@pytest.fixture
def device():
    return {"platform": "cpu", "kind": "fixture", "count": 1,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
            "devices": jax.devices()[:1]}


def cell(bench, device, seed=2**31 + 77, trace=False):
    return run.run_cell(bench, CELL, seed, 0.5, trace, device,
                        read_peak=lambda devices: 123456)


def test_the_program_comes_out_correct(bench, device):
    result, rows = cell(bench, device)
    assert result["correct"] is True, rows
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"images_per_s", "setup_s"}
    assert {"grad_diff_router", "grad_diff_experts", "grad_diff_output",
            "moe_dropped", "moe_bias_gap"} <= set(result["compared"])
    assert result["compared"]["moe_dropped"]["value"] == 0.0
    assert result["compared"]["moe_bias_gap"]["value"] == 0.0


def test_a_capacity_that_drops_rows_comes_out_not_correct(bench, device,
                                                          monkeypatch):
    """The program itself with every held expert's rows past the mean load
    left out of the grouped products: the program's own count says so (it
    reads the products' result), and the experts' gradients differ."""
    import jax.numpy as jnp
    from deep_vision_tpu.ops import moe

    whole = moe._grouped_product

    def capped(x, w, loads):
        ends = jnp.cumsum(loads)
        row = jnp.arange(x.shape[0])
        group = jnp.minimum(jnp.searchsorted(ends, row, side="right"),
                            loads.size - 1)
        kept = row - (ends - loads)[group] < 8
        return jnp.where(kept[:, None], whole(x, w, loads), 0)

    monkeypatch.setattr(moe, "_grouped_product", capped)
    result, rows = cell(bench, device)
    assert result["correct"] is False, rows
    assert result["compared"]["moe_dropped"]["value"] > 0
    assert result["compared"]["grad_diff_experts"]["value"] > 0.001


def test_a_program_that_does_not_balance_comes_out_not_correct(bench, device,
                                                               monkeypatch):
    from deep_vision_tpu.ops import moe

    monkeypatch.setattr(moe, "balanced_bias", lambda bias, indices, rate: bias)
    result, rows = cell(bench, device)
    assert result["correct"] is False, rows
    # every bias but one whose load met the mean of 32 to the row
    assert result["compared"]["moe_bias_gap"]["value"] > 0.9


def test_traced_run_reports_the_counters_and_no_scope_without_a_chip(
        bench, device, monkeypatch):
    from benchmark import trace

    monkeypatch.setattr(trace, "summarize", lambda d, m=None: dict(FAKE_TRACE))
    result, _ = cell(bench, device, trace=True)
    metrics = result["metrics"]
    assert metrics["tokens_per_step"]["value"] == 2 * 64
    # two expert layers, 128 tokens, four experts each of which a quarter held
    assert 0 < metrics["moe_assignments_per_step"]["value"] <= 2 * 128 * 4
    mean = 128 * 4 / 16
    assert mean / 2 <= metrics["moe_max_load"]["value"] <= 128
    for name in ("moe_ms", "moe_experts_ms", "moe_route_ms", "conv_op_ms",
                 "gqa_op_ms", "dense_ffn_ms", "moe_experts_roofline_pct",
                 "ssm_mixer_ms", "mlp_ms"):
        assert name not in metrics     # the CPU's trace has no chip's plane


def _three_steps(bench, seed, **fault):
    parts = run.resolve(bench, CELL)
    config, traffic = parts["config"], parts["traffic"]
    gen = run.load_module(os.path.join(run.HERE, "generators", "packed_docs.py"), "g")
    pool = gen.make_pool(config, traffic, seed)[:3]
    ref = run.load_module(os.path.join(parts["config_dir"], "lfm2-tiny.py"),
                          "ref").Reference(config)
    from benchmark import weights_moe
    from deep_vision_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
    from flax import traverse_util

    arch = dict(config, num_experts=config["published"]["num_experts"])
    model = Lfm2Moe(Lfm2MoeConfig.from_dict(arch, config["expert_first"],
                                            config["num_experts"]))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), pool[0]["tokens"],
                           pool[0]["segment_ids"]))
    shapes = {**traverse_util.flatten_dict(shapes["params"], sep="/"),
              **traverse_util.flatten_dict(shapes["batch_stats"], sep="/")}
    params0 = {leaf: np.asarray(weights_moe.make_leaf(leaf, i, shapes[leaf].shape, seed))
               for i, leaf in enumerate(sorted(shapes))}
    driver = parts["driver"]
    numbers, _ = driver.numbers_of(ref.run_steps(params0, pool, **fault),
                                   ref.run_steps(params0, pool), config)
    limits = {k: v for k, v in config["limits"].items() if k in numbers}
    return compare.judge(numbers, limits)


@pytest.mark.parametrize("seed, fault", [
    (21, {"operands": "fp8"}),
    (22, {"operands": "fp8"}),
    (23, {"weigh": "biased"}),
    (24, {"normalise": "held"}),
    (25, {"capacity": 1.25}),
    (26, {"reset": False}),
    (27, {"rows": "half"}),
    (29, {"balance": False}),
], ids=lambda v: v if isinstance(v, int) else "-".join(map(str, v.values())))
def test_the_control_and_every_planted_fault_come_out_not_correct(bench, seed, fault):
    ok, rows = _three_steps(bench, seed, **fault)
    assert not ok, rows


def test_the_reference_against_itself_comes_out_correct(bench):
    ok, rows = _three_steps(bench, 28)
    assert ok, rows


def test_scope_times_go_by_whole_components():
    pre = "jit(train_step)/jvp(forward)/Lfm2Moe/"
    ops = [(pre + "layer_1/moe/feed_forward/moe_route/top_k", 0, 2e6),
           (pre + "layer_1/moe/feed_forward/moe_experts/convert_element_type", 0, 2e6),
           (pre + "layer_1/moe/feed_forward/moe_experts/gmm/pallas_call", 0, 4e6),
           ("jit(train_step)/transpose(jvp(forward))/Lfm2Moe/checkpoint/rematted_computation/layer_1/moe/ffn_norm/mul", 0, 2e6),
           (pre + "layer_1/gqa_op/operator/q_proj/dot_general", 0, 4e6),
           (pre + "layer_0/conv_op/operator/in_proj/dot_general", 0, 8e6),
           (pre + "layer_0/dense_ffn/feed_forward/w1/dot_general;x/y", 0, 10e6),
           (pre + "embed/gather", 0, 1e6),
           (pre + "lm_head/dot_general", 0, 3e6),
           ("jit(train_step)/jvp(loss)/reduce_sum", 0, 1e6),
           ("jit(train_step)/optimizer/mul", 0, 5e6),
           ("", 0, 2e6)]
    got = moe_scopes.scope_ms(ops, 2.0)
    assert got == {"embed": 0.5, "conv_op": 4.0, "gqa_op": 2.0, "dense_ffn": 5.0,
                   "moe": 5.0, "moe_route": 1.0, "moe_experts": 3.0,
                   "lm_head": 1.5, "loss": 0.5, "optimizer": 2.5,
                   "unscoped": 1.0, "all": 22.0}
    granite = "jit(train_step)/jvp(forward)/GraniteHybrid/layer_0/mamba/mixer/ssd/mul"
    assert moe_scopes.scope_ms([(granite, 0, 1e6)], 2.0) is None
    from benchmark import lm_scopes

    assert lm_scopes.scope_ms(ops, 2.0) is None    # the accepted reader stays silent


def test_roofline_reads_the_assignments_logged_inside_the_traced_steps(tmp_path):
    rows = [("train_moe_assignments", 3, 30000.0), ("train_loss", 13, 5.0),
            ("train_moe_assignments", 13, 40000.0),
            ("train_moe_assignments", 23, 50000.0),
            ("train_moe_assignments", 33, 70000.0)]
    with open(tmp_path / "metrics.jsonl", "w") as f:
        for name, step, value in rows:
            f.write(json.dumps({"name": name, "step": step, "value": value}) + "\n")
    run_ = {"window": {"workdir": str(tmp_path)},
            "traffic": {"trace_steps": [10, 25], "check_steps": 3}}
    assert moe_scopes.traced_counter(run_, "train_moe_assignments") == 45000.0
    assert moe_scopes.traced_counter(run_, "train_moe_max_load") is None
    run_["traffic"]["trace_steps"] = [41, 45]     # no logged step inside
    assert moe_scopes.traced_counter(run_, "train_moe_assignments") == 70000.0


def test_roofline_takes_the_larger_of_operations_and_bytes(bench):
    from benchmark import flops_moe

    config = run.load_json(os.path.join(run.HERE, "configs", "LFM2-24B-A2B.json"))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    even = 4 * 8192 * 4 * 16 / 64
    assert flops_moe.experts_train_flops(config, even) == 6 * even * 3 * 2048 * 1536
    weights = 16 * 3 * 2048 * 1536 * 4
    assert flops_moe.experts_train_bytes(config, even) == (
        3 * weights * 4 + 4 * even * 2048 * 2)
    least, bound = flops_moe.experts_roofline_seconds(config, even, peaks)
    assert bound == "bytes" and 9.0e-3 < least < 10.0e-3
    least, bound = flops_moe.experts_roofline_seconds(config, 2 * even, peaks)
    assert bound == "flops"
