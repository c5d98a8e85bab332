"""The multi-token-prediction driver end to end on a fixture configuration
(the GLM-4.7-Flash equations at hidden 64, three layers of latent attention
of which the second and third are routed over 16 experts beside a shared
one, this "chip" holding experts 4-7, one prediction module, rows of 64
tokens, float32, through ``Trainer.train_epoch`` on the CPU): the result
line, the counters, the control, every planted fault and the scope reader."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import compare, lm_scopes, mla_scopes, moe_scopes, run

FIXTURE = os.path.join(run.HERE, "tests", "fixture_mtp")
CELL = "glm-tiny-train"
FAKE_TRACE = {"busy_s": 0.5, "window_s": 1.0, "chips": 1, "step_executions": 2.0,
              "device_ops": [["fusion.1", 0.3]], "idle_gaps": [["host:wait", 0.2]]}
NEW = {"mla_op_ms", "mla_core_ms", "mla_core_roofline_pct", "routed_ffn_ms",
       "shared_expert_ms", "mtp_ms", "attn_pairs_per_step", "mtp_targets_per_step"}

PRE = "jit(train_step)/jvp(forward)/Glm4MoeLite/"
BACK = "jit(train_step)/transpose(jvp(forward))/Glm4MoeLite/checkpoint/rematted_computation/"
OPS = [(PRE + "embed/gather", 0, 1e6),
       (PRE + "layer_0/mla_op/operator/q_a/dot_general", 0, 4e6),
       (PRE + "layer_0/mla_op/operator/mla_core/causal_gqa_fwd", 0, 6e6),
       (BACK + "layer_0/mla_op/operator/mla_core/transpose", 0, 2e6),
       (PRE + "layer_0/dense_block/feed_forward/w1/dot_general;x/y", 0, 10e6),
       (PRE + "layer_1/routed_ffn/feed_forward/moe_route/top_k", 0, 2e6),
       (PRE + "layer_1/routed_ffn/feed_forward/moe_experts/gmm/pallas_call", 0, 4e6),
       (PRE + "layer_1/routed_ffn/feed_forward/shared_expert/shared/w1/dot_general", 0, 2e6),
       (BACK + "layer_1/routed_ffn/ffn_norm/mul", 0, 2e6),
       (PRE + "lm_head/lm_head/dot_general", 0, 3e6),
       (PRE + "mtp/embed/gather", 0, 1e6),
       (PRE + "mtp/mtp/eh_proj/dot_general", 0, 3e6),
       (PRE + "mtp/mtp/layer/mla_op/operator/mla_core/causal_gqa_fwd", 0, 2e6),
       (PRE + "mtp/mtp/layer/routed_ffn/feed_forward/moe_experts/gmm/pallas_call", 0, 2e6),
       (PRE + "mtp/lm_head/lm_head/dot_general", 0, 1e6),
       ("jit(train_step)/jvp(loss)/reduce_sum", 0, 1e6),
       ("jit(train_step)/optimizer/mul", 0, 5e6),
       ("", 0, 1e6)]


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


def test_the_program_comes_out_correct(bench, device, capsys):
    result, rows = cell(bench, device)
    assert result["correct"] is True, rows
    # the fixture's traffic asks for four settling steps after the three checked
    assert "first 3 steps" in capsys.readouterr().out.split("[setup]")[1].split(
        "  4 more ")[0]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"images_per_s", "setup_s"}
    assert {"grad_diff_router", "grad_diff_experts", "grad_diff_output",
            "grad_diff_mtp", "grad_diff_latent", "mtp_targets_gap", "moe_dropped",
            "moe_bias_gap"} <= set(result["compared"])
    for name in ("moe_dropped", "moe_bias_gap", "mtp_targets_gap"):
        assert result["compared"][name]["value"] == 0.0


def test_a_program_that_weighs_the_module_wrongly_comes_out_not_correct(
        bench, device, monkeypatch):
    """The program itself with the second loss at another weight: the
    module's gradients scale with it and nothing else need move."""
    from deep_vision_tpu.tasks import language_modeling

    init = language_modeling.LanguageModelingTask.__init__
    monkeypatch.setattr(language_modeling.LanguageModelingTask, "__init__",
                        lambda self, weight=0.0: init(self, 2 * weight))
    result, rows = cell(bench, device)
    assert result["correct"] is False, rows
    assert result["compared"]["grad_diff_mtp"]["value"] > 0.5


def test_a_program_whose_second_target_crosses_documents_comes_out_not_correct(
        bench, device, monkeypatch):
    from deep_vision_tpu.tasks import language_modeling

    second = language_modeling.second_targets
    monkeypatch.setattr(language_modeling, "second_targets",
                        lambda targets, w: (second(targets, w)[0], w))
    result, rows = cell(bench, device)
    assert result["correct"] is False, rows
    assert result["compared"]["mtp_targets_gap"]["value"] > 0


def test_traced_run_reports_every_new_metric_and_the_accepted_readers_nothing(
        bench, device, monkeypatch, tmp_path):
    """A trace fixture: the harness's summary and the device's operations are
    planted (the CPU's trace has no chip's plane), the counters are the
    run's own."""
    from benchmark import trace

    monkeypatch.setattr(trace, "summarize", lambda d, m=None: dict(FAKE_TRACE))
    planted = tmp_path / "planted.xplane.pb"
    planted.write_bytes(b"")       # no plane: the spans' readers find nothing
    monkeypatch.setattr(trace, "find_xplane", lambda d: str(planted))
    monkeypatch.setattr(lm_scopes, "traced_ops", lambda path: (list(OPS), 1))
    result, _ = cell(bench, device, trace=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert NEW <= set(metrics)
    assert metrics["tokens_per_step"] == 2 * 64
    assert metrics["mla_op_ms"] == 7.0 and metrics["mla_core_ms"] == 5.0
    assert metrics["routed_ffn_ms"] == 6.0 and metrics["shared_expert_ms"] == 1.0
    assert metrics["mtp_ms"] == 4.5
    assert 64 <= metrics["attn_pairs_per_step"] < 2 * 64 * 65 / 2
    assert 0 < metrics["mtp_targets_per_step"] < 2 * 64 - 4
    config = run.resolve(bench, CELL)["config"]
    flops = 6 * 2 * metrics["attn_pairs_per_step"] * 16 * 4 * 4
    moved = 8 * 128 * 4 * 16 * 4 * 4
    assert metrics["mla_core_roofline_pct"] == pytest.approx(
        100 * max(flops / 1e12, moved / 1e11) * 1e3 / 5.0)
    assert config["num_hidden_layers"] + config["num_nextn_predict_layers"] == 4
    for name in ("moe_ms", "moe_experts_ms", "moe_route_ms", "conv_op_ms",
                 "gqa_op_ms", "dense_ffn_ms", "moe_experts_roofline_pct",
                 "ssm_mixer_ms", "ssd_scan_ms", "attn_mixer_ms", "mlp_ms",
                 "ssd_roofline_pct"):
        assert name not in metrics


def test_scope_times_go_by_whole_components():
    got = mla_scopes.scope_ms(OPS, 2.0)
    assert got == {"mla_op": 7.0, "dense_block": 5.0, "routed_ffn": 6.0,
                   "lm_head": 2.0, "embed": 1.0, "mtp_rest": 1.5, "loss": 0.5,
                   "optimizer": 2.5, "unscoped": 0.5, "mla_core": 5.0,
                   "shared_expert": 1.0, "moe_route": 1.0, "moe_experts": 3.0,
                   "mtp": 4.5, "all": 26.0}
    assert sum(got[k] for k in mla_scopes.DISJOINT) == got["all"]
    # the accepted readers stay silent on this model's trace
    assert lm_scopes.scope_ms(OPS, 2.0) is None
    assert moe_scopes.scope_ms(OPS, 2.0) is None
    # and this one on theirs
    lfm2 = "jit(train_step)/jvp(forward)/Lfm2Moe/layer_1/moe/feed_forward/moe_route/top_k"
    granite = "jit(train_step)/jvp(forward)/GraniteHybrid/layer_0/mamba/mixer/ssd/mul"
    assert mla_scopes.scope_ms([(lfm2, 0, 1e6), (granite, 0, 1e6)], 2.0) is None


def test_the_readers_return_nothing_for_a_program_without_the_model(tmp_path):
    """What the parent of the PR that added them gives: no such scope, no
    such counter, another configuration."""
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"name": "input_pairs_per_step", "step": 1,
                            "value": 5.0}) + "\n")
    run_ = {"window": {"workdir": str(tmp_path), "trace_dir": None},
            "traffic": {"trace_steps": [10, 25], "check_steps": 3},
            "config": {"num_experts": 16}, "trace": dict(FAKE_TRACE)}
    for name in sorted(NEW):
        reader = run.load_module(os.path.join(run.HERE, "metrics", name + ".py"), name)
        assert reader.read(dict(run_)) is None, name


def _three_steps(bench, seed, **fault):
    parts = run.resolve(bench, CELL)
    config, traffic = parts["config"], parts["traffic"]
    gen = run.load_module(os.path.join(run.HERE, "generators", "packed_docs.py"), "g")
    pool = gen.make_pool(config, traffic, seed)[:3]
    ref = run.load_module(os.path.join(parts["config_dir"], "glm-tiny.py"),
                          "ref").Reference(config)
    from benchmark import weights_moe
    from deep_vision_tpu.models.glm4_moe_lite import Glm4MoeLite, Glm4MoeLiteConfig
    from flax import traverse_util

    arch = dict(config, n_routed_experts=config["published"]["n_routed_experts"])
    model = Glm4MoeLite(Glm4MoeLiteConfig.from_dict(
        arch, config["expert_first"], config["n_routed_experts"]))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), pool[0]["tokens"],
                           pool[0]["segment_ids"]))
    shapes = {**traverse_util.flatten_dict(shapes["params"], sep="/"),
              **traverse_util.flatten_dict(shapes["batch_stats"], sep="/")}
    params0 = {leaf: np.asarray(weights_moe.make_leaf(leaf, i, shapes[leaf].shape, seed))
               for i, leaf in enumerate(sorted(shapes))}
    driver = parts["driver"]
    right = ref.run_steps(params0, pool)
    wrong = ref.run_steps(params0, pool, **fault)
    numbers, _ = driver.numbers_of(wrong, right, config)
    limits = {k: v for k, v in config["limits"].items() if k in numbers}
    return compare.judge(numbers, limits)


@pytest.mark.parametrize("seed, fault, meant", [
    (21, {"operands": "fp8"}, "grad_diff_output"),
    (23, {"rotate": "all"}, "grad_diff_latent"),
    (24, {"rope_key": "per_head"}, "grad_diff_latent"),
    (25, {"latent_norm": False}, "grad_diff_latent"),
    (26, {"shared": False}, "grad_diff_output"),
    (27, {"scale": 1.0}, "grad_diff_experts"),
    (28, {"second": "across"}, "mtp_targets_gap"),
    (29, {"mtp_weight": 0.0}, "grad_diff_mtp"),
    (30, {"balance": False}, "moe_bias_gap"),
], ids=lambda v: v if isinstance(v, (int, str)) else "-".join(map(str, v.values())))
def test_the_control_and_every_planted_fault_fail_the_limit_meant_for_them(
        bench, seed, fault, meant):
    ok, rows = _three_steps(bench, seed, **fault)
    assert not ok, rows
    failed = {name for name, value, limit in rows if not value <= limit}
    assert meant in failed, rows


def test_the_reference_against_itself_comes_out_correct(bench):
    ok, rows = _three_steps(bench, 31)
    assert ok, rows
