"""The language-model driver end to end on a fixture configuration (the
granite-4.0-h-micro equations at hidden 64, three layers mamba / attention /
mamba, rows of 64 tokens, float32, through ``Trainer.train_epoch`` on the
CPU): the result line, the counters, the planted fault and the control."""

import os

import jax
import numpy as np
import pytest

from benchmark import compare, lm_scopes, run

FIXTURE = os.path.join(run.HERE, "tests", "fixture_lm")
CELL = "granite-tiny-train"
FAKE_TRACE = {"busy_s": 0.5, "window_s": 1.0, "chips": 1, "step_executions": 10.0,
              "device_ops": [["fusion.1", 0.3]], "idle_gaps": [["host:wait", 0.2]]}


@pytest.fixture(scope="module")
def bench():
    return run.load_json(os.path.join(FIXTURE, "BENCHMARK.json"))


@pytest.fixture(autouse=True)
def no_staging_reuse(monkeypatch):
    """On the CPU backend ``device_put`` aliases a staging buffer instead of
    copying it, and the prefetcher hands the buffer back to its pool as soon
    as the batch's Python object dies, which can be before the asynchronous
    step has read it; the next batch is then copied over one in use.  A
    rematerialising model reads its batch twice, and under a loaded CPU that
    gave a non-finite step once in five runs (PERF.md s7d).  A chip's
    transfers are copies.  Here the buffers simply are not reused."""
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
    assert {"grad_diff_scan", "grad_diff_output", "delta_gap_median"} <= set(
        result["compared"])


def test_a_state_carried_across_documents_comes_out_not_correct(
        bench, device, monkeypatch):
    from deep_vision_tpu.models import granite_hybrid

    whole = granite_hybrid.ssd_scan
    monkeypatch.setattr(
        granite_hybrid, "ssd_scan",
        lambda x, dt, a, b, c, seg, chunk: whole(x, dt, a, b, c, seg * 0, chunk))
    result, rows = cell(bench, device)
    assert result["correct"] is False, rows


def test_traced_run_reports_the_counters_and_no_scope_without_a_chip(
        bench, device, monkeypatch):
    from benchmark import trace

    monkeypatch.setattr(trace, "summarize", lambda d, m=None: dict(FAKE_TRACE))
    result, _ = cell(bench, device, trace=True)
    metrics = result["metrics"]
    assert metrics["tokens_per_step"]["value"] == 2 * 64
    assert 2 <= metrics["docs_per_step"]["value"] <= 2 * 64 / 4
    assert metrics["h2d_bytes_per_step"]["value"] == 4 * 2 * 64 * 4
    for name in ("ssm_mixer_ms", "ssd_scan_ms", "attn_mixer_ms", "mlp_ms",
                 "ssd_roofline_pct"):
        assert name not in metrics     # the CPU's trace has no chip's plane


def _three_steps(bench, seed, **fault):
    parts = run.resolve(bench, CELL)
    config, traffic = parts["config"], parts["traffic"]
    gen = run.load_module(os.path.join(run.HERE, "generators", "packed_docs.py"), "g")
    pool = gen.make_pool(config, traffic, seed)[:3]
    ref = run.load_module(os.path.join(parts["config_dir"], "granite-tiny.py"),
                          "ref").Reference(config)
    from benchmark import weights_lm
    from deep_vision_tpu.models.granite_hybrid import (
        GraniteHybrid,
        GraniteHybridConfig,
    )
    from flax import traverse_util

    model = GraniteHybrid(GraniteHybridConfig.from_dict(config))
    shapes = traverse_util.flatten_dict(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), pool[0]["tokens"],
                           pool[0]["segment_ids"]))["params"], sep="/")
    params0 = {leaf: np.asarray(weights_lm.make_leaf(leaf, i, shapes[leaf].shape, seed))
               for i, leaf in enumerate(sorted(shapes))}
    driver = parts["driver"]
    numbers, _ = driver.numbers_of(ref.run_steps(params0, pool, **fault),
                                   ref.run_steps(params0, pool), config)
    limits = {k: v for k, v in config["limits"].items() if k in numbers}
    return compare.judge(numbers, limits)


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_the_control_in_fp8_comes_out_not_correct(bench, seed):
    ok, rows = _three_steps(bench, seed, operands="fp8")
    assert not ok, rows


def test_the_reference_without_the_reset_comes_out_not_correct(bench):
    ok, rows = _three_steps(bench, 24, reset_state=False)
    assert not ok, rows


def test_a_loss_over_half_of_every_row_comes_out_not_correct(bench):
    ok, rows = _three_steps(bench, 25, rows="half")
    assert not ok, rows


def test_generator_packs_as_the_programs_loader_does(bench):
    from deep_vision_tpu.data.text import pack_documents

    parts = run.resolve(bench, CELL)
    gen = run.load_module(os.path.join(run.HERE, "generators", "packed_docs.py"), "g")
    rng = np.random.default_rng(5)
    docs = gen.documents(parts["traffic"], 128, 64 * 6, rng)
    theirs = pack_documents(docs, 64)
    ours = gen.pack(docs, 64, len(theirs["tokens"]))
    for key in theirs:
        np.testing.assert_array_equal(ours[key], theirs[key])
    a = gen.make_pool(parts["config"], parts["traffic"], 2**31 + 9)
    b = gen.make_pool(parts["config"], parts["traffic"], 2**31 + 9)
    assert all(np.array_equal(x["tokens"], y["tokens"]) for x, y in zip(a, b))
    assert a[0]["tokens"].max() < 128 and a[0]["tokens"].shape == (2, 64)


def test_scope_times_go_by_whole_components():
    ops = [("jit(train_step)/jvp(forward)/GraniteHybrid/layer_0/mamba/mixer/ssd/mul", 0, 4e6),
           ("jit(train_step)/transpose(jvp(forward))/GraniteHybrid/checkpoint/rematted_computation/layer_0/mamba/mixer/in_proj/dot_general", 0, 6e6),
           ("jit(train_step)/jvp(forward)/GraniteHybrid/layer_5/attention/mixer/q_proj/dot_general", 0, 2e6),
           ("jit(train_step)/jvp(forward)/GraniteHybrid/layer_5/mlp/ffn/in_proj/dot_general;x/y", 0, 8e6),
           ("jit(train_step)/jvp(forward)/GraniteHybrid/embed/gather", 0, 1e6),
           ("jit(train_step)/jvp(forward)/GraniteHybrid/lm_head/dot_general", 0, 3e6),
           ("jit(train_step)/jvp(loss)/reduce_sum", 0, 1e6),
           ("jit(train_step)/optimizer/mul", 0, 5e6),
           ("", 0, 2e6)]
    got = lm_scopes.scope_ms(ops, 2.0)
    assert got == {"embed": 0.5, "mamba": 5.0, "attention": 1.0, "mlp": 4.0,
                   "lm_head": 1.5, "loss": 0.5, "optimizer": 2.5, "ssd": 2.0,
                   "unscoped": 1.0, "all": 16.0}
    assert lm_scopes.scope_ms([("jit(train_step)/jvp(forward)/ResNet/Conv_0/conv", 0, 1e6)], 2.0) is None


def test_an_operation_without_a_scope_goes_by_its_reader_then_by_its_writer():
    ssd = "jit(train_step)/jvp(forward)/GraniteHybrid/layer_0/mamba/mixer/ssd/mul"
    norm = "jit(train_step)/jvp(forward)/GraniteHybrid/layer_0/mamba/mixer/norm/reduce_sum"
    scopes = {
        "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %param.1)": ssd,
        # an asynchronous copy of the scan's result, made for the norm that reads it
        "%copy-start.2 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %fusion.1)": "",
        "%copy-done.2 = f32[8]{0:S(1)} copy-done((f32[8]{0:S(1)}, f32[8]{0}, u32[]) %copy-start.2)": "",
        "%fusion.3 = f32[]{} fusion(f32[8]{0:S(1)} %copy-done.2)": norm,
        # nothing named reads this one: it goes by what wrote its operand
        "%copy.4 = f32[8]{0} copy(f32[8]{0} %fusion.1)": "",
        "%copy-done.40 = f32[8]{0} copy-done((f32[8]{0}, f32[8]{0}, u32[]) %copy-start.40)": "",
        "%copy-start.40 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %get-tuple-element.7)": "",
        # a parameter moved for an operation outside the trace: stays unscoped
        "%copy-start.5 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %param.9)": "",
        "%fusion.10 = f32[8]{0} fusion(f32[8]{0} %fusion.1, f32[8]{0} %fusion.3)": "",
    }
    got = lm_scopes.inherited_scopes(scopes)
    names = {text.split(" ")[0]: scope for text, scope in got.items()}
    assert names == {"%fusion.1": ssd, "%copy-start.2": norm, "%copy-done.2": norm,
                     "%fusion.3": norm, "%copy.4": ssd, "%copy-done.40": "",
                     "%copy-start.40": "", "%copy-start.5": "", "%fusion.10": ssd}
