"""The driver end to end on the fixture configuration (LeNet-5 through
``Trainer.train_epoch`` on the CPU): the result line's keys, the seed, the
control and each fault a training cell can have."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import compare, refnn, run, weights

FIXTURE = os.path.join(run.HERE, "tests", "fixture")
CELL = "lenet5-train-b64"
FAKE_TRACE = {"busy_s": 0.5, "window_s": 1.0, "chips": 1, "step_executions": 10.0,
              "device_ops": [["fusion.1", 0.3]], "idle_gaps": [["host:wait", 0.2]]}


@pytest.fixture(scope="module")
def bench():
    return run.load_json(os.path.join(FIXTURE, "BENCHMARK.json"))


@pytest.fixture
def device():
    return {"platform": "cpu", "kind": "fixture", "count": 1,
            "peaks": {"bf16_flops_per_s": 1e12}, "devices": jax.devices()[:1]}


def cell(bench, device, seed=5, trace=False, seconds=0.5):
    return run.run_cell(bench, CELL, seed, seconds, trace, device,
                        read_peak=lambda devices: 123456)


def test_result_line_has_exactly_the_contracts_keys(bench, device):
    result, rows = cell(bench, device, seed=2**31 + 12345)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "compared"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"images_per_s", "setup_s"}
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    json.loads(json.dumps(result))  # plain numbers all the way down
    assert [r[0] for r in rows] == list(result["compared"])
    assert all(set(v) == {"value", "limit"} for v in result["compared"].values())


def test_traced_run_reports_the_layers(bench, device, monkeypatch):
    from benchmark import trace

    monkeypatch.setattr(trace, "summarize", lambda d, m=None: dict(FAKE_TRACE))
    result, _ = cell(bench, device, trace=True)
    assert list(result)[-2:] == ["breakdown", "compared"]
    assert set(result["metrics"]) == {
        "input_stall_pct", "h2d_bytes_per_step", "step_mfu_pct",
        "step_device_ms", "device_idle_pct", "hbm_peak_gib"}
    assert result["metrics"]["h2d_bytes_per_step"]["value"] == 64 * (32 * 32 + 4)
    assert result["metrics"]["device_idle_pct"]["value"] == 50.0
    assert result["metrics"]["step_device_ms"]["value"] == 50.0
    flops = run.resolve(bench, CELL)["config"]["train_flops_per_image"]
    # 10 traced executions x 64 images over the traced second, not the host's
    assert result["metrics"]["step_mfu_pct"]["value"] == pytest.approx(
        100.0 * 10 * 64 * flops / (1.0 * 1e12))
    assert result["device"]["busy_s"] == 0.5 and result["device"]["window_s"] == 1.0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_trace_without_a_device_is_refused(bench, device):
    with pytest.raises(SystemExit):
        cell(bench, device, trace=True)   # the CPU's trace has no chip's plane


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "resnet50-train-b256", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert '"metrics"' not in capsys.readouterr().out


def test_a_step_that_returns_its_state_unchanged(bench, device, monkeypatch):
    from deep_vision_tpu.core.state import TrainState

    monkeypatch.setattr(
        TrainState, "apply_gradients_if_finite",
        lambda self, loss, grads, **changes: self.replace(step=self.step + 1))
    result, _ = cell(bench, device)
    assert result["correct"] is False
    assert result["compared"]["delta_gap_median"]["value"] > 0.9


def test_half_of_the_batch_left_out(bench, device, monkeypatch):
    from deep_vision_tpu.tasks.classification import ClassificationTask

    whole = ClassificationTask.loss

    def half(self, outputs, batch):
        n = outputs.shape[0] // 2
        return whole(self, outputs[:n], {k: v[:n] for k, v in batch.items()})

    monkeypatch.setattr(ClassificationTask, "loss", half)
    result, _ = cell(bench, device)
    assert result["correct"] is False


def _three_steps(bench, seed, **kw):
    parts = run.resolve(bench, CELL)
    config, traffic = parts["config"], dict(parts["traffic"], pool_batches=3)
    gen = run.load_module(os.path.join(run.HERE, "generators", "image_pool.py"), "g")
    pool = gen.make_pool(config, traffic, seed)
    model = run.load_module(os.path.join(parts["config_dir"], "lenet5.py"),
                            "ref").Reference(config)
    shapes = {"Conv_0/kernel": (5, 5, 1, 6), "Conv_0/bias": (6,),
              "Conv_1/kernel": (5, 5, 6, 16), "Conv_1/bias": (16,),
              "Conv_2/kernel": (5, 5, 16, 120), "Conv_2/bias": (120,),
              "Dense_0/kernel": (120, 84), "Dense_0/bias": (84,),
              "Dense_1/kernel": (84, 10), "Dense_1/bias": (10,)}
    params0 = jax.device_get(weights.make(shapes, seed))
    key = weights.seed_key(seed)
    return config, [refnn.run_steps(model, config["optimizer"], params0, pool,
                                    key, **k) for k in ({}, kw)]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_control_in_fp8_comes_out_not_correct(bench, seed):
    config, (reference, control) = _three_steps(bench, seed, operands="fp8")
    numbers, _ = compare.training_numbers(control, reference)
    limits = {k: v for k, v in config["limits"].items() if k in numbers}
    ok, rows = compare.judge(numbers, limits)
    assert not ok, rows


def test_the_half_batch_fault_in_the_reference_comes_out_not_correct(bench):
    config, (reference, fault) = _three_steps(bench, 14, rows="half")
    numbers, _ = compare.training_numbers(fault, reference)
    assert numbers["grad_gap_median"] > config["limits"]["grad_gap_median"]


def test_same_seed_same_inputs_and_weights(bench):
    parts = run.resolve(bench, CELL)
    gen = run.load_module(os.path.join(run.HERE, "generators", "image_pool.py"), "g")
    a = gen.make_pool(parts["config"], parts["traffic"], 2**31 + 7)
    b = gen.make_pool(parts["config"], parts["traffic"], 2**31 + 7)
    c = gen.make_pool(parts["config"], parts["traffic"], 2**31 + 8)
    assert all(np.array_equal(x["image"], y["image"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["image"], c[0]["image"])
    assert [x["image"].shape for x in a] == [x["image"].shape for x in c]
    w1 = weights.make({"Conv_0/kernel": (3, 3, 4, 8), "bn/scale": (8,)}, 2**31 + 7)
    w2 = weights.make({"Conv_0/kernel": (3, 3, 4, 8), "bn/scale": (8,)}, 2**31 + 7)
    assert np.array_equal(w1["Conv_0/kernel"], w2["Conv_0/kernel"])
    assert float(np.asarray(w1["bn/scale"]).min()) == 1.0


def test_init_scales_reach_the_leaves_their_pattern_names():
    shapes = {"Block_0/BatchNorm_2/scale": (4,), "Block_0/BatchNorm_1/scale": (4,),
              "Block_0/BatchNorm_2/bias": (4,), "Block_0/Conv_0/kernel": (1, 1, 4, 4)}
    w = weights.make(shapes, 3, scales={"Block_*/BatchNorm_2/scale": 0.1})
    assert np.allclose(w["Block_0/BatchNorm_2/scale"], 0.1)
    assert np.allclose(w["Block_0/BatchNorm_1/scale"], 1.0)
    assert np.allclose(w["Block_0/BatchNorm_2/bias"], 0.0)
    plain = weights.make(shapes, 3)
    assert np.array_equal(w["Block_0/Conv_0/kernel"], plain["Block_0/Conv_0/kernel"])


def test_the_output_layers_number_reads_the_worst_listed_kernel():
    rng = np.random.default_rng(0)
    ref = {"loss": [1.0], "grad": {k: rng.standard_normal((3, 3)) for k in
                                   ("A/kernel", "B/kernel", "C/kernel")}}
    ref["delta"] = ref["grad"]
    off = {k: v.copy() for k, v in ref["grad"].items()}
    off["B/kernel"] = off["B/kernel"] * 1.5
    off["C/kernel"] = off["C/kernel"] * 3.0          # not an output layer
    program = {"loss": [1.0], "grad": off, "delta": off}
    numbers, where = compare.training_numbers(program, ref, ["A", "B"])
    assert where["grad_diff_output"] == "B/kernel"
    assert 0.2 < numbers["grad_diff_output"] <= 0.5
    assert "grad_diff_output" not in compare.training_numbers(program, ref)[0]
