"""The train loop's spans on a trace's clock and the step's phases, on
planted planes: which idle falls to the input, which to the loop, what the
clock check refuses, which phase a scope path lands in."""

import json

import pytest

from benchmark import spans

MS = 1_000_000  # ns
EPOCH = 1_790_000_000 * 10 ** 9  # the trace's profile_start_time, ns
STEP = "jit_train_step"
R = "jit(train_step)/"

# one whole step's operations: (scope, ms), 100 ms together
STEP_OPS = [
    (R + "prologue/train_ingest/pallas_call", 2),
    (R + "jvp(forward)/ResNet/BottleneckBlock_3/BatchNorm_0/reduce_sum", 10),
    (R + "jvp(forward)/ResNet/BottleneckBlock_3/Conv_0/conv_general_dilated", 20),
    (R + "jvp(loss)/best_iou/best_iou_max/pallas_call", 1),
    (R + "transpose(jvp(forward))/ResNet/BottleneckBlock_3/Conv_0/conv_general_dilated;"
     + R + "jvp(forward)/ResNet/BottleneckBlock_3/BatchNorm_0/mul", 45),
    (R + "transpose(jvp(loss))/mul", 1),
    (R + "transpose(jvp(forward))/ResNet/BottleneckBlock_3/BatchNorm_0/mul", 14),
    (R + "optimizer/add", 2),
    ("", 4),                                  # a copy without metadata
    (R + "jvp(jit(forward))/forward/mul", 1),  # a module called "forward"
]
# executions on the device, ms: the first is cut by the trace's start; 2 ms
# idle before the third, 4 ms before the fourth, 8 ms before the fifth
EXECUTIONS = [(0, 30), (30, 130), (132, 232), (236, 336), (344, 444)]


def chip():
    ops, modules = [], []
    for start, end in EXECUTIONS:
        modules.append((f"{STEP}(123)", start * MS, (end - start) * MS))
        if end - start < 100:
            ops.append((R + "transpose(jvp(forward))/x/mul", start * MS,
                        (end - start) * MS, 0, 0))
            continue
        t = start * MS
        for scope, ms in STEP_OPS:
            ops.append((scope, t, ms * MS, ms * 10 ** 9, ms * 10 ** 6))
            t += ms * MS
    return {"ops": ops, "modules": modules}


def consumer():
    """The loop, ms on the trace's clock: batches 0 and 1 go out before the
    trace starts inside iteration 2; fetch 2 returns 2 ms after execution 1
    ends (the whole first gap) and log 2, which drains the device, 3 ms after
    execution 2 ends; the loop then stalls on batch 4 until 0.5 ms before
    execution 4 starts."""
    marks = [("stall", 0, -1400, -1390), ("dispatch", 0, -1390, -1380),
             ("step", 0, -1380, -1300), ("stall", 1, -1300, -1200),
             ("dispatch", 1, -1200, -1190), ("step", 1, -1190, -1100),
             ("stall", 2, -1100, -1050), ("profile", 2, -1050, -2),
             ("dispatch", 2, -2, -1), ("fetch", 2, -1, 132),
             ("log", 2, 132, 235), ("step", 2, 235, 235.1),
             ("stall", 3, 235.1, 235.2), ("dispatch", 3, 235.2, 236),
             ("step", 3, 236, 236.1), ("stall", 4, 236.1, 343.5),
             ("dispatch", 4, 343.5, 343.9), ("step", 4, 343.9, 344),
             ("stall", 5, 344, 344.1), ("dispatch", 5, 344.1, 344.4),
             ("step", 5, 344.4, 344.5), ("stall", 6, 344.5, 500)]
    return [(stage, batch, EPOCH + round(a * MS), EPOCH + round(b * MS))
            for stage, batch, a, b in marks]


def producer(batches=6):
    out, t = [], EPOCH - 1500 * MS
    for batch in range(batches):
        for stage, ms in (("prep_wait", 1), ("assemble", 10), ("h2d", 30),
                          ("enqueue", 60)):
            out.append((stage, batch, t, t + ms * MS))
            t += ms * MS
    return out


def planted(start_ns=EPOCH, **changes):
    run = {"header": {"clock": [[5, EPOCH - 1050 * MS + 5],
                                [900 * MS, EPOCH - 150 * MS + 7_000]]},
           "producer": producer(), "consumer": consumer()}
    run.update(changes)
    return run, {"start_ns": start_ns, "chips": {"/device:TPU:0": chip()}}


def test_idle_under_stall_is_input_and_the_rest_is_loop():
    s, x = planted()
    got = spans.span_numbers(s, x, STEP)
    assert got["span_s"] == pytest.approx(0.444)
    # 8 ms gap: 7.5 under stall 4, 0.4 under dispatch 4, 0.1 under step 4;
    # 4 ms gap: 3 under log 2, 0.1 each under step 2 and stall 3, 0.8 under
    # dispatch 3; the 2 ms gap under fetch 2
    assert got["idle_s"] == pytest.approx(
        {"stall": 0.0076, "dispatch": 0.0012, "step": 0.0002, "fetch": 0.002,
         "log": 0.003})
    assert got["idle_input_pct"] == pytest.approx(100 * 7.6 / 444)
    assert got["idle_loop_pct"] == pytest.approx(100 * 6.4 / 444)
    # their sum is the idle share: busy is 430 of 444 ms
    assert got["idle_input_pct"] + got["idle_loop_pct"] == pytest.approx(
        100 * (1 - 430 / 444))
    assert got["input_batch_ms"] == pytest.approx(41.0)  # not the enqueue
    clock = got["clock"]
    assert clock["first_batch"] == 0
    assert clock["dispatch_lead_min_us"] == pytest.approx(500.0)  # step 4
    assert clock["fetch_lag_min_us"] == pytest.approx(2000.0)
    assert clock["drift_us"] == pytest.approx(7.0)


def test_two_chips_are_averaged():
    s, x = planted()
    late = chip()
    late["ops"] = [(sc, t + 1 * MS, *rest) for sc, t, *rest in late["ops"]][:-10]
    x["chips"]["/device:TPU:1"] = late  # ends 100 ms early, starts 1 ms late
    late["modules"] = [(n, t + 1 * MS, d) for n, t, d in late["modules"]][:-1]
    got = spans.span_numbers(s, x, STEP)
    busy = (430 + 330) / 2
    assert got["idle_input_pct"] + got["idle_loop_pct"] == pytest.approx(
        100 * (1 - busy / 444))


@pytest.mark.parametrize("why,change", [
    ("no Task Environment plane", {"start_ns": None}),
    ("spans 8 ms early: execution 3 starts before dispatch 4 could ask for it",
     {"start_ns": EPOCH + 8 * MS}),
    ("spans 8 ms late: execution 4 starts before its dispatch",
     {"start_ns": EPOCH - 8 * MS}),
    ("the spans end inside the traced span",
     {"consumer": consumer()[:-1]}),
    ("the spans start inside the traced span",
     {"consumer": [iv for iv in consumer() if iv[3] > EPOCH + 100 * MS]}),
    ("no fetch inside the trace to number the executions by",
     {"consumer": [("log" if iv[0] == "fetch" else iv[0],) + iv[1:]
                   for iv in consumer()]}),
])
def test_a_clock_that_cannot_be_placed_reads_nothing(why, change):
    s, x = planted(**change)
    assert spans.span_numbers(s, x, STEP) is None, why


def test_an_execution_before_its_dispatch_reads_nothing():
    s, x = planted()
    c = x["chips"]["/device:TPU:0"]
    c["modules"][4] = (c["modules"][4][0], 343 * MS, 100 * MS)
    assert spans.span_numbers(s, x, STEP) is None
    c["modules"][4] = (c["modules"][4][0], 343.5 * MS, 100 * MS)
    assert spans.span_numbers(s, x, STEP) is not None


def test_an_execution_under_way_as_a_log_ends_reads_nothing():
    """Off by one whole step the numbering moves with the clock and every
    execution still follows a dispatch; the logger's drain tells."""
    executions = [(a * MS, b * MS) for a, b in EXECUTIONS[1:]]
    late = spans.on_trace_clock(consumer(), EPOCH - 100 * MS)
    assert spans.check_clock(executions, late) is None
    unlogged = [iv for iv in late if iv[0] != "log"]
    assert spans.check_clock(executions, unlogged)["first_batch"] == 0
    on_time = spans.on_trace_clock(consumer(), EPOCH)
    assert spans.check_clock(executions, on_time)["first_batch"] == 1


def test_two_fetches_that_disagree_read_nothing():
    s, x = planted()
    s["consumer"] = [("fetch" if iv[:2] == ("step", 4) else iv[0],) + iv[1:]
                     for iv in s["consumer"]]  # says execution 3 ended by 344
    assert spans.span_numbers(s, x, STEP) is not None
    s["consumer"] = [("fetch" if iv[:2] == ("stall", 5) else iv[0],) + iv[1:]
                     for iv in s["consumer"]]  # says execution 4 ended by 344.1
    assert spans.span_numbers(s, x, STEP) is None


@pytest.mark.parametrize("scope,phase", [
    (R + "prologue/train_ingest/pallas_call", "prologue"),
    (R + "jvp(forward)/ResNet/Conv_0/conv_general_dilated", "forward"),
    (R + "transpose(jvp(forward))/ResNet/Conv_0/conv_general_dilated", "backward"),
    (R + "jvp(loss)/best_iou/best_iou_max/pallas_call", "loss"),
    (R + "transpose(jvp(loss))/mul", "backward"),
    (R + "optimizer/jit(_where)/select_n", "optimizer"),
    (R + "while/body/jvp(forward)/ResNet/Dense_0/dot_general", "forward"),
    (R + "jvp(jit(forward))/forward/mul", None),
    (R + "optimizer_state/add", None),
    ("", None),
])
def test_phase_of_a_scope_path(scope, phase):
    assert spans.phase_of(scope) == phase


def test_operations_land_in_their_phase_and_the_rest_unscoped():
    got = spans.device_phases({"/device:TPU:0": chip()}, executions=4.3)
    per = got["phase_ms"]
    assert per["prologue"] == pytest.approx(4 * 2 / 4.3)
    assert per["forward"] == pytest.approx(4 * 30 / 4.3)
    assert per["loss"] == pytest.approx(4 * 1 / 4.3)
    # a fusion that lists two scopes goes by the first; the cut execution's 30 ms
    assert per["backward"] == pytest.approx((4 * 60 + 30) / 4.3)
    assert per["optimizer"] == pytest.approx(4 * 2 / 4.3)
    assert per["unscoped"] == pytest.approx(4 * 5 / 4.3)
    assert sum(per.values()) == pytest.approx(430 / 4.3)
    assert got["unscoped_pct"] == pytest.approx(100 * 20 / 430)
    assert got["best_iou_ms"] == pytest.approx(4 * 1 / 4.3)
    # ms, and the compiler's GB and TFLOP as planted: 1 GFLOP and 1 MB a ms
    assert got["phase_gb_tflop"]["forward"] == pytest.approx(
        [4 * 30e-3 / 4.3, 4 * 30e-3 / 4.3])
    modules = {row[0]: row[1:] for row in got["modules_ms_gb_tflop"]}
    assert got["modules_ms_gb_tflop"][0][0] == "ResNet/BottleneckBlock_3/Conv_0"
    assert modules["ResNet/BottleneckBlock_3/Conv_0"][0] == pytest.approx(4 * 65 / 4.3)
    assert modules["ResNet/BottleneckBlock_3/BatchNorm_0"][0] == pytest.approx(4 * 24 / 4.3)
    assert modules["best_iou/best_iou_max"][0] == pytest.approx(4 * 1 / 4.3)
    kinds = {row[0]: row[1] for row in got["kinds_ms_gb_tflop"]}
    assert kinds["backward/Conv"] == pytest.approx(4 * 45 / 4.3)
    assert kinds["forward/BatchNorm"] == pytest.approx(4 * 10 / 4.3)
    assert kinds["backward/(backward)"] == pytest.approx(4 * 1 / 4.3)


def test_a_program_without_scopes_reads_nothing():
    """As the commit before the scopes: autodiff names the backward pass
    after the module (``transpose(jvp(ResNet))``) and nothing names the
    rest, so no split is read, not one with a forward pass of 0 ms."""
    bare = chip()
    bare["ops"] = [
        (R + "transpose(jvp(ResNet))/Conv_0/mul" if "transpose(" in scope
         else "", *rest) for scope, *rest in bare["ops"]]
    assert spans.device_phases({"/device:TPU:0": bare}, executions=4.3) is None
    assert spans.device_phases({"/device:TPU:0": chip()}, executions=None) is None


def test_readers_read_nothing_without_a_trace_or_spans(tmp_path):
    """An untraced run, a traced run of a program that writes no
    ``spans.jsonl`` and a trace directory without a trace: every reader
    returns None and none raises."""
    import os

    from benchmark import run as harness

    bench = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
    new = ["step_prologue_ms", "step_forward_ms", "step_loss_ms",
           "step_backward_ms", "step_optimizer_ms", "step_unscoped_pct",
           "best_iou_ms", "input_batch_ms", "idle_input_pct", "idle_loop_pct"]
    assert [m["name"] for m in bench["per_layer"]][-len(new):] == new
    runs = [
        {"window": {"workdir": str(tmp_path), "trace_dir": None}, "trace": None},
        {"window": {"workdir": str(tmp_path), "trace_dir": str(tmp_path / "p"),
                    "step_module": STEP},
         "trace": {"step_executions": 19.8}},
    ]
    for name in new:
        reader = harness.load_module(
            os.path.join(harness.HERE, "metrics", name + ".py"), "m_" + name)
        for run in runs:
            assert reader.read(run) is None


def test_reads_spans_jsonl_and_a_recorded_xplane(tmp_path):
    """The trainer's file round-trips, and a trace recorded here (no chip)
    gives the profile's start on the epoch's clock and no device plane."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmark import trace

    rows = [{"clock": [[1, 2], [3, 4]], "profile_steps": [2, 6], "depth": 2,
             "batches": 1, "h2d_bytes": 8},
            {"thread": "producer", "stage": "h2d", "batch": 0, "t0_ns": 10, "t1_ns": 20},
            {"thread": "consumer", "stage": "stall", "batch": 0, "t0_ns": 5, "t1_ns": 25}]
    with open(tmp_path / "spans.jsonl", "w") as f:
        f.write("\n".join(json.dumps(r) for r in rows) + "\n")
    got = spans.read_spans(str(tmp_path))
    assert got["header"]["profile_steps"] == [2, 6]
    assert got["producer"] == [("h2d", 0, 10, 20)]
    assert got["consumer"] == [("stall", 0, 5, 25)]
    assert spans.read_spans(str(tmp_path / "nowhere")) is None

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    before = time.time_ns()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    read = spans.read_xplane(trace.find_xplane(str(tmp_path)))
    assert read["chips"] == {}
    assert before <= read["start_ns"] <= time.time_ns()
