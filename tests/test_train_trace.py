"""What the train path hands a profiler trace (CPU, tier-1 fast):
``spans.jsonl`` beside a profiled epoch and nothing beside an unprofiled
one, and the step's phases as scopes in the lowered step (the kernels'
names are held in test_pallas_ops.py)."""

import json
import os
import re

import numpy as np
import pytest

from deep_vision_tpu.core.config import get_config
from deep_vision_tpu.core.trainer import Trainer
from deep_vision_tpu.data.loader import ArrayLoader
from deep_vision_tpu.data.mnist import synthetic_mnist
from deep_vision_tpu.ops import preprocess
from deep_vision_tpu.tasks.classification import ClassificationTask

PHASE_SCOPES = ("prologue", "jvp(forward)", "transpose(jvp(forward))",
                "jvp(loss)", "optimizer")


def lenet_trainer(tmp_path, mesh):
    cfg = get_config("lenet5")
    cfg.batch_size = 32
    cfg.log_every_steps = 2
    trainer = Trainer(cfg, cfg.model(), ClassificationTask(num_classes=10),
                      mesh=mesh, workdir=str(tmp_path),
                      preprocess_fn=preprocess.make_mnist_preprocess())
    train = ArrayLoader(synthetic_mnist(256), cfg.batch_size, seed=1)
    return trainer, train, trainer.init_state(next(iter(train)))


def scopes_of(trainer, state, batch) -> set:
    """Every scope path in the locations of the trainer's lowered step, on
    the uint8 wire (a float batch passes the prologue untouched)."""
    trainer._build_steps()
    wire = dict(batch, image=np.zeros(batch["image"].shape, np.uint8))
    text = trainer._jit_train_step.lower(state, wire).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


def test_profiled_epoch_writes_spans_jsonl(tmp_path, mesh1):
    trainer, train, state = lenet_trainer(tmp_path, mesh1)
    trainer.profile_steps = (2, 6)
    trainer.train_epoch(state, train, trainer.start_epoch)
    with open(tmp_path / "spans.jsonl") as f:
        rows = [json.loads(line) for line in f]
    header, rows = rows[0], rows[1:]
    assert set(header) == {"clock", "profile_steps", "depth", "batches",
                           "h2d_bytes"}
    assert header["profile_steps"] == [2, 6] and header["batches"] == 8
    assert header["depth"] == trainer.prefetch_depth
    assert header["h2d_bytes"] > 0
    # two (monotonic_ns, time_ns) pairs: as the trace started, as it stopped
    (m0, w0), (m1, w1) = header["clock"]
    assert m1 > m0 and w1 > w0 and abs((w1 - m1) - (w0 - m0)) < 50e6
    for row in rows:
        assert set(row) == {"thread", "stage", "batch", "t0_ns", "t1_ns"}
        assert row["t0_ns"] <= row["t1_ns"]
    consumer = [r for r in rows if r["thread"] == "consumer"]
    producer = [r for r in rows if r["thread"] == "producer"]
    # one dispatch per step, numbered as the producer numbers its batches
    assert [r["batch"] for r in consumer if r["stage"] == "dispatch"] == \
        list(range(8))
    assert [r["batch"] for r in producer if r["stage"] == "h2d"] == \
        list(range(8))
    assert [r["batch"] for r in consumer if r["stage"] == "fetch"] == [2, 4, 6]
    assert [r["batch"] for r in consumer if r["stage"] == "profile"] == [2, 6]
    # on the wall clock, around the trace: the first pair was taken inside
    # the loop's iteration 2, the second inside iteration 6
    starts = {r["batch"]: r["t0_ns"] for r in consumer if r["stage"] == "stall"}
    assert starts[2] < w0 < starts[3] and starts[6] < w1 < starts[7]
    for thread in (consumer, producer):
        assert all(a["t1_ns"] == b["t0_ns"] for a, b in zip(thread, thread[1:]))
    assert os.path.isdir(tmp_path / "profile")


def test_unprofiled_epoch_writes_no_spans(tmp_path, mesh1):
    trainer, train, state = lenet_trainer(tmp_path, mesh1)
    state = trainer.train_epoch(state, train, trainer.start_epoch)
    assert int(state.step) == 8
    assert not os.path.exists(tmp_path / "spans.jsonl")
    assert not os.path.exists(tmp_path / "profile")
    # the loop's marks are taken all the same, and change no exported sum
    stats = trainer._prefetcher.stats()
    assert stats["batches"] == 8
    assert stats["input_stall_frac"] == pytest.approx(
        stats["stall_ms"] / (stats["stall_ms"] + stats["step_ms"]))


def test_lowered_step_names_its_phases(tmp_path, mesh1):
    trainer, train, state = lenet_trainer(tmp_path, mesh1)
    scopes = scopes_of(trainer, state, next(iter(train)))
    for want in PHASE_SCOPES:
        assert any(want in s.split("/") for s in scopes), want
    assert all(s.startswith("jit(train_step)") for s in scopes
               if "jvp(" in s)


def test_lowered_yolo_step_names_best_iou(tmp_path, mesh1):
    """Both implementations of the ignore mask's IoU maximum lower under
    the one scope, inside the loss."""
    from deep_vision_tpu.data.detection import (
        DetectionLoader,
        synthetic_detection_dataset,
    )
    from deep_vision_tpu.tasks.detection import YoloTask

    cfg = get_config("yolov3_toy")
    batch = next(iter(DetectionLoader(
        synthetic_detection_dataset(2, image_size=64, num_classes=3),
        batch_size=2, num_classes=3, image_size=64)))
    for use_pallas in (False, True):
        trainer = Trainer(cfg, cfg.model(), YoloTask(3, use_pallas=use_pallas),
                          mesh=mesh1, workdir=str(tmp_path / str(use_pallas)),
                          preprocess_fn=preprocess.make_scale_preprocess())
        state = trainer.init_state(batch)
        scopes = scopes_of(trainer, state, batch)
        under = [s for s in scopes if "best_iou" in s.split("/")]
        assert under and all("jvp(loss)" in s.split("/") for s in under)
        for want in PHASE_SCOPES:
            assert any(want in s.split("/") for s in scopes), want


# ------------------------------------------------------- the launch record

def read_launch(path) -> tuple[dict, list, list, list]:
    """``launch.jsonl`` as (header, stages, an epoch's first_* lines,
    compile intervals)."""
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    body = rows[1:]
    return (rows[0],
            [r for r in body if r.get("parent") == "launch"],
            [r for r in body if r.get("parent") == "epoch" and "name" in r],
            [r for r in body if "kind" in r])


def test_profiled_epoch_writes_launch_jsonl(tmp_path, mesh1):
    import time

    from deep_vision_tpu.obs import launch

    epochs_before = launch.start().epochs
    trainer, train, state = lenet_trainer(tmp_path, mesh1)
    trainer.profile_steps = (2, 6)
    trainer.train_epoch(state, train, trainer.start_epoch)
    written_ns = time.time_ns()
    header, stages, firsts, compiles = read_launch(tmp_path / "launch.jsonl")
    assert set(header) == {"clock", "process_start_ns", "pid", "argv0",
                           "cache_hits", "cache_misses", "intervals",
                           "dropped"}
    with open(tmp_path / "spans.jsonl") as f:
        assert header["clock"] == json.loads(f.readline())["clock"]
    assert header["pid"] == os.getpid() and header["dropped"] == 0
    assert header["intervals"] == len(compiles)
    # the stages tile the process from its start to the file's writing
    assert stages[0]["t0_ns"] == header["process_start_ns"]
    assert stages[0]["name"] == "outside" and stages[1]["name"] == "import"
    assert all(a["t1_ns"] == b["t0_ns"] for a, b in zip(stages, stages[1:]))
    assert header["clock"][-1][1] < stages[-1]["t1_ns"] <= written_ns
    for row in stages + firsts:
        assert set(row) == {"name", "ordinal", "parent", "t0_ns", "t1_ns"}
        assert row["t0_ns"] <= row["t1_ns"]
    # this trainer's build, its init, and one epoch a call of train_epoch,
    # numbered by the calls the process has made
    names = [s["name"] for s in stages]
    assert names[-1] == "epoch" and "caller" in names
    build = [s for s in stages if s["name"] == "build"][-1]
    init = [s for s in stages if s["name"] == "init"][-1]
    epoch = stages[-1]
    assert build["t1_ns"] <= init["t0_ns"] < init["t1_ns"] <= epoch["t0_ns"]
    assert names.count("epoch") == epochs_before + 1
    assert epoch["ordinal"] == epochs_before
    mine = {f["name"]: f for f in firsts if f["ordinal"] == epoch["ordinal"]}
    assert mine["first_dispatch"]["t0_ns"] == epoch["t0_ns"]
    assert mine["first_dispatch"]["t1_ns"] == mine["first_fetch"]["t0_ns"]
    assert mine["first_fetch"]["t1_ns"] < epoch["t1_ns"]
    # the step's trace, lowering and compile lie inside that epoch, before
    # its first dispatch returned, and belong to batch 0
    step = [c for c in compiles if c["t0_ns"] >= build["t0_ns"]
            and c["fun"] in ("train_step", "jit(train_step)")]
    assert [c["kind"] for c in step] == ["trace", "lower", "backend_compile"]
    for c in step:
        assert set(c) == {"kind", "fun", "t0_ns", "t1_ns", "cache", "parent",
                          "batch"}
        assert c["parent"] == "epoch" and c["batch"] == 0
        assert (epoch["t0_ns"] <= c["t0_ns"] <= c["t1_ns"]
                <= mine["first_dispatch"]["t1_ns"])
    # ... and, brought onto the spans' own clock by the header's first
    # pair, between the process's start and the file's writing
    mono_ns, wall_ns = header["clock"][0]
    started = header["process_start_ns"] - wall_ns + mono_ns
    for c in step:
        assert started < c["t0_ns"] - wall_ns + mono_ns < time.monotonic_ns()
    # the init program compiled under ``init``
    assert any(c["parent"] == "init" and c["kind"] == "backend_compile"
               and init["t0_ns"] <= c["t0_ns"] <= c["t1_ns"] <= init["t1_ns"]
               for c in compiles)


def test_unprofiled_epoch_writes_no_launch_file(tmp_path, mesh1):
    trainer, train, state = lenet_trainer(tmp_path, mesh1)
    trainer.train_epoch(state, train, trainer.start_epoch)
    assert not os.path.exists(tmp_path / "launch.jsonl")
    assert not os.path.exists(tmp_path / "spans.jsonl")
    # no program compiled after the epoch's first dispatch, and the series
    # says so beside images_per_sec
    history = trainer.logger.history
    assert history["train_compiles"]["values"] == [0.0]
    assert history["train_compiles"]["steps"] == \
        history["images_per_sec"]["steps"]


def test_a_batch_of_another_shape_is_named_with_its_batch(tmp_path, mesh1,
                                                          capsys):
    trainer, train, state = lenet_trainer(tmp_path, mesh1)
    batches = list(train)
    state = trainer.train_epoch(state, batches[:3], trainer.start_epoch)
    assert "[compile]" not in capsys.readouterr().out
    half = {k: v[:16] for k, v in batches[3].items()}
    trainer.train_epoch(state, [batches[4], batches[5], half, batches[6]],
                        trainer.start_epoch + 1)
    assert trainer.logger.history["train_compiles"]["values"] == [0.0, 1.0]
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("[compile]")]
    assert len(lines) == 1
    assert re.fullmatch(r"\[compile\] epoch 2 batch 2: jit\(train_step\) "
                        r"\d+\.\ds (hit|miss|uncached)", lines[0])


def test_an_epochs_first_compile_is_not_a_late_one(tmp_path, mesh1, capsys):
    """An epoch whose FIRST batch has a new shape compiles before its first
    dispatch returns: that is the launch, not a recompile in the loop."""
    trainer, train, state = lenet_trainer(tmp_path, mesh1)
    batches = list(train)
    state = trainer.train_epoch(state, batches[:2], trainer.start_epoch)
    half = {k: v[:16] for k, v in batches[2].items()}
    trainer.train_epoch(state, [half], trainer.start_epoch + 1)
    assert trainer.logger.history["train_compiles"]["values"] == [0.0, 0.0]
    assert "[compile]" not in capsys.readouterr().out
