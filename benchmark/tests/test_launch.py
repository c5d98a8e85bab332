"""The launch record's six readers on a small committed record
(``fixture_launch/launch.jsonl``, seconds since an origin of 1,000):

    outside 1000-1003, import -1005, cache -1005.5, caller -1008.5,
    build -1010.5, caller -1010.6, init -1014.6 (compiles 1010.7-1013.5),
    caller -1018.6 (a cached program 1015-1016, its read inside it),
    epoch 0 -1029.6 (compiles 1018.7-1027.2), caller -1030.1,
    epoch 1 -1031.1, caller -1031.6, the window's epoch 2 -1056.6 (one
    program compiles in it at 1040)

with the harness's own clock started 0.05 s after the process."""

import os

import pytest

from benchmark import launch
from benchmark.byname import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_launch")
TO_WINDOW_S = 31.55
WANT = {
    "setup_outside_s": 3.0 + 2.0,
    "setup_build_s": 0.5 + 2.0 + (4.0 - 2.8),
    "setup_compile_s": 2.8 + 1.0 + 8.5,
    "setup_steps_s": (11.0 - 8.5) + 1.0,
    "setup_caller_s": TO_WINDOW_S - (5.0 + 3.7 + 12.3 + 3.5),
    "setup_cache_misses": 2.0,
}
TIMES = [k for k in WANT if k != "setup_cache_misses"]


def fixture_run(workdir=FIXTURE) -> dict:
    return {"window": {"workdir": workdir}, "to_window_s": TO_WINDOW_S}


def reader(name: str):
    return load_module(os.path.join(os.path.dirname(HERE), "metrics",
                                    name + ".py"), "metric_" + name)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_the_hand_computed_number(name):
    assert reader(name).read(fixture_run()) == pytest.approx(WANT[name], abs=1e-6)


def test_the_five_times_sum_to_the_window(capsys):
    run = fixture_run()
    assert sum(reader(n).read(run) for n in TIMES) == pytest.approx(TO_WINDOW_S)
    assert all(reader(n).read(run) >= 0 for n in TIMES)
    # one analysis and one printed line, whoever asks first
    assert capsys.readouterr().out.count("[launch] ") == 1
    found = run["launch_analysis"]
    assert found["process_start_to_window_s"] == pytest.approx(31.6)
    assert found["epochs_before_window"] == 2
    assert found["compiles_in_window"] == 1 and found["straddling"] == 0
    assert found["first_epochs_s"][0] == pytest.approx([0, 11.0, 10.0, 0.5])
    assert found["longest_programs"][0] == [
        "jit(train_step)", pytest.approx(7.0), "miss", "epoch"]
    assert found["compiles_n_s"]["caller/backend_compile"] == [
        1, pytest.approx(1.0)]
    assert found["stage_self_s"]["init"] == pytest.approx(1.2)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_gives_nothing_without_the_file(name, tmp_path, capsys):
    assert reader(name).read(fixture_run(str(tmp_path))) is None
    assert "[launch]" not in capsys.readouterr().out


def test_a_compile_across_a_stage_boundary_is_counted():
    record = launch.read_launch(FIXTURE)
    record["compiles"].append(dict(record["compiles"][0],
                                   t0_ns=record["stages"][3]["t0_ns"] - 10,
                                   t1_ns=record["stages"][3]["t0_ns"] + 10))
    assert launch.split(record, TO_WINDOW_S)["straddling"] == 1


def test_every_launch_metric_of_the_benchmark_has_its_reader():
    from benchmark import run

    bench = run.load_json(os.path.join(run.CHECKOUT, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"] if m["layer"] == "launch"]
    assert sorted(names) == sorted(WANT)
    assert all(m["moves"] == "setup_s" and "workloads" not in m
               for m in bench["per_layer"] if m["layer"] == "launch")
