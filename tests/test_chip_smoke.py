"""chip_smoke.py's two CPU-side promises: the rehearsal runs every phase,
and the real thing refuses to run without a chip."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, tmp_path, devices: int, timeout: float):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               # cache in the test's directory, not the checkout's
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("DEEP_VISION_TPU_NO_COMPILE_CACHE", None)
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=timeout)


def test_refuses_to_run_without_a_chip(tmp_path):
    """No flag + a CPU backend: non-zero exit before anything compiles,
    and no result line that could be read as a pass."""
    out = _run([], tmp_path, devices=1, timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""
    assert not (tmp_path / "chiprun_out").exists()


def test_rehearsal_runs_every_phase(tmp_path):
    """Two virtual devices, so the replicated/sharded halves of phases
    2-4 are rehearsed too."""
    out = _run(["--rehearse"], tmp_path, devices=2, timeout=800)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0].startswith("jax ") and "platform=cpu" in lines[0] \
        and "devices=2" in lines[0] and "compile_cache=" in lines[0]
    assert lines[1] == "platform: cpu — rehearsal, not a chip pass"
    last = json.loads(lines[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 2}}
    for tag in ("[kernels] serve_ingest", "[kernels] train_ingest",
                "[kernels] best_iou_max", "[train] 3 steps, 0 skipped",
                "[serve] float32:", "[serve] int8:", "[cache] compile"):
        assert any(ln.startswith(tag) for ln in lines), tag
    with open(tmp_path / "chiprun_out" / "chip_smoke_rehearsal.json") as f:
        report = json.load(f)
    assert report["rehearsal"] and report["device"]["count"] == 2
    assert report["compile_cache"]["dir"] == str(tmp_path / "jax_cache")
    assert [s["replicas"] for s in report["serve"]] == [2, 2]
    assert report["serve"][1]["ingest_path"] == "pallas"
