"""`make deploy-smoke`: the hands-off train→deploy loop, end to end
over real HTTP.  Boots `cli.serve --models lenet5 --watch` wiring
(build_server's plane path + DeployPipeline), then, while a client
thread hammers /v1/models/lenet5/classify the whole time:

  * writes a REAL async-Orbax checkpoint (step 1) into the watched
    workdir mid-load — the watcher must fingerprint it, debounce it,
    pass it through the accuracy gate (fresh random init under
    PRNGKey(0) is byte-identical to the serving weights, so agreement
    is 1.0), and roll it through canary → promote to v2 with ZERO
    client errors and no operator action;
  * writes a NaN-params checkpoint (step 2) — the gate must refuse it
    (a gate_failed ledger record), and v2 must keep serving;
  * POSTs /v1/deploy/lenet5/revert — one command back to the previous
    promoted version (v3 wraps v1's weights), still zero client errors;
  * asserts GET /v1/deploy/lenet5/history tells exactly that story,
    /v1/stats carries the deploy block, and /metrics exposes the
    dvt_deploy_* and dvt_serve_reverts_total series as parseable
    Prometheus text.

Run directly, not under pytest."""

import argparse
import json
import os
import re
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np

# plain script (not pytest): make the repo root importable when invoked
# as `python tests/deploy_smoke.py` from the checkout
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_PROM_LINE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (\S+)$")


def _get(base, path, timeout=60):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _post(base, path, payload=None, timeout=120):
    req = urllib.request.Request(
        base + path,
        data=json.dumps(payload).encode() if payload is not None else b"",
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _wait_for(what, predicate, deadline_s=90.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        out = predicate()
        if out is not None:
            return out
        time.sleep(0.1)
    raise AssertionError(f"timed out after {deadline_s}s waiting for {what}")


def smoke():
    import jax

    from deep_vision_tpu.cli.serve import build_server
    from deep_vision_tpu.core.checkpoint import Checkpointer
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.restore import load_state

    with tempfile.TemporaryDirectory() as workdir:
        os.makedirs(os.path.join(workdir, "lenet5"), exist_ok=True)
        args = argparse.Namespace(
            model=None, models="lenet5", workdir=workdir,
            stablehlo=None, host="127.0.0.1", port=0, max_batch=4,
            max_wait_ms=2.0, buckets=None, max_queue=64, warmup=True,
            verbose=False, pipeline_depth=2, faults="", fault_seed=0,
            serve_devices=1, shard_batches=False, wire_dtype="float32",
            infer_dtype="float32", hbm_budget_mb=0.0,
            canary_frac=0.5, canary_min_requests=3,
            canary_max_error_rate=0.0, canary_max_p99_ratio=50.0,
            shadow_frac=0.0, phase_timeout_s=60.0,
            # the continuous-deploy pipeline under test
            watch=True, watch_interval_s=0.1, gate_dir=None,
            gate_min_agreement=0.8, min_replicas=0, max_replicas=0)
        plane, server = build_server(args)
        server.start_background()
        base = f"http://{server.host}:{server.port}"
        deploy = server.httpd.deploy
        assert deploy is not None and deploy.watcher is not None
        ckpt = None
        try:
            status, health = _get(base, "/v1/healthz")
            assert status == 200 and health["status"] == "ok", health

            # the client load that must never see an error — through
            # checkpoint publish, gated rollout, refusal, and revert
            lenet_px = np.zeros((32, 32, 1)).tolist()
            errors, served = [], [0]
            stop = threading.Event()

            def hammer():
                while not stop.is_set():
                    try:
                        s, out = _post(base, "/v1/models/lenet5/classify",
                                       {"pixels": lenet_px}, timeout=60)
                        assert s == 200 and out["top"], out
                        served[0] += 1
                    except Exception as e:  # noqa: BLE001 — any failure is a lost request
                        errors.append(repr(e))

            t = threading.Thread(target=hammer, daemon=True)
            t.start()
            while served[0] < 5:
                time.sleep(0.01)

            # -- step 1: publish a real checkpoint mid-load ------------
            # fresh random init under PRNGKey(0) == the weights already
            # serving, so the synthetic accuracy gate sees agreement 1.0
            cfg = get_config("lenet5")
            with tempfile.TemporaryDirectory() as seed_dir:
                _, state = load_state(cfg, seed_dir,
                                      log=lambda *a, **k: None)
            ckpt = Checkpointer(
                os.path.join(workdir, "lenet5", "checkpoints"))
            ckpt.save(1, state)
            ckpt.wait_until_finished()

            def promoted():
                _, h = _get(base, "/v1/deploy/lenet5/history")
                ent = h["entries"]
                if ent and ent[-1]["outcome"] == "promoted":
                    return ent
                return None

            entries = _wait_for("auto-deploy of step 1", promoted)
            _, table = _get(base, "/v1/models")
            assert table["models"]["lenet5"]["active_version"] == 2
            outcomes = [e["outcome"] for e in entries]
            assert outcomes == ["candidate", "gate_passed", "promoted"], \
                outcomes
            gate = [e for e in entries
                    if e["outcome"] == "gate_passed"][0]["gate"]
            assert gate["agreement"] == 1.0, gate

            # -- step 2: a bad checkpoint must be refused --------------
            nan_state = state.replace(params=jax.tree_util.tree_map(
                lambda a: np.asarray(a) * np.nan, state.params))
            ckpt.save(2, nan_state)
            ckpt.wait_until_finished()

            def gate_failed():
                _, st = _get(base, "/v1/stats")
                w = st["deploy"]["watcher"]
                return w if w["gate_failures"] >= 1 else None

            watcher_stats = _wait_for("gate refusal of step 2",
                                      gate_failed)
            assert watcher_stats["deploys"] == 1, watcher_stats
            _, table = _get(base, "/v1/models")
            assert table["models"]["lenet5"]["active_version"] == 2, \
                "gate failure must leave the active version serving"
            _, hist = _get(base, "/v1/deploy/lenet5/history")
            last = hist["entries"][-1]
            assert last["outcome"] == "gate_failed", hist["entries"]
            assert "NaN" in last["gate"]["reason"], last

            # -- one-command revert back to v1's weights ---------------
            status, out = _post(base, "/v1/deploy/lenet5/revert")
            assert status == 200 and out["status"] == "reverted", out
            assert out["from_version"] == 2, out
            _, table = _get(base, "/v1/models")
            assert table["models"]["lenet5"]["active_version"] == 3
            # revert is symmetric: v2 was promoted too, so a second
            # revert swings back to its weights (v4 restores v2)
            status, out = _post(base, "/v1/deploy/lenet5/revert")
            assert status == 200 and out["restores"] == 2, (status, out)
            _, table = _get(base, "/v1/models")
            assert table["models"]["lenet5"]["active_version"] == 4
            # unknown model → 404 through the deploy routes
            try:
                status, _ = _get(base, "/v1/deploy/nope/history")
            except urllib.error.HTTPError as e:
                status = e.code
            assert status == 404, status

            stop.set()
            t.join(60)
            assert not errors, \
                f"deploy loop lost {len(errors)}: {errors[:3]}"

            # -- observability: stats block + metrics series -----------
            _, stats = _get(base, "/v1/stats")
            dep = stats["deploy"]
            assert dep["history"]["records"] >= 5, dep["history"]
            assert dep["watcher"]["polls"] > 0, dep["watcher"]
            assert stats["plane"]["reverts"] == 2, stats["plane"]
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=60) as r:
                text = r.read().decode()
            for line in text.splitlines():
                if not line or line.startswith("#"):
                    continue
                m = _PROM_LINE.match(line)
                assert m, f"bad metric line: {line}"
                float(m.group(2))
            for series in ("dvt_deploy_history_records_total",
                           "dvt_deploy_watcher_polls_total",
                           "dvt_deploy_deploys_total 1",
                           "dvt_deploy_gate_failures_total 1",
                           "dvt_serve_reverts_total 2"):
                assert series in text, f"missing {series}"
            print(f"deploy-smoke PASS: checkpoint published mid-load "
                  f"auto-deployed to v2 ({served[0]} client requests, "
                  f"0 errors), NaN checkpoint refused by the gate, "
                  f"revert restored v1's weights as v3; "
                  f"{dep['history']['records']} ledger records, "
                  f"{dep['watcher']['polls']} watcher polls, "
                  f"{len(text.splitlines())} metric lines parsed")
        finally:
            if ckpt is not None:
                ckpt.close()
            deploy.stop()
            server.shutdown()
            plane.stop(drain_deadline=5.0)
    return 0


def main():
    # smokes run on the CPU: pin the platform before jax initializes
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    return smoke()


if __name__ == "__main__":
    sys.exit(main())
