"""Does the system still start on the chip?  The quickest end-to-end proof.

    python chip_smoke.py             # needs a TPU; exits non-zero without
    python chip_smoke.py --rehearse  # tiny sizes on the CPU, proves nothing
                                     # about the chip (what tier-1 runs)

One process — the only one that touches JAX — drives the repo's two main
paths once at the published width of ResNet-50 (224², global batch 256,
bf16 compute, random weights from the config's seed), through the entry
points a user calls:

1. kernels  every Pallas kernel compiled (not interpreted) at the shapes
            the zoo uses, against the XLA reference its check compares to;
2. train    ``cli.train`` on uint8 dvrec records packed here from a seed:
            one epoch, its eval pass, its checkpoint;
3. serve    ``cli.serve``'s ``build_server`` on that workdir, real HTTP,
            float32 and int8, answers checked against ``model.apply``;
4. devices  every local device holds parameters, a batch shard and a
            serving replica that answered;
5. cache    compile seconds and cache entries, beside the previous run's.

A phase that fails raises; nothing is caught and carried past.  The last
line of stdout is one JSON object naming the device JAX reported.
Everything else the run learned is written to ``chiprun_out/chip_smoke.json``
(``chip_smoke_rehearsal.json`` for a rehearsal).
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import dataclasses
import faulthandler
import json
import multiprocessing
import os
import shutil
import sys
import tempfile
import time
import urllib.request

MODEL = "resnet50"
#: seconds after which a hung run dumps every thread's stack and exits
#: non-zero (the contract allows 1200 s, compilation included)
DEADLINE_S = 1150
#: the full account of a run, relative to the working directory (the
#: checkout root on the chip machine, where the tool collects it)
REPORT = os.path.join("chiprun_out", "chip_smoke{}.json")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything ``--rehearse`` shrinks.  The serving side has no size of
    its own to shrink: ``cli.serve`` takes the input shape from the
    config, so even the rehearsal serves 224² images."""

    train_flags: tuple      # extra cli.train flags (none at full size)
    image_size: int         # train resolution
    batch: int              # global train batch
    train_images: int
    val_images: int
    num_workers: int        # decode pool processes per loader
    max_batch: int          # cli.serve --max-batch (bucket ladder top)
    singles: int            # sequential single requests (bucket 1)
    burst: int              # concurrent requests after them
    int8_bucket: int
    yolo_size: int          # best_iou_max: n_pred = 3·(size/s)²
    yolo_batches: tuple


#: published ResNet-50 config untouched (zoo/resnet.py); 2,816 images =
#: 11 optimizer steps, so the every-10-steps logger fires twice
FULL = Sizes(train_flags=(), image_size=224, batch=256, train_images=2816,
             val_images=256,
             num_workers=max(2, min(8, (os.cpu_count() or 2) - 2)),
             max_batch=32, singles=8, burst=32, int8_bucket=8,
             yolo_size=416, yolo_batches=(16, 128))
REHEARSE = Sizes(train_flags=("--image-size", "32", "--batch-size", "8"),
                 image_size=32, batch=8, train_images=24, val_images=8,
                 num_workers=2, max_batch=2, singles=4, burst=4,
                 int8_bucket=2, yolo_size=64, yolo_batches=(2, 12))


def versions() -> dict:
    from importlib.metadata import PackageNotFoundError, version

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "optax",
                "orbax-checkpoint", "numpy"):
        try:
            out[pkg] = version(pkg)
        except PackageNotFoundError:
            out[pkg] = None
    return out


def cache_entries(path: str | None) -> int | None:
    if not path:
        return None
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# --------------------------------------------------------------- 1. kernels

def phase_kernels(sz: Sizes, interpret: bool, n_devices: int) -> list[dict]:
    """Each kernel at each shape: compiled or refused, and its max error
    against the XLA reference.  The parity functions raise on a Mosaic
    refusal or a mismatch; that is caught HERE only to finish the table,
    and the phase then fails — so every later phase runs knowing all of
    them compiled."""
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.ops import pallas_ops
    from deep_vision_tpu.serve.engine import power_of_two_buckets
    from deep_vision_tpu.tasks.detection import MAX_BOXES

    # cli.serve has no resolution flag: it serves the config's size, on
    # the bucket ladder the engine builds by default
    serve_size = get_config(MODEL).image_size
    cases = []
    for b in power_of_two_buckets(sz.max_batch):
        shape = (b, serve_size, serve_size, 3)
        cases.append(("serve_ingest", shape, lambda s=shape:
                      pallas_ops.serve_ingest_parity(
                          s, "imagenet", 2.64 / 127.0, interpret=interpret)))
    shape = (sz.batch // n_devices, sz.image_size, sz.image_size, 3)
    cases.append(("train_ingest", shape, lambda s=shape:
                  pallas_ops.train_ingest_parity(s, interpret=interpret)))
    for b in sz.yolo_batches:
        for stride in (8, 16, 32):
            n_pred = 3 * (sz.yolo_size // stride) ** 2
            cases.append(("best_iou_max", (b, n_pred, MAX_BOXES),
                          lambda b=b, n=n_pred: pallas_ops.best_iou_parity(
                              b, n, MAX_BOXES, interpret=interpret)))
    how = "interpreted" if interpret else "compiled by Mosaic"
    table = []
    for kernel, shape, check in cases:
        t0 = time.monotonic()
        row = {"kernel": kernel, "shape": list(shape)}
        try:
            row.update(status=how, max_err=check())
        except Exception as e:  # noqa: BLE001 — reported below, then the phase fails
            row.update(status="REFUSED",
                       error=f"{type(e).__name__}: {e}"[:1500])
        row["seconds"] = round(time.monotonic() - t0, 2)
        table.append(row)
        print(f"[kernels] {kernel:13s} {str(tuple(shape)):22s} "
              f"{row['status']:19s} "
              + (f"max_err={row['max_err']:.3g}" if "max_err" in row
                 else row["error"]) + f" ({row['seconds']}s)", flush=True)
    refused = [r for r in table if r["status"] == "REFUSED"]
    if refused:
        raise RuntimeError(
            f"{len(refused)} kernel shape(s) refused or diverged: "
            + "; ".join(f"{r['kernel']}{tuple(r['shape'])}" for r in refused))
    return table


# ----------------------------------------------------------------- 2. train

def pack_records(tmp: str, sz: Sizes) -> str:
    """Seeded synthetic JPEGs → raw-uint8 dvrec shards, so the trainer
    reads real records over the uint8 wire instead of ``--synthetic``'s
    float arrays."""
    from deep_vision_tpu.data.native import load as load_native
    from deep_vision_tpu.data.prep import prepare_imagenet
    from deep_vision_tpu.data.synthetic import make_synthetic_imagenet
    from deep_vision_tpu.data.transforms import imagenet_resize_for

    t0 = time.monotonic()
    # source JPEGs at the stored size: packing decodes, never rescales
    resize = imagenet_resize_for(sz.image_size)
    root, labels, val_root = make_synthetic_imagenet(
        tmp, sz.train_images, resize, val_images=sz.val_images)
    recs = os.path.join(tmp, "recs")
    for split, src in (("train", root), ("val", val_root)):
        # num_workers=1: in-process.  The packer's pool forks, and this
        # process already holds the device
        prepare_imagenet(src, labels, recs, split=split, num_shards=4,
                         num_workers=1, store="raw", resize=resize)
        shutil.rmtree(src)
    native = load_native() is not None
    print(f"[data] packed {sz.train_images}+{sz.val_images} uint8 records "
          f"in {time.monotonic() - t0:.1f}s; native dvrec reader: "
          + ("built from dvrec_reader.cc" if native
             else "unavailable — NumPy fallback")
          + f" (in-process reads only; this run decodes in a "
          f"{sz.num_workers}-process pool)", flush=True)
    return recs


@contextlib.contextmanager
def observed_trainer(seen: dict):
    """Watch ``cli.train``'s Trainer without changing what it does:
    ``main()`` returns an exit code, and the state, its placement and the
    first batch's sharding are only visible from inside."""
    import jax
    import numpy as np

    from deep_vision_tpu.core.trainer import Trainer

    init_state, fit, train_step = \
        Trainer.init_state, Trainer.fit, Trainer.train_step

    def watched_init(self, sample):
        state = init_state(self, sample)
        # host copy now: the first step donates these buffers
        seen["init_params"] = jax.tree_util.tree_map(
            np.asarray, jax.device_get(state.params))
        return state

    def watched_step(self, state, batch):
        if "batch_devices" not in seen:
            img = batch["image"]
            seen["batch_is_device_array"] = isinstance(img, jax.Array)
            seen["batch_dtype"] = str(img.dtype)
            seen["batch_devices"] = sorted(
                s.device.id for s in img.addressable_shards)
            seen["batch_shard_shape"] = list(
                img.addressable_shards[0].data.shape)
        return train_step(self, state, batch)

    def watched_fit(self, *a, **kw):
        state = fit(self, *a, **kw)
        seen["state"], seen["mesh"] = state, self.mesh
        # what the "[input] train ingest:" log line prints
        seen["fused_ingest"] = self.preprocess_fn.fused
        return state

    Trainer.init_state, Trainer.fit, Trainer.train_step = \
        watched_init, watched_fit, watched_step
    try:
        yield
    finally:
        Trainer.init_state, Trainer.fit, Trainer.train_step = \
            init_state, fit, train_step


def phase_train(sz: Sizes, recs: str, workdir: str,
                log) -> tuple[dict, str]:
    """Returns (what the report keeps, the trained params' digest)."""
    import jax
    import numpy as np

    from deep_vision_tpu.cli.train import main as train_main
    from deep_vision_tpu.core.restore import params_digest

    seen: dict = {}
    mark = log.mark()
    with observed_trainer(seen):
        rc = train_main([
            "-m", MODEL, "--data-root", recs, "--data-format", "records",
            "--epochs", "1", "--num-workers", str(sz.num_workers),
            "--workdir", workdir, *sz.train_flags])
    if rc != 0:
        raise RuntimeError(f"cli.train exited {rc}")
    # -- the step trained through the kernel phase 1 compiled
    assert seen["fused_ingest"], "train ingest took the XLA path"

    # -- metrics.jsonl: finite losses, no skipped step, every step taken
    with open(os.path.join(workdir, "metrics.jsonl")) as f:
        recs_log = [json.loads(ln) for ln in f if ln.strip()]
    by_name: dict = {}
    for r in recs_log:
        by_name.setdefault(r["name"], []).append((r["step"], r["value"]))
    losses = by_name["train_loss"] + by_name["val_loss"]
    assert losses and all(np.isfinite(v) for _, v in losses), losses
    assert all(v == 0 for _, v in by_name["train_bad_steps"]), \
        f"divergence guard skipped steps: {by_name['train_bad_steps']}"
    steps = sz.train_images // sz.batch
    state = seen["state"]
    assert int(state.step) == steps == max(s for s, _ in losses), \
        (int(state.step), steps, losses)
    assert int(jax.device_get(state.bad_steps)) == 0
    # the uint8 wire: one byte a pixel plus the int32 labels, no float copy
    wire = sz.batch * (sz.image_size ** 2 * 3 + 4)
    h2d = by_name["input_h2d_bytes_per_step"][-1][1]
    assert seen["batch_dtype"] == "uint8" and h2d == wire, \
        (seen["batch_dtype"], h2d, wire)

    # -- parameters moved, all of them finite
    final = jax.tree_util.tree_map(np.asarray, jax.device_get(state.params))
    pairs = list(zip(jax.tree_util.tree_leaves(seen["init_params"]),
                     jax.tree_util.tree_leaves(final)))
    assert all(np.isfinite(b).all() for _, b in pairs)
    moved = sum(not np.array_equal(a, b) for a, b in pairs)
    assert moved > len(pairs) // 2, f"only {moved}/{len(pairs)} leaves moved"

    # -- a committed checkpoint, not an Orbax staging directory
    ckpt = os.path.join(workdir, "checkpoints", str(steps))
    assert os.path.isfile(os.path.join(ckpt, "_CHECKPOINT_METADATA")), \
        os.listdir(os.path.join(workdir, "checkpoints"))

    # -- 4. devices: every device holds the parameters and a batch shard
    every = sorted(d.id for d in jax.devices())
    leaves = jax.tree_util.tree_leaves(state.params)
    assert all(sorted(d.id for d in a.sharding.device_set) == every
               and a.is_fully_replicated for a in leaves), \
        "parameters are not replicated over every device"
    assert seen["batch_is_device_array"] and \
        seen["batch_devices"] == every and \
        seen["batch_shard_shape"][0] == sz.batch // len(every), seen
    assert dict(seen["mesh"].shape) == {"data": len(every)}, seen["mesh"]

    result = {
        "steps": steps, "skipped_steps": 0,
        "train_loss": by_name["train_loss"],
        "val_loss": by_name["val_loss"],
        "leaves_moved": f"{moved}/{len(pairs)}",
        "h2d_bytes_per_step": h2d,
        "batch_shard_shape": seen["batch_shard_shape"],
        "devices_holding_params_and_a_shard": every,
        "checkpoint": os.path.relpath(ckpt, workdir),
        "compile": {"train_step": log.since(mark, "train_step"),
                    "eval_step": log.since(mark, "eval_step"),
                    "all": log.since(mark)}}
    print(f"[train] {steps} steps, 0 skipped, first logged loss "
          f"{by_name['train_loss'][0][1]:.4f} (step "
          f"{by_name['train_loss'][0][0]}), val loss "
          f"{by_name['val_loss'][-1][1]:.4f}, {moved}/{len(pairs)} param "
          f"leaves moved, checkpoint {result['checkpoint']} committed",
          flush=True)
    return result, params_digest(final)


# ----------------------------------------------------------------- 3. serve

def serve_args(workdir: str, sz: Sizes, infer_dtype: str,
               buckets: str | None) -> argparse.Namespace:
    """What ``cli.serve``'s parser would hand ``build_server`` for
    ``-m resnet50 --workdir W --warmup --serve-devices 0 --port 0
    --max-wait-ms 250`` (+ ``--infer-dtype`` / ``--buckets``): every flag
    not named here keeps its default (build_server getattr's them).

    max_wait_ms: a 224² image is ~600 kB of JSON and takes the server
    tens of ms to parse, so concurrent requests reach the batcher that
    far apart; with the 5 ms default window every one would ride alone
    in bucket 1 and no other bucket would see traffic."""
    return argparse.Namespace(
        model=MODEL, models=None, workdir=workdir, stablehlo=None,
        host="127.0.0.1", port=0, max_batch=sz.max_batch, max_wait_ms=250.0,
        buckets=buckets, max_queue=256, warmup=True, verbose=False,
        serve_devices=0, wire_dtype="uint8", infer_dtype=infer_dtype)


def http_json(url: str, body: bytes | None = None):
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        payload = r.read()
        ctype = r.headers.get("Content-Type", "")
        return r.status, (json.loads(payload) if "json" in ctype
                          else payload.decode())


def serve_once(workdir: str, sz: Sizes, infer_dtype: str,
               buckets: str | None, images, reference, tol: float,
               trained_step: int, trained_digest: str,
               log) -> dict:
    """Boot ``build_server``, answer ``images`` over HTTP, check every
    contract the serving path makes, shut down."""
    import jax
    import numpy as np

    from deep_vision_tpu.cli.serve import build_server

    mark = log.mark()
    t0 = time.monotonic()
    engine, server = build_server(
        serve_args(workdir, sz, infer_dtype, buckets))
    boot_s = time.monotonic() - t0
    server.start_background()
    base = f"http://{server.host}:{server.port}"
    n_dev = len(jax.local_devices())
    engines = list(getattr(engine, "replicas", [engine]))
    try:
        sm = engine.model
        # -- the trained checkpoint, byte for byte, not the random-init
        # fallback
        assert sm.restored_step == trained_step and \
            not sm.restore_fallback and \
            sm.params_digest == trained_digest, \
            (sm.restored_step, trained_step, sm.params_digest)
        assert len(engines) == n_dev, (len(engines), n_dev)

        status, health = http_json(base + "/v1/healthz")
        assert status == 200 and health["status"] == "ok", health
        status, before = http_json(base + "/v1/stats")
        before = before[MODEL]
        n_buckets = len(before["buckets"])
        # --warmup compiled every bucket on every replica, nothing else
        assert before["compiles"] == n_buckets * n_dev, before["compiles"]

        num_classes = reference.shape[-1]
        bodies = [json.dumps({"pixels": im.tolist(),
                              "top_k": num_classes}).encode()
                  for im in images]

        def classify(body):
            status, reply = http_json(base + "/v1/classify", body)
            logits = np.full(num_classes, np.nan, np.float32)
            for t in reply["top"]:
                logits[t["class"]] = t["logit"]
            return status, logits

        answers = [classify(b) for b in bodies[:sz.singles]]
        with concurrent.futures.ThreadPoolExecutor(sz.burst) as pool:
            answers += list(pool.map(classify, bodies[sz.singles:]))
        assert all(s == 200 for s, _ in answers), [s for s, _ in answers]
        got = np.stack([lg for _, lg in answers])
        assert np.isfinite(got).all() and got.shape == reference.shape

        # -- answers == model.apply on the restored variables
        span = float(reference.max() - reference.min())
        err = float(np.abs(got - reference).max()) / span
        agree = float((got.argmax(-1) == reference.argmax(-1)).mean())
        assert err <= tol, (
            f"{infer_dtype}: served logits are {err:.3%} of the logit "
            f"range from model.apply (allowed {tol:.1%})")

        status, after = http_json(base + "/v1/stats")
        after = after[MODEL]
        status, metrics = http_json(base + "/metrics")
        assert status == 200 and "dvt_serve_requests_served_total" in metrics
        # -- no compile inside the request window
        assert after["compiles"] == before["compiles"], \
            (before["compiles"], after["compiles"])
        assert after["served"] == len(images), after["served"]
        # -- uint8 on the wire: whole batches of one byte a pixel
        image_bytes = int(np.prod(images.shape[1:]))
        h2d = {int(b): n for b, n in
               after["pipeline"]["h2d_bytes_by_bucket"].items()}
        assert h2d and all(n % (b * image_bytes) == 0
                           for b, n in h2d.items()), h2d
        assert after["wire_dtype"] == "uint8"
        if n_buckets > 1:
            assert len(h2d) > 1, f"one bucket took all the traffic: {h2d}"
        # -- 4. devices: every replica answered
        if n_dev > 1:
            per_replica = [r["served"] for r in after["replicas"]]
            assert len(per_replica) == n_dev and min(per_replica) >= 1, \
                per_replica
        # -- the MFU block resolves the chip's peak from the table (and
        # has none to resolve anywhere else)
        mfu = after["mfu"]
        if jax.default_backend() == "tpu":
            from deep_vision_tpu.obs.mfu import peak_flops_per_s

            assert mfu["peak_flops_per_s"] == peak_flops_per_s() and \
                0 < mfu["serving_mfu"] < 1, mfu
        else:
            assert mfu["peak_flops_per_s"] is None and \
                mfu["serving_mfu"] is None, mfu
        ingest = None
        if infer_dtype == "int8":
            paths = {e.model.ingest_path for e in engines}
            assert paths == {"pallas"}, paths
            ingest = "pallas"
    finally:
        server.shutdown()
        engine.stop(drain_deadline=10.0)
    bucket_compile = log.since(mark, "apply")
    result = {"infer_dtype": infer_dtype, "replicas": n_dev,
              "buckets": before["buckets"], "requests": len(images),
              "restored_step": sm.restored_step,
              "max_err_of_logit_range": round(err, 6), "tolerance": tol,
              "top1_agreement": agree, "h2d_bytes_by_bucket": h2d,
              "compiles": after["compiles"], "boot_s": round(boot_s, 1),
              "ingest_path": ingest,
              "serving_mfu": mfu["serving_mfu"],
              "peak_flops_per_s": mfu["peak_flops_per_s"],
              "compile": {"buckets": bucket_compile,
                          "all": log.since(mark)}}
    print(f"[serve] {infer_dtype}: {len(images)} requests 200 over "
          f"{n_dev} replica(s), buckets used {sorted(h2d)}, logits within "
          f"{err:.3%} of range of model.apply (top-1 agreement "
          f"{agree:.2f}), compiles {after['compiles']} before and after, "
          f"step {sm.restored_step} restored"
          + (f", ingest {ingest}" if ingest else ""), flush=True)
    return result


def phase_serve(sz: Sizes, workdir: str, trained_step: int,
                trained_digest: str, log) -> list[dict]:
    import jax
    import numpy as np

    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.restore import load_state
    from deep_vision_tpu.ops.preprocess import serve_normalize

    cfg = get_config(MODEL)
    n = sz.singles + sz.burst
    images = np.random.default_rng(20260926).integers(
        0, 256, (n, cfg.image_size, cfg.image_size, 3), dtype=np.uint8)
    # the reference: the config's own model applied to the variables a
    # plain restore gives back — nothing of the serving stack in between
    info: dict = {}
    model, state = load_state(cfg, workdir, tag="smoke-reference", info=info)
    assert (info["step"], info["digest"]) == (trained_step, trained_digest), \
        info
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    reference = np.asarray(jax.jit(
        lambda v, x: model.apply(v, serve_normalize(x, "imagenet"),
                                 train=False).astype(np.float32)
    )(variables, images))
    assert np.isfinite(reference).all()
    common = dict(trained_step=trained_step, trained_digest=trained_digest,
                  log=log)
    # tolerances, as a share of the logit range, from the dtype: the
    # float32 server computes in the config's bfloat16 like the reference
    # and only its batch shapes differ — a few bf16 ulps (2^-8) through
    # 50 layers; int8 rounds every weight and the input to 1/127 of its
    # range on top of that.  First chip run (PR 21): 0.30% and 0.08%.
    n8 = 2 * len(jax.local_devices()) + sz.int8_bucket
    return [
        serve_once(workdir, sz, "float32", None, images, reference, 0.02,
                   **common),
        serve_once(workdir, sz, "int8", str(sz.int8_bucket), images[:n8],
                   reference[:n8], 0.05, **common)]


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rehearse", action="store_true",
                   help="run the same phases at a tiny size on the CPU "
                        "with the kernels interpreted; proves nothing "
                        "about the chip")
    args = p.parse_args(argv)
    if not __debug__:
        raise SystemExit("chip_smoke's checks are assert statements; "
                         "run it without -O")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    import jax

    backend = jax.default_backend()
    if not args.rehearse and backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend "
              f"'{backend}'); this is a chip check — use --rehearse for "
              f"the CPU rehearsal", file=sys.stderr)
        return 2
    sz = REHEARSE if args.rehearse else FULL
    on_tpu = backend == "tpu"

    from deep_vision_tpu.core.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # the program's own record of its compiles (obs/launch.py), which
    # enable_compile_cache has started
    from deep_vision_tpu.obs import launch

    log = launch.start().listen()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"device_kind='{dev.device_kind}' devices={device['count']} "
          f"compile_cache={cache_dir}", flush=True)
    if args.rehearse:
        print(f"platform: {dev.platform} — rehearsal, not a chip pass",
              flush=True)
    entries_before = cache_entries(cache_dir)
    report_path = REPORT.format("_rehearsal" if args.rehearse else "")
    previous = {}
    if os.path.isfile(report_path):
        with open(report_path) as f:
            previous = json.load(f)
    t_start = time.monotonic()
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        kernels = phase_kernels(sz, interpret=not on_tpu,
                                n_devices=device["count"])
        recs = pack_records(tmp, sz)
        workdir = os.path.join(tmp, "run")
        train, trained_digest = phase_train(sz, recs, workdir, log)
        serve = phase_serve(sz, workdir, train["steps"], trained_digest,
                            log)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    children = multiprocessing.active_children()
    assert not children, f"processes left running: {children}"

    report = {"ok": True, "rehearsal": args.rehearse, "device": device,
              "versions": versions(),
              "compile_cache": {"dir": cache_dir,
                                "entries_before": entries_before,
                                "entries_after": cache_entries(cache_dir),
                                "hits": log.hits, "misses": log.misses},
              "seconds": round(time.monotonic() - t_start, 1),
              "kernels": kernels, "train": train, "serve": serve}
    # -- 5. cache: this run's compile seconds beside the previous run's
    rows = [("train step", train["compile"]["train_step"]),
            ("eval step", train["compile"]["eval_step"])]
    for s in serve:
        secs = s["compile"]["buckets"]["seconds"]
        for i, b in enumerate(s["buckets"]):
            # warmup compiles replica by replica, bucket by bucket: every
            # len(buckets)-th program is this bucket on the next replica
            rows.append((f"{s['infer_dtype']} bucket {b}", {"total_s": round(
                sum(secs[i::len(s["buckets"])]), 2)}))
    report["compile_seconds"] = {k: v["total_s"] for k, v in rows}
    # a run on other devices compiled other programs: nothing to compare
    prev = previous.get("compile_seconds", {}) \
        if previous.get("device") == device else {}
    print(f"[cache] {cache_dir}: {entries_before} entries before, "
          f"{report['compile_cache']['entries_after']} after; "
          f"{log.hits} hits, {log.misses} misses this run", flush=True)
    for k, v in report["compile_seconds"].items():
        print(f"[cache] compile {k:20s} {v:8.2f}s"
              + (f"   previous run {prev[k]:8.2f}s" if k in prev else ""),
              flush=True)
    os.makedirs(os.path.dirname(report_path), exist_ok=True)
    with open(report_path, "w") as f:
        json.dump(report, f, indent=1)
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": device,
                      **({"rehearsal": True} if args.rehearse else {})}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
