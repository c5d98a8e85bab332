"""Serving CLI — boot the dynamic-batching inference engine over HTTP.

    # serve a trained workdir (best checkpoint, EMA weights if trained)
    python -m deep_vision_tpu.cli.serve -m resnet50 --workdir runs/r50

    # serve a StableHLO export (cli.infer export artifact)
    python -m deep_vision_tpu.cli.serve -m resnet50 --workdir runs/r50 \\
        --stablehlo model.stablehlo

    # tuning: batch buckets, drain window, queue bound
    python -m deep_vision_tpu.cli.serve -m yolov3_voc --workdir runs/y \\
        --max-batch 16 --max-wait-ms 8 --max-queue 512 --warmup

    # wire + compute dtype: clients ship raw uint8 pixels by default
    # (normalization runs on device); bf16 halves the compute footprint
    python -m deep_vision_tpu.cli.serve -m resnet50 --workdir runs/r50 \\
        --infer-dtype bfloat16
    python -m deep_vision_tpu.cli.serve -m resnet50 --workdir runs/r50 \\
        --wire-dtype float32   # the pre-uint8 host-normalized contract

    # chaos: boot with a deterministic fault spec (docs/SERVING.md)
    python -m deep_vision_tpu.cli.serve -m lenet5 --workdir runs/l \\
        --faults 'compute:exception:times=1' --fault-seed 0

    # multi-device: one engine replica per chip behind one queue, or
    # shard each padded batch across all chips (docs/SERVING.md)
    python -m deep_vision_tpu.cli.serve -m resnet50 --workdir runs/r50 \\
        --serve-devices 0
    python -m deep_vision_tpu.cli.serve -m resnet50 --workdir runs/r50 \\
        --shard-batches --max-batch 256

    # multi-model: serve the zoo behind one process with the model
    # control plane — per-model workdir subdirs, an HBM weight-cache
    # budget, and hot-reload/canary lifecycle endpoints
    # (docs/SERVING.md "Model lifecycle & weight cache")
    python -m deep_vision_tpu.cli.serve --models lenet5,yolov3_toy \\
        --workdir runs --hbm-budget-mb 512 --canary-frac 0.1

    # offline batch tier: POST bulk job manifests to /v1/jobs; shards
    # drain through the same engines strictly below interactive
    # traffic and checkpoint to JSONL so a restarted server resumes
    # mid-job (docs/BATCH.md)
    python -m deep_vision_tpu.cli.serve -m resnet50 --workdir runs/r50 \\
        --jobs-dir runs/r50/jobs

    # continuous deploy: watch each model's workdir for new
    # checkpoints, gate them on held-out data, roll out through
    # shadow/canary, and autoscale replicas with demand
    # (docs/DEPLOY.md)
    python -m deep_vision_tpu.cli.serve --models lenet5 --workdir runs \\
        --watch --gate-dir data/holdout --min-replicas 1 \\
        --max-replicas 4

Knobs and architecture: docs/SERVING.md.  Smoke: ``make serve-smoke``;
chaos suite: ``make serve-chaos``; deploy loop: ``make deploy-smoke``.
"""

from __future__ import annotations

import argparse


def _edge_kwargs(args):
    """Shared ServeServer edge wiring for both build paths.

    The selector event loop is the default front-end; --thread-server
    restores the thread-per-request baseline.  The response cache and tenant QoS stay OFF unless
    asked for, so single-purpose smokes keep their exact span/counter
    expectations."""
    from deep_vision_tpu.serve.admission import TenantQoS
    from deep_vision_tpu.serve.cache import ResponseCache

    cache_mb = float(getattr(args, "response_cache_mb", 0.0) or 0.0)
    qos_spec = getattr(args, "qos", None)
    return dict(
        edge=not getattr(args, "thread_server", False),
        max_connections=int(getattr(args, "max_connections", 1024)),
        http_workers=int(getattr(args, "http_workers", 8)),
        response_cache=ResponseCache(int(cache_mb * 2**20))
        if cache_mb > 0 else None,
        qos=TenantQoS.parse(qos_spec) if qos_spec else None)


def _batch_tier(args, resolve):
    """``--jobs-dir`` → (JobStore, started BatchScheduler) or
    (None, None).

    ``resolve(model_name) -> (model, engine)`` is the routing closure
    each build path supplies (engines dict or control plane); the
    scheduler fails a job terminally when it raises KeyError.  The
    shard size defaults to the engine's max batch — one shard is one
    full cohort, the unit the trough check reasons about
    (docs/BATCH.md)."""
    jobs_dir = getattr(args, "jobs_dir", None)
    if jobs_dir is None:
        return None, None
    from deep_vision_tpu.serve.batch_sched import BatchScheduler
    from deep_vision_tpu.serve.jobs import JobStore

    shard = int(getattr(args, "batch_shard_size", 0) or 0) \
        or int(args.max_batch)
    store = JobStore(jobs_dir or None, shard_size=shard,
                     max_cached_shards=int(
                         getattr(args, "batch_cache_shards", 64) or 0))
    sched = BatchScheduler(
        store, resolve,
        interval_s=float(getattr(args, "batch_interval_ms", 20.0) or
                         20.0) / 1e3,
        max_interactive_depth=int(getattr(args, "batch_max_depth", 0)
                                  or 0),
        pressure_high_ms=float(getattr(args, "batch_pressure_ms", 10.0)
                               or 10.0))
    sched.start()
    return store, sched


def _brownout(args, engines_provider):
    """``--brownout`` → started BrownoutController or None.

    ``engines_provider`` is the zero-arg callable the controller polls
    each tick (engines dict values or the plane's active engines), so a
    hot reload swaps the observed engine automatically.  The controller
    is wired into every optional-work producer by the caller — the
    ladder itself only reads signals and steps a level."""
    if not getattr(args, "brownout", False):
        return None
    from deep_vision_tpu.serve.brownout import BrownoutController

    bc = BrownoutController(
        engines_provider,
        interval_s=float(getattr(args, "brownout_interval_ms", 250.0)
                         or 250.0) / 1e3,
        l1_pressure_ms=float(getattr(args, "brownout_l1_ms", 50.0)),
        l2_pressure_ms=float(getattr(args, "brownout_l2_ms", 150.0)),
        l3_pressure_ms=float(getattr(args, "brownout_l3_ms", 400.0)),
        occupancy_high=float(getattr(args, "brownout_occupancy", 0.97)),
        shed_rate_high=float(getattr(args, "brownout_shed_rate", 0.10)),
        up_window=int(getattr(args, "brownout_up_window", 2)),
        down_window=int(getattr(args, "brownout_down_window", 8)),
        cooldown_s=float(getattr(args, "brownout_cooldown_s", 2.0)))
    force = int(getattr(args, "brownout_force", -1)
                if getattr(args, "brownout_force", -1) is not None
                else -1)
    if force >= 0:
        bc.force(force)
    bc.start()
    return bc


def _parse_mesh_arg(spec: str) -> tuple[int, int]:
    """``--mesh D,M`` (data,model) → (D, M); a single value N means
    N,1 — pure batch sharding, same as --shard-batches over N."""
    parts = [s.strip() for s in str(spec).split(",") if s.strip()]
    try:
        sizes = [int(s) for s in parts]
    except ValueError:
        sizes = []
    if len(sizes) == 1:
        sizes.append(1)
    if len(sizes) != 2 or any(n < 1 for n in sizes):
        raise ValueError(
            f"--mesh '{spec}': expected 'data,model' positive axis "
            "sizes (e.g. '2,2', '4,1', '1,4')")
    return sizes[0], sizes[1]


def _detect_knobs(args) -> dict:
    """The ``--detect-*`` flags as registry.load_checkpoint kwargs —
    getattr'd so programmatic Namespace callers (smokes, tests) that
    predate the knobs keep the device-decode defaults."""
    return dict(
        detect_decode=str(getattr(args, "detect_decode", "device")),
        detect_topk=int(getattr(args, "detect_topk", 100) or 100),
        detect_score_threshold=float(
            getattr(args, "detect_score_threshold", 0.05)),
        detect_iou_threshold=float(
            getattr(args, "detect_iou_threshold", 0.5)),
        detect_soft_nms=str(getattr(args, "detect_soft_nms", "off")
                            or "off"),
        detect_soft_sigma=float(
            getattr(args, "detect_soft_sigma", 0.5)),
        detect_max_per_class=int(
            getattr(args, "detect_max_per_class", 0) or 0))


def build_server(args):
    """argparse namespace → (engine, ServeServer); shared with the smoke
    test so `make serve-smoke` boots exactly the production wiring.

    Device scaling (docs/SERVING.md "Multi-device serving"):
    ``--serve-devices N`` replicates the engine over the first N local
    devices behind one queue (N=0 → all local devices; default 1 keeps
    the single-engine path byte-for-byte); ``--shard-batches`` instead
    builds ONE engine whose padded batches span the data axis of a mesh
    over those devices (mutually exclusive by construction — replication
    parallelizes many small batches, sharding one large batch);
    ``--mesh D,M`` generalizes to a 2-D data×model mesh — batches split
    D ways while the partition rules (``--partition-rules``) lay the
    params over the M-chip model axis (docs/SERVING.md "2-D mesh
    serving")."""
    from deep_vision_tpu.obs.trace import Tracer
    from deep_vision_tpu.serve.admission import AdmissionController
    from deep_vision_tpu.serve.engine import BatchingEngine, sharded_buckets
    from deep_vision_tpu.serve.faults import FaultPlane
    from deep_vision_tpu.serve.http import ServeServer
    from deep_vision_tpu.serve.registry import ModelRegistry
    from deep_vision_tpu.serve.replicas import ReplicatedEngine, local_devices

    registry = ModelRegistry()
    # uint8 is the production serving wire (4× smaller H2D payloads,
    # normalization fused into the bucket programs); the registry's
    # programmatic default stays float32 so direct callers keep the old
    # host-normalized contract (docs/SERVING.md "Wire format")
    wire_dtype = getattr(args, "wire_dtype", "uint8") or "uint8"
    infer_dtype = getattr(args, "infer_dtype", "float32") or "float32"
    models_arg = getattr(args, "models", None)
    if models_arg:
        if args.stablehlo:
            raise ValueError("--stablehlo serves one exported blob; "
                             "multi-model serving (--models) is "
                             "checkpoint-path only")
        return _build_plane_server(args, registry, wire_dtype,
                                   infer_dtype)
    if getattr(args, "watch", False) \
            or int(getattr(args, "max_replicas", 0) or 0):
        raise ValueError("--watch / --max-replicas need the model "
                         "control plane (--models ...): the deploy "
                         "pipeline rolls candidates through its "
                         "version table")
    calib_batches = int(getattr(args, "calib_batches", 2) or 2)
    calib_dir = getattr(args, "calib_dir", None)
    if args.stablehlo:
        # blobs were traced at float32 with host-side normalization —
        # the wire knob doesn't apply (describe() shows the real wire);
        # a non-f32 --infer-dtype is rejected by the registry with the
        # single "f32-wire/f32-compute only" error
        wire_dtype = "float32"
        sm = registry.load_exported(args.model, args.stablehlo,
                                    args.workdir,
                                    infer_dtype=infer_dtype)
    else:
        sm = registry.load_checkpoint(args.model, args.workdir,
                                      wire_dtype=wire_dtype,
                                      infer_dtype=infer_dtype,
                                      calib_batches=calib_batches,
                                      calib_dir=calib_dir,
                                      **_detect_knobs(args))
    buckets = [int(b) for b in args.buckets.split(",")] if args.buckets \
        else None
    fault_spec = getattr(args, "faults", None)
    faults = FaultPlane(fault_spec, getattr(args, "fault_seed", 0)) \
        if fault_spec else None  # None → engine reads DVT_SERVE_FAULTS
    serve_devices = int(getattr(args, "serve_devices", 1))
    shard_batches = bool(getattr(args, "shard_batches", False))
    mesh_arg = getattr(args, "mesh", None)
    if mesh_arg and shard_batches:
        raise ValueError("--mesh subsumes --shard-batches (a D×1 mesh "
                         "IS batch sharding); pass one")
    if mesh_arg:
        n_data, n_model = _parse_mesh_arg(mesh_arg)
        try:
            devices = local_devices(n_data * n_model)
        except ValueError:
            # re-raise under the flag the operator actually typed
            import jax

            raise ValueError(
                f"--mesh {n_data},{n_model} needs "
                f"{n_data * n_model} device(s); only "
                f"{len(jax.local_devices())} local device(s) present "
                f"— shrink an axis or add hosts") from None
    elif shard_batches:
        # shard over N devices (0/1 → every local device)
        devices = local_devices(serve_devices if serve_devices > 1
                                else None)
    elif serve_devices != 1:
        # replicate over N devices (0 → every local device)
        devices = local_devices(serve_devices or None)
    else:
        devices = None  # the PR 1–3 single-engine path, untouched
    tracer = Tracer(ring=getattr(args, "trace_ring", 256),
                    slow_ms=getattr(args, "slow_trace_ms", 250.0),
                    enabled=not getattr(args, "no_trace", False))
    engine_kwargs = dict(
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        buckets=buckets,
        tracer=tracer,
        pipeline_depth=getattr(args, "pipeline_depth", 2),
        faults=faults,
        watchdog_interval_s=getattr(args, "watchdog_interval_ms", 50.0)
        / 1e3,
        restart_budget=getattr(args, "restart_budget", 3),
        exec_timeout_k=getattr(args, "exec_timeout_k", 10.0),
        exec_timeout_min_s=getattr(args, "exec_timeout_min_s", 2.0),
        retry_budget=getattr(args, "retry_budget", 16),
        degraded_after=getattr(args, "degraded_after", 1),
        dead_after=getattr(args, "dead_after", 5),
        # per-workload SLO class (serve/workloads.py): the operator's
        # --max-queue capped by the model's workload — generative
        # batches hold the device longer, so their class bounds the
        # queue tighter (shed early, not after stacked deadline misses)
        admission=AdmissionController(
            max_queue=sm.workload.slo.bound_queue(args.max_queue),
            max_wait_ms=args.max_wait_ms))
    if mesh_arg:
        # 2-D data×model serving: batches split over ``data``, params
        # laid out over ``model`` by the partition rules — buckets key
        # off the DATA-axis size only (docs/SERVING.md "2-D mesh
        # serving")
        from deep_vision_tpu.parallel.mesh import make_mesh
        from deep_vision_tpu.parallel.partition import (
            parse_partition_rules,
        )

        mesh = make_mesh({"data": n_data, "model": n_model},
                         devices=devices)
        rules_arg = getattr(args, "partition_rules", None)
        rules = parse_partition_rules(rules_arg) if rules_arg else None
        if engine_kwargs["buckets"] is None:
            engine_kwargs["buckets"] = sharded_buckets(
                args.max_batch, n_data)
        engine = BatchingEngine(
            sm.for_mesh(mesh, partition_rules=rules,
                        strict=bool(getattr(args, "partition_strict",
                                            False)),
                        min_shard_dim=int(getattr(
                            args, "partition_min_dim", 1024) or 1024)),
            **engine_kwargs)
    elif shard_batches:
        from deep_vision_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"data": len(devices)}, devices=devices)
        if engine_kwargs["buckets"] is None:
            engine_kwargs["buckets"] = sharded_buckets(
                args.max_batch, len(devices))
        engine = BatchingEngine(sm.for_mesh(mesh), **engine_kwargs)
    elif devices is not None and len(devices) > 1:
        engine = ReplicatedEngine(sm, devices=devices, **engine_kwargs)
    else:
        engine = BatchingEngine(sm, **engine_kwargs)
    engine.start()
    if args.warmup:
        print(f"[serve] warming {engine.buckets} ...")
        engine.warmup()
    socket_timeout_s = getattr(args, "socket_timeout_s", 30.0)
    engines = {sm.name: engine}

    def resolve(name, _engines=engines):
        eng = _engines[name]  # KeyError → job fails terminally
        return registry.get(name), eng

    jobs, batch_sched = _batch_tier(args, resolve)
    brownout = _brownout(args, lambda: engines.values())
    if brownout is not None:
        if batch_sched is not None:
            batch_sched.brownout = brownout  # L1+: freeze the batch tier
        # L1+: stop paying for slow-trace serialization under overload
        tracer.suppress_slow = lambda: brownout.at_least(1)
    server = ServeServer(
        registry, engines, host=args.host, port=args.port,
        verbose=args.verbose,
        max_body_bytes=int(getattr(args, "max_body_mb", 32) * 2**20),
        socket_timeout_s=socket_timeout_s if socket_timeout_s > 0
        else None,
        tracer=tracer, jobs=jobs, batch_sched=batch_sched,
        brownout=brownout,
        **_edge_kwargs(args))
    return engine, server


def _build_plane_server(args, registry, wire_dtype: str,
                        infer_dtype: str):
    """``--models a,b,c`` → (ModelControlPlane, ServeServer).

    Per-model checkpoints restore from ``<workdir>/<name>`` subdirs
    (the multi-model workdir layout); every model's engine is built by
    one shared factory so hot-reloaded versions boot the same wiring as
    the originals.  The returned plane exposes the engine surface
    ``main()`` prints and stops through (``model``/``buckets``/
    ``faults``/``stop``)."""
    import os

    from deep_vision_tpu.obs.trace import Tracer
    from deep_vision_tpu.serve.admission import AdmissionController
    from deep_vision_tpu.serve.engine import BatchingEngine
    from deep_vision_tpu.serve.faults import FaultPlane
    from deep_vision_tpu.serve.http import ServeServer
    from deep_vision_tpu.serve.models import (
        CanaryPolicy,
        ModelControlPlane,
        WeightCache,
    )
    from deep_vision_tpu.serve.replicas import (
        ReplicatedEngine,
        local_devices,
    )

    names = [s.strip() for s in args.models.split(",") if s.strip()]
    if not names:
        raise ValueError("--models needs at least one config name")
    cascade_spec = None
    if getattr(args, "cascade", None):
        from deep_vision_tpu.serve.cascade import CascadeSpec

        cascade_spec = CascadeSpec.parse(
            args.cascade,
            min_agreement=float(getattr(args, "cascade_min_agreement",
                                        0.98)),
            sample_period=int(getattr(args, "cascade_sample_period",
                                      10)),
            min_sample=int(getattr(args, "cascade_min_sample", 200)),
            topk=int(getattr(args, "cascade_topk", 5)),
            per_class=bool(getattr(args, "cascade_per_class", False)),
            class_min_sample=int(getattr(args,
                                         "cascade_class_min_sample",
                                         50)))
        for tier in cascade_spec.tiers:
            if tier not in names:
                raise ValueError(
                    f"--cascade tier '{tier}' is not served; --models "
                    f"must include every cascade tier (got {names})")
        # every tier must speak the SAME verb (the chain escalates one
        # request through all of them), and the verb needs a
        # CascadeWorkloadRule (classify/detect today) — checked here,
        # before any checkpoint restore
        from deep_vision_tpu.core.config import get_config
        from deep_vision_tpu.serve.workloads import workload_for_task

        tier_verbs = {t: workload_for_task(get_config(t).task).verb
                      for t in cascade_spec.tiers}
        if len(set(tier_verbs.values())) > 1:
            raise ValueError(
                f"--cascade tiers must share one workload verb, got "
                f"{tier_verbs}")
        verb = tier_verbs[cascade_spec.big]
        if workload_for_task(
                get_config(cascade_spec.big).task).cascade_rule() \
                is None:
            raise ValueError(
                f"--cascade: the '{verb}' workload has no cascade "
                f"rule (classify and detect cascade today)")
    buckets = [int(b) for b in args.buckets.split(",")] if args.buckets \
        else None
    fault_spec = getattr(args, "faults", None)
    faults = FaultPlane(fault_spec, getattr(args, "fault_seed", 0)) \
        if fault_spec else None
    serve_devices = int(getattr(args, "serve_devices", 1))
    if getattr(args, "shard_batches", False):
        raise ValueError("--shard-batches is single-model only; "
                         "--models replicates per engine instead "
                         "(--serve-devices N)")
    if getattr(args, "mesh", None):
        raise ValueError("--mesh is single-model only; --models "
                         "replicates per engine instead "
                         "(--serve-devices N)")
    min_replicas = int(getattr(args, "min_replicas", 0) or 0)
    max_replicas = int(getattr(args, "max_replicas", 0) or 0)
    if max_replicas and not min_replicas:
        min_replicas = 1
    if max_replicas and max_replicas < min_replicas:
        raise ValueError(f"--max-replicas {max_replicas} < "
                         f"--min-replicas {min_replicas}")
    if min_replicas:
        if serve_devices != 1:
            raise ValueError("--min-replicas and --serve-devices both "
                             "set the replica floor; use one")
        # the autoscaler needs the elastic engine even at one replica
        devices = local_devices(min_replicas)
    else:
        devices = local_devices(serve_devices or None) \
            if serve_devices != 1 else None
    replicated = devices is not None and (len(devices) > 1
                                          or max_replicas > 1)
    tracer = Tracer(ring=getattr(args, "trace_ring", 256),
                    slow_ms=getattr(args, "slow_trace_ms", 250.0),
                    enabled=not getattr(args, "no_trace", False))
    engine_kwargs = dict(
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        buckets=buckets, tracer=tracer,
        pipeline_depth=getattr(args, "pipeline_depth", 2),
        faults=faults,
        watchdog_interval_s=getattr(args, "watchdog_interval_ms", 50.0)
        / 1e3,
        restart_budget=getattr(args, "restart_budget", 3),
        exec_timeout_k=getattr(args, "exec_timeout_k", 10.0),
        exec_timeout_min_s=getattr(args, "exec_timeout_min_s", 2.0),
        retry_budget=getattr(args, "retry_budget", 16),
        degraded_after=getattr(args, "degraded_after", 1),
        dead_after=getattr(args, "dead_after", 5))

    # one admission controller per model NAME, shared across its
    # versions: the per-bucket exec EWMAs and queue accounting survive a
    # hot reload instead of resetting with each new engine
    admissions: dict = {}

    def admission_for(name: str) -> AdmissionController:
        adm = admissions.get(name)
        if adm is None:
            # the model's workload SLO class caps the queue bound
            # (serve/workloads.py); registry lookup can only miss for
            # engines built outside the plane's deploy path — keep the
            # operator's bound there
            try:
                max_queue = registry.get(name).workload.slo.bound_queue(
                    args.max_queue)
            except (KeyError, AttributeError):
                max_queue = args.max_queue
            adm = admissions[name] = AdmissionController(
                max_queue=max_queue,
                max_wait_ms=args.max_wait_ms, name=name)
        return adm

    def engine_factory(model):
        kwargs = dict(engine_kwargs,
                      admission=admission_for(model.name))
        if replicated:
            return ReplicatedEngine(model, devices=devices, **kwargs)
        return BatchingEngine(model, **kwargs)

    cache = WeightCache(
        int(float(getattr(args, "hbm_budget_mb", 0) or 0) * 2**20))
    policy = CanaryPolicy(
        canary_frac=float(getattr(args, "canary_frac", 0.1)),
        min_requests=int(getattr(args, "canary_min_requests", 20)),
        max_error_rate=float(getattr(args, "canary_max_error_rate",
                                     0.0)),
        max_p99_ratio=float(getattr(args, "canary_max_p99_ratio", 3.0)),
        shadow_frac=float(getattr(args, "shadow_frac", 0.0)),
        phase_timeout_s=float(getattr(args, "phase_timeout_s", 30.0)))
    plane = ModelControlPlane(registry, engine_factory, cache=cache,
                              policy=policy,
                              admission_factory=admission_for)
    for name in names:
        workdir = os.path.join(args.workdir, name)
        # every NON-FINAL cascade tier fuses the (top1_idx, top1_prob)
        # confidence epilogue into its bucket programs (classify; the
        # detect decode epilogue already carries the signal); the big
        # tier keeps its plain outputs so escalated answers are
        # bit-identical to big-only serving (serve/cascade.py)
        front_k = cascade_spec.topk if cascade_spec is not None \
            and name in cascade_spec.tiers \
            and name != cascade_spec.big else 0
        tier_infer = infer_dtype
        tier_calib = getattr(args, "calib_dir", None)
        if cascade_spec is not None \
                and getattr(args, "cascade_quant_front", False) \
                and name == cascade_spec.front:
            # --cascade-quant-front: tier 0 serves int8-resident
            # weights, PTQ-calibrated at boot on the same held-out
            # directory the accuracy gate uses (synthetic when neither
            # is given).  The other tiers keep --infer-dtype.
            tier_infer = "int8"
            tier_calib = tier_calib or getattr(args, "gate_dir", None)
        sm = registry.load_checkpoint(
            name, workdir, wire_dtype=wire_dtype,
            infer_dtype=tier_infer,
            calib_batches=int(getattr(args, "calib_batches", 2) or 2),
            calib_dir=tier_calib,
            cascade_topk=front_k,
            **_detect_knobs(args))
        plane.deploy(sm, workdir=workdir)
    cascade = None
    if cascade_spec is not None:
        from deep_vision_tpu.serve.cascade import CascadeRouter

        # built AFTER the boot deploys: the router's version listener
        # only needs to see RELOADS (boot state is uncalibrated anyway).
        # The ledger root gives calibration restart durability — a
        # rebooted server reloads its threshold instead of failing
        # closed to all-big for another min_sample requests
        cascade = CascadeRouter(plane, cascade_spec,
                                root=os.path.join(args.workdir,
                                                  "_cascade"))
    if args.warmup:
        for name, eng in plane.active_engines().items():
            print(f"[serve] warming {name} {eng.buckets} ...")
        plane.warmup()

    # deploy pipeline (deploy/__init__.py, docs/DEPLOY.md): the ledger
    # always rides along with a watcher or autoscaler; --watch adds the
    # per-model checkpoint watcher + accuracy gate, --max-replicas adds
    # one autoscaler per (elastic) engine
    pipeline = None
    if getattr(args, "watch", False) or max_replicas > min_replicas:
        from deep_vision_tpu.deploy import (
            AccuracyGate,
            CheckpointWatcher,
            DeploymentHistory,
            DeployPipeline,
            ReplicaAutoscaler,
        )

        history = DeploymentHistory(os.path.join(args.workdir,
                                                 "_deploy"))
        watcher = None
        if getattr(args, "watch", False):
            gate = AccuracyGate(
                gate_dir=getattr(args, "gate_dir", None),
                min_agreement=float(getattr(args, "gate_min_agreement",
                                            0.8)))
            watcher = CheckpointWatcher(
                plane, history,
                interval_s=float(getattr(args, "watch_interval_s",
                                         2.0)),
                gate=gate)
            for name in names:
                watcher.watch(name)
        autoscalers = {}
        if max_replicas > min_replicas:
            for name in names:
                # resolve the engine per tick: a hot reload swaps the
                # active engine and the scaler must follow it
                autoscalers[name] = ReplicaAutoscaler(
                    lambda name=name: plane.active_engine(name),
                    name=name, min_replicas=min_replicas or 1,
                    max_replicas=max_replicas, history=history)
        pipeline = DeployPipeline(plane, history=history,
                                  watcher=watcher,
                                  autoscalers=autoscalers or None)
        pipeline.start()
    socket_timeout_s = getattr(args, "socket_timeout_s", 30.0)

    def resolve(name):
        # per-shard re-resolution: a hot reload swaps the active
        # engine and the NEXT shard follows it (KeyError → job fails)
        model = plane.resolve(name)
        return model, plane.active_engine(model.name)

    jobs, batch_sched = _batch_tier(args, resolve)
    brownout = _brownout(
        args, lambda: plane.active_engines().values())
    if brownout is not None:
        plane.brownout = brownout    # L1+: pause shadow duplication
        if cascade is not None:
            cascade.brownout = brownout  # L1 sample pause, L2 degrade
        if batch_sched is not None:
            batch_sched.brownout = brownout  # L1+: freeze the batch tier
        tracer.suppress_slow = lambda: brownout.at_least(1)
    server = ServeServer(
        registry, plane.active_engines(), host=args.host,
        port=args.port, verbose=args.verbose,
        max_body_bytes=int(getattr(args, "max_body_mb", 32) * 2**20),
        socket_timeout_s=socket_timeout_s if socket_timeout_s > 0
        else None,
        tracer=tracer, plane=plane, deploy=pipeline,
        jobs=jobs, batch_sched=batch_sched, cascade=cascade,
        brownout=brownout,
        **_edge_kwargs(args))
    return plane, server


def main(argv=None):
    p = argparse.ArgumentParser(
        description="deep_vision_tpu dynamic-batching inference server")
    p.add_argument("-m", "--model", default=None,
                   help="config name (see cli.train --list); required "
                        "unless --models boots the multi-model plane")
    p.add_argument("--models", default=None,
                   help="comma-separated config names: serve several "
                        "models behind one process via the model "
                        "control plane (versioned table, weight cache, "
                        "hot reload; docs/SERVING.md).  Checkpoints "
                        "restore from <workdir>/<name> subdirs")
    p.add_argument("--workdir", required=True,
                   help="training workdir (checkpoint restore; also "
                        "supplies variables for --stablehlo)")
    p.add_argument("--stablehlo", default=None,
                   help="serve this exported blob instead of re-jitting "
                        "the checkpoint (fixed batch = export batch)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000,
                   help="0 = pick a free port")
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--max-wait-ms", type=float, default=5.0,
                   help="batch drain window: latency floor under load, "
                        "batching opportunity at low load")
    p.add_argument("--buckets", default=None,
                   help="comma-separated batch buckets (default: powers "
                        "of two up to --max-batch)")
    p.add_argument("--max-queue", type=int, default=256,
                   help="admission bound; beyond this requests shed 429")
    p.add_argument("--pipeline-depth", type=int, default=2,
                   help="dispatched-but-undrained batch window: 1 = "
                        "synchronous, 2 = overlap batch N+1 formation/"
                        "H2D with batch N compute (docs/SERVING.md)")
    p.add_argument("--wire-dtype", choices=("uint8", "float32"),
                   default="uint8",
                   help="client wire format: uint8 = raw 0-255 pixels, "
                        "normalization runs on device inside the bucket "
                        "programs (4x smaller H2D; the default); "
                        "float32 = host-preprocessed floats (the "
                        "pre-uint8 contract).  StableHLO blobs always "
                        "serve their exported float32 signature")
    p.add_argument("--infer-dtype",
                   choices=("float32", "bfloat16", "int8"),
                   default="float32",
                   help="on-device compute dtype: bfloat16 casts params "
                        "once at load and runs bucket programs in bf16 "
                        "with float32 outputs (docs/SERVING.md bf16 "
                        "caveats); int8 post-training-quantizes weights "
                        "at load (per-channel scales, calibrated "
                        "activation scale, fused Pallas ingest, f32 "
                        "outputs — docs/SERVING.md int8 section); "
                        "checkpoint path only")
    p.add_argument("--calib-batches", type=int, default=2,
                   help="int8 calibration: batches run through the "
                        "instrumented forward to collect activation "
                        "absmax ranges (--infer-dtype int8 only)")
    p.add_argument("--calib-dir", default=None,
                   help="int8 calibration: directory of held-out uint8 "
                        "*.npy images (HWC or NHWC); default = "
                        "deterministic synthetic batches — fine for "
                        "latency work, use real data before trusting "
                        "the accuracy gate (docs/SERVING.md)")
    p.add_argument("--serve-devices", type=int, default=1,
                   help="replicate the engine over this many local "
                        "devices behind one queue (0 = all; default 1 "
                        "= single-device engine); params are copied "
                        "per device once, batches route to the least-"
                        "loaded replica")
    p.add_argument("--shard-batches", action="store_true",
                   help="instead of replicating, shard each padded "
                        "batch across the data axis of a mesh over "
                        "--serve-devices devices (0/1 = all) — one "
                        "logical big batch uses every chip; buckets "
                        "become multiples of the device count")
    p.add_argument("--mesh", default=None,
                   help="2-D data×model serving mesh as 'D,M' axis "
                        "sizes (needs D×M local devices): batches "
                        "split D ways over data, params shard M ways "
                        "over model per --partition-rules; buckets "
                        "become multiples of D (subsumes "
                        "--shard-batches: 'N,1' is pure batch "
                        "sharding)")
    p.add_argument("--partition-rules", default=None,
                   help="how --mesh lays params over the model axis: "
                        "a built-in table name ('classifier', 'gan') "
                        "or ';'-separated regex=axes entries matched "
                        "against /-joined param paths, e.g. "
                        "'head/kernel=-,model;.*=' (default: shard "
                        "the first dim ≥1024 divisible by the model "
                        "axis, replicate the rest)")
    p.add_argument("--partition-strict", action="store_true",
                   help="every param leaf must match exactly one "
                        "--partition-rules entry (layout drift fails "
                        "at load, not silently at runtime)")
    p.add_argument("--partition-min-dim", type=int, default=1024,
                   help="fallback sharder only touches dims >= this "
                        "(small leaves replicate — sharding them "
                        "trades ICI latency for no HBM win); lower "
                        "it for small test models")
    p.add_argument("--warmup", action="store_true",
                   help="compile every bucket before accepting traffic")
    p.add_argument("--verbose", action="store_true",
                   help="per-request HTTP access logs")
    # -- fault tolerance (docs/SERVING.md "Failure model & operations") --
    p.add_argument("--faults", default=None,
                   help="deterministic fault-injection spec, e.g. "
                        "'compute:exception:times=1;d2h:latency:"
                        "delay_ms=20' (default: env DVT_SERVE_FAULTS; "
                        "empty = disabled)")
    p.add_argument("--fault-seed", type=int, default=0,
                   help="seed for probabilistic (p=) fault firing")
    p.add_argument("--watchdog-interval-ms", type=float, default=50.0,
                   help="supervision tick; 0 disables the watchdog "
                        "(thread restarts + exec-timeout fast-fail)")
    p.add_argument("--restart-budget", type=int, default=3,
                   help="watchdog thread restarts before the engine "
                        "goes sticky-DEAD (healthz 503)")
    p.add_argument("--exec-timeout-k", type=float, default=10.0,
                   help="a batch older than k × its bucket's exec EWMA "
                        "fast-fails the in-flight window")
    p.add_argument("--exec-timeout-min-s", type=float, default=2.0,
                   help="exec-timeout floor (also the pre-EWMA bound)")
    p.add_argument("--retry-budget", type=int, default=16,
                   help="bisect-retry executions per failed batch before "
                        "the remainder is quarantined")
    p.add_argument("--degraded-after", type=int, default=1,
                   help="consecutive batch failures before DEGRADED "
                        "(healthz 503)")
    p.add_argument("--dead-after", type=int, default=5,
                   help="consecutive batch failures before DEAD")
    # -- model control plane (docs/SERVING.md "Model lifecycle") --
    p.add_argument("--hbm-budget-mb", type=float, default=0.0,
                   help="device-memory byte budget for the weight "
                        "cache: least-recently-served models spill "
                        "their params to host RAM and re-admit on "
                        "demand (0 = unbounded; --models only)")
    p.add_argument("--canary-frac", type=float, default=0.1,
                   help="fraction of live traffic a reloading version "
                        "serves while in CANARY (deterministic every "
                        "1/frac-th request)")
    p.add_argument("--canary-min-requests", type=int, default=20,
                   help="canary answers required before the promote "
                        "gates are judged")
    p.add_argument("--canary-max-error-rate", type=float, default=0.0,
                   help="auto-rollback when the canary error rate "
                        "(failures, quarantines, NaN outputs) exceeds "
                        "this")
    p.add_argument("--canary-max-p99-ratio", type=float, default=3.0,
                   help="auto-rollback when canary p99 latency exceeds "
                        "this multiple of the active version's")
    p.add_argument("--shadow-frac", type=float, default=0.0,
                   help="before CANARY, duplicate this fraction of live "
                        "requests onto the candidate, compare top-1 "
                        "agreement, and DISCARD the outputs (0 skips "
                        "the shadow phase)")
    p.add_argument("--phase-timeout-s", type=float, default=30.0,
                   help="max seconds a shadow/canary phase may wait for "
                        "its request quota before rolling back")
    # -- continuous deploy pipeline (docs/DEPLOY.md) --
    p.add_argument("--watch", action="store_true",
                   help="watch each model's <workdir>/<name> for new "
                        "checkpoints (debounced across two polls so an "
                        "in-progress async save never half-deploys), "
                        "gate them on held-out data, and roll passing "
                        "candidates through shadow/canary/promote "
                        "automatically (--models only)")
    p.add_argument("--watch-interval-s", type=float, default=2.0,
                   help="checkpoint-fingerprint poll interval")
    p.add_argument("--gate-dir", default=None,
                   help="held-out eval set for the deploy accuracy "
                        "gate: uint8 *.npy images (HWC or NHWC) plus "
                        "an optional labels.txt (one int per image); "
                        "without labels the gate scores top-1 "
                        "AGREEMENT against the active version; default "
                        "= deterministic synthetic batches (NaN screen "
                        "+ agreement only)")
    p.add_argument("--gate-min-agreement", type=float, default=0.8,
                   help="label-free gate: minimum candidate-vs-active "
                        "top-1 agreement to deploy")
    p.add_argument("--min-replicas", type=int, default=0,
                   help="boot each model's engine with this many "
                        "per-device replicas — the autoscaler's floor "
                        "(0 = use --serve-devices; --models only)")
    p.add_argument("--max-replicas", type=int, default=0,
                   help="autoscale replicas up to this ceiling on "
                        "queue-pressure, back down to --min-replicas "
                        "when idle (0 disables autoscaling; --models "
                        "only)")
    p.add_argument("--drain-deadline", type=float, default=5.0,
                   help="shutdown grace: reject new submits immediately, "
                        "finish admitted work up to this many seconds")
    p.add_argument("--max-body-mb", type=float, default=32.0,
                   help="reject request bodies over this size with 413")
    p.add_argument("--socket-timeout-s", type=float, default=30.0,
                   help="per-connection socket timeout: a stalled "
                        "client (slow-loris) is closed / answered 408 "
                        "instead of pinning a handler thread; 0 "
                        "disables")
    # -- async edge (docs/SERVING.md "Async edge, response cache &
    #    tenant QoS") --
    p.add_argument("--thread-server", action="store_true",
                   help="serve with the original thread-per-request "
                        "ThreadingHTTPServer instead of the selector "
                        "event loop (the baseline: "
                        "no keep-alive pooling, no connection bound)")
    p.add_argument("--max-connections", type=int, default=1024,
                   help="edge loop: open-connection ceiling — at "
                        "capacity the oldest fully-idle keep-alive "
                        "connection is evicted, else accepting pauses "
                        "until a slot frees")
    p.add_argument("--http-workers", type=int, default=8,
                   help="edge loop: worker threads running handler "
                        "logic off the event loop")
    p.add_argument("--response-cache-mb", type=float, default=0.0,
                   help="content-addressed response cache budget: "
                        "identical payloads to the same model VERSION "
                        "(wire/infer dtype included in the key) answer "
                        "from memory; promote/rollback changes the "
                        "version digest so stale hits are impossible "
                        "(0 = off)")
    p.add_argument("--qos", default=None,
                   help="per-tenant QoS spec, e.g. 'premium:rate=0,"
                        "shed_at=1.0;standard:rate=200,burst=50,"
                        "shed_at=0.8,tenants=acme|globex;default="
                        "standard' — X-DVT-Tenant maps tenants to "
                        "classes with token-bucket quotas and "
                        "pressure-weighted shedding (docs/SERVING.md; "
                        "empty = off)")
    # -- confidence-routed cascade (docs/SERVING.md "Cascaded
    #    serving") --
    p.add_argument("--cascade", default=None,
                   help="'t0:t1:...:big' — route classify/detect "
                        "requests addressed to the BIG model through "
                        "the chain of cheaper tiers first, escalating "
                        "past each hop whose confidence falls below "
                        "that hop's threshold, calibrated from live "
                        "tier-vs-big dual-run samples; every name "
                        "must appear in --models and share one verb "
                        "(serve/cascade.py; an uncalibrated hop "
                        "escalates through — fully uncalibrated = "
                        "all-big)")
    p.add_argument("--cascade-min-agreement", type=float, default=0.98,
                   help="calibration target: smallest confidence "
                        "threshold whose measured front-vs-big top-1 "
                        "agreement (above it) still clears this")
    p.add_argument("--cascade-sample-period", type=int, default=10,
                   help="every N-th cascade request dual-runs BOTH "
                        "tiers to feed the agreement histogram (the "
                        "big answer is returned, so sampling costs no "
                        "correctness)")
    p.add_argument("--cascade-min-sample", type=int, default=200,
                   help="calibration samples required before any "
                        "traffic may stop at the front tier; below it "
                        "the cascade fails closed to all-big")
    p.add_argument("--cascade-topk", type=int, default=5,
                   help="entries in the cheap tiers' fused device-side "
                        "top-k confidence epilogue (bounds top_k in "
                        "cheap-tier-served responses)")
    p.add_argument("--cascade-quant-front", action="store_true",
                   help="serve tier 0 with int8-resident weights: PTQ "
                        "at boot (serve/quant.py) calibrated on "
                        "--calib-dir, falling back to the --gate-dir "
                        "holdout, then deterministic synthetic batches "
                        "— the cheapest front the stack can build "
                        "without retraining")
    p.add_argument("--cascade-per-class", action="store_true",
                   help="calibrate a per-CLASS threshold axis at every "
                        "hop: classes with enough of their own "
                        "dual-run sample get their own threshold, so "
                        "a class the cheap tier is systematically "
                        "wrong about escalates even at confidences "
                        "the pooled threshold would serve")
    p.add_argument("--cascade-class-min-sample", type=int, default=50,
                   help="dual-run samples a single class needs before "
                        "its own threshold activates (below it the "
                        "class uses the pooled threshold)")
    # -- detect decode (docs/SERVING.md "Workloads") --
    p.add_argument("--detect-decode", choices=("device", "host"),
                   default="device",
                   help="where detection models decode: 'device' "
                        "(default) fuses decode → score floor → top-k "
                        "→ class-wise NMS into the bucket programs so "
                        "D2H ships K fixed-size boxes per image (≥100× "
                        "fewer bytes than the dense pyramid at 416²); "
                        "'host' keeps the dense head outputs on the "
                        "wire and decodes per request (the pre-fusion "
                        "baseline)")
    p.add_argument("--detect-topk", type=int, default=100,
                   help="max detections per image in the fused detect "
                        "decode (the K of the fixed-size output and "
                        "the D2H bytes/image ≈ K·28)")
    p.add_argument("--detect-score-threshold", type=float, default=0.05,
                   help="compiled score FLOOR of the fused detect "
                        "decode — per-request 'score_threshold' values "
                        "above it trim host-side, values below it "
                        "clamp to it (sub-floor boxes never survived "
                        "NMS on device)")
    p.add_argument("--detect-iou-threshold", type=float, default=0.5,
                   help="IoU threshold of the fused class-wise NMS "
                        "(YOLO family; CenterNet's peak decode is "
                        "NMS-free)")
    p.add_argument("--detect-soft-nms", choices=("off", "gaussian",
                                                 "linear"),
                   default="off",
                   help="suppression rule of the fused NMS: 'off' "
                        "(default) is hard greedy NMS; 'gaussian' / "
                        "'linear' switch to Soft-NMS score decay "
                        "(Bodla et al. 2017) — overlapping boxes "
                        "survive with decayed scores instead of dying "
                        "at the IoU threshold")
    p.add_argument("--detect-soft-sigma", type=float, default=0.5,
                   help="gaussian Soft-NMS decay width "
                        "exp(-iou²/sigma); ignored for 'off'/'linear'")
    p.add_argument("--detect-max-per-class", type=int, default=0,
                   help="cap detections per class in the fused decode "
                        "output (0 = uncapped) — stops one dense class "
                        "from monopolizing the fixed K rows")
    # -- offline batch tier (docs/BATCH.md) --
    p.add_argument("--jobs-dir", default=None,
                   help="enable the offline batch-inference tier "
                        "(POST /v1/jobs) and checkpoint job progress "
                        "as append-only JSONL under this directory — "
                        "a restarted server resumes unfinished jobs "
                        "from their last durable shard ('' = enabled "
                        "but memory-only, no restart durability)")
    p.add_argument("--batch-shard-size", type=int, default=0,
                   help="images per batch job shard — the durability "
                        "AND scheduling unit (0 = --max-batch, one "
                        "engine cohort; the worst interference any "
                        "interactive request can see)")
    p.add_argument("--batch-interval-ms", type=float, default=20.0,
                   help="batch scheduler poll pacing while deferred "
                        "behind interactive load")
    p.add_argument("--batch-max-depth", type=int, default=0,
                   help="max interactive queue depth at which a batch "
                        "shard may still be submitted (default 0: any "
                        "waiting interactive request parks the batch "
                        "tier)")
    p.add_argument("--batch-pressure-ms", type=float, default=10.0,
                   help="interactive pressure ceiling (queue_depth x "
                        "exec EWMA, ms) for the trough check; above "
                        "it batch work defers")
    p.add_argument("--batch-cache-shards", type=int, default=64,
                   help="per-job completed-shard payloads kept in "
                        "memory; with --jobs-dir the rest spill to the "
                        "JSONL ledger (LRU) and GET /v1/jobs/<id>/"
                        "results streams them back from disk (0 = "
                        "unbounded; memory-only stores never evict)")
    # -- overload brownout (docs/SERVING.md "Overload & brownout") --
    p.add_argument("--brownout", action="store_true",
                   help="arm the brownout degradation ladder: a "
                        "per-process controller polls queue pressure / "
                        "engine occupancy / shed rate and steps "
                        "L0→L3 — L1 sheds optional work (cascade "
                        "sampling, shadow duplication, batch tier, "
                        "slow traces), L2 degrades quality (forced "
                        "front-tier answers, stale cache hits, marked "
                        "X-DVT-Degraded), L3 hard-sheds lower QoS "
                        "classes so premium tenants keep answering "
                        "(docs/SERVING.md runbook)")
    p.add_argument("--brownout-interval-ms", type=float, default=250.0,
                   help="ladder evaluation tick")
    p.add_argument("--brownout-l1-ms", type=float, default=50.0,
                   help="queue pressure (depth × exec EWMA, ms) that "
                        "votes for L1")
    p.add_argument("--brownout-l2-ms", type=float, default=150.0,
                   help="queue pressure that votes for L2")
    p.add_argument("--brownout-l3-ms", type=float, default=400.0,
                   help="queue pressure that votes for L3")
    p.add_argument("--brownout-occupancy", type=float, default=0.97,
                   help="engine occupancy above this votes ≥L1")
    p.add_argument("--brownout-shed-rate", type=float, default=0.10,
                   help="interval shed fraction above this votes ≥L2")
    p.add_argument("--brownout-up-window", type=int, default=2,
                   help="consecutive hot ticks before the ladder "
                        "ENGAGES (jumps straight to the target level)")
    p.add_argument("--brownout-down-window", type=int, default=8,
                   help="consecutive cool ticks before the ladder "
                        "releases ONE level (hysteresis: engage fast, "
                        "release slow)")
    p.add_argument("--brownout-cooldown-s", type=float, default=2.0,
                   help="minimum dwell after any transition before a "
                        "release may happen")
    p.add_argument("--brownout-force", type=int, default=-1,
                   help="pin the ladder at this level at boot (0..3; "
                        "-1 = signals in control; also settable live "
                        "via POST /v1/brownout {\"force\": N|null})")
    # -- observability (docs/OBSERVABILITY.md) --
    p.add_argument("--log-level", default="info",
                   choices=("debug", "info", "warning", "error"),
                   help="structured-log threshold for the dvt.serve.* "
                        "loggers (one JSON line per event on stderr)")
    p.add_argument("--trace-ring", type=int, default=256,
                   help="per-request spans kept in memory for "
                        "GET /v1/traces")
    p.add_argument("--slow-trace-ms", type=float, default=250.0,
                   help="requests slower than this emit their full span "
                        "as a slow_request log line; 0 disables")
    p.add_argument("--no-trace", action="store_true",
                   help="disable per-request span collection entirely "
                        "(tracing costs ~one dict per request; this "
                        "removes even that)")
    args = p.parse_args(argv)
    if not args.model and not args.models:
        p.error("one of -m/--model or --models is required")
    if args.cascade and not args.models:
        p.error("--cascade routes across the multi-model plane; use "
                "--models front,big")

    from deep_vision_tpu.core.compile_cache import enable_compile_cache
    from deep_vision_tpu.obs.log import configure_logging

    configure_logging(args.log_level)
    enable_compile_cache()
    engine, server = build_server(args)
    sm = engine.model
    served = args.models or args.model
    print(f"[serve] {served} listening on "
          f"http://{server.host}:{server.port} "
          f"(buckets={engine.buckets}, max_wait={args.max_wait_ms}ms, "
          f"max_queue={args.max_queue}, "
          f"pipeline_depth={engine.pipeline_depth}, "
          f"wire={sm.wire_dtype}, infer={sm.infer_dtype})")
    if args.models:
        budget = getattr(args, "hbm_budget_mb", 0.0)
        print(f"[serve] model control plane: {served} "
              f"(hbm_budget={budget or 'unbounded'}"
              f"{'MB' if budget else ''}, "
              f"canary_frac={args.canary_frac}, "
              f"shadow_frac={args.shadow_frac}) — reload: curl -XPOST "
              f"http://{server.host}:{server.port}"
              f"/v1/models/<name>/reload")
    cascade = getattr(server.httpd, "cascade", None)
    if cascade is not None:
        print(f"[serve] cascade: "
              f"{' -> '.join(cascade.spec.tiers)} — requests "
              f"for '{cascade.spec.big}' answer from the cheapest "
              f"tier whose calibrated confidence allows "
              f"(min_agreement={cascade.spec.min_agreement}, "
              f"sample_period={cascade.spec.sample_period}, "
              f"min_sample={cascade.spec.min_sample}"
              + (", per_class" if cascade.spec.per_class else "")
              + (", int8 front"
                 if getattr(args, "cascade_quant_front", False)
                 else "")
              + "; uncalibrated hops escalate through)")
    deploy = getattr(server.httpd, "deploy", None)
    if deploy is not None:
        bits = []
        if deploy.watcher is not None:
            bits.append(f"watch every {args.watch_interval_s}s"
                        + (f", gate={args.gate_dir}" if args.gate_dir
                           else ", gate=synthetic"))
        if deploy.autoscalers:
            bits.append(f"autoscale {args.min_replicas or 1}.."
                        f"{args.max_replicas} replicas")
        print(f"[serve] deploy pipeline: {'; '.join(bits)} — history: "
              f"curl http://{server.host}:{server.port}"
              f"/v1/deploy/<name>/history")
    if hasattr(engine, "replicas"):
        print(f"[serve] {len(engine.replicas)} replicas: "
              + ", ".join(r.model.placement_desc() or "default"
                          for r in engine.replicas))
    elif getattr(engine.model, "placement", None) is not None:
        mesh_shape = engine.model.mesh_shape() \
            if hasattr(engine.model, "mesh_shape") else None
        if mesh_shape and mesh_shape.get("model", 1) > 1:
            print(f"[serve] 2-D mesh "
                  f"{mesh_shape.get('data', 1)}×"
                  f"{mesh_shape.get('model', 1)} data×model: "
                  f"{engine.model.placement_desc()}; per-chip params "
                  f"{engine.model.param_bytes():,} B of "
                  f"{engine.model.param_global_bytes():,} B logical")
        else:
            print("[serve] sharded batches: "
                  f"{engine.model.placement_desc()}")
    bo = getattr(server.httpd, "brownout", None)
    if bo is not None:
        print(f"[serve] brownout ladder armed: "
              f"L1@{args.brownout_l1_ms:g}ms "
              f"L2@{args.brownout_l2_ms:g}ms "
              f"L3@{args.brownout_l3_ms:g}ms queue pressure "
              f"(occupancy>{args.brownout_occupancy:g} → ≥L1, "
              f"shed_rate>{args.brownout_shed_rate:g} → ≥L2) — "
              f"override: curl -XPOST http://{server.host}:"
              f"{server.port}/v1/brownout -d '{{\"force\": 2}}'")
    jobs = getattr(server.httpd, "jobs", None)
    if jobs is not None:
        print(f"[serve] batch tier: POST http://{server.host}:"
              f"{server.port}/v1/jobs "
              f"(jobs_dir={jobs.root or 'memory-only'}, "
              f"shard_size={jobs.default_shard_size}, "
              f"max_depth={args.batch_max_depth}, "
              f"pressure={args.batch_pressure_ms}ms — docs/BATCH.md)")
    if engine.faults.enabled:
        print(f"[serve] FAULT INJECTION ACTIVE: '{engine.faults.spec}' "
              f"(seed {engine.faults.seed})")
    print(f"[serve] try: curl http://{server.host}:{server.port}/v1/healthz")
    print(f"[serve] metrics: curl http://{server.host}:{server.port}/metrics")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        print("[serve] shutting down")
    finally:
        if deploy is not None:
            # the watcher/autoscaler threads stop BEFORE the engines
            # drain — no scale action or rollout races the shutdown
            deploy.stop()
        batch_sched = getattr(server.httpd, "batch_sched", None)
        if batch_sched is not None:
            # likewise the batch scheduler: no shard submit may race
            # engine.stop(); in-flight shard results past this point
            # shed and replay from the JSONL checkpoint on next boot
            batch_sched.stop()
        brownout = getattr(server.httpd, "brownout", None)
        if brownout is not None:
            # the ladder polls engine signals — stop it before the
            # engines it reads drain away
            brownout.stop()
        server.shutdown()
        engine.stop(drain_deadline=args.drain_deadline)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
