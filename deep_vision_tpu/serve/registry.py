"""Model registry: name → ServingModel, loadable from two artifact kinds.

  * a training workdir — the shared restore path (``core/restore.py``:
    best-checkpoint preference, pipeline→monolithic conversion, EMA
    params), then per-bucket AOT compiles of ``model.apply``;
  * a StableHLO blob (``core/export.load_exported``) — Python-model-free
    serving of the export CLI's artifact, pinned to the batch shape it
    was traced at.

Both present the same surface to the engine: ``compile_bucket(b)`` hands
back a callable for a padded batch of exactly ``b`` images, so the
batcher owns WHEN to compile (and counts it) while the model owns HOW.

Execution contract (what the pipelined engine relies on):

  * callables accept either a host numpy batch or an already-transferred
    ``jax.Array`` (the engine stages + ``device_put``s itself so H2D
    overlaps the previous batch's compute; direct callers may pass
    numpy);
  * outputs are DEVICE-NATIVE and unblocked — the callable never calls
    ``block_until_ready``/``device_get``, so dispatch returns
    immediately and the engine's drainer performs the single bulk D2H
    per batch;
  * checkpoint-backed programs are compiled with the image argument
    DONATED (``donates_inputs``) where the runtime allows, recycling the
    padded batch's device allocation into the outputs; StableHLO blobs
    keep their exported (non-donating) signature.

Multi-device placement (serve/replicas.py, docs/SERVING.md):

  * ``placement`` is the model's input sharding (None = runtime default
    device).  The engine transfers every staged batch with
    ``jax.device_put(buf, placement)`` so the SAME engine code drives
    the default device, a pinned replica device, or a sharded mesh;
  * ``for_device(dev)`` returns a per-device VIEW: the variables are
    ``device_put`` to that device exactly once (at replica-set build,
    i.e. registry load time) and every bucket program is AOT-compiled
    pinned to it via sharded ``ShapeDtypeStruct``s — N replica views of
    one checkpoint share the host restore but own their device copies;
  * ``for_mesh(mesh)`` returns a mesh-sharded VIEW: bucket programs
    compiled with the batch dim laid across the ``data`` axis, so one
    logical padded mega-batch uses every chip (``--shard-batches``).
    On a 2-D ``data × model`` mesh the variables are additionally laid
    out by the regex partition rules (parallel/partition.py) — each
    chip holds only its addressable shard of the wide leaves, GSPMD
    inserts the ICI collectives, and ``param_bytes()`` prices the
    per-chip shard (``--mesh data,model`` + ``--partition-rules``).
"""

from __future__ import annotations

import warnings

import numpy as np

#: supported serving wire formats: what dtype the client ships and the
#: engine stages/H2D-transfers.  uint8 carries raw 0–255 pixels (4×
#: fewer bytes than float32) and moves normalization into the bucket
#: program's traced prologue (ops/preprocess.make_serve_preprocess);
#: float32 is the original host-normalized contract.
WIRE_DTYPES = ("float32", "uint8")
#: supported on-device compute dtypes (outputs are always float32):
#: bfloat16 casts params once at load; int8 post-training-quantizes
#: them (serve/quant.py) — int8-resident weights, fused ingest
#: quantize, float32 accumulation and outputs
INFER_DTYPES = ("float32", "bfloat16", "int8")


class ServingModel:
    """One deployable model: metadata + per-bucket compiled forwards."""

    #: whether compile_bucket programs donate their image input buffer
    donates_inputs = False

    def __init__(self, name: str, *, task: str, input_shape: tuple,
                 num_classes: int, config_name: str | None = None,
                 fixed_batch: int | None = None,
                 wire_dtype: str = "float32",
                 infer_dtype: str = "float32"):
        if str(wire_dtype) not in WIRE_DTYPES:
            raise ValueError(f"wire_dtype '{wire_dtype}' unsupported "
                             f"(have {WIRE_DTYPES})")
        if str(infer_dtype) not in INFER_DTYPES:
            raise ValueError(f"infer_dtype '{infer_dtype}' unsupported "
                             f"(have {INFER_DTYPES})")
        self.name = name
        self.task = task
        # (H, W, C) for image-in workloads, (latent_dim,) for
        # latent-in generative models — batch dim always excluded
        self.input_shape = tuple(input_shape)
        self.num_classes = num_classes
        self.config_name = config_name or name
        # the workload adapter serving this model's task: verbs,
        # codec, epilogue, SLO class, agreement metric
        # (serve/workloads.py — one shared stateless instance per verb)
        from deep_vision_tpu.serve.workloads import workload_for_task

        self.workload = workload_for_task(task)
        # wire dtype of the OUTPUT payload when the workload ships one
        # on-device-encoded (generate: "uint8"); None = small host-side
        # decode, no output wire contract
        self.output_wire: str | None = None
        # what the engine stages + transfers (np dtype: the StagingPool
        # buffers and the bulk H2D device_put carry exactly this)
        self.wire_dtype = np.dtype(str(wire_dtype))
        # what the bucket programs compute in (outputs stay float32)
        self.infer_dtype = str(infer_dtype)
        # StableHLO blobs are traced at one batch shape; checkpoint-backed
        # models compile any bucket (None = unconstrained)
        self.fixed_batch = fixed_batch
        # input sharding (jax.sharding.Sharding) the engine device_puts
        # staged batches with; None = runtime default device.  Set by
        # for_device()/for_mesh() views.
        self.placement = None
        # which checkpoint step the weights came from (None = random
        # init) and whether restore fell back past a corrupt newer step
        # — set by the registry loaders, surfaced in describe()
        self.restored_step: int | None = None
        self.restore_fallback = False
        # checkpoint-dir mtime + params byte digest (core/restore.py):
        # the control plane's "same weights?" identity for reload
        # detection, surfaced in describe() alongside the step
        self.restored_mtime: float | None = None
        self.params_digest: str | None = None
        # version number under the control plane's versioned model
        # table (serve/models.py); None outside plane-managed serving
        self.serve_version: int | None = None
        # cascade front-tier knob (serve/cascade.py): K > 0 makes the
        # classify workload fuse a softmax+top-K confidence epilogue
        # into this model's bucket programs, so the cascade router
        # reads (top1_class, top1_prob) off the bulk D2H instead of
        # dense logits.  0 = plain dense-logits serving.
        self.cascade_topk: int = 0
        # detect decode knobs (serve/workloads.py DetectWorkload),
        # read at bucket-compile time by make_epilogue and copied
        # across reloads by models._load_model.  "device" (default)
        # fuses decode → threshold → top-k → class-wise NMS into the
        # bucket programs so D2H ships K fixed-size boxes per image;
        # "host" keeps the dense pyramid on the wire and decodes in
        # respond() — the A/B baseline and D2H-comparison path.  The
        # score threshold is the compiled FLOOR: per-request
        # thresholds above it trim host-side.
        self.detect_decode: str = "device"
        self.detect_topk: int = 100
        self.detect_score_threshold: float = 0.05
        self.detect_iou_threshold: float = 0.5
        # suppression-rule knobs (ops/boxes.py): "off" keeps the
        # reference hard NMS bit-identical; "gaussian"/"linear" switch
        # to Soft-NMS score decay.  max_per_class > 0 caps how many
        # boxes each class keeps in the fixed-K output (0 = uncapped).
        self.detect_soft_nms: str = "off"
        self.detect_soft_sigma: float = 0.5
        self.detect_max_per_class: int = 0

    def compile_bucket(self, batch: int):
        raise NotImplementedError

    def release_device_weights(self) -> None:
        """Move this model's variables to host numpy, freeing their
        device (HBM) copy.  The control plane calls this once a retired
        version has drained, so versions retained for observability (or
        versioned ``registry.get``) cost host RAM, never HBM.  A later
        call still works — jax re-transfers host arrays on use — it is
        just no longer resident.  For mesh views ``device_get`` GATHERS
        every sharded leaf into its full logical host value first, so
        the spill is a complete checkpoint-equivalent copy whatever the
        device layout was."""
        variables = getattr(self, "_variables", None)
        if variables is None:
            return
        import jax

        self._variables = jax.tree_util.tree_map(
            np.asarray, jax.device_get(variables))

    def param_bytes(self) -> int:
        """PER-CHIP addressable bytes of the variable tree (the weight
        cache's HBM accounting unit for this model) — for int8 models
        this is the true quantized footprint (~0.26× f32: int8 kernels
        + f32 scales/biases), and for a model-sharded mesh view each
        leaf is priced at its ``shard_shape``, not the global logical
        size: a leaf split 4-way over ``model`` costs a chip a quarter
        of its bytes, and eviction budgets/spill decisions must see
        that.  Unsharded/replicated leaves price at full size, so
        single-device behavior is unchanged."""
        variables = getattr(self, "_variables", None)
        if variables is None:
            return 0
        import jax

        shardings = self._leaf_shardings()
        leaves = jax.tree_util.tree_leaves(variables)
        total = 0
        for i, a in enumerate(leaves):
            s = None
            if isinstance(a, jax.Array):
                s = a.sharding
            elif shardings is not None:
                # spilled host copy: the view's sharding tree still
                # describes how it lives on devices when re-admitted
                s = shardings[i]
            if s is not None:
                shard = s.shard_shape(tuple(a.shape))
                total += int(np.prod(shard)) * int(a.dtype.itemsize)
            else:
                # .nbytes is metadata on jax and numpy arrays — no D2H
                total += int(a.nbytes)
        return total

    def param_global_bytes(self) -> int:
        """Logical full-tree bytes (what replication would cost one
        chip) — the denominator for the sharding saving surfaced in
        /v1/stats next to the per-chip ``param_bytes()``."""
        variables = getattr(self, "_variables", None)
        if variables is None:
            return 0
        import jax

        return int(sum(int(np.prod(a.shape)) * int(a.dtype.itemsize)
                       for a in jax.tree_util.tree_leaves(variables)))

    def _leaf_shardings(self):
        """``_var_sharding`` flattened to a per-leaf list (None when no
        sharding view applies): single-Sharding views broadcast, mesh
        views carry a pytree congruent with ``_variables``."""
        import jax

        vs = getattr(self, "_var_sharding", None)
        if vs is None:
            return None
        if isinstance(vs, jax.sharding.Sharding):
            n = len(jax.tree_util.tree_leaves(
                getattr(self, "_variables", None)))
            return [vs] * n
        return jax.tree_util.tree_leaves(
            vs, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))

    def mesh_shape(self) -> dict | None:
        """``{"data": D, "model": M}`` for mesh views, None otherwise —
        advertised through engine stats → /v1/healthz → the gateway's
        fleet table."""
        mesh = getattr(self, "_mesh", None)
        if mesh is None:
            return None
        return {str(k): int(v) for k, v in mesh.shape.items()}

    def placement_desc(self) -> str | None:
        """Human-readable placement for stats/health (None = default)."""
        import jax

        if self.placement is None:
            return None
        devs = sorted(d.id for d in self.placement.device_set)
        if len(devs) == 1:
            return str(next(iter(self.placement.device_set)))
        return (f"sharded over {len(devs)} devices "
                f"{devs} ({jax.devices()[0].platform})")

    def describe(self) -> dict:
        d = {}
        if self.workload.verb == "detect":
            d["detect"] = {"decode": self.detect_decode,
                           "top_k": self.detect_topk,
                           "score_threshold": self.detect_score_threshold,
                           "iou_threshold": self.detect_iou_threshold,
                           "soft_nms": self.detect_soft_nms,
                           "soft_sigma": self.detect_soft_sigma,
                           "max_per_class": self.detect_max_per_class}
        return {"name": self.name, "task": self.task,
                "workload": self.workload.verb, **d,
                "input_shape": list(self.input_shape),
                "num_classes": self.num_classes,
                "fixed_batch": self.fixed_batch,
                "donates_inputs": self.donates_inputs,
                "wire_dtype": str(self.wire_dtype),
                "infer_dtype": self.infer_dtype,
                "output_wire": self.output_wire,
                "placement": self.placement_desc(),
                "mesh": self.mesh_shape(),
                "restored_step": self.restored_step,
                "restore_fallback": self.restore_fallback,
                "restored_mtime": self.restored_mtime,
                "params_digest": self.params_digest,
                "version": self.serve_version}


class CheckpointServingModel(ServingModel):
    """Workdir-checkpoint-backed: AOT-compile apply() per batch bucket."""

    donates_inputs = True

    def __init__(self, name: str, cfg, model, state,
                 wire_dtype: str = "float32",
                 infer_dtype: str = "float32",
                 calib_batches: int = 2,
                 calib_dir: str | None = None,
                 ingest: str = "pallas"):
        from deep_vision_tpu.serve.workloads import workload_for_task

        # the workload adapter owns the input codec: latent-in
        # generative models serve a (latent_dim,) float vector, not an
        # image, and override an operator-requested uint8 wire (a uint8
        # latent is meaningless); image-in workloads keep the config's
        # (H, W, C) and the requested wire
        wl = workload_for_task(cfg.task)
        super().__init__(
            name, task=cfg.task,
            input_shape=wl.serving_input_shape(cfg, model),
            num_classes=cfg.num_classes, config_name=cfg.name,
            wire_dtype=wl.wire_dtype_for(cfg, str(wire_dtype)),
            infer_dtype=infer_dtype)
        self.output_wire = wl.output_wire(cfg)
        self.cfg = cfg
        # which device-side normalization a uint8 wire needs — derived
        # from the config so it matches the host path the model trained
        # against (a float32 wire skips it: the client normalized)
        from deep_vision_tpu.ops.preprocess import serve_preprocess_kind

        self.preprocess_kind = serve_preprocess_kind(cfg.task, cfg.channels)
        # int8 calibration provenance (None / unused outside int8);
        # kept public so a hot reload rebuilds the same quantization
        # (serve/models.py _load_model) and describe() can price it
        self.quant = None
        self.calib_batches = int(calib_batches)
        self.calib_dir = calib_dir
        if str(ingest) not in ("pallas", "xla"):
            raise ValueError(f"ingest '{ingest}' unsupported "
                             f"(have ('pallas', 'xla'))")
        self.ingest = str(ingest)
        if self.infer_dtype == "bfloat16":
            import jax
            import jax.numpy as jnp

            # every zoo model threads its ``dtype`` attr through the
            # compute graph (x.astype(self.dtype) before the first conv)
            # — clone with bf16 so activations run in bf16, and cast the
            # float variable leaves ONCE here at load (half the param
            # HBM and per-device replica copies too)
            if hasattr(model, "dtype"):
                model = model.clone(dtype=jnp.bfloat16)
            state = state.replace(params=jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16)
                if jnp.issubdtype(a.dtype, jnp.floating) else a,
                state.params))
        self._model = model
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        if self.infer_dtype == "int8":
            # post-training quantization AT LOAD (serve/quant.py):
            # calibrate activation ranges on a held-out (or synthetic)
            # batch, then swap the variable tree for the int8-resident
            # one — bucket programs dequantize inside the trace, the
            # WeightCache rounds the int8 leaves through spill/re-admit
            # untouched, and param_bytes() prices the real footprint
            from deep_vision_tpu.serve.quant import quantize_for_serving

            variables, self.quant = quantize_for_serving(
                model, variables, kind=self.preprocess_kind,
                input_shape=self.input_shape,
                calib_batches=self.calib_batches,
                calib_dir=self.calib_dir)
        self._variables = variables
        # variable sharding paired with ``placement`` (replicated on a
        # mesh, pinned on a single device); None = wherever restore left
        # them
        self._var_sharding = None
        # HBM residency manager (serve/models.py WeightCache) — when
        # registered, bucket programs resolve their variables through
        # the cache at CALL time (late binding), so an evicted model's
        # weights can spill to host RAM and be device_put back on demand
        # without recompiling any retained AOT executable
        self._cache = None

    def describe(self) -> dict:
        d = super().describe()
        if self.quant is not None:
            d["quant"] = dict(self.quant.describe(),
                              param_bytes=self.param_bytes(),
                              ingest=getattr(self, "ingest_path",
                                             self.ingest))
        return d

    def _live_variables(self):
        """The variables a bucket program should run with RIGHT NOW:
        the cache's resident copy when this model is under residency
        management (which may trigger an evict→re-admit cycle), else
        the load-time device arrays.  Called once per dispatched batch
        — never per request."""
        cache = self._cache
        if cache is not None:
            managed = cache.variables_for(self)
            if managed is not None:
                return managed
        return self._variables

    def for_device(self, device) -> "CheckpointServingModel":
        """Per-device replica view: SAME host restore, its OWN device
        copy of the variables (one ``device_put`` per device, here, at
        replica-set build — never per batch) and bucket programs pinned
        to ``device`` (serve/replicas.py builds one view per local
        device)."""
        import copy

        import jax
        from jax.sharding import SingleDeviceSharding

        view = copy.copy(self)
        sharding = SingleDeviceSharding(device)
        view.placement = sharding
        view._var_sharding = sharding
        view._variables = jax.device_put(self._variables, sharding)
        return view

    def for_mesh(self, mesh, partition_rules=None, strict: bool = False,
                 min_shard_dim: int = 1024) -> "CheckpointServingModel":
        """Mesh-sharded view: bucket programs compiled with the batch
        dim split across the ``data`` axis, and — on a 2-D
        ``data × model`` mesh — variables laid out by the partition
        rules (parallel/partition.py) so each chip holds only its
        addressable shard of the wide leaves; GSPMD inserts the ICI
        collectives the layout implies.  On a 1-D data mesh (legacy
        ``--shard-batches``) variables replicate, exactly as before.

        ``partition_rules`` is an ordered ``(regex, PartitionSpec)``
        table (``match_partition_rules``); None = the first-divisible-
        axis fallback sharder.  ``strict`` demands every leaf match
        exactly one rule.  Buckets must be divisible by the data-axis
        size (compile_bucket enforces it, naming both axes)."""
        import copy

        from deep_vision_tpu.parallel.mesh import (
            MODEL_AXIS,
            batch_sharding,
            replicate,
            replicated_sharding,
        )

        view = copy.copy(self)
        view.placement = batch_sharding(mesh, ndim=1 + len(self.input_shape))
        n_model = mesh.shape.get(MODEL_AXIS, 1)
        if n_model > 1 or partition_rules is not None:
            from deep_vision_tpu.parallel.partition import (
                param_shardings,
                shard_variables,
            )

            # pytree of NamedShardings, congruent with _variables —
            # compile_bucket's v_spec and the WeightCache's re-admit
            # device_put both consume it leaf-for-leaf
            shardings = param_shardings(
                self._variables, mesh, min_shard_dim,
                rules=partition_rules, strict=strict)
            view._var_sharding = shardings
            view._variables = shard_variables(self._variables, shardings)
        else:
            view._var_sharding = replicated_sharding(mesh)
            view._variables = replicate(self._variables, mesh)
        view._mesh = mesh
        return view

    def compile_bucket(self, batch: int):
        import jax
        import jax.numpy as jnp

        if getattr(self, "_mesh", None) is not None:
            from deep_vision_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

            n_data = self._mesh.shape.get(DATA_AXIS, 1)
            n_model = self._mesh.shape.get(MODEL_AXIS, 1)
            if batch % n_data != 0:
                # only the batch dim splits over ``data``; ``model``
                # constrains nothing here but belongs in the message —
                # the operator picked one mesh, the error should name it
                nearest = max(n_data,
                              ((batch + n_data - 1) // n_data) * n_data)
                raise ValueError(
                    f"sharded serving of '{self.name}': bucket {batch} "
                    f"not divisible by the data axis of the "
                    f"{n_data}×{n_model} data×model mesh — "
                    f"nearest usable bucket is {nearest}; use buckets "
                    f"that are multiples of {n_data} "
                    f"(engine.sharded_buckets)")

        from deep_vision_tpu.ops.preprocess import (
            make_int8_ingest,
            make_serve_preprocess,
        )

        wire = jnp.dtype(str(self.wire_dtype))
        compute = jnp.bfloat16 if self.infer_dtype == "bfloat16" \
            else jnp.float32

        def _f32_outputs(out):  # dvtlint: traced
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, out)

        # workload epilogue (serve/workloads.py), fused into the same
        # AOT program as the model body — the output-side mirror of the
        # normalize prologue: pose decodes heatmaps→keypoints on device
        # (D2H moves K coordinate pairs, not H×W×K heatmaps), generate
        # encodes [-1,1] floats→uint8 (D2H moves 1 byte/pixel), detect
        # decodes + NMSes down to K fixed-size boxes per image (D2H
        # moves ~K·28 B instead of the dense multi-scale pyramid)
        post = self.workload.make_epilogue(self)

        def _finish(out):  # dvtlint: traced
            out = _f32_outputs(out)
            return post(out) if post is not None else out

        if self.infer_dtype == "int8":
            # the fused Pallas ingest is the default on the uint8 wire.
            # The fused kernel's constant table has no "gan" family —
            # GAN-kind ingest always takes the XLA prologue
            act_scale = float(self.quant.act_scale)
            use_pallas = self.ingest == "pallas" and \
                jnp.issubdtype(wire, jnp.integer) and \
                self.preprocess_kind != "gan"
            if use_pallas and jax.default_backend() == "tpu":
                from deep_vision_tpu.ops.pallas_ops import (
                    serve_ingest_parity,
                )

                # run the compiled kernel once at this bucket's shape
                # against the reference prologue: a kernel Mosaic refuses
                # or one that diverges fails the load with the reason
                serve_ingest_parity((batch, *self.input_shape),
                                    self.preprocess_kind, act_scale)
            self.ingest_path = "pallas" if use_pallas else "xla"
            pre_q = make_int8_ingest(self.preprocess_kind, wire,
                                     act_scale, use_pallas=use_pallas)
            from deep_vision_tpu.serve.quant import dequantize_params

            def apply(variables, x):  # dvtlint: traced
                # int8 activations dequantize into the first conv's
                # read; int8-resident weights dequantize in-trace (XLA
                # fuses both casts — no f32 weight copy persists in HBM)
                xq = pre_q(x)
                xf = xq.astype(jnp.float32) * act_scale
                v = dict(variables)
                scales = v.pop("param_scales")
                v["params"] = dequantize_params(v["params"], scales)
                out = self._model.apply(v, xf, train=False)
                return _finish(out)
        else:
            # traced prologue: a uint8 wire batch is cast + scaled +
            # normalized ON DEVICE (XLA fuses it into the first conv's
            # HBM read — the H2D carried 4× fewer bytes); a float32 wire
            # passes through (the client normalized).  Outputs always
            # leave the program as float32, whatever the compute dtype.
            pre = make_serve_preprocess(self.preprocess_kind, wire,
                                        compute)

            def apply(variables, x):
                out = self._model.apply(variables, pre(x), train=False)
                return _finish(out)

        x_spec = jax.ShapeDtypeStruct((batch, *self.input_shape),
                                      wire, sharding=self.placement)
        var_sharding = self._var_sharding
        if var_sharding is not None and \
                not isinstance(var_sharding, jax.sharding.Sharding):
            # mesh view: per-leaf sharding pytree (partition rules) —
            # each leaf's spec carries ITS layout into the AOT compile
            v_spec = jax.tree_util.tree_map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                self._variables, var_sharding)
        else:
            v_spec = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=var_sharding),
                self._variables)
        # AOT lower+compile: the engine's bucket dict is the jit cache,
        # so a served shape can never hit a surprise trace mid-request.
        # The image buffer is donated — each padded batch's device
        # allocation is recycled into the outputs (a no-op where the
        # backend declines; jax falls back to copying)
        with warnings.catch_warnings():
            # lowering warns when the donated image buffer can't alias
            # any output (e.g. classification logits are smaller than
            # the batch) — donation is best-effort by contract
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            compiled = jax.jit(apply, donate_argnums=(1,)).lower(
                v_spec, x_spec).compile()
        model = self  # late-bind variables: the weight cache may have
        # spilled + re-admitted them since this program compiled, and
        # the AOT executable must not pin the evicted device buffers

        placement = self.placement
        wire_np = self.wire_dtype

        def call(x):
            variables = model._live_variables()
            # keep donation meaningful for direct numpy callers too:
            # transfer first, hand the committed device buffer over —
            # honoring the view's placement (replica device / mesh)
            if not isinstance(x, jax.Array):
                x = jax.device_put(np.asarray(x, wire_np), placement)
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                return compiled(variables, x)

        # analytic FLOPs ride on the callable for the engine's
        # serving-MFU meter: XLA's own cost analysis on the AOT
        # executable, or the documented 2·params·batch lower bound when
        # the backend doesn't report flops (obs/mfu.py)
        from deep_vision_tpu.obs.mfu import (
            compiled_flops,
            params_flops_lower_bound,
        )

        mesh = getattr(self, "_mesh", None)
        n_mesh = int(np.prod(list(mesh.shape.values()))) if mesh else 1
        flops = compiled_flops(compiled)
        if flops is not None:
            # sharded executables cost-analyze ONE partition — already
            # the per-chip numerator the meter's per-chip peak expects
            call.cost_flops = flops
            call.flops_source = ("xla_cost_analysis_per_shard"
                                 if n_mesh > 1 else "xla_cost_analysis")
        else:
            call.cost_flops = params_flops_lower_bound(
                self._variables, batch, devices=n_mesh)
            call.flops_source = ("params_lower_bound_per_shard"
                                 if n_mesh > 1 else "params_lower_bound")
        return call


class ExportedServingModel(ServingModel):
    """StableHLO-blob-backed (core/export): fixed batch, no Python model.

    Blobs serve exactly their exported signature — traced at float32
    with host-side normalization — so the wire/infer dtype knobs don't
    apply here (``wire_dtype``/``infer_dtype`` stay "float32";
    ``cli.serve`` forces the same when ``--stablehlo`` is given).
    """

    def __init__(self, name: str, cfg, call, variables, fixed_batch: int):
        super().__init__(
            name, task=cfg.task,
            input_shape=(cfg.image_size, cfg.image_size, cfg.channels),
            num_classes=cfg.num_classes, config_name=cfg.name,
            fixed_batch=fixed_batch)
        self.cfg = cfg
        self._call = call
        self._variables = variables
        #: every batch size the blob was exported with (today one trace
        #: per blob; kept a list so multi-bucket exports slot in) — the
        #: error surface for unavailable buckets, instead of the XLA
        #: shape-mismatch noise the raw call would raise
        self.bucket_sizes = [int(fixed_batch)]

    def _unavailable(self, batch: int) -> ValueError:
        return ValueError(
            f"StableHLO blob for '{self.name}' was exported with bucket "
            f"sizes {self.bucket_sizes}; batch {batch} unavailable — "
            f"re-export with --batch {batch} or serve from the checkpoint")

    def compile_bucket(self, batch: int):
        if batch not in self.bucket_sizes:
            raise self._unavailable(batch)
        call, variables = self._call, self._variables

        def run(x):
            # check HERE, not inside XLA: the deserialized call's shape
            # error names avals, not what the operator can act on
            if x.shape[0] not in self.bucket_sizes:
                raise self._unavailable(x.shape[0])
            return call(variables, x)

        # a deserialized blob exposes no compiled executable to cost-
        # analyze, so the MFU numerator uses the documented fallback
        from deep_vision_tpu.obs.mfu import params_flops_lower_bound

        run.cost_flops = params_flops_lower_bound(variables, batch)
        run.flops_source = "params_lower_bound"
        return run


class ModelRegistry:
    def __init__(self):
        self._models: dict[str, ServingModel] = {}
        # name → version → ServingModel: the control plane
        # (serve/models.py) publishes each promoted version here so
        # ``get(name, version=N)`` can answer for any retained version;
        # plain single-version serving never populates it
        self._versions: dict[str, dict[int, ServingModel]] = {}

    def add(self, model: ServingModel,
            version: int | None = None) -> ServingModel:
        self._models[model.name] = model
        if version is None:
            version = model.serve_version
        if version is not None:
            self._versions.setdefault(model.name, {})[int(version)] = model
        return model

    def remove_version(self, name: str, version: int) -> None:
        """Forget one retained version (the control plane prunes
        retired versions past its retain window here, so the registry's
        refs don't pin pruned weights forever).  The default unversioned
        ``_models`` entry is untouched."""
        table = self._versions.get(name)
        if table is not None:
            table.pop(int(version), None)
            if not table:
                self._versions.pop(name, None)

    def load_checkpoint(self, config_name: str, workdir: str,
                        name: str | None = None,
                        wire_dtype: str = "float32",
                        infer_dtype: str = "float32",
                        calib_batches: int = 2,
                        calib_dir: str | None = None,
                        ingest: str = "pallas",
                        cascade_topk: int = 0,
                        detect_decode: str = "device",
                        detect_topk: int = 100,
                        detect_score_threshold: float = 0.05,
                        detect_iou_threshold: float = 0.5,
                        detect_soft_nms: str = "off",
                        detect_soft_sigma: float = 0.5,
                        detect_max_per_class: int = 0
                        ) -> ServingModel:
        """``wire_dtype``: what clients ship and the engine H2D-transfers
        — "uint8" (raw 0–255 pixels, normalization fused into the bucket
        programs; the ``cli.serve`` default) or "float32" (the original
        host-normalized contract; the programmatic default, so existing
        direct callers are untouched).  ``infer_dtype``: "bfloat16" casts
        params once here and runs bucket programs in bf16 compute with
        float32 outputs; "int8" post-training-quantizes here
        (serve/quant.py) — ``calib_batches`` held-out batches from
        ``calib_dir`` (deterministic synthetic data when None) calibrate
        the activation scales, and ``ingest`` picks the fused Pallas
        serve-prologue ("pallas", the default) or the XLA prologue ("xla").
        ``cascade_topk`` > 0 marks a cascade FRONT tier: the classify
        workload fuses its confidence epilogue (softmax + top-K on
        device) into the bucket programs (serve/cascade.py).

        ``detect_*`` configure detection models' fused decode
        (serve/workloads.py DetectWorkload): ``detect_decode="device"``
        (default) traces decode → score floor → top-``detect_topk`` →
        class-wise NMS into the bucket programs so the bulk D2H ships
        K fixed-size boxes per image; "host" keeps the dense pyramid
        rows and decodes per request in respond() — the A/B baseline.
        ``detect_soft_nms`` ("gaussian"/"linear") switches the fused
        NMS to Soft-NMS score decay with ``detect_soft_sigma``, and
        ``detect_max_per_class`` > 0 caps each class's share of the
        fixed-K output.  Non-detect models ignore them."""
        from deep_vision_tpu.core.config import get_config
        from deep_vision_tpu.core.restore import load_state

        cfg = get_config(config_name)
        info: dict = {}
        model, state = load_state(cfg, workdir, tag="serve", info=info)
        sm = CheckpointServingModel(name or config_name, cfg, model, state,
                                    wire_dtype=wire_dtype,
                                    infer_dtype=infer_dtype,
                                    calib_batches=calib_batches,
                                    calib_dir=calib_dir,
                                    ingest=ingest)
        sm.cascade_topk = int(cascade_topk)
        if str(detect_decode) not in ("device", "host"):
            raise ValueError(f"detect_decode '{detect_decode}' "
                             f"unsupported (have ('device', 'host'))")
        sm.detect_decode = str(detect_decode)
        sm.detect_topk = int(detect_topk)
        sm.detect_score_threshold = float(detect_score_threshold)
        sm.detect_iou_threshold = float(detect_iou_threshold)
        if str(detect_soft_nms) not in ("off", "gaussian", "linear"):
            raise ValueError(f"detect_soft_nms '{detect_soft_nms}' "
                             f"unsupported (have ('off', 'gaussian', "
                             f"'linear'))")
        sm.detect_soft_nms = str(detect_soft_nms)
        sm.detect_soft_sigma = float(detect_soft_sigma)
        sm.detect_max_per_class = int(detect_max_per_class)
        sm.restored_step = info.get("step")
        sm.restore_fallback = bool(info.get("fallback"))
        sm.restored_mtime = info.get("mtime")
        sm.params_digest = info.get("digest")
        return self.add(sm)

    def load_exported(self, config_name: str, blob_path: str, workdir: str,
                      name: str | None = None,
                      wire_dtype: str = "float32",
                      infer_dtype: str = "float32") -> ServingModel:
        """Serve a ``cli.infer export`` artifact.

        The blob's inputs are (variables, x) — the same variables pytree
        the exporting process restored — so the companion workdir supplies
        them through the identical restore path.

        Exported blobs are f32-wire/f32-compute only: the StableHLO was
        traced at one float32 signature with host-side normalization, so
        neither wire decoding nor a compute-dtype rewrite (bfloat16 OR
        int8 quantization) can apply — those need the re-jitting
        checkpoint path.  Checked FIRST, before any file I/O, so the
        operator gets the dtype error rather than a restore traceback.
        """
        if str(wire_dtype) != "float32" or str(infer_dtype) != "float32":
            raise ValueError(
                "exported StableHLO blobs are f32-wire/f32-compute "
                "only: the blob serves exactly its traced float32 "
                f"signature, so wire_dtype='{wire_dtype}' / "
                f"infer_dtype='{infer_dtype}' (bfloat16 and int8 "
                "included) need the checkpoint path — serve without "
                "--stablehlo")
        from deep_vision_tpu.core.config import get_config
        from deep_vision_tpu.core.export import load_exported
        from deep_vision_tpu.core.restore import load_state

        cfg = get_config(config_name)
        info: dict = {}
        _, state = load_state(cfg, workdir, tag="serve", info=info)
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        call = load_exported(blob_path)
        # the image input is the final positional arg, hence the last
        # flattened aval (variables dict leaves sort first)
        fixed_batch = int(call.in_avals[-1].shape[0])
        sm = ExportedServingModel(
            name or config_name, cfg, call, variables, fixed_batch)
        sm.restored_step = info.get("step")
        sm.restore_fallback = bool(info.get("fallback"))
        sm.restored_mtime = info.get("mtime")
        sm.params_digest = info.get("digest")
        return self.add(sm)

    def get(self, name: str | None = None,
            version: int | None = None) -> ServingModel:
        if name is None:
            if len(self._models) != 1:
                raise KeyError(
                    f"model name required (serving {sorted(self._models)})")
            if version is not None:
                return self.get(next(iter(self._models)), version)
            return next(iter(self._models.values()))
        if name not in self._models:
            raise KeyError(f"unknown model '{name}'; "
                           f"serving {sorted(self._models)}")
        if version is not None:
            table = self._versions.get(name, {})
            if int(version) not in table:
                raise KeyError(
                    f"model '{name}' has no version {version}; "
                    f"versions {sorted(table)}")
            return table[int(version)]
        return self._models[name]

    def names(self) -> list[str]:
        return sorted(self._models)

    def __contains__(self, name: str) -> bool:
        return name in self._models

    def __len__(self) -> int:
        return len(self._models)
