# Convenience targets — parity with the reference's per-directory Makefiles
# (ResNet/pytorch/Makefile train_*/resume_*, CycleGAN/tensorflow/Makefile).
# One Makefile, one CLI; jobs run in the foreground (use your own nohup/tmux
# where the reference baked `nohup ... &` in).

PY ?= python
DATA ?= ./data
WORKDIR ?= ./runs

# fast lane: excludes @slow (convergence / multi-epoch training) so it
# stays runnable-in-minutes on a 1-core TPU-VM host; test-all runs everything
test:
	$(PY) -m pytest tests/ -q -m "not slow"

test-all:
	$(PY) -m pytest tests/ -q

# dvtlint: the project's AST static analyzer (docs/ANALYSIS.md) — lock
# discipline, lock-order cycles, hot-path host syncs, traced-code side
# effects, wall-clock intervals, broad-except hygiene. --strict = CI
# mode: any finding (or parse failure) exits 1; escape hatches are
# counted and reported, never silent
lint:
	$(PY) -m deep_vision_tpu.analysis --strict

# the analyzer's own suite: per-rule fixtures both directions, the
# full-tree clean run, and the SanitizedLock deliberate-inversion proof
lint-test:
	$(PY) -m pytest tests/test_lint.py -q -m lint

# boot the HTTP serving stack on a random port against a LeNet fixture,
# issue one request, assert a 200 — once synchronous (pipeline_depth=1),
# once pipelined (depth=2), once fault-injected, and once replicated over
# 2 fake host devices (the cli.serve wiring, end to end; one bulk D2H
# per batch throughout); then the multi-model plane smoke (weight
# cache + hot reload under load), the gateway smoke (cross-host
# failover) and the observability smoke (/metrics, spans, id propagation)
# lint + lint-test gate the smoke: a serving-tier change that breaks the
# machine-checked invariants fails here before any engine boots;
# input_smoke.py rides the same chain so a train-input regression
# (staging-pool lifetime, wire bytes, fused-ingest parity) fails CI too
serve-smoke: lint lint-test
	$(PY) tests/input_smoke.py
	$(PY) tests/serve_smoke.py
	$(PY) tests/edge_smoke.py
	$(PY) tests/quant_smoke.py
	$(PY) tests/model_smoke.py
	$(PY) tests/deploy_smoke.py
	$(PY) tests/gateway_smoke.py
	$(PY) tests/obs_smoke.py
	$(PY) tests/mesh_smoke.py
	$(PY) tests/workload_smoke.py
	$(PY) tests/detect_smoke.py
	$(PY) tests/batch_smoke.py
	$(PY) tests/cascade_smoke.py
	$(PY) tests/brownout_smoke.py

# the async HTTP edge end to end over real sockets: keep-alive reuse
# visible in the connection counters, a content-addressed cache hit
# consuming zero engine capacity, the starved tenant class 429ing
# (Retry-After) while premium serves, a stalled body 408'd and a
# slow-loris closed silently by the deadline sweep
edge-smoke:
	$(PY) tests/edge_smoke.py

# the staged train-input pipeline end to end: uint8 batches through a
# DevicePrefetcher into a donated jitted step (two identical epochs),
# exactly 4x fewer image H2D bytes than the float32 wire, the fused
# Pallas train-ingest parity gate, and a leak-free close()
input-smoke:
	$(PY) tests/input_smoke.py

# the input-pipeline unit suite alone (wire parity, train_ingest
# interpret parity + fallback, staging-pool reuse bounds, goodput
# timers, abandoned-epoch cleanup, donation safety)
input-test:
	$(PY) -m pytest tests/test_input_pipeline.py -q -m input_pipeline

# the edge unit suite alone (selector loop, pipelining, bounded
# connections + eviction/accept-pause, cache lifecycle, tenant QoS,
# gateway connection pooling + payload affinity)
edge-test:
	$(PY) -m pytest tests/test_edge.py -q -m edge

# the int8 quantization path end to end: calibrate at load, serve
# int8-resident weights over real HTTP next to an f32 lane on the same
# weights, gate on top-1 agreement, the describe() quant block, and
# weight HBM <= 0.27x f32 (docs/SERVING.md "Int8 inference")
quant-smoke:
	$(PY) tests/quant_smoke.py

# the quantization unit/parity suite alone (per-channel roundtrip,
# calibration determinism, Pallas-vs-XLA ingest parity, weight-cache
# density, StableHLO rejection)
quant-test:
	$(PY) -m pytest tests/test_quant.py -q -m serve

# the multi-model control plane end to end: two models behind one plane
# on a weight-cache budget that holds only one of them (evict -> spill
# -> re-admit), a hot reload under live HTTP load (zero client errors,
# v2 promoted through the canary gates), /v1/models + plane-shaped
# /v1/stats, every /metrics line parsed (dvt_serve_model_up + cache)
model-smoke:
	$(PY) tests/model_smoke.py

# workload-generic serving end to end: pose + DCGAN behind the plane
# over real HTTP (fault-injected), the heatmap-decode / uint8-image
# epilogues compiled into the bucket programs, registry-driven verb
# routing (unknown verbs 404 with the supported list), a reload ->
# canary -> operator-promote rollout under live pose load with zero
# client errors, and dvt_serve_d2h_bytes_total per workload on /metrics
workload-smoke:
	$(PY) tests/workload_smoke.py

# the workload adapter unit suite alone (decode parity, epilogue D2H
# accounting, the exact 4x generate D2H win, cache/verb/agree gates)
workload-test:
	$(PY) -m pytest tests/test_workloads.py -q -m serve

# device-side detect decode end to end: YOLO behind the plane over
# real HTTP (fault-injected), the decode -> threshold -> top-k ->
# class-wise NMS epilogue compiled into the bucket programs (bulk D2H
# is exactly K fixed rows per image, not the dense anchor pyramid), a
# reload -> shadow (greedy-IoU agreement gate on live traffic) ->
# canary -> operator-promote rollout under detect load with zero
# client errors, and workload="detect" D2H accounting on /metrics
detect-smoke:
	$(PY) tests/detect_smoke.py

# the offline batch tier end to end: a bulk job POSTed over HTTP
# drains through the trough-filling scheduler while interactive
# requests keep answering 200, results stream back as chunked ndjson,
# and a second server over the same --jobs-dir resumes an unfinished
# job straight from its JSONL checkpoint (docs/BATCH.md)
batch-smoke:
	$(PY) tests/batch_smoke.py

# the batch-tier unit suite alone (job store replay + torn tails,
# priority-band starvation-freedom, restart resume exactly-once,
# interactive-p99 interference gate, occupancy autoscaling signal,
# chunked results stream on both HTTP front-ends)
batch-test:
	$(PY) -m pytest tests/test_batch.py -q -m batch

# the confidence-routed cascade end to end over HTTP: fail-closed
# all-big before calibration, live dual-run calibration flipping
# traffic to the front tier (X-DVT-Tier), an always-big QoS tenant
# pinned to the big tier, and a mid-load front reload resetting then
# REcalibrating the threshold with zero client errors
# (docs/SERVING.md "Cascaded serving")
cascade-smoke:
	$(PY) tests/cascade_smoke.py

# the cascade unit suite alone (deterministic threshold calibration,
# fail-closed thin samples, escalation bit-identity + deadline
# preservation, version-swap resets, always-big QoS routing)
cascade-test:
	$(PY) -m pytest tests/test_cascade.py -q -m models

# the continuous train->deploy loop end to end: a real async-Orbax
# checkpoint published mid-load auto-deploys through debounce -> gate
# -> canary -> promote with zero client errors, a NaN checkpoint is
# refused by the gate, and POST /v1/deploy/<name>/revert restores the
# previous promoted weights (docs/DEPLOY.md)
deploy-smoke:
	$(PY) tests/deploy_smoke.py

# the deploy unit suite alone (fingerprint tmp-skip, watcher debounce,
# gate pass/fail, revert under load, autoscaler hysteresis + drain)
deploy-test:
	$(PY) -m pytest tests/test_deploy.py -q -m deploy

# the model-plane unit suite alone (cache LRU/bit-identity, reload
# zero-loss, canary auto-rollback, shadow discard, lifecycle HTTP)
model-test:
	$(PY) -m pytest tests/test_models_plane.py -q -m models

# the observability surface alone: Prometheus /metrics on backend and
# gateway (every line parsed, counters monotonic between scrapes), a
# ?debug=1 span accounting for its full measured latency, the client's
# X-DVT-Request-Id crossing a real gateway hop into the backend's trace
# ring (docs/OBSERVABILITY.md)
obs-smoke:
	$(PY) tests/obs_smoke.py

# the observability unit/integration suite alone
obs-test:
	$(PY) -m pytest tests/test_obs.py -q -m obs

# the 2-D data×model mesh wiring end to end: cli.serve with a forced
# 2×2 mesh over 4 virtual host devices, fault-injected — 200s through
# bisect-retry, mesh shape + per-chip shard bytes in healthz/stats
# (strictly below the replicated footprint), and every /metrics line
# parsed including dvt_serve_mesh_shape / dvt_serve_param_shard_bytes
mesh-smoke:
	$(PY) tests/mesh_smoke.py

# the mesh unit suite alone (partition rules, strict tables, fallback
# sharder, mesh-cell parity, per-chip pricing, sharded cache spill)
mesh-test:
	$(PY) -m pytest tests/test_mesh_serving.py -q -m mesh

# the cross-host failover contract end to end: 2 backend serve
# SUBPROCESSES behind the in-process gateway, fault-injected load
# through the gateway, a real SIGKILL of one backend mid-run (zero
# client-visible errors, breaker opens), then /v1/drain on the survivor
# (healthz 503 draining -> gateway healthz 503)
gateway-smoke:
	$(PY) tests/gateway_smoke.py

# the gateway unit/chaos suite alone (stub + real in-process backends)
gateway-test:
	$(PY) -m pytest tests/test_gateway.py -q -m gateway

# just the multi-device pass: 2 forced host devices, a 2-replica engine
# at depth 2 with a fault-injected cohort (serve/replicas.py routing,
# per-replica health, recovery)
serve-multi:
	XLA_FLAGS=--xla_force_host_platform_device_count=2 \
		$(PY) tests/serve_smoke.py --multi

# the chaos lane alone: deterministic fault injection against a real
# engine — poison isolation, watchdog restarts, exec-timeout fast-fail,
# healthz 200→503→200 (docs/SERVING.md "Failure model & operations")
serve-chaos:
	DVT_SERVE_FAULT_SEED=0 $(PY) -m pytest tests/test_faults.py -q -m chaos

serve_%:
	$(PY) -m deep_vision_tpu.cli.serve -m $* --workdir $(WORKDIR)/$*

# the repo's one benchmark (BENCHMARK.json, PERF.md §2-§4): one plain
# run of its first cell, on the chip; the last stdout line is the result.
# The other cells are the same command under another --workload
bench:
	$(PY) benchmark/run.py --workload resnet50-train-b256 \
		--seed 2147484101 --seconds 20 --trace 0

train_%:
	$(PY) -m deep_vision_tpu.cli.train -m $* --data-root $(DATA) \
		--workdir $(WORKDIR)/$*

resume_%:
	$(PY) -m deep_vision_tpu.cli.train -m $* --data-root $(DATA) \
		--workdir $(WORKDIR)/$* --resume

smoke_%:
	$(PY) -m deep_vision_tpu.cli.train -m $* --synthetic --epochs 2 \
		--workdir /tmp/smoke_$*

eval_%:
	$(PY) -m deep_vision_tpu.cli.infer eval -m $* --data-root $(DATA) \
		--workdir $(WORKDIR)/$*

list:
	$(PY) -m deep_vision_tpu.cli.train --list -m x

.PHONY: test test-all bench serve-smoke \
	serve-multi serve-chaos gateway-smoke gateway-test obs-smoke \
	edge-smoke edge-test input-smoke input-test \
	obs-test model-smoke model-test quant-smoke quant-test \
	workload-smoke workload-test detect-smoke \
	mesh-smoke mesh-test \
	deploy-smoke deploy-test batch-smoke batch-test \
	cascade-smoke cascade-test lint lint-test list
