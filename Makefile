# Developer/CI targets. The tier-1 suite command of record lives in
# ROADMAP.md; these are the quick subsets.

PY ?= python

.PHONY: telemetry-smoke
# Telemetry-layer smoke: span/registry/export tests + the check that
# bench_resnet_profile.py --phases keys match telemetry phase names.
telemetry-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m telemetry \
		-p no:cacheprovider

.PHONY: health-smoke
# Health-layer smoke: guard-vector math, anomaly policies
# (WARN/SKIP_STEP/ROLLBACK/HALT), and the induced-NaN e2e that must HALT
# cleanly and leave a flight-recorder crash bundle behind.
health-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m health \
		-p no:cacheprovider

.PHONY: serve-smoke
# Serving smoke: the dynamic-batcher test subset, then a live HTTP
# round-trip (start InferenceServer -> concurrent ragged /predict ->
# scrape /metrics -> clean stop, asserting zero recompiles after warmup).
serve-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m serving \
		-p no:cacheprovider
	$(PY) bench_serving.py --smoke

.PHONY: chaos-smoke
# Chaos smoke: the deterministic fault-plan suite (seeded injections,
# retry/backoff math, breaker trip->half-open->close, crash-mid-write
# checkpointing, bit-identical TrainingSession resume) on CPU with the
# same pinning as tier-1. Every fault is armed with a fixed seed, so a
# failure here replays exactly.
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m resilience \
		-p no:cacheprovider

.PHONY: fused-smoke
# Fused multi-step driver smoke: K=1 vs K=4 bit-identity (params, updater
# state, listener losses), super-step health granularity, K-keyed AOT
# cache, kill-and-resume under fused_steps. CPU-pinned, fixed seeds.
fused-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m fused \
		-p no:cacheprovider

.PHONY: shard-smoke
# Sharding smoke: rule-table resolution, ZeRO-vs-all-reduce bit
# identity on the simulated 8-device mesh, save-on-mesh-A /
# restore-on-mesh-B, collective-counter parity. CPU-pinned with the
# same virtual-device flag as tier-1.
shard-smoke:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	$(PY) -m pytest tests -q -m sharding -p no:cacheprovider

.PHONY: decode-smoke
# Continuous-batching generation smoke: KV-cache math vs the no-cache
# oracle, continuous-vs-sequential token identity, late-join/EOS-retire
# scheduling, breaker/deadline admission, zero recompiles after warmup.
decode-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m decode \
		-p no:cacheprovider

.PHONY: comms-smoke
# Collective-scheduler smoke: plan determinism/digests, scheduler-vs-
# legacy bit-identity for every wrapper exchange mode, PRG205 plan
# audit, cross-mesh reshard + publish_to_engine — then the legacy-vs-
# scheduler A/B bench asserting no regression in collective launches or
# bytes. CPU-pinned, 8 virtual devices, fixed seeds.
comms-smoke:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	$(PY) -m pytest tests -q -m comms -p no:cacheprovider
	$(PY) bench_collectives.py --smoke

.PHONY: platform-smoke
# Multi-tenant platform smoke: the registry/hot-swap/canary/quota test
# subset (seeded chaos, deterministic rollback), then the two-tenant
# faulted-canary bench in assert mode — the healthy tenant's responses
# must stay byte-identical with zero recompiles while the canary trips,
# sheds, and rolls back.
platform-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m platform \
		-p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --multi-model --seconds 1.5 \
		--assert-isolation --out /tmp/bench_serving_mt_smoke.json

.PHONY: pod-smoke
# Pod scale-out smoke: the distributed-snapshot / pod-preemption test
# subset — seeded host-death chaos with bit-identical resume, the
# mid-shard-write commit-protocol pins, cross-pod-shape restore through
# comms.reshard, and the make_array scatter/gather parity pins. The
# real 2-process leg probes the jaxlib for CPU multi-process
# collectives and skips cleanly where they are absent.
pod-smoke:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	$(PY) -m pytest tests -q -m pod -p no:cacheprovider

.PHONY: kernels-smoke
# Pallas kernel-subsystem smoke: registry parity against the XLA
# references (interpret mode), autotuner + digest-verified tuning
# cache (corruption refusal, cross-process persistence), off-by-default
# bitwise pin, fallback zero-recompile churn, PRG207 + donation audit
# on kernel-bearing steps — then the in-process A/B bench asserting
# parity and zero recompiles after warmup for both modes.
kernels-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m kernels \
		-p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) bench_conv_matrix.py --kernels --smoke

.PHONY: attention-smoke
# Attention-kernel smoke: flash/paged parity (per candidate, f32+bf16),
# flash gradient parity, routing/fallback/retune pins, the kernel-routed
# decode subset (continuous-vs-sequential token identity, prefix-attached
# pages, donation audit) — then the kernel-registry A/B bench in smoke
# mode (flash-vs-stock prefill, paged-vs-masked decode across
# occupancies, zero recompiles after warmup asserted).
attention-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_kernels.py -q \
		-k "flash or paged or attention or attn or cache_tag" \
		-p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_decode.py -q -k kern \
		-p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) bench_attention.py --smoke

.PHONY: obs-smoke
# Observability smoke: the request-tracing / SLO burn-rate test subset
# (traceparent round-trip over live HTTP, one trace across prefix-attach
# → join → decode windows → retire, replay-deterministic tail sampling
# and SLO transitions, flight-recorder trace capture + keep-last-N,
# /traces + /slo endpoints, SRC107 fixtures), then the tracing-overhead
# A/B bench in the serving shape — tracing-on must hold the pinned
# throughput budget with zero recompiles in BOTH modes.
obs-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m obs -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) bench_serving.py --traces --seconds 1.5 \
		--rounds 2 --out /tmp/bench_serving_traces_smoke.json

.PHONY: lint
# Repo-discipline source lint (analysis/source.py AST rules): host syncs
# in compiled functions, lock discipline on shared registries, wall-clock/
# RNG in traced code, fit-loop bracketing, unused imports. Exits nonzero
# on any unwaived finding >= WARN; waive inline with
# "# dl4j: waive SRC1xx — reason" (docs/analysis.md has the catalog).
lint:
	JAX_PLATFORMS=cpu $(PY) -m deeplearning4j_tpu.analysis source

.PHONY: analysis-smoke
# Program-lint smoke: the per-rule seeded-defect fixtures, then the
# compile-time pass for real — one MLN / graph / ZeRO-wrapper step each
# through the AOT cache with the lint hook armed (donation audit included).
# CPU-pinned, 2 virtual devices, fixed seeds.
analysis-smoke:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=2" \
	$(PY) -m pytest tests -q -m analysis -p no:cacheprovider
	JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=2" \
	$(PY) -m deeplearning4j_tpu.analysis program

.PHONY: bench-serving
# Closed-loop 8-client serving benchmark: locked single-request baseline
# vs the dynamic micro-batching engine (acceptance bar: >= 4x).
bench-serving:
	$(PY) bench_serving.py --assert-speedup 4

.PHONY: quant-smoke
# Quantized-serving smoke: the int8 calibration / kernel-parity /
# registry / accuracy-gate test subset, then the f32-vs-int8 platform
# A/B (calibrate -> quantize -> canary behind the accuracy arm ->
# promote), asserting zero recompiles after warmup in BOTH modes and a
# bounded accuracy_max_delta.
quant-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests -q -m quant \
		-p no:cacheprovider
	$(PY) bench_serving.py --quant --seconds 1.5 --rounds 1 \
		--hidden 96 --out /tmp/bench_serving_quant_smoke.json

.PHONY: tier1
tier1:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider
