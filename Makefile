# Developer entry points. Pipelines launch via bin/run-pipeline.sh.

.PHONY: test t1 chaos chaos-elastic native bench bench-serve bench-serve-overload bench-serve-replicas bench-serve-daemon bench-serve-precision bench-capacity bench-fit bench-opt bench-multichip bench-imagenet bench-online trace-demo trace-report obs-serve serve-daemon profile-demo bench-watch lint dryrun clean northstar acceptance

# The canonical tier-1 verify (ROADMAP.md), verbatim at the defaults —
# builders and CI invoke this one entry point instead of hand-copying the
# command; `chaos` reuses it with T1_ENV/T1_LOG overridden so the two can
# never drift. bash for pipefail/PIPESTATUS.
T1_LOG ?= /tmp/_t1.log
T1_ENV ?=
t1: SHELL := /bin/bash
t1:
	set -o pipefail; rm -f $(T1_LOG); timeout -k 10 870 env JAX_PLATFORMS=cpu $(T1_ENV) python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee $(T1_LOG); rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' $(T1_LOG) | tr -cd . | wc -c); exit $$rc

# Tier-1 under the standard fault plan (utils/reliability.py): transient
# IOErrors at 5% of record boundaries, one injected device OOM, and 5% of
# daemon client connections dropping before the response write — seeded
# and deterministic. The suite must pass UNCHANGED: every injected fault
# is recovered (retry/backoff, quarantine, chunk downshift) invisibly,
# and a dropped connection's request still resolves (journey outcome
# conn_drop, zero unresolved futures; clients simply retry).
chaos:
	$(MAKE) t1 T1_ENV="KEYSTONE_FAULTS=io:0.05,oom:1,conn_drop:0.05 KEYSTONE_FAULTS_SEED=0" T1_LOG=/tmp/_chaos.log
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  KEYSTONE_FAULTS=io:0.05,oom:1 KEYSTONE_FAULTS_SEED=0 \
	  python -m keystone_tpu.pipelines.images.imagenet_sift_lcs_fv \
	  --stream --fv-backend pallas --gmm-k 2 --pca-dims 4 --top-k 2 \
	  --synthetic-n 96 --synthetic-classes 4 --stream-batch 32 \
	  --fit-sample-images 64 --checkpoint-dir /tmp/_chaos_imagenet_ckpt
	$(MAKE) chaos-elastic

# Elastic-mesh chaos leg (tools/chaos_elastic.py): fits killed mid-solve
# at width 8 resume at widths 4 AND 16 under the same fault plan, and
# the migrated resume must match the uninterrupted target-width fit
# BIT-FOR-BIT (stream solve, BCD epochs, OnlineState in all three
# forgetting modes) — with every migration counted and zero silent ones.
chaos-elastic:
	JAX_PLATFORMS=cpu KEYSTONE_FAULTS=io:0.05,oom:1 KEYSTONE_FAULTS_SEED=0 \
	  python tools/chaos_elastic.py --quick

# ImageNet v5e-64 bottleneck projection from measured rates; stages without
# a chip row say "not measured".
northstar:
	python tools/northstar.py

# Quality floors, all eight canonical pipelines, one pass/fail table.
acceptance:
	python tools/acceptance.py --synthetic

test:
	python -m pytest tests/ -q

native:
	$(MAKE) -C keystone_tpu/native

bench:
	python bench.py

# Shape-stable serving: per-shape jit vs bucketed+AOT-warmed on a
# mixed-size request trace. Gate: zero post-warmup compiles, >=2x p99.
# Writes the machine-readable BENCH_serve.json regression anchor.
bench-serve:
	python tools/bench_serve.py --out BENCH_serve.json

# Serving under 2x sustained over-capacity against the bounded queue +
# deadlines: reports fast-fail rate and accepted p99 — degradation must
# be bounded (rejections, not a latency cliff) and no future stranded.
bench-serve-overload:
	python tools/bench_serve.py --overload

# Replica-pool scaling on the forced 8-host-device CPU mesh: the same
# uniform trace served at devices=1 vs devices=4 through the pipelined
# dispatcher. Gates: outputs bit-identical to single-device, every
# replica serves traffic (dispatch balance max/min <= 3x); the >=1.3x
# throughput gate is hard only on >=2-core hosts (fingerprinted in the
# appended BENCH_serve.json row).
bench-serve-replicas:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  python tools/bench_serve.py --devices 4 --out BENCH_serve.json

# Networked serving daemon smoke: export two demo artifacts, stand up a
# live daemon (HTTP/JSON + framed-socket ingress, tenant admission),
# drive both wires, verify 403/429 admission, /healthz generation
# identity, and a hot-swap UNDER TRAFFIC with zero dropped requests and
# per-generation bit-identity. Tier-1 runs the same smoke in-process
# (tests/test_daemon.py).
serve-daemon:
	JAX_PLATFORMS=cpu python tools/serve_daemon.py --smoke

# Daemon overload + swap-under-load bench through the REAL socket: flood
# at 2x the admitted best-effort concurrency — the excess must fast-fail
# 429 at admission (zero device cost) while the gold tenant's p99 stays
# within 2x its deadline across TWO mid-flood hot-swaps. APPENDS the
# fingerprinted serve_daemon row to the BENCH_serve.json history that
# `make bench-watch` regresses against.
bench-serve-daemon:
	JAX_PLATFORMS=cpu python tools/bench_serve.py --daemon --out BENCH_serve.json

# Capacity-loop A/B: the same shifting-mix flood with the learned
# capacity model off (the pre-model baseline) vs on. Hard gates: model-on
# goodput (deadline-met 200s/s) beats model-off at equal-or-better gold
# p99, zero predicted-infeasible journeys ever reached a device, at
# least one cross-tenant micro-batch formed, and the re-plan loop
# reacted to the mid-flood mix shift. APPENDS the fingerprinted
# serve_capacity row to the BENCH_serve.json history `make bench-watch`
# regresses against.
bench-capacity:
	JAX_PLATFORMS=cpu python tools/bench_capacity.py --out BENCH_serve.json

# Memory-bounded precision A/B: f32 hand-picked single-bucket ladder vs
# HBM-planned ladder + bf16 through the same trained canonical head.
# Hard gates on any backend: wall AND p99 beat the baseline, planned f32
# bit-identical to hand-picked f32, quality within the declared
# tolerance of the f32 oracle (qualify() refuses otherwise), zero
# post-warmup compiles. APPENDS the fingerprinted serve_precision row to
# the BENCH_serve.json history `make bench-watch` regresses against.
bench-serve-precision:
	JAX_PLATFORMS=cpu python tools/bench_serve.py --precision --out BENCH_serve.json

# Observability smoke: a small fit + streamed solve + serve under
# KEYSTONE_TRACE=1, Chrome-trace exported to /tmp/keystone_trace.json,
# schema-validated, and checked for full span coverage (executor nodes,
# solver chunks, prefetch residency, serving lifecycle). Tier-1 runs the
# same demo in-process via tests/test_observability.py.
trace-demo:
	KEYSTONE_TRACE=1 JAX_PLATFORMS=cpu python tools/trace_demo.py --out /tmp/keystone_trace.json
	JAX_PLATFORMS=cpu python tools/trace_report.py /tmp/keystone_trace.json --top 12

# Durable-telemetry smoke: run the live daemon smoke with journey export
# on (KEYSTONE_TELEMETRY_DIR), then — after the daemon has exited —
# reconstruct the full cross-process timeline and the per-tenant SLO
# report from the on-disk segments ALONE. Tier-1 runs the same
# reconstruction in-process (tests/test_trace_report.py).
trace-report:
	rm -rf /tmp/keystone_telemetry && mkdir -p /tmp/keystone_telemetry
	KEYSTONE_TELEMETRY_DIR=/tmp/keystone_telemetry KEYSTONE_TRACE=1 \
	  JAX_PLATFORMS=cpu python tools/serve_daemon.py --smoke
	JAX_PLATFORMS=cpu python tools/trace_report.py \
	  --telemetry /tmp/keystone_telemetry --out /tmp/keystone_journeys.json
	JAX_PLATFORMS=cpu python tools/trace_report.py \
	  --telemetry /tmp/keystone_telemetry --slo

# Observability export smoke: stand up a live warmed PipelineService +
# the stdlib metrics server, fetch /metrics and /healthz over a real
# socket, validate the Prometheus text exposition (shared
# validate_prometheus_text oracle), cross-check scraped counts against
# metrics_registry.snapshot(), and assert /healthz flips to 503 after
# close(). Tier-1 runs the same smoke in-process
# (tests/test_flight_recorder.py).
obs-serve:
	JAX_PLATFORMS=cpu python tools/metrics_server.py

# Training-side profiling smoke: a small fit + apply under the resource
# profiler — every executed node must get an attribution row with
# nonzero wall time, the solve node's cost-model FLOPs must land within
# 2x of the achieved_tflops oracle, KEYSTONE_PROFILE=0 outputs must be
# bit-identical to profiled ones, and a kill-mid-solve chaos run must
# auto-dump a flight-recorder journey naming the last completed chunk.
# Tier-1 runs the same demo in-process (tests/test_profile.py).
profile-demo:
	JAX_PLATFORMS=cpu python tools/profile_report.py --demo

# Stage-parallel executor walk: a two-branch host-featurize -> solve
# pipeline fitted under the legacy serial walk (KEYSTONE_EXEC_WORKERS=0)
# vs the ready-set scheduler (=4). Gates: predictions bit-identical,
# >=1.3x wall-clock speedup (hard only on >=2-core hosts — one core
# cannot overlap two host branches; there the gate is "no worse than
# 0.75x", the replica-bench precedent). APPENDS the fingerprinted row to
# the BENCH_fit.json history `make bench-watch` regresses against.
bench-fit:
	JAX_PLATFORMS=cpu python tools/bench_fit.py --out BENCH_fit.json

# Profile-guided optimizer A/B: the canonical re-used-subchain and
# two-branch pipelines fitted-and-applied optimizer-off vs optimizer-on,
# where "on" consumes the MEASURED profile a prior fit(profile=True)
# stored (zero sample-run executions, counted and gated). Gates:
# predictions bit-identical, >=1.2x wall-clock win per pipeline (hard on
# any core count — the win is recompute avoidance, not overlap), zero
# sample runs. APPENDS the fingerprinted row to the BENCH_fit.json
# history `make bench-watch` regresses against; prints the optimizer's
# decision table (tools/profile_report.py --decisions renders the same
# surface standalone).
bench-opt:
	JAX_PLATFORMS=cpu python tools/bench_optimizer.py --out BENCH_fit.json

# Mesh-native data-parallel fit bench: the canonical two-branch jittable
# featurize -> solve pipeline fitted in a 1-device and an N-fake-device
# subprocess (XLA_FLAGS=--xla_force_host_platform_device_count, the
# test_multihost precedent), each A/Bing the explicitly-specced sharded
# walk against the single-device walk. Gates: sharded predictions
# bit-identical to the single-device walk (hard, always, both widths),
# zero silent single-device fallbacks (registry-counter-verified; the
# bench's held-out batch is deliberately non-divisible so the mask-pad
# path is always exercised), rows/s scaling hard only on real multi-chip
# hardware (fake CPU devices time-slice the host — the PR-5/PR-9
# precedent). APPENDS the fingerprinted fit_multichip row to the
# BENCH_fit.json history `make bench-watch` regresses against.
bench-multichip:
	JAX_PLATFORMS=cpu python tools/bench_multichip.py --out BENCH_fit.json

# Real-pipeline multichip bench: the ImageNet SIFT+LCS+FV featurize ->
# BlockLS solve chain fitted in 1-device and N-fake-device subprocesses
# (bench-multichip precedent), with the fused jittable tail lowered
# through SpecLayout.jit under buffer donation. Hard gates: sharded
# predictions bit-identical to the single-device walk, donation
# invisible (donate-on preds digest == donate-off), Pallas FV active on
# the sharded path (counter-verified), zero silent fallbacks, and the
# donation decision path exercised (buffers_donated + donation_refused
# > 0 — the flagship's shrinking featurize stages legitimately refuse,
# see README "Fused & donated fits"). Rows/s scaling and the
# donated-vs-undonated peak-HBM gate are hard only on real multi-chip
# hardware (fake CPU devices time-slice the host and report no HBM).
# APPENDS the fingerprinted fit_imagenet_multichip row to BENCH_fit.json.
bench-imagenet:
	JAX_PLATFORMS=cpu python tools/bench_imagenet.py --out BENCH_fit.json

# Online-learning drift gate: a label-shifted synthetic stream folds
# into the retained gram/AtB accumulators with time-decay, re-solves,
# and hot-swaps the refreshed model into a LIVE daemon mid-traffic.
# Hard gates: post-refresh accuracy (measured through the wire on the
# new generation) recovers to within tolerance of a full refit over the
# shifted data, the online re-solve wall sits >=2x below the full-refit
# wall, and the swap-under-refresh leaves zero dropped requests /
# unresolved journeys. APPENDS the fingerprinted fit_online row to the
# BENCH_fit.json history `make bench-watch` regresses against. Tier-1
# runs the same harness in-process (tests/test_online.py).
bench-online:
	JAX_PLATFORMS=cpu python tools/bench_online.py --out BENCH_fit.json

# Bench regression sentinel: parse every BENCH_*/MULTICHIP_*/BENCH_serve/
# BENCH_fit history row, fit per-metric noise bands from
# fingerprint-compatible runs, exit nonzero naming any metric whose
# latest row regresses.
# Tier-1 runs the same gate in-process (tests/test_bench_watch.py).
bench-watch:
	python tools/bench_watch.py

# Static analysis, both layers, against the checked-in expectations:
# keystone_lint.py (stdlib-ast invariant checker: lock discipline,
# env-read-once, resolve-once, perf_counter timing, broad handlers,
# dispatch host syncs) is nonzero on any finding NOT in
# tools/lint_baseline.json; lint_report.py (graph layer) must lint the
# canonical serving chains clean AND refuse the row-coupled control
# chain. Tier-1 runs both in-process (tests/test_keystone_lint.py,
# tests/test_analysis.py) so this gate can never silently rot.
lint:
	python tools/keystone_lint.py
	JAX_PLATFORMS=cpu python tools/lint_report.py

dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  python -c \
	  "import jax; jax.config.update('jax_platforms','cpu'); \
	   import __graft_entry__ as g; g.dryrun_multichip(8)"

clean:
	$(MAKE) -C keystone_tpu/native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
