# Test/bench entry points (CI runs these; see .github/workflows/ci.yml)

.PHONY: test test-fast test-resilience test-cluster test-serving test-decode test-quant-serving test-spec-decode test-fleet test-fleet-chaos test-obs test-slo test-data test-ingest test-bundle test-kernels test-collectives test-layout test-recsys bench bench-dispatch bench-watch bench-gradcomm bench-layout bench-decode bench-decode-quant bench-spec bench-fleet bench-fleet-chaos bench-slo bench-recsys dryrun examples bench-scaling bench-loader

# full suite, parallelized over cores (pytest-xdist): each worker is its
# own process with its own 8-virtual-device CPU mesh, so distribution
# tests stay isolated.  ~12.5 min serial on 1 core; -n auto cuts CI
# (2-core) wall time roughly in half.
test:
	python -m pytest tests/ -q -n auto

test-serial:
	python -m pytest tests/ -q

# the quick pre-commit loop: skips tests marked slow (multi-process
# integration + minutes-scale compile-shape checks); CI's `make test`
# still runs everything.
test-fast:
	python -m pytest tests/ -q -x -m "not slow" -n auto

# the contributor loop: the core path, serial, budgeted <= 5 min warm on
# 1 core — covers tensor ops, layers, optim, the sharded train step,
# records, serving and storage without the long tail of integration files.
CORE_TESTS = tests/test_tensor.py tests/test_nn_layers.py \
  tests/test_optim.py tests/test_distri_optimizer.py \
  tests/test_parallel.py tests/test_records.py tests/test_serving.py \
  tests/test_storage_remote.py
test-core:
	python -m pytest $(CORE_TESTS) -q

# the fault-tolerance suite (docs/resilience.md): fault injection,
# supervisor resume, elastic resume, GC-never-deletes-last-valid
test-resilience:
	python -m pytest tests/test_resilience.py tests/test_ckpt_sharded.py -q

# pod-scale coordinated fault tolerance (docs/resilience.md §Multi-host
# recovery): membership views + leader failover, partition heal, gang
# abort/rendezvous, peer-shard restore parity vs checkpoint restore,
# preemption propagation + SIGTERM step-exact resume, elastic re-sharded
# mid-epoch resume, checkpoint mirror retry.  The true 2-process
# kill/rejoin drill is a `slow` mark (add -m 'slow or not slow' locally)
test-cluster:
	python -m pytest tests/test_cluster.py tests/test_resume_exact.py -q \
	  -m "not slow"

# the serving suite (docs/serving.md): engine + frontend + pool, including
# the request-lifecycle chaos tests (worker kill, deadline expiry,
# backpressure 429s, drain-vs-drop, breaker/hedge) and the continuous-
# batching/registry/autoscaler suite (fixed-vs-continuous parity,
# deadline-aware ordering, multi-tenant SLO metrics, keep-alive reuse,
# pool autoscale up/down)
test-serving:
	python -m pytest tests/test_serving.py tests/test_serving_multiproc.py \
	  tests/test_serving_chaos.py tests/test_serving_continuous.py -q

# token-level decode serving (docs/serving.md §Autoregressive decode):
# continuous-vs-one-scan byte parity (greedy + seeded sample, mid-flight
# insertion), page-aliasing-free slot reuse, zero-recompile sweep,
# streaming chunk framing, prefill-never-stalls-decode scheduling,
# per-token deadline enforcement, paged flash-decode kernel parity
test-decode:
	python -m pytest tests/test_decode_engine.py -q

# the quantized-serving suite (docs/quantization.md §Serving memory
# hierarchy): per-page int8 quantize/dequantize bounds + monotone scale
# floors, stale-scale aliasing under slot reuse, int8-vs-f32 token
# parity budget (greedy + bounded logp drift), kernel-vs-reference
# agreement on int8 pages, weight_quant="int8" serving, the quantized
# KV handoff/migration surface, and /health page-dtype accounting
test-quant-serving:
	python -m pytest tests/test_quant_serving.py -q

# the speculative-decoding suite (docs/serving.md §Speculative
# decoding): spec-on vs spec-off byte parity (greedy + seeded sample,
# mid-flight admission), dense-twin acceptance pinned at 1.0,
# zero-recompile sweep with the draft/verify programs in the bucket
# set, spec x int8 token-parity budget, draft-page free on
# cancel/disconnect, decode_pressure honesty, and the multi-query
# verify kernel's parity with the gathered-jnp reference
test-spec-decode:
	python -m pytest tests/test_spec_decode.py -q

# the decode-fleet suite (docs/serving.md §Decode fleet): prefix-cache
# byte parity (cached-prefix vs cold prefill, greedy + seeded),
# eviction-never-frees-live-pages refcounting, KV handoff wire-format
# roundtrip + cross-engine prefill->decode parity, the KV-aware router,
# /health decode pressure + /fleet/prefill, and the pool-proxy
# prefill/decode split over real worker processes (streaming relay)
test-fleet:
	python -m pytest tests/test_fleet.py -q

# decode-fleet fault tolerance (docs/serving.md §Fleet fault tolerance):
# resume_from byte parity (re-prefill + migration adoption, greedy AND
# seeded), two-phase live drain with corrupt-handoff degradation,
# client-disconnect slot reclaim, breaker-driven snapshot invalidation,
# and — the slow pair — SIGKILL failover and scale-down drain against
# real subprocess pool workers with mid-flight streams
test-fleet-chaos:
	python -m pytest tests/test_fleet_chaos.py -q

# the observability suite (docs/observability.md): span tracer + chrome
# export, Prometheus exposition (+HELP lines, scrape-under-mutation),
# latency histograms, flight recorder under injected faults, TFRecord
# framing, profile_dir wiring, step-time attribution, live MFU/collective
# gauges, recompile sentinel, perf-regression sentinel
test-obs:
	python -m pytest tests/test_obs.py tests/test_perf_attr.py -q

# the fleet-observability suite (docs/observability.md §Federation /
# §SLOs & burn rates / §Decode timelines): windowed histograms incl.
# rotation-under-concurrent-observe, labeled Prometheus series + the
# collision-safe tenant-label aliases, the federated pool scrape under a
# mid-scrape worker kill, declarative SLO burn rates + the slo_burn
# chaos spec, decode chrome-trace timelines, flight-dump event rings,
# and cluster-side metric federation
test-slo:
	python -m pytest tests/test_slo.py -q

# SLO burn-rate alert-latency drill (docs/observability.md §SLOs & burn
# rates): injects a hard latency violation and measures evaluation
# ticks until the burn gauge crosses the alert threshold; exits
# non-zero when detection takes more than one window — the
# SLO_r*.json artifact source
bench-slo:
	python -m bigdl_tpu.obs.slo --bench

# the Pallas kernel suite (docs/performance.md §Pallas kernels /
# §Kernel autotuning / §Block-sparse FFN): kernel-vs-oracle parity in
# interpret mode, block-sparse matmul + pruning schedule, autotune
# cache determinism + explicit-kwarg precedence, gradient checks
test-kernels:
	python -m pytest tests/test_ops_pallas.py -q

# read-only perf-regression sentinel over the committed bench trajectory
# (docs/performance.md §Regression sentinel).  Read-only: it never
# writes artifacts.
# `make bench-watch` proves the gate on synthetic rows (the CI step);
# `python -m bigdl_tpu.obs.sentinel fresh.json` checks a real capture.
bench-watch:
	python -m bigdl_tpu.obs.sentinel --smoke

# the input-pipeline suite (docs/data.md): streaming stage parallelism,
# ring safety, worker-count determinism, crash propagation, record IO
test-data:
	python -m pytest tests/test_pipeline_stream.py tests/test_records.py \
	  tests/test_native_vision.py -q

# multi-host sharded ingest (docs/data.md §Multi-host ingest): 2-host
# feed parity (no dup/no loss, byte-identical reconstruction), restart-
# mid-epoch determinism across a process-count change, double-buffered
# dispatch overlap, worker autosizing, measured-window stage rates
test-ingest:
	python -m pytest tests/test_ingest_multihost.py -q

# fused multi-step execution (docs/performance.md): K-vs-1 byte-identical
# trajectories (incl. remainder bundles + on/off-grid resume), poisoned-
# bundle rewind, trigger-edge clamping, auto-K, /metrics lines
test-bundle:
	python -m pytest tests/test_step_bundle.py -q

# quantized + overlapped gradient collectives (docs/parallelism.md
# §Gradient compression & bucketed overlap): blockwise-int8 primitives
# vs the f32 oracle, int8-vs-fp32 loss parity on a 2-device CPU mesh,
# bucketed==monolithic trajectories, honest wire-dtype ledger,
# bf16_grads deprecation shim, overlap audit, MULTICHIP sentinel rows
test-collectives:
	python -m pytest tests/test_grad_comm.py -q

# the declarative sharding layer (docs/parallelism.md §Declarative
# layouts): parallelism= combo-string parser errors, layout-table
# completeness for the transformer/seq2seq/two-tower families (a new
# param landing in silent-replicate FAILS), the replicated-params
# audit gauge/flight line, fsdp x tp == dp loss-trajectory parity on
# the 12L transformer, and model-sharded serving through
# InferenceModel/DecodeEngine with zero unexpected recompiles
test-layout:
	python -m pytest tests/test_layout.py -q

bench:
	python bench.py

# dispatch-gap microbench (small-model geometry); --smoke is the CI gate
# that fails when the K=8 host-overhead reduction drops below 3x
bench-dispatch:
	python bench.py --dispatch

dryrun:
	python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# strong-scaling + loader-throughput artifacts (committed per round)
bench-scaling:
	python bench_scaling.py

# gradient-compression A/B (docs/parallelism.md §Gradient compression):
# analytic wire ledger fp32/bf16/int8 on the MULTICHIP_LARGE geometry +
# measured loss parity and overlap efficiency; exits non-zero when the
# int8 reduction drops below 3x or parity breaks — the
# MULTICHIP_GRADCOMM_r*.json artifact source
bench-gradcomm:
	python bench_scaling.py --grad-comm

# declarative-layout ledger A/B (docs/parallelism.md §Declarative
# layouts): per-axis collective bytes + per-chip param bytes of
# parallelism="dp" vs "fsdp:2,tp:4" on the 12L transformer geometry;
# exits non-zero when the per-chip param-bytes reduction drops below 4x
# or any parameter silently replicates — the MULTICHIP_LAYOUT_r*.json
# artifact source
bench-layout:
	python bench_scaling.py --layout

bench-loader:
	python bench_loader.py

# the recsys serving suite (docs/recsys.md): feature->recall->ranking
# pipeline end-to-end, sharded-vs-unsharded candidate-id parity, the
# closed (batch, k) recall bucket set under a mixed sweep (zero
# unexpected recompiles), predict_inline tenant routing, POST /recommend
# through the HTTP frontend, and the sharded feature-table merge cap
test-recsys:
	python -m pytest tests/test_recsys_pipeline.py \
	  tests/test_friesian_serving.py tests/test_friesian_sharded.py -q

# sustained-load serving bench (docs/serving.md §Continuous batching):
# subprocess server + keep-alive load clients, reports rps/p50/p99/
# occupancy + the zero-recompile mixed-size sweep; --smoke is the CI gate
bench-serving:
	python bench_serving.py

# token-level decode bench (docs/serving.md §Autoregressive decode):
# streaming keep-alive clients over a mixed prompt/output-length
# geometry; continuous vs whole-batch-restart A/B (>= 2x gated);
# the DECODE_r*.json artifact source
bench-decode:
	python bench_serving.py --decode

# quantized decode bench (docs/quantization.md §Serving memory
# hierarchy): int8 KV pages + int8 serving weights vs f32 on the same
# geometry — greedy token parity, >= 1.8x slot capacity at an equal KV
# HBM budget, zero unexpected recompiles; the DECODE_QUANT_r*.json
# artifact source
bench-decode-quant:
	python bench_serving.py --decode --quant

# speculative decode bench (docs/serving.md §Speculative decoding):
# the weight-shared block-sparse draft + single-call verify vs the
# same engine spec-off on the mixed geometry — byte parity, >= 1.5x
# tokens/s/user, zero unexpected recompiles; the DECODE_SPEC_r*.json
# artifact source
bench-spec:
	python bench_serving.py --decode --spec

# disaggregated decode-fleet bench (docs/serving.md §Decode fleet):
# mixed-geometry streaming clients against a 2-worker pool with the
# KV-aware router + prefill/decode split; TTFT p99 gated at >= 2x
# better than the single-host decode bench; the DECODE_POOL_r*.json
# artifact source
bench-fleet:
	python bench_serving.py --fleet

# chaos variant (docs/serving.md §Fleet fault tolerance): same 2-worker
# pool, a decode worker SIGKILLed mid-run at 24 streaming clients; the
# gate is zero failed requests + exact token parity vs the no-fault
# baseline + bounded recovery p99; the DECODE_CHAOS_r*.json source
bench-fleet-chaos:
	python bench_serving.py --fleet --chaos

# recsys + forecast bench (docs/recsys.md §Bench geometry): sharded
# feature engineering -> TwoTower + TCN(parallelism=dp)/Autoformer
# training, then sustained keep-alive POST /recommend load against the
# mesh-sharded (fsdp:2,tp:4) pipeline; gates candidate-id parity, the
# >= 8x per-chip embedding shrink, and zero unexpected recompiles; the
# RECSYS_r*.json artifact source
bench-recsys:
	python bench_recsys.py

# every example end-to-end at tiny sizes (the reference's nightly example
# runs, SURVEY.md §5, scaled for CI); fails on the first broken example
examples:
	BIGDL_TPU_EXAMPLES_TINY=1 sh -c '\
	  set -e; \
	  for f in examples/*.py; do \
	    case $$f in */_sim_mesh.py) continue;; esac; \
	    echo "== $$f"; python $$f; \
	  done'
