# One-command CI (reference: ci/build.py + ci/docker/runtime_functions.sh —
# the function registry every CI stage called). Stages:
#   sanity  - syntax/compile sweep over the package + tools (the parse
#             floor; semantic hazards are the `lint` stage's job)
#   lint    - jit-hazard linter (tools/lint.py, docs/ANALYSIS.md): host
#             syncs in compiled hot paths, trace-time branches,
#             nondeterminism in op code, mutable defaults, unlocked
#             global-registry mutation
#   audit   - structural HLO audit (tools/audit.py): zero f64 in bf16
#             programs, 100% donation coverage on the TrainStep and
#             decode-cache carries, shape recompiles logged with a cause
#   shardcheck - golden-program sharding + communication gate
#             (tools/shardcheck.py): contract violations, accidental
#             reshards, new collective kinds, comm-byte regressions and
#             fingerprint drift vs mxnet_tpu/analysis/goldens/
#   memcheck - golden-program memory gate (tools/memcheck.py): buffer-
#             liveness peak-residency regressions > 5%, new
#             materialization classes (KV gather-materialize, f32
#             upcasts, remat-defeating live ranges), donation drops vs
#             mxnet_tpu/analysis/goldens/mem_*.json, plus a
#             memory_analysis() cross-validation of the estimator
#   kernelcheck - Pallas kernel correctness gate: CPU interpret-mode
#             parity/bit-identity suites for every custom kernel (flash
#             attention, fused layernorm, paged decode attention, fused
#             Adam, fused softmax-xent, the experts' grouped matmul),
#             docs/PERFORMANCE.md
#   profcheck - measured-profiling gate (tools/profcheck.py): traces two
#             shared golden families for real, asserts non-empty device
#             op timelines, measured overlap, and step-time agreement
#             with the metrics registry
#   native  - build libmxtpu.so (C++ runtime: recordio/jpeg/runtime/c_api)
#   fast    - pytest without @slow (target < 10 min on 8 virtual CPU devs)
#   slow    - the @slow remainder (model compiles, 4-process launches)
#   ci      - sanity + lint + native + fast + audit + shardcheck +
#             memcheck + profcheck + kernelcheck +
#             chaos-elastic + chaos-serve +
#             chaos-fleet (the pre-merge gate; chaos-elastic is the slow
#             4-process kill-a-worker drill, chaos-serve the
#             serving-resilience drill: injected gen.* faults + deadlines
#             + accept-rate collapse, chaos-fleet the multi-replica
#             router drill: kill + wedge with zero in-deadline drops,
#             tools/servedrill.py)
#   test    - full suite (ci + slow), what the driver effectively runs

PY ?= python

# chaos pass (docs/RESILIENCE.md): deterministic transient faults on every
# IO/DCN fault site, fixed seed — the tier-1 suite must pass anyway, proving
# the retry/atomic-commit layers absorb them. every>=2 so the default
# 3-attempt retry policy can never see an injected failure twice in a row.
CHAOS_FAULTS ?= ckpt.save:every=3;ckpt.load:every=3;kv.save_states:every=2;kv.load_states:every=3;kv.dcn_psum:every=4;kv.dcn_psum_batch:every=4;data.batch:every=7;seed=1234

.PHONY: ci sanity lint audit shardcheck memcheck profcheck kernelcheck native fast slow test chaos chaos-elastic chaos-serve chaos-fleet obs obsfleet perfwin ampbench bench clean

ci: sanity lint native fast audit shardcheck memcheck profcheck kernelcheck chaos-elastic chaos-serve chaos-fleet obsfleet

sanity:
	$(PY) -m compileall -q mxnet_tpu tools tests examples bench.py chip_smoke.py __graft_entry__.py

# jit-hazard lint (docs/ANALYSIS.md): AST rules over the package + tools.
# `python tools/lint.py --changed` is the fast pre-commit variant.
lint:
	$(PY) tools/lint.py

# structural program audit (docs/ANALYSIS.md): lowers the bf16 step/window
# and decode programs on CPU and asserts dtype purity, donation coverage,
# and explained recompile causes
audit:
	$(PY) tools/audit.py

# golden-program sharding + communication gate (docs/ANALYSIS.md): lowers
# the representative program families on 8 virtual CPU devices, runs the
# sharding contract checker + the comm cost model, and diffs against the
# committed goldens — contract violations, accidental reshards, new
# collective kinds, comm-byte regressions > tolerance, donation drops and
# fingerprint drift all fail; rebless intentional changes with
# `python tools/shardcheck.py --update-golden`
shardcheck:
	$(PY) tools/shardcheck.py

# golden-program memory gate (docs/ANALYSIS.md "Memory"): runs the
# buffer-liveness pass over the same program families and diffs peak
# residency, materialization classes and donation coverage against the
# committed mem_*.json goldens; also cross-validates the estimator
# against jax's memory_analysis() on the mesh-less step/decode programs.
# Rebless intentional changes with `python tools/memcheck.py
# --update-golden`
memcheck:
	$(PY) tools/memcheck.py

# measured-profiling gate (docs/OBSERVABILITY.md "Measured profiling"):
# captures real traces of the fsdp step + decode golden families, parses
# the XPlane timelines, and asserts non-empty op rows, measured overlap
# and measured-vs-registry step-time agreement. The failure path stays
# tested via `python tools/profcheck.py --inject-empty-trace`
profcheck:
	$(PY) tools/profcheck.py

# Pallas kernel correctness gate (docs/PERFORMANCE.md "Custom kernels"):
# every kernel's CPU interpret-mode parity/bit-identity suite, runnable
# standalone before blessing perf artifacts on hardware
kernelcheck:
	$(PY) -m pytest tests/test_flash_attention.py tests/test_pallas_layernorm.py \
	    tests/test_pallas_paged_attention.py \
	    tests/test_pallas_paged_latent_attention.py tests/test_pallas_optimizer.py \
	    tests/test_pallas_softmax_xent.py tests/test_packed_attention.py \
	    tests/test_pallas_grouped_matmul.py -q

native:
	$(MAKE) -C native

fast: native
	@start=$$(date +%s); \
	$(PY) -m pytest tests/ -q -m "not slow"; rc=$$?; \
	el=$$(( $$(date +%s) - start )); \
	echo "make fast: $${el}s (budget 600s)"; \
	if [ $$rc -ne 0 ]; then exit $$rc; fi; \
	if [ $$el -gt 600 ]; then echo "make fast: OVER BUDGET (>600s)"; exit 1; fi

slow: native
	$(PY) -m pytest tests/ -q -m "slow"

chaos: native
	MXNET_TPU_FAULTS="$(CHAOS_FAULTS)" MXNET_TPU_RETRY_BASE_DELAY=0.005 \
		$(PY) -m pytest tests/ -q -m "not slow"
	MXNET_TPU_RETRY_BASE_DELAY=0.005 $(PY) tools/obs_smoke.py --chaos-check

# elastic chaos drill (docs/RESILIENCE.md "Elastic training"): a 4-process
# launch is SIGKILLed mid-run; the supervisor re-forms the mesh (1:1
# replacement, and separately scaled down to 3 under the shrink policy),
# the job resumes from the latest valid manifest checkpoint, and final
# params match the never-killed baseline within documented tolerance —
# with mesh_reformations_total >= 1 and an elastic_restore event carrying
# cause + old/new world size
chaos-elastic: native
	$(PY) -m pytest tests/test_launch_dist.py -q -k "elastic"

# serving chaos drill (docs/RESILIENCE.md "Serving resilience"): batcher
# traffic on a speculative engine under injected gen.* faults, deadline
# pressure, cancellations, a shed-inducing submit burst, and a forced
# accept-rate collapse — asserts no hang, explicit finish reasons on every
# request, bit-identical surviving rows vs an undisturbed baseline,
# speculative fallback + re-arm observed via telemetry, and a clean
# drained state. The failure path stays tested via
# `python tools/servedrill.py --inject-leak`
chaos-serve: native
	$(PY) tools/servedrill.py

# fleet serving chaos drill (docs/INFERENCE.md "Fleet serving"): three
# router-fed replicas on the CPU backend with a deterministic clock; one
# replica is killed and one wedged mid-burst. Asserts zero dropped
# in-deadline requests (redistributed re-runs stay bit-identical to the
# baseline), the wedged replica walks DEGRADED->DRAINING->DEAD with its
# work redistributed, a replacement joins, and the survivors drain to a
# clean empty end state. Failure path stays tested via
# `python tools/servedrill.py --fleet --inject-drop`
chaos-fleet: native
	$(PY) tools/servedrill.py --fleet

# observability gate (docs/OBSERVABILITY.md): a 2-step LeNet train with
# telemetry on must yield a non-empty obs_report summary covering step/
# loss/throughput metrics, >=1 recompile, KVStore byte/latency histograms,
# checkpoint durations, and retry counters that match attempt_log
obs: native
	$(PY) tools/obs_smoke.py

# fleet observability gate (docs/OBSERVABILITY.md "Fleet view"): a
# 4-process launch whose rank 2 is SIGSTOPped mid-run must be flagged as a
# straggler by the fleet aggregator (and surfaced in the supervisor log),
# and the elastic chaos drill's merged fleet report must attribute the
# re-formation interval to downtime — goodput buckets summing to wall time
# (±1%) with a nonzero reformation bucket
obsfleet: native
	$(PY) -m pytest tests/test_launch_dist.py -q -k "fleet"

# fused multi-step window gate (docs/PERFORMANCE.md): CPU dry-run of the
# compiled k-step scan window on a LeNet — asserts ONE window lowering,
# prefetch queue metrics armed, and amortized per-step time strictly below
# the single-step path; artifact committed as BENCH_r06.json
perfwin: native
	$(PY) tools/benchall.py --window 4 --out BENCH_r06.json

# compiled mixed-precision gate (docs/PERFORMANCE.md "Mixed precision"):
# HLO dtype assertions (bf16 dots + f32 master update, f16 loss scaling
# fully in-graph) + buffer-liveness remat delta (>=25% MemoryReport
# temp-peak bytes on the long-context step, the units make memcheck
# gates) + a dispatch-isolated f32-vs-bf16 step-time A/B (recorded, not
# gated on CPU); artifact committed as AMPBENCH_r01.json
ampbench:
	$(PY) tools/ampbench.py --out AMPBENCH_r01.json

test: sanity native
	$(PY) -m pytest tests/ -q

# on the chip only: send it through the chip tool, after `python
# chip_smoke.py`; it fails without a TPU
bench:
	$(PY) bench.py

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
