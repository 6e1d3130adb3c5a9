"""Runtime config / env-var layer (reference SURVEY §5.6: the ``MXNET_*``
env-var tier read via ``dmlc::GetEnv`` at use sites).

One typed module: every knob has a declared type/default and an ``MXNET_*``
alias where the reference semantics survive on TPU. Knobs whose mechanism is
deleted (engine type, GPU mem pool, cuDNN autotune) are accepted and mapped
to their closest analog or a no-op, so reference launch scripts run.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict

__all__ = ["get", "set", "knobs", "describe", "apply_compile_cache"]

# name -> (type, default, env aliases, doc)
_KNOBS: Dict[str, tuple] = {
    "safe_accumulation": (bool, True, ("MXNET_SAFE_ACCUMULATION",),
                          "accumulate low-precision reductions in f32"),
    "engine_type": (str, "xla", ("MXNET_ENGINE_TYPE",),
                    "reference: ThreadedEnginePerDevice/NaiveEngine; here "
                    "'xla' (async) or 'naive' (sync eager via jax.disable_jit "
                    "debugging semantics)"),
    "exec_bulk_exec_train": (bool, True, ("MXNET_EXEC_BULK_EXEC_TRAIN",),
                             "reference op-bulking; here jit fusion (no-op)"),
    "gpu_mem_pool_type": (str, "xla", ("MXNET_GPU_MEM_POOL_TYPE",),
                          "allocator pooling is XLA's BFC arena (no-op)"),
    "cudnn_autotune_default": (int, 0, ("MXNET_CUDNN_AUTOTUNE_DEFAULT",),
                               "XLA autotunes convs itself (no-op)"),
    "kvstore_usetree": (bool, False, ("MXNET_KVSTORE_USETREE",),
                        "comm-tree selection is XLA's collective scheduling"),
    "kvstore_bigarray_bound": (int, 1000000, ("MXNET_KVSTORE_BIGARRAY_BOUND",),
                               "kept for API compat"),
    "use_fusion": (bool, True, ("MXNET_USE_FUSION",),
                   "pointwise fusion — always on via XLA"),
    # -- Pallas kernel selection. "v5e, PR 21" = chip_smoke.py /
    # tools/kernelbench.py on one TPU v5e under jax 0.9.0, Mosaic-compiled
    # (interpret=False) and compared with the XLA composition; speed
    # against XLA is not measured for any of them yet. The packed attention
    # kernel (ops/pallas_packed_attention.py) has no knob on purpose: its
    # gate reads backend, dtype, shapes, mask form and mesh. v5e, PR 25
    # (chip_smoke.py check_packed): (64, 128, 3 x 1024) bf16 with BERT's key
    # mask compiles, forward and backward, 0.004% (out, dv) and 0.36% (dq,
    # dk) from the einsum path, no gradient on a masked key ----------------
    "fused_layernorm": (bool, False, ("MXNET_TPU_FUSED_LAYERNORM",),
                        "route LayerNorm through the Pallas kernel on TPU "
                        "(opt-in. v5e, PR 21: compiles and agrees to one "
                        "bf16 step at 8192-32768 rows x 1024-4096 wide)"),
    "flash_attention": (bool, True, ("MXNET_TPU_FLASH_ATTENTION",),
                        "use the Pallas flash kernel when shapes allow "
                        "(no mask, seq >= 2048. v5e, PR 21: forward and "
                        "backward compile and agree with the einsum "
                        "reference within 0.4% at seq 2048, causal and "
                        "not, and at seq 4096; head 64)"),
    "flash_pallas_bwd": (bool, True, ("MXNET_TPU_FLASH_PALLAS_BWD",),
                         "FlashAttention-2 Pallas backward kernels (dq + "
                         "dkv); off = XLA chunked-recompute backward "
                         "(kernel-free)"),
    "paged_attention_kernel": (bool, True, ("MXNET_TPU_PAGED_ATTENTION_KERNEL",),
                               "paged attention reads the pools through "
                               "the Pallas kernel (the pages a row holds) "
                               "where ops.pallas_paged_attention."
                               "paged_attention_refusal passes: a TPU, no "
                               "mesh, float32/bfloat16, heads that fill "
                               "128-lane tiles (GPT-2's 64 do), page size, "
                               "VMEM; else, and off, the XLA gather"),
    "fused_adam": (bool, False, ("MXNET_TPU_FUSED_ADAM",),
                   "route Adam/AdamW updates through the fused Pallas "
                   "kernel on TPU (one pass over grad/m/v/master; opt-in. "
                   "v5e, PR 21: compiles and equals the XLA chain exactly "
                   "at 2^20 and 2^24 elements)"),
    "fused_softmax_xent": (bool, False, ("MXNET_TPU_FUSED_SOFTMAX_XENT",),
                           "fused softmax-cross-entropy Pallas kernel "
                           "(custom VJP) for sparse-label gluon loss on "
                           "TPU (opt-in. v5e, PR 21: at 128 rows a block "
                           "Mosaic refused it — 'Ran out of memory in "
                           "memory space vmem ... Scoped allocation with "
                           "size 33.00M and limit 16.00M' — and with the "
                           "row block scaled to the class count it "
                           "compiles and agrees within 1e-5 at 8192 x "
                           "32768 and 16384 x 50304)"),
    "default_dtype": (str, "float32", ("MXNET_DEFAULT_DTYPE",), "creation dtype"),
    "storage_fallback_warn": (bool, True, ("MXNET_STORAGE_FALLBACK_WARN",),
                              "warn when a sparse input densifies at an op "
                              "boundary (reference: 'Storage type fallback' "
                              "log in executor/infer_graph_attr_pass)"),
    "profiler_dir": (str, "/tmp/mxnet_tpu_profile", ("MXNET_PROFILER_DIR",),
                     "xplane trace output directory"),
    "num_cpu_workers": (int, 4, ("MXNET_CPU_WORKER_NTHREADS", "OMP_NUM_THREADS"),
                        "host-side data worker default"),
    # -- resilience subsystem (docs/RESILIENCE.md) ---------------------------
    "faults": (str, "", ("MXNET_TPU_FAULTS",),
               "fault-injection spec armed at import, e.g. "
               "'ckpt.save:every=3;kv.dcn_psum:on=2:times=2;seed=7' — "
               "deterministic failures at named sites for chaos testing"),
    "retry_max_attempts": (int, 3, ("MXNET_TPU_RETRY_MAX_ATTEMPTS",),
                           "attempts per IO/DCN site before RetryError"),
    "retry_base_delay": (float, 0.05, ("MXNET_TPU_RETRY_BASE_DELAY",),
                         "first backoff delay in seconds"),
    "retry_max_delay": (float, 2.0, ("MXNET_TPU_RETRY_MAX_DELAY",),
                        "backoff ceiling in seconds"),
    "retry_jitter": (float, 0.25, ("MXNET_TPU_RETRY_JITTER",),
                     "max fractional jitter added to each backoff delay"),
    "retry_timeout": (float, 0.0, ("MXNET_TPU_RETRY_TIMEOUT",),
                      "per-site wall-clock budget across all attempts of "
                      "one call, seconds (0 = unlimited)"),
    "ckpt_keep_last": (int, 0, ("MXNET_TPU_CKPT_KEEP_LAST",),
                       "retention sweep after each save_train_state: keep "
                       "the newest N committed checkpoints (0 = keep all)"),
    "ckpt_sharded": (bool, False, ("MXNET_TPU_CKPT_SHARDED",),
                     "force the world-size-agnostic npz-shards checkpoint "
                     "format even for fully-addressable single-process "
                     "state (multi-process and non-addressable saves use "
                     "it regardless)"),
    # -- elastic training (docs/RESILIENCE.md "Elastic training") ------------
    "dist_init_retries": (int, 3, ("MXNET_TPU_DIST_INIT_RETRIES",),
                          "attempts for jax.distributed bootstrap (site "
                          "dist.init) — a replacement worker joining before "
                          "the coordinator port is up retries instead of "
                          "hard-failing"),
    "dist_init_timeout": (float, 0.0, ("MXNET_TPU_DIST_INIT_TIMEOUT",),
                          "per-attempt jax.distributed.initialize timeout "
                          "in seconds (0 = jax default)"),
    "elastic_hb_interval": (float, 0.5, ("MXNET_TPU_ELASTIC_HB_INTERVAL",),
                            "seconds between heartbeat-file touches"),
    "elastic_hb_timeout": (float, 5.0, ("MXNET_TPU_ELASTIC_HB_TIMEOUT",),
                           "heartbeat staleness after which a peer counts "
                           "as lost and the worker requests a mesh "
                           "re-formation"),
    # -- serving resilience (docs/RESILIENCE.md "Serving resilience") --------
    "serve_default_deadline": (float, 0.0, ("MXNET_TPU_SERVE_DEADLINE",),
                               "default per-request deadline in seconds "
                               "applied at submit when the caller passes "
                               "none (0 = no deadline)"),
    "serve_max_queue": (int, 0, ("MXNET_TPU_SERVE_MAX_QUEUE",),
                        "bounded admission queue: submits past this depth "
                        "are shed per serve_queue_policy (0 = unbounded)"),
    "serve_queue_policy": (str, "reject", ("MXNET_TPU_SERVE_QUEUE_POLICY",),
                           "full-queue policy: 'reject' sheds the NEW "
                           "request; 'shed' evicts the oldest queued "
                           "request already past its deadline (falls back "
                           "to reject when none is)"),
    "serve_shed_page_floor": (int, 0, ("MXNET_TPU_SERVE_SHED_PAGE_FLOOR",),
                              "load-shed watermark: with a backlog queued, "
                              "shed new submits while free KV pages are "
                              "below this floor (0 = off)"),
    "serve_head_aging_steps": (int, 8, ("MXNET_TPU_SERVE_HEAD_AGING_STEPS",),
                               "admission aging guard: after this many "
                               "step-boundary deferrals of the queue head "
                               "on free pages, freed pages are reserved "
                               "for the head and bypass admission stops "
                               "(prevents head starvation behind a stream "
                               "of small requests)"),
    "serve_spec_window": (int, 8, ("MXNET_TPU_SERVE_SPEC_WINDOW",),
                          "speculative accept-rate window (rounds) the "
                          "degradation governor decides on"),
    "serve_spec_floor": (float, 0.125, ("MXNET_TPU_SERVE_SPEC_FLOOR",),
                         "windowed accept rate below which speculation "
                         "falls back to plain paged decode (break-even "
                         "is ~1/speculate_k)"),
    "serve_spec_cooldown": (int, 16, ("MXNET_TPU_SERVE_SPEC_COOLDOWN",),
                            "plain decode steps before a fallen-back "
                            "engine re-arms speculation"),
    "serve_watchdog_s": (float, 0.0, ("MXNET_TPU_SERVE_WATCHDOG_S",),
                         "soft per-dispatch timeout for the serving loop: "
                         "a dispatch exceeding it emits gen_stuck_dispatch "
                         "(event + counter) instead of hanging silently "
                         "(0 = off)"),
    # -- fleet serving tier (docs/INFERENCE.md "Fleet serving") --------------
    "router_hb_timeout": (float, 5.0, ("MXNET_TPU_ROUTER_HB_TIMEOUT",),
                          "replica heartbeat staleness (seconds since the "
                          "last published snapshot) after which fleet "
                          "health marks it DEGRADED"),
    "router_drain_after": (float, 5.0, ("MXNET_TPU_ROUTER_DRAIN_AFTER",),
                           "seconds a replica may stay DEGRADED before the "
                           "router drains it (no new admissions, queued "
                           "work redistributed)"),
    "router_dead_grace": (float, 30.0, ("MXNET_TPU_ROUTER_DEAD_GRACE",),
                          "seconds a DRAINING replica gets for in-flight "
                          "rows to finish or expire before it is declared "
                          "DEAD and its remaining work redistributed"),
    "router_queue_bound": (int, 4, ("MXNET_TPU_ROUTER_QUEUE_BOUND",),
                           "max published admission-queue depth the router "
                           "will dispatch onto; deeper replicas keep the "
                           "request in the router backlog"),
    "router_classes": (str, "interactive,normal,batch",
                       ("MXNET_TPU_ROUTER_CLASSES",),
                       "priority classes in admission order (first = "
                       "dispatched first under contention)"),
    "router_affinity": (bool, True, ("MXNET_TPU_ROUTER_AFFINITY",),
                        "pin a session's requests to the replica holding "
                        "its prefix pages while that replica is LIVE"),
    "router_seed": (int, 0, ("MXNET_TPU_ROUTER_SEED",),
                    "seed for the power-of-two-choices candidate sampling "
                    "(deterministic routing in drills and tests)"),
    "router_prefix_tokens": (int, 16, ("MXNET_TPU_ROUTER_PREFIX_TOKENS",),
                             "sessionless affinity: requests whose first N "
                             "prompt tokens match are routed to the same "
                             "replica so its radix prefix cache keeps the "
                             "shared pages hot; 0 disables"),
    # -- request tracing + SLO ledger (docs/OBSERVABILITY.md
    #    "Request tracing & SLO ledger") -------------------------------------
    "trace": (bool, False, ("MXNET_TPU_TRACE",),
              "per-request span tracing for the serving tier: router and "
              "replicas append span JSONL into the fleet dir, joined by "
              "request id at aggregation (off = one attribute read per "
              "emission site)"),
    "trace_sample": (float, 0.01, ("MXNET_TPU_TRACE_SAMPLE",),
                     "fraction of HEALTHY traces whose spans are kept "
                     "(deterministic hash of trace id, so router and "
                     "replicas agree without coordinating); anomalous/"
                     "slow/low-margin traces are always kept"),
    "trace_seed": (int, 0, ("MXNET_TPU_TRACE_SEED",),
                   "seed of the deterministic healthy-sampling hash"),
    "trace_slow_pct": (float, 95.0, ("MXNET_TPU_TRACE_SLOW_PCT",),
                       "tail-sampling slow percentile: traces at or above "
                       "this percentile of recent end-to-end latency are "
                       "always kept"),
    "trace_margin_floor": (float, 0.0, ("MXNET_TPU_TRACE_MARGIN_FLOOR",),
                           "deadline-margin floor (seconds): a trace "
                           "finishing with less margin is always kept AND "
                           "requests a measured-profile capture on its "
                           "replica (prof-request contract); 0 = off"),
    "trace_slo_target": (float, 0.99, ("MXNET_TPU_TRACE_SLO_TARGET",),
                         "SLO attainment target the burn rates are "
                         "computed against (burn = violation rate / "
                         "(1 - target); > 1 burns budget)"),
    "trace_slo_windows": (str, "60,300,3600", ("MXNET_TPU_TRACE_SLO_WINDOWS",),
                          "comma-separated burn-rate window lengths in "
                          "seconds, anchored at the newest finish "
                          "timestamp the aggregator sees"),
    # -- observability subsystem (docs/OBSERVABILITY.md) ---------------------
    "telemetry": (bool, False, ("MXNET_TPU_TELEMETRY",),
                  "arm hot-path telemetry at first use: step/comm/data/ckpt "
                  "metrics + the JSONL event log (off = one bool check per "
                  "instrumented call)"),
    "telemetry_dir": (str, "/tmp/mxnet_tpu_telemetry", ("MXNET_TPU_TELEMETRY_DIR",),
                      "run directory for events-h{host}.jsonl + metrics.json/"
                      ".prom exports"),
    "telemetry_rotate_mb": (int, 64, ("MXNET_TPU_TELEMETRY_ROTATE_MB",),
                            "event-log rotation threshold per file (rotated "
                            "segments are gzip-compressed)"),
    "events_keep_bytes": (int, 0, ("MXNET_TPU_EVENTS_KEEP_BYTES",),
                          "cap on total bytes of retained rotated event-log "
                          "segments (.jsonl.N.gz); 0 = keep exactly one "
                          "rotated segment (the pre-cap behavior)"),
    # -- measured profiling (docs/OBSERVABILITY.md "Measured profiling") -----
    "prof_every_n_steps": (int, 0, ("MXNET_TPU_PROF_EVERY_N_STEPS",),
                           "trace every N-th training step into a capture "
                           "dir (periodic measured baseline); 0 = off"),
    "prof_keep_bytes": (int, 512 * 1024 * 1024, ("MXNET_TPU_PROF_KEEP_BYTES",),
                        "retention cap on total bytes of kept step-capture "
                        "trace dirs (oldest swept first, newest always "
                        "kept); 0 = unbounded"),
    # -- fleet observability (docs/OBSERVABILITY.md "Fleet view") ------------
    "fleet_dir": (str, "", ("MXNET_TPU_FLEET_DIR",),
                  "shared directory for cross-rank telemetry snapshots "
                  "(telemetry-h{rank}/ per rank, same contract as the "
                  "elastic heartbeat dir); empty = fleet snapshots off"),
    "fleet_snapshot_interval": (float, 5.0,
                                ("MXNET_TPU_FLEET_SNAPSHOT_INTERVAL",),
                                "seconds between per-rank fleet telemetry "
                                "snapshots"),
    "straggler_factor": (float, 3.0, ("MXNET_TPU_STRAGGLER_FACTOR",),
                         "a rank whose step / collective-wait time exceeds "
                         "the fleet median by this factor is flagged as a "
                         "straggler"),
    "peak_flops": (float, 0.0, ("MXNET_TPU_PEAK_FLOPS",),
                   "accelerator peak FLOP/s per process for train_mfu "
                   "(e.g. 1.97e14 for one v5e chip); 0 = MFU not computed"),
}

_values: Dict[str, Any] = {}
# set() may be called while loader/telemetry threads resolve knobs (JH005)
_values_lock = threading.Lock()


def _coerce(typ, raw):
    if typ is bool:
        return str(raw).lower() in ("1", "true", "yes", "on")
    return typ(raw)


def get(name: str):
    if name in _values:
        return _values[name]
    typ, default, envs, _doc = _KNOBS[name]
    for e in envs:
        if e in os.environ:
            return _coerce(typ, os.environ[e])
    return default


def set(name: str, value) -> None:
    typ, _d, _e, _doc = _KNOBS[name]
    with _values_lock:
        _values[name] = _coerce(typ, value)


def knobs():
    return sorted(_KNOBS)


def describe(name: str) -> str:
    typ, default, envs, doc = _KNOBS[name]
    return f"{name} ({typ.__name__}, default={default!r}, env={'/'.join(envs)}): {doc}"


#: where the persistent XLA compilation cache lives unless the environment
#: places it: one fixed directory inside the checkout (git-ignored). The
#: path is part of the cache key, so it never carries a temporary name, a
#: pid or a time.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def apply_compile_cache():
    """Place jax's persistent compilation cache, so a restarted run skips
    XLA compilation of every program it has compiled before. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and nothing
    is set here; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.
    jax's own thresholds decide what is worth an entry. Called from
    package import; returns the directory in effect."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
