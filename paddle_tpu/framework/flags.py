"""Global flag registry.

Reference parity: paddle/fluid/platform/flags.cc (27 DEFINE_* gflags),
pybind/global_value_getter_setter.cc:325 (REGISTER_PUBLIC_GLOBAL_VAR) and the
Python bridge paddle.set_flags/get_flags (python/paddle/fluid/framework.py:5743).

TPU-first: one Python-side registry; every flag can be seeded from the
environment (``FLAGS_xxx=...``) at import, exactly like InitGflags
(platform/init.h:34) parses env on startup. Subsystems read flags lazily so
set_flags takes effect between steps.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional

_REGISTRY: Dict[str, "_Flag"] = {}


class _Flag:
    __slots__ = ("name", "value", "default", "doc", "validator", "writable")

    def __init__(self, name, default, doc="", validator=None, writable=True):
        self.name = name
        self.default = default
        self.doc = doc
        self.validator = validator
        self.writable = writable
        self.value = self._from_env(default)

    def _from_env(self, default):
        raw = os.environ.get("FLAGS_" + self.name)
        if raw is None:
            return default
        if isinstance(default, bool):
            return raw.lower() in ("1", "true", "yes", "on")
        if isinstance(default, int):
            return int(raw)
        if isinstance(default, float):
            return float(raw)
        return raw


def define_flag(name: str, default: Any, doc: str = "",
                validator: Optional[Callable[[Any], bool]] = None,
                writable: bool = True) -> None:
    if name in _REGISTRY:
        # Re-registration with the SAME default is an idempotent no-op
        # (module reload); a DIFFERENT default used to silently overwrite
        # nothing -- the second caller believed its default won when the
        # first registration's value stayed live.  Make the conflict loud.
        prev = _REGISTRY[name]
        if prev.default != default or type(prev.default) is not type(default):
            raise ValueError(
                f"flag {name!r} is already registered with default "
                f"{prev.default!r}; re-registration with a different "
                f"default {default!r} would be silently ignored -- "
                f"rename the flag or reuse the existing registration")
        return
    _REGISTRY[name] = _Flag(name, default, doc, validator, writable)


def flags_snapshot() -> Dict[str, Any]:
    """Snapshot every flag's CURRENT value -> {name: value}.  Pair with
    :func:`flags_restore` so tests mutate flags without hand-rolled
    try/finally bookkeeping::

        snap = flags_snapshot()
        try:
            set_flags({"FLAGS_graph_lint": "error"})
            ...
        finally:
            flags_restore(snap)
    """
    return {name: f.value for name, f in _REGISTRY.items()}


def flags_restore(snapshot: Dict[str, Any]) -> None:
    """Restore values captured by :func:`flags_snapshot`.  Bypasses the
    writable/validator gates (the values were live before, so they are
    valid by construction); flags registered after the snapshot keep
    their current value."""
    for name, value in snapshot.items():
        f = _REGISTRY.get(name)
        if f is not None:
            f.value = value


def set_flags(flags: Dict[str, Any]) -> None:
    """paddle.set_flags parity (framework.py:5743)."""
    for name, value in flags.items():
        key = name[6:] if name.startswith("FLAGS_") else name
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {name!r}")
        flag = _REGISTRY[key]
        if not flag.writable:
            raise ValueError(f"flag {name!r} is not public-writable")
        if flag.validator is not None and not flag.validator(value):
            raise ValueError(f"invalid value {value!r} for flag {name!r}")
        flag.value = value


def get_flags(flags) -> Dict[str, Any]:
    """paddle.get_flags parity (framework.py:5766)."""
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for name in flags:
        key = name[6:] if name.startswith("FLAGS_") else name
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {name!r}")
        out[name] = _REGISTRY[key].value
    return out


def flag(name: str) -> Any:
    return _REGISTRY[name].value


def all_flags() -> Dict[str, Any]:
    return {f"FLAGS_{k}": v.value for k, v in _REGISTRY.items()}


# ---- Core flags (subset of platform/flags.cc relevant on TPU) ----------------
define_flag("check_nan_inf", False,
            "Sweep op outputs for NaN/Inf each eager op (flags.cc:45 parity; "
            "TPU impl uses jnp.isfinite reductions).")
define_flag("benchmark", False,
            "Synchronize after every eager op and record timings "
            "(operator.cc:1163 parity; TPU impl: block_until_ready per op).")
define_flag("eager_delete_tensor_gb", 0.0,
            "GC threshold parity (flags.cc); no-op on TPU (XLA owns buffers).")
define_flag("use_pallas_kernels", True,
            "Lower hot fused ops (attention, layernorm) through Pallas TPU "
            "kernels when running on TPU; fall back to jnp otherwise.")
define_flag("use_pallas_fused_bn", False,
            "Route channels-last train-mode batch_norm through the Pallas "
            "fused-BN kernels (ops/pallas/fused_bn.py). OFF by default: "
            "measured SLOWER end-to-end than XLA's own epilogue fusion on "
            "the v5e bench chip (974 vs 1971 img/s ResNet-50) -- see "
            "PERF.md's round-4 roofline correction.")
define_flag("use_pallas_fused_conv", False,
            "Route eligible NHWC train-mode conv+BN(+ReLU) chains (and the "
            "space-to-depth ResNet stem) through the fused Pallas conv "
            "pipeline (ops/pallas/fused_conv.py). OFF by default under the "
            "measured-crossover honesty rule: the default flips only with "
            "an end-to-end ResNet-50 win recorded on the bench chip in "
            "PERF.md round-6 (the BN-only predecessor measured 974 vs 1971 "
            "img/s because opaque customs break XLA's conv fusion; this "
            "kernel owns the whole chain precisely to beat that). Legacy "
            "env PADDLE_TPU_PALLAS_CONV=1 also honored.")
define_flag("allocator_strategy", "auto_growth",
            "allocator_strategy parity (allocator_strategy.h:21); informational "
            "on TPU -- PJRT owns HBM via BFC.")
define_flag("cudnn_deterministic", False,
            "Determinism flag parity (flags.cc:98); on TPU compiled programs "
            "are deterministic by default.")
define_flag("fraction_of_gpu_memory_to_use", 0.92,
            "Memory-fraction parity; forwarded informationally.")
define_flag("paddle_num_threads", 1, "Host-side intra-op threads parity.")
define_flag("static_executor_mode", "fused",
            "'fused' compiles a whole Program into one XLA computation "
            "(idiomatic TPU); 'op_by_op' interprets per-op for debugging "
            "(executor.cc:473 hot-loop parity).")
define_flag("enable_profiler",
            os.environ.get("PADDLE_TPU_PROFILE", "").lower()
            in ("1", "true", "yes", "on"),
            "Emit host-side RecordEvent spans from the instrumented "
            "runtime paths (static executor, @to_static dispatch, "
            "TrainStep, device.synchronize) even outside an active "
            "profiler.Profiler record window. Seeded by FLAGS_enable_"
            "profiler or PADDLE_TPU_PROFILE; a Profiler's record phase "
            "turns the spans on regardless of this flag.")
define_flag("train_sentinel",
            os.environ.get("PADDLE_TPU_SENTINEL", "").lower()
            in ("1", "true", "yes", "on"),
            "In-graph numerics sentinel: one fused isfinite reduction over "
            "loss + gradients inside the jitted train step; a non-finite "
            "step is skipped in-graph (params/opt state keep their old "
            "values) and counted in the train_skipped_steps gauge. "
            "Off-path cost when disabled: one Python branch at trace "
            "time, zero graph change. Seeded by PADDLE_TPU_SENTINEL.")
define_flag("sentinel_max_bad_steps", 8,
            "Abort bound for the numerics sentinel: this many CONSECUTIVE "
            "skipped (non-finite) steps raises FloatingPointError with a "
            "diagnostic dump (offending tensor, step, last-good "
            "checkpoint) instead of silently burning the job.",
            validator=lambda v: int(v) >= 1)
define_flag("ckpt_keep", 3,
            "Checkpoint retention: the CheckpointManager keeps this many "
            "newest COMPLETE step checkpoints and GCs the rest (plus "
            "crashed-save debris older than the newest complete step). "
            "0 keeps everything.",
            validator=lambda v: int(v) >= 0)
define_flag("store_max_retries", 3,
            "TCPStore client ops (set/get/add/wait) retry transient "
            "socket errors (ECONNRESET, timeouts) this many times with "
            "exponential backoff + jitter, reconnecting between attempts "
            "— a bounced rendezvous server no longer kills workers.",
            validator=lambda v: int(v) >= 0)
define_flag("store_retry_backoff", 0.05,
            "Base delay (seconds) of the TCPStore retry backoff; attempt "
            "k sleeps base * 2^k plus up to 50% deterministic jitter.",
            validator=lambda v: float(v) > 0)
define_flag("use_int8_inference",
            os.environ.get("PADDLE_TPU_INT8", "").lower()
            in ("1", "true", "yes", "on"),
            "Serve frozen int8 inference programs: the Predictor prefers a "
            "model prefix's '.int8' sibling artifact (quantization/"
            "freeze.py save_int8_model) and keys its AOT executable cache "
            "on the quant signature so int8 and float executables never "
            "collide. Off-path cost: one Python branch at predictor "
            "construction. Seeded by PADDLE_TPU_INT8.")
define_flag("wide_deep_device_dedup",
            os.environ.get("PADDLE_TPU_WD_DEDUP", "").lower()
            in ("1", "true", "yes", "on"),
            "Wide&Deep cached-mode id dedup runs ON DEVICE (static-shape "
            "sort-based unique + segment-ids, rec/wide_deep.py) instead of "
            "host np.unique over the full B*S id block; the host resolves "
            "only the deduped prefix against the hot-row cache. OFF by "
            "default pending a chip measurement (PERF.md int8/dedup "
            "round); the hot-row cache and capacity behavior are "
            "unchanged. Seeded by PADDLE_TPU_WD_DEDUP.")
define_flag("jit_ledger_dir",
            os.environ.get("PADDLE_TPU_JIT_LEDGER_DIR", ""),
            "When non-empty, recompile-ledger events (profiler.ledger) "
            "additionally stream as JSONL via utils.monitor.LogWriter "
            "into this directory. The in-memory event ring and the "
            "jit_compile_count/jit_cache_hit/jit_compile_ms_total stats "
            "are always maintained.")
define_flag("graph_lint",
            os.environ.get("PADDLE_TPU_GRAPH_LINT", "off").lower()
            or "off",
            "Graph-lint tri-state (paddle_tpu.analysis): 'off' = no "
            "analysis (one Python branch per compile, zero per step); "
            "'warn' = run the pass suite over every fresh jit/Executor/"
            "TrainStep trace and emit GraphLintWarning + gauges/JSONL; "
            "'error' = additionally raise EnforceError at trace time on "
            "ERROR-severity findings (host-transfer, donation, "
            "collective-consistency). Seeded by PADDLE_TPU_GRAPH_LINT.",
            validator=lambda v: str(v).lower() in ("off", "warn", "error"))
define_flag("graph_lint_suppress", "",
            "Comma-separated lint pass ids to skip (e.g. "
            "'layout,dead-fetch'); the scoped analysis.suppress() context "
            "manager composes with this.")
define_flag("hlo_audit",
            os.environ.get("PADDLE_TPU_HLO_AUDIT", "off").lower()
            or "off",
            "Compiled-program audit tri-state (paddle_tpu.analysis.hlo): "
            "'off' = no audit (one Python branch per fresh TrainStep "
            "compile, zero per step); 'warn' = AOT-relower every fresh "
            "train-step signature, inspect the partitioned HLO "
            "(collective census, ZeRO layout contract, per-device "
            "memory) and emit HloAuditWarning + hlo_audit_* gauges/"
            "JSONL; 'error' = additionally raise EnforceError BEFORE "
            "the step executes when an ERROR-severity finding fires "
            "(hlo-full-gather: de-sharded ZeRO state). NB: warn/error "
            "add one extra XLA compile per fresh signature (the audit "
            "lowers its own executable). Seeded by PADDLE_TPU_HLO_AUDIT.",
            validator=lambda v: str(v).lower() in ("off", "warn", "error"))
define_flag("hlo_audit_dir",
            os.environ.get("PADDLE_TPU_HLO_AUDIT_DIR", ""),
            "When non-empty, every HLO-audit diagnostic additionally "
            "streams as JSONL via utils.monitor.LogWriter into this "
            "directory (next to the recompile ledger's "
            "PADDLE_TPU_JIT_LEDGER_DIR sink). Gauges are always "
            "maintained.")
define_flag("hlo_audit_hbm_gb", 16.0,
            "Per-device HBM budget (GiB) for the hlo-memory-budget audit "
            "pass: a compiled step whose per-device args+outputs+temps+"
            "code exceed it is flagged. Default 16 GiB (v5e).",
            validator=lambda v: float(v) > 0)
define_flag("hlo_audit_collective_budget", 0.9,
            "Collective-bound threshold for the hlo-collective-budget "
            "audit pass: flagged when ring-model interconnect wire bytes "
            "exceed this fraction of the program's total bytes accessed "
            "(cost_analysis) — the step scales with the network, not the "
            "chip.",
            validator=lambda v: float(v) > 0)
define_flag("graph_lint_dir",
            os.environ.get("PADDLE_TPU_GRAPH_LINT_DIR", ""),
            "When non-empty, every lint diagnostic additionally streams "
            "as JSONL via utils.monitor.LogWriter into this directory "
            "(next to the recompile ledger's PADDLE_TPU_JIT_LEDGER_DIR "
            "sink). Gauges are always maintained.")
define_flag("autoshard",
            os.environ.get("PADDLE_TPU_AUTOSHARD", "off").lower()
            or "off",
            "Auto-sharding tri-state (paddle_tpu.analysis.autoshard): "
            "'off' = no rule matching (one Python branch per TrainStep "
            "state init, zero per step); 'propose' = compute the "
            "rules-table sharding plan for every TrainStep model and "
            "publish it (autoshard_* gauges + graph-lint JSONL sink) "
            "WITHOUT mutating annotations; 'apply' = additionally write "
            "the proposed PartitionSpecs onto unannotated parameters "
            "before the sharding tree is built (hand shard_parameter "
            "annotations always win; a contradicting rule is an "
            "autoshard-conflict lint finding, ERROR severity). Seeded "
            "by PADDLE_TPU_AUTOSHARD.",
            validator=lambda v: str(v).lower() in ("off", "propose",
                                                   "apply"))
define_flag("autoshard_rules",
            os.environ.get("PADDLE_TPU_AUTOSHARD_RULES", "default")
            or "default",
            "Which PartitionRules table drives auto-sharding (and the "
            "rule-naming in sharding-coverage diagnostics): 'default' "
            "(transformer+conv+embedding), 'transformer', 'conv', "
            "'embedding', or any name published via "
            "analysis.autoshard.register_rules_table. Resolution is "
            "lazy, so custom tables may register after import. Seeded "
            "by PADDLE_TPU_AUTOSHARD_RULES.",
            validator=lambda v: bool(str(v).strip()))

# ---- Mesh-sharded embedding tables (paddle_tpu.rec.sharded_embedding) -------
define_flag("sharded_embedding",
            os.environ.get("PADDLE_TPU_SHARDED_EMB", "").lower()
            in ("1", "true", "yes", "on"),
            "Row-partition the CTR deep-leg embedding table over a mesh "
            "axis with in-graph all-to-all lookup (rec/sharded_embedding."
            "py): deduped ids bucket by owner shard, route via "
            "lax.all_to_all inside shard_map, gather from the local table "
            "slice and route back — the HeterPS hashtable seat done "
            "TPU-style, opening tables single-chip HBM cannot hold. "
            "Consumed by WideDeepTrainer (cached mode: the hot-row device "
            "cache short-circuits the all-to-all for the skewed head; "
            "only cache misses route) and HeterTrainer (device service "
            "leg). OFF by default: the replicated/host-table path is "
            "unchanged and bit-identical (one Python branch at trainer "
            "construction). Seeded by PADDLE_TPU_SHARDED_EMB.")
define_flag("sharded_embedding_axis", "dp",
            "Mesh axis the sharded embedding tables row-partition over "
            "(P(axis, None) on the table parameter, so ZeRO/autoshard "
            "layering composes). 'dp' rides the widest axis of CTR "
            "meshes; any named axis of the live mesh is accepted.",
            validator=lambda v: str(v) in ("dp", "mp", "pp", "sp"))
define_flag("sharded_embedding_bucket_cap", 0,
            "Static per-destination bucket capacity for the all-to-all "
            "routing (ids each shard may send to one owner per step). 0 "
            "= auto: the safe cap (the shard's whole request slice — no "
            "overflow possible). A positive cap shrinks the routed "
            "buffers for flat id distributions; the trainers detect "
            "overflow (one scalar D2H, the device-dedup protocol) and "
            "re-run one octave up, so a too-small cap costs recompiles, "
            "never correctness.",
            validator=lambda v: int(v) >= 0)

# ---- Expert-parallel Mixture-of-Experts (paddle_tpu.nn.layer.moe) -----------
define_flag("moe_capacity_factor",
            float(os.environ.get("PADDLE_TPU_MOE_CAPACITY_FACTOR", "1.25")
                  or 1.25),
            "Default capacity factor of MoE token dispatch: each routing "
            "group may park at most ceil(cf * tokens * top_k / E) "
            "assignments on one expert; overflow assignments DROP (the "
            "token keeps its residual) and are counted in the "
            "moe_tokens_dropped_total metric.  1.0 = exactly-balanced "
            "budget, 1.25 = the usual head-room.  Only consulted when a "
            "MoELayer/GPTMoEConfig leaves capacity_factor unset; models "
            "without MoE layers are untouched (dense FFN is the default "
            "everywhere).  Seeded by PADDLE_TPU_MOE_CAPACITY_FACTOR.",
            validator=lambda v: float(v) > 0)
define_flag("moe_top_k", 2,
            "Default top-k of MoE softmax gating (k experts per token; "
            "k=2 renormalizes the chosen pair, k=1 is the Switch rule). "
            "Only consulted when a MoELayer/GPTMoEConfig leaves top_k "
            "unset.  Seeded by FLAGS_moe_top_k.",
            validator=lambda v: int(v) in (1, 2))
define_flag("moe_axis",
            os.environ.get("PADDLE_TPU_MOE_AXIS", "ep") or "ep",
            "Mesh axis MoE expert stacks shard over (P(axis, None, None) "
            "on the stacked expert parameters) and token rows route "
            "across: 'ep' is the dedicated expert-parallel axis "
            "(parallel.mesh.EP_AXIS); 'dp' rides the data axis (classic "
            "EP=DP).  A mesh without the axis falls back to the meshless "
            "local dispatch (single shard, no all_to_all).  The "
            "autoshard 'expert' rules table reads this flag, so rule "
            "proposals and layer annotations always name the same axis. "
            "Seeded by PADDLE_TPU_MOE_AXIS.",
            validator=lambda v: str(v) in ("ep", "dp", "mp", "pp", "sp"))

# ---- Serving engine (paddle_tpu.serving) ------------------------------------
define_flag("serving_buckets", "1,2,4,8,16,32,64",
            "Default batch-bucket ladder for the serving engine: pending "
            "requests continuously batch into the smallest bucket that "
            "holds them and pad up, so steady-state serving only ever "
            "executes shapes compiled at warm-up (zero recompiles). "
            "Per-model override via ModelSpec(buckets=...).",
            validator=lambda v: all(int(b) > 0 for b in
                                    str(v).split(",") if b.strip()))
define_flag("serving_workers", 2,
            "Serving worker threads per Server; each worker runs its own "
            "Predictor.clone() (AnalysisPredictor::Clone seat: shared "
            "weights + executables, per-clone IO buffers).",
            validator=lambda v: int(v) >= 1)
define_flag("serving_queue_capacity", 1024,
            "Bound on requests pending in the serving queue; submit() past "
            "it blocks up to its timeout then raises UnavailableError "
            "(backpressure instead of unbounded host memory).",
            validator=lambda v: int(v) >= 1)
define_flag("serving_batch_timeout_ms", 2.0,
            "How long the continuous batcher holds a non-full batch open "
            "for more arrivals before dispatching what it has. 0 "
            "dispatches immediately (lowest latency, smallest batches).",
            validator=lambda v: float(v) >= 0)
define_flag("serving_pipeline_depth", 2,
            "Batches a worker keeps in flight on device before fencing "
            "the oldest: H2D + dispatch of batch N+1 overlap execution "
            "of batch N (jit-served models; the executor path is "
            "synchronous). 1 disables pipelining.",
            validator=lambda v: int(v) >= 1)
define_flag("serving_strict", True,
            "Steady-state shape discipline: a batch whose bucket has no "
            "warm-up-compiled executable FAILS (its requests get "
            "EnforceError) instead of compiling on the fly. Disable only "
            "for debugging; any fallback compile is ledgered and counted "
            "in the serving_steady_compiles gauge either way.")
define_flag("serving_metrics_window", 2048,
            "Sliding-window size (completed requests) of the per-model "
            "serving latency reservoir behind the p50/p99 gauges.",
            validator=lambda v: int(v) >= 16)

# ---- Multi-host cluster serving (paddle_tpu.serving.cluster) ----------------
define_flag("serving_replicas",
            int(os.environ.get("PADDLE_TPU_SERVING_REPLICAS", "1")),
            "Replica count the cluster serving CLI (tools/serve.py "
            "--router) spawns behind the front-end router. 1 (the "
            "default) is the single-process path — no router, no RPC, "
            "one branch.",
            validator=lambda v: int(v) >= 1)
define_flag("serving_role",
            os.environ.get("PADDLE_TPU_SERVING_ROLE", "both").lower()
            or "both",
            "Worker-pool role of this serving process: 'both' (default; "
            "full prefill+decode grids, single-process behavior "
            "unchanged), 'prefill' (compute-bound pool: warm-up compiles "
            "ONLY the prefill grid, serves prefill_handoff), or 'decode' "
            "(memory-bound pool: ONLY the decode grid, serves "
            "decode_from_handoff). Disaggregation is these two pools "
            "plus the explicit KV-cache handoff between them.",
            validator=lambda v: str(v).lower() in ("both", "prefill",
                                                   "decode"))
define_flag("router_heartbeat_s",
            float(os.environ.get("PADDLE_TPU_ROUTER_HEARTBEAT_S", "2.0")),
            "Interval at which a cluster replica publishes liveness to "
            "the rendezvous TCPStore (the elastic HeartbeatReporter "
            "reused for serving).",
            validator=lambda v: float(v) > 0)
define_flag("router_stale_after_s",
            float(os.environ.get("PADDLE_TPU_ROUTER_STALE_AFTER_S",
                                 "10.0")),
            "Router-side eviction threshold: a replica whose heartbeat "
            "is older than this is evicted from dispatch (its in-flight "
            "requests re-dispatch to surviving replicas; nothing is "
            "lost past the submit ack).",
            validator=lambda v: float(v) > 0)
define_flag("router_retry_backoff_s",
            float(os.environ.get("PADDLE_TPU_ROUTER_RETRY_BACKOFF_S",
                                 "0.05")),
            "Default per-replica backoff after an UNAVAILABLE "
            "backpressure rejection that carried no retry-after hint "
            "(rejections normally carry the queue's own estimate).",
            validator=lambda v: float(v) >= 0)

# ---- Elastic cluster lifecycle (serving/cluster/lifecycle.py) ---------------
define_flag("autoscale_queue_high",
            float(os.environ.get("PADDLE_TPU_AUTOSCALE_QUEUE_HIGH",
                                 "8.0")),
            "Scale-up trigger: mean queue depth per live replica above "
            "which the AutoscaleController spawns another replica "
            "(subject to its max and cooldown).",
            validator=lambda v: float(v) > 0)
define_flag("autoscale_idle_polls",
            int(os.environ.get("PADDLE_TPU_AUTOSCALE_IDLE_POLLS", "3")),
            "Scale-down trigger: consecutive controller polls the "
            "cluster must look idle (empty queues, cold retry hints) "
            "before one replica is drained and retired.",
            validator=lambda v: int(v) >= 1)
define_flag("autoscale_cooldown_polls",
            int(os.environ.get("PADDLE_TPU_AUTOSCALE_COOLDOWN_POLLS",
                               "2")),
            "Polls the controller sits out after any scale action — "
            "hysteresis so a replica mid-boot is not double-spawned and "
            "a fresh retirement is not immediately reversed.",
            validator=lambda v: int(v) >= 0)
define_flag("drain_timeout_s",
            float(os.environ.get("PADDLE_TPU_DRAIN_TIMEOUT_S", "30.0")),
            "Graceful-drain budget: how long a retiring replica may "
            "take to finish queued batches and slot-loop rows before "
            "the controller escalates to eviction (the SIGKILL-style "
            "path graceful retirement exists to avoid).",
            validator=lambda v: float(v) > 0)
define_flag("serving_tenant_quota",
            int(os.environ.get("PADDLE_TPU_SERVING_TENANT_QUOTA", "0")),
            "Default per-tenant pending-request quota in the "
            "RequestQueue (admission control): a tenant at its quota "
            "gets UnavailableError with a retry_after hint while other "
            "tenants keep their queue slots. 0 (default) = unlimited — "
            "single-tenant behavior unchanged, one branch. Per-tenant "
            "overrides via RequestQueue.set_tenant_policy.",
            validator=lambda v: int(v) >= 0)

# ---- Request tracing + typed metrics plane (paddle_tpu.profiler) ------------
define_flag("trace",
            os.environ.get("PADDLE_TPU_TRACE", "off").lower() or "off",
            "Request-scoped span tracing tri-state (profiler.tracing): "
            "'off' = no spans (one Python branch per instrumentation "
            "point); 'sample' = trace every k-th request/step where k = "
            "round(1/FLAGS_trace_sample_rate); 'full' = trace every "
            "request and training step.  Spans cover the whole serving "
            "path (submit -> queue wait -> pack -> H2D -> execute -> D2H "
            "-> reply), the train-step phase breakdown, and generate()'s "
            "prefill/decode scan boundary; recompile-ledger events "
            "auto-attach to the active span.  Host-side timing only: "
            "tracing never changes a traced program or adds a compile "
            "key.  Seeded by PADDLE_TPU_TRACE.",
            validator=lambda v: str(v).lower() in ("off", "sample",
                                                   "full"))
define_flag("trace_sample_rate", 0.01,
            "Fraction of requests/steps traced under FLAGS_trace=sample "
            "(deterministic stride sampling: every round(1/rate)-th root "
            "span is kept, so long runs converge to the rate without a "
            "per-request RNG draw).",
            validator=lambda v: 0.0 < float(v) <= 1.0)
define_flag("trace_dir",
            os.environ.get("PADDLE_TPU_TRACE_DIR", ""),
            "When non-empty, every finished span additionally streams as "
            "JSONL via utils.monitor.LogWriter into this directory "
            "(tools/obs_report.py joins these with metrics snapshots "
            "into per-request waterfalls).  The bounded in-memory span "
            "ring is always maintained while tracing is on.")
define_flag("flight_dir",
            os.environ.get("PADDLE_TPU_FLIGHT_DIR", ""),
            "When non-empty, arm the per-process flight recorder "
            "(profiler.flight): a bounded in-memory ring of recent "
            "spans, recompile-ledger events and metric snapshots, "
            "atomically persisted into this directory as "
            "postmortem_<id>.json — rewritten every "
            "FLAGS_flight_interval_s and on SIGTERM/fatal paths — so "
            "even a SIGKILLed replica leaves evidence "
            "(tools/obs_report.py --postmortem reads it).  Empty = "
            "recorder fully off (zero hot-path cost).  Seeded by "
            "PADDLE_TPU_FLIGHT_DIR.")
define_flag("flight_interval_s", 1.0,
            "Flight-recorder persistence cadence: the background dumper "
            "rewrites the postmortem artifact (atomic replace, "
            "checkpoint discipline) this often, bounding how much "
            "history an uncatchable SIGKILL can destroy.",
            validator=lambda v: float(v) > 0)
define_flag("flight_spans", 256,
            "How many most-recent finished spans (and ledger events, "
            "capped at half this) a flight-recorder dump carries — the "
            "artifact stays a bounded postmortem, not a trace archive.",
            validator=lambda v: int(v) > 0)
define_flag("log_writer_max_mb", 64.0,
            "Size cap (MiB) per LogWriter JSONL sink file (recompile "
            "ledger, graph-lint, hlo-audit, trace dirs): past the cap "
            "the file rotates ('f.jsonl' -> 'f.jsonl.1' -> 'f.jsonl.2', "
            "two rollovers kept), so a long-running serve process "
            "cannot grow any sink without bound.  0 disables rotation.",
            validator=lambda v: float(v) >= 0)

# ---- Autoregressive decoding (text.generation + serving decode) -------------
define_flag("decode_buckets", "16,32,64,128,256,512,1024",
            "Sequence-length bucket ladder for incremental decoding: "
            "prompt lengths pad (left) up to the smallest bucket, and "
            "KV-cache lengths round up to the smallest bucket holding "
            "prompt + max_new_tokens, so generate() and the serving "
            "decode path only ever compile (batch, prefill-bucket, "
            "cache-bucket) shapes fixed at warm-up.",
            validator=lambda v: all(int(b) > 0 for b in
                                    str(v).split(",") if b.strip()))
define_flag("decode_max_len", 1024,
            "Hard ceiling on KV-cache length (prompt + generated tokens) "
            "for generate() and serving decode; requests past it raise "
            "OutOfRange instead of growing an unbounded cache shape.",
            validator=lambda v: int(v) >= 1)
define_flag("decode_slots",
            int(os.environ.get("PADDLE_TPU_DECODE_SLOTS", "0") or 0),
            "Slot count S of the iteration-level continuous-batching "
            "decode loop (serving/slots.py): ONE single-token step "
            "executable per (S, cache-bucket) in which requests occupy "
            "slots, finished rows retire at token boundaries and queued "
            "requests join by restarting a row's validity window — no "
            "recompile, no cache copy.  0 (default) keeps the "
            "run-to-completion scanned decode path byte-identical to "
            "before (one Python branch at decode-runtime load).  Seeded "
            "by PADDLE_TPU_DECODE_SLOTS.",
            validator=lambda v: 0 <= int(v) <= 256)
define_flag("prefill_chunk",
            int(os.environ.get("PADDLE_TPU_PREFILL_CHUNK", "16") or 16),
            "Chunk width T of Sarathi-style chunked prefill under the "
            "slot decode loop (FLAGS_decode_slots > 0): a joining "
            "request's prompt is split into ceil(len/T) LEFT-padded "
            "chunks interleaved with decode steps — T decode steps, one "
            "chunk, repeat — so TTFT p99 of short requests is not "
            "hostage to head-of-line long prompts.  Irrelevant when "
            "FLAGS_decode_slots == 0.  Seeded by "
            "PADDLE_TPU_PREFILL_CHUNK.",
            validator=lambda v: 1 <= int(v) <= 4096)
define_flag("prefix_cache", False,
            "Radix-trie prefix KV cache under the slot decode loop "
            "(serving/prefix_cache.py): completed prefills publish their "
            "prompt's ring-cache plane blocks back into a token-prefix "
            "trie, and a joining request restores the longest cached "
            "prefix into its validity window, chunk-prefilling only the "
            "uncached suffix.  Off (default) = the slot loop admits "
            "exactly as before (one Python branch at admission).  "
            "Requires FLAGS_decode_slots > 0 to have any effect.")
define_flag("prefix_cache_hbm_mb", 256.0,
            "Device-memory budget (MiB) of the prefix KV cache; "
            "least-recently-used unpinned leaf blocks evict until the "
            "cache fits.  0 = unbounded (the trie grows until cleared).",
            validator=lambda v: float(v) >= 0.0)
define_flag("session_store", False,
            "Parked-session KV store (serving/sessions.py): a decode "
            "request carrying a session id parks its ring-cache row as a "
            "host-RAM snapshot at turn end, and the follow-up turn "
            "restores the snapshot into a slot and decodes from the "
            "committed position instead of re-prefilling the whole "
            "history.  Graceful drain parks in-flight session rows for "
            "migration instead of waiting them out.  Off (default) = "
            "session ids are ignored; off-path is one Python branch.")
define_flag("session_store_dir", "",
            "Optional disk-spill directory for parked sessions (empty = "
            "host RAM only).  Snapshots write under the sha256-verified "
            "atomic-manifest discipline; a directory shared between "
            "replicas doubles as the migration transport — any replica "
            "can restore a session a dead replica parked there.")
define_flag("session_park_after_ms", 0,
            "Age (ms) a RAM-parked session must reach before it spills "
            "to FLAGS_session_store_dir.  0 (default) writes through to "
            "disk at park time — the mode that survives SIGKILL, since "
            "a lazily-spilled snapshot still in RAM dies with the "
            "process.  Ignored when the spill directory is unset.",
            validator=lambda v: int(v) >= 0)

# ---- Persistent executable cache (paddle_tpu.jit.persistent_cache) ----------
define_flag("executable_cache",
            os.environ.get("PADDLE_TPU_EXEC_CACHE", "off").lower()
            or "off",
            "Persistent on-disk AOT executable cache tri-state "
            "(jit/persistent_cache.py): 'off' = every fresh compile "
            "pays XLA (one Python branch per fresh-compile path, zero "
            "per step); 'read' = fresh compiles first probe "
            "FLAGS_executable_cache_dir for a serialized executable "
            "with a matching (ledger key, program identity, "
            "jaxlib/device fingerprint, lowering flags) digest and a "
            "verified sha256 — hits deserialize in O(load) and are "
            "ledgered as kind 'cache_load'; 'readwrite' additionally "
            "serializes every fresh compile back into the dir (one "
            "host compiles, N hosts load).  Wired into @to_static "
            "dispatch, the static Executor, TrainStep.aot_compile "
            "(and so HLO-audit lowerings), and the serving warm-up "
            "grids (dense + decode + speculative).  Seeded by "
            "PADDLE_TPU_EXEC_CACHE.",
            validator=lambda v: str(v).lower() in ("off", "read",
                                                   "readwrite"))
define_flag("executable_cache_dir",
            os.environ.get("PADDLE_TPU_EXEC_CACHE_DIR", ""),
            "Directory of the persistent executable cache (entries: "
            "<digest>.pjrt payload + <digest>.json sha256 manifest, "
            "written with the checkpoint subsystem's atomic "
            "temp+fsync+rename discipline).  Empty disables the cache "
            "regardless of FLAGS_executable_cache — both must be set "
            "(tools/serve.py --cache-dir sets both).  Seeded by "
            "PADDLE_TPU_EXEC_CACHE_DIR.")
define_flag("executable_cache_max_gb",
            float(os.environ.get("PADDLE_TPU_EXEC_CACHE_MAX_GB", "0")
                  or 0),
            "Payload-size cap (GiB) for the persistent executable "
            "cache: after each store, least-recently-used entries are "
            "evicted until the cache fits.  0 = unbounded (GC via "
            "tools/exec_cache.py gc --max-gb/--max-age).  Seeded by "
            "PADDLE_TPU_EXEC_CACHE_MAX_GB.",
            validator=lambda v: float(v) >= 0)

# ---- Speculative decoding + quantized KV cache (text.speculative) -----------
define_flag("spec_decode",
            os.environ.get("PADDLE_TPU_SPEC_DECODE", "").lower()
            in ("1", "true", "yes", "on"),
            "Serve decode models through draft/target speculative "
            "decoding (text/speculative.py) when the DecodeModelSpec "
            "carries a draft layer: a small GPT drafts FLAGS_spec_gamma "
            "tokens per step, the target verifies all of them in ONE "
            "batched forward, and greedy acceptance walks the longest "
            "agreeing prefix — output tokens are bit-identical to plain "
            "greedy decode of the target (acceptance/rollback is "
            "lossless by construction), at up to gamma+1 tokens per "
            "target pass.  OFF by default: the plain Generator path is "
            "unchanged (one Python branch at decode-runtime load).  An "
            "explicit generate(draft_model=...) call opts in regardless "
            "of the flag.  Seeded by PADDLE_TPU_SPEC_DECODE.")
define_flag("spec_gamma", 4,
            "Tokens the draft model proposes per speculative step "
            "(gamma).  Each step costs gamma+1 draft forwards plus ONE "
            "gamma+1-wide target verify forward and commits 1..gamma+1 "
            "tokens; higher gamma pays off when draft/target agreement "
            "is high.  Per-call override via "
            "SpeculativeGenerator(gamma=...).",
            validator=lambda v: 1 <= int(v) <= 16)
define_flag("kv_cache_dtype",
            os.environ.get("PADDLE_TPU_KV_CACHE_DTYPE", "bf16").lower()
            or "bf16",
            "Storage dtype of the decode KV ring cache: 'bf16' (native "
            "model dtype planes — today's layout) or 'int8' (int8 rows "
            "+ per-(token, head) f32 scales as extra cache planes "
            "written at the same traced cache_position), halving "
            "cached-context HBM.  The attention read dequantizes the "
            "planes (rows times scales, to the query's dtype) and "
            "attends over them under the caller's mask.  One Python "
            "branch at cache init; flipping it recompiles the generate "
            "executables (the cache dtype is part of the compile key). "
            "Seeded by PADDLE_TPU_KV_CACHE_DTYPE.",
            validator=lambda v: str(v).lower() in ("bf16", "int8"))
