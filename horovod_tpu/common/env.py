"""Canonical environment/flag schema.

The reference funnels all configuration through ~30 ``HOROVOD_*`` env vars
(/root/reference/horovod/common/common.h:66-96, parsed at
operations.cc:395-538 and utils/env_parser.cc). We keep the same three-layer
scheme (env vars < CLI flags that set env vars < YAML config file) with one
canonical table here so every subsystem reads configuration the same way.

Env vars keep the ``HOROVOD_`` prefix so existing user run-books transfer.
"""

from __future__ import annotations

import dataclasses
import os

# --- knob names (reference: common.h:66-96) ---------------------------------
HOROVOD_FUSION_THRESHOLD = "HOROVOD_FUSION_THRESHOLD"
HOROVOD_CYCLE_TIME = "HOROVOD_CYCLE_TIME"
HOROVOD_CACHE_CAPACITY = "HOROVOD_CACHE_CAPACITY"
# ragged-vs-dense eager alltoall crossover (nonzero cross edges)
HOROVOD_ALLTOALL_EDGE_LIMIT = "HOROVOD_ALLTOALL_EDGE_LIMIT"
HOROVOD_TIMELINE = "HOROVOD_TIMELINE"
HOROVOD_TIMELINE_MARK_CYCLES = "HOROVOD_TIMELINE_MARK_CYCLES"
HOROVOD_AUTOTUNE = "HOROVOD_AUTOTUNE"
HOROVOD_AUTOTUNE_LOG = "HOROVOD_AUTOTUNE_LOG"
HOROVOD_AUTOTUNE_WARMUP_SAMPLES = "HOROVOD_AUTOTUNE_WARMUP_SAMPLES"
HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE = "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"
HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES = "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"
# joint fast-path autotuner (utils/autotune.py; docs/autotune.md):
# persisted winning-config file (all-or-nothing parse on reload), and the
# convergence guardrail — a candidate regressing the goodput score by
# >= REVERT_PCT percent for REVERT_WINDOWS consecutive sample windows is
# reverted to the best known config and penalized in the optimizer
HOROVOD_AUTOTUNE_TUNED_FILE = "HOROVOD_AUTOTUNE_TUNED_FILE"
HOROVOD_AUTOTUNE_REVERT_PCT = "HOROVOD_AUTOTUNE_REVERT_PCT"
HOROVOD_AUTOTUNE_REVERT_WINDOWS = "HOROVOD_AUTOTUNE_REVERT_WINDOWS"
# fused-plan granularity: max tensors per fused chunk (0 = byte-bounded
# only) — a joint-tuning knob (arXiv:2209.12769): smaller chunks overlap
# better, larger chunks amortize dispatches (ops/queue.py chunking)
HOROVOD_PLAN_CHUNK_TENSORS = "HOROVOD_PLAN_CHUNK_TENSORS"
HOROVOD_LOG_LEVEL = "HOROVOD_LOG_LEVEL"
HOROVOD_STALL_CHECK_DISABLE = "HOROVOD_STALL_CHECK_DISABLE"
HOROVOD_STALL_CHECK_TIME_SECONDS = "HOROVOD_STALL_CHECK_TIME_SECONDS"
HOROVOD_STALL_SHUTDOWN_TIME_SECONDS = "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"
HOROVOD_RESPONSE_TIMEOUT_S = "HOROVOD_RESPONSE_TIMEOUT_S"
HOROVOD_HIERARCHICAL_ALLREDUCE = "HOROVOD_HIERARCHICAL_ALLREDUCE"
HOROVOD_HIERARCHICAL_ALLGATHER = "HOROVOD_HIERARCHICAL_ALLGATHER"
HOROVOD_BATCH_D2D_MEMCOPIES = "HOROVOD_BATCH_D2D_MEMCOPIES"
HOROVOD_NUM_NCCL_STREAMS = "HOROVOD_NUM_NCCL_STREAMS"  # accepted, ignored (no NCCL on TPU)
HOROVOD_ELASTIC = "HOROVOD_ELASTIC"
# metrics registry exposure (utils/metrics.py): periodic JSON dump path,
# dump/push interval in seconds, and the worker->launcher KV push toggle
HOROVOD_METRICS_FILE = "HOROVOD_METRICS_FILE"
HOROVOD_METRICS_DUMP_INTERVAL = "HOROVOD_METRICS_DUMP_INTERVAL"
HOROVOD_METRICS_PUSH = "HOROVOD_METRICS_PUSH"
# chaos fault-point spec + deterministic seed (utils/faults.py; see
# docs/fault_tolerance.md for the grammar)
HOROVOD_FAULT_SPEC = "HOROVOD_FAULT_SPEC"
HOROVOD_FAULT_SEED = "HOROVOD_FAULT_SEED"
# global overrides for every control-plane retry policy (utils/retry.py);
# call sites pass per-site defaults, these widen all of them at once
HOROVOD_RETRY_MAX_ATTEMPTS = "HOROVOD_RETRY_MAX_ATTEMPTS"
HOROVOD_RETRY_DEADLINE = "HOROVOD_RETRY_DEADLINE"
HOROVOD_RETRY_BASE_DELAY = "HOROVOD_RETRY_BASE_DELAY"
# elastic respawn-before-blacklist budget: per-host transient-failure
# retries and the backoff scale between respawn rounds (elastic/driver.py)
HOROVOD_ELASTIC_RESPAWN_ATTEMPTS = "HOROVOD_ELASTIC_RESPAWN_ATTEMPTS"
HOROVOD_ELASTIC_RESPAWN_BACKOFF = "HOROVOD_ELASTIC_RESPAWN_BACKOFF"
# elastic rendezvous identity: discovery epoch and reset generation
# (driver-injected, read by elastic/state.py and the controller's
# KV-scope prefix so stale rounds never cross a reset), plus the
# committed-state snapshot path for elastic restore (elastic/state.py)
HOROVOD_ELASTIC_EPOCH = "HOROVOD_ELASTIC_EPOCH"
HOROVOD_ELASTIC_GEN = "HOROVOD_ELASTIC_GEN"
HOROVOD_ELASTIC_STORE = "HOROVOD_ELASTIC_STORE"
# steady-state fast path (docs/performance.md): staging-ring slot count
# and the escape hatch disabling compiled fused-chunk plans (legacy
# per-cycle eager dispatch)
HOROVOD_STAGING_RING_SLOTS = "HOROVOD_STAGING_RING_SLOTS"
HOROVOD_FUSED_PLAN_DISABLE = "HOROVOD_FUSED_PLAN_DISABLE"
# cross-rank distributed tracing (utils/tracing.py; docs/timeline.md):
# master switch, buffered-span cap per rank, and a clock-offset override
# (seconds this rank's clock must be shifted to match the rendezvous
# coordinator's) replacing the NTP-style /clock estimation
HOROVOD_TRACE = "HOROVOD_TRACE"
HOROVOD_TRACE_BUFFER = "HOROVOD_TRACE_BUFFER"
HOROVOD_TRACE_CLOCK_OFFSET = "HOROVOD_TRACE_CLOCK_OFFSET"
# runtime lock-order/hold auditor (utils/lockcheck.py; docs/development.md):
# master switch and the held-too-long warning threshold in milliseconds
HOROVOD_LOCKCHECK = "HOROVOD_LOCKCHECK"
HOROVOD_LOCKCHECK_HOLD_MS = "HOROVOD_LOCKCHECK_HOLD_MS"
# ZeRO-1 sharded weight update (opt/sharded.py; docs/sharded_optimizer.md):
# master switch for the reduce-scatter → sharded step → allgather path in
# the framework shims, and the replicate threshold in elements below which
# a leaf stays on the classic allreduce path
HOROVOD_SHARDED_UPDATE = "HOROVOD_SHARDED_UPDATE"
HOROVOD_SHARDED_MIN_ELEMS = "HOROVOD_SHARDED_MIN_ELEMS"
# blockwise quantized wire format (ops/compression.py; docs/performance.md
# "Quantized allreduce"): none|int8|int4 selects the fused-chunk wire
# dtype, the per-block element count for absmax scales, the
# error-feedback master switch, the name-pattern opt-out list, and the
# small-leaf threshold in elements below which a tensor stays on the
# uncompressed path. Mutually exclusive with HOROVOD_SHARDED_UPDATE.
HOROVOD_COMPRESSION = "HOROVOD_COMPRESSION"
HOROVOD_QUANT_BLOCK = "HOROVOD_QUANT_BLOCK"
HOROVOD_QUANT_EF = "HOROVOD_QUANT_EF"
HOROVOD_QUANT_OPTOUT = "HOROVOD_QUANT_OPTOUT"
HOROVOD_QUANT_MIN_ELEMS = "HOROVOD_QUANT_MIN_ELEMS"
# native-core sanitizer build: address|thread adds the matching
# -fsanitize flags to the on-demand g++ build (_native/__init__.py)
HOROVOD_NATIVE_SANITIZE = "HOROVOD_NATIVE_SANITIZE"
# postmortem layer (utils/flightrec.py + utils/diag.py;
# docs/observability.md "Debugging a hung job"): flight-recorder master
# switch and ring capacity, the wedge-watchdog no-progress threshold in
# seconds (0 = off), and where diagnostic bundles are written
HOROVOD_FLIGHTREC = "HOROVOD_FLIGHTREC"
HOROVOD_FLIGHTREC_BUFFER = "HOROVOD_FLIGHTREC_BUFFER"
HOROVOD_WATCHDOG_SECS = "HOROVOD_WATCHDOG_SECS"
HOROVOD_DIAG_DIR = "HOROVOD_DIAG_DIR"
# per-step performance ledger + SLO budget engine (utils/perfledger.py;
# docs/observability.md "Performance ledger & SLO budgets"): master
# switch, per-step record-ring capacity, and the declarative budget spec
# — either the inline grammar ("negotiate_p95_ms<=5,plan_hit_rate>=0.95")
# or a JSON object / path to a JSON file mapping stat name to bound
HOROVOD_PERFLEDGER = "HOROVOD_PERFLEDGER"
HOROVOD_PERFLEDGER_BUFFER = "HOROVOD_PERFLEDGER_BUFFER"
HOROVOD_SLO_SPEC = "HOROVOD_SLO_SPEC"
# control-plane scale-out (ops/controller.py, ops/wire.py,
# runner/http_server.py; docs/scaling.md): hierarchical node-leader
# negotiation + binary wire-format v2 master switch, ranks per leader
# group (pods: set to the per-host process count), how long a member
# waits on its leader before falling back to flat submission, and the
# rendezvous KV shard count (listener sockets/stores in the launcher)
HOROVOD_HIER_NEGOTIATION = "HOROVOD_HIER_NEGOTIATION"
HOROVOD_HIER_GROUP_SIZE = "HOROVOD_HIER_GROUP_SIZE"
HOROVOD_HIER_FALLBACK_S = "HOROVOD_HIER_FALLBACK_S"
HOROVOD_KV_SHARDS = "HOROVOD_KV_SHARDS"
# device-memory & compile ledger (utils/memledger.py;
# docs/observability.md "Memory & compile ledger"): master switch and
# sample-ring capacity, plus an optional byte cap on the compiled-plan
# cache (ops/collectives.py) driving reason="memory" evictions from the
# per-plan program-size accounting (0 = uncapped)
HOROVOD_MEMLEDGER = "HOROVOD_MEMLEDGER"
HOROVOD_MEMLEDGER_BUFFER = "HOROVOD_MEMLEDGER_BUFFER"
HOROVOD_PLAN_CACHE_MAX_BYTES = "HOROVOD_PLAN_CACHE_MAX_BYTES"
# step-anatomy profiler (utils/anatomy.py; docs/observability.md "Step
# anatomy & headroom"): per-collective critical-path attribution and
# overlap/replay headroom estimation — master switch and per-step
# record-ring capacity
HOROVOD_ANATOMY = "HOROVOD_ANATOMY"
HOROVOD_ANATOMY_BUFFER = "HOROVOD_ANATOMY_BUFFER"
# whole-step megaplan capture & replay (ops/megaplan.py;
# docs/performance.md "Whole-step replay"): master switch, and how many
# consecutive identical working cycles (the response-cache/SAME_AS_LAST
# stability signal) must be observed before the full step schedule —
# negotiated order, chunk grouping, compiled chunk programs — is
# captured and steady-state cycles replay it with ~one validity check
HOROVOD_MEGAPLAN = "HOROVOD_MEGAPLAN"
HOROVOD_MEGAPLAN_STABLE_ROUNDS = "HOROVOD_MEGAPLAN_STABLE_ROUNDS"
# preemption-tolerant async sharded checkpointing (utils/async_ckpt.py;
# docs/fault_tolerance.md "Surviving preemption"): master switch, the
# directory shard checkpoints + manifest land in, and the SIGTERM grace
# window in seconds — the elastic driver waits this long between
# forwarding SIGTERM and escalating to SIGKILL, and the worker-side
# preemption handler bounds its final flush by the same budget
HOROVOD_ASYNC_CKPT = "HOROVOD_ASYNC_CKPT"
HOROVOD_ASYNC_CKPT_DIR = "HOROVOD_ASYNC_CKPT_DIR"
HOROVOD_PREEMPT_GRACE_S = "HOROVOD_PREEMPT_GRACE_S"
# fleet health engine (utils/health.py; docs/observability.md "Fleet
# health & history"): master switch, per-series history ring capacity,
# samples collected before the drift detector freezes its median/MAD
# baseline, and an optional path the full history rings are dumped to at
# shutdown (renderable by tools/benchtrend --from-history)
HOROVOD_HEALTH = "HOROVOD_HEALTH"
HOROVOD_HEALTH_BUFFER = "HOROVOD_HEALTH_BUFFER"
HOROVOD_HEALTH_WARMUP = "HOROVOD_HEALTH_WARMUP"
HOROVOD_HEALTH_FILE = "HOROVOD_HEALTH_FILE"

# ---------------------------------------------------------------------------
# Env-gated subsystems: master switch -> owning module. This mapping IS the
# machine-readable registry of the zero-cost contract — hvdlint's gate-prover
# pass (tools/hvdlint/passes/zerocost.py) parses it to decide which modules'
# hooks must pay at most one is-None check while disabled, and cross-checks
# it both ways: a module following the gated-trio pattern (enabled() reading
# a master switch + a module-global None handle) that is missing here fails
# lint, as does an entry whose module never reads its switch. Keys are the
# schema constants above; values are repo-relative module paths.
# ---------------------------------------------------------------------------
GATED_SUBSYSTEMS = {
    HOROVOD_TRACE: "horovod_tpu/utils/tracing.py",
    HOROVOD_FLIGHTREC: "horovod_tpu/utils/flightrec.py",
    HOROVOD_PERFLEDGER: "horovod_tpu/utils/perfledger.py",
    HOROVOD_MEMLEDGER: "horovod_tpu/utils/memledger.py",
    HOROVOD_ANATOMY: "horovod_tpu/utils/anatomy.py",
    HOROVOD_HEALTH: "horovod_tpu/utils/health.py",
    HOROVOD_MEGAPLAN: "horovod_tpu/ops/megaplan.py",
    HOROVOD_AUTOTUNE: "horovod_tpu/utils/autotune.py",
    HOROVOD_ASYNC_CKPT: "horovod_tpu/utils/async_ckpt.py",
    HOROVOD_LOCKCHECK: "horovod_tpu/utils/lockcheck.py",
}

# worker identity (reference: gloo_context.cc:136-192 reads the same set)
HOROVOD_RANK = "HOROVOD_RANK"
HOROVOD_SIZE = "HOROVOD_SIZE"
HOROVOD_LOCAL_RANK = "HOROVOD_LOCAL_RANK"
HOROVOD_LOCAL_SIZE = "HOROVOD_LOCAL_SIZE"
HOROVOD_CROSS_RANK = "HOROVOD_CROSS_RANK"
HOROVOD_CROSS_SIZE = "HOROVOD_CROSS_SIZE"
HOROVOD_HOSTNAME = "HOROVOD_HOSTNAME"
HOROVOD_GLOO_RENDEZVOUS_ADDR = "HOROVOD_GLOO_RENDEZVOUS_ADDR"
HOROVOD_GLOO_RENDEZVOUS_PORT = "HOROVOD_GLOO_RENDEZVOUS_PORT"
HOROVOD_GLOO_IFACE = "HOROVOD_GLOO_IFACE"
HOROVOD_CONTROLLER = "HOROVOD_CONTROLLER"
# per-job HMAC key authenticating every KV-store request/response
# (reference runner/common/util/secret.py); launcher-minted, env-injected
HOROVOD_SECRET_KEY = "HOROVOD_SECRET_KEY"

# TPU-specific (new in this framework)
HOROVOD_TPU_COORDINATOR = "HOROVOD_TPU_COORDINATOR"  # jax.distributed coordinator addr
HOROVOD_TPU_NUM_PROCESSES = "HOROVOD_TPU_NUM_PROCESSES"
HOROVOD_TPU_PROCESS_ID = "HOROVOD_TPU_PROCESS_ID"
HOROVOD_TPU_MESH = "HOROVOD_TPU_MESH"  # e.g. "dp=8" or "dp=4,tp=2"
# skip building/loading the native C++ core (numpy fallbacks everywhere)
HOROVOD_TPU_DISABLE_NATIVE = "HOROVOD_TPU_DISABLE_NATIVE"


def get_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def get_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    try:
        return int(v) if v is not None else default
    except ValueError:
        return default


def get_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    try:
        return float(v) if v is not None else default
    except ValueError:
        return default


def get_str(name: str, default: str = "") -> str:
    return os.environ.get(name, default)


@dataclasses.dataclass
class RuntimeConfig:
    """Snapshot of all runtime knobs, read once at ``hvd.init()``.

    Mirrors the env-read block at reference operations.cc:395-538.

    - ``fusion_threshold_bytes``: fusion buffer size; reference default is
      128 MiB (operations.cc:446-451, env in MiB). On TPU this bounds how many
      pending eager tensors are flattened into one fused collective program.
    - ``cycle_time_ms``: background cycle sleep; reference default 1 ms
      (operations.cc:456).
    - ``cache_capacity``: response-cache entries (operations.cc:467); for us,
      max cached compiled fused-collective programs.
    """

    fusion_threshold_bytes: int = 128 * 1024 * 1024
    cycle_time_ms: float = 1.0
    cache_capacity: int = 1024
    timeline_filename: str = ""
    timeline_mark_cycles: bool = False
    autotune: bool = False
    autotune_log: str = ""
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 20
    autotune_max_samples: int = 20
    # joint autotuner extras (docs/autotune.md): winning-config file and
    # the score-regression revert guardrail (X percent, K windows)
    autotune_tuned_file: str = ""
    autotune_revert_pct: float = 20.0
    autotune_revert_windows: int = 2
    # fused-plan granularity cap in tensors per chunk (0 = unbounded)
    plan_chunk_tensors: int = 0
    stall_check_disable: bool = False
    stall_warning_time_s: float = 60.0
    stall_shutdown_time_s: float = 0.0
    # how long a worker blocks on a negotiation-round response before
    # declaring the controller dead (coordinator failures error-close the
    # round proactively, so this is a backstop, not the common path)
    response_timeout_s: float = 300.0
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    elastic: bool = False
    metrics_file: str = ""
    metrics_dump_interval_s: float = 30.0
    metrics_push: bool = True
    # steady-state fast path: persistent staging slots per FusionBuffer and
    # the fused-plan escape hatch (legacy per-cycle eager dispatch)
    staging_ring_slots: int = 4
    fused_plan_disable: bool = False
    # cross-rank tracing (utils/tracing.py): spans, merged /timeline,
    # straggler attribution — off by default (zero-cost contract)
    trace_enabled: bool = False
    trace_buffer: int = 4096
    # ZeRO-1 sharded weight update (opt/sharded.py) — off by default;
    # the threshold mirrors sharding_policy.DEFAULT_MIN_SHARD_ELEMS
    sharded_update: bool = False
    sharded_min_elems: int = 2 ** 14
    # blockwise quantized wire (ops/compression.py) — "" keeps the wire
    # uncompressed (zero-cost contract: no hvd_quant_* series exist)
    compression: str = ""
    quant_block: int = 256
    quant_error_feedback: bool = True
    quant_optout: str = ""
    quant_min_elems: int = 4096
    # postmortem layer (utils/flightrec.py, utils/diag.py) — all off by
    # default (flight recorder zero-cost, watchdog thread not created)
    flightrec_enabled: bool = False
    flightrec_buffer: int = 2048
    watchdog_secs: float = 0.0
    diag_dir: str = ""
    # per-step performance ledger + SLO budgets (utils/perfledger.py) —
    # off by default (zero-cost contract: no hvd_perf_*/hvd_slo_* series)
    perfledger_enabled: bool = False
    perfledger_buffer: int = 1024
    slo_spec: str = ""
    # device-memory & compile ledger (utils/memledger.py) — off by
    # default (zero-cost contract: no hvd_mem_*/hvd_compile_* series);
    # plan_cache_max_bytes=0 leaves the plan cache entry-capped only
    memledger_enabled: bool = False
    memledger_buffer: int = 512
    plan_cache_max_bytes: int = 0
    # step-anatomy profiler (utils/anatomy.py) — off by default
    # (zero-cost contract: no hvd_anatomy_* series)
    anatomy_enabled: bool = False
    anatomy_buffer: int = 512
    # whole-step megaplan capture & replay (ops/megaplan.py) — off by
    # default (zero-cost contract: no hvd_megaplan_* series); the
    # stable-round count mirrors the reference response cache's
    # warmup-before-bypass behavior
    megaplan: bool = False
    megaplan_stable_rounds: int = 5
    # preemption-tolerant async sharded checkpointing (utils/async_ckpt.py)
    # — off by default (zero-cost contract: no hvd_ckpt_* series);
    # async_ckpt_dir="" resolves to ./horovod_ckpt at init
    async_ckpt: bool = False
    async_ckpt_dir: str = ""
    preempt_grace_s: float = 15.0
    # fleet health engine (utils/health.py) — off by default (zero-cost
    # contract: no hvd_health_* series); health_file="" skips the
    # on-exit history dump
    health_enabled: bool = False
    health_buffer: int = 512
    health_warmup: int = 20
    health_file: str = ""
    # control-plane scale-out (ops/controller.py + runner/http_server.py)
    # — off by default: the negotiation wire is byte-identical to the
    # flat/JSON v1 protocol and no hvd_hier_*/wire-v2 series exist
    hier_negotiation: bool = False
    hier_group_size: int = 8
    hier_fallback_s: float = 5.0
    kv_shards: int = 1

    @classmethod
    def from_env(cls) -> "RuntimeConfig":
        c = cls()
        mib = get_int(HOROVOD_FUSION_THRESHOLD, -1)
        if mib >= 0:
            # reference accepts raw bytes via HOROVOD_FUSION_THRESHOLD
            c.fusion_threshold_bytes = mib
        c.cycle_time_ms = get_float(HOROVOD_CYCLE_TIME, c.cycle_time_ms)
        c.cache_capacity = get_int(HOROVOD_CACHE_CAPACITY, c.cache_capacity)
        c.timeline_filename = get_str(HOROVOD_TIMELINE)
        c.timeline_mark_cycles = get_bool(HOROVOD_TIMELINE_MARK_CYCLES)
        c.autotune = get_bool(HOROVOD_AUTOTUNE)
        c.autotune_log = get_str(HOROVOD_AUTOTUNE_LOG)
        # same knob names as reference utils/env_parser.cc autotune block
        c.autotune_warmup_samples = get_int(HOROVOD_AUTOTUNE_WARMUP_SAMPLES,
                                            c.autotune_warmup_samples)
        c.autotune_steps_per_sample = get_int(HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE,
                                              c.autotune_steps_per_sample)
        c.autotune_max_samples = get_int(HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES,
                                         c.autotune_max_samples)
        c.autotune_tuned_file = get_str(HOROVOD_AUTOTUNE_TUNED_FILE)
        c.autotune_revert_pct = get_float(HOROVOD_AUTOTUNE_REVERT_PCT,
                                          c.autotune_revert_pct)
        c.autotune_revert_windows = get_int(HOROVOD_AUTOTUNE_REVERT_WINDOWS,
                                            c.autotune_revert_windows)
        c.plan_chunk_tensors = get_int(HOROVOD_PLAN_CHUNK_TENSORS,
                                       c.plan_chunk_tensors)
        c.stall_check_disable = get_bool(HOROVOD_STALL_CHECK_DISABLE)
        c.stall_warning_time_s = get_float(HOROVOD_STALL_CHECK_TIME_SECONDS, 60.0)
        c.stall_shutdown_time_s = get_float(HOROVOD_STALL_SHUTDOWN_TIME_SECONDS, 0.0)
        c.response_timeout_s = get_float(HOROVOD_RESPONSE_TIMEOUT_S,
                                         c.response_timeout_s)
        c.hierarchical_allreduce = get_bool(HOROVOD_HIERARCHICAL_ALLREDUCE)
        c.hierarchical_allgather = get_bool(HOROVOD_HIERARCHICAL_ALLGATHER)
        c.elastic = get_bool(HOROVOD_ELASTIC)
        c.metrics_file = get_str(HOROVOD_METRICS_FILE)
        c.metrics_dump_interval_s = get_float(HOROVOD_METRICS_DUMP_INTERVAL,
                                              c.metrics_dump_interval_s)
        c.metrics_push = get_bool(HOROVOD_METRICS_PUSH, True)
        c.staging_ring_slots = get_int(HOROVOD_STAGING_RING_SLOTS,
                                       c.staging_ring_slots)
        c.fused_plan_disable = get_bool(HOROVOD_FUSED_PLAN_DISABLE)
        c.trace_enabled = get_bool(HOROVOD_TRACE)
        c.trace_buffer = get_int(HOROVOD_TRACE_BUFFER, c.trace_buffer)
        c.sharded_update = get_bool(HOROVOD_SHARDED_UPDATE)
        c.sharded_min_elems = get_int(HOROVOD_SHARDED_MIN_ELEMS,
                                      c.sharded_min_elems)
        c.compression = get_str(HOROVOD_COMPRESSION).strip().lower()
        c.quant_block = get_int(HOROVOD_QUANT_BLOCK, c.quant_block)
        c.quant_error_feedback = get_bool(HOROVOD_QUANT_EF, True)
        c.quant_optout = get_str(HOROVOD_QUANT_OPTOUT)
        c.quant_min_elems = get_int(HOROVOD_QUANT_MIN_ELEMS,
                                    c.quant_min_elems)
        c.flightrec_enabled = get_bool(HOROVOD_FLIGHTREC)
        c.flightrec_buffer = get_int(HOROVOD_FLIGHTREC_BUFFER,
                                     c.flightrec_buffer)
        c.watchdog_secs = get_float(HOROVOD_WATCHDOG_SECS, c.watchdog_secs)
        c.diag_dir = get_str(HOROVOD_DIAG_DIR)
        c.perfledger_enabled = get_bool(HOROVOD_PERFLEDGER)
        c.perfledger_buffer = get_int(HOROVOD_PERFLEDGER_BUFFER,
                                      c.perfledger_buffer)
        c.slo_spec = get_str(HOROVOD_SLO_SPEC)
        c.memledger_enabled = get_bool(HOROVOD_MEMLEDGER)
        c.memledger_buffer = get_int(HOROVOD_MEMLEDGER_BUFFER,
                                     c.memledger_buffer)
        c.plan_cache_max_bytes = get_int(HOROVOD_PLAN_CACHE_MAX_BYTES,
                                         c.plan_cache_max_bytes)
        c.anatomy_enabled = get_bool(HOROVOD_ANATOMY)
        c.anatomy_buffer = get_int(HOROVOD_ANATOMY_BUFFER, c.anatomy_buffer)
        c.megaplan = get_bool(HOROVOD_MEGAPLAN)
        c.megaplan_stable_rounds = get_int(HOROVOD_MEGAPLAN_STABLE_ROUNDS,
                                           c.megaplan_stable_rounds)
        c.async_ckpt = get_bool(HOROVOD_ASYNC_CKPT)
        c.async_ckpt_dir = get_str(HOROVOD_ASYNC_CKPT_DIR)
        c.preempt_grace_s = get_float(HOROVOD_PREEMPT_GRACE_S,
                                      c.preempt_grace_s)
        c.health_enabled = get_bool(HOROVOD_HEALTH)
        c.health_buffer = get_int(HOROVOD_HEALTH_BUFFER, c.health_buffer)
        c.health_warmup = get_int(HOROVOD_HEALTH_WARMUP, c.health_warmup)
        c.health_file = get_str(HOROVOD_HEALTH_FILE)
        c.hier_negotiation = get_bool(HOROVOD_HIER_NEGOTIATION)
        c.hier_group_size = get_int(HOROVOD_HIER_GROUP_SIZE,
                                    c.hier_group_size)
        c.hier_fallback_s = get_float(HOROVOD_HIER_FALLBACK_S,
                                      c.hier_fallback_s)
        c.kv_shards = get_int(HOROVOD_KV_SHARDS, c.kv_shards)
        return c
