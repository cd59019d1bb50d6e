"""Single catalog of environment-variable configuration.

The reference reads ~100 ``MXNET_*`` env vars ad-hoc via ``dmlc::GetEnv`` at
point of use (SURVEY §5.6; canonical catalog in the reference's
docs/static_site/src/pages/api/faq/env_var.md).  This rebuild centralizes every
knob here: one typed accessor, one place to document, introspectable via
``mxnet_tpu.runtime``.

Only knobs that are meaningful on the TPU/XLA stack are kept; reference knobs
that are absorbed by XLA (e.g. MXNET_GPU_WORKER_NTHREADS, memory-pool tuning)
are accepted but ignored, so existing launch scripts don't break.
"""

from __future__ import annotations

import os
import threading

__all__ = ["get", "get_bool", "get_int", "get_float", "describe", "KNOWN_VARS"]

# name -> (default, type, help)
KNOWN_VARS = {
    # engine family (reference: src/engine/engine.cc :: CreateEngine)
    "MXNET_ENGINE_TYPE": (
        "ThreadedEnginePerDevice",
        str,
        "Execution engine. 'NaiveEngine' blocks after every op (serialized, "
        "deterministic debugging — reference semantics); anything else keeps "
        "JAX/XLA async dispatch.",
    ),
    "MXNET_EXEC_BULK_EXEC_TRAIN": (
        "1", str, "Accepted for compat; XLA fuses/bulk-schedules automatically."),
    "MXNET_EXEC_BULK_EXEC_INFERENCE": ("1", str, "Accepted for compat; no-op."),
    # memory family — absorbed by XLA/PJRT allocator
    "MXNET_GPU_MEM_POOL_TYPE": ("Round", str, "Accepted for compat; no-op on TPU."),
    "MXNET_GPU_MEM_POOL_RESERVE": ("5", str, "Accepted for compat; no-op on TPU."),
    # kvstore family
    "MXNET_KVSTORE_REDUCTION_NTHREADS": ("4", int, "Compat; reductions run on-device."),
    "MXNET_KVSTORE_BIGARRAY_BOUND": (
        str(1000 * 1000), int,
        "Arrays larger than this (elements) may use reduce_scatter+all_gather "
        "instead of one psum in dist kvstore."),
    "MXNET_KVSTORE_USETREE": ("0", str, "Compat; ICI topology handled by XLA."),
    "MXNET_KVSTORE_BUCKET_MB": (
        "25", float,
        "Gradient-fusion bucket size (MB) for kvstore pushpull_list: dense "
        "uncompressed grads flatten-concat into buckets of at most this many "
        "bytes and reduce with ONE dispatch per bucket (DDP/Horovod-style "
        "fusion). 0 disables fusion (per-key pushpull, bit-identical)."),
    # profiler / telemetry
    "MXNET_PROFILER_AUTOSTART": ("0", int, "Start the profiler at import."),
    "MXNET_PROFILER_MODE": ("0", int, "Compat flag for storage profiling."),
    "MXNET_TELEMETRY": (
        "0", int,
        "If 1, runtime telemetry (span tracer + metrics across dispatch, "
        "kvstore, trainer, dataloader, checkpoint) records from import; "
        "0 leaves it off until telemetry.enable()/profiler.start()."),
    "MXNET_TELEMETRY_BUFFER": (
        "65536", int,
        "Span ring-buffer capacity (events); oldest events drop beyond it."),
    # observability plane (ISSUE 10: aggregation + StepClock + flight rec)
    "MXNET_TELEMETRY_DIR": (
        None, str,
        "Cross-process telemetry collection directory: every process "
        "exports a rank-tagged span+metric snapshot here at exit (and on "
        "flight-recorder dumps); rank 0 / tools/telemetry_report.py merge "
        "the shards into ONE Chrome trace and ONE Prometheus snapshot. "
        "Unset = no export."),
    # analytic performance observatory (ISSUE 12: telemetry.costmodel +
    # telemetry.httpd)
    "MXNET_COSTMODEL": (
        "0", int,
        "If 1, the compile/cost ledger arms at import: every owned jit "
        "boundary (op dispatch, TrainStep, fused optimizer/kvstore "
        "buckets, serving prefill/decode) records per-executable compile "
        "seconds, XLA cost_analysis flops/bytes, and memory_analysis "
        "peak-HBM into telemetry.costmodel.LEDGER (report(cost=True), "
        "/ledger.json, BENCH rows).  0 (default) records nothing; "
        "costmodel.arm() flips it at runtime."),
    "MXNET_COSTMODEL_MEMORY": (
        "1", int,
        "If 1 (default), the armed cost ledger also AOT-compiles each new "
        "executable for memory_analysis (argument/output/temp bytes -> "
        "peak-HBM estimate) — one extra XLA compile per executable; 0 "
        "keeps the cheap trace-only cost_analysis (flops/bytes) alone."),
    "MXNET_PEAK_FLOPS": (
        "0", float,
        "Per-chip peak FLOP/s for analytic-MFU accounting (0 = auto from "
        "the device kind: v5e 197e12 bf16, v4 275e12, v5p 459e12, CPU "
        "5e11; float32 = bf16/4)."),
    "MXNET_PEAK_HBM_GBS": (
        "0", float,
        "Per-chip HBM bandwidth in GB/s for the roofline ridge (0 = auto "
        "from the device kind: v5e 819, v4 1228, v5p 2765, CPU 50)."),
    "MXNET_TELEMETRY_PORT": (
        None, int,
        "If set, a daemon-thread HTTP server exposes the LIVE telemetry "
        "plane on this port: /metrics (Prometheus exposition of the "
        "registry — the scrape surface a replica router dispatches on), "
        "/statusz (knobs, world, stepclock verdict, serving gauges), "
        "/ledger.json (cost + op ledgers).  0 binds an ephemeral port; "
        "unset (default) = no server."),
    "MXNET_STEPCLOCK_WINDOW": (
        "64", int,
        "Steps the StepClock keeps for the rolling input-/comms-/compute-"
        "bound verdict and telemetry.report()'s phase medians."),
    "MXNET_FLIGHTREC": (
        "1", int,
        "If 1 (default), the crash flight recorder arms at import: "
        "unhandled exceptions, deadline-exceeded, chaos 'exit' faults, "
        "SIGTERM, and SIGUSR2 (on demand) each dump a bounded postmortem "
        "(last spans, metric state, chaos sites, resolved knobs) per "
        "rank.  0 disables the dumps and installs no handlers."),
    "MXNET_FLIGHTREC_DIR": (
        None, str,
        "Directory for flight-recorder dumps (default: MXNET_TELEMETRY_DIR "
        "when set, else ~/.cache/mxnet_tpu/flightrec — never the working "
        "tree; spawned workers inherit the env so one job-wide redirect "
        "covers every rank)."),
    "MXNET_FLIGHTREC_SPANS": (
        "256", int,
        "Most-recent trace events included in each flight-recorder dump."),
    "MXNET_FLIGHTREC_MAX_DUMPS": (
        "16", int,
        "Flight-recorder dump-file cap per process (rate limit: a retry "
        "loop hitting deadlines must not flood the disk)."),
    # data pipeline
    "MXNET_CPU_WORKER_NTHREADS": ("1", int, "Worker threads for host-side data aug."),
    # multi-core decode pipeline (ISSUE 7: io/pipeline.py)
    "MXNET_IO_POOL": (
        "1", int,
        "If 1 (default), ImageRecordIter(preprocess_threads>1) and "
        "DataLoader over decode-aware datasets run the shared-memory "
        "multi-process decode pipeline (bit-identical batches); 0 forces "
        "in-process decode everywhere."),
    "MXNET_IO_PREFETCH": (
        "2", int,
        "Batches the decode pipeline keeps in flight ahead of the "
        "consumer (shared-memory slab count is this + 1 — host memory "
        "scales with it).  2 = double buffering: one batch consumed, two "
        "decoding."),
    "MXNET_IO_CHUNK": (
        "0", int,
        "Records per decode-pool task.  0 = auto (one task wave per "
        "batch across the worker pool; stragglers hide behind the next "
        "prefetched batch's queued chunks)."),
    "MXNET_IO_TIMEOUT_S": (
        "60", float,
        "Deadline (seconds) on one decode chunk.  A worker that blows it "
        "is treated as hung: the pool is hard-killed (a late write into "
        "a recycled slab must be impossible), the chunk re-decodes "
        "in-process, and the degradation ladder (MXNET_DATALOADER_RETRIES) "
        "advances."),
    # testing / RNG (reference: tests/python/unittest/common.py)
    "MXNET_TEST_SEED": (None, int, "Per-test RNG seed override."),
    "MXNET_MODULE_SEED": (None, int, "Module-wide RNG seed override."),
    # TPU-rebuild-specific
    "MXNET_TPU_DEFAULT_MATMUL_PRECISION": (
        "highest", str,
        "jax matmul precision for float32 ops: default|high|highest. "
        "'highest' gives true-f32 MXNet numerics (3/6-pass bf16 on the MXU); "
        "set 'default' to trade accuracy for raw MXU throughput."),
    "MXNET_TPU_JIT_IMPERATIVE": (
        "1", int,
        "If 1, imperative op dispatch goes through a per-(op,shape,dtype,attrs) "
        "jax.jit cache; if 0, ops run op-by-op eagerly."),
    "MXNET_SHOW_ENV": ("0", int, "Print the env-var catalog at import (1.7 parity)."),
    "MXNET_GELU_TANH": (
        "0", int,
        "If 1, gelu (the op, LeakyReLU act_type='gelu', and "
        "gluon.nn.GELU) defaults to the tanh approximation "
        "0.5x(1+tanh(sqrt(2/pi)(x+0.044715x^3))) instead of the exact erf "
        "form — a cheaper, untried lever for the seq-512 MFU target. "
        "An explicit approximate= attr always wins; read when an op/block "
        "first resolves, so set it before building the model."),
    "MXNET_PARAMS_FORMAT": (
        "npz", str,
        "Default mx.nd.save container: 'npz' (rich: sparse/bf16) or 'dmlc' "
        "(the reference's byte-compatible .params layout). load() "
        "auto-detects both."),
    "MXNET_CHECKPOINT_KEEP": (
        "3", int,
        "How many step checkpoints mx.checkpoint.CheckpointManager retains."),
    "MXNET_CHECKPOINT_SHARDED": (
        "0", int,
        "If 1, mesh-sharded params save as sharded jax.Arrays (orbax "
        "writes shards in parallel per host — the pod-scale path); 0 "
        "(default) gathers them to host arrays first, making the "
        "checkpoint topology-free (restores on any mesh or none)."),
    # GSPMD sharding engine (ISSUE 8: mxnet_tpu.sharding)
    "MXNET_SHARDING_SKIP_ALLREDUCE": (
        "1", int,
        "If 1 (default), gluon.Trainer skips the local/device kvstore "
        "gradient reduction for params flagged Parameter.mesh_reduced "
        "(a mesh-jitted step already psum'd their grads — reducing again "
        "would double-count); dist stores always reduce. 0 restores the "
        "unconditional reduction."),
    # auto-sharder / memory-axis scale (ISSUE 14: mxnet_tpu.autoshard)
    "MXNET_MICROBATCH": (
        "1", int,
        "Trace-time default for parallel.TrainStep(n_micro=): gradient-"
        "accumulation microbatch count per step (the batch splits into "
        "this many slices scanned with fixed-association accumulation "
        "and ONE optimizer update). 1 (default) keeps the original "
        "single-pass step, bit-identically."),
    "MXNET_REMAT": (
        "0", int,
        "Trace-time default for parallel.TrainStep(remat=): if 1, the "
        "net forward runs under gluon.utils.remat_call so activations "
        "are recomputed during backward instead of saved (memory for "
        "compute; single-output nets only)."),
    "MXNET_AUTOSHARD_HBM_GB": (
        "0", float,
        "Default per-device HBM budget (GB) for autoshard.plan() and "
        "tools/autoshard.py when the caller passes none; 0 (default) "
        "means unbounded — the planner ranks purely on speed."),
    "MXNET_AUTOSHARD_MAX_MICRO": (
        "8", int,
        "Largest microbatch count the auto-sharder may propose while "
        "searching for a fitting layout (candidates double from 1 up "
        "to this bound)."),
    # resilience family (ISSUE 3: mx.resilience)
    "MXNET_KVSTORE_TIMEOUT_S": (
        "300", float,
        "Deadline (seconds) on blocking dist-kvstore calls (bring-up, "
        "allreduce, barrier): a dead/wedged peer raises KVStoreTimeoutError "
        "instead of hanging forever. 0 disables the bound."),
    "MXNET_RESILIENCE_MAX_RETRIES": (
        "3", int,
        "Re-attempts a Retry policy makes after the first failure of a "
        "transient (retryable) operation; 0 fails fast."),
    "MXNET_RESILIENCE_BACKOFF_S": (
        "0.05", float,
        "Base backoff (seconds) before retry attempt k sleeps "
        "backoff * 2^k (with +/-25% jitter)."),
    "MXNET_RESILIENCE_BACKOFF_MAX_S": (
        "2", float, "Cap on the exponential retry backoff (seconds)."),
    "MXNET_RESILIENCE_SIGTERM_SAVE": (
        "1", int,
        "If 1, mx.checkpoint.auto_resume installs a SIGTERM hook that "
        "checkpoints after the in-flight step and exits cleanly "
        "(preemption-safe save); 0 leaves the default signal behavior."),
    "MXNET_DATALOADER_RETRIES": (
        "2", int,
        "Worker-pool batch failures DataLoader absorbs via in-process "
        "refetch before permanently degrading to single-process loading."),
    "MXNET_LOCKCHECK": (
        "0", int,
        "If 1, locks created through analysis.tracked() record their "
        "acquisition order and raise LockOrderError on a cycle — the "
        "runtime twin of graftcheck GC06 (debug/test builds; disarmed "
        "locks are returned raw, zero overhead)."),
    "MXNET_CHAOS": (
        "0", int,
        "If 1, arm chaos faults from MXNET_CHAOS_SITES at import "
        "(mx.resilience.chaos fault injection for recovery testing)."),
    "MXNET_CHAOS_SITES": (
        None, str,
        "Comma list of faults to arm when MXNET_CHAOS=1: "
        "'site:kind[:times[:delay_s]]' with kind in "
        "delay|transient|fatal|exit, e.g. 'kvstore.allreduce:transient:2'."),
    # distributed bring-up (tools/launch.py writes these per worker; the
    # dist kvstore reads them at _ensure_dist)
    "MXNET_DIST_COORDINATOR": (
        None, str,
        "host:port of the jax.distributed rendezvous coordinator "
        "(JAX_COORDINATOR_ADDRESS also honored); unset = single-process."),
    "MXNET_DIST_NUM_WORKERS": (
        "1", int, "World size the dist kvstore rendezvous waits for."),
    "MXNET_DIST_RANK": (
        "0", int, "This worker's process id in the dist kvstore world."),
    # elastic controller (ISSUE 11: resilience/controller.py +
    # tools/elastic_launch.py; the *_DIR/INCARNATION/WORLD_TARGET vars are
    # WRITTEN by the controller into each worker's env)
    "MXNET_ELASTIC_MIN_WORKERS": (
        "1", int,
        "Smallest world size the elastic controller will shrink to on "
        "worker death before restarting at the same size."),
    "MXNET_ELASTIC_MAX_RESTARTS": (
        "8", int,
        "Unplanned whole-job restarts the controller performs before "
        "declaring the job dead (planned grow-backs are free); each "
        "burns a Retry-policy exponential backoff."),
    "MXNET_ELASTIC_REGROW_STEPS": (
        "0", int,
        "Committed checkpoint steps a DEGRADED (shrunk) incarnation must "
        "add before the controller drains it and grows back to the "
        "target world.  0 = never grow back automatically."),
    "MXNET_ELASTIC_HEARTBEAT_S": (
        "2", float,
        "Worker heartbeat interval (resilience.heartbeat daemon thread; "
        "started by the dist kvstore at bring-up when a heartbeat dir "
        "is configured)."),
    "MXNET_ELASTIC_HEARTBEAT_DIR": (
        None, str,
        "Directory of per-rank heartbeat files (hb-rank<R>.json, atomic "
        "rewrites).  The elastic controller injects one per incarnation; "
        "unset = heartbeats off."),
    "MXNET_ELASTIC_HANG_S": (
        "60", float,
        "Heartbeat staleness after which the controller declares a "
        "worker hung and SIGKILLs it (a wedged rank holds every peer "
        "hostage inside the collective).  0 disables hang detection."),
    "MXNET_ELASTIC_STRAGGLER_FACTOR": (
        "0", float,
        "Straggler threshold fed by the stepclock verdicts in the "
        "heartbeats: when every peer is comms-bound and exactly one "
        "rank is not, and its compute median exceeds this factor times "
        "the fastest peer's, the controller kills it and resizes.  "
        "0 (default) disables straggler mitigation."),
    "MXNET_ELASTIC_GRACE_S": (
        "10", float,
        "Drain grace: seconds between the controller's SIGTERM (the "
        "preemption-save path) and SIGKILL when stopping workers."),
    "MXNET_ELASTIC_INCARNATION": (
        "0", int,
        "Job incarnation counter the controller injects per (re)start; "
        "workers use it to scope restart-once behaviors and the "
        "heartbeat/flightrec records carry it."),
    "MXNET_ELASTIC_WORLD_TARGET": (
        None, int,
        "The job's TARGET world size, fixed across resizes (injected by "
        "the controller).  Workers shard a fixed data space over it so "
        "training math is world-size-independent; unset = current "
        "world."),
    # optimizer aggregation (reference MXNET_OPTIMIZER_AGGREGATION_SIZE)
    "MXNET_OPTIMIZER_AGGREGATION_SIZE": (
        "4", int,
        "Max same-dtype params fused into one multi-tensor optimizer "
        "dispatch (multi_sgd_update family); 1 disables aggregation. "
        "Only reached when MXNET_OPTIMIZER_FUSED=0."),
    # flat-buffer fused optimizer (ISSUE 5: optimizer_fusion)
    "MXNET_OPTIMIZER_FUSED": (
        "1", int,
        "If 1 (default), adam/sgd updates run as ONE donated jitted "
        "dispatch per dtype bucket over persistent flat state buffers "
        "(optimizer_fusion; bitwise identical to the per-param path); "
        "0 restores per-param updates everywhere."),
    "MXNET_OPTIMIZER_BUCKET_MB": (
        "25", float,
        "Fused-optimizer bucket size bound (MB): same-dtype parameters "
        "group into flat-state buckets of at most this many bytes, one "
        "donated update dispatch each. <= 0 disables optimizer fusion."),
    # serving engine (ISSUE 6: mx.serving — paged KV + continuous batching)
    "MXNET_SERVING_BLOCK_TOKENS": (
        "16", int,
        "Paged-KV block size (token positions per pool block): sequences "
        "allocate cache in blocks of this many tokens and a per-sequence "
        "block table maps positions to blocks, so mixed-length traffic "
        "shares one fixed-shape pool with no retrace."),
    "MXNET_SERVING_MAX_BATCH": (
        "8", int,
        "Decode slots in the continuous batch — the fixed B of the "
        "compiled (B, 1) decode step.  Finished sequences' slots are "
        "backfilled from the queue every iteration."),
    "MXNET_SERVING_MAX_SEQ": (
        "256", int,
        "Longest sequence (prompt + generation) a serving request may "
        "reach; sets each slot's block-table width.  Requests that could "
        "exceed it are rejected at submit."),
    "MXNET_SERVING_NUM_BLOCKS": (
        "0", int,
        "KV pool blocks (plus the reserved scratch block 0).  0 = worst "
        "case (max_batch * blocks_per_seq + 1: no preemption possible); "
        "smaller pools oversubscribe and rely on preemption-by-recompute."),
    "MXNET_SERVING_PREFILL_TOKENS": (
        "64", int,
        "Fixed padded prompt shape (1, P) the prefill step compiles at — "
        "prompts above it are rejected; must be <= MXNET_SERVING_MAX_SEQ."),
    "MXNET_SERVING_SLA_S": (
        "0", float,
        "Default per-request SLA deadline (seconds, submit to finish): "
        "expired requests are evicted (queued or mid-decode) with "
        "RequestDeadlineExceeded — the serving twin of the resilience "
        "Deadline policy.  0 = no deadline; submit(deadline_s=) overrides."),
    "MXNET_SERVING_PREFIX_CACHE": (
        "0", int,
        "If 1, the paged KV cache refcounts blocks and keeps a hash-keyed "
        "prefix index over full blocks of prompt tokens: a prompt sharing "
        "a cached prefix maps those blocks into its table (copy-on-write "
        "on contended writes) and prefills only the tail — bit-identical "
        "to the cold path, >= 2x fewer prefill positions on shared-"
        "system-prompt traffic.  Decoder-only (llama) engines only."),
    "MXNET_SERVING_DRAFT": (
        None, str,
        "Draft-model zoo config name for speculative decoding (e.g. "
        "'llama_tiny'): the replica CLI and serve_bench build it with the "
        "engine's vocab and seed so every replica speculates identically. "
        " Unset (default) = speculation off.  In-process callers pass "
        "ServingEngine(draft_model=) instead."),
    "MXNET_SERVING_SPEC_K": (
        "3", int,
        "Draft tokens proposed per scheduler iteration when speculative "
        "decoding is armed; the target verifies all of them (plus its "
        "own fallback token) in ONE fixed-shape (B, K+1) dispatch — "
        "accept-longest-prefix keeps output bit-identical to plain "
        "greedy decode at any acceptance rate."),
    # serving router tier (ISSUE 13: serving.router + serving.replica —
    # the *_DIR/INDEX vars are WRITTEN by the router into each replica's
    # env, the rest tune the router process itself)
    "MXNET_ROUTER_QUEUE": (
        "64", int,
        "Admission bound on requests outstanding in the router (waiting "
        "for dispatch + dispatched, unfinished).  Submits beyond it are "
        "shed immediately with RouterOverloaded (mxnet_router_shed_total) "
        "so overload degrades p99-bounded instead of collapsing."),
    "MXNET_ROUTER_HEDGE_S": (
        "0", float,
        "Tail-latency hedging: a dispatched request unfinished after this "
        "many seconds is duplicated to a second replica; the first "
        "completion wins and the loser is cancelled.  0 (default) "
        "disables hedging."),
    "MXNET_ROUTER_MAX_RETRIES": (
        "2", int,
        "Times the router resubmits one request to a surviving replica "
        "after the replica serving it died; beyond it the handle fails "
        "with ReplicaDeadError.  Resubmission re-prefills and is "
        "token-identical (greedy decode is deterministic)."),
    "MXNET_ROUTER_MAX_RESPAWNS": (
        "8", int,
        "Per-replica respawn budget: crashes beyond it leave the replica "
        "permanently down (the tier keeps serving on the survivors).  "
        "Respawns back off with the Retry policy's exponential schedule."),
    "MXNET_ROUTER_HANG_S": (
        "20", float,
        "Replica heartbeat staleness after which the router declares it "
        "hung, SIGKILLs it, resubmits its in-flight requests, and "
        "respawns it.  0 disables hang detection."),
    "MXNET_ROUTER_PING_S": (
        "1", float,
        "Idle-load refresh interval: the router pings each replica this "
        "often so least-loaded dispatch stays fresh between acks."),
    "MXNET_ROUTER_AFFINITY_TOKENS": (
        "16", int,
        "Prompt-prefix length (tokens) hashed for the router's prefix-"
        "affinity dispatch hint: least-loaded TIES prefer the replica "
        "that last served the same prefix hash, so shared-system-prompt "
        "streams hit the per-replica paged-KV prefix cache (bounded "
        "map; dead/busier replicas fall back to the rotating "
        "tie-break).  0 disables the hint."),
    "MXNET_ROUTER_DIR": (
        None, str,
        "Router tier working directory (WRITTEN by the router into each "
        "replica's env): the replica publishes its RPC port file here "
        "and the router keeps its state journal, heartbeats, telemetry "
        "shards, and flight-recorder dumps under it."),
    "MXNET_ROUTER_INDEX": (
        None, int,
        "This replica's index in the router tier (WRITTEN by the router; "
        "also mirrored into MXNET_DIST_RANK so heartbeat files and "
        "telemetry shards are rank-tagged per replica)."),
    # native (C++) fast lanes
    "MXNET_USE_NATIVE": (
        "1", int,
        "0 disables the native recordio scanner / fused JPEG decoder "
        "outright (pure-python fallbacks everywhere)."),
    "MXNET_NATIVE_CACHE": (
        None, str,
        "Directory the on-demand-compiled native libraries are built "
        "into and loaded from (default mxnet_tpu/src/build/ inside the "
        "checkout; set it when the package dir is read-only)."),
}

_lock = threading.Lock()
_cache: dict = {}


def get(name, default=None):
    """String value of an env var, with catalog defaults."""
    if name in os.environ:
        return os.environ[name]
    if name in KNOWN_VARS:
        d = KNOWN_VARS[name][0]
        return d if d is not None else default
    return default


def _typed(name, default, caster):
    v = get(name)
    if v is None:
        return default
    try:
        return caster(v)
    except (TypeError, ValueError):
        return default


def get_int(name, default=0):
    return _typed(name, default, int)


def get_float(name, default=0.0):
    return _typed(name, default, float)


def get_bool(name, default=False):
    v = get(name)
    if v is None:
        return default
    return str(v).lower() in ("1", "true", "yes", "on")


def describe():
    """Return the full catalog as rows (name, current, default, help)."""
    rows = []
    for name, (default, _typ, doc) in sorted(KNOWN_VARS.items()):
        rows.append((name, get(name), default, doc))
    return rows


if get_bool("MXNET_SHOW_ENV"):
    for _row in describe():
        print("%-40s = %-24s # %s" % (_row[0], _row[1], _row[3]))
