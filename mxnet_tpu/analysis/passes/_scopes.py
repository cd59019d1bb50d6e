"""Designated scopes the graftcheck passes key on.

One committed registry of WHERE each rule applies: the hot-path purity
scope (GC01/GC05), the flag-discipline module set (GC05), and the
threaded-module prefixes (GC04/GC06/GC10).  Passes import these instead
of hard-coding paths so adding a module to a scope is one reviewable
diff line.
"""

from __future__ import annotations

import ast

# --------------------------------------------------------------------------
# designated scopes
# --------------------------------------------------------------------------

# Hot-path purity scope (GC01/GC05): module rel-path -> function names, or
# None meaning every function in the module is hot.
HOT_PATHS = {
    "ops/registry.py": {"invoke", "invoke_arrays", "_apply_cast",
                        "_callable_for", "_build_callable", "_normalize_out"},
    "kvstore/fusion.py": None,
    "kvstore/local.py": {"_reduce", "_reduce_rowsparse", "_store_merged",
                         "push", "pull", "pushpull", "pushpull_list",
                         "_fused_pushpull", "pushpull_flat",
                         "_split_fusable", "_stage_bucket"},
    "gluon/trainer.py": {"step", "_allreduce_grads", "_allreduce_grads_impl",
                         "_update", "_update_impl", "_update_aggregated",
                         "_update_fused", "_fused_kind"},
    "optimizer_fusion.py": None,
    # serving hot path: the per-iteration scheduler core and everything
    # inside the jitted decode trace (models.py raw bodies + the paged
    # attention kernel) must stay host-sync-free
    "serving/engine.py": {"step", "_admit", "_admit_one", "_ensure_blocks",
                          "_emit", "_req_finished", "_finish", "_preempt",
                          "_spec_step", "_spec_budgets", "_upload_tables",
                          "_sync_prefix_counters"},
    "serving/models.py": None,
    # prefix-cache bookkeeping (ISSUE 15): match/admit/prepare_write/
    # ensure_capacity run on every admission and scheduler iteration
    "serving/cache.py": None,
    "kernels/paged_attention.py": None,
    # io decode pipeline (ISSUE 7): the per-batch scheduler/collector core
    # and the worker decode body are the input-bound hot path
    "io/pipeline.py": {"next_batch", "_assemble_loop", "_collect", "_pump",
                       "_issue", "_inline_chunk", "_decode_chunk",
                       "_read_payload", "_attach_slab"},
    # sharding engine (ISSUE 8): rule matching/resolution runs at trace
    # time but sits on the TrainStep dispatch path, and the per-step
    # __call__/run bodies must stay host-sync-free
    "sharding.py": None,
    "parallel.py": {"__call__", "run", "_param_sharding",
                    "_shardings", "_data_shardings", "_build",
                    "_build_multi"},
    # observability plane (ISSUE 10): the StepClock feeds from the
    # trainer/TrainStep step path and counter shipping rides the decode
    # ack channel — both must stay host-sync-free and flag-disciplined
    "telemetry/stepclock.py": {"begin_step", "note", "end_step"},
    "telemetry/aggregate.py": {"counter_deltas", "absorb_counter_deltas"},
    # analytic observatory (ISSUE 12): the jit-boundary wrapper sits on
    # every instrumented dispatch (op dispatch included when armed) and
    # the scrape handler runs per request on server threads — both must
    # stay host-sync-free and flag-disciplined
    "telemetry/costmodel.py": {"__call__", "_probe", "wrap_jit",
                               "wrap_jit_if_armed", "_on_time_span"},
    "telemetry/httpd.py": {"do_GET"},
    # elastic control plane (ISSUE 11): the controller's monitor loop
    # polls several times a second and the heartbeat note sits on the
    # worker's step path — both must stay host-sync-free and
    # flag-disciplined
    "resilience/controller.py": {"_watch_loop", "_poll_workers",
                                 "_read_heartbeats", "_check_hangs",
                                 "_check_straggler", "_manifest_latest"},
    "resilience/heartbeat.py": {"set_step", "beat", "_beater"},
    # serving router tier (ISSUE 13): the dispatch/ack/reader loops run
    # per request, the monitor polls several times a second, and the
    # replica's waiter/handler sit on every ack — all must stay
    # host-sync-free and flag-disciplined
    "serving/router.py": {"_dispatch_loop", "_dispatch_one",
                          "_pick_replica", "_send_to", "_on_ack",
                          "_reader_loop", "_monitor_loop", "_hedge_scan",
                          "_respawn_dead", "_check_heartbeats",
                          "_sweep_queued_deadlines", "_finish_req"},
    "serving/replica.py": {"_handle", "_waiter", "_send", "_load"},
}

# GC05 additionally audits these (they sit on the per-batch/per-call path
# even though they are not purity-critical).
FLAG_DISCIPLINE_MODULES = set(HOT_PATHS) | {
    "gluon/data/dataloader.py", "kvstore/dist.py",
}

# Threaded modules (GC04): rel-path prefixes.  These own locks or run user
# code on worker threads.
THREADED_MODULES = (
    "engine.py", "native.py", "profiler.py", "checkpoint.py",
    "ops/registry.py", "telemetry/", "resilience/",
    "gluon/data/dataloader.py", "kvstore/sparse_ps.py", "serving/",
    "io/pipeline.py",
)


def _dotted(expr):
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
        return ".".join(reversed(parts))
    return None


def _is_threaded(rel):
    return any(rel == t or (t.endswith("/") and rel.startswith(t))
               for t in THREADED_MODULES)


def _walk_shallow(fn):
    """Yield nodes of ``fn``'s body without descending into nested
    function definitions (those are analyzed as their own scopes)."""
    stack = [fn]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            stack.append(child)


def _hot_functions(module):
    """Yield (qualname, FunctionDef) for every designated hot function in
    the module (nested defs inside a hot function are hot too)."""
    spec = HOT_PATHS.get(module.rel)
    if module.rel not in HOT_PATHS:
        return

    def walk(node, prefix, inside_hot):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                hot = inside_hot or spec is None or child.name in spec
                if hot:
                    yield qual, child
                yield from walk(child, qual + ".", hot)
            elif isinstance(child, ast.ClassDef):
                yield from walk(child, f"{prefix}{child.name}.", inside_hot)

    yield from walk(module.tree, "", False)


