"""Flat-buffer fused optimizer updates — multi-tensor apply with donation.

The builders' step decomposition of the BERT seq-512 lane (through the
earlier chip access; not re-measured on today's code) named the
optimizer as a gap: the 110M-param multi-precision adam cost 8.9 ms/step
against a ~3.2 ms HBM bound because the update ran as one small dispatch
per parameter, each re-reading weights and states from HBM.  Every serious trainer fuses here — PyTorch
DDP buckets gradients, NVIDIA Apex runs multi-tensor `FusedAdam` — and
this module is that layer for the TPU rebuild, shaped like PR 2's kvstore
gradient fusion (same `GradBucketer` bucket layout, same bit-identity
contract, same cached-executable discipline):

- Same-dtype dense parameters group, in key order, into size-bounded
  buckets (``MXNET_OPTIMIZER_BUCKET_MB``, default 25) planned by
  ``kvstore.fusion.GradBucketer``.
- Each bucket updates with ONE jitted call whose ``donate_argnums``
  cover every weight and optimizer-state buffer (adam m/v, sgd momentum,
  multi-precision fp32 masters): each buffer is read once and written in
  place, and steady-state dispatch count equals bucket count.
  Executables cache per bucket signature (shapes, dtype, optimizer
  kind, static hyperparams), so the retrace count stays flat
  (``exec_builds()`` is the invariant tests assert).
- The gradient side has two entry modes: per-parameter gradients
  (``fused_update`` — the no-kvstore / in-process path) and ONE flat
  reduced bucket straight off the fused-allreduce wire
  (``fused_update_flat`` — ``KVStoreLocal.pushpull_flat`` hands the
  psum output over and the executable slices it per segment, skipping
  the unflatten/reflatten HBM round trip entirely).
- ``traced=True`` runs the same math inline on traced values —
  ``parallel.TrainStep`` routes its in-trace update through it so the
  fused SPMD step stops paying ~200 dispatch-wrapper traces.

Bit-identity contract: the update math mirrors ``ops/optimizer_ops.py``
formula-for-formula, including scalar promotion (python-float attrs
trace as weak f32, so every dynamic scalar here is rounded to f32 and
then cast to the compute dtype) and the multi-precision
``grad.astype(float32)`` / ``master.astype(weight.dtype)`` casts.
Fused and per-param paths are bitwise identical on every tested
combination; callers may switch freely.

Layout note (measured, XLA:CPU): within one executable, every per-param
output keeps its own buffer.  Concatenating state outputs into one flat
buffer looks attractive (it is how the gradients arrive), but the
fused concat loop carries region-dependent scalars (per-param lr/wd)
and XLA contracts (fma) it differently from the small per-param
kernels — a 1-ulp split that survives ``lax.optimization_barrier``
(fusion inlines straight through barriers).  Per-param output fusions
have the same structure as the reference kernels and round identically;
per-param lr/wd must ride as individual scalar args for the same reason
(an indexed vector load inside the kernel changes codegen).  The flat
gradient entry slices its segments behind an ``optimization_barrier``
for the same reason: a slice fused into the update loop rounds
differently from the per-param kernels under jaxlib 0.9.

Donation invariant: callers must NOT alias donated buffers — after a
fused update, previously captured raw ``jax.Array`` references to
weight or state buffers are dead (NDArray handles stay valid; they
re-read the swapped slot).

Fallback rules (exactly like the kvstore fused path): sparse/row-sparse
parameters, ``update_on_kvstore``, loss-scale overflow skips, and
unsupported optimizers keep the per-key path, gated by
``MXNET_OPTIMIZER_FUSED`` (1 enables, 0 restores per-param everywhere).
"""

from __future__ import annotations

import threading
import time as _time

import numpy as _np

from . import config
from . import telemetry as _tel
from .telemetry import costmodel as _costmodel
from .telemetry import tracer as _ttrace

__all__ = ["fusion_enabled", "fusion_active", "supported_kind",
           "bucket_bytes_from_env", "fused_update", "fused_update_flat",
           "traced_update", "plan_trainstep", "planner", "reset",
           "exec_builds", "record_fallback", "record_update",
           "DEFAULT_OPT_BUCKET_MB"]

DEFAULT_OPT_BUCKET_MB = 25.0

# fused-update visibility (ISSUE 5 satellite): dispatches (= buckets),
# parameters riding fused vs falling back, per-bucket host latency
_M_FUSED_UPDATES = _tel.counter(
    "mxnet_optimizer_fused_updates_total",
    "Fused optimizer update calls (one per replica step taking the "
    "bucketed path).")
_M_FUSED_BUCKETS = _tel.counter(
    "mxnet_optimizer_fused_buckets_total",
    "Optimizer buckets dispatched (one donated jitted update each).")
_M_FUSED_PARAMS = _tel.counter(
    "mxnet_optimizer_fused_params_total",
    "Parameters updated through the fused bucket path.")
_M_FALLBACK_PARAMS = _tel.counter(
    "mxnet_optimizer_fused_fallback_params_total",
    "Parameters that fell back to the per-param update path "
    "(sparse / unsupported).")
_M_BUCKET_SECONDS = _tel.histogram(
    "mxnet_optimizer_fused_bucket_seconds",
    "Host-side latency per fused optimizer bucket dispatch.")


def fusion_enabled():
    """MXNET_OPTIMIZER_FUSED knob (default on); 0 restores the per-param
    update path everywhere (bit-identical by contract)."""
    return config.get_int("MXNET_OPTIMIZER_FUSED", 1) != 0


def bucket_bytes_from_env():
    """MXNET_OPTIMIZER_BUCKET_MB → bytes; <= 0 disables fusion."""
    return int(config.get_float("MXNET_OPTIMIZER_BUCKET_MB",
                                DEFAULT_OPT_BUCKET_MB) * (1 << 20))


def supported_kind(optimizer):
    """'adam' / 'sgd' for exactly the optimizers whose update the fused
    executables reproduce bit-for-bit; None for everything else
    (subclasses excluded on purpose — they may override the math)."""
    from . import optimizer as _opt
    t = type(optimizer)
    if t is _opt.Adam:
        return "adam"
    if t is _opt.SGD:
        return "sgd"
    return None


def fusion_active(optimizer):
    """ONE gate for every entry point: knob on, bucket bound positive,
    and the optimizer's math reproduced exactly.  Callers that bypass
    this (e.g. an SGD subclass inheriting update_multi) must fall back
    to their legacy path."""
    return (fusion_enabled() and bucket_bytes_from_env() > 0
            and supported_kind(optimizer) is not None)


# -- bucket planning ---------------------------------------------------------

_lock = threading.Lock()
_planner = None


def planner():
    """Module-wide GradBucketer planning optimizer buckets (the kvstore's
    layout machinery, reused with n_rep=1).  Rebuilt whenever
    MXNET_OPTIMIZER_BUCKET_MB changes, so a runtime knob flip (e.g. a
    bucket-size sweep) replans instead of half-applying."""
    global _planner
    nbytes = bucket_bytes_from_env()
    with _lock:
        if _planner is None or _planner.bucket_bytes != nbytes:
            from .kvstore.fusion import GradBucketer
            _planner = GradBucketer(nbytes)
        return _planner


def reset():
    """Drop plan + executable caches (tests flip knobs at runtime)."""
    global _planner
    with _lock:
        _planner = None
        _EXEC_CACHE.clear()


# -- state roles -------------------------------------------------------------

def _roles(kind, mp, has_mom):
    if kind == "adam":
        return (("master",) if mp else ()) + ("mean", "var")
    return (("master",) if mp else ()) + (("mom",) if has_mom else ())


def _role_arrays(kind, mp, has_mom, state):
    """Per-param state tree -> NDArrays in _roles order."""
    if kind == "adam":
        if mp:
            master, (m, v) = state
            return [master, m, v]
        m, v = state
        return [m, v]
    if mp:
        master, mom = state
        return [master] + ([mom] if has_mom else [])
    return [state] if has_mom else []


def _offsets(sizes):
    offs, off = [], 0
    for s in sizes:
        offs.append(off)
        off += s
    return tuple(offs), off


# -- the per-param math (shared by jitted executables and traced mode) -------

def _scal(s, dtype):
    """Dynamic scalar → compute dtype, mirroring how the per-param ops see
    python-float attrs: weak-f32 first (jit traces python floats as weak
    f32), then the array-dtype demotion."""
    import jax.numpy as jnp
    if isinstance(s, (int, float)):
        s = _np.float32(s)
    return jnp.asarray(s).astype(dtype)


def _param_update(kind, mp, has_mom, cfg, w, g, sts, lr, wd, rescale,
                  momentum):
    """One parameter's update on raw jax values in their native shapes.
    Mirrors ops/optimizer_ops.py {sgd,sgd_mom,adam}_update plus the
    update_multi_precision wrapper formula-for-formula (same op order,
    same scalar promotion, same mp casts) — this is what makes the fused
    path bitwise identical to the per-param path.  Returns
    (new_weight, new_states_in_role_order)."""
    import jax.numpy as jnp
    beta1, beta2, eps, clip = cfg
    if mp:
        w16 = w
        w = sts[0]                  # fp32 master
        cdt = w.dtype
        g = g.astype(cdt)           # update_multi_precision: grad → f32
    else:
        cdt = w.dtype
    lr = _scal(lr, cdt)
    wd = _scal(wd, cdt)
    g = g * _scal(rescale, cdt)
    if clip is not None and clip >= 0:
        g = jnp.clip(g, -clip, clip)
    if kind == "adam":
        m, v = sts[-2], sts[-1]
        g = g + wd * w
        new_m = beta1 * m + (1 - beta1) * g
        new_v = beta2 * v + (1 - beta2) * jnp.square(g)
        new_w = w - lr * new_m / (jnp.sqrt(new_v) + eps)
        outs = (new_m, new_v)
    elif has_mom:
        new_mom = _scal(momentum, cdt) * sts[-1] - lr * (g + wd * w)
        new_w = w + new_mom
        outs = (new_mom,)
    else:
        new_w = w - lr * (g + wd * w)
        outs = ()
    if mp:
        return new_w.astype(w16.dtype), (new_w,) + outs
    return new_w, outs


# -- cached donated executables ----------------------------------------------

_EXEC_CACHE: dict = {}
_builds = 0


def exec_builds():
    """Executable constructions so far — a steady-state training loop must
    not grow this after its first step (the retrace invariant)."""
    return _builds


def _get_exec(kind, mp, has_mom, shapes, sizes, dtype, cfg, flat_grad):
    global _builds
    key = (kind, mp, has_mom, tuple(shapes), str(dtype), cfg, flat_grad)
    fn = _EXEC_CACHE.get(key)
    if fn is None:
        with _lock:
            fn = _EXEC_CACHE.get(key)
            if fn is None:
                fn = _build_exec(kind, mp, has_mom, tuple(shapes),
                                 tuple(sizes), cfg, flat_grad)
                _EXEC_CACHE[key] = fn
                _builds += 1
    return fn


def _build_exec(kind, mp, has_mom, shapes, sizes, cfg, flat_grad):
    """ONE jitted update for a whole bucket.  Argument layout:
    ``w_0..w_{n-1}, grads (n per-param arrays | 1 flat buffer),
    states (role-major: role0_p0..role0_p{n-1}, role1_p0..),
    lr_0..lr_{n-1}, wd_0..wd_{n-1}, rescale[, momentum]`` →
    ``(w'_0.., states'_role_major..)``.  Weights and states are donated
    — read once, written in place.  Per-param lr/wd ride as individual
    traced SCALARS and every output keeps its own per-param buffer: both
    are bit-identity requirements (see the module docstring's layout
    note on XLA:CPU fma contraction)."""
    import jax

    n = len(shapes)
    offs, _ = _offsets(sizes)
    n_roles = len(_roles(kind, mp, has_mom))
    g_args = 1 if flat_grad else n
    base = n + g_args

    def fn(*args):
        ws = args[:n]
        flats = args[base:base + n_roles * n]
        s0 = base + n_roles * n
        lrs = args[s0:s0 + n]
        wds = args[s0 + n:s0 + 2 * n]
        rescale = args[s0 + 2 * n]
        mom = args[s0 + 2 * n + 1] if has_mom else None
        new_ws = []
        new_states = [[] for _ in range(n_roles)]
        if flat_grad:
            # the barrier keeps each segment a buffer of its own: with
            # the slice fused into the update loop, XLA:CPU (jaxlib
            # 0.9) contracts the mul+adds differently from the
            # per-param kernels and ~1 element in 256 lands 1 ulp off
            gs = jax.lax.optimization_barrier(tuple(
                args[n][offs[i]:offs[i] + sizes[i]].reshape(shapes[i])
                for i in range(n)))
        else:
            gs = args[n:n + n]
        for i in range(n):
            g = gs[i]
            sts = [flats[r * n + i] for r in range(n_roles)]
            new_w, outs = _param_update(kind, mp, has_mom, cfg, ws[i], g,
                                        sts, lrs[i], wds[i], rescale, mom)
            new_ws.append(new_w)
            for r in range(n_roles):
                new_states[r].append(outs[r])
        return tuple(new_ws) + tuple(
            s for role in new_states for s in role)

    donate = tuple(range(n)) + tuple(range(base, base + n_roles * n))
    return _costmodel.wrap_jit(jax.jit(fn, donate_argnums=donate),
                               f"optimizer_fusion.{kind}")


# -- apply -------------------------------------------------------------------

def _static_cfg(optzr, kind):
    clip = optzr.clip_gradient
    clip = float(clip) if clip is not None else -1.0
    if kind == "adam":
        return (optzr.beta1, optzr.beta2, optzr.epsilon, clip)
    return (None, None, None, clip)


def _eff_lr_wd(optzr, kind, indices):
    """Per-param effective lr/wd AFTER counts advanced — adam's bias
    correction folds into lr exactly like Adam.update does (host f64
    math imperative, traced scalars inside TrainStep)."""
    lrs, wds = [], []
    for i in indices:
        lr = optzr._get_lr(i)
        if kind == "adam":
            t = optzr._index_update_count[i]
            lr = lr * ((1. - optzr.beta2 ** t) ** 0.5
                       / (1. - optzr.beta1 ** t))
        lrs.append(lr)
        wds.append(optzr._get_wd(i))
    return lrs, wds


def _apply_bucket(optzr, kind, shapes, sizes, indices, weights, grads,
                  flat_grad, states, traced):
    """Update one bucket: ONE donated dispatch imperative, inline math
    traced.  ``states`` aligns with ``indices`` (per-param trees)."""
    from .optimizer import Optimizer
    mp = bool(optzr.multi_precision) and Optimizer._is_half(weights[0].dtype)
    has_mom = kind == "sgd" and bool(getattr(optzr, "momentum", 0.0))
    cfg = _static_cfg(optzr, kind)
    lrs, wds = _eff_lr_wd(optzr, kind, indices)
    n = len(indices)
    n_roles = len(_roles(kind, mp, has_mom))
    # role-major per-param state NDArrays (mirrors the executable layout)
    by_role = [[_role_arrays(kind, mp, has_mom, st)[r] for st in states]
               for r in range(n_roles)]

    if traced:
        offs, _ = _offsets(sizes)
        for i in range(n):
            if flat_grad is not None:
                g = flat_grad[offs[i]:offs[i] + sizes[i]].reshape(shapes[i])
            else:
                g = grads[i]._data
            sts = [by_role[r][i]._data for r in range(n_roles)]
            new_w, outs = _param_update(
                kind, mp, has_mom, cfg, weights[i]._data, g, sts,
                lrs[i], wds[i], optzr.rescale_grad,
                getattr(optzr, "momentum", None))
            weights[i]._set_data(new_w)
            for r in range(n_roles):
                by_role[r][i]._set_data(outs[r])
        return

    enabled = _ttrace._ENABLED
    t0 = _time.perf_counter_ns() if enabled else 0
    fn = _get_exec(kind, mp, has_mom, shapes, sizes, weights[0].dtype, cfg,
                   flat_grad is not None)
    args = [w._data for w in weights]
    if flat_grad is not None:
        dev = getattr(weights[0]._data, "device", None)
        if dev is not None and getattr(flat_grad, "device", None) != dev:
            import jax
            flat_grad = jax.device_put(flat_grad, dev)
        args.append(flat_grad)
    else:
        args += [g._data for g in grads]
    for role in by_role:
        args += [s._data for s in role]
    args += [_np.float32(lr) for lr in lrs]
    args += [_np.float32(wd) for wd in wds]
    args.append(_np.float32(optzr.rescale_grad))
    if has_mom:
        args.append(_np.float32(optzr.momentum))
    outs = fn(*args)
    for i in range(n):
        weights[i]._set_data(outs[i])
    for r in range(n_roles):
        for i in range(n):
            by_role[r][i]._set_data(outs[n + r * n + i])
    if enabled:
        _M_FUSED_BUCKETS.inc()
        _M_FUSED_PARAMS.inc(n)
        _M_BUCKET_SECONDS.observe((_time.perf_counter_ns() - t0) / 1e9)


def fused_update(optzr, indices, weights, grads, states, traced=False):
    """Multi-tensor fused update from per-param gradients: plan dtype
    buckets, one donated jitted dispatch per bucket.  The states list
    aligns with indices (per-param trees from Updater._ensure_state)."""
    kind = supported_kind(optzr)
    if kind is None:
        raise RuntimeError(f"optimizer_fusion does not support "
                           f"{type(optzr).__name__}")
    for i in indices:
        optzr._update_count(i)
    signature = tuple((tuple(w.shape), str(w.dtype), 1) for w in weights)
    buckets = planner().plan(signature)
    for b in buckets:
        pos = b.positions
        _apply_bucket(optzr, kind, b.shapes, b.sizes,
                      [indices[p] for p in pos],
                      [weights[p] for p in pos],
                      [grads[p] for p in pos], None,
                      [states[p] for p in pos], traced)


def fused_update_flat(optzr, indices, weights, states, shapes, sizes,
                      flat_grad, traced=False):
    """One bucket whose reduced gradients arrive as a single flat buffer
    straight off the fused allreduce wire (KVStoreLocal.pushpull_flat) —
    the flat buffer feeds the donated update directly (the executable
    slices it per segment), skipping the unflatten/reflatten HBM round
    trip."""
    kind = supported_kind(optzr)
    if kind is None:
        raise RuntimeError(f"optimizer_fusion does not support "
                           f"{type(optzr).__name__}")
    for i in indices:
        optzr._update_count(i)
    _apply_bucket(optzr, kind, tuple(tuple(s) for s in shapes),
                  tuple(sizes), indices, weights, None, flat_grad,
                  states, traced)


def record_fallback(n_params):
    """Parameters the caller routed per-key (sparse / unsupported)."""
    if n_params:
        _M_FALLBACK_PARAMS.inc(n_params)


def record_update():
    """One replica step took the bucketed path (Trainer counts this once
    per replica — fused_update/fused_update_flat can run several times
    within one step, so they must not self-count)."""
    _M_FUSED_UPDATES.inc()


# -- TrainStep integration ---------------------------------------------------

def plan_trainstep(optzr, trainable):
    """Bucket plan for a TrainStep's trainable params, computed at resolve
    time (host side, before any tracing).  Returns (kind, plan) with
    plan = [(bucket, positions)], or None when fusion is off or the
    optimizer is unsupported."""
    if not trainable or not fusion_active(optzr):
        return None
    kind = supported_kind(optzr)
    signature = tuple((tuple(p.data().shape), str(p.data().dtype), 1)
                      for p in trainable)
    buckets = planner().plan(signature)
    return kind, [(b, list(b.positions)) for b in buckets]


def traced_update(optzr, kind, plan, trainable, states):
    """The in-trace fused update TrainStep's raw() body calls instead of
    the per-param update_multi_precision loop.  Same math as the
    imperative executables."""
    for b, pos in plan:
        _apply_bucket(optzr, kind, b.shapes, b.sizes, pos,
                      [trainable[p]._data for p in pos],
                      [trainable[p]._data._grad for p in pos], None,
                      [states[p] for p in pos], True)
