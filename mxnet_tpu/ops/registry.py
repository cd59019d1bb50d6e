"""Operator registry + imperative dispatch — the rebuild of nnvm's op registry
and the imperative invoke path.

Reference anchors (SURVEY §2 N4/N7/N25, §3.1):
 - ``NNVM_REGISTER_OP(name).set_attr<FCompute>(...)`` — C++ attribute registry.
 - ``src/imperative/imperative.cc :: Imperative::Invoke`` + ``InvokeOp`` — the
   eager path: infer shape/type, record on the autograd tape, push to engine.
 - ``python/mxnet/ndarray/register.py`` — Python namespaces *generated from the
   registry* at import.

TPU-native design: an op is a JAX-traceable Python callable
``fn(*jax_arrays, **attrs) -> array | tuple``.  Shape/dtype inference comes
free from JAX abstract evaluation (no FInferShape/FInferType to write);
gradients come free from JAX autodiff (FGradient only where semantics diverge,
via ``custom_vjp`` inside the impl).  Imperative dispatch optionally routes
through a per-(op, attrs) ``jax.jit`` cache — XLA then specializes per
shape/dtype, which is the TPU analog of the reference's kernel dispatch.
When autograd is recording, we capture ``jax.vjp`` residuals at dispatch time
(the tape stores concrete vjp closures, so backward never re-runs forward).
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time as _time

from ..base import MXNetError
from .. import config, engine
from .. import telemetry as _telemetry
from ..telemetry import costmodel as _costmodel
from ..telemetry import tracer as _ttrace

__all__ = ["Op", "register", "get", "list_ops", "invoke", "invoke_arrays"]

_REGISTRY: dict = {}
_ndarray_mod = None  # set by mxnet_tpu.ndarray at import (late-bound to break cycle)


def _nd():
    global _ndarray_mod
    if _ndarray_mod is None:
        from .. import ndarray as _m
        _ndarray_mod = _m.ndarray
    return _ndarray_mod


class Op:
    """One registered operator.

    Attributes
    ----------
    name : registry name; dots create sub-namespaces (``random.uniform`` →
        ``mx.nd.random.uniform``), leading ``_`` marks internal.
    fn : the JAX impl, ``fn(*arrays, **attrs)``.
    num_outputs : static output count, or -1 (tuple of variable length).
    differentiable : False for int-valued/sampling ops — recording skips them
        (reference ops mark these with zero FGradient).
    mutate_inputs : pairs ``(out_idx, in_idx)`` — output out_idx is written
        back into input in_idx's slot (reference FMutateInputs, e.g. BatchNorm
        running stats).  The impl *returns* updated values (functional);
        dispatch performs the slot writeback.
    wrap_key : if not None, dispatch injects a fresh PRNG key kwarg under this
        name (stateful-RNG facade, see mxnet_tpu.random).
    """

    __slots__ = ("name", "fn", "num_outputs", "differentiable",
                 "mutate_inputs", "wrap_key", "wrap_train", "doc", "jit",
                 "visible_outputs", "dynamic_attrs", "infer_args",
                 "input_names", "aux_names", "omit_inputs")

    def __init__(self, name, fn, num_outputs=1, differentiable=True,
                 mutate_inputs=(), wrap_key=None, wrap_train=None, jit=True,
                 doc=None, visible_outputs=None, dynamic_attrs=()):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs
        self.differentiable = differentiable
        self.mutate_inputs = tuple(mutate_inputs)
        self.wrap_key = wrap_key
        self.wrap_train = wrap_train
        self.jit = jit
        self.doc = doc if doc is not None else fn.__doc__
        # visible_outputs: how many outputs the *caller* sees (reference
        # "visible outputs" concept — BatchNorm returns 1 of its 3).
        self.visible_outputs = visible_outputs
        # dynamic_attrs: scalar attrs passed as *traced* jit arguments so a
        # per-step-varying value (lr schedule, lamb's t) does not trigger a
        # fresh XLA compile per value.
        self.dynamic_attrs = tuple(dynamic_attrs)
        # infer_args(known_shapes, attrs) -> shapes — fills unknown input
        # shapes from known ones (the FInferShape backward-propagation role,
        # used by Symbol.infer_shape / simple_bind)
        self.infer_args = None
        # input_names: declared positional inputs (reference nnvm
        # FListInputNames) — symbol composition auto-creates variables
        # "<name>_<input>" for the ones not passed, aux_names marking
        # auxiliary states (BatchNorm moving stats).  omit_inputs(attrs)
        # returns input names absent under these attrs (e.g. no_bias).
        self.input_names = None
        self.aux_names = frozenset()
        self.omit_inputs = None

    def __repr__(self):
        return f"<Op {self.name}>"


def register(name, **kwargs):
    """Decorator: ``@register("dot")`` — the NNVM_REGISTER_OP analog."""
    def deco(fn):
        if name in _REGISTRY:
            raise MXNetError(f"op {name!r} already registered")
        _REGISTRY[name] = Op(name, fn, **kwargs)
        return fn
    return deco


def get(name):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise MXNetError(f"no such operator: {name!r}") from None


def alias(new_name, existing_name):
    """Expose an op under a second name (the upstream registries carry
    legacy CamelCase aliases next to snake_case).  Fails loudly on a
    missing target or a name collision — same invariants as register()."""
    if existing_name not in _REGISTRY:
        raise MXNetError(f"alias target {existing_name!r} not registered")
    if new_name in _REGISTRY:
        raise MXNetError(f"op {new_name!r} already registered")
    _REGISTRY[new_name] = _REGISTRY[existing_name]


def list_ops():
    return sorted(_REGISTRY)


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

_jit_cache: dict = {}
_jit_lock = threading.Lock()


def _costmodel_rearm():
    """arm()/disarm() flips whether fresh dispatch callables carry the
    cost-ledger wrapper; drop the built ones so the next dispatch rebuilds
    through wrap_jit_if_armed under the new mode (the per-op hot path
    itself stays wrapper-free while disarmed)."""
    with _jit_lock:
        _jit_cache.clear()
    _callable_memo.clear()


_costmodel.add_rearm_hook(_costmodel_rearm)

# The layout of the TrainStep program this thread is tracing: set by
# parallel.TrainStep around its trace, None otherwise.  An op that GSPMD
# cannot partition — the Pallas flash kernel, ops.contrib._flash — reads it
# to place its own shard_map.  Its key is part of every trace-cache key of
# this module and of gluon's CachedOp: a nested jit caches its trace by
# avals alone, and would replay a kernel laid out for another mesh.
_step = threading.local()


def step_layout():
    """``(DeviceMesh, batch_axes)`` of the TrainStep being traced on this
    thread, else None; ``batch_axes`` are the mesh axes dim 0 of the batch
    is sharded over."""
    return getattr(_step, "layout", None)


def step_layout_key():
    """Hashable identity of :func:`step_layout` (None outside a trace)."""
    return getattr(_step, "key", None)


@contextlib.contextmanager
def step_layout_scope(mesh, batch_axes):
    saved = step_layout(), step_layout_key()
    _step.layout = (mesh, batch_axes)
    _step.key = (tuple(d.id for d in mesh.devices), mesh.axis_names,
                 mesh.shape, batch_axes)
    try:
        yield
    finally:
        _step.layout, _step.key = saved


# Pre-dispatch array-cast hook (mxnet_tpu.amp): fn(op_name, arrays) -> arrays,
# jax-traceable so it folds into jit traces.  _dispatch_epoch bumps whenever
# the hook changes so shape/dtype-keyed caches (CachedOp) retrace.
_cast_hook = None
_dispatch_epoch = 0


def set_dispatch_cast_hook(fn):
    global _cast_hook, _dispatch_epoch
    _cast_hook = fn
    _dispatch_epoch += 1


def dispatch_epoch():
    return _dispatch_epoch


def _apply_cast(op, arrays):
    if _cast_hook is None:
        return arrays
    return _cast_hook(op.name, arrays)


# Monitor hooks (mx.monitor): fn(op_name, out_arrays) called post-dispatch
# with the op's raw output arrays.  Kept as a list so several monitors can
# coexist (the reference allows one callback per executor; global here).
_monitor_hooks: list = []


def add_monitor_hook(fn):
    if fn not in _monitor_hooks:
        _monitor_hooks.append(fn)


def remove_monitor_hook(fn):
    try:
        _monitor_hooks.remove(fn)
    except ValueError:
        pass


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


# memo over the FULL _callable_for result: on the hot path (telemetry off,
# attrs hashable) a repeat invoke is one tuple build + dict probe instead of
# re-freezing attrs and rebuilding wrapper/partial closures per call.  Only
# ops interned in _REGISTRY participate — transient Op objects (numpy
# wrappers, autograd backward replays, CachedOp) carry per-instance
# closures that must never outlive them.  Keys with unhashable attr values
# (PRNG keys, traced arrays, list attrs) also skip the memo and take the
# build path, which handles them via _freeze/TypeError.
_callable_memo: dict = {}
_CALLABLE_MEMO_MAX = 1024


def _callable_for(op, attrs):
    """A positional-only callable with attrs bound, jitted when enabled.

    Attrs named in op.dynamic_attrs holding plain numbers are passed as traced
    jit arguments (one compile covers all their values); everything else is a
    static part of the cache key.
    """
    jit_on = op.jit and config.get_int("MXNET_TPU_JIT_IMPERATIVE", 1)
    mkey = None
    if _REGISTRY.get(op.name) is op:  # interned op: stable identity
        try:
            mkey = (op.name, jit_on,
                    tuple(attrs.items()) if attrs else None,
                    getattr(_step, "key", None))
            f = _callable_memo.get(mkey)
            if f is not None:
                return f
        except TypeError:
            mkey = None
    f = _build_callable(op, attrs, jit_on)
    if mkey is not None:
        if len(_callable_memo) >= _CALLABLE_MEMO_MAX:
            _callable_memo.clear()
        _callable_memo[mkey] = f
    return f


def _build_callable(op, attrs, jit_on):
    dyn = {k: attrs[k] for k in op.dynamic_attrs
           if k in attrs and isinstance(attrs[k], (int, float))
           and not isinstance(attrs[k], bool)}
    static = {k: v for k, v in attrs.items() if k not in dyn}
    if not jit_on:
        return functools.partial(op.fn, **attrs) if attrs else op.fn
    dyn_keys = tuple(sorted(dyn))
    key = (op.name, _freeze(static), dyn_keys, step_layout_key())
    try:
        jf = _jit_cache.get(key)
    except TypeError:  # unhashable attr (e.g. a traced array kwarg) — no cache
        return functools.partial(op.fn, **attrs) if attrs else op.fn
    if jf is None:
        import jax

        def wrapper(_dyn_vals, *arrays, _fn=op.fn, _static=static,
                    _dyn_keys=dyn_keys):
            kw = dict(_static)
            kw.update(zip(_dyn_keys, _dyn_vals))
            return _fn(*arrays, **kw)

        with _jit_lock:
            jf = _jit_cache.setdefault(
                key, _costmodel.wrap_jit_if_armed(jax.jit(wrapper),
                                                  f"op:{op.name}"))
    dyn_vals = tuple(dyn[k] for k in dyn_keys)
    return lambda *arrays: jf(dyn_vals, *arrays)


def invoke_arrays(op, arrays, attrs):
    """Run an op on raw jax arrays (no NDArray wrapping, no tape)."""
    arrays = _apply_cast(op, arrays)
    f = _callable_for(op, attrs)
    return f(*arrays)


def _normalize_out(op, raw):
    if isinstance(raw, (tuple, list)):
        return list(raw)
    return [raw]


def invoke(op, inputs, attrs=None, out=None, ctx=None):
    """The Imperative::Invoke analog.

    inputs : list of NDArray (reads).
    out : None | NDArray | list[NDArray] — in-place destination(s); written
        via slot swap (versioned-buffer discipline, SURVEY §7.1 N3 row).
    Returns NDArray or list of NDArrays.
    """
    from .. import autograd
    nd = _nd()
    if isinstance(op, str):
        op = get(op)
    attrs = dict(attrs) if attrs else {}

    in_ctx = None
    for a in inputs:
        if isinstance(a, nd.NDArray):
            in_ctx = a.ctx
            break
    if in_ctx is None:
        from ..context import current_context
        in_ctx = ctx if ctx is not None else current_context()

    arrays = [a._data if isinstance(a, nd.NDArray) else a for a in inputs]

    if op.wrap_key is not None:
        from .. import random as _rnd
        attrs[op.wrap_key] = _rnd.get_key(in_ctx)
    if op.wrap_train is not None and op.wrap_train not in attrs:
        attrs[op.wrap_train] = autograd.is_training()

    # telemetry gate: exactly one module-attribute check on the disabled path
    _t0 = _time.perf_counter_ns() if _ttrace._ENABLED else None

    recording = autograd.is_recording() and op.differentiable
    if recording:
        # capture residuals now; backward replays the stored closure only
        import jax
        f0 = _callable_for(op, attrs)

        # canonicalize list outputs to tuples so backward's tuple cotangents
        # match the vjp's output tree (multi-output ops may return lists)
        def f(*arrs, _f=f0):
            r = _f(*arrs)
            return tuple(r) if isinstance(r, list) else r
        if _cast_hook is not None:
            # amp casts must sit INSIDE the differentiated fn so vjp casts
            # the input gradients back to the params' dtypes (the reference
            # amp_cast op differentiates the same way)
            def f(*arrs, _f=f, _name=op.name):
                return _f(*_cast_hook(_name, list(arrs)))
        out_raw, vjp_fn = jax.vjp(f, *arrays)
    else:
        out_raw = invoke_arrays(op, arrays, attrs)
        vjp_fn = None

    out_arrays = _normalize_out(op, out_raw)
    engine.on_dispatch(out_arrays)
    _hook_ns = 0
    if _monitor_hooks:
        _h0 = _time.perf_counter_ns() if _t0 is not None else 0
        for _h in _monitor_hooks:
            _h(op.name, out_arrays)
        if _t0 is not None:
            _hook_ns = _time.perf_counter_ns() - _h0

    if _t0 is not None:
        # host dispatch time; device time lives in the XLA trace (N20 split)
        _telemetry.record_dispatch(op.name, _t0, _time.perf_counter_ns(),
                                   _hook_ns)

    # mutate_inputs ops (running stats etc.): write back into input slots
    for out_idx, in_idx in op.mutate_inputs:
        dst = inputs[in_idx]
        if isinstance(dst, nd.NDArray):
            dst._set_data(out_arrays[out_idx])

    # materialize outputs
    if out is None:
        results = [nd.NDArray._from_data(a, ctx=in_ctx) for a in out_arrays]
    else:
        outs = out if isinstance(out, (list, tuple)) else [out]
        if len(outs) != len(out_arrays):
            raise MXNetError(
                f"op {op.name}: {len(out_arrays)} outputs but {len(outs)} out= arrays")
        for dst, arr in zip(outs, out_arrays):
            dst._set_data(arr)
        results = list(outs)

    if recording:
        autograd._record(op, vjp_fn, inputs, results, attrs)

    if op.visible_outputs is not None and out is None:
        results = results[:op.visible_outputs]
    if len(results) == 1 and op.num_outputs in (1, -1):
        return results[0]
    if op.visible_outputs == 1:
        return results[0]
    return results
