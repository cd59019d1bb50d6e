"""gluon.Block / HybridBlock — the user-facing model API.

Rebuild of python/mxnet/gluon/block.py (P6) + src/imperative/cached_op.cc
(N5).  API parity: ``Block`` (child auto-registration, ``collect_params``,
name scopes), ``HybridBlock`` (``hybrid_forward(F, x, **params)``,
``hybridize()``, ``export()``, ``infer_shape`` via deferred param init),
``SymbolBlock``-style import is handled by ``model.load_checkpoint``.

TPU-native CachedOp: instead of capturing an nnvm subgraph and re-executing it
through the C++ engine with a static memory plan, ``hybridize()`` traces the
block's Python forward into a ``jax.jit``-compiled function of
``(params..., inputs..., rng_key)``, cached per (input shapes/dtypes,
train-flag).  The whole block then dispatches as ONE registry op — a single
fused XLA computation (the reference's static_alloc/static_shape/bulking all
collapse into what XLA does natively), and autograd records one vjp for the
whole block.  Mutated auxiliary states (BatchNorm running stats) are detected
at trace time and threaded out as extra outputs, then written back to their
slots after each call — preserving FMutateInputs semantics functionally.
"""

from __future__ import annotations

import re
import threading

import numpy as _np

from ..base import MXNetError
from .. import ndarray as nd
from .. import regions as _regions
from ..ndarray.ndarray import NDArray
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp"]

_naming = threading.local()


def _prefix_counter(hint):
    if not hasattr(_naming, "counts"):
        _naming.counts = {}
    n = _naming.counts.get(hint, 0)
    _naming.counts[hint] = n + 1
    return f"{hint}{n}_"


class _BlockScope:
    """Name scope machinery (reference block.py :: _BlockScope)."""
    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _prefix_counter(hint)
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            hint_count = current._counter.get(hint, 0)
            current._counter[hint] = hint_count + 1
            prefix = f"{hint}{hint_count}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *exc):
        if self._block._empty_prefix:
            return False
        _BlockScope._current.value = self._old
        return False


class Block:
    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        # region scope of this block's forward (mxnet_tpu.regions): a child
        # takes the name it is registered under in its parent — the
        # attribute or ``register_child`` name, which the user's code fixes
        # (a prefix the user left out comes from a process-wide counter) —
        # and a block with no parent keeps its own ``name``, so the zoo
        # BERT's ops read ``bert/encoder/layer3/attn_qkv/…``
        self._region = self._name
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pat = re.compile(select)
            ret.update({k: v for k, v in self.params.items() if pat.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
                value._region = name
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block
        block._region = name

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init=init, ctx=ctx, verbose=verbose,
                                         force_reinit=force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self.params.values():
            p.cast(dtype)

    def save_parameters(self, filename, deduplicate=False):  # noqa: ARG002
        params = self._collect_params_with_prefix()
        nd.save(filename, {k: v.data() for k, v in params.items()})

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):  # noqa: ARG002
        loaded = nd.load(filename, ctx=ctx)
        params = self._collect_params_with_prefix()
        full_dict = self.collect_params()
        for name, p in params.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif p.name in loaded:
                p.set_data(loaded[p.name])
            elif not allow_missing:
                raise MXNetError(f"Parameter {name} missing in {filename}")
        if not ignore_extra:
            known = set(params) | {p.name for p in params.values()} \
                | set(full_dict.keys())
            extra = [k for k in loaded if k not in known]
            if extra:
                raise MXNetError(f"{filename} has extra parameters {extra}")

    # alias pair used across reference versions
    save_params = save_parameters
    load_params = load_parameters

    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        with _regions.scope(self._region):
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-layer summary (reference HybridBlock.summary)."""
        rows = []

        def hook_factory(blk, bname):
            def hook(b, inp, out):
                shape = out.shape if isinstance(out, NDArray) else \
                    [o.shape for o in out if isinstance(o, NDArray)]
                n_params = sum(int(_np.prod(p.shape))
                               for p in b._reg_params.values()
                               if p.shape is not None)
                rows.append((bname, type(b).__name__, shape, n_params))
            return hook

        handles = []
        def attach(b, bname):
            h = hook_factory(b, bname)
            b._forward_hooks.append(h)
            handles.append((b, h))
            for n, c in b._children.items():
                attach(c, f"{bname}.{n}" if bname else n)
        attach(self, "")
        try:
            self(*inputs)
        finally:
            for b, h in handles:
                b._forward_hooks.remove(h)
        print(f"{'Layer':<40}{'Output Shape':<24}{'Params':<12}")
        print("-" * 76)
        total = 0
        for bname, cls, shape, n in rows:
            print(f"{bname + ' (' + cls + ')':<40}{str(shape):<24}{n:<12}")
            total += n
        print("-" * 76)
        print(f"Total params (incl. shared): {total}")

    def __repr__(self):
        lines = []
        for name, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        body = "\n".join(lines)
        return f"{type(self).__name__}(\n{body}\n)" if body \
            else f"{type(self).__name__}()"


class CachedOp:
    """The hybridize() execution object (reference src/imperative/cached_op.cc).

    Holds per-(shape,dtype,train) jitted callables of
    ``f(rng_key, *param_arrays, *input_arrays) -> (outputs..., mutated_aux...)``.
    """

    def __init__(self, block, static_alloc=False, static_shape=False,
                 inline_limit=2, flags=None):  # noqa: ARG002 - XLA handles both
        self.block = block
        self._cache = {}
        self._donate = bool(static_alloc)  # donation ≈ static_alloc reuse

    def _trace(self, params, inputs, train_mode, kwargs):
        import jax
        from .. import autograd, random as _rnd

        param_list = list(params)
        n_p = len(param_list)
        mutated_idx = []  # filled during trace
        key_uses = [0]    # whether the block consumes RNG (dropout etc.)

        from ..ndarray.ndarray import swap_slot_values

        def raw(key, *arrays):
            p_arr = arrays[:n_p]
            i_arr = arrays[n_p:]
            with swap_slot_values(zip((p._data for p in param_list),
                                      p_arr)) as saved:
                in_nds = [NDArray._from_data(a) for a in i_arr]
                scope = _rnd.trace_key_scope(key)
                with autograd._scope(recording=False, training=train_mode), \
                        scope:
                    out = self.block.hybrid_forward_dispatch(*in_nds, **kwargs)
                key_uses[0] = max(key_uses[0], scope.uses)
                outs = [out] if isinstance(out, NDArray) else list(out)
                out_arrays = [o._data for o in outs]
                mutated_idx.clear()
                mut_arrays = []
                for i, (p, (slot, old)) in enumerate(zip(param_list, saved)):
                    if slot.value is not old and slot.value is not p_arr[i]:
                        mutated_idx.append(i)
                        mut_arrays.append(slot.value)
                all_out = tuple(out_arrays) + tuple(mut_arrays)
                # single output must be a leaf, not a 1-tuple, so the captured
                # vjp accepts a bare cotangent
                return all_out if len(all_out) > 1 else all_out[0]

        # graftcheck: ignore[GC02] — deliberate CachedOp protocol: raw
        # reads self.block/params at trace time, and the per-shape cache is
        # keyed on (shapes, dtypes, train_mode) + cleared on dispatch-epoch
        # bumps (amp toggles), so no stale capture survives; mutated_idx /
        # key_uses are trace-time out-params, not runtime state
        jitted = jax.jit(raw)
        # abstract trace now so mutated_idx and the output count are known
        key0 = jax.random.PRNGKey(0)
        out_shapes = jax.eval_shape(raw, key0,
                                    *[p.data()._data for p in param_list],
                                    *inputs)
        n_total = len(out_shapes) if isinstance(out_shapes, (tuple, list)) \
            else 1
        return jitted, list(mutated_idx), n_total, bool(key_uses[0])

    def __call__(self, param_list, input_nds, train_mode, kwargs):
        from ..ops import registry as _reg
        from .. import random as _rnd

        # select the param replica co-located with the inputs (multi-ctx DP);
        # the trace itself is ctx-agnostic (same shapes) and shared
        ctx = next((a.ctx for a in input_nds), None)
        in_arrays = [a._data for a in input_nds]
        # amp on/off bumps the dispatch epoch ⇒ drop stale traces (their
        # cast decisions are baked in; keeping them would leak executables)
        if getattr(self, "_cache_epoch", None) != _reg.dispatch_epoch():
            self._cache.clear()
            self._cache_epoch = _reg.dispatch_epoch()
        key = tuple((tuple(a.shape), str(a.dtype)) for a in in_arrays) \
            + (train_mode, tuple(sorted(kwargs.items())),
               _reg.step_layout_key())
        entry = self._cache.get(key)
        if entry is None:
            entry = self._trace(param_list, in_arrays, train_mode, kwargs)
            self._cache[key] = entry
        jitted, mutated_idx, n_total, uses_rng = entry
        n_p = len(param_list)
        n_mut = len(mutated_idx)
        n_out = n_total - n_mut

        if uses_rng:
            def fn(*arrays, _key=None):
                return jitted(_key, *arrays)
        else:
            import jax
            _key0 = jax.random.PRNGKey(0)

            def fn(*arrays):
                return jitted(_key0, *arrays)

        op = _reg.Op(f"CachedOp_{self.block.name}", fn,
                     num_outputs=n_total,
                     visible_outputs=n_out,
                     mutate_inputs=tuple(
                         (n_out + j, mutated_idx[j]) for j in range(n_mut)),
                     wrap_key="_key" if uses_rng else None, jit=False)
        p_nds = [p.data(ctx) for p in param_list]
        res = _reg.invoke(op, p_nds + input_nds, {})
        return res


class HybridBlock(Block):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_op = None
        self._cached_op_args = {}
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        self._active = active
        self._cached_op = None
        self._cached_op_args = dict(static_alloc=static_alloc,
                                    static_shape=static_shape, **kwargs)
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def cast(self, dtype):
        self._cached_op = None
        super().cast(dtype)

    def _clear_cached_op(self):
        self._cached_op = None

    def infer_shape(self, *args):
        """Resolve deferred-init params from concrete input shapes (the nnvm
        InferShape role; here each layer's infer_param_shapes rule)."""
        self.hybrid_forward_dispatch(*args)

    def infer_param_shapes(self, args):
        """Layer-specific deferred-shape rule; layers with deferred params
        override (Dense/Conv/BatchNorm...)."""
        pending = [p.name for p in self._reg_params.values()
                   if p._data is None and p._deferred_init is not None]
        if pending:
            raise DeferredInitializationError(
                f"{type(self).__name__} cannot infer shapes for deferred "
                f"parameters {pending}; initialize them explicitly")

    def hybrid_forward_dispatch(self, *args, **kwargs):
        """Call user hybrid_forward with F + param kwargs (imperative F).
        Params are selected by the input's context so multi-ctx data
        parallelism uses the replica living with the data (reference
        passes ctx through DataParallel executor groups)."""
        pending = [p for p in self._reg_params.values()
                   if p._data is None and p._deferred_init is not None]
        if pending:
            self.infer_param_shapes(args)
            for p in pending:
                p._finish_deferred_init()
        ctx = next((a.ctx for a in args if isinstance(a, NDArray)), None)
        params = {name: p.data(ctx) for name, p in self._reg_params.items()}
        return self.hybrid_forward(nd, *args, **params, **kwargs)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def forward(self, *args, **kwargs):
        if self._active:
            try:
                return self._call_cached_op(*args, **kwargs)
            except DeferredInitializationError:
                # first call with deferred params: one imperative pass
                # resolves them layer-by-layer, then the cached op compiles
                self.hybrid_forward_dispatch(*args, **kwargs)
                return self._call_cached_op(*args, **kwargs)
        return self.hybrid_forward_dispatch(*args, **kwargs)

    def _call_cached_op(self, *args, **kwargs):
        from .. import autograd
        if self._cached_op is None:
            self._cached_op = CachedOp(self, **{
                k: v for k, v in self._cached_op_args.items()
                if k in ("static_alloc", "static_shape", "inline_limit")})
        params = list(self.collect_params().values())
        # every param must be concrete before tracing
        for p in params:
            if p._data is None:
                raise DeferredInitializationError(
                    f"Parameter {p.name} not yet initialized for CachedOp")
        input_nds = [a for a in args if isinstance(a, NDArray)]
        return self._cached_op(params, input_nds, autograd.is_training(),
                               kwargs)

    def export(self, path, epoch=0):
        """Serialize compiled graph + params (reference HybridBlock.export →
        symbol json + .params pair; here real StableHLO text + .params).

        The block must have been hybridized and called at least once so a
        compiled cache entry exists (same precondition as the reference)."""
        import jax
        params = list(self.collect_params().values())
        fname_params = f"{path}-{epoch:04d}.params"
        nd.save(fname_params, {p.name: p.data() for p in params})
        if not (self._cached_op and self._cached_op._cache):
            raise MXNetError(
                "export() requires hybridize() and at least one forward call "
                "(reference raises on un-hybridized export)")
        cache_key, entry = next(iter(self._cached_op._cache.items()))
        jitted = entry[0]
        # cache key = ((shape, dtype_str) per input..., train_mode, kwargs)
        in_specs = [jax.ShapeDtypeStruct(s, _np.dtype(d))
                    for s, d in cache_key[:-2]]
        lowered = jitted.lower(jax.random.PRNGKey(0),
                               *[p.data()._data for p in params], *in_specs)
        hlo = lowered.as_text()
        with open(f"{path}-symbol.txt", "w") as f:
            f.write(hlo)
        return fname_params


class SymbolBlock(HybridBlock):
    """Run a symbolic graph (or traced callable) as a Gluon block.

    Two construction paths, mirroring the reference:
     - ``SymbolBlock(callable)`` wraps a live traced function;
     - ``SymbolBlock.imports(symbol_file, input_names, param_file)`` loads
       the json+params interchange pair written by ``Symbol.save`` /
       ``model.save_checkpoint`` (reference
       gluon/block.py :: SymbolBlock.imports) and executes it through the
       graph executor — the "train anywhere, serve elsewhere" round trip.
    """

    def __init__(self, outputs_fn=None, params=None, prefix=None):
        super().__init__(prefix=prefix, params=params)
        self._fn = outputs_fn
        self._symbol = None
        self._input_names = None
        self._imported_params = {}
        self._sb_executor = None
        self._sb_shapes = None

    @classmethod
    def imports(cls, symbol_file, input_names, param_file=None, ctx=None):
        """Load symbol json (+ optional .params) for inference.

        ``param_file`` entries may be 'arg:NAME'/'aux:NAME'-prefixed
        (Module/save_checkpoint convention) or flat names (Gluon
        save_parameters convention)."""
        from .. import symbol as _sym
        from .. import ndarray as _ndm
        sym = _sym.load(symbol_file) if isinstance(symbol_file, str) \
            else symbol_file
        if isinstance(input_names, str):
            input_names = [input_names]
        blk = cls()
        blk._symbol = sym
        blk._input_names = list(input_names)
        blk._sb_ctx = ctx
        if param_file:
            loaded = _ndm.load(param_file)
            blk._imported_params = {
                (k.split(":", 1)[1] if k.startswith(("arg:", "aux:"))
                 else k): v
                for k, v in loaded.items()}
        return blk

    def forward(self, *args, **kwargs):
        if self._symbol is None:
            return super().forward(*args, **kwargs)
        from ..context import current_context
        if kwargs:
            raise MXNetError(
                "SymbolBlock takes inputs positionally in input_names "
                f"order {self._input_names} (got kwargs {list(kwargs)})")
        if len(args) != len(self._input_names):
            raise MXNetError(
                f"SymbolBlock expects {len(self._input_names)} inputs "
                f"{self._input_names}, got {len(args)}")
        ctx = self._sb_ctx or current_context()
        # inputs land on the bind ctx like the imported params do — feeding
        # a cpu buffer into a tpu-bound executor is the classic device bug
        ins = [(a if isinstance(a, NDArray) else nd.array(a))
               .as_in_context(ctx) for a in args]
        shapes = tuple(tuple(a.shape) for a in ins)
        if self._sb_executor is None or self._sb_shapes != shapes:
            shape_kw = dict(zip(self._input_names, shapes))
            try:
                ex = self._symbol.simple_bind(ctx, grad_req="null",
                                              **shape_kw)
            except MXNetError as e:
                params = set(self._imported_params)
                unbound = [a for a in self._symbol.list_arguments()
                           if a not in params
                           and a not in self._input_names]
                raise MXNetError(
                    f"SymbolBlock: could not bind — unbound inputs "
                    f"{unbound} are neither in input_names nor in the "
                    "param file. For a training checkpoint with a loss "
                    "head (e.g. SoftmaxOutput's *_label), either list the "
                    "label in input_names or strip the head first: "
                    "sym.get_internals()['<name>_output'] "
                    "(reference SymbolBlock.imports contract)") from e
            for name in list(ex.arg_dict):
                if name in self._imported_params:
                    # .params files load on cpu; land them on the bind ctx
                    ex.arg_dict[name] = \
                        self._imported_params[name].as_in_context(ctx)
            for name in list(ex.aux_dict):
                if name in self._imported_params:
                    ex.aux_dict[name] = \
                        self._imported_params[name].as_in_context(ctx)
            self._sb_executor, self._sb_shapes = ex, shapes
        self._sb_executor.forward(
            is_train=False, **dict(zip(self._input_names, ins)))
        outs = self._sb_executor.outputs
        return outs[0] if len(outs) == 1 else outs

    def hybrid_forward(self, F, *args, **params):  # noqa: ARG002
        return self._fn(*args, **params)
