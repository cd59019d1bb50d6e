"""Autograd: tape-based imperative differentiation.

Rebuild of the reference's N4 (src/imperative/imperative.cc ::
Imperative::RecordOp / Imperative::Backward) + python/mxnet/autograd.py.

Reference design: recording appends nnvm nodes to a tape; Backward builds a
graph, applies the nnvm ``Gradient`` pass (each op's FGradient), and interprets
it.  TPU-native design: recording captures a **concrete jax.vjp closure per
dispatched op** (residuals stored at forward time, so backward never re-runs
forward), and ``backward()`` walks the tape in reverse accumulating cotangents.
``create_graph=True`` (higher-order grad) re-enters the normal dispatch path
with each stored vjp closure treated as an op, so second-and-higher derivatives
are recorded tapes like any other compute.

Public API parity: ``record/pause/train_mode/predict_mode`` scopes,
``is_recording/is_training``, ``backward``, ``grad``, ``Function`` (custom py
autograd, reference c_api_function.cc / autograd.py :: Function),
``get_symbol`` is NOT provided (symbolic tape export is CachedOp's job here).
"""

from __future__ import annotations

import contextlib
import threading

import numpy as _np

from . import regions as _regions
from .base import MXNetError

__all__ = ["record", "pause", "train_mode", "predict_mode", "is_recording",
           "is_training", "set_recording", "set_training", "backward", "grad",
           "Function", "mark_variables"]

_tls = threading.local()


def _st():
    if not hasattr(_tls, "recording"):
        _tls.recording = False
        _tls.training = False
        _tls.tape = []
        _tls.session_depth = 0  # nesting depth of record() scopes
        _tls.create_graph_mode = False
    return _tls


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(flag):
    s = _st()
    prev, s.recording = s.recording, flag
    return prev


def set_training(flag):
    s = _st()
    prev, s.training = s.training, flag
    return prev


@contextlib.contextmanager
def _scope(recording=None, training=None):
    s = _st()
    prev_r, prev_t = s.recording, s.training
    entered_session = False
    if recording is not None:
        if recording:
            # only a truly outermost record session (not one nested under an
            # active-but-paused session) starts a fresh tape
            if s.session_depth == 0:
                s.tape = []
            s.session_depth += 1
            entered_session = True
        s.recording = recording
    if training is not None:
        s.training = training
    try:
        yield
    finally:
        s.recording, s.training = prev_r, prev_t
        if entered_session:
            s.session_depth -= 1


def record(train_mode=True):
    """``with autograd.record():`` — turn on recording (+train mode)."""
    return _scope(recording=True, training=train_mode)


def pause(train_mode=False):
    return _scope(recording=False, training=train_mode)


def train_mode():
    return _scope(training=True)


def predict_mode():
    return _scope(training=False)


# --------------------------------------------------------------------------
# tape
# --------------------------------------------------------------------------

_backward_epoch = 0


def _current_epoch():
    return _backward_epoch


class _Node:
    """One recorded op application."""
    __slots__ = ("op_name", "vjp_fn", "in_entries", "out_avals", "grads",
                 "op", "attrs", "inputs", "region")

    def __init__(self, op_name, vjp_fn, in_entries, out_avals,
                 op=None, attrs=None, inputs=None):
        self.op_name = op_name
        self.vjp_fn = vjp_fn
        self.in_entries = in_entries  # per input: ("node", node, idx) | ("leaf", nd) | None
        self.out_avals = out_avals    # [(shape, dtype)] per output
        self.grads = None             # cotangent accumulation during backward
        # retained for create_graph=True (higher-order): re-derive the vjp
        # from the op's fn at the recorded inputs so the backward ops land on
        # the tape *connected to the original inputs*
        self.op = op
        self.attrs = attrs
        self.inputs = inputs
        # the region path the op ran in (mxnet_tpu.regions): backward
        # re-enters it around vjp_fn under a trace, so the compiled
        # backward's instructions carry the forward's names
        self.region = _regions.current()


def _entries_for(inputs):
    from .ndarray import ndarray as _nd
    in_entries = []
    for a in inputs:
        if isinstance(a, _nd.NDArray):
            node = a._node
            if node is not None:
                in_entries.append(("node", node[0], node[1]))
            elif a._grad is not None:
                in_entries.append(("leaf", a))
            else:
                in_entries.append(None)
        else:
            in_entries.append(None)
    return in_entries


def _record(op, vjp_fn, inputs, outputs, attrs=None):
    """Called by ops.registry.invoke after a recorded dispatch."""
    s = _st()
    out_avals = [(o.shape, o.dtype) for o in outputs]
    node = _Node(op.name, vjp_fn, _entries_for(inputs), out_avals,
                 op=op, attrs=dict(attrs) if attrs else {}, inputs=list(inputs))
    s.tape.append(node)
    for i, o in enumerate(outputs):
        o._node = (node, i)
    return node


def _zeros_like_aval(aval):
    """The cotangent of an output nothing downstream differentiated: zeros
    of its type, or ``float0`` zeros for an integer-valued output (the
    expert ids of ``contrib.moe_router``), which is what ``jax.vjp`` takes
    there."""
    import jax
    import jax.numpy as jnp
    shape, dtype = aval
    if not jnp.issubdtype(dtype, jnp.inexact):
        return _np.zeros(shape, jax.dtypes.float0)
    return jnp.zeros(shape, dtype)


def backward(heads, head_grads=None, retain_graph=False, train_mode=True,
             create_graph=False):
    """Run backward from ``heads``; leaf ``.grad`` buffers are filled.

    Reference: MXAutogradBackwardEx → Imperative::Backward.
    """
    from .ndarray import ndarray as _nd
    if isinstance(heads, _nd.NDArray):
        heads = [heads]
        if head_grads is not None and isinstance(head_grads, _nd.NDArray):
            head_grads = [head_grads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    if len(head_grads) != len(heads):
        raise MXNetError("heads and head_grads length mismatch")

    s = _st()
    import jax.numpy as jnp
    from .ndarray import ndarray as _nd

    global _backward_epoch
    _backward_epoch += 1
    s.create_graph_mode = create_graph

    def _mk_seed(h, hg):
        if hg is not None:
            return hg if create_graph else hg._data
        ones = jnp.ones(h.shape, h.dtype)
        return _nd.NDArray._from_data(ones) if create_graph else ones

    # seed cotangents
    any_node = False
    tape = s.tape
    for h, hg in zip(heads, head_grads):
        node = h._node
        if node is None:
            if h._grad is not None:
                # backward directly on a leaf: d leaf/d leaf = head grad
                h._accumulate_grad(_mk_seed(h, hg))
            continue
        any_node = True
        n, idx = node
        if n.vjp_fn is None and n.inputs is None:
            raise MXNetError(
                "cannot run backward twice through the same graph: the tape "
                "was freed by the previous backward() (pass retain_graph=True "
                "to keep it, matching the reference contract)")
        if n.grads is None:
            n.grads = [None] * len(n.out_avals)
        seed = _mk_seed(h, hg)
        n.grads[idx] = seed if n.grads[idx] is None else n.grads[idx] + seed
    if not any_node:
        s.create_graph_mode = False
        return

    try:
        with _scope(training=train_mode):
            if create_graph:
                # record the backward ops onto the SAME tape (no reset) so
                # higher-order chains stay connected through original nodes
                with _keep_tape_recording():
                    visited = _run_tape_backward(tape, create_graph=True)
            else:
                visited = _run_tape_backward(tape, create_graph=False)
    finally:
        s.create_graph_mode = False

    if not retain_graph and not create_graph:
        # free only the subgraph this backward visited: per-device losses
        # recorded on the same tape (the reference's multi-ctx idiom
        # ``for l in losses: l.backward()``) keep their own nodes alive
        for n in visited:
            n.vjp_fn = None  # free residuals
            n.inputs = None
        if s.tape is tape:
            s.tape = [n for n in tape if n not in visited]
    else:
        for n in tape:
            n.grads = None


@contextlib.contextmanager
def _keep_tape_recording():
    """Recording on, but never resetting the tape (used by create_graph)."""
    s = _st()
    prev_r = s.recording
    s.recording = True
    s.session_depth += 1
    try:
        yield
    finally:
        s.recording = prev_r
        s.session_depth -= 1


def _freed(node):
    return node.vjp_fn is None and node.inputs is None


def _backprop_node(n, create_graph):
    """One node's step of backward: its vjp on the cotangents accumulated
    in ``grads``, each input's share added to its producer or leaf."""
    if create_graph:
        in_grads = _recorded_vjp_call(n)
    else:
        cts = tuple(_coerce_ct(g, av) if g is not None
                    else _zeros_like_aval(av)
                    for g, av in zip(n.grads, n.out_avals))
        in_grads = n.vjp_fn(cts[0] if len(cts) == 1 else cts)
    for entry, g in zip(n.in_entries, in_grads):
        if entry is None or g is None:
            continue
        gd = g._data if hasattr(g, "_data") else g
        if getattr(gd, "dtype", None) is not None:
            import jax
            if gd.dtype == jax.dtypes.float0:
                # gradient w.r.t. an integer-valued input (indices,
                # lengths): carries no information and float0 supports
                # no arithmetic — drop instead of accumulating
                continue
        kind = entry[0]
        if kind == "leaf":
            entry[1]._accumulate_grad(g)
        else:  # ("node", node, idx)
            _, pnode, pidx = entry
            if _freed(pnode):
                # the producer was freed by an earlier backward (it may
                # even be off the tape): silent gradient loss otherwise
                raise MXNetError(
                    "cannot run backward: a shared subgraph was freed by "
                    "a previous backward() (pass retain_graph=True, or "
                    "call backward once on the combined heads)")
            if pnode.grads is None:
                pnode.grads = [None] * len(pnode.out_avals)
            pnode.grads[pidx] = (g if pnode.grads[pidx] is None
                                 else pnode.grads[pidx] + g)


def _run_tape_backward(tape, create_graph=False):
    visited = set()
    # one check per backward: an imperative one enters no named_scope
    named = None
    if _regions.tracing():
        import jax
        named = jax.named_scope
    for n in reversed(tape):
        if n.grads is None or all(g is None for g in n.grads):
            continue
        if _freed(n):
            raise MXNetError(
                "cannot run backward through a subgraph already freed by a "
                "previous backward() (pass retain_graph=True to keep it)")
        visited.add(n)
        if named is not None and n.region:
            # under a trace the vjp's ops, and the sums of cotangents it
            # feeds, take the names of the region the op ran in
            with named(n.region):
                _backprop_node(n, create_graph)
        else:
            _backprop_node(n, create_graph)
        n.grads = None
    return visited


def _coerce_ct(g, aval):
    """Cast a cotangent to its primal output's dtype.

    Mixed-precision tapes (mx.amp) legitimately produce f32 cotangents for
    bf16 primal outputs (downstream ops upcast); jax.vjp requires exact
    dtype match, so coerce here — the reference's backward does the same
    implicitly through amp_cast nodes in the grad graph."""
    _, want_dtype = aval
    data = g._data if hasattr(g, "_data") else g
    import jax
    import numpy as _onp
    want = _onp.dtype(want_dtype)
    want_float = want.kind == "f" or want.name == "bfloat16"
    if data.dtype == jax.dtypes.float0 or not want_float:
        # integer-valued primal outputs take float0 cotangents — never cast
        return g
    if data.dtype != want_dtype:
        cast = data.astype(want_dtype)
        if hasattr(g, "_data"):
            from .ndarray import ndarray as _nd
            return _nd.NDArray._from_data(cast)
        return cast
    return g


def _recorded_vjp_call(node):
    """create_graph=True: replay the op's vjp as a *recorded* op whose inputs
    are the original forward inputs plus the cotangents, so the backward ops
    land on the tape connected to the original leaves (higher-order grads).

    Falls back to the stored closure (disconnected, first-order only) for
    nodes without a replayable op (custom autograd.Function)."""
    from .ops import registry as _reg
    from .ndarray import ndarray as _nd
    import jax

    cts = [_coerce_ct(g, av) if g is not None else
           _nd.NDArray._from_data(_zeros_like_aval(av))
           for g, av in zip(node.grads, node.out_avals)]

    if node.op is None or node.inputs is None:
        ct_raw = tuple(c._data for c in cts)
        return node.vjp_fn(ct_raw[0] if len(ct_raw) == 1 else ct_raw)

    fwd_inputs = [a for a in node.inputs]
    n_in = len(fwd_inputs)
    op, attrs = node.op, node.attrs

    def replay(*args, **kw):
        ins, ct = args[:n_in], args[n_in:]
        f = _reg._callable_for(op, kw)
        _, vjp = jax.vjp(f, *ins)
        res = vjp(ct[0] if len(ct) == 1 else tuple(ct))
        return res if len(res) > 1 else res[0]

    g_op = _reg.Op(f"_backward_{node.op_name}", replay,
                   num_outputs=n_in if n_in > 1 else 1, jit=False)
    res = _reg.invoke(g_op, fwd_inputs + cts, attrs)
    if not isinstance(res, list):
        res = [res]
    return res


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """autograd.grad — return grads of heads wrt variables (not into .grad).

    Reference: python/mxnet/autograd.py :: grad (MXAutogradBackwardEx with
    variable handles).
    """
    from .ndarray import ndarray as _nd
    single_var = isinstance(variables, _nd.NDArray)
    if single_var:
        variables = [variables]
    if retain_graph is None:
        retain_graph = create_graph

    # temporarily give each variable a fresh grad buffer marked 'add'
    saved = [(v._grad, v.grad_req) for v in variables]
    for v in variables:
        v._grad = _nd.zeros(v.shape, dtype=v.dtype, ctx=v.ctx)
        v.grad_req = "add"
    try:
        backward(heads, head_grads, retain_graph=retain_graph,
                 train_mode=train_mode, create_graph=create_graph)
        out = []
        for v in variables:
            if v._grad_epoch != _backward_epoch:
                raise MXNetError(
                    "cannot differentiate with respect to a variable that "
                    "the recorded graph does not reach (reference contract: "
                    "MXAutogradBackwardEx errors on unreachable variables)")
            out.append(v._grad)
    finally:
        for v, (og, oreq) in zip(variables, saved):
            v._grad, v.grad_req = og, oreq
    return out[0] if single_var else out


def mark_variables(variables, gradients, grad_reqs="write"):
    """Reference API: attach grad buffers to arrays (used by Module path)."""
    from .ndarray import ndarray as _nd
    if isinstance(variables, _nd.NDArray):
        variables, gradients = [variables], [gradients]
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, r in zip(variables, gradients, grad_reqs):
        v._grad = g
        v.grad_req = r


# --------------------------------------------------------------------------
# custom Function (reference: autograd.py :: Function + c_api_function.cc)
# --------------------------------------------------------------------------

class Function:
    """User-defined differentiable function.

    Subclass and implement ``forward(self, *inputs)`` and
    ``backward(self, *output_grads)`` operating on NDArrays.  Parity with the
    reference's ``mx.autograd.Function`` (which trampolines through the C API);
    here it is a tape node whose vjp calls the user's ``backward`` in pause().
    """

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray import ndarray as _nd
        s = _st()
        rec = s.recording
        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single else list(outputs)
        if rec:
            func = self

            def vjp_fn(cts):
                if not isinstance(cts, tuple):
                    cts = (cts,)
                with pause():
                    ct_nds = [_nd.NDArray._from_data(c) for c in cts]
                    igs = func.backward(*ct_nds)
                if isinstance(igs, _nd.NDArray):
                    igs = [igs]
                return [g._data if isinstance(g, _nd.NDArray) else g for g in igs]

            node = _Node(type(self).__name__, vjp_fn,
                         in_entries=[None] * len(inputs),
                         out_avals=[(o.shape, o.dtype) for o in outs])
            # fill input entries like _record does
            entries = []
            for a in inputs:
                if isinstance(a, _nd.NDArray):
                    if a._node is not None:
                        entries.append(("node", a._node[0], a._node[1]))
                    elif a._grad is not None:
                        entries.append(("leaf", a))
                    else:
                        entries.append(None)
                else:
                    entries.append(None)
            node.in_entries = entries
            s.tape.append(node)
            for i, o in enumerate(outs):
                o._node = (node, i)
        return outs[0] if single else tuple(outs)
