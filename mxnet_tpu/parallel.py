"""TPU-native parallelism: device meshes, sharded training, collectives.

This module is NEW capability relative to the reference (SURVEY §2.4 flags
pipeline/tensor/sequence parallelism ABSENT upstream): the reference scales by
parameter servers + NCCL allreduce (src/kvstore/comm.h :: CommDevice,
kvstore_dist.h, kvstore_nccl.h); the TPU-native equivalent is ONE mesh
abstraction over ICI/DCN with XLA collectives:

 - ``DeviceMesh`` — named-axis mesh over local (or pod-global) devices;
   thin, typed wrapper around ``jax.sharding.Mesh``.
 - ``TrainStep`` — the fused SPMD training step: traces the *imperative*
   Gluon forward + autograd backward + optimizer update into ONE jitted XLA
   computation over the mesh.  Parameters are replicated (or sharded per
   ``Parameter.sharding`` hints — tensor parallelism), the batch is sharded
   on the data axis, and GSPMD inserts the gradient all-reduces that ride
   ICI.  This is the TPU answer to the reference's
   `update_on_kvstore` fused path + CommDevice reduction, and the engine of
   BASELINE's throughput targets.
 - eager collectives (``allreduce``, ``allgather``) — host-callable psum
   over a mesh via ``shard_map`` for kvstore-style imperative use.

The multi-ctx *replica* path (split_and_load + per-ctx grads + kvstore
'device') lives in gluon.{utils,trainer} for API parity; this module is the
performance path.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import threading
import time

import numpy as _np
from jax.profiler import TraceAnnotation

from .base import MXNetError
from .context import Context
from . import ndarray as nd
from . import regions as _regions
from . import telemetry as _tel
from .telemetry import costmodel as _costmodel
from .telemetry import stepclock as _sclock
from .telemetry import tracer as _ttrace
from .ndarray.ndarray import NDArray

# sharded-step observability (ISSUE 8 satellite): dispatches vs retraces —
# a steady-state sharded loop must show dispatches growing while retraces
# stay flat (the runtime twin of graftcheck GC02 for the mesh path).  Always
# on, like the dispatch record (telemetry.stepclock): one inc a dispatch.
_M_STEP_DISPATCHES = _tel.counter(
    "mxnet_sharding_step_dispatches_total",
    "Sharded TrainStep dispatches (one per __call__/run invocation).")
_M_RETRACES = _tel.counter(
    "mxnet_sharding_retraces_total",
    "TrainStep executable builds (trace+compile); growth at steady state "
    "is a retrace bug — see graftcheck GC02.")
_M_MICROBATCHES = _tel.counter(
    "mxnet_trainstep_microbatches_total",
    "Microbatches executed by gradient-accumulation TrainSteps "
    "(n_micro per dispatch; n_micro=1 steps do not count).")
_M_RESOLVE_SECONDS = _tel.gauge(
    "mxnet_trainstep_resolve_seconds",
    "Seconds inside TrainStep._resolve (fixing the parameter and state "
    "order; the imperative forward that finishes deferred init included); "
    "only grows.")
_DEVICE_BYTES_HELP = (
    "On the fullest device of a TrainStep's mesh, at its last dispatch that "
    "built a program: memory_stats' bytes_in_use just before the call "
    "(kind=in_use) and the bytes of the program's arguments resident there "
    "(kind=arguments).  Absent where the runtime gives no memory_stats.")
_log = logging.getLogger("mxnet_tpu.trainstep")

__all__ = ["DeviceMesh", "make_mesh", "data_parallel_ctxs", "TrainStep",
           "allreduce", "allgather", "current_mesh", "set_mesh",
           "attention", "ring_attention", "ulysses_attention",
           "report_counter"]


def __getattr__(name):
    # sequence-parallel attention (SURVEY §5.7): lazily re-exported so
    # importing parallel doesn't pull the kernels package.  Two SP
    # strategies: ring (K/V rotation, long-sequence memory win) and
    # Ulysses (all-to-all head re-sharding, local attention).
    if name in ("attention", "ring_attention"):
        from .kernels.ring_attention import (ring_attention,
                                             sequence_parallel_attention)
        val = sequence_parallel_attention if name == "attention" \
            else ring_attention
        globals()[name] = val
        return val
    if name == "ulysses_attention":
        # the INSIDE-shard_map kernel, mirroring ring_attention's export;
        # the global entry is kernels.ulysses.ulysses_sequence_parallel_attention
        from .kernels.ulysses import ulysses_attention
        globals()[name] = ulysses_attention
        return ulysses_attention
    raise AttributeError(f"module 'mxnet_tpu.parallel' has no attribute {name!r}")


def _jax():
    import jax
    return jax


_current_mesh = None


def current_mesh():
    return _current_mesh


def set_mesh(mesh):
    global _current_mesh
    _current_mesh = mesh
    return mesh


class DeviceMesh:
    """A named-axis device mesh (axes e.g. ('dp',), ('dp','tp'), ('dp','tp','sp')).

    Wraps jax.sharding.Mesh; the axis order convention follows the scaling
    playbook: outermost axis = data parallel (DCN-friendly), inner axes =
    tensor/sequence parallel (ICI-local).
    """

    def __init__(self, shape=None, axis_names=("dp",), devices=None):
        jax = _jax()
        if devices is None:
            devices = jax.devices()
        if shape is None:
            shape = (len(devices),)
        total = 1
        for s in shape:
            total *= s
        if total != len(devices):
            raise MXNetError(
                f"mesh shape {shape} needs {total} devices, got {len(devices)}")
        if len(shape) != len(axis_names):
            raise MXNetError("mesh shape and axis_names rank mismatch")
        arr = _np.array(devices, dtype=object).reshape(shape)
        self.mesh = jax.sharding.Mesh(arr, axis_names)
        self.axis_names = tuple(axis_names)
        self.shape = tuple(shape)
        self.devices = list(devices)

    # -- sharding constructors ------------------------------------------------
    def replicated(self):
        jax = _jax()
        return jax.sharding.NamedSharding(self.mesh,
                                          jax.sharding.PartitionSpec())

    def sharded(self, *spec):
        """NamedSharding with the given per-dim axis assignment, e.g.
        mesh.sharded('dp') shards dim0 over the data axis; an entry may
        also be a tuple of axes ('dp', 'fsdp') sharding one dim over
        several mesh axes (true N-axis layouts)."""
        jax = _jax()
        return jax.sharding.NamedSharding(self.mesh, self.spec(*spec))

    def spec(self, *spec):
        """PartitionSpec over THIS mesh's axes — an entry naming an axis
        the mesh doesn't carry is a layout typo and raises (use
        sharding.resolve_spec for the degrade-to-replicated behavior)."""
        for entry in spec:
            entry = entry if isinstance(entry, (tuple, list)) else (entry,)
            for a in entry:
                if a is not None and a not in self.axis_names:
                    raise MXNetError(
                        f"mesh {self!r} has no axis {a!r}; axes are "
                        f"{self.axis_names}")
        return _jax().sharding.PartitionSpec(*spec)

    @property
    def size(self):
        return len(self.devices)

    def axis_size(self, name):
        return self.shape[self.axis_names.index(name)]

    def ctxs(self):
        """One mx Context per mesh device (for split_and_load-style loops)."""
        out = []
        for d in self.devices:
            kind = "cpu" if d.platform == "cpu" else "tpu"
            out.append(Context(kind, d.id))
        return out

    def __repr__(self):
        dims = ", ".join(f"{n}={s}" for n, s in zip(self.axis_names, self.shape))
        return f"DeviceMesh({dims})"


def make_mesh(shape=None, axis_names=("dp",), devices=None):
    return set_mesh(DeviceMesh(shape=shape, axis_names=axis_names,
                               devices=devices))


def data_parallel_ctxs(n=None):
    """The ctx list for the reference-style multi-device loop
    (reference: ``[mx.gpu(i) for i in range(n)]``)."""
    jax = _jax()
    devs = jax.devices()
    if n is not None:
        devs = devs[:n]
    return [Context("cpu" if d.platform == "cpu" else "tpu", d.id)
            for d in devs]


# --------------------------------------------------------------------------
# eager collectives (imperative kvstore building blocks)
# --------------------------------------------------------------------------

# jitted collective cache: a fresh closure per call would pay full
# retrace+compile every time (round-2 advisor finding) — key on the mesh
# identity (device ids + axis names), shape, dtype, and the op variant.
_collective_cache: dict = {}


def _collective_fn(kind, mesh, shape, dtype, variant):
    key = (kind, tuple(d.id for d in mesh.devices), mesh.axis_names,
           tuple(shape), str(dtype), variant)
    fn = _collective_cache.get(key)
    if fn is not None:
        return fn
    jax = _jax()
    axis = mesh.axis_names[0]
    n = mesh.size
    if kind == "allreduce":
        mean = variant

        def f(xs):
            s = jax.lax.psum(xs.sum(axis=0), axis)
            if mean:
                s = s / n
            return s[None]
    else:  # allgather
        def f(xs):
            return jax.lax.all_gather(xs[0], axis)[None]

    fn = jax.jit(jax.shard_map(f, mesh=mesh.mesh, in_specs=mesh.spec(axis),
                               out_specs=mesh.spec(axis), check_vma=False))
    _collective_cache[key] = fn
    return fn


def allreduce(values, mesh=None, op="sum"):
    """Reduce a per-device list of NDArrays into identical copies on every
    input device.  ``op`` is 'sum' or 'mean'.

    The eager analog of CommDevice::ReduceSum: values[i] lives on device i of
    the mesh.  When the inputs already sit on the mesh devices in order, the
    stacked global array is assembled zero-copy from the committed shards
    (make_array_from_single_device_arrays) and the reduction is a single
    jitted psum over the mesh — on real TPU hardware it rides ICI with no
    host staging.
    """
    jax = _jax()
    if op not in ("sum", "mean"):
        raise MXNetError(f"allreduce op must be 'sum' or 'mean', got {op!r}")
    arrays = [v._data if isinstance(v, NDArray) else v for v in values]
    n = len(arrays)
    if n == 1:
        return list(values)
    if mesh is None or mesh.size != n:
        # reduce over exactly the values' devices: build a local sub-mesh
        # (no global-mesh mutation — a partial reduction must not re-point
        # current_mesh(), and the psum axis must span exactly n shards)
        devs = [getattr(a, "device", None) for a in arrays]
        if any(d is None for d in devs) or len(set(devs)) != n:
            devs = jax.devices()[:n]
        mesh = DeviceMesh(devices=devs, axis_names=("dp",))
    axis = mesh.axis_names[0]
    sharding = mesh.sharded(axis)
    shape = tuple(arrays[0].shape)

    in_devices = [getattr(a, "device", None) for a in arrays]
    if n == mesh.size and in_devices == mesh.devices:
        # zero-copy: each committed shard becomes one row of the global array
        shards = [a[None] for a in arrays]  # expand on-device
        stacked = jax.make_array_from_single_device_arrays(
            (n,) + shape, sharding, shards)
    else:
        stacked = jax.device_put(
            jax.numpy.stack([_np.asarray(a) for a in arrays]), sharding)

    summed = _collective_fn("allreduce", mesh, stacked.shape, stacked.dtype,
                            op == "mean")(stacked)
    per_shard = {s.device: s.data for s in summed.addressable_shards}
    out = []
    for a in arrays:
        dev = getattr(a, "device", None)
        local = per_shard.get(dev)
        if local is None:
            local = jax.device_put(_np.asarray(summed.addressable_shards[0].data),
                                   dev)
        out.append(NDArray._from_data(local.reshape(shape)))
    return out


def allgather(values, mesh=None):
    """Concatenate per-device shards along axis 0 on every device
    (all_gather over the mesh axis — same zero-copy assembly as allreduce)."""
    jax = _jax()
    arrays = [v._data if isinstance(v, NDArray) else v for v in values]
    n = len(arrays)
    if n == 1:
        return list(values)
    if mesh is None or mesh.size != n:
        devs = [getattr(a, "device", None) for a in arrays]
        if any(d is None for d in devs) or len(set(devs)) != n:
            devs = jax.devices()[:n]
        mesh = DeviceMesh(devices=devs, axis_names=("dp",))
    axis = mesh.axis_names[0]
    shard_shape = tuple(arrays[0].shape)
    sharding = mesh.sharded(axis)

    in_devices = [getattr(a, "device", None) for a in arrays]
    if in_devices == mesh.devices:
        stacked = jax.make_array_from_single_device_arrays(
            (n,) + shard_shape, sharding, [a[None] for a in arrays])
    else:
        stacked = jax.device_put(
            jax.numpy.stack([_np.asarray(a) for a in arrays]), sharding)

    gathered = _collective_fn("allgather", mesh, stacked.shape,
                              stacked.dtype, None)(stacked)
    out_shape = (n * shard_shape[0],) + shard_shape[1:] if shard_shape \
        else (n,)
    per_shard = {s.device: s.data for s in gathered.addressable_shards}
    out = []
    for a in arrays:
        local = per_shard.get(getattr(a, "device", None))
        if local is None:
            local = jax.device_put(
                _np.asarray(gathered.addressable_shards[0].data),
                getattr(a, "device", None))
        out.append(NDArray._from_data(local.reshape(out_shape)))
    return out


# --------------------------------------------------------------------------
# the fused SPMD train step
# --------------------------------------------------------------------------

class _TracedCount(dict):
    """Stand-in for Optimizer._index_update_count during tracing: every index
    reads the traced step scalar; writes are no-ops (the host advances the
    real counters)."""

    def __init__(self, t):
        super().__init__()
        self._t = t

    def __contains__(self, key):  # noqa: ARG002
        return True

    def __getitem__(self, key):  # noqa: ARG002
        return self._t

    def __setitem__(self, key, value):
        pass


class _HostPhase:
    """``with _HostPhase("h2d") as ph:`` — one host interval of a dispatch
    under the name ``trainstep.<phase>``: a
    ``jax.profiler.TraceAnnotation`` (inert, ~0.4 us, unless a profiler
    session is open; then a span on the trace's host plane, on the device
    trace's clock) and two ``perf_counter`` stamps, ``t0`` and ``t1``, for
    the dispatch record (telemetry.stepclock)."""

    __slots__ = ("_annotation", "t0", "t1")

    def __init__(self, name):
        self._annotation = TraceAnnotation("trainstep." + name)

    def __enter__(self):
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        return False

    @property
    def span(self):
        return self.t0, self.t1


def _bank_device_bytes(mesh, arguments):
    """``mxnet_trainstep_device_bytes`` for the fullest device of ``mesh``:
    what ``memory_stats`` has in use there, and the bytes of ``arguments``
    (device arrays) whose shards live there: metadata, no transfer.
    Nothing where the runtime gives no ``memory_stats`` (the CPU's)."""
    in_use = {}
    for dev in mesh.devices:
        stats = dev.memory_stats()
        if stats and "bytes_in_use" in stats:
            in_use[dev] = int(stats["bytes_in_use"])
    if not in_use:
        return
    fullest = max(in_use, key=in_use.get)
    resident = sum(shard.data.nbytes for a in arguments
                   for shard in a.addressable_shards
                   if shard.device == fullest)
    for kind, nbytes in (("in_use", in_use[fullest]), ("arguments", resident)):
        _tel.gauge("mxnet_trainstep_device_bytes", _DEVICE_BYTES_HELP,
                   labels={"kind": kind}).set(nbytes)


# -- counters a block reports from inside the step -------------------------------
# A block cannot bank a traced value in the registry itself: inside the step
# it is a tracer.  It hands it to ``report_counter``; the TrainStep tracing on
# this thread returns every step's values beside the losses and banks them in
# ``telemetry.REGISTRY`` when the losses are fetched (they are outputs of the
# same program: no extra wait for the device).  Always on, like the registry's
# other counters, whatever MXNET_TELEMETRY says: a step that reports returns a
# few scalars a step more, and banking is one device-to-host copy of them per
# fetched dispatch; a net that reports nothing pays nothing.

_reports = threading.local()
_REDUCE = ("sum", "max", "mean")    # report kinds: the reduction's name


def report_counter(name, value, labels=None, kind="sum"):
    """Report ``value`` (an array, reduced over its elements, or a number)
    under the registry name ``name`` from inside a ``TrainStep``'s forward.
    ``kind="sum"``: a counter (``*_total``) that grows by every step's sum;
    ``kind="max"``: a gauge that keeps the largest value any step showed;
    ``kind="mean"``: a gauge set to the mean over the elements and over the
    steps of the dispatch fetched last.  Several reports under one name and
    labels in one step combine the same way (a second mean is refused: it
    would need the first one's count).  Outside a TrainStep's trace (an
    imperative forward) this does nothing.  A block under
    ``gluon.utils.remat_call`` reports as any other: the call hands its
    values out of the checkpointed forward."""
    sink = getattr(_reports, "sink", None)
    if sink is None:
        return
    import jax.numpy as jnp
    if kind not in _REDUCE:
        raise MXNetError(f"report_counter kind {kind!r}: want sum|max|mean")
    raw = value._data if isinstance(value, NDArray) else jnp.asarray(value)
    raw = getattr(jnp, kind)(raw)
    key = (name, tuple(sorted((labels or {}).items())), kind)
    if key in sink:
        if kind == "mean":
            raise MXNetError(f"report_counter({name!r}, kind='mean'): "
                             "reported twice in one step")
        raw = sink[key] + raw if kind == "sum" \
            else jnp.maximum(sink[key], raw)
    sink[key] = raw


@contextlib.contextmanager
def _collect_reports(inner=False):
    """The dict ``report_counter`` fills on this thread while the body
    runs.  ``inner``: a body nested in a collecting one (``remat_call``'s),
    which collects only where the outer body does, and hands its values
    on."""
    outer = getattr(_reports, "sink", None)
    _reports.sink = sink = None if inner and outer is None else {}
    try:
        yield {} if sink is None else sink
    finally:
        _reports.sink = outer


def _bank_reports(reports):
    """Per-step values ``{(name, labels, kind): (steps,) array}`` into the
    registry; one device-to-host copy for all of them."""
    import jax
    for (name, labels, kind), per_step in jax.device_get(reports).items():
        labels = dict(labels) or None
        if kind == "sum":
            _tel.counter(name, labels=labels).inc(int(_np.sum(per_step)))
        elif kind == "mean":
            _tel.gauge(name, labels=labels).set(float(_np.mean(per_step)))
        else:
            gauge = _tel.gauge(name, labels=labels)
            gauge.set(max(gauge.value, float(_np.max(per_step))))


class _StepLosses(NDArray):
    """The losses of a dispatch.  Fetching them (``asnumpy`` and what goes
    through it) stamps the dispatch's record, once: the blocking part is
    the interval ``trainstep.fetch``, and a dispatch that came far later
    than the ring's usual gets one line on the ``mxnet_tpu.trainstep``
    logger.  Where the step's blocks reported counters, the same fetch
    banks them."""

    __slots__ = ("_reports", "_record")

    def asnumpy(self):
        rec, self._record = self._record, None
        if rec is None:
            return super().asnumpy()
        late = self._data.is_ready()
        with _HostPhase("fetch") as fetch:
            out = super().asnumpy()
        slow = _sclock.close_fetch(rec, *fetch.span, late)
        if slow:
            _log.warning(slow)
        if _ttrace._ENABLED:
            _sclock.span_to_ring("fetch", *fetch.span)
        reports, self._reports = self._reports, None
        if reports:
            _bank_reports(reports)
        return out


class TrainStep:
    """One fully-fused, mesh-sharded training step.

    ``TrainStep(net, loss_fn, optimizer, mesh)`` traces the imperative
    pipeline —

        with autograd.record():
            loss = loss_fn(net(data), label).mean()
        loss.backward(); optimizer.update(...)

    — into a single ``jax.jit`` computation whose inputs/outputs carry
    NamedShardings: batch sharded over the mesh's first ('dp') axis, params
    and optimizer state replicated or sharded per ``Parameter.sharding``
    (tensor parallelism).  GSPMD inserts the gradient reductions; on a pod
    they ride ICI exactly like the scaling-book recipe.

    Per-step scalars (t, per-param lr incl. schedules and Adam bias
    correction) enter as *traced* arguments, so the step compiles once.

    Declarative layouts (the GSPMD sharding engine, mxnet_tpu.sharding):
    ``partition_rules`` is an ordered ``(regex, spec)`` list matched
    against the net's param names at resolve time — matched params (and
    their same-shaped optimizer state: adam m/v, momentum, fp32 masters)
    carry the resolved NamedSharding through the jit, unmatched params
    replicate bit-identically.  ``data_spec`` names the batch layout per
    dim (default ``('dp',)``): e.g. ``('dp', 'sp')`` shards (B, L) token
    batches over data AND sequence axes — the dp×tp×sp 3-axis recipe.

    Memory-axis knobs (ISSUE 14): ``n_micro`` runs the step as
    gradient-accumulation microbatching (scan over B/n_micro slices,
    fixed-association accumulation, ONE optimizer update; n_micro=1 is
    the original single-pass trace, bit-identical), ``remat`` wraps the
    net forward in ``gluon.utils.remat_call`` (activations recomputed in
    backward; single-output nets only), and ``plan`` consumes an
    ``autoshard.Plan`` (mesh + rule pack + data_spec + n_micro + remat
    as defaults).  Trace-time knob defaults: MXNET_MICROBATCH,
    MXNET_REMAT.

    Equivalent reference machinery: CachedOp::Forward/Backward +
    Trainer.step + CommDevice reduce + fused optimizer kernels, all in one
    XLA program.
    """

    def __init__(self, net, loss_fn, optimizer, optimizer_params=None,
                 mesh=None, donate=True, partition_rules=None,
                 data_spec=None, n_micro=None, remat=None, plan=None):
        from . import optimizer as opt
        from . import config as _config
        self.net = net
        self.loss_fn = loss_fn
        if isinstance(optimizer, str):
            self.optimizer = opt.create(optimizer, **(optimizer_params or {}))
        else:
            self.optimizer = optimizer
        if plan is not None:
            # an autoshard Plan (mxnet_tpu.autoshard) is consumed
            # directly: it supplies the mesh, the rule pack, the batch
            # layout and the microbatch/remat policy — any explicit
            # constructor argument still wins (plan as defaults)
            if mesh is None:
                mesh = plan.build_mesh()
            if partition_rules is None:
                partition_rules = plan.rules()
            if data_spec is None:
                data_spec = plan.data_spec
            if n_micro is None:
                n_micro = plan.n_micro
            if remat is None:
                remat = plan.remat
        if n_micro is None:
            n_micro = max(1, _config.get_int("MXNET_MICROBATCH", 1))
        n_micro = int(n_micro)
        if n_micro < 1:
            raise MXNetError(f"n_micro must be >= 1, got {n_micro}")
        self._n_micro = n_micro
        self._remat = bool(_config.get_int("MXNET_REMAT", 0)) \
            if remat is None else bool(remat)
        self.mesh = mesh or current_mesh() or make_mesh()
        self._donate = donate
        self._rules = partition_rules
        if data_spec is not None:
            data_spec = tuple(data_spec)
            for entry in data_spec:
                axes = entry if isinstance(entry, (tuple, list)) \
                    else (entry,)
                for a in axes:
                    if a is not None and a not in self.mesh.axis_names:
                        raise MXNetError(
                            f"data_spec {data_spec} names axis {a!r} the "
                            f"mesh {self.mesh!r} does not carry")
        self._data_spec = data_spec
        self._param_specs = None  # name -> logical spec (partition_rules)
        self._p_sh = None         # resolved per-param NamedShardings
        self._s_sh = None         # resolved per-state NamedShardings
        self._params = None       # all params (incl. aux) in fixed order
        self._trainable = None
        self._states = None       # index -> optimizer state (NDArray tree)
        self._state_nds = None    # flattened state NDArrays
        self._state_owner = None  # trainable index owning each state NDArray
        self._fused = None        # (kind, bucket plan) — optimizer_fusion
        self._cache = {}
        self._cache_epoch = None
        self._fresh = set()       # programs built and not yet dispatched
        self._owner = next(_sclock.OWNERS)    # in the dispatch records
        self._last_losses = None  # the dispatch before: was the device fed?
        self._step_count = 0

    def _evict_stale_traces(self):
        """amp on/off bumps the dispatch epoch: traces baked pre-toggle cast
        decisions, so running them would silently use the wrong precision."""
        from .ops import registry as _reg
        if self._cache_epoch != _reg.dispatch_epoch():
            self._cache.clear()
            self._fresh.clear()
            self._cache_epoch = _reg.dispatch_epoch()

    # -- state plumbing -------------------------------------------------------
    @staticmethod
    def _flat_state(st, out):
        if st is None:
            return
        if isinstance(st, (list, tuple)):
            for s in st:
                TrainStep._flat_state(s, out)
        elif isinstance(st, NDArray):
            out.append(st)

    def optimizer_state(self):
        """What the optimizer holds for each trainable parameter, by
        parameter name: ``{"weight": the values the update is applied to
        (the float32 master under ``multi_precision``, else the parameter
        itself), "state": the optimizer's own state for it — Adam's
        ``(m, v)``, SGD's momentum, None for a stateless optimizer}`` as
        NDArrays.  Handles, not copies: a later dispatch donates the
        arrays behind them, so read ``_data``/``asnumpy()`` before it.
        Empty before the first dispatch (or ``lowered``) has resolved the
        parameters."""
        out = {}
        opt = self.optimizer
        for i, p in enumerate(self._trainable or ()):
            st = self._states[i]
            # optimizer.create_state_multi_precision's own rule: (master,
            # state) for a half-precision weight, the plain state otherwise
            if opt.multi_precision and opt._is_half(p.data().dtype):
                out[p.name] = {"weight": st[0], "state": st[1]}
            else:
                out[p.name] = {"weight": p.data(), "state": st}
        return out

    def _resolve(self, data_nd):
        """Fix the param/state order; ``data_nd=None`` (lowering from
        shapes alone) skips the forward that finishes deferred init, so
        every param must already know its shape.  The whole of it is
        the interval ``trainstep.resolve`` and grows
        ``mxnet_trainstep_resolve_seconds``."""
        with _HostPhase("resolve") as resolve:
            from . import autograd
            if data_nd is not None:
                with autograd.pause():
                    self.net(data_nd)  # finish deferred init
            self._params = list(self.net.collect_params().values())
            if data_nd is None:
                deferred = [p.name for p in self._params
                            if p.shape is None or 0 in p.shape]
                if deferred:
                    raise MXNetError(
                        "TrainStep.lowered from shapes needs an initialized "
                        "net: run one forward first (deferred-init params: "
                        f"{deferred[:4]})")
            self._trainable = [p for p in self._params if p.grad_req != "null"]
            if self._rules is not None:
                # declarative layout: resolve the rule set against the named
                # param tree ONCE (first-match-wins, scalars + unmatched
                # replicate) — _param_sharding then reads these specs
                from . import sharding as _sh
                self._param_specs = _sh.match_partition_rules(
                    self._rules, {p.name: p for p in self._params})
            self._states = {
                i: self.optimizer.create_state_multi_precision(i, p.data())
                for i, p in enumerate(self._trainable)}
            flat, owners = [], []
            for i in range(len(self._trainable)):
                n0 = len(flat)
                self._flat_state(self._states[i], flat)
                owners.extend([i] * (len(flat) - n0))
            self._state_nds = flat
            self._state_owner = owners
            self._p_sh = self._s_sh = None  # re-resolve shardings next use
            # fused optimizer (optimizer_fusion): plan the dtype buckets NOW
            # (host side, before any tracing); raw() then updates through the
            # fused math inline — the same formulas the imperative Trainer
            # path dispatches with donation — instead of tracing ~2 registry
            # dispatch wrappers per parameter
            from . import optimizer_fusion as _fus
            self._fused = _fus.plan_trainstep(self.optimizer, self._trainable)
        _M_RESOLVE_SECONDS.inc(resolve.t1 - resolve.t0)
        if _ttrace._ENABLED:
            _sclock.span_to_ring("resolve", *resolve.span)

    def _param_sharding(self, p):
        """Resolved NamedSharding for one param.  With partition_rules
        the rule mapping is AUTHORITATIVE: a matched-() or unmatched
        param replicates (the bit-identity contract) — construction-time
        Parameter.sharding hints do not resurrect under it.  Without
        rules the hint applies.  Either way axes the mesh doesn't carry
        and indivisible dims degrade to unsharded so the same layout
        runs on smaller meshes unchanged."""
        from . import sharding as _sh
        if self._param_specs is not None:
            spec = self._param_specs.get(p.name, ())
        else:
            spec = p.sharding
        if spec:
            return _sh.resolve_spec(spec, self.mesh, shape=p.shape)[0]
        # under declared rules an empty spec (scalar, matched-() rule,
        # unmatched) is replication too — count it so resolved+fallback
        # covers every param and a missing-rule regression shows up in
        # the coverage numbers.  A rule-less TrainStep declares no
        # layout and stays out of the coverage telemetry entirely.
        if _ttrace._ENABLED and self._param_specs is not None:
            _sh._M_FALLBACK.inc()
        return self.mesh.replicated()

    def _shardings(self):
        """(per-param, per-state) NamedShardings, resolved ONCE per
        resolve — the mxnet_sharding_{resolved,fallback}_params_total
        counters then count each param exactly once (layout coverage),
        and the per-step dispatch path reuses the objects instead of
        rebuilding them.  Optimizer state rides its owner param's layout
        when the shapes match (adam m/v, momenta, fp32 masters are
        elementwise over the weight), else replicates."""
        if self._p_sh is None:
            self._p_sh = tuple(self._param_sharding(p)
                               for p in self._params)
            by_param = {id(p): sh
                        for p, sh in zip(self._params, self._p_sh)}
            repl = self.mesh.replicated()
            out = []
            for s, i in zip(self._state_nds, self._state_owner):
                p = self._trainable[i]
                if tuple(s.shape) == tuple(p.shape or ()):
                    out.append(by_param[id(p)])
                else:
                    out.append(repl)
            self._s_sh = tuple(out)
        return self._p_sh, self._s_sh

    def _data_shardings(self, data_ndim, label_ndim, stacked=False):
        """(data, label) NamedShardings from data_spec (default: dim0
        over the mesh's first axis).  The spec clips to each operand's
        rank — a (B,) label under data_spec ('dp', 'sp') shards over dp
        only — and stacked run() batches get a leading unsharded steps
        dim."""
        spec = self._data_spec if self._data_spec is not None \
            else (self.mesh.axis_names[0],)
        lead = (None,) if stacked else ()
        return (self.mesh.sharded(*(lead + spec[:data_ndim])),
                self.mesh.sharded(*(lead + spec[:label_ndim])))

    # -- trace ----------------------------------------------------------------
    def _make_raw(self):
        """The traced single-step body shared by _build (one step per call)
        and _build_multi (lax.scan of many steps per call).

        ``n_micro > 1`` turns the body into gradient-accumulation
        microbatching: the batch reshapes to (n_micro, B/n_micro, ...) and
        a lax.scan runs forward+backward per microbatch, accumulating
        gradients in FIXED association (the scan's sequential carry —
        micro 0 first, always), then applies ONE optimizer update with the
        mean gradient.  The reported loss is the mean of per-microbatch
        losses, which equals the full-batch objective for the per-sample-
        mean losses every lane uses.  ``n_micro == 1`` takes the original
        single-pass body — bit-identical to the pre-microbatching step by
        construction (same trace, no scan, no accumulator).

        ``remat`` wraps the net forward in ``gluon.utils.remat_call``:
        activations inside the net are recomputed during backward instead
        of saved (single-output nets only — remat_call's contract)."""
        from . import autograd, random as _rnd
        from .ops import registry as _reg

        params, trainable = self._params, self._trainable
        state_nds = self._state_nds
        optzr = self.optimizer
        loss_fn = self.loss_fn
        net = self.net
        fused = self._fused
        n_micro = self._n_micro
        remat = self._remat
        from . import optimizer_fusion as _fus

        from .ndarray.ndarray import swap_slot_values

        # the mesh axes dim 0 of the batch is sharded over (ops that place
        # their own shard_map read them through registry.step_layout)
        batch_axes = self._data_spec[0] if self._data_spec \
            else self.mesh.axis_names[0]
        if not isinstance(batch_axes, (tuple, list)):
            batch_axes = (batch_axes,)
        batch_axes = tuple(a for a in batch_axes if a is not None)

        def forward_loss(key, d, l):
            """(remat'd) forward + loss under record scope; grads land in
            the (pre-zeroed) grad slots."""
            d_nd, l_nd = NDArray._from_data(d), NDArray._from_data(l)
            scope = _rnd.trace_key_scope(key)
            reports = {}
            with scope, autograd._scope(recording=True, training=True):
                with _collect_reports() as reports:
                    if remat:
                        from .gluon.utils import remat_call
                        out = remat_call(net, d_nd)
                    else:
                        out = net(d_nd)
                with _regions.scope("loss"):
                    loss = loss_fn(out, l_nd)
                    if loss.shape:
                        loss = loss.mean()
            # the tape re-enters each op's region around its vjp, so the
            # backward's instructions carry the forward's names
            autograd.backward([loss])
            return loss, reports

        def apply_update():
            with _regions.scope("optimizer"):
                if fused is not None:
                    # fused flat update: same segment math as the
                    # imperative donated executables, inlined into
                    # this trace (bitwise identical to the loop below)
                    _fus.traced_update(optzr, fused[0], fused[1],
                                       trainable, self._states)
                else:
                    for i, p in enumerate(trainable):
                        optzr.update_multi_precision(i, p._data,
                                                     p._data._grad,
                                                     self._states[i])

        # The function's name becomes the compiled module's (jit_train_step,
        # jit_train_steps) and so part of JAX's compile-cache key — which
        # the region scopes are not: they reach the module as op_name
        # metadata, and the key strips metadata.  A tree that shares a cache
        # directory with an older one would load that tree's executable and
        # read its names (or none) back from it.  So a change to the scopes
        # that telemetry reads renames the program with it (last: PR 27,
        # from raw/raw_multi).
        def train_step(key, t, lr_vec, rescale, param_vals, state_vals, d,
                       l):
            import jax
            import jax.numpy as jnp
            saved_opt = (optzr._update_count, optzr._index_update_count,
                         optzr._get_lr, optzr.rescale_grad)
            layout = _reg.step_layout_scope(self.mesh, batch_axes)
            # one swap covers params + optimizer state + grad buffers
            # (grads enter zeroed in-trace: params the loss does not reach
            # keep a zero gradient — the reference tolerates stale grads)
            pairs = (list(zip((p._data for p in params), param_vals))
                     + list(zip(state_nds, state_vals))
                     + [(p._data._grad,
                         jnp.zeros(p.shape, p._data._grad.dtype))
                        for p in trainable])
            try:
                with layout, swap_slot_values(pairs):
                    optzr._update_count = lambda idx: None
                    optzr._index_update_count = _TracedCount(t)
                    optzr._get_lr = lambda idx: lr_vec[idx]
                    optzr.rescale_grad = rescale

                    if n_micro == 1:
                        loss, reports = forward_loss(key, d, l)
                        apply_update()
                        new_p = tuple(p._data._slot.value for p in params)
                        new_s = tuple(s._slot.value for s in state_nds)
                        return new_p, new_s, loss._data, reports

                    # microbatched: (B, ...) -> (n_micro, B/n_micro, ...)
                    # keeping each microbatch on the declared data layout
                    d_sh, l_sh = self._data_shardings(
                        len(d.shape), len(l.shape), stacked=True)
                    dm = jax.lax.with_sharding_constraint(
                        d.reshape((n_micro, d.shape[0] // n_micro)
                                  + d.shape[1:]), d_sh)
                    lm = jax.lax.with_sharding_constraint(
                        l.reshape((n_micro, l.shape[0] // n_micro)
                                  + l.shape[1:]), l_sh)
                    keys = jax.random.split(key, n_micro)
                    grad_nds = [p._data._grad for p in trainable]

                    def micro(acc, xs):
                        k_i, dd, ll = xs
                        # fresh zero grads per microbatch; the micro's
                        # gradient is read before the swap restores
                        with swap_slot_values(
                                [(g, jnp.zeros(p.shape, g.dtype))
                                 for g, p in zip(grad_nds, trainable)]):
                            loss, reports = forward_loss(k_i, dd, ll)
                            g = tuple(gn._slot.value for gn in grad_nds)
                        # fixed-association accumulation: acc + micro_i,
                        # in scan order
                        acc = tuple(a + gi for a, gi in zip(acc, g))
                        return acc, (loss._data, reports)

                    zeros = tuple(
                        jnp.zeros(p.shape, p._data._grad.dtype)
                        for p in trainable)
                    acc, (losses, reports) = jax.lax.scan(
                        micro, zeros, (keys, dm, lm))
                    # a step's report is its microbatches' combined
                    reports = {k: getattr(v, k[2])(0)
                               for k, v in reports.items()}
                    inv = jnp.asarray(1.0 / n_micro, losses.dtype)
                    mean_g = tuple(a * jnp.asarray(1.0 / n_micro, a.dtype)
                                   for a in acc)
                    with swap_slot_values(list(zip(grad_nds, mean_g))):
                        apply_update()
                        new_p = tuple(p._data._slot.value for p in params)
                        new_s = tuple(s._slot.value for s in state_nds)
                        return new_p, new_s, (losses.sum() * inv), reports
            finally:
                (optzr._update_count, optzr._index_update_count,
                 optzr._get_lr, optzr.rescale_grad) = saved_opt

        return train_step

    def _build(self, data, label):
        import jax
        raw = self._make_raw()
        repl = self.mesh.replicated()
        d_sh, l_sh = self._data_shardings(len(data.shape), len(label.shape))
        p_sh, s_sh = self._shardings()
        in_sh = (repl, repl, repl, repl, p_sh, s_sh, d_sh, l_sh)
        out_sh = (p_sh, s_sh, repl, repl)
        donate = (4, 5) if self._donate else ()
        return _costmodel.wrap_jit(
            jax.jit(raw, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=donate), "parallel.TrainStep")

    def _build_multi(self, stacked, data_ndim, label_ndim):
        """K steps fused into ONE XLA program via lax.scan.

        Amortizes per-dispatch host/RPC latency over K steps — on TPU the
        standard "jit the training loop" recipe (every step after the first
        starts with zero launch gap).  ``stacked=True`` scans over per-step
        batches (leading dim = steps); False reuses one batch each step.
        """
        import jax
        raw = self._make_raw()

        def train_steps(keys, ts, lr_vecs, rescale, param_vals, state_vals,
                        d, l):
            def body(carry, xs):
                p_vals, s_vals = carry
                if stacked:
                    key, t, lr_vec, dd, ll = xs
                else:
                    key, t, lr_vec = xs
                    dd, ll = d, l
                new_p, new_s, loss, reports = raw(key, t, lr_vec, rescale,
                                                  p_vals, s_vals, dd, ll)
                return (new_p, new_s), (loss, reports)

            xs = (keys, ts, lr_vecs, d, l) if stacked else (keys, ts, lr_vecs)
            (p, s), (losses, reports) = jax.lax.scan(
                body, (param_vals, state_vals), xs)
            return p, s, losses, reports

        repl = self.mesh.replicated()
        p_sh, s_sh = self._shardings()
        lead = 1 if stacked else 0
        d_sh, l_sh = self._data_shardings(data_ndim - lead,
                                          label_ndim - lead, stacked=stacked)
        in_sh = (repl, repl, repl, repl, p_sh, s_sh, d_sh, l_sh)
        out_sh = (p_sh, s_sh, repl, repl)
        donate = (4, 5) if self._donate else ()
        return _costmodel.wrap_jit(
            jax.jit(train_steps, in_shardings=in_sh, out_shardings=out_sh,
                    donate_argnums=donate), "parallel.TrainStep")

    def _program(self, data, label, stacked=None, steps=None):
        """The jitted program for these (shape, dtype)s, built on first
        use: ``__call__``'s single step when ``steps`` is None, else
        ``run``'s scan of ``steps`` steps."""
        self._evict_stale_traces()
        key_sig = ((tuple(data.shape), str(data.dtype)),
                   (tuple(label.shape), str(label.dtype)))
        if steps is not None:
            key_sig = ("multi", stacked, steps) + key_sig
        fn = self._cache.get(key_sig)
        if fn is None:
            fn = self._build(data, label) if steps is None else \
                self._build_multi(stacked, len(data.shape),
                                  len(label.shape))
            self._cache[key_sig] = fn
            self._fresh.add(fn)     # its first dispatch is the one that
            _M_RETRACES.inc()       # ``built`` (the dispatch record)
        return fn

    def lowered(self, data, label, steps=None, scan=True):
        """The ``jax.stages.Lowered`` of the program ``run(data, label,
        steps)`` — or, with ``scan=False``, ``__call__(data, label)`` —
        dispatches, from shapes alone: nothing runs and no step is
        counted.  ``.compile()`` it to read ``as_text()`` (is the Pallas
        kernel in the program?) and ``memory_analysis()``, also for a
        described topology the process has no device of (mesh built over
        ``topo.devices``).  ``data``/``label`` may be arrays or
        ``jax.ShapeDtypeStruct``s; from structs alone the net must
        already be initialized."""
        import jax
        if self._params is None:
            if isinstance(data, jax.ShapeDtypeStruct):
                self._resolve(None)
            else:
                data = data if isinstance(data, NDArray) else nd.array(data)
                self._resolve(NDArray._from_data(data._data[0])
                              if scan and steps is None else data)
        f32 = _np.float32
        n_tr = len(self._trainable)

        def struct(x, lead=()):
            return jax.ShapeDtypeStruct(lead + tuple(x.shape), x.dtype)

        p_vals = tuple(struct(p._data._data) for p in self._params)
        s_vals = tuple(struct(s._data) for s in self._state_nds)
        if not scan:
            fn = self._program(data, label)
            lead = ()
        else:
            stacked = steps is None
            if stacked:
                steps = data.shape[0]
            fn = self._program(data, label, stacked, steps)
            lead = (steps,)
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        return fn.lower(struct(key, lead), jax.ShapeDtypeStruct(lead, f32),
                        jax.ShapeDtypeStruct(lead + (n_tr,), f32),
                        jax.ShapeDtypeStruct((), f32), p_vals, s_vals,
                        struct(data), struct(label))

    def run(self, data, label, steps=None):
        """Run many fused training steps in ONE jitted dispatch.

        ``run(stacked_data, stacked_label)`` scans over the leading
        (steps,) dim — per-step batches; ``run(data, label, steps=K)``
        reuses one batch K times (perf benchmarking).  Returns the per-step
        losses as a (steps,) NDArray.  Numerics match ``steps`` sequential
        ``__call__``s (same RNG stream discipline: one fresh key per step).
        """
        import jax
        if not isinstance(data, NDArray):
            data = nd.array(data)
        if not isinstance(label, NDArray):
            label = nd.array(label)
        stacked = steps is None
        if stacked:
            steps = data.shape[0]
        b_dim = data.shape[1] if stacked else data.shape[0]
        if b_dim % self._n_micro:
            raise MXNetError(
                f"batch {b_dim} is not divisible by n_micro="
                f"{self._n_micro}")
        if self._params is None:
            probe = NDArray._from_data(data._data[0]) if stacked else data
            self._resolve(probe)

        fn = self._program(data, label, stacked, steps)

        def bookkeeping():
            # per-step scalars ship as stacked traced arrays; one fresh
            # key per step from the seeded stateful stream
            from . import random as _rnd
            ts, lr_vecs, rescale = self._advance(steps)
            return jax.random.split(_rnd.get_key(), steps), ts, lr_vecs, \
                rescale

        return self._dispatch(fn, bookkeeping, data, label, stacked, steps)

    # -- call -----------------------------------------------------------------
    def __call__(self, data, label):
        """Run one step; returns the (replicated) scalar loss NDArray."""
        if not isinstance(data, NDArray):
            data = nd.array(data)
        if not isinstance(label, NDArray):
            label = nd.array(label)
        if data.shape[0] % self._n_micro:
            raise MXNetError(
                f"batch {data.shape[0]} is not divisible by n_micro="
                f"{self._n_micro}")
        if self._params is None:
            self._resolve(data)

        fn = self._program(data, label)

        def bookkeeping():
            # per-step dropout key from the seeded stateful stream
            # (mx.random.seed)
            from . import random as _rnd
            ts, lr_vecs, rescale = self._advance(1)
            return _rnd.get_key(), ts[0], lr_vecs[0], rescale

        return self._dispatch(fn, bookkeeping, data, label, False, 1)

    def _advance(self, steps):
        """Host-side bookkeeping of ``steps`` steps up front: advance the
        real counters and compute per-param lr (schedules, multipliers);
        ``(t (steps,), lr (steps, n_trainable), rescale)`` ship to the
        program as traced float32 values."""
        n_tr = len(self._trainable)
        ts, lr_vecs = [], []
        for _ in range(steps):
            self._step_count += 1
            for i in range(n_tr):
                self.optimizer._update_count(i)
            ts.append(self.optimizer._index_update_count.get(
                0, self._step_count))
            lr_vecs.append([self.optimizer._get_lr(i) for i in range(n_tr)])
        return (_np.asarray(ts, _np.float32),
                _np.asarray(lr_vecs, _np.float32).reshape(steps, n_tr),
                _np.float32(self.optimizer.rescale_grad))

    def _dispatch(self, fn, bookkeeping, data, label, stacked, steps):
        """The host's part of one dispatch of ``fn``, in four phases
        (module docstring of telemetry.stepclock): ``bookkeeping()``
        returns the program's leading scalar arguments, ``h2d`` puts the
        batch, parameters and optimizer state where the program wants
        them, ``enqueue`` calls the jitted program — asynchronously: it
        returns before the device has finished, so its time says nothing
        about the chip — and ``writeback`` hands the new arrays to the
        parameter and state handles.  Every dispatch leaves its record in
        ``telemetry.stepclock.DISPATCHES``, telemetry on or off.  Returns
        the program's losses as an NDArray whose fetch stamps the record
        and, where the step's blocks reported counters
        (``report_counter``), banks them."""
        import jax
        rec = _sclock.open_dispatch(self._owner, steps)
        with _HostPhase("bookkeeping") as ph_bookkeeping:
            scalars = bookkeeping()
        with _HostPhase("h2d") as ph_h2d:
            lead = 1 if stacked else 0
            d_sh, l_sh = self._data_shardings(len(data.shape) - lead,
                                              len(label.shape) - lead,
                                              stacked=stacked)
            d = jax.device_put(data._data, d_sh)
            l = jax.device_put(label._data, l_sh)
            p_sh, s_sh = self._shardings()
            p_vals = tuple(jax.device_put(p._data._data, sh)
                           for p, sh in zip(self._params, p_sh))
            s_vals = tuple(jax.device_put(s._data, sh)
                           for s, sh in zip(self._state_nds, s_sh))
        _M_STEP_DISPATCHES.inc()
        if self._n_micro > 1:
            _M_MICROBATCHES.inc(self._n_micro * steps)
        built = fn in self._fresh
        if built:
            self._fresh.discard(fn)
            _bank_device_bytes(self.mesh, p_vals + s_vals + (d, l))
        with _HostPhase("enqueue") as ph_enqueue:
            new_p, new_s, losses, reports = fn(*scalars, p_vals, s_vals, d, l)
        # one non-blocking look: did the device still have the dispatch
        # before this one to run when this one was queued behind it?
        last, self._last_losses = self._last_losses, losses
        fed = last is not None and not last.is_ready()
        with _HostPhase("writeback") as ph_writeback:
            for p, v in zip(self._params, new_p):
                p._data._set_data(v)
            for s, v in zip(self._state_nds, new_s):
                s._set_data(v)
        # one flag read per dispatch (graftcheck GC05); the StepClock
        # treats each dispatch as one "step"
        _sclock.close_dispatch(
            rec, {"bookkeeping": ph_bookkeeping.span, "h2d": ph_h2d.span,
                  "enqueue": ph_enqueue.span, "writeback": ph_writeback.span},
            fed, built, _ttrace._ENABLED)
        out = _StepLosses._from_data(losses)
        out._reports = reports
        out._record = rec
        return out
