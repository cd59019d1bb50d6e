"""Device context: ``mx.cpu()`` / ``mx.tpu()`` / ``mx.gpu()``.

TPU-native rebuild of the reference's ``python/mxnet/context.py :: Context``
(+ ``include/mxnet/base.h :: struct Context`` dev-type enums).  A Context is a
named handle onto a JAX device; the one-line migration story of the whole
project is ``mx.cpu() -> mx.tpu()``.

Semantics preserved from the reference:
 - ``Context(kind, dev_id)`` value object, ``__eq__``/``__hash__`` on both.
 - thread-local *current context* stack (``with mx.tpu(0): ...``), consulted by
   every array-creating call that doesn't pass ``ctx=``.
 - ``num_gpus()`` / ``num_tpus()`` / ``current_context()``.
 - dev-type integer codes kept for serialization parity (kCPU=1, kGPU=2,
   kCPUPinned=3, kCPUShared=5; TPU takes 6, a free slot).

TPU-first deltas: ``gpu(i)`` resolves onto the accelerator platform when one is
present (so unmodified reference scripts run on TPU); ``cpu_pinned``/
``cpu_shared`` alias plain cpu — pinned-memory staging and POSIX-shm transfer
are host-runtime details XLA/PJRT owns now.
"""

from __future__ import annotations

import threading

from .base import MXNetError

__all__ = [
    "Context", "cpu", "gpu", "tpu", "cpu_pinned", "cpu_shared",
    "current_context", "num_gpus", "num_tpus",
]

_ACCEL_PLATFORM = "tpu"


def _jax():
    import jax
    return jax


class Context:
    devtype2num = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devnum2type = {v: k for k, v in devtype2num.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devtype2num:
            raise MXNetError(
                f"unknown device type {device_type!r}; "
                f"expected one of {sorted(self.devtype2num)}")
        self.device_type = device_type
        self.device_id = device_id
        self._old_ctx = None

    @property
    def device_typeid(self):
        return self.devtype2num[self.device_type]

    # -- resolution onto JAX --------------------------------------------------
    def _platform(self):
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            return "cpu"
        return "accel"  # tpu or gpu-aliased-to-accelerator

    def jax_device(self):
        """The concrete jax.Device this context denotes (resolved lazily)."""
        jax = _jax()
        if self._platform() == "cpu":
            # LOCAL devices only: in multi-process (jax.distributed) runs a
            # context always denotes this process's own devices, like the
            # reference's per-worker ctx (global jax.devices() would hand
            # rank>0 processes an unaddressable device)
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError:  # no cpu backend registered (rare)
                devs = [d for d in jax.devices() if d.platform == "cpu"]
        else:
            devs = _accelerator_devices()
            if not devs:
                if self.device_type == "gpu":
                    raise MXNetError(
                        "mx.gpu() requested but no accelerator platform is "
                        "available (and this build is TPU-native; gpu aliases "
                        "the accelerator). Available: "
                        + ", ".join(sorted({d.platform for d in jax.devices()})))
                raise MXNetError(
                    "mx.tpu() requested but no TPU platform is available. "
                    "Available: "
                    + ", ".join(sorted({d.platform for d in jax.devices()})))
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self} out of range: only {len(devs)} device(s) on its platform")
        return devs[self.device_id]

    # -- value semantics ------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- scope ----------------------------------------------------------------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx
        return False

    def empty_cache(self):
        """Reference API ``ctx.empty_cache()``; XLA owns pooling — no-op."""


def _accelerator_devices():
    jax = _jax()
    try:
        devs = [d for d in jax.local_devices()
                if d.platform == _ACCEL_PLATFORM]
    except RuntimeError:
        devs = []
    if devs:
        return devs
    return [d for d in jax.devices() if d.platform == _ACCEL_PLATFORM]


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id=0):
    return Context("cpu_shared", device_id)


def gpu(device_id=0):
    """Alias onto the accelerator platform so reference scripts run unmodified."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value


def num_gpus():
    """Reference API; counts accelerator devices (gpu aliases tpu here)."""
    return len(_accelerator_devices())


def num_tpus():
    return len(_accelerator_devices())
