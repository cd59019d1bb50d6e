"""Region scopes: the names a traced program's parts carry into the compiled
module (``metadata={op_name="…/encoder/layer3/attention/…"}``) and, through
it, into the device trace.

``with regions.scope(name):`` pushes ``name`` onto this thread's region path
and, while JAX is tracing, enters ``jax.named_scope(name)``.  Three places
push: ``gluon.Block.__call__`` (the name the block was registered under in
its parent, the root its own ``name``), the attention ops (``attention``) and
``parallel.TrainStep`` (``loss``, ``optimizer``).

The path is the package's own, not JAX's private name stack, because the
package differentiates with its own tape: ``autograd`` stores one ``jax.vjp``
closure per op and calls it later, outside whatever scope the forward ran
in, and JAX names the closure's ops by the scope of the call.  So the tape
keeps ``current()`` on each node (one attribute store) and ``backward``
re-enters it with ``jax.named_scope`` around the closure when it runs under
a trace.  Outside a trace nothing here touches JAX.
"""

from __future__ import annotations

import threading

__all__ = ["scope", "current", "tracing"]


class _Path(threading.local):
    value = ""          # "bert/encoder/layer3", "" outside every scope


_path = _Path()


def current():
    """This thread's region path, ``/``-joined; "" outside every scope."""
    return _path.value


def tracing():
    """Whether JAX is staging a program out on this thread (a jit trace, a
    scan body, a vjp): names only mean something then."""
    import jax
    return not jax.core.trace_ctx.is_top_level()


class scope:
    """Context manager: ``name`` joins the region path and, under a trace,
    JAX's name stack."""

    __slots__ = ("_name", "_outer", "_named")

    def __init__(self, name):
        self._name = name

    def __enter__(self):
        self._outer = outer = _path.value
        _path.value = f"{outer}/{self._name}" if outer else self._name
        self._named = None
        import jax
        if not jax.core.trace_ctx.is_top_level():
            self._named = jax.named_scope(self._name)
            self._named.__enter__()
        return self

    def __exit__(self, *exc):
        _path.value = self._outer
        if self._named is not None:
            self._named.__exit__(*exc)
        return False
