"""mxnet_tpu — a TPU-native deep-learning framework with MXNet 1.x's
capability surface (reference: shuo-ouyang/incubator-mxnet), built on
JAX/XLA/Pallas instead of the reference's C++ engine + CUDA/oneDNN kernels.

Canonical import: ``import mxnet_tpu as mx`` — then the reference's idioms
work with a one-line context swap: ``mx.cpu()`` → ``mx.tpu()``.

Layer map of this package vs the reference (SURVEY §1/§7.1):
  base/context/config/engine      ← base.h, context.py, env vars, engine (N1)
  ndarray/ + ops/                 ← NDArray (N3) + operator corpus (N7/N25)
  autograd                        ← imperative recording/backward (N4)
  symbol/ + cachedop (hybridize)  ← nnvm Symbol + CachedOp (N5/N6)
  gluon/                          ← python/mxnet/gluon (P6-P10)
  optimizer/metric/initializer/lr_scheduler  ← P12/P16/P21
  kvstore/                        ← src/kvstore + ps-lite (N12-N17) → XLA collectives
  parallel/                       ← NEW: mesh/sharding/ring-attention (TPU-first)
  io/ + image + recordio          ← src/io + python io/image (N19/P14/P15)
  profiler/runtime                ← N20/N22
"""

__version__ = "0.1.0"

from . import config  # noqa: F401


def _apply_matmul_precision():
    # float32 means float32 (MXNet numerics): the XLA default lets f32
    # dots run in reduced precision; raise it globally unless overridden.
    # mxnet_tpu.amp flips this to bf16-first policies at runtime.
    prec = config.get("MXNET_TPU_DEFAULT_MATMUL_PRECISION", "highest")
    if prec and prec != "default":
        import jax
        jax.config.update("jax_default_matmul_precision", prec)


def _apply_x64():
    # the reference supports float64 NDArrays end-to-end; JAX canonicalizes
    # f64→f32 unless x64 is on.  Explicit float32 (our default dtype)
    # is unaffected by this flag.
    if config.get("MXNET_TPU_ENABLE_X64", "1") == "1":
        import jax
        jax.config.update("jax_enable_x64", True)


def _apply_compile_cache():
    # One persistent compile cache for every entry point (chip_smoke.py,
    # the benchmark's runner, the serving replica): a chip call starts with
    # no compiled code, and BERT-base alone compiles for most of a minute.
    # Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and
    # nothing is set here.  Otherwise the cache lives at ONE fixed path in
    # the checkout (git-ignored): the path is part of the cache key, so a
    # directory named by pid, time or tempfile would never hit.
    import os
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache"))


_apply_matmul_precision()
_apply_x64()
_apply_compile_cache()

from .base import MXNetError  # noqa: F401
from .context import (  # noqa: F401
    Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus, num_tpus,
)
from . import engine  # noqa: F401
from . import ndarray  # noqa: F401
from . import ndarray as nd  # noqa: F401
from . import autograd  # noqa: F401
from . import random  # noqa: F401

# stateful-RNG convenience: mx.random.seed + mx.random.uniform(...) etc.
random.uniform = nd.random.uniform
random.normal = nd.random.normal
random.randn = lambda *shape, **kw: nd.random.normal(shape=shape, **kw)
random.randint = nd.random.randint
random.multinomial = nd.random.multinomial
random.shuffle = nd.shuffle


def waitall():
    nd.waitall()


def _lazy(name):
    import importlib
    return importlib.import_module(f".{name}", __name__)


def __getattr__(name):
    # lazy submodule loading keeps `import mxnet_tpu` fast and breaks cycles
    lazies = {"gluon", "optimizer", "metric", "initializer", "lr_scheduler",
              "io", "image", "kvstore", "profiler", "runtime", "symbol",
              "parallel", "test_utils", "recordio", "callback", "model",
              "util", "numpy", "numpy_extension", "contrib", "amp", "module",
              "monitor", "checkpoint", "dmlc_params", "operator",
              "pipeline", "name", "attribute", "rtc", "native",
              "visualization", "library", "telemetry", "resilience",
              "analysis", "serving", "autoshard"}
    if name in lazies:
        mod = _lazy(name)
        globals()[name] = mod
        return mod
    # reference canonical short names
    if name == "sym":
        mod = _lazy("symbol")
        globals()["sym"] = mod
        return mod
    if name == "mod":
        mod = _lazy("module")
        globals()["mod"] = mod
        return mod
    if name == "np":
        mod = _lazy("numpy")
        globals()["np"] = mod
        return mod
    if name == "npx":
        mod = _lazy("numpy_extension")
        globals()["npx"] = mod
        return mod
    if name == "kv":
        mod = _lazy("kvstore")
        globals()["kv"] = mod
        return mod
    if name == "viz":
        # reference: `from . import visualization as viz`
        mod = _lazy("visualization")
        globals()["viz"] = mod
        return mod
    if name == "init":
        # reference: `from . import initializer as init` (python/mxnet/__init__.py)
        mod = _lazy("initializer")
        globals()["init"] = mod
        return mod
    if name == "AttrScope":
        # reference exposes mx.AttrScope at top level
        from .attribute import AttrScope
        globals()["AttrScope"] = AttrScope
        return AttrScope
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")
