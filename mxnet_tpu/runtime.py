"""mx.runtime — feature introspection (reference src/libinfo.cc N22 +
python/mxnet/runtime.py).  Features reflect what this build/host actually
supports; compile-time CUDA/MKLDNN flags map to their TPU-stack analogs."""

from __future__ import annotations

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    import jax
    feats = {}
    platforms = {d.platform for d in jax.devices()}
    feats["TPU"] = "tpu" in platforms
    feats["CPU"] = True
    feats["CUDA"] = False          # TPU-native build
    feats["CUDNN"] = False
    feats["MKLDNN"] = False        # XLA:CPU plays this role
    feats["XLA"] = True
    feats["PALLAS"] = _has_pallas()
    feats["BF16"] = True
    feats["F16C"] = True
    feats["BLAS_OPEN"] = True
    feats["LAPACK"] = True
    feats["OPENCV"] = _has("cv2")
    feats["DIST_KVSTORE"] = True   # dist_tpu_sync (jax.distributed)
    feats["INT64_TENSOR_SIZE"] = True
    feats["SIGNAL_HANDLER"] = False
    feats["PROFILER"] = True
    feats["OPENMP"] = False
    feats["SSE"] = False
    feats["TENSORRT"] = False
    feats["TVM_OP"] = False
    return feats


def _has(mod):
    import importlib.util
    return importlib.util.find_spec(mod) is not None


def _has_pallas():
    try:
        from jax.experimental import pallas  # noqa: F401
        return True
    except ImportError:
        return False


class Features(dict):
    """mx.runtime.Features() — dict of Feature (reference LibInfo::Features)."""

    instance = None

    def __new__(cls):
        if cls.instance is None:
            inst = super().__new__(cls)
            inst.update({k: Feature(k, v) for k, v in _detect().items()})
            cls.instance = inst
        return cls.instance

    def __init__(self):
        super().__init__()

    def is_enabled(self, name):
        name = name.upper()
        if name not in self:
            raise RuntimeError(f"feature {name!r} does not exist")
        return self[name].enabled

    def __repr__(self):
        return str(list(self.values()))


def feature_list():
    return list(Features().values())
