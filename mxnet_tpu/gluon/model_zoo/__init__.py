"""gluon.model_zoo (reference python/mxnet/gluon/model_zoo, P9).

``vision`` mirrors the reference's CNN zoo; ``bert`` is the GluonNLP-style
transformer family the BASELINE north-star configs train (the reference keeps
BERT in the external GluonNLP repo — here it ships in-tree because it is the
flagship perf model).
"""

from . import bert  # noqa: F401


def __getattr__(name):
    import importlib
    if name in ("vision", "llama", "mla_moe", "qwen3_next", "ouro",
                "transformer", "yolo"):
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def get_model(name, **kwargs):
    """Reference model_zoo.get_model factory."""
    from . import vision
    return vision.get_model(name, **kwargs)
