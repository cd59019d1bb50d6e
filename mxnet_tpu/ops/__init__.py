"""Operator corpus (rebuild of src/operator/** — SURVEY §2.2).

Importing this package populates the registry; Python namespaces
(``mx.nd.*``) are then generated from the registry by
``mxnet_tpu.ndarray.register`` exactly like the reference generates them from
nnvm registry introspection at import time.
"""

from . import registry  # noqa: F401
from .registry import register, get, list_ops, invoke  # noqa: F401

# registration side effects
from . import elemwise  # noqa: F401
from . import reduce  # noqa: F401
from . import matrix  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer_ops  # noqa: F401
from . import random_ops  # noqa: F401
from . import linalg  # noqa: F401
from . import contrib  # noqa: F401
from . import moe  # noqa: F401
from . import linear_attention  # noqa: F401
from . import state_space  # noqa: F401
from . import short_conv  # noqa: F401
from . import vision  # noqa: F401
from . import quantization  # noqa: F401
from . import sparse_ops  # noqa: F401

# Reference-name ALIASES (the upstream op registry exposes legacy
# CamelCase names alongside snake_case — `mx.nd.SequenceMask` and
# `mx.nd.sequence_mask` are the same kernel there; the generated
# namespaces here mirror that by aliasing registry entries).
_ALIASES = {
    "SequenceMask": "sequence_mask",
    "SequenceLast": "sequence_last",
    "SequenceReverse": "sequence_reverse",
    "SwapAxis": "swapaxes",
    "MakeLoss": "make_loss",
    "BlockGrad": "stop_gradient",
    "Pad": "pad",
    "Cast": "cast",
    "Reshape": "reshape",
    "Flatten": "flatten",
    "Concat": "concat",
    "Softmax": "SoftmaxOutput",   # upstream: Softmax aliases the LOSS head
    "SliceChannel": "slice_channel",
    "ElementWiseSum": "add_n",
    "l2_normalization": "L2Normalization",
    "logical_xor": "broadcast_logical_xor",
    "contrib.boolean_mask": "boolean_mask",   # 1.x contrib namespace alias
}
for _alias, _target in _ALIASES.items():
    registry.alias(_alias, _target)
