"""Operations and bytes that a looped language model's training step needs,
from the configuration's published keys and the traffic alone (the ``ouro``
model type: ``num_hidden_layers`` decoder layers applied ``total_ut_steps``
times over shared weights, every loop step's output read by the head and
the exit gate).  Nothing here looks at the program.

Conventions as in ``counts.py``: 6 FLOPs a matmul weight and token forward +
backward, embedding gathers left out, and nothing made again counts: a
layer application is counted once forward and once backward, however many
forward passes the program runs to save memory.  Causal attention counts
the half of the score matrix at and under the diagonal.
"""

BF16_BYTES = 2


def layer_matmul_params(cfg):
    """W_q, W_k, W_v, W_o and the SwiGLU's three matrices of one layer."""
    u, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * u * h * d + 2 * u * kv * d + 3 * u * cfg["intermediate_size"]


def layer_params(cfg):
    """One layer with its four norms."""
    return layer_matmul_params(cfg) + 4 * cfg["hidden_size"]


def parameters(cfg):
    """Parameters this chip holds: the layers once (the loop shares them),
    the embedding and head slices, the final norm, the exit gate with its
    bias.  What the zoo model's own count must equal."""
    u = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * u * cfg["vocab_size"] + u + u + 1)


def layer_applications(cfg):
    """Applications of a decoder layer in one forward pass of the model."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def attention_flops_per_token_layer(cfg, seq):
    """Causal attention proper of one layer application, forward +
    backward, per token: QK^T and PV forward, dV, dP, dQ, dK backward, each
    2*S*H*d over the causal half: 3*S*H*(d + d)."""
    return 3 * seq * cfg["num_attention_heads"] * 2 * cfg["head_dim"]


def attention_flops_per_layer(cfg, batch, seq):
    """Of one layer application over a batch."""
    return batch * seq * attention_flops_per_token_layer(cfg, seq)


def attention_bytes_per_layer(cfg, batch, seq, bytes_per=BF16_BYTES):
    """Least HBM traffic of one layer application's attention, forward +
    backward: q, k, v, o read twice, do read, dq, dk, dv written: 12
    tensors of B*H*S*d."""
    return 12 * batch * cfg["num_attention_heads"] * seq \
        * cfg["head_dim"] * bytes_per


def head_params_per_token(cfg):
    """The vocabulary projection of the slice and the exit gate, which
    every loop step's output passes."""
    return cfg["hidden_size"] * (cfg["vocab_size"] + 1)


def train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token of one chip's share: every
    layer application's matrices and attention, and the head and the gate
    once a loop step."""
    return (layer_applications(cfg)
            * (6 * layer_matmul_params(cfg)
               + attention_flops_per_token_layer(cfg, seq))
            + cfg["total_ut_steps"] * 6 * head_params_per_token(cfg))
