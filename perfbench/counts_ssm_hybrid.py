"""Operations and bytes that a hybrid Mamba-2 + attention decoder's training
step needs, from the configuration's published keys and the traffic alone
(the ``granitemoehybrid`` model type with no routed experts: each layer's
mixer is a Mamba-2 scan or grouped-query attention, as ``layer_types``
says, every layer has the same SwiGLU, the head is tied to the embedding).
Nothing here looks at the program.

Conventions as in ``counts.py``: 6 FLOPs a matmul weight and token forward +
backward, embedding gathers left out, and nothing made again counts: a
layer is counted once forward and once backward, however many forward
passes the program runs to save memory.  Causal attention counts the half
of the score matrix at and under the diagonal.  The Mamba-2 scan is counted
in its chunked form at the published chunk (``mamba_chunk_size``): the
matrix products that form needs, whatever computes them, and the bytes of
its operands and results, so that a later kernel is read against the same
work as today's ``jax.numpy`` + ``lax.scan``.
"""

BF16_BYTES = 2
F32_BYTES = 4


def layer_kinds(cfg):
    """(Mamba-2 layers, attention layers) of the configuration's depth."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    mamba = kinds.count("mamba")
    return mamba, len(kinds) - mamba


def _mamba_widths(cfg):
    """(inner channels, convolution channels) of a Mamba-2 mixer."""
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    return inner, inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mamba_matmul_params(cfg):
    """W_in and W_out of one Mamba-2 mixer."""
    u = cfg["hidden_size"]
    inner, conv = _mamba_widths(cfg)
    return u * (inner + conv + cfg["mamba_n_heads"]) + inner * u


def conv_params(cfg):
    """The depthwise convolution's taps of one Mamba-2 mixer."""
    return _mamba_widths(cfg)[1] * cfg["mamba_d_conv"]


def attention_matmul_params(cfg):
    """W_q, W_k, W_v, W_o of one attention mixer."""
    u, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d = u // h
    return 2 * u * h * d + 2 * u * cfg["num_key_value_heads"] * d


def mlp_params(cfg):
    """The SwiGLU's W_in (gate and up) and W_out of every layer."""
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def parameters(cfg):
    """Parameters this chip holds: the layers, the tied embedding slice and
    the final norm.  What the zoo model's own count must equal."""
    u = cfg["hidden_size"]
    mamba, full = layer_kinds(cfg)
    inner, conv = _mamba_widths(cfg)
    per_mamba = (mamba_matmul_params(cfg) + conv_params(cfg) + conv
                 + 3 * cfg["mamba_n_heads"] + inner)
    return (mamba * per_mamba + full * attention_matmul_params(cfg)
            + cfg["num_hidden_layers"] * (mlp_params(cfg) + 2 * u)
            + u * cfg["vocab_size"] + u)


def attention_flops_per_token_layer(cfg, seq):
    """Causal attention proper of an attention layer, forward + backward,
    per token: QK^T and PV forward, dV, dP, dQ, dK backward, each 2*S*H*d
    over the causal half: 3*S*H*(d + d)."""
    h = cfg["num_attention_heads"]
    return 3 * seq * h * 2 * (cfg["hidden_size"] // h)


def scan_flops_per_token_layer(cfg):
    """The chunked Mamba-2 scan of one layer, forward + backward, per token.
    Forward, a chunk of Q positions: ``C B^T`` (2 Q Q N a group), the
    scores against the chunk's writes (2 Q Q P a head), each chunk's state
    (2 Q P N a head) and the carried state's view from each position (2 Q N
    P a head); the scan across chunks only decays and adds.  The backward
    twice that."""
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    forward_per_chunk = (2 * q * q * n * cfg["mamba_n_groups"]
                         + h * (2 * q * q * p + 4 * q * p * n))
    return 3 * forward_per_chunk / q


def scan_flops_per_layer(cfg, batch, seq):
    return batch * seq * scan_flops_per_token_layer(cfg)


def scan_bytes_per_layer(cfg, batch, seq, bytes_per=BF16_BYTES):
    """Least HBM traffic of the same: x, B, C, dt read and y written
    forward; x, B, C, dt, dy read and dx, dB, dC, ddt written backward.  x,
    y and their gradients are (B, S, H, P), B, C and theirs (B, S, G, N) in
    the activations' type; dt and its gradient (B, S, H) float32; A and D a
    few hundred bytes."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return batch * seq * ((5 * h * p + 6 * gn) * bytes_per
                          + 3 * h * F32_BYTES)


def head_params_per_token(cfg):
    """The tied head's product over the slice."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token of one chip's share: every
    layer's products (the convolution's taps counted as weights), causal
    attention in the attention layers, the chunked scan in the Mamba-2
    ones, and the head."""
    mamba, full = layer_kinds(cfg)
    weights = (mamba * (mamba_matmul_params(cfg) + conv_params(cfg))
               + full * attention_matmul_params(cfg)
               + cfg["num_hidden_layers"] * mlp_params(cfg)
               + head_params_per_token(cfg))
    return (6 * weights + full * attention_flops_per_token_layer(cfg, seq)
            + mamba * scan_flops_per_token_layer(cfg))
