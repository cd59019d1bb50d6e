"""Operations and bytes that a short-convolution + attention MoE decoder's
training step needs, from the configuration's published keys and the
traffic alone (the ``lfm2_moe`` model type: each layer's operator is a
gated short convolution or grouped-query attention, as ``layer_types`` says
at the model's layers ``layers_held``; the first ``first_k_dense_replace``
of them have a dense SwiGLU, the rest a routed MoE with no shared expert;
the head is tied to the embedding).  Nothing here looks at the program.

Conventions as in ``counts.py``: 6 FLOPs a matmul weight and token forward +
backward, embedding gathers left out, and nothing made again counts: a
layer is counted once forward and once backward, however many forward
passes the program runs to save memory.  Causal attention counts the half
of the score matrix at and under the diagonal.  The routed experts count
at the pairs an even router puts on the held experts.  The gated
convolution is counted as the op ``contrib.gated_short_conv`` sees it, its
operands and results, so that a later kernel is read against the same work
as today's ``jax.numpy``.
"""

BF16_BYTES = 2


def layer_kinds(cfg):
    """The operator of each layer this chip holds."""
    return [cfg["layer_types"][i] for i in cfg["layers_held"]]


def conv_layers(cfg):
    return layer_kinds(cfg).count("conv")


def conv_matmul_params(cfg):
    """W_in (three parts of the width) and W_out of one short convolution."""
    return 4 * cfg["hidden_size"] ** 2


def conv_taps(cfg):
    """The depthwise convolution's taps of one short convolution."""
    return cfg["hidden_size"] * cfg["conv_L_cache"]


def attention_matmul_params(cfg):
    """W_q, W_k, W_v, W_o of one attention operator."""
    u, h = cfg["hidden_size"], cfg["num_attention_heads"]
    d = u // h
    return 2 * u * h * d + 2 * u * cfg["num_key_value_heads"] * d


def dense_params(cfg):
    """The dense SwiGLU's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_pairs_per_token(cfg):
    """(token, expert) pairs a token puts on the held experts if the router
    spreads its k choices evenly over the layer's experts."""
    return cfg["num_experts_per_tok"] * cfg["experts_held"][1] \
        / cfg["router_width"]


def parameters(cfg):
    """Parameters this chip holds: the layers, the tied embedding slice and
    the final norm.  What the zoo model's own count must equal (the expert
    bias, which takes no gradient, included)."""
    u = cfg["hidden_size"]
    d = u // cfg["num_attention_heads"]
    total = u * cfg["vocab_size"] + u
    for n, kind in enumerate(layer_kinds(cfg)):
        total += 2 * u
        total += conv_matmul_params(cfg) + conv_taps(cfg) if kind == "conv" \
            else attention_matmul_params(cfg) + 2 * d
        if n < cfg["first_k_dense_replace"]:
            total += dense_params(cfg)
        else:
            total += (u + 1) * cfg["router_width"] \
                + cfg["experts_held"][1] * expert_params(cfg)
    return total


def attention_flops_per_token_layer(cfg, seq):
    """Causal attention proper of an attention layer, forward + backward,
    per token: QK^T and PV forward, dV, dP, dQ, dK backward, each 2*S*H*d
    over the causal half: 3*S*H*(d + d)."""
    h = cfg["num_attention_heads"]
    return 3 * seq * h * 2 * (cfg["hidden_size"] // h)


def matmul_params_per_token(cfg):
    """Weights a token is multiplied with on the way to the loss: each
    layer's operator (the convolution's taps counted as weights) and
    feed-forward (in an expert layer the router, all its outputs, and the
    routed experts at ``expected_pairs_per_token``), and the tied head."""
    u = cfg["hidden_size"]
    total = u * cfg["vocab_size"]
    for n, kind in enumerate(layer_kinds(cfg)):
        total += conv_matmul_params(cfg) + conv_taps(cfg) if kind == "conv" \
            else attention_matmul_params(cfg)
        total += dense_params(cfg) if n < cfg["first_k_dense_replace"] \
            else (u * cfg["router_width"]
                  + expected_pairs_per_token(cfg) * expert_params(cfg))
    return total


def train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token of one chip's share."""
    full = len(layer_kinds(cfg)) - conv_layers(cfg)
    return (6 * matmul_params_per_token(cfg)
            + full * attention_flops_per_token_layer(cfg, seq))


def gated_conv_flops(cfg, tokens):
    """``C * conv(B * x)`` of one layer over ``tokens`` positions, forward
    (a product for ``B * x``, two a tap, a product for ``C *``) and
    backward (twice that)."""
    return 3 * (2 + 2 * cfg["conv_L_cache"]) * tokens * cfg["hidden_size"]


def gated_conv_bytes(cfg, tokens, bytes_per=BF16_BYTES):
    """Least HBM traffic of the same: ``[B | C | x]`` (3 U a position) read
    and ``y`` (U) written forward; ``dy`` and ``[B | C | x]`` read and its
    gradient (3 U) written backward: 11 U a position; the taps read twice
    and their gradient written."""
    u, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    return (11 * tokens * u + 3 * u * taps) * bytes_per
