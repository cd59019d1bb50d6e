"""Operations and bytes that a hybrid Gated DeltaNet + gated attention + MoE
decoder's training step needs, from the configuration's published keys and
the traffic alone (the ``qwen3_next`` model type; ``counts_mla_moe.py`` is
the MLA decoder's, and the routed experts' grouped products are counted
there for both).  Nothing here looks at the program.

Conventions as in ``counts.py``: 6 FLOPs a matmul weight and token forward +
backward, embedding gathers left out, nothing recomputed counts.  Causal
attention counts the half of the score matrix at and under the diagonal.
The gated delta rule is counted in its chunked form at the published chunk
of 64 positions: the matrix products that form needs, whatever computes
them, and the bytes of its operands and results, so that a later kernel is
read against the same work as today's ``lax.scan``.
"""

from perfbench.counts_mla_moe import expected_pairs_per_token, expert_params

BF16_BYTES = 2
F32_BYTES = 4


def layer_kinds(cfg):
    """(linear layers, full-attention layers) of the configuration's
    depth: layer i is full iff (i + 1) % full_attention_interval == 0."""
    full = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return cfg["num_hidden_layers"] - full, full


def _linear_widths(cfg):
    """(key heads x d_k, value heads x d_v) of a linear layer."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def linear_mixer_params(cfg):
    """W_qkvz, W_ba, the convolution's taps and W_out of one Gated DeltaNet
    mixer (A_log, dt_bias and the gated norm's scale are a few hundred)."""
    u = cfg["hidden_size"]
    keys, values = _linear_widths(cfg)
    return (u * (2 * keys + 2 * values)
            + u * 2 * cfg["linear_num_value_heads"]
            + (2 * keys + values) * cfg["linear_conv_kernel_dim"]
            + values * u)


def full_mixer_params(cfg):
    """W_q (query and gate), W_k, W_v, W_o of one gated-attention mixer."""
    u, d = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return u * h * 2 * d + 2 * u * kv * d + h * d * u


def moe_params_per_token(cfg):
    """Weights of one layer's feed-forward a token is multiplied with: the
    router (all its outputs), the shared expert and its gate, and the
    routed experts a token reaches on this chip at the expected pairs."""
    u = cfg["hidden_size"]
    return (u * cfg["router_width"]
            + 3 * u * cfg["shared_expert_intermediate_size"] + u
            + expected_pairs_per_token(cfg) * expert_params(cfg))


def matmul_params_per_token(cfg):
    """Weights a token is multiplied with on the way to the loss."""
    linear, full = layer_kinds(cfg)
    return (linear * linear_mixer_params(cfg)
            + full * full_mixer_params(cfg)
            + cfg["num_hidden_layers"] * moe_params_per_token(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def parameters(cfg):
    """Parameters this chip holds (embedding, norms and every held expert
    included): what the zoo model's own count must equal."""
    u = cfg["hidden_size"]
    linear, full = layer_kinds(cfg)
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    moe = (u * cfg["router_width"]
           + 3 * u * cfg["shared_expert_intermediate_size"] + u
           + cfg["experts_held"][1] * expert_params(cfg))
    return (linear * (linear_mixer_params(cfg) + 2 * hv + dv)
            + full * (full_mixer_params(cfg) + 2 * cfg["head_dim"])
            + cfg["num_hidden_layers"] * (moe + 2 * u)
            + u + 2 * u * cfg["vocab_size"])


def attention_flops_per_token_layer(cfg, seq):
    """Causal attention proper of a full layer, forward + backward, per
    token: QK^T and PV forward, dV, dP, dQ, dK backward, each 2*S*H*d over
    the causal half: 3*S*H*(d + d)."""
    return 3 * seq * cfg["num_attention_heads"] * 2 * cfg["head_dim"]


def attention_flops_per_layer(cfg, batch, seq):
    return batch * seq * attention_flops_per_token_layer(cfg, seq)


def attention_bytes_per_layer(cfg, batch, seq, bytes_per=BF16_BYTES):
    """Least HBM traffic of a full layer's attention, forward + backward:
    q, k, v, o read twice, do read, dq, dk, dv written: 12 tensors of
    B*H*S*d (K and V counted at the query heads, as the kernel is given
    them)."""
    return 12 * batch * cfg["num_attention_heads"] * seq \
        * cfg["head_dim"] * bytes_per


def scan_flops_per_token_layer(cfg):
    """The chunked gated delta rule of a linear layer, forward + backward,
    per token, over all value heads.  Forward, a chunk of C positions and a
    head: K_beta K^T and Q K^T (2*C*C*d_k each), the unit-triangular solve
    for the chunk's writes against d_k + d_v columns (C*C*(d_k + d_v)), the
    within-chunk product with the solved values (2*C*C*d_v), and three
    products with the carried state (2*C*d_k*d_v each: the writes' view of
    it, the queries' view of it, its update); the backward twice that."""
    c = cfg["gdn_chunk_size"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    forward_per_chunk = (4 * c * c * dk + c * c * (dk + dv)
                         + 2 * c * c * dv + 6 * c * dk * dv)
    return 3 * cfg["linear_num_value_heads"] * forward_per_chunk / c


def scan_flops_per_layer(cfg, batch, seq):
    return batch * seq * scan_flops_per_token_layer(cfg)


def scan_bytes_per_layer(cfg, batch, seq, bytes_per=BF16_BYTES):
    """Least HBM traffic of the same: q, k, v read and o written forward;
    q, k, v, do read and dq, dk, dv written backward (11 tensors of
    B*S*H*d, q and k at the value heads as the op is given them); g and
    beta read twice and their gradients written in float32."""
    hv = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    wide = (3 * dk + 2 * dv) + (4 * dk + 2 * dv)    # by width, q/k vs v/o
    return batch * seq * hv * (wide * bytes_per + 6 * F32_BYTES)


def train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token of one chip's share."""
    linear, full = layer_kinds(cfg)
    return (6 * matmul_params_per_token(cfg)
            + full * attention_flops_per_token_layer(cfg, seq)
            + linear * scan_flops_per_token_layer(cfg))
