"""Operations and bytes that an MLA + MoE decoder's training step needs,
from the configuration's published keys and the traffic alone (the
``deepseek_v3`` model type; ``counts.py`` is BERT's).  Nothing here looks at
the program; the pairs a run really routed come from its counters and are an
argument.

Conventions as in ``counts.py``: 6 FLOPs a matmul weight and token forward +
backward, embedding gathers left out, nothing recomputed counts (the routed
experts' forward that the program runs again in its backward, and the
attention kernel's recomputed scores, are work the implementation chose).
Causal attention counts the half of the score matrix at and under the
diagonal.
"""

BF16_BYTES = 2


def _widths(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def attention_params(cfg):
    """W_q, W_kva, W_kvb, W_o of one layer."""
    u, h, dqk, dv = _widths(cfg)
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (u * h * dqk + u * (rank + rope)
            + rank * h * (cfg["qk_nope_head_dim"] + dv) + h * dv * u)


def expert_params(cfg):
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_pairs_per_token(cfg):
    """(token, expert) pairs a token puts on the held experts if the router
    spreads its k choices evenly over the layer's experts."""
    return cfg["num_experts_per_tok"] * cfg["experts_held"][1] \
        / cfg["router_width"]


def matmul_params_per_token(cfg):
    """Weights a token is multiplied with on the way to the loss: every
    layer's attention projections; the dense FFN in the first layers; in an
    expert layer the router (all its outputs), the shared experts and the
    routed experts a token reaches on this chip at the expected
    ``expected_pairs_per_token``; the vocabulary projection of the slice."""
    u = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    expert_layer = (u * cfg["router_width"]
                    + cfg["n_shared_experts"] * expert_params(cfg)
                    + expected_pairs_per_token(cfg) * expert_params(cfg))
    return (layers * attention_params(cfg)
            + dense * 3 * u * cfg["intermediate_size"]
            + (layers - dense) * expert_layer + u * cfg["vocab_size"])


def attention_flops_per_token_layer(cfg, seq):
    """Causal attention proper, forward + backward, per token and layer:
    QK^T (dqk wide) and PV (dv wide) forward, dV and dP (dv), dQ and dK (dqk)
    backward, each 2*S*H*width over the causal half: 3*S*H*(dqk + dv)."""
    _u, h, dqk, dv = _widths(cfg)
    return 3 * seq * h * (dqk + dv)


def train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token of one chip's share."""
    return (6 * matmul_params_per_token(cfg)
            + cfg["num_hidden_layers"]
            * attention_flops_per_token_layer(cfg, seq))


def attention_flops_per_layer(cfg, batch, seq):
    return batch * seq * attention_flops_per_token_layer(cfg, seq)


def attention_bytes_per_layer(cfg, batch, seq, bytes_per=BF16_BYTES):
    """Least HBM traffic of one layer's attention, forward + backward: q, k
    (dqk wide) read twice and dq, dk written; v, o read twice, do read and dv
    written (dv wide): 6 tensors of each width."""
    _u, h, dqk, dv = _widths(cfg)
    return 6 * batch * h * seq * (dqk + dv) * bytes_per


def grouped_flops(cfg, pairs):
    """The three grouped products of ``pairs`` (token, expert) pairs,
    forward and the two gradients of each."""
    return 2 * 3 * pairs * cfg["hidden_size"] \
        * cfg["moe_intermediate_size"] * 3


def grouped_bytes(cfg, pairs, bytes_per=BF16_BYTES):
    """Least HBM traffic of the same: the held experts' three matrices read
    forward, read backward and their gradients written; per pair the row
    read for gate and up, both written, their product read and the output
    written (3*U + 3*I elements), and as much again for each of the two
    gradient passes."""
    u, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["experts_held"][1]
    return 3 * (3 * held * u * i + pairs * (3 * u + 3 * i)) * bytes_per
