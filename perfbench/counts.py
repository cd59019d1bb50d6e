"""Operations and bytes that the algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  Nothing here looks at the program: every size comes from a
configuration file (the published keys) or a traffic file.
"""

BF16_BYTES = 2


def matmul_params(cfg):
    """Weights that a token is multiplied with on the way to the loss.

    Per encoder layer the q/k/v/output projections (4*U^2) and the two
    feed-forward matrices (2*U*I); after the last layer the vocabulary
    projection (U*V).  Embedding tables are gathers and are left out (PaLM
    appendix B convention); the pooler is not reached by the MLM loss.  The
    published MLM head has a U*U transform before the vocabulary projection;
    the zoo's BERTModel and the plain reference have none, so it is not
    counted (PERF.md, Open questions).
    """
    u, i = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 4 * u * u + 2 * u * i
    return cfg["num_hidden_layers"] * per_layer + u * cfg["vocab_size"]


def train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token, recomputation not counted:
    6 per matmul weight, plus attention's QK^T and PV (2 matmuls of 2*S*U
    FLOPs a token forward, twice that backward = 12*L*U*S)."""
    return (6 * matmul_params(cfg)
            + 12 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq)


def attention_flops_per_layer(batch, heads, seq, head_dim):
    """Attention proper for one layer, forward + backward: QK^T and PV
    forward (2 matmuls), dV, dP, dQ, dK backward (4 matmuls), each
    2*B*H*S^2*D.  The backward's recomputed QK^T is work the kernel chose,
    not work the algorithm needs, and does not count."""
    return 12 * batch * heads * seq * seq * head_dim


def attention_bytes_per_layer(batch, heads, seq, head_dim,
                              bytes_per=BF16_BYTES):
    """Least HBM traffic of one layer's attention, forward + backward: read
    q, k, v and write o (forward); read q, k, v, o, do and write dq, dk, dv
    (backward): 12 tensors of B*H*S*D."""
    return 12 * batch * heads * seq * head_dim * bytes_per


def roofline_seconds(flops, nbytes, peak):
    """(least seconds, which bound) on a chip with these peaks."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
