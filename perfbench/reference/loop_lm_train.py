"""Plain reference for the looped language model's training cells: straight
jax.numpy, float32, every matrix product at ``Precision.HIGHEST``, no
kernel, nothing kept from one loop step for the next but its output.
Written from the equations of the published ``ouro`` model type ("Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741; the Hugging
Face ``modeling_ouro`` for the order of the norms):

- decoder layer, four RMSNorms: ``a = x + N2(Attn(N1(x)))``, ``y = a +
  N4(W_down(silu(W_gate N3(a)) * W_up N3(a)))``; ``N(x) = x rsqrt(mean(x^2)
  + eps) w``; ``Attn``: q, k, v in ``num_attention_heads`` heads of
  ``head_dim`` (as many key-value heads), rotary on q and k over the whole
  head, pair i = (i, i + d/2) turned by position * theta^(-2i/d), causal
  softmax of ``q k^T / sqrt(d)``, heads merged, ``W_o``; no bias.
- the loop: ``h_0 = E[tokens]``; for ``t = 1 … total_ut_steps``: ``h_t =
  Nf(M(h_{t-1}))``, ``M`` the same ``num_hidden_layers`` layers with the
  same weights at every ``t``, ``Nf`` the final norm; ``logits_t = h_t
  W_head``.
- the exit gate: ``lam_t = sigmoid(h_t w_g + b_g)``; ``S_0 = 1``, ``S_t =
  S_{t-1} (1 - lam_t)``; ``p_t = lam_t S_{t-1}`` for ``t < T`` and ``p_T =
  S_{T-1}``.
- the loss, a position: ``sum_t p_t CE(logits_t, label) - beta H(p)``,
  ``H(p) = -sum_t p_t log p_t``, ``beta`` the configuration's
  ``exit_entropy_beta``; a step's loss is its mean over positions.  Adam
  as ``bert_train`` has it (MXNet's form).

It imports nothing of the program.  So that a step fits one chip beside the
caller's copy of the weights, a layer application keeps its input alone
(``jax.checkpoint``), attention is computed 512 query rows at a time and
the loss ``block_rows`` rows of the batch at a time; the loop steps are a
``lax.scan`` over one traced pass of the stack, so that the compiler sees
the layers once and a weight's gradient adds up over its uses in one
buffer.  The first gradient is returned as its leaves' norms.

Departures from the published description, shared with the configuration
file: only ``num_hidden_layers`` layers and ``vocab_size`` rows of
embedding and head; no second-stage training of the gate alone.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.bert_train import FP8_MATMUL, HIGHEST_MATMUL

__all__ = ["param_shapes", "train_steps", "exits", "position_loss",
           "HIGHEST_MATMUL", "FP8_MATMUL"]

_QUERY_ROWS = 512       # rows of queries whose scores are held at once
_LAYER_LEAVES = ("attn_in_norm", "q_w", "k_w", "v_w", "o_w", "attn_out_norm",
                 "mlp_in_norm", "gate_w", "up_w", "down_w", "mlp_out_norm")


def param_shapes(cfg):
    """name -> (shape, init) in a fixed order; matrices (out, in) like a
    Dense layer's.  Every 'normal' leaf N(0, 0.02), the norms at 1, the
    exit gate's bias at 0."""
    u, i = cfg["hidden_size"], cfg["intermediate_size"]
    e = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    vocab = cfg["vocab_size"]
    out = {"embed": ((vocab, u), "normal")}
    for n in range(cfg["num_hidden_layers"]):
        pre = f"layer{n}."
        out.update({
            pre + "attn_in_norm": ((u,), "ones"),
            pre + "q_w": ((e, u), "normal"),
            pre + "k_w": ((kv, u), "normal"),
            pre + "v_w": ((kv, u), "normal"),
            pre + "o_w": ((u, e), "normal"),
            pre + "attn_out_norm": ((u,), "ones"),
            pre + "mlp_in_norm": ((u,), "ones"),
            pre + "gate_w": ((i, u), "normal"),
            pre + "up_w": ((i, u), "normal"),
            pre + "down_w": ((u, i), "normal"),
            pre + "mlp_out_norm": ((u,), "ones")})
    out.update({"final_norm": ((u,), "ones"),
                "exit_w": ((1, u), "normal"), "exit_b": ((1,), "zeros"),
                "lm_head_w": ((vocab, u), "normal")})
    return out


# -- the model -----------------------------------------------------------------

def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def _post_norm(x, w, eps):
    """N2 and N4: the norm of a sub-layer's output before it joins the
    residual stream."""
    return _norm(x, w, eps)


def _rope(x, theta):
    """x (..., seq, d): pair i = (x[i], x[i + d/2]) turned by position *
    theta^(-2i/d)."""
    seq, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attend(q, k, v, start, matmul):
    """Causal attention of the query rows ``start…`` of the sequences: q
    (batch, heads, rows, d), k, v (batch, heads, seq, d)."""
    scores = matmul("bhqd,bhkd->bhqk", q, k) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    row = start + jnp.arange(q.shape[2])[:, None]
    scores = jnp.where(row >= jnp.arange(k.shape[2])[None], scores, -jnp.inf)
    return matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def _attention(x, p, cfg, matmul):
    """x (batch, seq, U) -> (batch, seq, U), before the post-norm."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    if cfg["num_key_value_heads"] != h:
        raise ValueError("the published model has no grouped heads")
    b, s, _ = x.shape
    theta = jnp.float32(cfg["rope_theta"])

    def heads(w):
        return matmul("bsu,eu->bse", x, w).reshape(b, s, h, d) \
            .transpose(0, 2, 1, 3)

    q, k, v = _rope(heads(p["q_w"]), theta), _rope(heads(p["k_w"]), theta), \
        heads(p["v_w"])
    rows = math.gcd(s, _QUERY_ROWS)
    attend = jax.checkpoint(functools.partial(_attend, matmul=matmul))
    ctx = lax.map(
        lambda r: attend(lax.dynamic_slice_in_dim(q, r, rows, axis=2), k, v,
                         r), jnp.arange(0, s, rows))    # (blocks, b, h, rows, d)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(b, s, h * d)
    return matmul("bse,ue->bsu", ctx, p["o_w"])


def _layer(x, p, cfg, matmul):
    eps = cfg["rms_norm_eps"]
    a = x + _post_norm(
        _attention(_norm(x, p["attn_in_norm"], eps), p, cfg, matmul),
        p["attn_out_norm"], eps)
    h = _norm(a, p["mlp_in_norm"], eps)
    h = jax.nn.silu(matmul("bsu,iu->bsi", h, p["gate_w"])) \
        * matmul("bsu,iu->bsi", h, p["up_w"])
    return a + _post_norm(matmul("bsi,ui->bsu", h, p["down_w"]),
                          p["mlp_out_norm"], eps)


def _weights_of_use(params, use, uses):
    """The layers' weights as loop step ``use`` (0-based and traced, of
    ``uses``) reads them: the same arrays every time."""
    return params


def _carried(normed, raw):
    """What loop step t + 1 starts from: the final norm's output, which
    the head and the gate read too."""
    return normed


def exits(params, tokens, cfg, matmul=HIGHEST_MATMUL):
    """tokens (batch, seq) -> ``h_1 … h_T`` stacked (T, batch, seq, U)."""
    steps, eps = cfg["total_ut_steps"], cfg["rms_norm_eps"]
    layers = [{leaf: params[f"layer{n}.{leaf}"] for leaf in _LAYER_LEAVES}
              for n in range(cfg["num_hidden_layers"])]

    @jax.checkpoint
    def apply(x, p):        # keeps its input; what is inside is made again
        return _layer(x, p, cfg, matmul)

    def loop_step(h, t):    # one pass over the stack: one traced body
        raw = h
        for p in _weights_of_use(layers, t, steps):
            raw = apply(raw, p)
        normed = _norm(raw, params["final_norm"], eps)
        return _carried(normed, raw), normed

    _, out = lax.scan(loop_step, params["embed"][tokens], jnp.arange(steps))
    return out


def exit_distribution(lam):
    """lam (T, …) -> p (T, …): ``p_t = lam_t prod_{s<t} (1 - lam_s)``, the
    last step taking what is left."""
    left, p = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def position_loss(p, ce, beta):
    """p, ce (T, …) -> (…): the expected loss over the exit step less
    ``beta`` times the exit distribution's entropy."""
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1)),
                                 0), 0)
    return jnp.sum(p * ce, 0) - beta * entropy


def _rows_loss(params, tokens, labels, cfg, matmul):
    """The loss of every position of some rows: (rows, seq)."""
    hs = exits(params, tokens, cfg, matmul)
    lam = jax.nn.sigmoid(matmul("tbsu,eu->tbse", hs, params["exit_w"])[..., 0]
                         + params["exit_b"][0])
    ce = []
    for h in hs:
        logits = matmul("bsu,vu->bsv", h, params["lm_head_w"])
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        ce.append(jax.nn.logsumexp(logits, axis=-1) - picked)
    return position_loss(exit_distribution(lam), jnp.stack(ce),
                         jnp.float32(cfg["exit_entropy_beta"]))


def _loss(params, tokens, labels, cfg, matmul, block_rows):
    """The mean over all positions, ``block_rows`` rows of the batch at a
    time."""
    rows, seq = tokens.shape
    if rows % block_rows:
        raise ValueError(f"{rows} rows do not divide into blocks of "
                         f"{block_rows}")
    blocks = (rows // block_rows, block_rows, seq)
    return lax.map(
        lambda xs: _rows_loss(params, *xs, cfg, matmul),
        (tokens.reshape(blocks), labels.reshape(blocks))).mean()


def _freeze(cfg):
    """The configuration's numbers as a hashable, for jit's static
    argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))))


@functools.partial(jax.jit, static_argnames=("cfg_items", "matmul",
                                             "block_rows"))
def _loss_and_grad(params, tokens, labels, cfg_items, matmul, block_rows):
    return jax.value_and_grad(_loss)(params, tokens, labels, dict(cfg_items),
                                     matmul, block_rows)


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(g))).reshape(1)
            for k, g in tree.items()}


def _adam(params, grads, m, v, t, lr, beta1, beta2, eps):
    lr_t = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
    m = jax.tree.map(lambda a, g: beta1 * a + (1 - beta1) * g, m, grads)
    v = jax.tree.map(lambda a, g: beta2 * a + (1 - beta2) * g * g, v, grads)
    params = jax.tree.map(lambda w, a, c: w - lr_t * a / (jnp.sqrt(c) + eps),
                          params, m, v)
    return params, m, v


# the first step must leave the caller's initial weights alone; every later
# one gives its own up
_adam_first = jax.jit(_adam, donate_argnums=(1, 2, 3))
_adam_next = jax.jit(_adam, donate_argnums=(0, 2, 3))


def train_steps(params, tokens, labels, cfg, opt, matmul=HIGHEST_MATMUL,
                block_rows=None, positions=None, skip_update=False):
    """Follow the first ``tokens.shape[0]`` steps from ``params`` (float32,
    as the harness drew them, left untouched).

    ``tokens``/``labels`` are (steps, batch, seq) int32.  Returns
    ``(losses, first_grads, m, v, params)``: each step's loss, the first
    step's gradient as one-element arrays that hold each leaf's norm,
    Adam's moments and the parameters after the last step.  ``positions``
    (every row cut to its first ``positions`` positions, the mean taken
    over them) and ``skip_update`` plant the faults the benchmark's tests
    need: part of the batch left out, and a step that returns its state
    unchanged.
    """
    tokens, labels = tokens[:, :, :positions], labels[:, :, :positions]
    block_rows = block_rows or tokens.shape[1]
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    hyper = [jnp.float32(opt[k]) for k in ("learning_rate", "beta1", "beta2",
                                           "epsilon")]
    losses, first = [], None
    for t in range(tokens.shape[0]):
        loss, grads = _loss_and_grad(params, tokens[t], labels[t],
                                     _freeze(cfg), matmul, block_rows)
        losses.append(loss)
        if first is None:
            first = _leaf_norms(grads)
        if not skip_update:
            step = _adam_first if t == 0 else _adam_next
            params, m, v = step(params, grads, m, v, jnp.float32(t + 1),
                                *hyper)
        del grads
    return jnp.stack(losses), first, m, v, params
