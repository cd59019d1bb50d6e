"""Plain reference for the hybrid Gated DeltaNet + gated attention + MoE
training cells: straight jax.numpy, float32, every matrix product at
``Precision.HIGHEST``, no kernel, no chunk, no sort, no grouped product.
Written from the layer equations of the published ``qwen3_next`` model type
(Gated DeltaNet: arXiv:2412.06464, equation 10; the Hugging Face
``modeling_qwen3_next`` for the order of operations):

- block, pre-norm: ``x += mixer(norm(x)); x += moe(norm(x))``; ``norm(x) =
  x rsqrt(mean(x^2) + eps) (1 + w)``, ``w`` starts at 0; layer ``i`` runs
  full attention iff ``(i + 1) % full_attention_interval == 0``; final
  norm, untied head, no bias anywhere.
- Gated DeltaNet: ``[q | k | v | z] = x W_qkvz``, ``[b | a] = x W_ba``;
  ``[q | k | v]`` pass a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps and SiLU; q, k in
  ``linear_num_key_heads`` heads, each repeated to the
  ``linear_num_value_heads`` value heads (value heads 2j, 2j+1 share key
  head j); ``q <- l2norm(q) / sqrt(d_k)``, ``k <- l2norm(k)``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` a value head; the
  state S (d_k x d_v a head, zero at a row's start) goes TOKEN BY TOKEN:
  ``S <- exp(g_t) S; S <- S + k_t (x) beta_t (v_t - S^T k_t); o_t = S^T
  q_t``; ``y = rmsnorm(o) w_n silu(z)`` a head, then ``W_out``.
- gated attention: ``x W_q`` a head ``[query | gate]``; q, k pass a
  per-head zero-centred RMSNorm, then rotary on the first
  ``partial_rotary_factor * head_dim`` dims, pair i = (i, i + half); causal
  softmax of ``q k^T / sqrt(head_dim)`` over ``num_key_value_heads`` shared
  heads; ``(ctx * sigmoid(gate)) W_o``.
- MoE: ``p = softmax(x W_r)`` over all experts; the top k, ties to the
  lower index; weights ``p / sum of the chosen p``; SwiGLU experts; plus
  ``sigmoid(x w_g) * SwiGLU_shared(x)`` on every token.
- loss: mean cross-entropy of the logits against the labels at every
  position.  Adam as ``bert_train`` has it (MXNet's form).

It imports nothing of the program.  The recurrence is a ``lax.scan`` over
positions (an outer scan over blocks of them under ``jax.checkpoint``, so
that the backward holds one block's states, not 8,192); the routed part is
computed expert by expert over ALL tokens, each token's output weighted by
the weight it gave that expert (zero where it did not choose it).

Departures from the published description, shared with the configuration
file: only the experts ``experts_held`` exist here, so a pair that falls on
another expert adds nothing (its chip would add it); only
``num_hidden_layers`` layers and ``vocab_size`` rows of embedding and head;
``W_qkvz``'s columns lie ``[q | k | v | z]``, each by head (the published
code groups them by key head: a fixed permutation of the columns, another
draw of the same model); ``A_log`` is one constant for all heads and the
convolution's taps and the two output projections start at widths of
their own (``init_scale``; ``param_shapes``); no multi-token-prediction
module, no auxiliary loss.

So that the float32 state of 626M parameters fits one chip beside the
caller's copy of the initial weights, gradients are taken a layer at a time
(each layer one program forward and one backward, which runs the layer
again from its kept input), attention is computed a slice of query rows at
a time, Adam runs a leaf at a time with its moments waiting in the host's
memory, and the first gradient is returned as its leaves' norms.
"""

import functools
import math

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from perfbench.reference.bert_train import FP8_MATMUL, HIGHEST_MATMUL
# the scaffolding that knows no model: a configuration as jit's static
# argument, a layer's leaves, `init_scale`, sums and norms over trees
from perfbench.reference.mla_moe_train import (
    _as_given, _embed_backward, _freeze, _init_scale, _layer_leaves,
    _leaf_norms, _scale, at_init)

__all__ = ["param_shapes", "at_init", "train_steps", "logits", "moe_ffn",
           "gated_delta_net", "recurrence", "HIGHEST_MATMUL", "FP8_MATMUL"]

_QUERY_ROWS = 512       # rows of queries whose scores are held at once
_SCAN_BLOCK = 128       # positions of the recurrence whose states are held


def _dims(cfg):
    """(U, key heads, value heads, d_k, d_v, taps) of the linear layers and
    (heads, kv heads, head dim, rotary dims) of the full ones."""
    d = cfg["head_dim"]
    return ((cfg["hidden_size"], cfg["linear_num_key_heads"],
             cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
             cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"]),
            (cfg["num_attention_heads"], cfg["num_key_value_heads"], d,
             int(d * cfg["partial_rotary_factor"])))


def is_full(cfg, n):
    """Whether layer ``n`` runs full attention."""
    return (n + 1) % cfg["full_attention_interval"] == 0


def param_shapes(cfg):
    """name -> (shape, init) in a fixed order; matrices (out, in) like a
    Dense layer's, the stacked experts (expert, in, out).

    The harness draws every 'normal' leaf N(0, 0.02) and knows 'ones' and
    'zeros'; the leaves the configuration's ``init_scale`` names start at
    their factor of that (``at_init``): ``A_log`` (a 'ones' leaf) at the
    constant that gives the decays the file states, ``conv_w`` at the
    width of a default ``Conv1d`` start, and the two output projections
    ``out_w`` and ``o_w`` narrower, as ``mla_moe_train.param_shapes`` says
    of ``o_w``.  Every branch stays open: each leaf takes a gradient from
    the first step on."""
    (u, hk, hv, dk, dv, taps), (h, kv, d, _rot) = _dims(cfg)
    keys, values = hk * dk, hv * dv
    vocab = cfg["vocab_size"]
    e_i, held = cfg["moe_intermediate_size"], cfg["experts_held"][1]
    s_i = cfg["shared_expert_intermediate_size"]
    out = {"embed": ((vocab, u), "normal")}
    for n in range(cfg["num_hidden_layers"]):
        pre = f"layer{n}."
        out[pre + "attn_norm"] = ((u,), "zeros")
        if is_full(cfg, n):
            out.update({
                pre + "q_w": ((h * 2 * d, u), "normal"),
                pre + "k_w": ((kv * d, u), "normal"),
                pre + "v_w": ((kv * d, u), "normal"),
                pre + "q_norm": ((d,), "zeros"),
                pre + "k_norm": ((d,), "zeros"),
                pre + "o_w": ((u, h * d), "normal")})
        else:
            out.update({
                pre + "in_qkvz_w": ((2 * keys + 2 * values, u), "normal"),
                pre + "in_ba_w": ((2 * hv, u), "normal"),
                pre + "conv_w": ((2 * keys + values, taps), "normal"),
                pre + "A_log": ((hv,), "ones"),
                pre + "dt_bias": ((hv,), "ones"),
                pre + "gate_norm": ((dv,), "ones"),
                pre + "out_w": ((u, values), "normal")})
        out.update({
            pre + "ffn_norm": ((u,), "zeros"),
            pre + "router_w": ((cfg["router_width"], u), "normal"),
            pre + "experts_gate": ((held, u, e_i), "normal"),
            pre + "experts_up": ((held, u, e_i), "normal"),
            pre + "experts_down": ((held, e_i, u), "normal"),
            pre + "shared_gate_w": ((s_i, u), "normal"),
            pre + "shared_up_w": ((s_i, u), "normal"),
            pre + "shared_down_w": ((u, s_i), "normal"),
            pre + "shgate_w": ((1, u), "normal")})
    out.update({"final_norm": ((u,), "zeros"),
                "lm_head_w": ((vocab, u), "normal")})
    return out


# -- the model -----------------------------------------------------------------

def _norm(x, w, eps):
    """Zero-centred RMSNorm over the last dim."""
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * (1.0 + w)


def _l2norm(x):
    return x * lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)


def _swiglu(x, gate_w, up_w, down_w, matmul):
    h = jax.nn.silu(matmul("nu,iu->ni", x, gate_w)) \
        * matmul("nu,iu->ni", x, up_w)
    return matmul("ni,ui->nu", h, down_w)


def _causal_conv(x, w):
    """x (b, s, c), w (c, taps): ``out[t] = sum_j w[:, j] x[t - (taps - 1)
    + j]``, zero before the row's start."""
    taps, s = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[:, j] for j in range(taps))


def _decay_and_strength(ba, a_log, dt_bias, hv):
    """(g, beta), each (b, s, value heads): the log of a position's decay
    and its write strength."""
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
    return g, beta


def _token(matmul, state, x):
    """One position of the gated delta rule; state (b, h, d_k, d_v)."""
    q, k, v, g, beta = x
    state = state * jnp.exp(g)[..., None, None]
    seen = matmul("bhkv,bhk->bhv", state, k)
    state = state + matmul("bhk,bhv->bhkv", k, beta[..., None] * (v - seen))
    return state, matmul("bhkv,bhk->bhv", state, q)


def recurrence(q, k, v, g, beta, matmul=HIGHEST_MATMUL):
    """The gated delta rule token by token: q, k (b, s, h, d_k), v (b, s, h,
    d_v), g and beta (b, s, h), float32 -> o (b, s, h, d_v).  The state
    starts at zero.  This is the definition the chunked scan is held to."""
    b, s, h, dk = q.shape
    block = math.gcd(s, _SCAN_BLOCK)

    def by_block(x):    # (b, s, …) -> (blocks, block, b, …)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((s // block, block) + x.shape[1:])

    @jax.checkpoint
    def run_block(state, xs):
        return lax.scan(functools.partial(_token, matmul), state, xs)

    _, out = lax.scan(run_block,
                      jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                      tuple(by_block(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out.reshape((s,) + out.shape[2:]), 0, 1)


def gated_delta_net(x, p, cfg, matmul):
    """x (batch, seq, U) -> (batch, seq, U)."""
    (_u, hk, hv, dk, dv, _taps), _ = _dims(cfg)
    b, s, _ = x.shape
    keys, values = hk * dk, hv * dv
    qkvz = matmul("bsu,eu->bse", x, p["in_qkvz_w"])
    ba = matmul("bsu,eu->bse", x, p["in_ba_w"])
    qkv = jax.nn.silu(_causal_conv(qkvz[..., :2 * keys + values],
                                   p["conv_w"]))
    z = qkvz[..., 2 * keys + values:].reshape(b, s, hv, dv)
    q = qkv[..., :keys].reshape(b, s, hk, dk)
    k = qkv[..., keys:2 * keys].reshape(b, s, hk, dk)
    v = qkv[..., 2 * keys:].reshape(b, s, hv, dv)
    q = jnp.repeat(_l2norm(q) / jnp.sqrt(jnp.float32(dk)), hv // hk, axis=2)
    k = jnp.repeat(_l2norm(k), hv // hk, axis=2)
    g, beta = _decay_and_strength(ba, p["A_log"], p["dt_bias"], hv)
    o = recurrence(q, k, v, g, beta, matmul)
    o = o * lax.rsqrt(jnp.square(o).mean(-1, keepdims=True)
                      + cfg["rms_norm_eps"]) * p["gate_norm"]
    y = (o * jax.nn.silu(z)).reshape(b, s, values)
    return matmul("bse,ue->bsu", y, p["out_w"])


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


def _output_gate(ctx, gate):
    return ctx * jax.nn.sigmoid(gate)


def gated_attention(x, p, cfg, matmul):
    """x (batch, seq, U) -> (batch, seq, U)."""
    _, (h, kv, d, rot) = _dims(cfg)
    b, s, _ = x.shape
    eps, theta = cfg["rms_norm_eps"], jnp.float32(cfg["rope_theta"])
    qg = matmul("bsu,eu->bse", x, p["q_w"]).reshape(b, s, h, 2 * d)
    gate = qg[..., d:].reshape(b, s, h * d)
    k = matmul("bsu,eu->bse", x, p["k_w"]).reshape(b, s, kv, d)
    v = matmul("bsu,eu->bse", x, p["v_w"]).reshape(b, s, kv, d)

    def turned(t, w):       # per-head norm, heads first, rotary on the slice
        t = _norm(t, w, eps).transpose(0, 2, 1, 3)
        return jnp.concatenate([_rope(t[..., :rot], theta), t[..., rot:]],
                               -1)

    q = turned(qg[..., :d], p["q_norm"])
    k = jnp.repeat(turned(k, p["k_norm"]), h // kv, axis=1)
    v = jnp.repeat(v.transpose(0, 2, 1, 3), h // kv, axis=1)
    rows = min(_QUERY_ROWS, s)
    attend = jax.checkpoint(functools.partial(_attend, matmul=matmul))
    ctx = lax.map(
        lambda r: attend(lax.dynamic_slice_in_dim(q, r, rows, axis=2), k, v,
                         r), jnp.arange(0, s, rows))    # (slices, b, h, rows, d)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(b, s, h * d)
    return matmul("bse,ue->bsu", _output_gate(ctx, gate), p["o_w"])


def _scores(logits):
    return jax.nn.softmax(logits, axis=-1)


def route(x, p, cfg, matmul):
    """x (N, U) -> (N, E) float32: the weight each token gives each expert
    of the layer, zero where it did not choose it."""
    s = _scores(matmul("nu,eu->ne", x, p["router_w"]))
    picked, chosen = lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def _shared_gate(x, w, matmul):
    return jax.nn.sigmoid(matmul("nu,eu->ne", x, w))


def moe_ffn(x, p, cfg, matmul, held=None, shared=True):
    """The MoE feed-forward of x (N, U): the experts ``held = (first,
    count)`` (default: the configuration's ``experts_held``; the leaves
    ``experts_*`` hold exactly those) over all tokens, each token's output
    weighted by the weight it gave that expert, plus the gated shared
    expert."""
    first, count = held or cfg["experts_held"]
    weight = route(x, p, cfg, matmul)

    def add_expert(y, expert):
        gate, up, down, w = expert
        h = jax.nn.silu(matmul("nu,ui->ni", x, gate)) \
            * matmul("nu,ui->ni", x, up)
        return y + w[:, None] * matmul("ni,iu->nu", h, down), None

    # one traced body for all held experts, its two (N, width) products made
    # again in the backward: 32 experts' worth would be 1.5 GB a layer
    y, _ = lax.scan(jax.checkpoint(add_expert), jnp.zeros_like(x),
                    (p["experts_gate"], p["experts_up"], p["experts_down"],
                     weight[:, first:first + count].T))
    if shared:
        y = y + _shared_gate(x, p["shgate_w"], matmul) \
            * _swiglu(x, p["shared_gate_w"], p["shared_up_w"],
                      p["shared_down_w"], matmul)
    return y


def _layer(x, p, cfg, matmul, full):
    eps = cfg["rms_norm_eps"]
    mixer = gated_attention if full else gated_delta_net
    x = x + mixer(_norm(x, p["attn_norm"], eps), p, cfg, matmul)
    h = _norm(x, p["ffn_norm"], eps).reshape(-1, x.shape[-1])
    return x + moe_ffn(h, p, cfg, matmul).reshape(x.shape)


# One program a kind of layer, forward and backward, and one for the head:
# the three linear layers share theirs.  The backward of a layer starts from
# the layer's input, kept from the forward pass, and runs the layer again
# inside its vjp.

@functools.partial(jax.jit, static_argnames=("cfg_items", "matmul", "full"))
def _layer_forward(x, p, cfg_items, matmul, full):
    return _layer(x, p, dict(cfg_items), matmul, full)


@functools.partial(jax.jit, static_argnames=("cfg_items", "matmul", "full"),
                   donate_argnums=(0,))
def _layer_backward(acc, x, p, d_out, cfg_items, matmul, full):
    """(acc + the layer's parameter gradients, the gradient of its input);
    ``acc`` is given up to the sum."""
    _, vjp = jax.vjp(lambda x, p: _layer(x, p, dict(cfg_items), matmul,
                                         full), x, p)
    d_x, d_p = vjp(d_out)
    return {k: a + d_p[k] for k, a in acc.items()}, d_x


def _head_loss(x, norm, head_w, labels, eps, matmul):
    """Sum over the positions of the cross-entropy of the head's logits."""
    out = matmul("bsu,vu->bsv", _norm(x, norm, eps), head_w)
    picked = jnp.take_along_axis(out, labels[..., None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(out, axis=-1) - picked).sum()


@functools.partial(jax.jit, static_argnames=("eps", "matmul"),
                   donate_argnums=(0,))
def _head_backward(acc, x, norm, head_w, labels, eps, matmul):
    val, (d_x, d_norm, d_head) = jax.value_and_grad(
        _head_loss, argnums=(0, 1, 2))(x, norm, head_w, labels, eps, matmul)
    return ({"final_norm": acc["final_norm"] + d_norm,
             "lm_head_w": acc["lm_head_w"] + d_head}, d_x, val)


def _hidden_states(params, tokens, cfg, matmul):
    """The input of every layer, and the last layer's output."""
    xs = [params["embed"][tokens]]
    for n in range(cfg["num_hidden_layers"]):
        xs.append(_layer_forward(xs[-1], _layer_leaves(params, n),
                                 _freeze(cfg), matmul, is_full(cfg, n)))
    return xs


def logits(params, tokens, cfg, matmul=HIGHEST_MATMUL):
    """tokens (batch, seq) -> (batch, seq, vocab) float32, of ``params`` as
    the model holds them (``at_init`` of the harness's, or trained)."""
    x = _norm(_hidden_states(params, tokens, cfg, matmul)[-1],
              params["final_norm"], cfg["rms_norm_eps"])
    return matmul("bsu,vu->bsv", x, params["lm_head_w"])


def _add_block_grad(acc, params, tokens, labels, cfg, matmul):
    """This block of rows' loss sum; its gradient is added into ``acc``
    (name -> array, updated in place, leaf by leaf)."""
    xs = _hidden_states(params, tokens, cfg, matmul)
    top = ("final_norm", "lm_head_w")
    head, d_x, val = _head_backward(
        {k: acc[k] for k in top}, xs.pop(), params["final_norm"],
        params["lm_head_w"], labels, cfg["rms_norm_eps"], matmul)
    acc.update(head)
    for n in reversed(range(cfg["num_hidden_layers"])):
        pre = f"layer{n}."
        leaves = _layer_leaves(params, n)
        mine, d_x = _layer_backward(
            {k: acc[pre + k] for k in leaves}, xs.pop(), leaves, d_x,
            _freeze(cfg), matmul, is_full(cfg, n))
        acc.update({pre + k: g for k, g in mine.items()})
    acc["embed"] = _embed_backward(acc["embed"], tokens, d_x)
    return val


def loss_and_grad(params, tokens, labels, cfg, matmul, block_rows,
                  rows=None, positions=None):
    """Mean cross-entropy of one batch and its gradient, in blocks of
    ``block_rows`` rows whose sums are added.  ``rows`` restricts the batch
    to its first ``rows`` rows and ``positions`` every row to its first
    ``positions`` positions, the mean taken over what is left (the
    half-batch fault, by rows or, for a batch of one row, by positions)."""
    tokens, labels = tokens[:rows, :positions], labels[:rows, :positions]
    n_rows = tokens.shape[0]
    if n_rows % block_rows:
        raise ValueError(f"{n_rows} rows do not divide into blocks of "
                         f"{block_rows}")
    total = jnp.float32(0)
    grads = jax.tree.map(jnp.zeros_like, params)
    for r in range(0, n_rows, block_rows):
        total = total + _add_block_grad(
            grads, params, tokens[r:r + block_rows],
            labels[r:r + block_rows], cfg, matmul)
    n = tokens.size
    return total / n, _scale(grads, jnp.float32(1.0 / n))


def _adam(w, g, m, v, t, lr, beta1, beta2, eps):
    lr_t = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    return w - lr_t * m / (jnp.sqrt(v) + eps), m, v


# one leaf at a time: the first step must leave the caller's initial
# weights alone; every later one gives its own up
_adam_first = jax.jit(_adam, donate_argnums=(1, 2, 3))
_adam_next = jax.jit(_adam, donate_argnums=(0, 2, 3))


def train_steps(params, tokens, labels, cfg, opt, matmul=HIGHEST_MATMUL,
                block_rows=None, rows=None, positions=None,
                skip_update=False):
    """Follow the first ``tokens.shape[0]`` steps from ``params`` (float32,
    as the harness drew them, left untouched; training starts from
    ``at_init`` of them).

    ``tokens``/``labels`` are (steps, batch, seq) int32.  Returns
    ``(losses, first_grads, m, v, params)``: each step's loss, the first
    step's gradient as one-element arrays that hold each leaf's norm,
    Adam's moments (host arrays) and the parameters after the last step, as
    ``params`` plus the change training made (``_as_given``).  ``rows``,
    ``positions`` and ``skip_update`` plant the faults the benchmark's tests
    need: part of the batch left out, and a step that returns its state
    unchanged.
    """
    block_rows = block_rows or tokens.shape[1]
    given = {k: w for k, w in params.items()
             if _init_scale(cfg, k) is not None}
    trained = at_init(params, cfg)
    device = next(iter(trained.values())).device
    # the moments live on the host except inside a leaf's update
    m = {k: np.zeros(w.shape, np.float32) for k, w in trained.items()}
    v = {k: np.zeros(w.shape, np.float32) for k, w in trained.items()}
    hyper = [jnp.float32(opt[k]) for k in ("learning_rate", "beta1", "beta2",
                                           "epsilon")]
    losses, first = [], None
    for t in range(tokens.shape[0]):
        loss, grads = loss_and_grad(trained, tokens[t], labels[t], cfg,
                                    matmul, block_rows, rows, positions)
        losses.append(loss)
        if first is None:
            first = _leaf_norms(grads)
        if not skip_update:
            step = _adam_first if t == 0 else _adam_next
            for k in list(trained):
                trained[k], m_k, v_k = step(
                    trained[k], grads.pop(k), jax.device_put(m[k], device),
                    jax.device_put(v[k], device), jnp.float32(t + 1), *hyper)
                m[k], v[k] = jax.device_get((m_k, v_k))
                m_k.delete()
                v_k.delete()
        del grads
    return jnp.stack(losses), first, m, v, _as_given(trained, given, cfg)
