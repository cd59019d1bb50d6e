"""Plain reference for the short-convolution + attention MoE training cells:
straight jax.numpy, float32, every matrix product at ``Precision.HIGHEST``,
no kernel, no sort, no grouped product.  Written from the layer equations
of the published ``lfm2_moe`` model type (the Hugging Face
``modeling_lfm2_moe`` for the order of operations):

- block, pre-norm: ``h = x + operator(rms(x))``, ``y = h + ffn(rms(h))``;
  ``rms(x) = x rsqrt(mean(x^2) + eps) w``; the operator of layer ``i`` is
  ``layer_types[i]``; the first ``first_k_dense_replace`` layers here have
  the dense SwiGLU, the rest the MoE; final norm; ``logits = rms(h) E^T``,
  the head tied to the embedding, which has no multiplier.
- short convolution: ``[B | C | x] = x W_in``; ``z = conv(B * x)``, a
  depthwise causal convolution of ``conv_L_cache`` taps without bias,
  computed by ``lax.conv_general_dilated`` with one group a channel (a
  second algorithm beside the program's sum of shifted rows); ``(C * z)
  W_out``.
- attention: q, k, v in ``num_attention_heads`` / ``num_key_value_heads``
  heads (key-value head ``j`` serves query heads ``j g … j g + g - 1``);
  q and k RMS-normed per head; RoPE at ``rope_theta`` on the whole head,
  the pairs (i, i + d/2); causal softmax of ``q k^T / sqrt(d)``; ``W_o``.
- dense feed-forward: ``W_2 (silu(W_1 x) * W_3 x)``.
- MoE: ``s = sigmoid(W_r x)``; the top k of ``s + b``; the weights ``s`` at
  the chosen over their sum + ``router_norm_eps``, times
  ``routed_scaling_factor``; SwiGLU experts; no shared expert.
- loss: mean cross-entropy of the logits against the labels at every
  position.  Adam as ``bert_train`` has it (MXNet's form).

It imports nothing of the program.  The routed part is computed expert by
expert over ALL tokens, each token's output weighted by a dense routing
mask (the weight it gave that expert, zero where it did not choose it): no
pair is formed, nothing is sorted, nothing can be dropped.  Attention takes
512 query rows at a time.

Departures from the published description, shared with the configuration
file: the model's layers ``layers_held`` alone (``num_hidden_layers`` of
them, with the first ``first_k_dense_replace`` of them dense); only the
experts ``experts_held`` exist here, so a pair that falls on another expert
adds nothing (its chip would add it); ``vocab_size`` rows of the tied
embedding; the expert bias is a seeded constant that takes no gradient and
no update; the leaves ``init_scale`` names start at their factor of the
harness's width.

So that the float32 state of 469M parameters fits one chip beside the
caller's copy of the initial weights, gradients are taken a block of rows
at a time and a layer at a time (each layer one program forward and one
backward, which runs the layer again from its kept input), Adam runs a
leaf at a time with its moments waiting in the host's memory, and the
first gradient is returned as its leaves' norms.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.reference.bert_train import FP8_MATMUL, HIGHEST_MATMUL
# the scaffolding that knows no model: a configuration as jit's static
# argument, a layer's leaves, `init_scale`, sums and norms over trees, the
# embedding's scatter-add, Adam a leaf at a time, attention over a slice of
# query rows
from perfbench.reference.mla_moe_train import (
    _as_given, _embed_backward, _freeze, _init_scale, _layer_leaves,
    _leaf_norms, _scale, at_init)
from perfbench.reference.ssm_hybrid_train import (
    _adam_first, _adam_next, _attend)

__all__ = ["param_shapes", "at_init", "train_steps", "logits",
           "short_conv", "attention", "route", "moe_ffn", "kinds",
           "HIGHEST_MATMUL", "FP8_MATMUL"]

_QUERY_ROWS = 512       # rows of queries whose scores are held at once
_FROZEN = ".router_b"   # leaves that take no gradient


def kinds(cfg):
    """The operator of each layer this configuration holds: ``layer_types``
    at the model's layers ``layers_held``."""
    return [cfg["layer_types"][i] for i in cfg["layers_held"]]


def _is_dense(cfg, n):
    return n < cfg["first_k_dense_replace"]


def param_shapes(cfg):
    """name -> (shape, init) in a fixed order; matrices (out, in) like a
    Dense layer's, the taps (channels, taps), the stacked experts (expert,
    in, out).  The expert bias ``router_b`` is drawn like a weight and held
    fixed; the leaves the configuration's ``init_scale`` names start at
    their factor of the harness's width (``at_init``)."""
    u, vocab = cfg["hidden_size"], cfg["vocab_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = u // h
    e_i, held = cfg["moe_intermediate_size"], cfg["experts_held"][1]
    out = {"embed": ((vocab, u), "normal")}
    for n, kind in enumerate(kinds(cfg)):
        pre = f"layer{n}."
        out[pre + "operator_norm"] = ((u,), "ones")
        if kind == "conv":
            out.update({
                pre + "in_proj_w": ((3 * u, u), "normal"),
                pre + "conv_w": ((u, cfg["conv_L_cache"]), "normal"),
                pre + "out_w": ((u, u), "normal")})
        else:
            out.update({
                pre + "q_w": ((h * d, u), "normal"),
                pre + "k_w": ((kv * d, u), "normal"),
                pre + "v_w": ((kv * d, u), "normal"),
                pre + "q_norm": ((d,), "ones"),
                pre + "k_norm": ((d,), "ones"),
                pre + "o_w": ((u, h * d), "normal")})
        out[pre + "ffn_norm"] = ((u,), "ones")
        if _is_dense(cfg, n):
            i = cfg["intermediate_size"]
            out.update({pre + "ffn_gate_w": ((i, u), "normal"),
                        pre + "ffn_up_w": ((i, u), "normal"),
                        pre + "ffn_down_w": ((u, i), "normal")})
        else:
            out.update({
                pre + "router_w": ((cfg["router_width"], u), "normal"),
                pre + "router_b": ((cfg["router_width"],), "normal"),
                pre + "experts_gate": ((held, u, e_i), "normal"),
                pre + "experts_up": ((held, u, e_i), "normal"),
                pre + "experts_down": ((held, e_i, u), "normal")})
    out["final_norm"] = ((u,), "ones")
    return out


# -- the model -----------------------------------------------------------------
# The small functions below are the pieces the planted faults of
# perfbench/tools/faults_conv_moe.py replace.

def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def _conv(v, w):
    """Depthwise causal convolution: v (b, s, c), w (c, taps); ``out[t] =
    sum_j w[:, j] v[t - (taps - 1) + j]``, zero before the row's start."""
    taps, c = w.shape[1], w.shape[0]
    return lax.conv_general_dilated(
        v, w.T[:, None, :], window_strides=(1,), padding=[(taps - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=c,
        precision=lax.Precision.HIGHEST)


def _gate(c, z):
    """The convolution's output gate: ``C * z``."""
    return c * z


def short_conv(x, p, cfg, matmul):
    """x (batch, seq, U) -> (batch, seq, U)."""
    u = cfg["hidden_size"]
    bcx = matmul("bsu,eu->bse", x, p["in_proj_w"])
    b, c, xs = bcx[..., :u], bcx[..., u:2 * u], bcx[..., 2 * u:]
    return matmul("bsu,eu->bse", _gate(c, _conv(b * xs, p["conv_w"])),
                  p["out_w"])


def _qk_norm(x, w, eps):
    """The per-head norm of q and k."""
    return _norm(x, w, eps)


def _rope(x, theta, positions):
    """x (..., seq, d): dims i and i + d/2 turned together by ``position
    * theta^(-2i/d)``."""
    half = x.shape[-1] // 2
    freq = jnp.float32(theta) ** (-jnp.arange(half, dtype=jnp.float32)
                                  / half)
    angle = positions[:, None] * freq[None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _positions(s):
    """Each position's index for RoPE."""
    return jnp.arange(s, dtype=jnp.float32)


def attention(x, p, cfg, matmul):
    """x (batch, seq, U) -> (batch, seq, U)."""
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    b, s, _ = x.shape
    eps, theta, pos = cfg["norm_eps"], cfg["rope_theta"], _positions(s)

    def heads(w, n, norm=None):
        out = matmul("bsu,eu->bse", x, w).reshape(b, s, n, d)
        if norm is not None:
            out = _rope(_qk_norm(out, norm, eps).transpose(0, 2, 1, 3),
                        theta, pos)
            return out
        return out.transpose(0, 2, 1, 3)

    q = heads(p["q_w"], h, p["q_norm"])
    k = jnp.repeat(heads(p["k_w"], kv, p["k_norm"]), h // kv, axis=1)
    v = jnp.repeat(heads(p["v_w"], kv), h // kv, axis=1)
    rows = math.gcd(s, _QUERY_ROWS)
    attend = jax.checkpoint(functools.partial(
        _attend, scale=jnp.float32(1.0 / math.sqrt(d)), matmul=matmul))
    ctx = lax.map(
        lambda r: attend(lax.dynamic_slice_in_dim(q, r, rows, axis=2), k, v,
                         r), jnp.arange(0, s, rows))    # (blocks, b, h, rows, d)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(b, s, h * d)
    return matmul("bse,ue->bsu", ctx, p["o_w"])


def _choice(s, bias):
    """What the top k is taken of: the scores with the expert bias."""
    return s + bias


def _weights_of(s, bias, chosen):
    """The chosen experts' weights before the normalisation: their scores,
    without the bias."""
    return jnp.take_along_axis(s, chosen, axis=1)


def _normalise(picked, eps):
    return picked / (picked.sum(-1, keepdims=True) + eps)


def route(x, p, cfg, matmul):
    """x (N, U) -> (N, E) float32: the weight each token gives each expert
    of the layer, zero where it did not choose it."""
    s = jax.nn.sigmoid(matmul("nu,eu->ne", x, p["router_w"]))
    _, chosen = lax.top_k(_choice(s, p["router_b"]),
                          cfg["num_experts_per_tok"])
    picked = _weights_of(s, p["router_b"], chosen)
    if cfg["norm_topk_prob"]:
        picked = _normalise(picked, jnp.float32(cfg["router_norm_eps"]))
    picked = picked * jnp.float32(cfg["routed_scaling_factor"])
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def _swiglu(x, gate_w, up_w, down_w, matmul):
    h = jax.nn.silu(matmul("nu,iu->ni", x, gate_w)) \
        * matmul("nu,iu->ni", x, up_w)
    return matmul("ni,ui->nu", h, down_w)


def moe_ffn(x, p, cfg, matmul, held=None):
    """The routed feed-forward of x (N, U): the experts ``held = (first,
    count)`` (default: the configuration's ``experts_held``; the leaves
    ``experts_*`` hold exactly those) over all tokens, each token's output
    weighted by the weight it gave that expert."""
    first, count = held or cfg["experts_held"]
    weight = route(x, p, cfg, matmul)

    def add_expert(y, expert):
        gate, up, down, w = expert
        h = jax.nn.silu(matmul("nu,ui->ni", x, gate)) \
            * matmul("nu,ui->ni", x, up)
        return y + w[:, None] * matmul("ni,iu->nu", h, down), None

    # each held expert over ALL tokens, one traced body for all of them
    y, _ = lax.scan(add_expert, jnp.zeros_like(x),
                    (p["experts_gate"], p["experts_up"], p["experts_down"],
                     weight[:, first:first + count].T))
    return y


def _layer(x, p, cfg, matmul, kind, dense):
    eps = cfg["norm_eps"]
    operator = short_conv if kind == "conv" else attention
    h = x + operator(_norm(x, p["operator_norm"], eps), p, cfg, matmul)
    f = _norm(h, p["ffn_norm"], eps).reshape(-1, h.shape[-1])
    if dense:
        f = _swiglu(f, p["ffn_gate_w"], p["ffn_up_w"], p["ffn_down_w"],
                    matmul)
    else:
        f = moe_ffn(f, p, cfg, matmul)
    return h + f.reshape(h.shape)


# One program a kind of layer, forward and backward, and one for the head:
# the layers of one kind share theirs.  The backward of a layer starts from
# the layer's input, kept from the forward pass, and runs the layer again
# inside its vjp.

_STATIC = ("cfg_items", "matmul", "kind", "dense")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _layer_forward(x, p, cfg_items, matmul, kind, dense):
    return _layer(x, p, dict(cfg_items), matmul, kind, dense)


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnums=(0,))
def _layer_backward(acc, x, p, d_out, cfg_items, matmul, kind, dense):
    """(acc + the layer's parameter gradients, the gradient of its input);
    ``acc`` holds the layer's trained leaves and is given up to the sum."""
    _, vjp = jax.vjp(lambda x, p: _layer(x, p, dict(cfg_items), matmul,
                                         kind, dense), x, p)
    d_x, d_p = vjp(d_out)
    return {k: a + d_p[k] for k, a in acc.items()}, d_x


def _logits(x, norm, embed, eps, matmul):
    return matmul("bsu,vu->bsv", _norm(x, norm, eps), embed)


def _head_loss(x, norm, embed, labels, eps, matmul):
    """Sum over the positions of the cross-entropy of the tied head's
    logits."""
    out = _logits(x, norm, embed, eps, matmul)
    picked = jnp.take_along_axis(out, labels[..., None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(out, axis=-1) - picked).sum()


@functools.partial(jax.jit, static_argnames=("eps", "matmul"),
                   donate_argnums=(0,))
def _head_backward(acc, x, norm, embed, labels, eps, matmul):
    val, (d_x, d_norm, d_embed) = jax.value_and_grad(
        _head_loss, argnums=(0, 1, 2))(x, norm, embed, labels, eps, matmul)
    return ({"final_norm": acc["final_norm"] + d_norm,
             "embed": acc["embed"] + d_embed}, d_x, val)


def _layer_args(cfg, n):
    """A layer program's static arguments; the configuration's numbers with
    RoPE's base, which the published file nests, among them."""
    theta = cfg["rope_parameters"]["rope_theta"]
    return dict(cfg_items=_freeze(dict(cfg, rope_theta=theta)),
                kind=kinds(cfg)[n], dense=_is_dense(cfg, n))


def _hidden_states(params, tokens, cfg, matmul):
    """The input of every layer, and the last layer's output."""
    xs = [params["embed"][tokens]]
    for n in range(cfg["num_hidden_layers"]):
        xs.append(_layer_forward(xs[-1], _layer_leaves(params, n),
                                 matmul=matmul, **_layer_args(cfg, n)))
    return xs


def logits(params, tokens, cfg, matmul=HIGHEST_MATMUL):
    """tokens (batch, seq) -> (batch, seq, vocab) float32, of ``params`` as
    the model holds them (``at_init`` of the harness's, or trained)."""
    return _logits(_hidden_states(params, tokens, cfg, matmul)[-1],
                   params["final_norm"], params["embed"], cfg["norm_eps"],
                   matmul)


def _add_block_grad(acc, trained, frozen, tokens, labels, cfg, matmul):
    """This block of rows' loss sum; its gradient is added into ``acc``
    (name -> array, updated in place, leaf by leaf).  The tied embedding
    takes the head's gradient and the gather's."""
    params = {**trained, **frozen}
    xs = _hidden_states(params, tokens, cfg, matmul)
    head, d_x, val = _head_backward(
        {k: acc[k] for k in ("final_norm", "embed")}, xs.pop(),
        params["final_norm"], params["embed"], labels, cfg["norm_eps"],
        matmul)
    acc.update(head)
    for n in reversed(range(cfg["num_hidden_layers"])):
        pre = f"layer{n}."
        mine = {k: acc[pre + k] for k in _layer_leaves(trained, n)}
        mine, d_x = _layer_backward(
            mine, xs.pop(), _layer_leaves(params, n), d_x, matmul=matmul,
            **_layer_args(cfg, n))
        acc.update({pre + k: g for k, g in mine.items()})
    acc["embed"] = _embed_backward(acc["embed"], tokens, d_x)
    return val


def loss_and_grad(trained, frozen, tokens, labels, cfg, matmul, block_rows,
                  rows=None, positions=None):
    """Mean cross-entropy of one batch and its gradient, in blocks of
    ``block_rows`` rows whose sums are added.  ``rows`` keeps the first
    ``rows`` rows, ``positions`` cuts every row to its first ``positions``
    positions; the mean is taken over what is left (the faults that leave
    part of the batch out)."""
    tokens = tokens[:rows, :positions]
    labels = labels[:rows, :positions]
    n_rows = tokens.shape[0]
    if n_rows % block_rows:
        raise ValueError(f"{n_rows} rows do not divide into blocks of "
                         f"{block_rows}")
    total = jnp.float32(0)
    grads = jax.tree.map(jnp.zeros_like, trained)
    for r in range(0, n_rows, block_rows):
        total = total + _add_block_grad(
            grads, trained, frozen, tokens[r:r + block_rows],
            labels[r:r + block_rows], cfg, matmul)
    n = tokens.size
    return total / n, _scale(grads, jnp.float32(1.0 / n))


def train_steps(params, tokens, labels, cfg, opt, matmul=HIGHEST_MATMUL,
                block_rows=None, rows=None, positions=None,
                skip_update=False):
    """Follow the first ``tokens.shape[0]`` steps from ``params`` (float32,
    as the harness drew them, left untouched; training starts from
    ``at_init`` of them).

    ``tokens``/``labels`` are (steps, batch, seq) int32.  Returns
    ``(losses, first_grads, m, v, params)`` over the trained leaves (the
    expert bias is in none of them): each step's loss, the first step's
    gradient as one-element arrays that hold each leaf's norm, Adam's
    moments (host arrays) and the parameters after the last step, as
    ``params`` plus the change training made (``_as_given``).  ``rows``,
    ``positions`` and ``skip_update`` plant the faults the benchmark's
    tests need: part of the batch left out, and a step that returns its
    state unchanged.
    """
    block_rows = block_rows or tokens.shape[1]
    if rows:
        block_rows = min(block_rows, rows)
    given = {k: w for k, w in params.items()
             if _init_scale(cfg, k) is not None}
    params = at_init(params, cfg)
    frozen = {k: w for k, w in params.items() if k.endswith(_FROZEN)}
    trained = {k: w for k, w in params.items() if k not in frozen}
    device = next(iter(trained.values())).device
    # the moments live on the host except inside a leaf's update
    m = {k: np.zeros(w.shape, np.float32) for k, w in trained.items()}
    v = {k: np.zeros(w.shape, np.float32) for k, w in trained.items()}
    hyper = [jnp.float32(opt[k]) for k in ("learning_rate", "beta1", "beta2",
                                           "epsilon")]
    losses, first = [], None
    for t in range(tokens.shape[0]):
        loss, grads = loss_and_grad(trained, frozen, tokens[t], labels[t],
                                    cfg, matmul, block_rows, rows, positions)
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
