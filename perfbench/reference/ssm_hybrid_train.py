"""Plain reference for the hybrid Mamba-2 + attention training cells:
straight jax.numpy, float32, every matrix product at ``Precision.HIGHEST``,
no kernel and no chunk.  Written from the layer equations of the published
``granitemoehybrid`` model type without routed experts (Mamba-2: "Transformers
are SSMs", arXiv:2405.21060; the Hugging Face ``modeling_granitemoehybrid``
for the order of operations):

- embedding ``h_0 = E[tokens] * embedding_multiplier``; block, pre-norm
  with scaled branches: ``a = x + r mixer(rms(x))``, ``y = a + r
  MLP(rms(a))``, ``r`` the ``residual_multiplier``; ``rms(x) = x
  rsqrt(mean(x^2) + eps) w``; the mixer of layer ``i`` is
  ``layer_types[i]``; final norm; ``logits = rms(h) E^T /
  logits_scaling``, the head tied to the embedding.
- MLP: ``[g | u] = x W_in``, ``(silu(g) * u) W_out``.
- Mamba-2: ``[z | xBC | dt] = x W_in``; ``xBC = silu(conv(xBC) + b)``, a
  depthwise causal convolution of ``mamba_d_conv`` taps; x in
  ``mamba_n_heads`` heads of ``mamba_d_head``, B and C in
  ``mamba_n_groups`` groups of ``mamba_d_state`` (group ``j`` serves heads
  ``j r … j r + r - 1``); ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the scan in its QUADRATIC form, independent of the chunked
  one: ``y_i = sum_{j <= i} (C_i . B_j) exp(sum_{j < k <= i} dt_k A) dt_j
  x_j + D x_i`` a head; ``o = rmsnorm(y * silu(z)) w`` over all the inner
  channels (the gate first); ``W_out``.
- attention: q, k, v in ``num_attention_heads`` / ``num_key_value_heads``
  heads (key-value head ``j`` serves query heads ``j g … j g + g - 1``), no
  position encoding, causal softmax of ``q k^T attention_multiplier``,
  ``W_o``.
- loss: mean cross-entropy of the logits against the labels at every
  position.  Adam as ``bert_train`` has it (MXNet's form).

It imports nothing of the program.  The scan and attention take a slice of
query rows at a time (the scan's scores and decays for 512 rows and all
heads are (heads, 512, seq) each).

Departures from the published description, shared with the configuration
file: only ``num_hidden_layers`` layers and ``vocab_size`` rows of the tied
embedding; ``A_log``, ``dt_bias`` and the convolution's taps start at
widths of their own (``init_scale``; ``param_shapes``).

So that the float32 state of 772M parameters fits one chip beside the
caller's copy of the initial weights, gradients are taken a layer at a time
(each layer one program forward and one backward, which runs the layer
again from its kept input), Adam runs a leaf at a time with its moments
waiting in the host's memory, and the first gradient is returned as its
leaves' norms.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.reference.bert_train import FP8_MATMUL, HIGHEST_MATMUL
# the scaffolding that knows no model: a configuration as jit's static
# argument, a layer's leaves, `init_scale`, sums and norms over trees
from perfbench.reference.mla_moe_train import (
    _as_given, _embed_backward, _freeze, _init_scale, _layer_leaves,
    _leaf_norms, _scale, at_init)

__all__ = ["param_shapes", "at_init", "train_steps", "logits", "mamba",
           "attention", "scan", "HIGHEST_MATMUL", "FP8_MATMUL"]

_QUERY_ROWS = 512       # rows of queries whose scores are held at once


def _dims(cfg):
    """(heads, head width, groups, state, taps) of a Mamba-2 mixer."""
    return (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"])


def is_mamba(cfg, n):
    """Whether layer ``n`` runs the Mamba-2 mixer."""
    return cfg["layer_types"][n] == "mamba"


def param_shapes(cfg):
    """name -> (shape, init) in a fixed order; matrices (out, in) like a
    Dense layer's, the convolution (channels, taps).

    The harness draws every 'normal' leaf N(0, 0.02) and knows 'ones' and
    'zeros'; the leaves the configuration's ``init_scale`` names start at
    their factor of that (``at_init``): ``A_log`` and ``dt_bias`` (two
    'ones' leaves) at the constants that set a position's decay and step,
    ``conv_w`` at the width of a default ``Conv1d`` start."""
    u, vocab = cfg["hidden_size"], cfg["vocab_size"]
    h, p, g, n, taps = _dims(cfg)
    inner, conv = h * p, h * p + 2 * g * n
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    out = {"embed": ((vocab, u), "normal")}
    for layer in range(cfg["num_hidden_layers"]):
        pre = f"layer{layer}."
        out[pre + "in_norm"] = ((u,), "ones")
        if is_mamba(cfg, layer):
            out.update({
                pre + "in_proj_w": ((inner + conv + h, u), "normal"),
                pre + "conv_w": ((conv, taps), "normal"),
                pre + "conv_b": ((conv,), "zeros"),
                pre + "A_log": ((h,), "ones"),
                pre + "D": ((h,), "ones"),
                pre + "dt_bias": ((h,), "ones"),
                pre + "gate_norm": ((inner,), "ones"),
                pre + "out_w": ((u, inner), "normal")})
        else:
            out.update({
                pre + "q_w": ((q, u), "normal"),
                pre + "k_w": ((kv, u), "normal"),
                pre + "v_w": ((kv, u), "normal"),
                pre + "o_w": ((u, q), "normal")})
        out.update({
            pre + "post_norm": ((u,), "ones"),
            pre + "mlp_in_w": ((2 * cfg["shared_intermediate_size"], u),
                               "normal"),
            pre + "mlp_out_w": ((u, cfg["shared_intermediate_size"]),
                                "normal")})
    out["final_norm"] = ((u,), "ones")
    return out


# -- the model -----------------------------------------------------------------

def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * w


def _gated_norm(y, z, w, eps):
    """``rmsnorm(y * silu(z)) w`` over all of the last dim: the gate first."""
    return _norm(y * jax.nn.silu(z), w, eps)


def _causal_conv(x, w):
    """x (b, s, c), w (c, taps): ``out[t] = sum_j w[:, j] x[t - (taps - 1)
    + j]``, zero before the row's start."""
    taps, s = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[:, j] for j in range(taps))


def _step(dt, dt_bias):
    """A position's step a head: ``softplus(dt + dt_bias)``."""
    return jax.nn.softplus(dt + dt_bias)


def _write(dt, x):
    """What a position writes, before ``B``: ``dt x``."""
    return dt[..., None] * x


def _reach(i, j):
    """Where position ``i`` reads position ``j``'s write: ``j <= i``."""
    return i >= j


def _skip(D, x):
    """The term that bypasses the state: ``D x``."""
    return D[:, None] * x


def _scan_rows(start, x, dt, cum, B, C_rows, A, matmul):
    """``y_i`` for the query rows ``start … start + rows - 1``, without the
    skip: x (b, s, h, p), dt and cum (b, s, h), B (b, s, g, n), C_rows (b,
    rows, g, n)."""
    rows, s, h = C_rows.shape[1], x.shape[1], x.shape[2]
    cb = matmul("bign,bjgn->bgij", C_rows, B)
    cb = jnp.repeat(cb, h // B.shape[2], axis=1)                # (b,h,i,j)
    i = start + jnp.arange(rows)[:, None]
    j = jnp.arange(s)[None]
    cum_rows = lax.dynamic_slice_in_dim(cum, start, rows, axis=1)
    ratio = jnp.moveaxis(cum_rows, 1, 2)[..., :, None] \
        - jnp.moveaxis(cum, 1, 2)[..., None, :]                 # (b,h,i,j)
    decay = jnp.exp(jnp.where(_reach(i, j), ratio, -jnp.inf))
    return matmul("bhij,bjhp->bihp", cb * decay, _write(dt, x))


def scan(x, dt, A, B, C, D, matmul=HIGHEST_MATMUL):
    """The Mamba-2 scan in its quadratic form: x (b, s, h, p), dt (b, s, h)
    after the softplus, A (h,) below 0, B and C (b, s, g, n), D (h,) ->
    y (b, s, h, p).  The definition the chunked scan is held to."""
    b, s, h, p = x.shape
    cum = jnp.cumsum(dt * A, axis=1)                  # sum_{k <= i} dt_k A
    rows = math.gcd(s, _QUERY_ROWS)
    one = jax.checkpoint(functools.partial(_scan_rows, matmul=matmul))
    y = lax.map(lambda r: one(r, x, dt, cum, B,
                              lax.dynamic_slice_in_dim(C, r, rows, axis=1),
                              A),
                jnp.arange(0, s, rows))               # (blocks, b, rows, h, p)
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, h, p)
    return y + _skip(D, x)


def mamba(x, p, cfg, matmul):
    """x (batch, seq, U) -> (batch, seq, U)."""
    h, hp, g, n, _taps = _dims(cfg)
    b, s, _ = x.shape
    inner, conv = h * hp, h * hp + 2 * g * n
    zxd = matmul("bsu,eu->bse", x, p["in_proj_w"])
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:inner + conv], \
        zxd[..., inner + conv:]
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"]) + p["conv_b"])
    xs = xbc[..., :inner].reshape(b, s, h, hp)
    B = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
    C = xbc[..., inner + g * n:].reshape(b, s, g, n)
    y = scan(xs, _step(dt, p["dt_bias"]), -jnp.exp(p["A_log"]), B, C,
             p["D"], matmul)
    o = _gated_norm(y.reshape(b, s, inner), z, p["gate_norm"],
                    cfg["rms_norm_eps"])
    return matmul("bse,ue->bsu", o, p["out_w"])


def _attend(q, k, v, start, scale, matmul):
    """Causal attention of the query rows ``start…``: q (batch, heads,
    rows, d), k, v (batch, heads, seq, d)."""
    scores = matmul("bhqd,bhkd->bhqk", q, k) * scale
    row = start + jnp.arange(q.shape[2])[:, None]
    scores = jnp.where(row >= jnp.arange(k.shape[2])[None], scores, -jnp.inf)
    return matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def attention(x, p, cfg, matmul):
    """x (batch, seq, U) -> (batch, seq, U)."""
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    b, s, _ = x.shape

    def heads(w, n):
        return matmul("bsu,eu->bse", x, w).reshape(b, s, n, d) \
            .transpose(0, 2, 1, 3)

    q = heads(p["q_w"], h)
    k = jnp.repeat(heads(p["k_w"], kv), h // kv, axis=1)
    v = jnp.repeat(heads(p["v_w"], kv), h // kv, axis=1)
    rows = math.gcd(s, _QUERY_ROWS)
    attend = jax.checkpoint(functools.partial(
        _attend, scale=jnp.float32(cfg["attention_multiplier"]),
        matmul=matmul))
    ctx = lax.map(
        lambda r: attend(lax.dynamic_slice_in_dim(q, r, rows, axis=2), k, v,
                         r), jnp.arange(0, s, rows))    # (blocks, b, h, rows, d)
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(b, s, h * d)
    return matmul("bse,ue->bsu", ctx, p["o_w"])


def _mlp(x, p, cfg, matmul):
    hidden = cfg["shared_intermediate_size"]
    gu = matmul("bsu,iu->bsi", x, p["mlp_in_w"])
    return matmul("bsi,ui->bsu", jax.nn.silu(gu[..., :hidden])
                  * gu[..., hidden:], p["mlp_out_w"])


def _layer(x, p, cfg, matmul, kind):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = mamba if kind == "mamba" else attention
    a = x + r * mixer(_norm(x, p["in_norm"], eps), p, cfg, matmul)
    return a + r * _mlp(_norm(a, p["post_norm"], eps), p, cfg, matmul)


# One program a kind of layer, forward and backward, and one for the head:
# the Mamba-2 layers share theirs.  The backward of a layer starts from the
# layer's input, kept from the forward pass, and runs the layer again
# inside its vjp.

@functools.partial(jax.jit, static_argnames=("cfg_items", "matmul", "kind"))
def _layer_forward(x, p, cfg_items, matmul, kind):
    return _layer(x, p, dict(cfg_items), matmul, kind)


@functools.partial(jax.jit, static_argnames=("cfg_items", "matmul", "kind"),
                   donate_argnums=(0,))
def _layer_backward(acc, x, p, d_out, cfg_items, matmul, kind):
    """(acc + the layer's parameter gradients, the gradient of its input);
    ``acc`` is given up to the sum."""
    _, vjp = jax.vjp(lambda x, p: _layer(x, p, dict(cfg_items), matmul,
                                         kind), x, p)
    d_x, d_p = vjp(d_out)
    return {k: a + d_p[k] for k, a in acc.items()}, d_x


def _logits(x, norm, embed, eps, scaling, matmul):
    return matmul("bsu,vu->bsv", _norm(x, norm, eps), embed) / scaling


def _head_loss(x, norm, embed, labels, eps, scaling, matmul):
    """Sum over the positions of the cross-entropy of the tied head's
    logits."""
    out = _logits(x, norm, embed, eps, scaling, matmul)
    picked = jnp.take_along_axis(out, labels[..., None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(out, axis=-1) - picked).sum()


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "matmul"),
                   donate_argnums=(0,))
def _head_backward(acc, x, norm, embed, labels, eps, scaling, matmul):
    val, (d_x, d_norm, d_embed) = jax.value_and_grad(
        _head_loss, argnums=(0, 1, 2))(x, norm, embed, labels, eps, scaling,
                                       matmul)
    return ({"final_norm": acc["final_norm"] + d_norm,
             "embed": acc["embed"] + d_embed}, d_x, val)


def _kind(cfg, n):
    return "mamba" if is_mamba(cfg, n) else "attention"


def _hidden_states(params, tokens, cfg, matmul):
    """The input of every layer, and the last layer's output."""
    xs = [params["embed"][tokens] * jnp.float32(cfg["embedding_multiplier"])]
    for n in range(cfg["num_hidden_layers"]):
        xs.append(_layer_forward(xs[-1], _layer_leaves(params, n),
                                 _freeze(cfg), matmul, _kind(cfg, n)))
    return xs


def logits(params, tokens, cfg, matmul=HIGHEST_MATMUL):
    """tokens (batch, seq) -> (batch, seq, vocab) float32, of ``params`` as
    the model holds them (``at_init`` of the harness's, or trained)."""
    return _logits(_hidden_states(params, tokens, cfg, matmul)[-1],
                   params["final_norm"], params["embed"], cfg["rms_norm_eps"],
                   cfg["logits_scaling"], matmul)


def _add_block_grad(acc, params, tokens, labels, cfg, matmul):
    """This block of rows' loss sum; its gradient is added into ``acc``
    (name -> array, updated in place, leaf by leaf).  The tied embedding
    takes the head's gradient and the gather's."""
    xs = _hidden_states(params, tokens, cfg, matmul)
    top = ("final_norm", "embed")
    head, d_x, val = _head_backward(
        {k: acc[k] for k in top}, xs.pop(), params["final_norm"],
        params["embed"], labels, cfg["rms_norm_eps"], cfg["logits_scaling"],
        matmul)
    acc.update(head)
    for n in reversed(range(cfg["num_hidden_layers"])):
        pre = f"layer{n}."
        leaves = _layer_leaves(params, n)
        mine, d_x = _layer_backward(
            {k: acc[pre + k] for k in leaves}, xs.pop(), leaves, d_x,
            _freeze(cfg), matmul, _kind(cfg, n))
        acc.update({pre + k: g for k, g in mine.items()})
    acc["embed"] = _embed_backward(
        acc["embed"], tokens, d_x * jnp.float32(cfg["embedding_multiplier"]))
    return val


def loss_and_grad(params, tokens, labels, cfg, matmul, block_rows,
                  positions=None):
    """Mean cross-entropy of one batch and its gradient, in blocks of
    ``block_rows`` rows whose sums are added.  ``positions`` cuts every row
    to its first ``positions`` positions, the mean taken over what is left
    (the half-batch fault of a batch of one row)."""
    tokens, labels = tokens[:, :positions], labels[:, :positions]
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
                block_rows=None, positions=None, skip_update=False):
    """Follow the first ``tokens.shape[0]`` steps from ``params`` (float32,
    as the harness drew them, left untouched; training starts from
    ``at_init`` of them).

    ``tokens``/``labels`` are (steps, batch, seq) int32.  Returns
    ``(losses, first_grads, m, v, params)``: each step's loss, the first
    step's gradient as one-element arrays that hold each leaf's norm,
    Adam's moments (host arrays) and the parameters after the last step, as
    ``params`` plus the change training made (``_as_given``).
    ``positions`` and ``skip_update`` plant the faults the benchmark's
    tests need: part of the batch left out, and a step that returns its
    state unchanged.
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
                                    matmul, block_rows, positions)
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
