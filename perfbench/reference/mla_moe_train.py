"""Plain reference for the MLA + dropless-MoE training cells: straight
jax.numpy, float32, every matrix product at ``Precision.HIGHEST``, no
kernel, no sort, no grouped product.  Written from the layer equations of
the published ``deepseek_v3`` model type (DeepSeek-V3 technical report,
arXiv:2412.19437, sections 2.1.1 and 2.1.2; the Hugging Face
``modeling_deepseek_v3`` for the order of the weights' rows):

- block, pre-norm: ``x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x))``; the first
  ``first_k_dense_replace`` layers have a dense SwiGLU, the rest the MoE;
  final RMSNorm, untied head.
- MLA, ``q_lora_rank`` null: ``q = W_q h`` in heads of ``[nope | rope]``;
  ``[c | k_r] = W_kva h``; ``c <- RMSNorm(c)``; per head ``[k_nope | v] =
  W_kvb c``; RoPE on ``q_rope`` and ``k_r`` (one for all heads); causal
  softmax of ``q k^T / sqrt(nope + rope)`` times v; ``W_o``.
- MoE: ``s = sigmoid(W_g h)``; the top k of ``s + b``; weights ``s`` at the
  chosen, over their sum + 1e-20, times ``routed_scaling_factor``; SwiGLU
  experts; plus the shared experts as one SwiGLU on every token.
- loss: mean cross-entropy of the logits against the labels at every
  position.  Adam as ``bert_train`` has it (MXNet's form).

It imports nothing of the program.  The routed part is computed expert by
expert over ALL tokens, each token's output weighted by the weight it gave
that expert (zero where it did not choose it): no pair is formed, so nothing
can be dropped.

Departures from the published description, shared with the configuration
file: only the experts ``experts_held`` exist here, so a pair that falls on
another expert adds nothing (its chip would add it); only
``num_hidden_layers`` layers and ``vocab_size`` rows of embedding and head;
the router's bias ``e_score_correction_bias`` is a seeded constant that
takes no gradient and no update; the attention's output projection starts
at an eighth of the other weights' width (``param_shapes`` says why); RoPE
turns each pair (2i, 2i+1) in place,
where the published code first moves the pairs to [evens | odds]: the same
permutation of q's and k's rope dims, which their product does not see.

So that the float32 state of 576M parameters fits one chip beside the
caller's copy of the initial weights (five copies in all, 11.5 GB),
gradients are taken a block of rows at a time and added into one donated
accumulator, a layer at a time (each layer one program forward and one
backward, which runs the layer again from its kept input), attention is
computed a slice of query rows at a time,
Adam's buffers are donated, Adam's moments wait in the host's memory while
gradients are taken, and the first gradient is returned as its leaves'
norms (one number a leaf: all that ``compare.leaf_norms`` reads of it).
"""

import functools

import jax
import numpy as np
import jax.numpy as jnp
from jax import lax

from perfbench.reference.bert_train import FP8_MATMUL, HIGHEST_MATMUL

__all__ = ["param_shapes", "at_init", "train_steps", "logits", "moe_ffn",
           "HIGHEST_MATMUL", "FP8_MATMUL"]

_QUERY_ROWS = 1024      # rows of queries whose scores are held at once
_FROZEN = ".router_b"   # leaves that take no gradient


def _dims(cfg):
    heads = cfg["num_attention_heads"]
    return (cfg["hidden_size"], heads, cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def param_shapes(cfg):
    """name -> (shape, init) in a fixed order; matrices (out, in) like a
    Dense layer's, the stacked experts (expert, in, out).

    ``o_w`` is drawn like the others and starts at ``init_scale`` of it
    (the configuration's: 1/8, so N(0, 0.0025); ``at_init``).  With seeded
    N(0, 0.02) weights everywhere, attention over uniform tokens is an
    average, the average is nearly one vector for all late positions of a
    row and as large as the tokens' own part of the residual, and deeper
    layers feed on it: the router then sends most of a row's tokens to the
    few experts whose rows point along that vector, other experts for every
    row and seed (read on the chip so: one held expert with 7,500 of a
    step's 8,192 tokens, 0.64 pairs a token where an even router gives
    0.75).  A trained model's router is balanced, by the bias rule this
    benchmark does not run.  A residual branch's output projection that
    starts narrower is common practice (GPT-2 divides its width by the root
    of the number of residual branches: 1/9.8 at the published 48 layers);
    an eighth is the nearest power of two, which bfloat16 and float32 hold
    alike.  The branch stays open: every projection of the attention gets a
    gradient from the first step on, so the comparison sees the attention's
    backward as well as its forward."""
    u, h, nope, rope, dv, rank = _dims(cfg)
    v, i = cfg["vocab_size"], cfg["intermediate_size"]
    e_i, held = cfg["moe_intermediate_size"], cfg["experts_held"][1]
    s_i = cfg["n_shared_experts"] * e_i
    out = {"embed": ((v, u), "normal")}
    for n in range(cfg["num_hidden_layers"]):
        pre = f"layer{n}."
        out.update({
            pre + "attn_norm": ((u,), "ones"),
            pre + "q_w": ((h * (nope + rope), u), "normal"),
            pre + "kv_a_w": ((rank + rope, u), "normal"),
            pre + "kv_a_norm": ((rank,), "ones"),
            pre + "kv_b_w": ((h * (nope + dv), rank), "normal"),
            pre + "o_w": ((u, h * dv), "normal"),
            pre + "ffn_norm": ((u,), "ones")})
        if n < cfg["first_k_dense_replace"]:
            out.update({pre + "mlp_gate_w": ((i, u), "normal"),
                        pre + "mlp_up_w": ((i, u), "normal"),
                        pre + "mlp_down_w": ((u, i), "normal")})
        else:
            out.update({
                pre + "router_w": ((cfg["router_width"], u), "normal"),
                pre + "router_b": ((cfg["router_width"],), "normal"),
                pre + "experts_gate": ((held, u, e_i), "normal"),
                pre + "experts_up": ((held, u, e_i), "normal"),
                pre + "experts_down": ((held, e_i, u), "normal"),
                pre + "shared_gate_w": ((s_i, u), "normal"),
                pre + "shared_up_w": ((s_i, u), "normal"),
                pre + "shared_down_w": ((u, s_i), "normal")})
    out.update({"final_norm": ((u,), "ones"), "lm_head_w": ((v, u), "normal")})
    return out


def _init_scale(cfg, leaf):
    return cfg.get("init_scale", {}).get(leaf.rsplit(".", 1)[-1])


def at_init(params, cfg):
    """The seeded values as training starts from them: the leaves the
    configuration's ``init_scale`` names times their factor (the harness
    draws every 'normal' leaf at one width)."""
    return {k: w if _init_scale(cfg, k) is None
            else w * jnp.float32(_init_scale(cfg, k))
            for k, w in params.items()}


def _as_given(trained, given, cfg):
    """``trained`` moved to where the harness's own values stand: the
    caller takes the parameters' change as ``returned - given``, and for a
    leaf that started at ``scale * given`` that is the change it made."""
    return {k: w if _init_scale(cfg, k) is None
            else w + given[k] * jnp.float32(1.0 - _init_scale(cfg, k))
            for k, w in trained.items()}


# -- the model -----------------------------------------------------------------

def _rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (..., seq, d): each pair (2i, 2i+1) of the last dim turned by
    position * theta^(-2i/d), as one complex product."""
    seq, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    z = lax.complex(pairs[..., 0], pairs[..., 1]) \
        * lax.complex(jnp.cos(angle), jnp.sin(angle))
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def _swiglu(x, gate_w, up_w, down_w, matmul):
    h = jax.nn.silu(matmul("nu,iu->ni", x, gate_w)) \
        * matmul("nu,iu->ni", x, up_w)
    return matmul("ni,ui->nu", h, down_w)


def _attend(q, k, v, start, matmul):
    """Causal attention of the query rows ``start…`` of the sequences: q
    (batch, heads, rows, d), k (batch, heads, seq, d), v (batch, heads, seq,
    dv)."""
    scores = matmul("bhqd,bhkd->bhqk", q, k) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    row = start + jnp.arange(q.shape[2])[:, None]
    scores = jnp.where(row >= jnp.arange(k.shape[2])[None], scores, -jnp.inf)
    return matmul("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def _mla(x, p, cfg, matmul):
    """x (batch, seq, U) -> (batch, seq, U)."""
    u, h, nope, rope, dv, rank = _dims(cfg)
    b, s, _ = x.shape
    theta = jnp.float32(cfg["rope_theta"])
    q = matmul("bsu,eu->bse", x, p["q_w"]).reshape(b, s, h, nope + rope)
    q = q.transpose(0, 2, 1, 3)                             # (b, h, s, d)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv_a = matmul("bsu,eu->bse", x, p["kv_a_w"])
    latent = _rms_norm(kv_a[..., :rank], p["kv_a_norm"], cfg["rms_norm_eps"])
    k_r = _rope(kv_a[..., rank:], theta)                    # (b, s, rope)
    kv = matmul("bsr,er->bse", latent, p["kv_b_w"]).reshape(b, s, h,
                                                            nope + dv)
    kv = kv.transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, None], (b, h, s, rope))], -1)
    v = kv[..., nope:]
    # a slice of query rows at a time (one traced body for all slices),
    # its scores made again in the backward: only one slice's are ever held
    rows = min(_QUERY_ROWS, s)
    attend = jax.checkpoint(functools.partial(_attend, matmul=matmul))
    out = lax.map(
        lambda r: attend(lax.dynamic_slice_in_dim(q, r, rows, axis=2), k, v,
                         r), jnp.arange(0, s, rows))    # (slices, b, h, rows, dv)
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, s, h * dv)
    return matmul("bse,ue->bsu", out, p["o_w"])


def route(x, p, cfg, matmul):
    """x (N, U) -> (N, E) float32: the weight each token gives each expert
    of the layer, zero where it did not choose it."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(matmul("nu,eu->ne", x, p["router_w"]))
    _, chosen = lax.top_k(s + p["router_b"], k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    picked = picked * jnp.float32(cfg["routed_scaling_factor"])
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def moe_ffn(x, p, cfg, matmul, held=None, shared=True):
    """The MoE feed-forward of x (N, U): the experts ``held = (first,
    count)`` (default: the configuration's ``experts_held``; the leaves
    ``experts_*`` hold exactly those) over all tokens, each token's output
    weighted by the weight it gave that expert, plus the shared experts."""
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
    if shared:
        y = y + _swiglu(x, p["shared_gate_w"], p["shared_up_w"],
                        p["shared_down_w"], matmul)
    return y


def _ffn_input(x, p, cfg, matmul):
    """(x + MLA(RMSNorm(x)), the feed-forward's input as (tokens, U))."""
    eps = cfg["rms_norm_eps"]
    x = x + _mla(_rms_norm(x, p["attn_norm"], eps), p, cfg, matmul)
    return x, _rms_norm(x, p["ffn_norm"], eps).reshape(-1, x.shape[-1])


def _layer(x, p, cfg, matmul, dense):
    x, h = _ffn_input(x, p, cfg, matmul)
    if dense:
        h = _swiglu(h, p["mlp_gate_w"], p["mlp_up_w"], p["mlp_down_w"],
                    matmul)
    else:
        h = moe_ffn(h, p, cfg, matmul)
    return x + h.reshape(x.shape)


def _freeze(cfg):
    """The configuration's numbers as a hashable, for jit's static
    argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, list))
                        and all(isinstance(x, (int, float))
                                for x in (v if isinstance(v, list) else [v]))))


# One program a layer, forward and backward, and one for the head: the four
# expert layers share theirs.  The whole loss as one program takes seven
# minutes to compile at the published widths and plans 2.5 GB of
# temporaries; these take a few tens of seconds and hold one layer's.  The
# backward of a layer starts from the layer's input, kept from the forward
# pass, and runs the layer again inside its vjp.

@functools.partial(jax.jit, static_argnames=("cfg_items", "matmul", "dense"))
def _layer_forward(x, p, cfg_items, matmul, dense):
    return _layer(x, p, dict(cfg_items), matmul, dense)


@functools.partial(jax.jit, static_argnames=("cfg_items", "matmul", "dense"),
                   donate_argnums=(0,))
def _layer_backward(acc, x, p, d_out, cfg_items, matmul, dense):
    """(acc + the layer's parameter gradients, the gradient of its input);
    ``acc`` holds the layer's trained leaves and is given up to the sum."""
    _, vjp = jax.vjp(lambda x, p: _layer(x, p, dict(cfg_items), matmul,
                                         dense), x, p)
    d_x, d_p = vjp(d_out)
    return {k: a + d_p[k] for k, a in acc.items()}, d_x


def _head_loss(x, norm, head_w, labels, eps, matmul):
    """Sum over the positions of the cross-entropy of the head's logits."""
    out = matmul("bsu,vu->bsv", _rms_norm(x, norm, eps), head_w)
    picked = jnp.take_along_axis(out, labels[..., None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(out, axis=-1) - picked).sum()


@functools.partial(jax.jit, static_argnames=("eps", "matmul"),
                   donate_argnums=(0,))
def _head_backward(acc, x, norm, head_w, labels, eps, matmul):
    val, (d_x, d_norm, d_head) = jax.value_and_grad(
        _head_loss, argnums=(0, 1, 2))(x, norm, head_w, labels, eps, matmul)
    return ({"final_norm": acc["final_norm"] + d_norm,
             "lm_head_w": acc["lm_head_w"] + d_head}, d_x, val)


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_backward(acc, tokens, d_x):
    return acc.at[tokens].add(d_x)


def _layer_leaves(tree, n):
    pre = f"layer{n}."
    return {k[len(pre):]: w for k, w in tree.items() if k.startswith(pre)}


def _hidden_states(params, tokens, cfg, matmul):
    """The input of every layer, and the last layer's output."""
    xs = [params["embed"][tokens]]
    for n in range(cfg["num_hidden_layers"]):
        xs.append(_layer_forward(xs[-1], _layer_leaves(params, n),
                                 _freeze(cfg), matmul,
                                 n < cfg["first_k_dense_replace"]))
    return xs


def logits(params, tokens, cfg, matmul=HIGHEST_MATMUL):
    """tokens (batch, seq) -> (batch, seq, vocab) float32, of ``params`` as
    the model holds them (``at_init`` of the harness's, or trained)."""
    x = _rms_norm(_hidden_states(params, tokens, cfg, matmul)[-1],
                  params["final_norm"], cfg["rms_norm_eps"])
    return matmul("bsu,vu->bsv", x, params["lm_head_w"])


def _add_block_grad(acc, trained, frozen, tokens, labels, cfg, matmul):
    """This block of rows' loss sum; its gradient is added into ``acc``
    (name -> array, updated in place, leaf by leaf)."""
    params = {**trained, **frozen}
    xs = _hidden_states(params, tokens, cfg, matmul)
    top = ("final_norm", "lm_head_w")
    head, d_x, val = _head_backward(
        {k: acc[k] for k in top}, xs.pop(), params["final_norm"],
        params["lm_head_w"], labels, cfg["rms_norm_eps"], matmul)
    acc.update(head)
    for n in reversed(range(cfg["num_hidden_layers"])):
        pre = f"layer{n}."
        mine = {k: acc[pre + k] for k in _layer_leaves(trained, n)}
        mine, d_x = _layer_backward(
            mine, xs.pop(), _layer_leaves(params, n), d_x, _freeze(cfg),
            matmul, n < cfg["first_k_dense_replace"])
        acc.update({pre + k: g for k, g in mine.items()})
    acc["embed"] = _embed_backward(acc["embed"], tokens, d_x)
    return val


def loss_and_grad(trained, frozen, tokens, labels, cfg, matmul, block_rows,
                  rows=None):
    """Mean cross-entropy of one batch and its gradient, in blocks of
    ``block_rows`` rows whose sums are added.  ``rows`` restricts the batch
    to its first ``rows`` rows, the mean taken over them alone (the
    half-batch fault)."""
    if rows is not None:
        tokens, labels = tokens[:rows], labels[:rows]
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


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(tree, by):
    return jax.tree.map(lambda g: g * by, tree)


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v)))[None]
            for k, v in tree.items()}


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


def _to_host(tree):
    """The tree as numpy arrays, its device buffers freed at once."""
    out = jax.device_get(tree)
    for leaf in tree.values():
        leaf.delete()
    return out


def train_steps(params, tokens, labels, cfg, opt, matmul=HIGHEST_MATMUL,
                block_rows=None, rows=None, skip_update=False):
    """Follow the first ``tokens.shape[0]`` steps from ``params`` (float32,
    as the harness drew them, left untouched; training starts from
    ``at_init`` of them).

    ``tokens``/``labels`` are (steps, batch, seq) int32.  Returns
    ``(losses, first_grads, m, v, params)`` over the trained leaves (the
    router's bias is in none of them): each step's loss, the first step's
    gradient as one-element arrays that hold each leaf's norm, Adam's
    moments and the parameters after the last step, as ``params`` plus the
    change training made (``_as_given``).  ``rows`` and
    ``skip_update`` plant the faults the benchmark's tests need: part of the
    batch left out, and a step that returns its state unchanged.
    """
    block_rows = block_rows or tokens.shape[1]
    given = {k: w for k, w in params.items()
             if _init_scale(cfg, k) is not None}
    params = at_init(params, cfg)
    frozen = {k: w for k, w in params.items() if k.endswith(_FROZEN)}
    trained = {k: w for k, w in params.items() if k not in frozen}
    device = next(iter(trained.values())).device
    # the moments live on the host except inside an update
    m = v = {k: np.zeros(w.shape, np.float32) for k, w in trained.items()}
    hyper = [jnp.float32(opt[k]) for k in ("learning_rate", "beta1", "beta2",
                                           "epsilon")]
    losses, first = [], None
    for t in range(tokens.shape[0]):
        loss, grads = loss_and_grad(trained, frozen, tokens[t], labels[t],
                                    cfg, matmul, block_rows, rows)
        losses.append(loss)
        if first is None:
            first = _leaf_norms(grads)
        if not skip_update:
            step = _adam_first if t == 0 else _adam_next
            trained, m, v = step(trained, grads, jax.device_put(m, device),
                                 jax.device_put(v, device),
                                 jnp.float32(t + 1), *hyper)
            m, v = _to_host(m), _to_host(v)
        del grads
    return jnp.stack(losses), first, m, v, _as_given(trained, given, cfg)
