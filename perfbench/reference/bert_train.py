"""Plain reference for the BERT training cells: straight jax.numpy, float32.

BERT encoder as Devlin et al. (arXiv:1810.04805) describe it (token and
position embeddings, post-norm blocks of multi-head self-attention and a
GELU feed-forward), the masked-LM cross-entropy taken at every position, and
Adam in the "efficient" form of Kingma & Ba (arXiv:1412.6980, section 2):
alpha_t = alpha * sqrt(1 - beta2^t) / (1 - beta1^t) and
theta <- theta - alpha_t * m / (sqrt(v) + eps), which is how MXNet defines
its Adam.  No kernel, no fusion, no scan over steps; it imports nothing of
the program and is given nothing the program made.

Departures from the published model, shared with the configuration files'
``assumed`` lists: no token-type embedding, no dense+LayerNorm transform in
the MLM head, an untied vocabulary projection, LayerNorm eps as the
configuration states it, no dropout.  The fused q/k/v projection is stored
as one (3U, U) leaf whose rows run [head][q|k|v][head_dim], so that each leaf
here is one leaf of the program; the mathematics is the published one.

Every matrix product goes through ``matmul`` (float32 at
``Precision.HIGHEST``: on a TPU a float32 product is otherwise computed in
bfloat16 passes).  ``FP8_MATMUL`` is the control: the same products with
operands, results and cotangents rounded to 3 mantissa bits (fp8 e4m3's),
the precision below the bfloat16 the configurations state.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax


def param_shapes(cfg):
    """name -> (shape, init) in a fixed order; init is 'normal' (std 0.02, as
    published), 'zeros' or 'ones'.  ``pooler_*`` belong to the published
    model but the MLM loss does not reach them."""
    u, i = cfg["hidden_size"], cfg["intermediate_size"]
    v, p = cfg["vocab_size"], cfg["max_position_embeddings"]
    out = {"word_embed": ((v, u), "normal"), "pos_embed": ((p, u), "normal"),
           "embed_ln_g": ((u,), "ones"), "embed_ln_b": ((u,), "zeros")}
    for n in range(cfg["num_hidden_layers"]):
        pre = f"layer{n}."
        out.update({
            pre + "qkv_w": ((3 * u, u), "normal"),
            pre + "qkv_b": ((3 * u,), "zeros"),
            pre + "proj_w": ((u, u), "normal"),
            pre + "proj_b": ((u,), "zeros"),
            pre + "ln1_g": ((u,), "ones"), pre + "ln1_b": ((u,), "zeros"),
            pre + "ffn1_w": ((i, u), "normal"),
            pre + "ffn1_b": ((i,), "zeros"),
            pre + "ffn2_w": ((u, i), "normal"),
            pre + "ffn2_b": ((u,), "zeros"),
            pre + "ln2_g": ((u,), "ones"), pre + "ln2_b": ((u,), "zeros")})
    out.update({"pooler_w": ((u, u), "normal"), "pooler_b": ((u,), "zeros"),
                "decoder_w": ((v, u), "normal"),
                "decoder_b": ((v,), "zeros")})
    return out


# -- the two precisions --------------------------------------------------------

def _highest(spec, a, b):
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _round_mantissa3(x):
    """x with its float32 mantissa rounded to 3 bits (nearest, ties to
    even).  The exponent keeps float32's range: fp8's narrow one, which a
    real fp8 path covers with scales, is not made the limit."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    keep = jnp.uint32(0xFFF00000)           # sign, exponent, 3 mantissa bits
    half = jnp.uint32(0x0007FFFF) + ((bits >> 20) & jnp.uint32(1))
    return lax.bitcast_convert_type((bits + half) & keep, jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8(spec, a, b):
    """A matrix product with fp8 operands and an fp8 result, accumulated in
    float32, forward and backward: where the program holds activations and
    gradients in bfloat16, this holds them in 3 mantissa bits."""
    return _round_mantissa3(
        _highest(spec, _round_mantissa3(a), _round_mantissa3(b)))


def _fp8_fwd(spec, a, b):
    qa, qb = _round_mantissa3(a), _round_mantissa3(b)
    return _round_mantissa3(_highest(spec, qa, qb)), (qa, qb)


def _fp8_bwd(spec, res, g):
    _, vjp = jax.vjp(lambda x, y: _highest(spec, x, y), *res)
    da, db = vjp(_round_mantissa3(g))
    return _round_mantissa3(da), _round_mantissa3(db)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


HIGHEST_MATMUL = _highest
FP8_MATMUL = _fp8


# -- the model -----------------------------------------------------------------

def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def loss_sum(params, tokens, labels, cfg, matmul):
    """Sum over the rows' positions of the MLM cross-entropy."""
    heads = cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    b, s = tokens.shape
    u = cfg["hidden_size"]
    d = u // heads
    x = params["word_embed"][tokens] + params["pos_embed"][:s][None]
    x = _layer_norm(x, params["embed_ln_g"], params["embed_ln_b"], eps)
    for n in range(cfg["num_hidden_layers"]):
        p = {k.split(".", 1)[1]: w for k, w in params.items()
             if k.startswith(f"layer{n}.")}
        qkv = matmul("bsu,eu->bse", x, p["qkv_w"]) + p["qkv_b"]
        qkv = qkv.reshape(b, s, heads, 3, d)
        q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
        scores = matmul("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = matmul("bhqk,bkhd->bqhd", probs, v).reshape(b, s, u)
        att = matmul("bsu,eu->bse", ctx, p["proj_w"]) + p["proj_b"]
        x = _layer_norm(x + att, p["ln1_g"], p["ln1_b"], eps)
        h = matmul("bsu,iu->bsi", x, p["ffn1_w"]) + p["ffn1_b"]
        h = jax.nn.gelu(h, approximate=False)
        h = matmul("bsi,ui->bsu", h, p["ffn2_w"]) + p["ffn2_b"]
        x = _layer_norm(x + h, p["ln2_g"], p["ln2_b"], eps)
    logits = matmul("bsu,vu->bsv", x, params["decoder_w"]) \
        + params["decoder_b"]
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (jax.nn.logsumexp(logits, axis=-1) - picked).sum()


@functools.partial(jax.jit, static_argnames=("cfg_items", "matmul"))
def _block_grad(params, tokens, labels, cfg_items, matmul):
    return jax.value_and_grad(loss_sum)(params, tokens, labels,
                                        dict(cfg_items), matmul)


def loss_and_grad(params, tokens, labels, cfg, matmul, block_rows,
                  rows=None):
    """Mean cross-entropy of one batch and its gradient, computed in blocks
    of ``block_rows`` rows whose sums are added, so that the float32
    activations of a full batch need not fit.  ``rows`` restricts the batch
    to its first ``rows`` rows, the mean taken over them alone (the
    half-batch fault)."""
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float))))
    if rows is not None:
        tokens, labels = tokens[:rows], labels[:rows]
    n_rows = tokens.shape[0]
    if n_rows % block_rows:
        raise ValueError(f"{n_rows} rows do not divide into blocks of "
                         f"{block_rows}")
    total, grads = jnp.float32(0), None
    for r in range(0, n_rows, block_rows):
        val, g = _block_grad(params, tokens[r:r + block_rows],
                             labels[r:r + block_rows], cfg_items, matmul)
        total = total + val
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = tokens.size
    return total / n, jax.tree.map(lambda g: g / n, grads)


@jax.jit
def adam_update(params, grads, m, v, t, lr, beta1, beta2, eps):
    lr_t = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
    m = jax.tree.map(lambda a, g: beta1 * a + (1 - beta1) * g, m, grads)
    v = jax.tree.map(lambda a, g: beta2 * a + (1 - beta2) * g * g, v, grads)
    params = jax.tree.map(lambda w, a, c: w - lr_t * a / (jnp.sqrt(c) + eps),
                          params, m, v)
    return params, m, v


def train_steps(params, tokens, labels, cfg, opt, matmul=HIGHEST_MATMUL,
                block_rows=None, rows=None, skip_update=False):
    """Follow the first ``tokens.shape[0]`` steps from ``params`` (float32).

    ``tokens``/``labels`` are (steps, batch, seq) int32.  Returns
    ``(losses, first_grads, m, v, params)``: each step's loss, the first
    step's gradient, Adam's moments and the parameters after the last step.
    ``rows`` and ``skip_update`` plant the faults the benchmark's tests need:
    part of the batch left out, and a step that returns its state unchanged.
    """
    block_rows = block_rows or tokens.shape[1]
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t in range(tokens.shape[0]):
        loss, grads = loss_and_grad(params, tokens[t], labels[t], cfg,
                                    matmul, block_rows, rows)
        losses.append(loss)
        if first is None:
            first = grads
        if not skip_update:
            params, m, v = adam_update(
                params, grads, m, v, jnp.float32(t + 1),
                jnp.float32(opt["learning_rate"]), jnp.float32(opt["beta1"]),
                jnp.float32(opt["beta2"]), jnp.float32(opt["epsilon"]))
    return jnp.stack(losses), first, m, v, params
