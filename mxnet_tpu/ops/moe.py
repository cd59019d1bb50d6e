"""Dropless mixture-of-experts ops: a top-k router over all experts of a
layer (sigmoid scores with a choice-only bias, DeepSeek-V3's, or softmax
scores with none, ``qwen3_next``'s), and the routed feed-forward of the
experts THIS device holds.

The layer expert parallelism needs, run without its exchange: the router
scores every token against all E experts of the layer; the device holds the
``count`` experts from ``first`` on; the (token, choice) pairs that fall on
them are sorted by expert, their rows gathered, three grouped matrix
products run over the held experts (``jax.lax.ragged_dot``: the TPU
compiler lowers it to Mosaic kernels of its own that visit only the row
tiles the group sizes cover, under ``jax_enable_x64`` too, where the
megablox ``gmm`` that ships with JAX does not lower), and the weighted
results gathered back per token.  Nothing has a capacity and no pair is
dropped: the pair buffers can hold all N*k rows, the worst case, with the
pairs of absent experts sorted last, where no grouped product touches them.
A pair on an absent expert contributes nothing here (its expert's device
would add it).

Both moves of rows are gathers in both directions (a permutation's
transpose is the inverse permutation), so no scatter runs forward or
backward, and the whole routed feed-forward is recomputed in the backward
(``jax.checkpoint``): the worst-case pair buffers are temporaries of one
layer at a time, not residuals of every layer.

Region scopes inside ``contrib.moe_experts`` (the ops run under their
blocks' scopes ``router`` and ``experts``): ``dispatch`` (sort, gather),
``grouped`` (the three products and the gate), ``combine`` (gather back,
weights).
"""

from __future__ import annotations

import functools

from .. import regions
from .registry import register


@register("contrib.moe_router", num_outputs=2)
def _moe_router(x, weight, bias=None, k=1, scale=1.0, normalize=True,
                score="sigmoid"):
    """Top-k routing over all E experts of a layer.  ``x`` (N, U);
    ``weight`` (E, U), the layout of a Dense weight.  ``score``:
    ``"sigmoid"`` (DeepSeek-V3's ``noaux_tc`` with one group) or
    ``"softmax"`` over the E logits (``qwen3_next``'s).  ``bias`` (E,) or
    None: added to the scores for the CHOICE alone.  Returns ``(weights
    (N, k) float32, experts (N, k) int32)``: the scores (without the bias)
    at the chosen experts, divided by their sum + 1e-20 if ``normalize``,
    times ``scale``.  The product runs in float32 at the highest precision
    whatever the activations' type; of equal scores the lower expert
    wins."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    logits = jnp.einsum("nu,eu->ne", x.astype(f32), weight.astype(f32),
                        precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"moe_router score {score!r}: want sigmoid|softmax")
    _, experts = jax.lax.top_k(
        scores if bias is None else scores + bias.astype(f32), k)
    chosen = jnp.take_along_axis(scores, experts, axis=1)
    if normalize:
        chosen = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20)
    return chosen * f32(scale), experts.astype(jnp.int32)


@functools.cache
def _pair_moves():
    """``(take, untake)``: the two moves of rows between tokens and the pair
    buffer, each with its transpose written as a gather too (a permutation's
    transpose is its inverse).

    ``take(x, token, inv, live_of)``: ``rows[p] = x[token[p]]``.  ``inv``
    (N, k) holds the buffer row of each token's k pairs and ``live_of``
    (N, k) which of them fell on a held expert: the cotangent's other rows
    come from grouped products that never wrote them, and count as zero.

    ``untake(rows, order, inv)``: ``out[n, j] = rows[inv[n, j]]``, transpose
    ``d_rows[p] = d_out.reshape(N * k, U)[order[p]]`` (``order[p]`` is the
    pair that sits in buffer row p; the cotangent of a pair that is not
    live is zero already, through its weight)."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def take(x, token, inv, live_of):
        return x[token]

    def take_fwd(x, token, inv, live_of):
        return x[token], (inv, live_of)

    def take_bwd(res, d_rows):
        inv, live_of = res
        n, k = inv.shape
        d_x = d_rows[inv.reshape(-1)].reshape(n, k, -1)
        d_x = jnp.where(live_of[:, :, None], d_x.astype(jnp.float32),
                        jnp.float32(0.0))
        return d_x.sum(axis=1).astype(d_rows.dtype), None, None, None

    @jax.custom_vjp
    def untake(rows, order, inv):
        n, k = inv.shape
        return rows[inv.reshape(-1)].reshape(n, k, -1)

    def untake_fwd(rows, order, inv):
        return untake(rows, order, inv), (order, inv.shape)

    def untake_bwd(res, d_out):
        order, (n, k) = res
        return d_out.reshape(n * k, -1)[order], None, None

    take.defvjp(take_fwd, take_bwd)
    untake.defvjp(untake_fwd, untake_bwd)
    return take, untake


@register("contrib.moe_experts", num_outputs=2)
def _moe_experts(x, weights, experts, w_gate, w_up, w_down, first=0):
    """The routed SwiGLU experts held here, dropless.  ``x`` (N, U);
    ``weights`` (N, k) float32 and ``experts`` (N, k) int32 from
    ``contrib.moe_router``, expert ids over the whole layer; ``w_gate``,
    ``w_up`` (count, U, I) and ``w_down`` (count, I, U): the experts
    ``first … first + count - 1``.  Returns ``(y (N, U) in x's type,
    tokens (count,) int32)``: the weighted sum over each token's pairs that
    fell on a held expert (zero for a token with none), and how many pairs
    fell on each held expert."""
    import jax
    import jax.numpy as jnp
    take, untake = _pair_moves()
    n, k = experts.shape
    held_n = w_gate.shape[0]
    i32 = jnp.int32

    with regions.scope("dispatch"):
        local = experts.reshape(-1) - i32(first)                # (P,)
        key = jnp.where((local >= 0) & (local < held_n), local, i32(held_n))
        order = jnp.argsort(key, stable=True).astype(i32)       # (P,)
        at = jnp.arange(order.shape[0], dtype=i32)
        inv = jnp.zeros_like(order).at[order].set(
            at, unique_indices=True).reshape(n, k)
        # where each held expert's pairs start among the sorted keys
        starts = jnp.searchsorted(key[order], jnp.arange(held_n + 1,
                                                         dtype=i32))
        tokens = jnp.diff(starts).astype(i32)
        total = starts[held_n].astype(i32)

    # all held pairs lie in the first ``total`` sorted positions
    live = at < total                                           # (P,)
    live_of = inv < total                                       # (N, k)

    @jax.checkpoint
    def routed(x, weights, w_gate, w_up, w_down):
        with regions.scope("dispatch"):
            rows_x = take(x, order // i32(k), inv, live_of)
        with regions.scope("grouped"):
            gate = jax.lax.ragged_dot(rows_x, w_gate, tokens)
            up = jax.lax.ragged_dot(rows_x, w_up, tokens)
            out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down, tokens)
        with regions.scope("combine"):
            # rows past the held pairs were never written: mask them
            out = jnp.where(live[:, None], out, jnp.zeros((), out.dtype))
            per_pair = untake(out, order, inv)
            w = jnp.where(live_of, weights, jnp.float32(0.0))
            return jnp.einsum(
                "nk,nku->nu", w,
                per_pair.astype(jnp.float32)).astype(x.dtype)

    return routed(x, weights, w_gate, w_up, w_down), tokens
