"""Dropless mixture-of-experts ops: a top-k router over all experts of a
layer (sigmoid scores with a choice-only bias, DeepSeek-V3's and LFM2's, or
softmax scores with none, ``qwen3_next``'s; the chosen weights normalised
over their sum plus an epsilon the caller may set), and the routed
feed-forward of the experts THIS device holds.

The layer expert parallelism needs, run without its exchange: the router
scores every token against all E experts of the layer; the device holds the
``count`` experts from ``first`` on; the (token, choice) pairs are sorted by
expert, those of absent experts last, so the live pairs (the ones that fell
on a held expert) are the first ``total`` positions of the sorted order.
Nothing has a capacity and no pair is dropped.  A pair on an absent expert
contributes nothing here (its expert's device would add it).

Everything that runs in pair order runs over WINDOWS of the sorted order:
``_window_rows`` rows each (a rule of the shapes alone), walked by a loop
whose trip count, ``ceil(total / rows)``, is read from the input.  One body
handles a window: it gathers the window's rows of ``x``, runs three grouped
matrix products over the held experts with the group sizes clipped to the
window (``jax.lax.ragged_dot``: the TPU compiler lowers it to Mosaic kernels
of its own that visit only the row tiles the group sizes cover, under
``jax_enable_x64`` too, where the megablox ``gmm`` that ships with JAX does
not lower; an expert whose pairs straddle a window's edge is done in two
parts), masks the rows past the live pairs (a grouped product never writes
them) and writes the window into a buffer of all N*k rows that starts as
zeros.  So the cost follows the pairs that fell here at any load, all N*k of
them included, and nothing between dispatch and combine is sized for the
worst case but that one buffer.  The first window runs inline, ahead of the
loop, so that the usual one-window step adds nothing to zeros.

By token the weighted results are gathered back through the inverse
permutation and summed in float32.  The routed part is one
``jax.custom_vjp``: its residuals are its inputs (the backward waits for the
cotangent behind an ``optimization_barrier``, as ``jax.checkpoint``'s does,
so the compiler keeps nothing of the forward for it), and its backward walks
the same windows, making a window's three forward products again, running
the six backward products, adding the window's weight gradients in float32
and writing the window's rows of ``x``'s cotangent, which a gather through
the inverse permutation then sums by token.  No scatter runs forward or
backward.

Region scopes inside ``contrib.moe_experts`` (the ops run under their
blocks' scopes ``router`` and ``experts``): ``dispatch`` (sort, gather),
``grouped`` (the products and the gate), ``combine`` (gather back,
weights).
"""

from __future__ import annotations

import functools

from .. import regions
from .registry import register


@register("contrib.moe_router", num_outputs=2)
def _moe_router(x, weight, bias=None, k=1, scale=1.0, normalize=True,
                score="sigmoid", eps=1e-20):
    """Top-k routing over all E experts of a layer.  ``x`` (N, U);
    ``weight`` (E, U), the layout of a Dense weight.  ``score``:
    ``"sigmoid"`` (DeepSeek-V3's ``noaux_tc`` with one group) or
    ``"softmax"`` over the E logits (``qwen3_next``'s).  ``bias`` (E,) or
    None: added to the scores for the CHOICE alone.  Returns ``(weights
    (N, k) float32, experts (N, k) int32)``: the scores (without the bias)
    at the chosen experts, divided by their sum + ``eps`` if
    ``normalize`` (1e-20 by default; LFM2's published router adds 1e-6),
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
        chosen = chosen / (chosen.sum(axis=-1, keepdims=True) + eps)
    return chosen * f32(scale), experts.astype(jnp.int32)


_ROW_TILE = 512


def _window_rows(n, k):
    """Rows of one window of the sorted pair buffer, from the shapes the op
    sees: the tokens, rounded up to the grouped products' row tile.  A
    device that holds 1/k of a layer's experts or less under an even router
    gets one window a layer; the weight gradients are added once a window
    (three float32 stacks read and written), which is what a smaller window
    would pay for following the load more closely."""
    return _windows(n, _ROW_TILE) * _ROW_TILE


def _windows(total, rows):
    """How many windows of ``rows`` rows hold the first ``total`` rows."""
    return (total + (rows - 1)) // rows


def _ragged_dot(lhs, rhs, group_sizes):
    """``jax.lax.ragged_dot``, under a name of this module: on the chip the
    rows past ``sum(group_sizes)`` are never written, and a test plants NaN
    there through this name."""
    import jax
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


@functools.cache
def _routed(k, rows):
    """The routed part for ``k`` choices a token in windows of ``rows``
    rows: ``routed(x, weights, w_gate, w_up, w_down, order, inv, starts)
    -> y (N, U)``, one ``jax.custom_vjp``.  ``order`` (windows * rows,) is
    the sorted order of the pairs, padded to whole windows; ``inv`` (N, k)
    the buffer row of each token's pairs; ``starts`` (count + 1,) where each
    held expert's pairs start, the last entry the count of live pairs."""
    import jax
    import jax.numpy as jnp
    f32, i32 = jnp.float32, jnp.int32
    by_group = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])

    def glu(gate, up):
        return jax.nn.silu(gate) * up

    def weight_grad(rows_in, d_rows_out, sizes):
        """(count, in, out) float32: each group's rows_in^T d_rows_out."""
        return jax.lax.ragged_dot_general(
            rows_in, d_rows_out, sizes, by_group, preferred_element_type=f32)

    def window(i, order, starts):
        """Window ``i``: its first row, the pairs in it, how many of them
        fall on each held expert, and which of its rows hold a live pair."""
        lo = i * i32(rows)
        pairs = jax.lax.dynamic_slice(order, (lo,), (rows,))
        sizes = jnp.diff(jnp.clip(starts, lo, lo + i32(rows)))
        return lo, pairs, sizes, lo + jnp.arange(rows, dtype=i32) < starts[-1]

    def walk(body, init, total):
        """``body(i, carry)`` over the windows that hold a live pair.  The
        first runs inline: what it adds to or writes into ``init``'s zeros
        the compiler folds, so the usual one-window step pays for no
        read-add-write of the carry; the others run in a loop."""
        return jax.lax.fori_loop(i32(1), _windows(total, rows), body,
                                 body(i32(0), init))

    @jax.custom_vjp
    def routed(x, weights, w_gate, w_up, w_down, order, inv, starts):
        return forward(x, weights, w_gate, w_up, w_down, order, inv,
                       starts)[0]

    def forward(x, weights, w_gate, w_up, w_down, order, inv, starts):
        n = x.shape[0]

        def body(i, out_all):
            lo, pairs, sizes, live = window(i, order, starts)
            with regions.scope("dispatch"):
                rows_x = x[pairs // i32(k)]
            with regions.scope("grouped"):
                gate = _ragged_dot(rows_x, w_gate, sizes)
                up = _ragged_dot(rows_x, w_up, sizes)
                out = _ragged_dot(glu(gate, up), w_down, sizes)
            with regions.scope("combine"):
                # rows past the live pairs were never written: mask them
                out = jnp.where(live[:, None], out, jnp.zeros((), out.dtype))
                return jax.lax.dynamic_update_slice(out_all, out,
                                                    (lo, i32(0)))

        out_all = walk(body, jnp.zeros((order.shape[0], x.shape[1]),
                                       x.dtype), starts[-1])
        with regions.scope("combine"):
            per_pair = out_all[inv.reshape(-1)].reshape(n, k, -1)
            w = jnp.where(inv < starts[-1], weights, f32(0.0))
            y = jnp.einsum("nk,nku->nu", w,
                           per_pair.astype(f32)).astype(x.dtype)
        return y, (x, weights, w_gate, w_up, w_down, order, inv, starts)

    def backward(res, d_y):
        # as jax.checkpoint does: what the backward makes again must wait
        # for the cotangent, or the compiler shares the first window's
        # gather and products with the forward's and keeps them across the
        # whole step (0.6 GB more planned in the MLA + MoE cell)
        res, d_y = jax.lax.optimization_barrier((res, d_y))
        x, weights, w_gate, w_up, w_down, order, inv, starts = res
        n = x.shape[0]
        flat_weights = weights.reshape(-1)
        gate_t, up_t, down_t = (jnp.swapaxes(w, 1, 2)
                                for w in (w_gate, w_up, w_down))

        def body(i, carry):
            d_rows_all, d_weight_all, d_gate_w, d_up_w, d_down_w = carry
            lo, pairs, sizes, live = window(i, order, starts)
            token = pairs // i32(k)
            with regions.scope("dispatch"):
                rows_x = x[token]
            with regions.scope("combine"):
                # the cotangent in pair order: the pair's token's row of
                # d_y under the pair's weight
                d_y_rows = d_y[token].astype(f32)
                w = jnp.where(live, flat_weights[pairs], f32(0.0))
                d_out = (w[:, None] * d_y_rows).astype(x.dtype)
            with regions.scope("grouped"):
                gate = _ragged_dot(rows_x, w_gate, sizes)
                up = _ragged_dot(rows_x, w_up, sizes)
                hidden, glu_vjp = jax.vjp(glu, gate, up)
                out = _ragged_dot(hidden, w_down, sizes)
                d_hidden = _ragged_dot(d_out, down_t, sizes)
                d_gate, d_up = glu_vjp(d_hidden)
                d_rows = _ragged_dot(d_gate, gate_t, sizes) \
                    + _ragged_dot(d_up, up_t, sizes)
                d_gate_w = d_gate_w + weight_grad(rows_x, d_gate, sizes)
                d_up_w = d_up_w + weight_grad(rows_x, d_up, sizes)
                d_down_w = d_down_w + weight_grad(hidden, d_out, sizes)
            with regions.scope("combine"):
                # out's rows past the live pairs may hold anything
                d_weight = jnp.where(
                    live, (d_y_rows * out.astype(f32)).sum(axis=-1),
                    f32(0.0))
            return (jax.lax.dynamic_update_slice(d_rows_all, d_rows,
                                                 (lo, i32(0))),
                    jax.lax.dynamic_update_slice(d_weight_all, d_weight,
                                                 (lo,)),
                    d_gate_w, d_up_w, d_down_w)

        d_rows_all, d_weight_all, *d_stacks = walk(body, (
            jnp.zeros((order.shape[0], x.shape[1]), x.dtype),
            jnp.zeros(order.shape, f32),
            jnp.zeros(w_gate.shape, f32), jnp.zeros(w_up.shape, f32),
            jnp.zeros(w_down.shape, f32)), starts[-1])
        # the float32 stacks go before the passes by token start: beside
        # their (N, k, U) float32 temporaries they would set the op's peak
        d_rows_all, d_stacks = jax.lax.optimization_barrier((
            d_rows_all, [d.astype(w.dtype) for d, w in zip(
                d_stacks, (w_gate, w_up, w_down))]))
        live_of = inv < starts[-1]
        with regions.scope("dispatch"):
            # d_rows' rows past the live pairs were never written either
            d_x = d_rows_all[inv.reshape(-1)].reshape(n, k, -1)
            d_x = jnp.where(live_of[:, :, None], d_x.astype(f32), f32(0.0))
            d_x = d_x.sum(axis=1).astype(x.dtype)
        with regions.scope("combine"):
            d_weights = jnp.where(live_of, d_weight_all[inv], f32(0.0))
        return (d_x, d_weights.astype(weights.dtype),
                *d_stacks, None, None, None)

    routed.defvjp(forward, backward)
    return routed


@register("contrib.moe_experts", num_outputs=3)
def _moe_experts(x, weights, experts, w_gate, w_up, w_down, first=0):
    """The routed SwiGLU experts held here, dropless.  ``x`` (N, U);
    ``weights`` (N, k) float32 and ``experts`` (N, k) int32 from
    ``contrib.moe_router``, expert ids over the whole layer; ``w_gate``,
    ``w_up`` (count, U, I) and ``w_down`` (count, I, U): the experts
    ``first … first + count - 1``.  Returns ``(y (N, U) in x's type,
    tokens (count,) int32, windows () int32)``: the weighted sum over each
    token's pairs that fell on a held expert (zero for a token with none),
    how many pairs fell on each held expert, and how many windows of
    ``_window_rows(N, k)`` rows the forward walked to cover them."""
    import jax.numpy as jnp
    n, k = experts.shape
    held_n = w_gate.shape[0]
    i32 = jnp.int32
    rows = _window_rows(n, k)

    with regions.scope("dispatch"):
        local = experts.reshape(-1) - i32(first)                # (P,)
        key = jnp.where((local >= 0) & (local < held_n), local, i32(held_n))
        order = jnp.argsort(key, stable=True).astype(i32)       # (P,)
        at = jnp.arange(order.shape[0], dtype=i32)
        inv = jnp.zeros_like(order).at[order].set(
            at, unique_indices=True).reshape(n, k)
        # where each held expert's pairs start among the sorted keys; all
        # held pairs lie in the first ``starts[held_n]`` sorted positions
        starts = jnp.searchsorted(key[order], jnp.arange(
            held_n + 1, dtype=i32)).astype(i32)
        tokens = jnp.diff(starts)
        windows = _windows(starts[held_n], rows)
        order = jnp.pad(order, (0, -order.shape[0] % rows))     # whole windows

    y = _routed(k, rows)(x, weights, w_gate, w_up, w_down, order, inv,
                         starts)
    return y, tokens, windows
