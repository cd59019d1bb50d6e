"""Linear-attention ops: the gated delta rule as a chunked scan, and the
depthwise causal convolution that stands in front of it (Gated DeltaNet,
arXiv:2412.06464; the mixer of three in four layers of ``qwen3_next``).

The rule keeps one matrix of state a head, ``S`` (key width x value width),
zero at a row's start::

    S_t = a_t S_{t-1} + k_t (x) [b_t (v_t - (a_t S_{t-1})^T k_t)]
    o_t = S_t^T q_t                   a_t = exp(g_t), g_t <= 0

Token by token that is L dependent steps of rank-one work.  In chunks of C
positions it is matrix products: inside a chunk the C writes solve one unit
lower-triangular system, ``(I + strict_lower(diag(b) K K^T o decay))^-1``,
and only the state between chunks is carried by a scan, L / C steps of
(C x Dk) x (Dk x Dv) products.  Every decay ratio is ``exp(G_i - G_j)`` of
the chunk's cumulative sums taken under the mask ``i >= j``, so every
exponent is at most 0; ``exp(G_i) * exp(-G_j)`` would overflow.

One path, chosen by nothing but shapes.  ``jax.numpy`` + ``lax.scan``; the
whole op sits in a ``jax.checkpoint``, so what a layer keeps for its
backward is the op's inputs, and the backward runs the chunks again (the
chunk-boundary states, 64 KB a head and chunk in float32, and the chunk's
solved writes are temporaries of one layer at a time).  The op runs under
the region scope ``gdn_scan``.
"""

from __future__ import annotations

from .. import regions
from .registry import register


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a`` (..., C, C): a
    triangular solve against the identity, by substitution, float32 (the
    solve is where the writes of a chunk depend on each other).  A sum of
    the powers of ``a`` ends at ``C - 1`` too and is all matrix products,
    but the powers grow to 1e18 at C = 64 when a chunk's keys are nearly
    parallel, and cancel to nothing.  Its gradient is the closed form
    ``-inv^T g inv^T``, two products."""
    import jax
    import jax.numpy as jnp

    def solved(a):
        eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
        return jax.scipy.linalg.solve_triangular(
            eye + a, eye, lower=True, unit_diagonal=True)

    @jax.custom_vjp
    def inverse(a):
        return solved(a)

    def forward(a):
        inv = solved(a)
        return inv, inv

    def backward(inv, g):
        hi = jax.lax.Precision.HIGHEST
        inv_t = jnp.swapaxes(inv, -1, -2)
        return (-jnp.matmul(jnp.matmul(inv_t, g, precision=hi), inv_t,
                            precision=hi),)

    inverse.defvjp(forward, backward)
    return inverse(a)


_HEAD_GROUPS = (4, 2, 1)    # the solves run a group of heads at a time


def _product(spec, a, b, operand):
    """``einsum`` with both operands in ``operand``'s type, summed in
    float32."""
    import jax.numpy as jnp
    return jnp.einsum(spec, a.astype(operand), b.astype(operand),
                      preferred_element_type=jnp.float32)


def _solved_chunks(q, k, v, g, beta, operand):
    """Every chunk at once: what a chunk contributes that does not depend
    on the carried state.  ``q``, ``k``, ``v`` (B, H, N, C, D) in
    ``operand``'s type, ``g`` and ``beta`` (B, H, N, C) float32.  Returns
    ``(w, u, qk, q_in, k_out)`` in ``operand``'s type and the chunk's whole
    decay in float32.  Decays and the solve are float32; every product
    takes its operands in ``operand`` and sums in float32."""
    import jax.numpy as jnp
    f32 = jnp.float32
    rows = jnp.arange(q.shape[-2], dtype=jnp.int32)
    at_or_under = rows[:, None] >= rows[None, :]
    under = rows[:, None] > rows[None, :]

    def product(spec, a, b):
        return _product(spec, a, b, operand)

    big_g = jnp.cumsum(g, axis=-1)                              # (B,H,N,C)
    ratio = big_g[..., :, None] - big_g[..., None, :]           # G_i - G_j
    decay = jnp.exp(jnp.where(at_or_under, ratio, -jnp.inf))    # 0 over diag
    k_beta = k.astype(f32) * beta[..., None]
    a = jnp.where(under, product("...id,...jd->...ij", k_beta, k) * decay,
                  f32(0.0))
    solve = _unit_lower_inverse(a)                              # (I + a)^-1
    into = jnp.exp(big_g)[..., None]            # decay from the chunk's start
    xs = (product("...ij,...jd->...id", solve, k_beta * into),  # (…,C,Dk)
          product("...ij,...jd->...id", solve,
                  v.astype(f32) * beta[..., None]),
          product("...id,...jd->...ij", q, k) * decay,          # within chunk
          q.astype(f32) * into,
          k.astype(f32)
          * jnp.exp(big_g[..., -1:] - big_g)[..., None])        # to its end
    return tuple(x.astype(operand) for x in xs) \
        + (jnp.exp(big_g[..., -1]),)                            # (B,H,N)


def _chunked(q, k, v, g, beta, operand):
    """The rule over (B, H, N, C, D) chunks: q, k, v in ``operand``'s type
    (the type the op was given), g and beta float32; float32 out.  Every
    product takes its operands in ``operand`` (what the MXU rounds a
    float32 operand to anyway under the default precision, at half the
    bytes) and sums in float32; with float32 operands nothing is
    rounded."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, h, _n, _c, dk = k.shape
    groups = next(n for n in _HEAD_GROUPS if h % n == 0)

    # the solves, a group of heads at a time (one traced body, its
    # intermediates made again in the backward): they hold a dozen arrays
    # of a chunked tensor's size each, forward and backward
    def by_group(x):        # (B, H, …) -> (groups, B, H / groups, …)
        return jnp.moveaxis(
            x.reshape((b, groups, h // groups) + x.shape[2:]), 1, 0)

    xs = jax.lax.map(
        jax.checkpoint(lambda args: _solved_chunks(*args, operand)),
        tuple(by_group(x) for x in (q, k, v, g, beta)))

    def by_chunk(x):        # (groups, B, H / groups, N, …) -> (N, B, H, …)
        x = jnp.moveaxis(jnp.moveaxis(x, 3, 0), 1, 2)
        return x.reshape(x.shape[:2] + (h,) + x.shape[4:])

    def product(spec, a, b):
        return _product(spec, a, b, operand)

    # across chunks: the state, carried
    def step(state, xs):
        w, u, qk, q_in, k_out, a_chunk = xs
        seen = state.astype(operand)
        v_new = u.astype(f32) - product("bhck,bhkv->bhcv", w, seen)
        written = v_new.astype(operand)
        out = product("bhck,bhkv->bhcv", q_in, seen) \
            + product("bhij,bhjv->bhiv", qk, written)
        state = a_chunk[..., None, None] * state \
            + product("bhck,bhcv->bhkv", k_out, written)
        return state, out

    state0 = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    _, out = jax.lax.scan(step, state0, tuple(by_chunk(x) for x in xs))
    return jnp.moveaxis(out, 0, 2)                              # (B,H,N,C,Dv)


@register("contrib.gated_delta_rule")
def _gated_delta_rule(q, k, v, g, beta, chunk=64):
    """The gated delta rule over whole rows, causal, state zero at a row's
    start.  ``q``, ``k`` (B, L, H, Dk) and ``v`` (B, L, H, Dv), as
    projected (the caller has normed and scaled q and k and repeated them
    to v's heads); ``g`` (B, L, H) the log of each position's decay, at
    most 0; ``beta`` (B, L, H) its write strength.  Returns ``o`` (B, L, H,
    Dv) in v's type.  Computed in chunks of ``chunk`` positions (a row is
    padded to a whole number of them with positions that write nothing and
    decay nothing); decays, the chunk's triangular solve (always at the
    highest precision), every sum and the carried state are float32;
    every other product takes its operands in v's type."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, l, h, _dk = q.shape
    pad = -l % chunk
    n = (l + pad) // chunk

    def chunks(x):
        """(B, L, H, …) -> (B, H, N, C, …)."""
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    @jax.checkpoint
    def rule(q, k, v, g, beta):
        wide = [chunks(x.astype(v.dtype)) for x in (q, k, v)]
        out = _chunked(*wide, chunks(g.astype(f32)),
                       chunks(beta.astype(f32)), v.dtype)       # (B,H,N,C,Dv)
        out = jnp.moveaxis(out, 1, 3).reshape(b, n * chunk, h, -1)
        return out[:, :l].astype(v.dtype)

    with regions.scope("gdn_scan"):
        return rule(q, k, v, g, beta)


@register("contrib.causal_conv1d")
def _causal_conv1d(x, weight):
    """Depthwise causal convolution along a row: ``x`` (B, L, C), ``weight``
    (C, K); ``out[t] = sum_j weight[:, j] * x[t - (K - 1) + j]``, positions
    before the row's start count as zero, no bias (a ``Conv1d`` with
    ``groups = C``, left padding K - 1).  The taps come from the weight's
    shape; summed in float32, returned in x's type."""
    import jax.numpy as jnp
    f32 = jnp.float32
    taps = weight.shape[1]
    length = x.shape[1]
    padded = jnp.pad(x.astype(f32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(f32)
    out = padded[:, :length] * w[:, 0]
    for j in range(1, taps):
        out = out + padded[:, j:j + length] * w[:, j]
    return out.astype(x.dtype)
