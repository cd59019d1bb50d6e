"""State-space ops: the scan of a Mamba-2 mixer in its chunked SSD form
(state space duality, arXiv:2405.21060, section 6 and its minimal listing;
the mixer of nine in ten layers of ``granitemoehybrid``).

A head keeps one matrix of state, ``h`` (head width P x state width N),
zero at a row's start::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        A < 0, dt_t >= 0
    y_t = h_t C_t + D x_t

``B`` and ``C`` come in G groups, each shared by H / G consecutive heads.
Token by token that is L dependent steps.  In chunks of Q positions it is
matrix products, four of them, and no solve:

1. inside a chunk, ``y = (L o C B^T) (dt x)`` with ``L_ij = exp(G_i -
   G_j)`` at ``i >= j`` (G the chunk's cumulative sums of ``dt A``): one Q x
   Q ``C B^T`` a chunk and group, whatever the heads;
2. what each chunk adds to the state, ``sum_j exp(G_Q - G_j) dt_j x_j
   B_j^T``, every chunk at once;
3. across chunks, the state carried by a scan that only decays and adds
   (``h <- exp(G_Q) h + added``);
4. the carried state as each position sees it, ``exp(G_i) C_i h_in``.

It shares the chunk skeleton of ``ops/linear_attention.py``: rows padded to
whole chunks (``_chunks``; a padded position has ``dt = 0``: it writes
nothing and decays nothing), a group of heads at a time under ``lax.map``,
every product with its operands in the input's type and summed in float32
(``_product``), and the decay ratios ``exp(G_i - G_j)`` taken under the
mask (``_decays``).  The whole op sits in a ``jax.checkpoint``, so a layer
keeps the op's inputs for its backward and the chunks' blocks are
temporaries of one group of heads at a time.  The op runs under the region
scope ``ssd_scan``.
"""

from __future__ import annotations

from .. import regions
from .linear_attention import (_HEAD_GROUPS, _at_or_under, _chunks, _decays,
                               _product, _rows)
from .registry import register


def _group(x, dt, a, cb, bs, cs, operand):
    """The scan of one group of heads over every chunk.  ``x`` (B, G, R, N,
    Q, P) in ``operand``'s type; ``dt`` (B, G, R, N, Q) and ``a`` (G, R),
    float32: a head's steps and its ``A``; ``cb`` (B, G, N, Q, Q) float32,
    ``C B^T`` of each chunk; ``bs``, ``cs`` (B, G, N, Q, Ns).  Returns y (B,
    G, R, N, Q, P) float32, without ``D x``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def product(spec, p, q):
        return _product(spec, p, q, operand)

    big_g = jnp.cumsum(dt * a[None, :, :, None, None], axis=-1)
    written = x.astype(f32) * dt[..., None]                     # dt_j x_j
    scores = cb[:, :, None] * _decays(big_g, _at_or_under(dt.shape[-1]))
    y = product("bgrnij,bgrnjp->bgrnip", scores, written)       # inside
    to_end = jnp.exp(big_g[..., -1:] - big_g)[..., None]
    added = product("bgrnjp,bgnjd->bgrnpd", written * to_end, bs)

    def carry(state, chunk):
        added_c, decay_c = chunk
        return decay_c[..., None, None] * state + added_c, state

    state0 = jnp.zeros(added.shape[:3] + added.shape[4:], f32)
    _, into = jax.lax.scan(carry, state0,
                           (jnp.moveaxis(added, 3, 0),
                            jnp.moveaxis(jnp.exp(big_g[..., -1]), 3, 0)))
    seen = product("bgnid,bgrnpd->bgrnip", cs, jnp.moveaxis(into, 0, 3))
    return y + jnp.exp(big_g)[..., None] * seen


@register("contrib.ssd_scan")
def _ssd_scan(x, dt, A, B, C, D, chunk=256):
    """The Mamba-2 scan over whole rows, causal, state zero at a row's
    start.  ``x`` (B, L, H, P); ``dt`` (B, L, H) float32, a position's step
    (after the softplus), at least 0; ``A`` (H,) below 0; ``B``, ``C`` (B,
    L, G, N), group ``g`` serving heads ``g H / G … (g + 1) H / G - 1``;
    ``D`` (H,).  Returns ``y`` (B, L, H, P) in x's type.  Computed in
    chunks of ``chunk`` positions; decays, the carried state and every sum
    are float32, every product takes its operands in x's type."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    b, length, h, _p = x.shape
    g = B.shape[2]
    if h % g:
        raise ValueError(f"{g} groups of B and C do not divide {h} heads")
    r = h // g
    groups = next(n for n in _HEAD_GROUPS if r % n == 0)
    operand = x.dtype

    def by_group(t):    # (B, H, …) -> (groups, B, G, R / groups, …)
        t = t.reshape((b, g, groups, r // groups) + t.shape[2:])
        return jnp.moveaxis(t, 2, 0)

    @jax.checkpoint
    def rule(x, dt, A, B, C, D):
        bs, cs = (_chunks(t.astype(operand), chunk) for t in (B, C))
        cb = _product("bgnid,bgnjd->bgnij", cs, bs, operand)
        a = jnp.moveaxis(A.astype(f32).reshape(g, groups, r // groups), 1, 0)
        y = jax.lax.map(
            jax.checkpoint(lambda xs: _group(*xs, cb, bs, cs, operand)),
            (by_group(_chunks(x, chunk)), by_group(_chunks(dt.astype(f32),
                                                           chunk)), a))
        y = jnp.moveaxis(y, 0, 2).reshape((b, h) + y.shape[4:])
        y = _rows(y, length) + D.astype(f32)[:, None] * x.astype(f32)
        return y.astype(x.dtype)

    with regions.scope("ssd_scan"):
        return rule(x, dt, A, B, C, D)
