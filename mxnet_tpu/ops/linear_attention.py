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

One algorithm, two lowerings chosen by the platform a program is compiled
for (``jax.lax.platform_dependent``, as attention's).  Any platform but a
TPU, and any shape the kernels' tiling does not take, gets the form below,
``jax.numpy`` + ``lax.scan``, which is also the statement of the
mathematics; the whole of it sits in a ``jax.checkpoint``.  A TPU gets the
Pallas kernels of ``kernels/gated_delta.py`` for the forward (``gdn_solve``
+ ``gdn_fwd``: every chunk's system solved by substitution a system a lane,
then the walk with a head's state in VMEM) under one ``jax.custom_vjp``
whose backward is the chunked form's own, made again from the op's inputs
around ``gdn_solve``'s inverses, float32 (in place of the compiler's
triangular solves, two a layer).  Either way what a layer keeps for its
backward is the op's inputs, and the backward runs the chunks again (the
chunk-boundary states, 64 KB a head and chunk in float32, and the chunk's
solved writes are temporaries of one layer at a time).  The op runs under
the region scope ``gdn_scan``.
"""

from __future__ import annotations

from .. import regions
from .registry import register


def _unit_lower_inverse(a, given=None):
    """``(I + a)^-1`` of strictly lower-triangular ``a`` (..., C, C): a
    triangular solve against the identity, by substitution, float32 (the
    solve is where the writes of a chunk depend on each other).  A sum of
    the powers of ``a`` ends at ``C - 1`` too and is all matrix products,
    but the powers grow to 1e18 at C = 64 when a chunk's keys are nearly
    parallel, and cancel to nothing.  Its gradient is the closed form
    ``-inv^T g inv^T``, two products.  ``given``: the inverse in float32,
    made elsewhere (``gdn_solve`` on a TPU), taken for the solve's
    result."""
    import jax
    import jax.numpy as jnp

    def solved(a, given):
        if given is not None:
            return given
        eye = jnp.broadcast_to(jnp.eye(a.shape[-1], dtype=a.dtype), a.shape)
        return jax.scipy.linalg.solve_triangular(
            eye + a, eye, lower=True, unit_diagonal=True)

    @jax.custom_vjp
    def inverse(a, given):
        return solved(a, given)

    def forward(a, given):
        inv = solved(a, given)
        return inv, (inv, given)

    def backward(kept, g):
        inv, given = kept
        hi = jax.lax.Precision.HIGHEST
        inv_t = jnp.swapaxes(inv, -1, -2)
        return (-jnp.matmul(jnp.matmul(inv_t, g, precision=hi), inv_t,
                            precision=hi),
                None if given is None else jnp.zeros_like(given))

    inverse.defvjp(forward, backward)
    return inverse(a, given)


_HEAD_GROUPS = (4, 2, 1)    # the solves run a group of heads at a time


def _chunks(x, chunk):
    """(B, L, H, …) -> (B, H, N, C, …): a row padded at its end with zeros
    to a whole number N of chunks of C = ``chunk`` positions."""
    import jax.numpy as jnp
    b, l = x.shape[:2]
    pad = -l % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape((b, (l + pad) // chunk, chunk) + x.shape[2:])
    return jnp.moveaxis(x, 3, 1)


def _rows(out, length):
    """(B, H, N, C, …) -> (B, ``length``, H, …): ``_chunks`` undone, the
    padding cut off."""
    import jax.numpy as jnp
    b, h, n, c = out.shape[:4]
    out = jnp.moveaxis(out, 1, 3).reshape((b, n * c, h) + out.shape[4:])
    return out[:, :length]


def _at_or_under(chunk):
    """The (C, C) mask ``i >= j`` of a chunk's positions."""
    import jax.numpy as jnp
    rows = jnp.arange(chunk, dtype=jnp.int32)
    return rows[:, None] >= rows[None, :]


def _decays(big_g, at_or_under):
    """``exp(G_i - G_j)`` at ``i >= j`` and 0 above the diagonal, of a
    chunk's cumulative log decays ``big_g`` (…, C) -> (…, C, C);
    ``at_or_under`` is ``_at_or_under(C)``.  The ratio is taken under the
    mask, so every exponent is at most 0; ``exp(G_i) * exp(-G_j)`` would
    overflow."""
    import jax.numpy as jnp
    ratio = big_g[..., :, None] - big_g[..., None, :]           # G_i - G_j
    return jnp.exp(jnp.where(at_or_under, ratio, -jnp.inf))     # 0 over diag


def _product(spec, a, b, operand):
    """``einsum`` with both operands in ``operand``'s type, summed in
    float32."""
    import jax.numpy as jnp
    return jnp.einsum(spec, a.astype(operand), b.astype(operand),
                      preferred_element_type=jnp.float32)


def _solved_chunks(q, k, v, g, beta, operand, inverse=None):
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
    decay = _decays(big_g, at_or_under)
    k_beta = k.astype(f32) * beta[..., None]
    a = jnp.where(under, product("...id,...jd->...ij", k_beta, k) * decay,
                  f32(0.0))
    solve = _unit_lower_inverse(a, inverse)                     # (I + a)^-1
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


def _chunked(q, k, v, g, beta, operand, inverse=None):
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

    given = () if inverse is None else (inverse,)   # (B, H, N, C, C)
    xs = jax.lax.map(
        jax.checkpoint(lambda args: _solved_chunks(*args[:5], operand,
                                                   *args[5:])),
        tuple(by_group(x) for x in (q, k, v, g, beta) + given))

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
    from ..kernels import gated_delta

    def portable(*xs):
        return _chunked_rule(*xs, chunk)

    def kernels(*xs):
        return _kernel_rule(*xs, chunk)

    with regions.scope("gdn_scan"):
        # by the shapes a device gets: its share of the heads under a mesh
        if gated_delta.eligible(chunk, _shards(q.shape)[-1], q.shape[-1],
                                v.shape[-1], v.dtype):
            # resolved when the program is lowered: a TPU gets the Pallas
            # kernels, any other platform the chunked form
            return jax.lax.platform_dependent(q, k, v, g, beta,
                                              tpu=kernels, default=portable)
        return portable(q, k, v, g, beta)


def _kernel_rule(q, k, v, g, beta, chunk, interpret=False):
    """The op as a TPU runs it: the Pallas kernels' forward under one
    ``jax.custom_vjp`` whose residuals are the op's inputs and whose
    backward is the chunked form's, around ``gdn_solve``'s float32 inverses.
    ``interpret=True`` runs the Pallas interpreter (the CPU tests)."""
    import jax
    import jax.numpy as jnp
    from ..kernels import gated_delta

    shards = _shards(q.shape)   # read once: the backward is traced later

    def forward(*inputs):
        return _per_shard(lambda *xs: gated_delta.forward(
            *xs, chunk, interpret), shards, *inputs)

    def backward(inputs, d_out):
        # as jax.checkpoint does: the chunks made again wait for the
        # cotangent, so nothing of the forward is shared or kept.  The
        # chunks' systems are solved by the kernel once more, in place of
        # the compiler's triangular solves (two a layer, 5.5 ms), from k in
        # the type the chunked form multiplies it in
        inputs, d_out = jax.lax.optimization_barrier((inputs, d_out))
        _q, k, v, g, beta = inputs
        inverse = jnp.moveaxis(jax.lax.stop_gradient(_per_shard(
            lambda *xs: gated_delta.inverses(*xs, chunk, interpret),
            shards, k.astype(v.dtype), g, beta)), 2, 1)     # (B, H, N, C, C)
        return jax.vjp(lambda *xs: _chunked_rule(*xs, chunk, inverse),
                       *inputs)[1](d_out)

    rule = jax.custom_vjp(forward)
    rule.defvjp(lambda *inputs: (forward(*inputs), inputs), backward)
    return rule(q, k, v, g, beta)


def _chunked_rule(q, k, v, g, beta, chunk, inverse=None):
    """The op in ``jax.numpy``: ``_chunked`` over rows padded to whole
    chunks, the whole of it in a ``jax.checkpoint``.  ``inverse``: every
    chunk's solved system (B, H, N, C, C) in float32, where a kernel has
    made it."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    @jax.checkpoint
    def rule(q, k, v, g, beta, *inverse):
        wide = [_chunks(x.astype(v.dtype), chunk) for x in (q, k, v)]
        out = _chunked(*wide, _chunks(g.astype(f32), chunk),
                       _chunks(beta.astype(f32), chunk), v.dtype,
                       *inverse)                                # (B,H,N,C,Dv)
        return _rows(out, q.shape[1]).astype(v.dtype)

    return rule(q, k, v, g, beta, *(() if inverse is None else (inverse,)))


def _shards(shape):
    """How a ``TrainStep`` traced over several devices splits (B, L, H, …)
    operands: ``(mesh, batch axes, head axis, heads a device)``, the batch
    over the step's data axes and the heads over ``tp`` where they divide
    (``ops/contrib.py::_flash``'s rule; a row's positions stay whole, the
    scan carries state along them).  No mesh, or one device: ``(None, None,
    None, H)``."""
    from .registry import step_layout
    b, _l, h = shape[:3]
    layout = step_layout()
    if layout is None or layout[0].size == 1:
        return None, None, None, h
    mesh, batch_axes = layout
    n_b = 1
    for a in batch_axes:
        n_b *= mesh.axis_size(a)
    tp = mesh.axis_size("tp") if "tp" in mesh.axis_names else 0
    by_head = tp and h % tp == 0
    return (mesh, batch_axes if batch_axes and b % n_b == 0 else None,
            "tp" if by_head else None, h // tp if by_head else h)


def _per_shard(fn, shards, *xs):
    """``fn`` over (B, L, H, …) operands, on each device's own block when a
    ``TrainStep`` traces over several: GSPMD cannot partition a Mosaic
    kernel, so the call is a ``shard_map`` over ``shards``, ``_shards``'
    split."""
    import jax
    mesh, b_spec, h_spec, _heads = shards
    if mesh is None:
        return fn(*xs)
    specs = tuple(jax.sharding.PartitionSpec(
        b_spec, None, h_spec, *(None,) * (x.ndim - 3)) for x in xs)
    return jax.shard_map(fn, mesh=mesh.mesh, in_specs=specs,
                         out_specs=specs[0], check_vma=False)(*xs)


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
