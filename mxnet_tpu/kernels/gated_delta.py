"""The gated delta rule's forward in Pallas for TPU: a kernel that solves
every chunk's system by substitution, in float32, a system a lane, and a walk
that keeps a head's state in VMEM across its chunks.
``contrib.gated_delta_rule`` (``ops/linear_attention.py``, which holds the
``jax.numpy`` statement of the same mathematics, the rule's definition and
the backward) runs ``forward`` on a TPU and ``inverses`` in its backward.

A chunk of C positions of one head, state ``S`` (Dk, Dv) at its start,
``G`` the chunk's running sum of log-decays, ``M_ij = exp(G_i - G_j)`` under
the mask ``i >= j`` (every exponent at most 0)::

    A  = strict_lower(M o (b k) k^T)          T = (I + A)^-1
    r  = b v - (exp(G) b k) S                 x = T r        (the writes)
    o  = (exp(G) q) S + (M o q k^T) x
    S' = exp(G_C) S + (exp(G_C - G) k)^T x

``T`` does not depend on the state, so every chunk's is made before the
walk, by ``gdn_solve``: forward substitution, ``T_i = e_i - sum_{j<i} A_ij
T_j``, exact float32 arithmetic on the VPU (never a sum or a product of
powers of the system: a chunk of repeated keys puts those 1e19 off).  Done
a matrix at a time the coefficient ``A_ij`` would have to be spread over a
row's lanes 2,016 times a system; instead 128 groups of systems are turned
so that a lane is a group (``A`` made on the MXU, 128 x 128 transposes on
the XLU), every ``A_ij`` is then a row of 128 groups' values, and a step
of the substitution is elementwise: ~250 bundles a system where ten
float32 products of 64 rows at the highest precision (the same inverse by
doubling over diagonal blocks, on the MXU) took 1,500.  Decays, the solve,
every sum and the carried state are float32; every product takes its
operands in v's type and sums in float32.

Layout: q, k, v and o are (B, L, H, D) seen as (B, L, H * D), so a block of
``hb`` heads of a chunk is a (C, hb * D) window at a lane-aligned column and
nothing is moved to a (B, H, N, C, D) form; g and beta (two float32 a
position and head) are laid out by the caller as rows, G's running sums
taken there, and turned to columns in the kernels.  Two heads are stacked
into the MXU's 128 rows where two chunks fill them (``_pack``; C = 64, what
``eligible`` admits on a TPU), so that a (C, C) matrix of the chunk's
algebra is the (128, 128) block diagonal of a pair's and a pair's inverses
travel side by side, ``[T_0 | T_1]`` (C, 128).

Two calls:

* ``gdn_solve``: every chunk's ``T``, 128 groups a grid step.  The forward
  takes it in v's type (``x = T r`` is a product like any other; 33 MB a
  layer in bf16, a temporary); ``inverses`` hands it out in float32, for the
  backward's products with it at the highest precision.
* ``gdn_fwd``: the walk above over a grid (batch, head blocks, chunks), the
  chunk axis sequential: the state of the block's heads is a float32 VMEM
  scratch from a row's first chunk to its last; writes ``o``.

Index maps and scalars are pinned to int32 / float32: the package runs with
``jax_enable_x64``.  Measured at (1, 8192, 32, 128) bfloat16 on a v5e
(PERF.md, PR 36): ``gdn_solve`` 1.24 ms, ``gdn_fwd`` 1.25.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import metrics as _metrics
from .flash_attention import _VMEM_SCOPED_DEFAULT, _zi

__all__ = ["forward", "inverses", "eligible"]

_F32 = jnp.float32
_SUBLANES = 8   # rows of the block that holds a chunk's g and beta

_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ b^T
_TN = ((0,), (0,))      # a^T @ b
_LANES = 128    # the MXU's rows; groups a step of ``gdn_solve``, a lane each


def eligible(chunk, heads, dk, dv, dtype):
    """Whether the kernels' tiling takes these shapes on a TPU: lane-aligned
    head widths (a head's columns are a block of their own), and pairs of
    heads whose chunks stack to the MXU's 128 rows, which ``gdn_solve``
    turns whole: a chunk of 64 with an even number of heads.  (The Pallas
    interpreter takes any; that is the CPU tests' way in.)"""
    return dk % 128 == 0 and dv % 128 == 0 and chunk == 64 \
        and heads % 2 == 0 \
        and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                 jnp.dtype(jnp.float32))


def _head_block(heads):
    """Heads a grid step: the first of 4, 2, 1 that divides them."""
    return next(x for x in (4, 2, 1) if heads % x == 0)


def _pack(hb, chunk):
    """Heads of a grid step stacked into one block of rows: two where the
    MXU's 128 rows hold two chunks, so that a (C, C) matrix of the chunk's
    algebra is the block diagonal of a pair's."""
    return 2 if hb % 2 == 0 and 2 * chunk <= _LANES else 1


def _dot(a, b, dims):
    """2-D product summed in float32.  Operands of float32 are multiplied at
    the highest precision (nothing rounded), narrower ones as they are."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=_F32,
        precision=jax.lax.Precision.HIGHEST if a.dtype == _F32
        else jax.lax.Precision.DEFAULT)


def _loop(n, body):
    """``body(i)`` for i in 0 … n - 1, a loop in the kernel; the index an
    int32 (a Python bound is an int64 under x64)."""
    def step(i, carry):
        body(i)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), step, jnp.int32(0))


def _side_by_side(x, c):
    """The diagonal blocks of (n, n), ``[x_0 | x_1]`` (c, n)."""
    n = x.shape[0]
    if n == c:
        return x
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, n), 1)
    return jnp.where(cols >= c, x[c:], x[:c])


def _block_diagonal(x):
    """``[x_0 | x_1]`` (c, n) as the block diagonal (n, n)."""
    c, n = x.shape
    if n == c:
        return x
    cols = jax.lax.broadcasted_iota(jnp.int32, (c, n), 1)
    zero = jnp.zeros_like(x)
    return jnp.concatenate([jnp.where(cols < c, x, zero),
                            jnp.where(cols >= c, x, zero)], axis=0)


def _stacked(ref, heads, width, rows=slice(None)):
    """Heads ``heads``' (C, width) columns of a (1, rows, hb * width)
    block, one under the other."""
    return jnp.concatenate(
        [ref[0, rows, j * width:(j + 1) * width] for j in heads], axis=0)


def _by_head(x, c):
    """The heads' (c, …) blocks of a stacked (n, …)."""
    return [x[i * c:(i + 1) * c] for i in range(x.shape[0] // c)]


def _by_position(rows):
    """``(g_row, g_col, beta)`` of a stack of heads from its (8, n) block of
    per-position float32, G's running sums in row 0 and beta in row 1: G
    as a row (1, n), and both as columns (n, 1), which one 128 x 128
    transpose on the XLU makes (a column operand would pad 4 lanes to 128
    in HBM and in VMEM)."""
    n = rows.shape[1]
    turned = jnp.concatenate(
        [rows, jnp.zeros((_LANES - rows.shape[0], n), _F32)], axis=0).T
    return rows[:1], turned[:, :1], turned[:, 1:2]


def _decays(g_col, g_row, c):
    """``(strict, decay)`` (n, n) of n / c heads stacked along the rows:
    the mask ``i > j`` inside a head's own block, and ``exp(G_i - G_j)``
    under ``i >= j`` there, 0 elsewhere.  ``g_col`` (n, 1) and ``g_row``
    (1, n) the chunk's running sums of each head."""
    n = g_col.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    low, strict = rows >= cols, rows > cols
    if n > c:       # two heads: under the diagonal, all but the second's
        own = (rows < c) | (cols >= c)      # rows against the first's columns
        low, strict = low & own, strict & own
    decay = jnp.where(
        low, jnp.exp(jnp.where(low, g_col - g_row, _F32(0.0))), _F32(0.0))
    return strict, decay


def _solve_kernel(k_ref, gb_ref, inv_ref, side, turned, solved, *, hb, dk,
                  chunk, chunks):
    """The inverses of ``chunks`` chunks of ``hb`` heads: ``chunks * hb /
    pack`` groups of ``pack`` systems, 128 on a TPU.  ``side`` (groups * C,
    n) holds a group's ``[A_0 | A_1]`` in rows ``group * C + i``, and the
    inverses on the way out; ``turned`` and ``solved`` (C, n, groups) hold
    ``A`` and ``T`` with a group a lane: ``[i, s * C + j, group]`` is entry
    (i, j) of the group's system s."""
    od = k_ref.dtype
    c, pack = chunk, _pack(hb, chunk)
    n, groups = pack * c, hb // pack
    lanes = chunks * groups

    def systems(at):
        rows = pl.ds(pl.multiple_of(at * c, c), c)
        for group in range(groups):
            heads = range(group * pack, (group + 1) * pack)
            k = _stacked(k_ref, heads, dk, rows)
            g_row, g_col, beta = _by_position(gb_ref[0, 0, at, group])
            strict, decay = _decays(g_col, g_row, c)
            kb = (k.astype(_F32) * beta).astype(od)
            to = pl.multiple_of((at * groups + group) * c, c)
            side[pl.ds(to, c), :] = _side_by_side(jnp.where(
                strict, _dot(kb, k, _NT) * decay, _F32(0.0)), c)

    _loop(chunks, systems)

    # a group a lane: row i of every group, turned
    def turn(i):
        turned[i] = side[pl.ds(i, lanes, stride=c), :].T        # (n, lanes)

    _loop(c, turn)

    # T_i = e_i - sum_{j<i} A_ij T_j, elementwise over the lanes
    position = jax.lax.broadcasted_iota(jnp.int32, (n, lanes), 0)
    if pack > 1:
        position = jnp.where(position >= c, position - c, position)

    def row(i):
        def column(j, acc):
            return jnp.concatenate(
                [acc[s * c:(s + 1) * c]
                 - turned[i, pl.ds(s * c + j, 1), :]
                 * solved[j, s * c:(s + 1) * c, :] for s in range(pack)],
                axis=0)

        solved[i] = jax.lax.fori_loop(
            jnp.int32(0), i, column, (position == i).astype(_F32))

    _loop(c, row)

    def back(i):
        side[pl.ds(i, lanes, stride=c), :] = solved[i].T        # (lanes, n)

    _loop(c, back)
    inv_ref[0, 0, 0] = side[...].astype(inv_ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, gb_ref, inv_ref, out_ref, s_scr, *, hb,
                dk, dv, chunk):
    """One chunk of ``hb`` heads, ``_pack`` of them at a time, stacked
    along the rows: every (n, n) matrix is the block diagonal of the heads'
    (C, C)."""
    od = v_ref.dtype
    c, pack = chunk, _pack(hb, chunk)
    n = pack * c

    @pl.when(pl.program_id(2) == 0)
    def _row_starts():
        s_scr[...] = jnp.zeros_like(s_scr)

    for group in range(hb // pack):
        heads = range(group * pack, (group + 1) * pack)
        q, k, v = _stacked(q_ref, heads, dk), _stacked(k_ref, heads, dk), \
            _stacked(v_ref, heads, dv)
        g_row, g_col, beta = _by_position(gb_ref[0, 0, 0, group])
        _strict, decay = _decays(g_col, g_row, c)
        g_last = jnp.concatenate(
            [jnp.broadcast_to(g_col[(i + 1) * c - 1:(i + 1) * c, :], (c, 1))
             for i in range(pack)], axis=0)
        kf = k.astype(_F32)
        into = jnp.exp(g_col)               # decay from the chunk's start
        kbi = kf * beta * into
        ko = kf * jnp.exp(g_last - g_col)   # … and to its end
        # the chunk's whole decay as a column over the state's rows: Mosaic
        # has no broadcast of a (1, 1) along sublanes and lanes at once, so
        # G's last entry is picked out of its row form by a mask
        lanes = jax.lax.broadcasted_iota(jnp.int32, (dk, n), 1)
        whole = [jnp.exp(jnp.sum(jnp.where(
            lanes == (i + 1) * c - 1, g_row, _F32(0.0)), axis=1,
            keepdims=True)) for i in range(pack)]               # (Dk, 1)
        state = [s_scr[j] for j in heads]
        seen = [s.astype(od) for s in state]
        inv = _block_diagonal(inv_ref[0, 0, 0, group * c:(group + 1) * c, :])
        # the chunk's right-hand side against the states its heads see,
        # and its solved writes
        r = v.astype(_F32) * beta - jnp.concatenate(
            [_dot(x.astype(od), s, _NN)
             for x, s in zip(_by_head(kbi, c), seen)], axis=0)
        written = _dot(inv, r.astype(od), _NN).astype(od)
        out = jnp.concatenate(
            [_dot(x.astype(od), s, _NN)
             for x, s in zip(_by_head(q.astype(_F32) * into, c), seen)],
            axis=0) + _dot((_dot(q, k, _NT) * decay).astype(od), written, _NN)
        for j, o in zip(heads, _by_head(out, c)):
            out_ref[0, :, j * dv:(j + 1) * dv] = o.astype(out_ref.dtype)
        for j, s, a, x, w in zip(heads, state, whole, _by_head(ko, c),
                                 _by_head(written, c)):
            s_scr[j] = a * s + _dot(x.astype(od), w, _TN)


class _Shapes(NamedTuple):
    """A call's sizes: the op's, and the tiling's (``hb`` heads a grid step,
    ``pack`` of them stacked, ``groups`` stacks a step; ``n`` chunks a row,
    ``n_all`` with the chunks ``gdn_solve`` adds to fill its last step of
    ``chunks``)."""
    b: int
    l: int
    h: int
    dk: int
    dv: int
    chunk: int
    hb: int
    pack: int
    groups: int
    n: int
    chunks: int
    n_all: int

    @property
    def rows(self):         # of a tensor padded to whole chunks
        return self.n * self.chunk

    @property
    def rows_all(self):     # … and to whole steps of the solve
        return self.n_all * self.chunk

    @property
    def stacked(self):      # rows of a stack of heads: the MXU's 128
        return self.pack * self.chunk


def _shapes(q_shape, dv, chunk):
    b, l, h, dk = q_shape
    hb = _head_block(h)
    pack = _pack(hb, chunk)
    groups = hb // pack
    chunks = _LANES // groups
    n = -(-l // chunk)
    return _Shapes(b, l, h, dk, dv, chunk, hb, pack, groups, n, chunks,
                   -(-n // chunks) * chunks)


def _rows(x, to):
    """(B, L, …) padded with zeros to ``to`` rows."""
    return jnp.pad(x, ((0, 0), (0, to - x.shape[1]))
                   + ((0, 0),) * (x.ndim - 2))


def _wide(x, dtype, s):
    """q, k or v as the kernels take it: in ``dtype`` (v's), rows padded to
    whole chunks (positions that write nothing), heads folded into the
    columns."""
    return _rows(x.astype(dtype), s.rows).reshape(s.b, s.rows, -1)


def _by_stack(g, beta, s):
    """g and beta as the kernels take them, (B, H / hb, chunks, stacks, 8,
    stacked): a stack of heads' rows, their chunks side by side in the
    lanes, G's running sums in row 0 and beta in row 1; the chunks padded
    to whole steps of the solve (positions that decay nothing)."""
    def by_chunk(x):        # (B, L, H) -> (B, chunks, C, H)
        return _rows(x.astype(_F32), s.rows_all).reshape(
            s.b, s.n_all, s.chunk, s.h)

    def stacks(x):          # -> (B, H / hb, chunks, stacks, stacked)
        x = x.reshape(s.b, s.n_all, s.chunk, s.h // s.hb, s.hb)
        return jnp.transpose(x, (0, 3, 1, 4, 2)).reshape(
            s.b, s.h // s.hb, s.n_all, s.groups, s.stacked)

    rows = jnp.stack([stacks(jnp.cumsum(by_chunk(g), axis=2)),
                      stacks(by_chunk(beta))], axis=4)
    return jnp.pad(rows, ((0, 0),) * 4 + ((0, _SUBLANES - 2), (0, 0)))


def _count(kernel, s):
    _metrics.counter(
        "mxnet_gdn_kernel_chunks_total",
        "Chunks the gated delta rule's Pallas kernels work through, over "
        "batch and heads, a traced call.",
        labels={"kernel": kernel}).inc(s.b * s.h * s.n)


def _solve(k, by_position, s, dtype, interpret):
    """Every chunk's ``[T_0 | T_1]`` in ``dtype``, (B, H / hb, steps,
    groups a step * C, stacked): ``gdn_solve``, over k padded to whole
    steps."""
    _count("gdn_solve", s)
    k = _rows(k, s.rows_all)
    lanes = s.chunks * s.groups
    rows, steps = s.chunks * s.chunk, s.n_all // s.chunks
    # both buffers of every block (a lane dim pads to 128) and the scratch
    vmem = 2 * (rows * s.hb * s.dk * k.dtype.itemsize
                + lanes * 8 * s.stacked * 4
                + lanes * s.chunk * s.stacked * jnp.dtype(dtype).itemsize) \
        + 3 * lanes * s.chunk * s.stacked * 4

    def block(*shape):
        return pl.BlockSpec((1, 1) + shape, lambda b, h, m:
                            (b, h, m) + (_zi(),) * (len(shape) - 1))

    return pl.pallas_call(
        functools.partial(_solve_kernel, hb=s.hb, dk=s.dk, chunk=s.chunk,
                          chunks=s.chunks),
        grid=(s.b, s.h // s.hb, steps),
        in_specs=[pl.BlockSpec((1, rows, s.hb * s.dk),
                               lambda b, h, m: (b, m, h)),
                  block(s.chunks, s.groups, _SUBLANES, s.stacked)],
        out_specs=block(1, lanes * s.chunk, s.stacked),
        out_shape=jax.ShapeDtypeStruct(
            (s.b, s.h // s.hb, steps, lanes * s.chunk, s.stacked), dtype),
        scratch_shapes=[pltpu.VMEM((lanes * s.chunk, s.stacked), _F32),
                        pltpu.VMEM((s.chunk, s.stacked, lanes), _F32),
                        pltpu.VMEM((s.chunk, s.stacked, lanes), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=max(vmem + 8 * 2 ** 20, _VMEM_SCOPED_DEFAULT)),
        interpret=interpret, name="gdn_solve")(k, by_position)


def forward(q, k, v, g, beta, chunk=64, interpret=False):
    """The gated delta rule over whole rows (``contrib.gated_delta_rule``'s
    contract), ``gdn_solve`` + ``gdn_fwd``, for a caller that brings the
    backward: ``q``, ``k`` (B, L, H, Dk), ``v`` (B, L, H, Dv), ``g`` and
    ``beta`` (B, L, H); ``o`` (B, L, H, Dv) in v's type.
    ``interpret=True`` runs the Pallas interpreter (the CPU tests)."""
    s = _shapes(q.shape, v.shape[-1], chunk)
    od = v.dtype
    q, k, v = _wide(q, od, s), _wide(k, od, s), _wide(v, od, s)
    by_stack = _by_stack(g, beta, s)
    inverse = _solve(k, by_stack, s, od, interpret)

    def wide(width):        # a chunk of a head block of (B, L, H * width)
        return pl.BlockSpec((1, s.chunk, s.hb * width),
                            lambda b, h, i: (b, i, h))

    _count("gdn_fwd", s)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=s.hb, dk=s.dk, dv=s.dv,
                          chunk=s.chunk),
        grid=(s.b, s.h // s.hb, s.n),
        in_specs=[
            wide(s.dk), wide(s.dk), wide(s.dv),
            pl.BlockSpec(
                (1, 1, 1, s.groups, _SUBLANES, s.stacked),
                lambda b, h, i: (b, h, i, _zi(), _zi(), _zi())),
            # the step's inverses where ``gdn_solve`` left them
            pl.BlockSpec(
                (1, 1, 1, s.groups * s.chunk, s.stacked),
                lambda b, h, i: (b, h, i // jnp.int32(s.chunks),
                                 i % jnp.int32(s.chunks), _zi()))],
        out_specs=wide(s.dv),
        out_shape=jax.ShapeDtypeStruct((s.b, s.rows, s.h * s.dv), od),
        scratch_shapes=[pltpu.VMEM((s.hb, s.dk, s.dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="gdn_fwd")(q, k, v, by_stack, inverse)
    return out.reshape(s.b, s.rows, s.h, s.dv)[:, :s.l]


def inverses(k, g, beta, chunk=64, interpret=False):
    """Every chunk's ``T = (I + A)^-1`` alone (``gdn_solve``), (B, N, H, C,
    C) float32 (heads third, as in the op's operands), for a caller that
    walks the chunks itself; ``A``'s product takes its operands in k's
    type, as the forward's does in v's."""
    b, _l, h, dk = k.shape
    s = _shapes(k.shape, dk, chunk)
    out = _solve(_wide(k, k.dtype, s), _by_stack(g, beta, s), s, _F32,
                 interpret)
    # (B, H / hb, steps, chunks a step x stacks x C, pack x C)
    out = out.reshape(b, h // s.hb, s.n_all, s.groups, chunk, s.pack, chunk)
    return jnp.transpose(out, (0, 2, 1, 3, 5, 4, 6)).reshape(
        b, s.n_all, h, chunk, chunk)[:, :s.n]
