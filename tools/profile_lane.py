#!/usr/bin/env python
"""Decomposition profiler for the bench lanes.

Written when no device-side trace plane could be read: per-op device time
is reconstructed by measuring each step component STANDALONE at the exact bench shapes, scanned inside one
jit (lax.scan) so dispatch cost is amortized exactly like bench.py:

  full        the real TrainStep (what bench.py times)
  attention   the flash kernel fwd+bwd, one layer's shape x num_layers
  dense       one encoder cell minus attention (qkv/proj/ffn/gelu/ln),
              fwd+bwd, x num_layers
  head        MLM decoder matmul + softmax-CE fwd+bwd (the vocab matmul)
  embed       token+position gather + embed layernorm fwd+bwd
  adam        optimizer update over all params

The residual (full - sum of parts) is scan/bookkeeping overhead.  Each
component prints ms/step and its share of the ideal roofline.

Usage:
  python tools/profile_lane.py --lane bert512   # the 0.43-MFU regime
  python tools/profile_lane.py --lane llama2048
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed_scan(fn, carry, n_steps, n_rep=3, name=""):
    """Median wall ms/step of fn scanned n_steps times inside one jit."""
    import jax

    @jax.jit
    def run(c):
        def body(c, _):
            return fn(c), None
        c, _ = jax.lax.scan(body, c, None, length=n_steps)
        return c

    out = run(carry)
    jax.block_until_ready(out)
    times = []
    for _ in range(n_rep):
        t0 = time.perf_counter()
        out = run(carry)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / n_steps * 1e3)
    ms = float(np.median(times))
    print(f"    [{name or 'component'}] {ms:.2f} ms/step", flush=True)
    return ms


def profile_bert512(batch=32, seq=512, scan_steps=32):
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    jax.config.update("jax_default_matmul_precision", "default")
    bf16 = ml_dtypes.bfloat16
    layers, units, hidden, heads, vocab = 12, 768, 3072, 12, 30522
    d_head = units // heads
    r = np.random.RandomState(0)

    def t(*shape, dt=bf16, scale=0.02):
        return jnp.asarray((r.randn(*shape) * scale).astype(dt))

    results = {}

    # ---- attention: flash kernel fwd+bwd at one layer's shape ----------
    from mxnet_tpu.kernels.flash_attention import flash_attention
    q = t(batch, heads, seq, d_head, scale=1.0)
    k = t(batch, heads, seq, d_head, scale=1.0)
    v = t(batch, heads, seq, d_head, scale=1.0)

    def att_step(qq):
        def f(qi):
            return flash_attention(qi, k, v,
                                   sm_scale=1.0 / np.sqrt(d_head)).sum()
        g = jax.grad(f)(qq)
        return (qq + g.astype(qq.dtype) * bf16(1e-8)).astype(qq.dtype)

    per_layer = _timed_scan(att_step, q, scan_steps, name="attention/layer")
    results["attention"] = per_layer * layers

    # ---- dense: one encoder cell minus attention, fwd+bwd --------------
    wqkv = t(units, 3 * units)
    wproj = t(units, units)
    w1 = t(units, hidden)
    w2 = t(hidden, units)
    gam = jnp.ones((units,), bf16)
    x0 = t(seq, batch, units, scale=1.0)

    def ln(h):
        h32 = h.astype(jnp.float32)
        m = h32.mean(-1, keepdims=True)
        vr = ((h32 - m) ** 2).mean(-1, keepdims=True)
        return ((h32 - m) * jax.lax.rsqrt(vr + 1e-12)).astype(h.dtype) * gam

    def cell_no_att(xx):
        def f(xi):
            qkv = xi @ wqkv
            # fold the full qkv projection into the consumed value (summed
            # thirds, NOT a slice): a sliced dot lets XLA narrow the
            # matmul to 1/3 and the component under-measures
            ctxv = (qkv[..., :units] + qkv[..., units:2 * units]
                    + qkv[..., 2 * units:])       # attention itself is
            out = ln(xi + ctxv @ wproj)           # measured separately
            h = jax.nn.gelu(out @ w1) @ w2
            return ln(out + h).astype(jnp.float32).sum()
        g = jax.grad(f)(xx)
        return (xx + g.astype(xx.dtype) * bf16(1e-8)).astype(xx.dtype)

    results["dense"] = _timed_scan(cell_no_att, x0, scan_steps, name="dense/layer") * layers

    # ---- head: MLM decoder matmul + softmax CE fwd+bwd -----------------
    wdec = t(units, vocab)
    labels = jnp.asarray(r.randint(0, vocab, (batch * seq,)), jnp.int32)
    xh = t(batch * seq, units, scale=1.0)

    def head_step(xx):
        def f(xi):
            logits = (xi @ wdec).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, labels[:, None],
                                         axis=-1)[:, 0]
            return (lse - picked).mean()
        g = jax.grad(f)(xx)
        return (xx + g.astype(xx.dtype) * bf16(1e-8)).astype(xx.dtype)

    results["head"] = _timed_scan(head_step, xh, scan_steps, name="head")

    # ---- embed: gathers + embed LN fwd+bwd ------------------------------
    wemb = t(vocab, units)
    wpos = t(512, units)
    toks = jnp.asarray(r.randint(0, vocab, (batch, seq)), jnp.int32)

    def embed_step(we_):
        def f(wi):
            e = wi[toks] + wpos[None, :seq]
            return ln(e).astype(jnp.float32).sum()
        g = jax.grad(f)(we_)
        return (we_ + g.astype(we_.dtype) * bf16(1e-8)).astype(we_.dtype)

    results["embed"] = _timed_scan(embed_step, wemb, scan_steps, name="embed")

    # ---- adam: the optimizer update over all params ---------------------
    n_params = (layers * (units * 3 * units + 3 * units + units * units
                          + units + units * hidden + hidden
                          + hidden * units + units + 4 * units)
                + vocab * units + 512 * units + 2 * units
                + units * units + units + units * vocab + vocab)
    p32 = jnp.asarray(r.randn(n_params).astype(np.float32))
    gr = jnp.asarray(r.randn(n_params).astype(np.float32) * 1e-3)

    # NOTE: gr rides the CARRY, not a closure — closed-over device arrays
    # are baked into the HLO as constants, and a 440MB constant bloats
    # the program and its compile
    def adam_step(state):
        p, m, v, g = state
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        p = p - 1e-4 * m / (jnp.sqrt(v) + 1e-8)
        return (p, m, v, g)

    results["adam"] = _timed_scan(adam_step,
                                  (p32, jnp.zeros_like(p32),
                                   jnp.zeros_like(p32), gr), scan_steps,
                                  name="adam")
    return results


def profile_llama2048(batch=4, seq=2048, scan_steps=8):
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    jax.config.update("jax_default_matmul_precision", "default")
    bf16 = ml_dtypes.bfloat16
    # mirror bench.run_llama_once's arch (same env override); components
    # here measure the NO-remat cost — the remat lane's extra forward
    # shows up as part of the full-step residual
    arch = os.environ.get("MXNET_BENCH_LLAMA_ARCH", "8,2048,5504,16,8,0")
    layers, units, hidden, heads, kv_heads =         [int(x) for x in arch.split(",")][:5]
    vocab = 8192
    d_head = units // heads
    r = np.random.RandomState(0)

    def t(*shape, dt=bf16, scale=0.02):
        return jnp.asarray((r.randn(*shape) * scale).astype(dt))

    results = {}
    from mxnet_tpu.kernels.flash_attention import flash_attention
    q = t(batch, heads, seq, d_head, scale=1.0)
    k = t(batch, heads, seq, d_head, scale=1.0)
    v = t(batch, heads, seq, d_head, scale=1.0)

    def att_step(qq):
        def f(qi):
            return flash_attention(qi, k, v, causal=True,
                                   sm_scale=1.0 / np.sqrt(d_head)).sum()
        g = jax.grad(f)(qq)
        return (qq + g.astype(qq.dtype) * bf16(1e-8)).astype(qq.dtype)

    results["attention"] = _timed_scan(att_step, q, scan_steps, name="attention/layer") * layers

    wq = t(units, units)
    wk = t(units, units // (heads // kv_heads))
    wv = t(units, units // (heads // kv_heads))
    wo = t(units, units)
    wg = t(units, hidden)
    wu = t(units, hidden)
    wd = t(hidden, units)
    x0 = t(batch, seq, units, scale=1.0)

    def rms(h):
        h32 = h.astype(jnp.float32)
        return (h32 * jax.lax.rsqrt((h32 ** 2).mean(-1, keepdims=True)
                                    + 1e-6)).astype(h.dtype)

    def cell_no_att(xx):
        def f(xi):
            xn = rms(xi)
            qq = xn @ wq
            kk = xn @ wk             # folded into the output below — dead
            vv = xn @ wv             # projections would be DCE'd by XLA
            out = xi + qq @ wo
            out = out + jnp.pad(kk + vv,
                                ((0, 0), (0, 0), (0, units - kk.shape[-1])))
            xn2 = rms(out)
            h = (jax.nn.silu(xn2 @ wg) * (xn2 @ wu)) @ wd
            return rms(out + h).astype(jnp.float32).sum()
        g = jax.grad(f)(xx)
        return (xx + g.astype(xx.dtype) * bf16(1e-8)).astype(xx.dtype)

    results["dense"] = _timed_scan(cell_no_att, x0, scan_steps, name="dense/layer") * layers

    wdec = t(units, vocab)
    labels = jnp.asarray(r.randint(0, vocab, (batch * seq,)), jnp.int32)
    xh = t(batch * seq, units, scale=1.0)

    def head_step(xx):
        def f(xi):
            logits = (xi @ wdec).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, labels[:, None],
                                         axis=-1)[:, 0]
            return (lse - picked).mean()
        g = jax.grad(f)(xx)
        return (xx + g.astype(xx.dtype) * bf16(1e-8)).astype(xx.dtype)

    results["head"] = _timed_scan(head_step, xh, scan_steps, name="head")
    return results


def _full_step_ms(lane):
    """Run the real bench lane in-process and return its step_ms."""
    import bench
    if lane == "bert512":
        res = bench.run_once("bert_12_768_12", 32, 512, "bfloat16", 32, 1)
    else:
        res = bench.run_llama_once(4, 2048, "bfloat16", 8, 1)
    return res["extra"]["step_ms"], res["extra"]["mfu"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--lane", choices=["bert512", "llama2048"],
                    default="bert512")
    ap.add_argument("--skip-full", action="store_true",
                    help="only the component measurements")
    args = ap.parse_args(argv)
    os.environ.setdefault("MXNET_FUSED_ATTENTION", "1")

    full_ms = mfu = None
    if not args.skip_full:
        full_ms, mfu = _full_step_ms(args.lane)
    parts = profile_bert512() if args.lane == "bert512" \
        else profile_llama2048()

    print(f"\n== {args.lane} decomposition (ms/step, scan-amortized) ==")
    total = sum(parts.values())
    for name, ms in sorted(parts.items(), key=lambda kv: -kv[1]):
        print(f"  {name:<10} {ms:8.2f} ms")
    print(f"  {'SUM':<10} {total:8.2f} ms")
    if full_ms is not None:
        print(f"  {'FULL step':<10} {full_ms:8.2f} ms   (mfu {mfu:.4f})")
        print(f"  {'residual':<10} {full_ms - total:8.2f} ms  "
              "(scan/bookkeeping/fusion differences)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
