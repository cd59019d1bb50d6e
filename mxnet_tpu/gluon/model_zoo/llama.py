"""Llama-style decoder LLM through the Gluon HybridBlock API — the
BASELINE stretch config 5 ("Llama-3-8B trains via HybridBlock API with
TP/SP/CP shardings").

Architecture (Llama 3 family): pre-RMSNorm decoder blocks, rotary
position embeddings, grouped-query attention (n_kv_heads < n_heads),
SwiGLU MLP, untied LM head, causal masking.  The reference has no LLM
in-tree (SURVEY §5.7 — its transformer support tops out at the fused
single-device attention ops); this model exists to prove the Gluon API
stretches to modern LLM shape + sharding requirements.

Parallelism hooks (consumed by ``parallel.TrainStep`` via
``Parameter.sharding`` GSPMD hints):
 - ``apply_tp_shardings(model)`` — megatron split: qkv + gate/up
   column-parallel, o_proj + down row-parallel, embeddings/LM head over
   the vocab dim.
 - sequence/context parallelism: attention lowers through
   ``contrib.masked_selfatt`` (flash/dense); for a sequence-sharded mesh
   use ``parallel.attention`` (ring attention) with the same q/k/v
   layout — see kernels/ring_attention.py.

Configs: ``llama3_8b`` (the stretch target: 32L/4096/14336/32H/8KV) plus
tiny variants for tests and the multichip dryrun.
"""

from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock
from ..nn import Dense, Embedding

__all__ = ["LlamaModel", "llama_model", "apply_tp_shardings",
           "LLAMA_CONFIGS"]

# name -> (layers, units, hidden, heads, kv_heads)
LLAMA_CONFIGS = {
    "llama3_8b": (32, 4096, 14336, 32, 8),
    "llama_tiny": (2, 64, 172, 4, 2),        # tests / dryrun
    "llama_small": (4, 256, 688, 8, 4),
}


class RMSNorm(HybridBlock):
    """Root-mean-square norm (no mean subtraction, no bias) — Llama's
    norm; computed in f32 like the reference implementations.
    ``zero_centered``: the scale is ``1 + weight`` and the weight starts
    at 0 (``qwen3_next``'s form)."""

    def __init__(self, units, eps=1e-5, zero_centered=False, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        self._zero_centered = bool(zero_centered)
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units,),
                init="zeros" if zero_centered else "ones")

    def hybrid_forward(self, F, x, weight):
        xf = x.astype("float32")
        var = (xf * xf).mean(axis=-1, keepdims=True)
        out = xf * F.rsqrt(var + self._eps)
        scale = weight.astype("float32")
        if self._zero_centered:
            scale = scale + 1.0
        return (out * scale).astype(x.dtype)


def _rope(F, x, base=500000.0, rotate=None, interleaved=False):
    """Rotary embeddings over the last dim; x: (B, H, L, D).  ``base``: the
    published ``rope_theta``.  ``rotate=(begin, end)``: only that slice of
    the last dim turns, the rest passes through (latent attention keeps a
    position-free part of each head).  ``interleaved``: the slice holds its
    pairs as (2i, 2i+1), DeepSeek's ``rope_interleave``; it is
    de-interleaved to [evens | odds] and leaves in that order, which is the
    rotate-half form every other caller has."""
    if rotate is not None:
        begin, end = rotate
        D = x.shape[3]
        parts = [_rope(F, x[:, :, :, begin:end], base,
                       interleaved=interleaved)]
        if begin:
            parts.insert(0, x[:, :, :, :begin])
        if end < D:
            parts.append(x[:, :, :, end:])
        return F.concat(*parts, dim=-1) if len(parts) > 1 else parts[0]
    B, H, L, D = x.shape
    half = D // 2
    inv = 1.0 / (base ** (F.arange(0, half).astype("float32") / half))
    pos = F.arange(L).astype("float32")
    ang = pos.reshape((L, 1)) * inv.reshape((1, half))      # (L, half)
    cos = F.cos(ang).reshape((1, 1, L, half)).astype(x.dtype)
    sin = F.sin(ang).reshape((1, 1, L, half)).astype(x.dtype)
    if interleaved:
        pairs = x.reshape((B, H, L, half, 2))
        x1 = F.slice_axis(pairs, axis=4, begin=0, end=1) \
            .reshape((B, H, L, half))
        x2 = F.slice_axis(pairs, axis=4, begin=1, end=2) \
            .reshape((B, H, L, half))
    else:
        x1 = x[:, :, :, :half]
        x2 = x[:, :, :, half:]
    return F.concat(x1 * cos - x2 * sin, x1 * sin + x2 * cos, dim=-1)


class LlamaBlock(HybridBlock):
    def __init__(self, units, hidden, heads, kv_heads, attn_impl="fused",
                 sp_axis="sp", rope_base=500000.0, **kwargs):
        super().__init__(**kwargs)
        if units % heads or heads % kv_heads:
            raise MXNetError("units % heads and heads % kv_heads must be 0")
        if attn_impl not in ("fused", "ring", "ulysses"):
            raise MXNetError(
                f"attn_impl {attn_impl!r}: want fused|ring|ulysses")
        self._units = units
        self._heads = heads
        self._kv = kv_heads
        self._hd = units // heads
        self._attn_impl = attn_impl
        self._sp_axis = sp_axis
        self._rope_base = float(rope_base)
        with self.name_scope():
            self.q_proj = Dense(units, flatten=False, use_bias=False,
                                in_units=units, prefix="q_")
            self.k_proj = Dense(self._hd * kv_heads, flatten=False,
                                use_bias=False, in_units=units, prefix="k_")
            self.v_proj = Dense(self._hd * kv_heads, flatten=False,
                                use_bias=False, in_units=units, prefix="v_")
            self.o_proj = Dense(units, flatten=False, use_bias=False,
                                in_units=units, prefix="o_")
            self.gate = Dense(hidden, flatten=False, use_bias=False,
                              in_units=units, prefix="gate_")
            self.up = Dense(hidden, flatten=False, use_bias=False,
                            in_units=units, prefix="up_")
            self.down = Dense(units, flatten=False, use_bias=False,
                              in_units=hidden, prefix="down_")
            self.attn_norm = RMSNorm(units, prefix="attn_norm_")
            self.mlp_norm = RMSNorm(units, prefix="mlp_norm_")

    def hybrid_forward(self, F, x):
        # x: (B, L, C) batch-major (modern-LLM layout)
        B, L, _ = x.shape
        h = self.attn_norm(x)
        q = self.q_proj(h).reshape((B, L, self._heads, self._hd)) \
            .transpose((0, 2, 1, 3))                       # (B, H, L, D)
        k = self.k_proj(h).reshape((B, L, self._kv, self._hd)) \
            .transpose((0, 2, 1, 3))
        v = self.v_proj(h).reshape((B, L, self._kv, self._hd)) \
            .transpose((0, 2, 1, 3))
        q = _rope(F, q, self._rope_base)
        k = _rope(F, k, self._rope_base)
        if self._attn_impl != "fused":
            # sequence/context parallelism: ring or Ulysses attention over
            # the current mesh's sp axis (falls back to local attention
            # when no mesh is active — same math, so tests run anywhere)
            ctx_vec = F.contrib.sp_att_qkv(
                q, k, v, impl=self._attn_impl, axis=self._sp_axis,
                num_kv_groups=self._heads // self._kv, causal=True)
        else:
            # direct q/k/v entry point: no interleave round-trip; the GQA
            # kv-head broadcast happens inside the op next to the kernel.
            # valid_length=None is the STATIC all-valid fact — the flash
            # kernel compiles without any mask passes (pure causal)
            ctx_vec = F.contrib.masked_att_qkv(
                q, k, v, None, num_kv_groups=self._heads // self._kv,
                causal=True)                                # (B, H, L, D)
        attn = self.o_proj(ctx_vec.transpose((0, 2, 1, 3))
                           .reshape((B, L, self._units)))
        x = x + attn
        h = self.mlp_norm(x)
        mlp = self.down(F.silu(self.gate(h)) * self.up(h))
        return x + mlp


class LlamaModel(HybridBlock):
    def __init__(self, vocab_size=128256, num_layers=2, units=64,
                 hidden=172, heads=4, kv_heads=2, attn_impl="fused",
                 sp_axis="sp", remat=None, rope_base=500000.0, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        # activation rematerialization per decoder block (the reference's
        # MXNET_BACKWARD_DO_MIRROR memory/compute trade — SURVEY §5.7);
        # None = read the env flag at construction
        if remat is None:
            from ... import config as _cfg
            remat = bool(_cfg.get_int("MXNET_BACKWARD_DO_MIRROR", 0))
        self._remat = bool(remat)
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="tok_")
            self.blocks = []
            for i in range(num_layers):
                blk = LlamaBlock(units, hidden, heads, kv_heads,
                                 attn_impl=attn_impl, sp_axis=sp_axis,
                                 rope_base=rope_base, prefix=f"layer{i}_")
                self.register_child(blk, f"layer{i}")
                self.blocks.append(blk)
            self.norm = RMSNorm(units, prefix="final_norm_")
            self.lm_head = Dense(vocab_size, flatten=False, use_bias=False,
                                 in_units=units, prefix="lm_head_")

    def hybrid_forward(self, F, tokens):
        # tokens: (B, L) int32 → logits (B, L, vocab)
        from ... import autograd
        x = self.embed(tokens)
        use_remat = self._remat and autograd.is_recording()
        if use_remat:
            from ..utils import remat_call
        for blk in self.blocks:
            x = remat_call(blk, x) if use_remat else blk(x)
        return self.lm_head(self.norm(x))


def llama_model(name="llama_tiny", vocab_size=32000, **kwargs):
    if name not in LLAMA_CONFIGS:
        raise MXNetError(
            f"unknown llama config {name!r}; options {sorted(LLAMA_CONFIGS)}")
    L, U, H, A, KV = LLAMA_CONFIGS[name]
    return LlamaModel(vocab_size=vocab_size, num_layers=L, units=U,
                      hidden=H, heads=A, kv_heads=KV, **kwargs)


def apply_tp_shardings(model, axis="tp"):
    """Megatron tensor-parallel annotation for a LlamaModel — delegates
    to the declarative rule pack (mxnet_tpu.sharding.llama_rules):
    q/k/v + gate/up + lm_head column-parallel, o_proj + down
    row-parallel, the token table over the vocab dim, norms replicated.
    Dense weights are (out_features, in_features)."""
    from ... import sharding as _sh
    _sh.apply_rules(model, _sh.llama_rules(tp=axis))
    return model
