"""DeepSeek-V3-shaped decoder through the Gluon HybridBlock API: multi-head
latent attention (MLA) and a dropless mixture-of-experts feed-forward with
shared experts (``model_type`` ``deepseek_v3``; the benchmark's
configuration ``kanana_2_30b_a3b`` is one such model).

Pre-norm blocks: ``x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x))``.  The first
``first_dense`` layers have a dense SwiGLU FFN, the others
``gluon.contrib.moe.DroplessMoE``.  Final RMSNorm, untied head, no bias
anywhere.

MLA in its training form (nothing absorbed, ``q_lora_rank`` null): the query
projects to ``heads`` heads of ``qk_nope + qk_rope``; keys and values come
from one latent of ``kv_lora_rank`` (RMSNorm'ed) expanded per head to
``qk_nope`` + ``v_head``, and one ``qk_rope``-wide rotary key shared by all
heads.  RoPE turns the rope slices only (``llama._rope`` with ``rotate`` and
``interleaved``).  Attention runs through ``contrib.masked_att_qkv`` with
``qk_nope + qk_rope``-wide queries and keys and ``v_head``-wide values,
scaled by the query/key width: on a TPU the flash kernel.

Children are registered under names the benchmark's region file tells apart:
``embed``, ``layers/layer<i>/{attn_norm, mla/{q_proj, kv_a_proj, kv_a_norm,
kv_b_proj, o_proj}, ffn_norm, mlp | moe/{router, experts, shared}}``,
``norm``, ``lm_head``.
"""

from __future__ import annotations

from ..block import HybridBlock
from ..contrib.moe import DroplessMoE, SwiGLU
from ..nn import Dense, Embedding
from .llama import RMSNorm, _rope

__all__ = ["MLAttention", "MLAMoEDecoderLayer", "MLAMoEModel"]


def _dense(out_units, in_units, prefix):
    return Dense(out_units, flatten=False, use_bias=False,
                 in_units=in_units, prefix=prefix)


class MLAttention(HybridBlock):
    """Causal multi-head latent attention, training form."""

    def __init__(self, units, heads, qk_nope, qk_rope, v_head, kv_lora_rank,
                 rope_base=10000.0, rope_interleave=True, eps=1e-6,
                 **kwargs):
        super().__init__(**kwargs)
        self._heads, self._nope, self._rope = heads, qk_nope, qk_rope
        self._v, self._rank = v_head, kv_lora_rank
        self._base, self._interleave = float(rope_base), bool(rope_interleave)
        with self.name_scope():
            self.q_proj = _dense(heads * (qk_nope + qk_rope), units, "q_")
            self.kv_a_proj = _dense(kv_lora_rank + qk_rope, units, "kv_a_")
            self.kv_a_norm = RMSNorm(kv_lora_rank, eps=eps,
                                     prefix="kv_a_norm_")
            self.kv_b_proj = _dense(heads * (qk_nope + v_head), kv_lora_rank,
                                    "kv_b_")
            self.o_proj = _dense(units, heads * v_head, "o_")

    def hybrid_forward(self, F, x):
        B, L, _ = x.shape
        H, nope, rope, dv = self._heads, self._nope, self._rope, self._v
        q = self.q_proj(x).reshape((B, L, H, nope + rope)) \
            .transpose((0, 2, 1, 3))                        # (B, H, L, 192)
        kv_a = self.kv_a_proj(x)                            # (B, L, rank+rope)
        latent = self.kv_a_norm(kv_a[:, :, :self._rank])
        k_rope = kv_a[:, :, self._rank:].reshape((B, 1, L, rope))
        kv = self.kv_b_proj(latent).reshape((B, L, H, nope + dv)) \
            .transpose((0, 2, 1, 3))                        # (B, H, L, 256)
        q = _rope(F, q, self._base, rotate=(nope, nope + rope),
                  interleaved=self._interleave)
        k_rope = _rope(F, k_rope, self._base, interleaved=self._interleave)
        k = F.concat(kv[:, :, :, :nope],
                     F.broadcast_to(k_rope, shape=(B, H, L, rope)), dim=-1)
        v = kv[:, :, :, nope:]
        ctx = F.contrib.masked_att_qkv(q, k, v, None, causal=True)
        return self.o_proj(ctx.transpose((0, 2, 1, 3))
                           .reshape((B, L, H * dv)))


class MLAMoEDecoderLayer(HybridBlock):
    """One pre-norm block; ``moe=None`` gives the dense SwiGLU of width
    ``dense_hidden``, else ``moe`` holds ``DroplessMoE``'s arguments."""

    def __init__(self, units, attention, dense_hidden=None, moe=None,
                 eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps=eps, prefix="attn_norm_")
            self.mla = MLAttention(units, eps=eps, prefix="mla_", **attention)
            self.ffn_norm = RMSNorm(units, eps=eps, prefix="ffn_norm_")
            if moe is None:
                self.mlp = SwiGLU(units, dense_hidden, prefix="mlp_")
                self.moe = None
            else:
                self.mlp = None
                self.moe = DroplessMoE(units, prefix="moe_", **moe)

    def hybrid_forward(self, F, x):
        x = x + self.mla(self.attn_norm(x))
        ffn = self.mlp if self.moe is None else self.moe
        return x + ffn(self.ffn_norm(x))


class _Layers(HybridBlock):
    """The decoder layers in order, under one scope (``layers``)."""

    def __init__(self, layers, **kwargs):
        super().__init__(**kwargs)
        self._layers = layers
        for i, layer in enumerate(layers):
            self.register_child(layer, f"layer{i}")

    def hybrid_forward(self, F, x):
        for layer in self._layers:
            x = layer(x)
        return x


class MLAMoEModel(HybridBlock):
    """tokens (B, L) int32 -> logits (B, L, vocab_size).

    ``attention``: MLAttention's arguments less ``units``; ``moe``:
    DroplessMoE's less ``units`` (``hidden_size``, ``num_experts``,
    ``num_experts_per_token``, ``experts_held``, ``num_shared_experts``,
    ``routed_scaling_factor``, ``norm_topk_prob``)."""

    def __init__(self, vocab_size, num_layers, units, dense_hidden,
                 attention, moe, first_dense=1, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="tok_")
            self.layers = _Layers([
                MLAMoEDecoderLayer(
                    units, attention, dense_hidden=dense_hidden,
                    moe=None if i < first_dense else moe, eps=eps,
                    prefix=f"layer{i}_")
                for i in range(num_layers)], prefix="")
            self.norm = RMSNorm(units, eps=eps, prefix="final_norm_")
            self.lm_head = _dense(vocab_size, units, "lm_head_")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.norm(self.layers(self.embed(tokens))))
