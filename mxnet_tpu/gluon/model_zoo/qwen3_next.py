"""Hybrid linear-attention MoE decoder through the Gluon HybridBlock API
(``model_type`` ``qwen3_next``; the benchmark's configuration
``qwen3_next_80b_a3b`` is one such model): of every
``full_attention_interval`` layers the last runs gated softmax attention,
the others a Gated DeltaNet mixer; every layer's feed-forward is a dropless
MoE with a softmax router and a sigmoid-gated shared expert.

Pre-norm blocks: ``x += mixer(norm(x)); x += moe(norm(x))``; the norm is
zero-centred, ``x * rsqrt(mean(x^2) + eps) * (1 + w)`` with ``w`` starting
at 0 (``llama.RMSNorm(zero_centered=True)``).  Final norm, untied head, no
bias anywhere.

Gated DeltaNet (``GatedDeltaNet``): ``[q | k | v | z] = x W_qkvz`` and
``[b | a] = x W_ba``; q, k, v pass a depthwise causal convolution
(``contrib.causal_conv1d``) and SiLU; q and k are l2-normed per head (q
also divided by the root of its width) and repeated to the value heads
(value heads ``r j … r j + r - 1`` share key head ``j``); ``beta =
sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` per value head in
float32; the recurrence runs as a chunked scan
(``contrib.gated_delta_rule``); the output is RMS-normed per head, scaled,
multiplied by ``silu(z)`` and projected.

Gated attention (``GatedAttention``): the query projection gives each head
``[query | gate]``; q and k pass a per-head zero-centred RMSNorm, then
rotary on the first ``rotary_dim`` dims (half-split pairing); causal
grouped-query attention through ``contrib.masked_att_qkv`` (on a TPU the
flash kernel); the context is multiplied by ``sigmoid(gate)`` and
projected.

Children are registered under names the benchmark's region file tells
apart: ``embed``, ``layers/layer<i>/{attn_norm, gdn/{in_proj, conv, scan,
gate_norm, out_proj} | attn/{q_proj, k_proj, v_proj, q_norm, k_norm,
o_proj}, ffn_norm, moe/{router, experts, shared, shared_gate}}``, ``norm``,
``lm_head``.  A traced forward grows ``mxnet_gdn_chunks_total{layer}`` by
the chunks of its scan over batch and heads.
"""

from __future__ import annotations

from ... import regions
from ...telemetry import metrics as _metrics
from ..block import HybridBlock
from ..contrib.moe import DroplessMoE
from ..nn import Embedding
from .llama import RMSNorm, _rope
from .mla_moe import _Layers, _dense

__all__ = ["GatedDeltaNet", "GatedAttention", "Qwen3NextDecoderLayer",
           "Qwen3NextModel"]

_CHUNK = 64     # the published chunk size of the scan


class _InProj(HybridBlock):
    """``x -> (x W_qkvz, x W_ba)``."""

    def __init__(self, units, qkvz, ba, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.qkvz = _dense(qkvz, units, "qkvz_")
            self.ba = _dense(ba, units, "ba_")

    def hybrid_forward(self, F, x):
        return self.qkvz(x), self.ba(x)


class _ConvSiLU(HybridBlock):
    """Depthwise causal convolution of ``taps`` taps along a row, then
    SiLU; (B, L, channels) in and out."""

    def __init__(self, channels, taps, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(channels, taps),
                                          init=None)

    def hybrid_forward(self, F, x, weight):
        return F.silu(F.contrib.causal_conv1d(x, weight))


class _Scan(HybridBlock):
    """The recurrence of a Gated DeltaNet mixer: holds the per-head decay
    parameters, norms q and k, and runs ``contrib.gated_delta_rule``."""

    def __init__(self, key_heads, value_heads, key_dim, value_dim, **kwargs):
        super().__init__(**kwargs)
        self._hk, self._hv = key_heads, value_heads
        self._dk, self._dv = key_dim, value_dim
        with self.name_scope():
            self.A_log = self.params.get("A_log", shape=(value_heads,),
                                         init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(value_heads,),
                                           init="ones")

    def _l2norm(self, F, x):
        xf = x.astype("float32")
        return xf * F.rsqrt((xf * xf).sum(axis=-1, keepdims=True) + 1e-6)

    def hybrid_forward(self, F, qkv, ba, A_log, dt_bias):
        B, L, _ = qkv.shape
        hk, hv, dk, dv = self._hk, self._hv, self._dk, self._dv
        keys = hk * dk
        q = qkv[:, :, :keys].reshape((B, L, hk, dk))
        k = qkv[:, :, keys:2 * keys].reshape((B, L, hk, dk))
        v = qkv[:, :, 2 * keys:].reshape((B, L, hv, dv))
        q = (self._l2norm(F, q) * dk ** -0.5).astype(qkv.dtype)
        k = self._l2norm(F, k).astype(qkv.dtype)
        if hv > hk:
            q = F.repeat(q, repeats=hv // hk, axis=2)
            k = F.repeat(k, repeats=hv // hk, axis=2)
        ba = ba.astype("float32")
        beta = F.sigmoid(ba[:, :, :hv])
        g = -F.exp(A_log.astype("float32")) \
            * F.softrelu(ba[:, :, hv:] + dt_bias.astype("float32"))
        if regions.tracing():
            _metrics.counter(
                "mxnet_gdn_chunks_total",
                "Chunks of the gated delta rule's scan over batch and "
                "heads, a traced forward of a layer.",
                labels={"layer": regions.current()}).inc(
                    B * hv * -(-L // _CHUNK))
        return F.contrib.gated_delta_rule(q, k, v, g, beta, chunk=_CHUNK)


class _GatedRMSNorm(HybridBlock):
    """``(rmsnorm(o) * w) * silu(z)`` over the last dim, float32 inside;
    ``w`` starts at 1."""

    def __init__(self, units, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(units,),
                                          init="ones")

    def hybrid_forward(self, F, o, z, weight):
        of = o.astype("float32")
        var = (of * of).mean(axis=-1, keepdims=True)
        out = of * F.rsqrt(var + self._eps) * weight.astype("float32")
        return (out * F.silu(z.astype("float32"))).astype(o.dtype)


class GatedDeltaNet(HybridBlock):
    """Gated DeltaNet mixer; x (B, L, units) -> (B, L, units)."""

    def __init__(self, units, key_heads, value_heads, key_dim, value_dim,
                 conv_taps=4, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if value_heads % key_heads:
            raise ValueError("value_heads must be a multiple of key_heads")
        self._hv, self._dv = value_heads, value_dim
        keys, values = key_heads * key_dim, value_heads * value_dim
        self._conv_width = 2 * keys + values
        with self.name_scope():
            self.in_proj = _InProj(units, 2 * keys + 2 * values,
                                   2 * value_heads, prefix="in_")
            self.conv = _ConvSiLU(self._conv_width, conv_taps,
                                  prefix="conv_")
            self.scan = _Scan(key_heads, value_heads, key_dim, value_dim,
                              prefix="scan_")
            self.gate_norm = _GatedRMSNorm(value_dim, eps,
                                           prefix="gate_norm_")
            self.out_proj = _dense(units, values, "out_")

    def hybrid_forward(self, F, x):
        B, L, _ = x.shape
        qkvz, ba = self.in_proj(x)
        o = self.scan(self.conv(qkvz[:, :, :self._conv_width]), ba)
        z = qkvz[:, :, self._conv_width:].reshape((B, L, self._hv, self._dv))
        return self.out_proj(self.gate_norm(o, z)
                             .reshape((B, L, self._hv * self._dv)))


class GatedAttention(HybridBlock):
    """Causal grouped-query attention with per-head q/k norms, partial
    rotary and a sigmoid output gate; x (B, L, units) -> (B, L, units)."""

    def __init__(self, units, heads, kv_heads, head_dim, rotary_dim,
                 rope_base=10000.0, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        if heads % kv_heads:
            raise ValueError("heads must be a multiple of kv_heads")
        self._h, self._kv, self._d = heads, kv_heads, head_dim
        self._rot, self._base = int(rotary_dim), float(rope_base)
        with self.name_scope():
            self.q_proj = _dense(heads * 2 * head_dim, units, "q_")
            self.k_proj = _dense(kv_heads * head_dim, units, "k_")
            self.v_proj = _dense(kv_heads * head_dim, units, "v_")
            self.q_norm = RMSNorm(head_dim, eps=eps, zero_centered=True,
                                  prefix="q_norm_")
            self.k_norm = RMSNorm(head_dim, eps=eps, zero_centered=True,
                                  prefix="k_norm_")
            self.o_proj = _dense(units, heads * head_dim, "o_")

    def hybrid_forward(self, F, x):
        B, L, _ = x.shape
        H, KV, D = self._h, self._kv, self._d
        qg = self.q_proj(x).reshape((B, L, H, 2 * D))       # [query | gate]
        gate = qg[:, :, :, D:].reshape((B, L, H * D))
        q = self.q_norm(qg[:, :, :, :D]).transpose((0, 2, 1, 3))
        k = self.k_norm(self.k_proj(x).reshape((B, L, KV, D))) \
            .transpose((0, 2, 1, 3))
        v = self.v_proj(x).reshape((B, L, KV, D)).transpose((0, 2, 1, 3))
        q = _rope(F, q, self._base, rotate=(0, self._rot))
        k = _rope(F, k, self._base, rotate=(0, self._rot))
        ctx = F.contrib.masked_att_qkv(q, k, v, None, num_kv_groups=H // KV,
                                       causal=True)
        ctx = ctx.transpose((0, 2, 1, 3)).reshape((B, L, H * D))
        return self.o_proj(ctx * F.sigmoid(gate))


class Qwen3NextDecoderLayer(HybridBlock):
    """One pre-norm block: ``attention`` (GatedAttention's arguments) or
    ``linear`` (GatedDeltaNet's), never both, and the MoE feed-forward."""

    def __init__(self, units, moe, attention=None, linear=None, eps=1e-6,
                 **kwargs):
        super().__init__(**kwargs)
        if (attention is None) == (linear is None):
            raise ValueError("a layer has attention or linear, one of them")
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps=eps, zero_centered=True,
                                     prefix="attn_norm_")
            self.attn = self.gdn = None
            if attention is not None:
                self.attn = GatedAttention(units, eps=eps, prefix="attn_",
                                           **attention)
            else:
                self.gdn = GatedDeltaNet(units, eps=eps, prefix="gdn_",
                                         **linear)
            self.ffn_norm = RMSNorm(units, eps=eps, zero_centered=True,
                                    prefix="ffn_norm_")
            self.moe = DroplessMoE(units, score="softmax", shared_gate=True,
                                   prefix="moe_", **moe)

    def hybrid_forward(self, F, x):
        mixer = self.gdn if self.attn is None else self.attn
        x = x + mixer(self.attn_norm(x))
        return x + self.moe(self.ffn_norm(x))


class Qwen3NextModel(HybridBlock):
    """tokens (B, L) int32 -> logits (B, L, vocab_size).

    Layer ``i`` runs full attention iff ``(i + 1) % full_attention_interval
    == 0``.  ``attention``: GatedAttention's arguments less ``units``
    (``heads``, ``kv_heads``, ``head_dim``, ``rotary_dim``, ``rope_base``);
    ``linear``: GatedDeltaNet's (``key_heads``, ``value_heads``,
    ``key_dim``, ``value_dim``, ``conv_taps``); ``moe``: DroplessMoE's less
    ``units``, the score and the shared gate (``hidden_size``,
    ``num_experts``, ``num_experts_per_token``, ``experts_held``,
    ``num_shared_experts``, ``norm_topk_prob``)."""

    def __init__(self, vocab_size, num_layers, units, attention, linear, moe,
                 full_attention_interval=4, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="tok_")
            self.layers = _Layers([
                Qwen3NextDecoderLayer(
                    units, moe, eps=eps, prefix=f"layer{i}_",
                    **({"attention": attention}
                       if (i + 1) % full_attention_interval == 0
                       else {"linear": linear}))
                for i in range(num_layers)], prefix="")
            self.norm = RMSNorm(units, eps=eps, zero_centered=True,
                                prefix="final_norm_")
            self.lm_head = _dense(vocab_size, units, "lm_head_")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.norm(self.layers(self.embed(tokens))))
