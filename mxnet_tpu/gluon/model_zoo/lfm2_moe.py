"""Hybrid short-convolution + attention MoE decoder through the Gluon
HybridBlock API (``model_type`` ``lfm2_moe``; the benchmark's configuration
``lfm2_24b_a2b`` is one such model): each layer's operator is a gated short
convolution or grouped-query attention, as ``layer_types`` says; the first
``num_dense`` layers' feed-forward is a dense SwiGLU, every later one a
dropless MoE with a sigmoid router and no shared expert.

Pre-norm blocks (RMSNorms whose weight starts at 1, the Hugging Face
``modeling_lfm2_moe`` order)::

    h = x + operator(rms(x))
    y = h + ffn(rms(h))

The embedding has no multiplier; a final norm, then the head, tied to the
embedding.  No bias anywhere.

Short convolution (``ShortConv``): ``[B | C | x] = x W_in``, then
``contrib.gated_short_conv`` (``C * conv(B * x)``, a depthwise causal
convolution of ``conv_taps`` taps), then ``W_out``.

Attention (``Lfm2Attention``): q, k, v projections; q and k pass an RMSNorm
per head; RoPE on the whole head, pairs by half-split; causal grouped-query
attention through ``contrib.masked_att_qkv`` (on a TPU the flash kernel) at
the scale ``1 / sqrt(head_dim)``; ``W_o``.

The MoE (``gluon.contrib.moe.DroplessMoE``): ``s = sigmoid(x W_r)``; the top
k of ``s + b`` (``b`` the expert bias, for the choice alone, no gradient);
the weights ``s`` at the chosen over their sum + 1e-6, times the scaling.

Activations: under ``autograd.is_recording()`` every layer runs under
``gluon.utils.remat_call`` with no policy: a layer keeps its input, and its
backward makes the rest again.  A rule of the model: no argument, no
environment variable.

Children are registered under names the benchmark's region file tells
apart: ``embed``, ``layers/layer<i>/{operator_norm, conv/{in_proj,
gated_conv, out_proj} | attn/{q_proj, k_proj, v_proj, q_norm, k_norm,
o_proj}, ffn_norm, ffn | moe/{router, experts}}``, ``norm``, ``lm_head``
(the tied product with ``embed``'s weight).  A traced forward grows
``mxnet_short_conv_passes_total{layer, kind}`` by the forward passes of a
layer's gated convolution the step will make: two for a layer made again
in the backward (``kind="made_again"``), one for a layer whose activations
are kept (``kind="kept"``).
"""

from __future__ import annotations

from ... import autograd, regions
from ...telemetry import metrics as _metrics
from ..block import HybridBlock
from ..contrib.moe import DroplessMoE, SwiGLU
from ..nn import Embedding
from ..utils import remat_call
from .llama import RMSNorm, _rope
from .mla_moe import _Layers as _PlainLayers
from .mla_moe import _dense

__all__ = ["ShortConv", "Lfm2Attention", "Lfm2DecoderLayer", "Lfm2MoeModel"]


class _GatedConv(HybridBlock):
    """The taps of a gated short convolution, (units, taps), and the op:
    ``[B | C | x]`` (B, L, 3 units) -> ``C * conv(B * x)`` (B, L, units)."""

    def __init__(self, units, taps, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(units, taps),
                                          init=None)

    def hybrid_forward(self, F, bcx, weight):
        return F.contrib.gated_short_conv(bcx, weight)


class ShortConv(HybridBlock):
    """Gated short-convolution operator; x (B, L, units) -> (B, L, units)."""

    def __init__(self, units, conv_taps=3, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = _dense(3 * units, units, "in_proj_")
            self.gated_conv = _GatedConv(units, conv_taps,
                                         prefix="gated_conv_")
            self.out_proj = _dense(units, units, "out_proj_")

    def hybrid_forward(self, F, x):
        return self.out_proj(self.gated_conv(self.in_proj(x)))


class Lfm2Attention(HybridBlock):
    """Causal grouped-query attention with per-head q/k norms and RoPE on
    the whole head; x (B, L, units) -> (B, L, units)."""

    def __init__(self, units, heads, kv_heads, head_dim, rope_base,
                 eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        if heads % kv_heads:
            raise ValueError("heads must be a multiple of kv_heads")
        self._h, self._kv, self._d = heads, kv_heads, head_dim
        self._base = float(rope_base)
        with self.name_scope():
            self.q_proj = _dense(heads * head_dim, units, "q_proj_")
            self.k_proj = _dense(kv_heads * head_dim, units, "k_proj_")
            self.v_proj = _dense(kv_heads * head_dim, units, "v_proj_")
            self.q_norm = RMSNorm(head_dim, eps=eps, prefix="q_norm_")
            self.k_norm = RMSNorm(head_dim, eps=eps, prefix="k_norm_")
            self.o_proj = _dense(units, heads * head_dim, "o_proj_")

    def hybrid_forward(self, F, x):
        B, L, _ = x.shape
        H, KV, D = self._h, self._kv, self._d

        def heads(proj, norm, n):
            h = proj(x).reshape((B, L, n, D))
            if norm is not None:
                h = norm(h)
            return h.transpose((0, 2, 1, 3))

        q = _rope(F, heads(self.q_proj, self.q_norm, H), self._base)
        k = _rope(F, heads(self.k_proj, self.k_norm, KV), self._base)
        ctx = F.contrib.masked_att_qkv(q, k, heads(self.v_proj, None, KV),
                                       None, num_kv_groups=H // KV,
                                       causal=True)
        return self.o_proj(ctx.transpose((0, 2, 1, 3)).reshape((B, L, H * D)))


class Lfm2DecoderLayer(HybridBlock):
    """One pre-norm block: ``conv`` (ShortConv's arguments) or
    ``attention`` (Lfm2Attention's), never both; ``moe=None`` gives the
    dense SwiGLU of width ``dense_hidden``, else ``moe`` holds
    DroplessMoE's arguments."""

    def __init__(self, units, conv=None, attention=None, dense_hidden=None,
                 moe=None, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        if (conv is None) == (attention is None):
            raise ValueError("a layer has conv or attention, one of them")
        with self.name_scope():
            self.operator_norm = RMSNorm(units, eps=eps,
                                         prefix="operator_norm_")
            self.conv = self.attn = None
            if conv is not None:
                self.conv = ShortConv(units, prefix="conv_", **conv)
            else:
                self.attn = Lfm2Attention(units, eps=eps, prefix="attn_",
                                          **attention)
            self.ffn_norm = RMSNorm(units, eps=eps, prefix="ffn_norm_")
            self.ffn = self.moe = None
            if moe is None:
                self.ffn = SwiGLU(units, dense_hidden, prefix="ffn_")
            else:
                self.moe = DroplessMoE(units, num_shared_experts=0,
                                       prefix="moe_", **moe)

    def hybrid_forward(self, F, x):
        operator = self.attn if self.conv is None else self.conv
        h = x + operator(self.operator_norm(x))
        ffn = self.ffn if self.moe is None else self.moe
        return h + ffn(self.ffn_norm(h))


class _Layers(_PlainLayers):
    """The decoder layers in order, under one scope (``layers``); while
    recording each runs under ``remat_call`` and keeps its input alone."""

    def hybrid_forward(self, F, x):
        recording = autograd.is_recording()
        if regions.tracing():
            kind, passes = ("made_again", 2) if recording else ("kept", 1)
            for i, layer in enumerate(self._layers):
                if layer.conv is not None:
                    _metrics.counter(
                        "mxnet_short_conv_passes_total",
                        "Forward passes of a layer's gated short "
                        "convolution a traced step makes: two for a layer "
                        "made again in the backward, one for a kept one.",
                        labels={"layer": f"{regions.current()}/layer{i}",
                                "kind": kind}).inc(passes)
        for layer in self._layers:
            x = remat_call(layer, x) if recording else layer(x)
        return x


class _TiedHead(HybridBlock):
    """``x E^T``: the head's product with the embedding's own weight,
    handed in; it holds no parameter."""

    def __init__(self, vocab_size, **kwargs):
        super().__init__(**kwargs)
        self._vocab = vocab_size

    def hybrid_forward(self, F, x, weight):
        return F.FullyConnected(x, weight, None, num_hidden=self._vocab,
                                flatten=False, no_bias=True)


class Lfm2MoeModel(HybridBlock):
    """tokens (B, L) int32 -> logits (B, L, vocab_size).

    ``layer_types``: ``"conv"`` or ``"full_attention"`` a layer, in order;
    the first ``num_dense`` layers have the dense SwiGLU of width
    ``dense_hidden``, the others the MoE.  ``conv``: ShortConv's arguments
    less ``units`` (``conv_taps``); ``attention``: Lfm2Attention's less
    ``units`` and ``eps`` (``heads``, ``kv_heads``, ``head_dim``,
    ``rope_base``); ``moe``: DroplessMoE's less ``units`` and the shared
    experts (``hidden_size``, ``num_experts``, ``num_experts_per_token``,
    ``experts_held``, ``routed_scaling_factor``, ``norm_topk_prob``,
    ``router_eps``)."""

    def __init__(self, vocab_size, layer_types, units, dense_hidden,
                 num_dense, conv, attention, moe, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            self.layers = _Layers([
                Lfm2DecoderLayer(
                    units, dense_hidden=dense_hidden,
                    moe=None if i < num_dense else moe, eps=eps,
                    prefix=f"layer{i}_",
                    **({"conv": conv} if kind == "conv"
                       else {"attention": attention}))
                for i, kind in enumerate(layer_types)], prefix="")
            self.norm = RMSNorm(units, eps=eps, prefix="norm_")
            self.lm_head = _TiedHead(vocab_size, prefix="lm_head_")

    def hybrid_forward(self, F, tokens):
        return self.lm_head(self.norm(self.layers(self.embed(tokens))),
                            self.embed.weight.data(tokens.ctx))
