"""Hybrid Mamba-2 + attention decoder through the Gluon HybridBlock API
(``model_type`` ``granitemoehybrid`` with no routed experts; the
benchmark's configuration ``granite_4_0_h_micro`` is one such model): each
layer's mixer is a Mamba-2 state-space mixer or grouped-query attention, as
``layer_types`` says, and every layer has the same SwiGLU.

Pre-norm blocks with a scalar on each branch (RMSNorms, the Hugging Face
``modeling_granitemoehybrid`` order)::

    a = x + r * mixer(rms(x))
    y = a + r * MLP(rms(a))           r = residual_multiplier

``h_0 = E[tokens] * embedding_multiplier``; ``logits = rms(h) E^T /
logits_scaling``, the head tied to the embedding.  ``MLP(x) = (silu(g) *
u) W_out`` with ``[g | u] = x W_in``.  No bias but the convolution's.

Mamba-2 mixer (``Mamba2Mixer``): ``[z | xBC | dt] = x W_in``; ``xBC =
silu(causal_conv1d(xBC) + b)``, depthwise; ``xBC`` splits into x (heads of
``head_dim``), B and C (``groups`` groups of ``state``); ``dt =
softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` a head, float32; the
scan runs as ``contrib.ssd_scan`` (``y = h C + D x``, ``h`` carried); ``o =
rmsnorm(y * silu(z)) * w``: the gate first, then one norm over all the
inner channels; ``W_out``.

Attention (``GraniteAttention``): q, k, v projections, no position
encoding, causal grouped-query attention through ``contrib.masked_att_qkv``
(on a TPU the flash kernel) at the softmax scale ``attention_multiplier``:
q is multiplied by ``attention_multiplier * sqrt(head_dim)`` and the op
divides by ``sqrt(head_dim)``; ``W_o``.

Activations: under ``autograd.is_recording()`` every layer runs under
``gluon.utils.remat_call`` with no policy: a layer keeps its input, and its
backward makes the rest again.  A rule of the model: no argument, no
environment variable.

Children are registered under names the benchmark's region file tells
apart: ``embed``, ``layers/layer<i>/{in_norm, mamba/{in_proj, conv, scan,
norm, out_proj} | attn/{q_proj, k_proj, v_proj, o_proj}, post_norm,
mlp/{input_linear, output_linear}}``, ``norm``, ``lm_head`` (the tied
product with ``embed``'s weight).  A traced forward grows
``mxnet_ssd_chunks_total{layer}`` by the chunks of its scan over batch and
heads.
"""

from __future__ import annotations

from ... import autograd, regions
from ...telemetry import metrics as _metrics
from ..block import HybridBlock
from ..nn import Embedding
from ..utils import remat_call
from .llama import RMSNorm
from .mla_moe import _Layers as _PlainLayers
from .mla_moe import _dense

__all__ = ["Mamba2Mixer", "GraniteAttention", "GraniteMLP",
           "GraniteDecoderLayer", "GraniteHybridModel"]


class _ConvSiLU(HybridBlock):
    """Depthwise causal convolution of ``taps`` taps along a row, its bias,
    then SiLU; (B, L, channels) in and out."""

    def __init__(self, channels, taps, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(channels, taps),
                                          init=None)
            self.bias = self.params.get("bias", shape=(channels,),
                                        init="zeros")

    def hybrid_forward(self, F, x, weight, bias):
        return F.silu(F.contrib.causal_conv1d(x, weight) + bias)


class _Scan(HybridBlock):
    """The recurrence of a Mamba-2 mixer: holds the per-head ``A_log``,
    ``D`` and ``dt_bias``, splits x, B and C, and runs
    ``contrib.ssd_scan``."""

    def __init__(self, heads, head_dim, groups, state, chunk, **kwargs):
        super().__init__(**kwargs)
        self._h, self._p, self._g, self._n = heads, head_dim, groups, state
        self._chunk = int(chunk)
        with self.name_scope():
            self.A_log = self.params.get("A_log", shape=(heads,),
                                         init="zeros")
            self.D = self.params.get("D", shape=(heads,), init="ones")
            self.dt_bias = self.params.get("dt_bias", shape=(heads,),
                                           init="ones")

    def hybrid_forward(self, F, xbc, dt, A_log, D, dt_bias):
        B_, L, _ = xbc.shape
        h, p, g, n = self._h, self._p, self._g, self._n
        inner = h * p
        x = xbc[:, :, :inner].reshape((B_, L, h, p))
        b = xbc[:, :, inner:inner + g * n].reshape((B_, L, g, n))
        c = xbc[:, :, inner + g * n:].reshape((B_, L, g, n))
        dt = F.softrelu(dt.astype("float32") + dt_bias.astype("float32"))
        a = -F.exp(A_log.astype("float32"))
        if regions.tracing():
            _metrics.counter(
                "mxnet_ssd_chunks_total",
                "Chunks of the Mamba-2 scan over batch and heads, a traced "
                "forward of a layer.",
                labels={"layer": regions.current()}).inc(
                    B_ * h * -(-L // self._chunk))
        y = F.contrib.ssd_scan(x, dt, a, b, c, D.astype("float32"),
                               chunk=self._chunk)
        return y.reshape((B_, L, inner))


class _GatedNorm(HybridBlock):
    """``rmsnorm(y * silu(z)) * w`` over all of the last dim: the gate
    first, then the norm (one group); float32 inside, ``w`` starts at 1."""

    def __init__(self, units, eps, **kwargs):
        super().__init__(**kwargs)
        self._eps = eps
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(units,),
                                          init="ones")

    def hybrid_forward(self, F, y, z, weight):
        gated = y.astype("float32") * F.silu(z.astype("float32"))
        var = (gated * gated).mean(axis=-1, keepdims=True)
        return (gated * F.rsqrt(var + self._eps)
                * weight.astype("float32")).astype(y.dtype)


class Mamba2Mixer(HybridBlock):
    """Mamba-2 mixer; x (B, L, units) -> (B, L, units)."""

    def __init__(self, units, heads, head_dim, groups, state, conv_taps=4,
                 chunk=256, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        if heads % groups:
            raise ValueError("heads must be a multiple of groups")
        self._inner = heads * head_dim
        self._conv = self._inner + 2 * groups * state
        with self.name_scope():
            self.in_proj = _dense(self._inner + self._conv + heads, units,
                                  "in_proj_")
            self.conv = _ConvSiLU(self._conv, conv_taps, prefix="conv_")
            self.scan = _Scan(heads, head_dim, groups, state, chunk,
                              prefix="scan_")
            self.norm = _GatedNorm(self._inner, eps, prefix="norm_")
            self.out_proj = _dense(units, self._inner, "out_proj_")

    def hybrid_forward(self, F, x):
        zxd = self.in_proj(x)                       # [z | xBC | dt]
        inner, conv = self._inner, self._conv
        y = self.scan(self.conv(zxd[:, :, inner:inner + conv]),
                      zxd[:, :, inner + conv:])
        return self.out_proj(self.norm(y, zxd[:, :, :inner]))


class GraniteAttention(HybridBlock):
    """Causal grouped-query attention with no position encoding and the
    softmax scale ``multiplier``; x (B, L, units) -> (B, L, units)."""

    def __init__(self, units, heads, kv_heads, head_dim, multiplier,
                 **kwargs):
        super().__init__(**kwargs)
        if heads % kv_heads:
            raise ValueError("heads must be a multiple of kv_heads")
        self._h, self._kv, self._d = heads, kv_heads, head_dim
        # the op divides by sqrt(head_dim): q times this gives `multiplier`
        self._q_scale = float(multiplier) * head_dim ** 0.5
        with self.name_scope():
            self.q_proj = _dense(heads * head_dim, units, "q_proj_")
            self.k_proj = _dense(kv_heads * head_dim, units, "k_proj_")
            self.v_proj = _dense(kv_heads * head_dim, units, "v_proj_")
            self.o_proj = _dense(units, heads * head_dim, "o_proj_")

    def hybrid_forward(self, F, x):
        B, L, _ = x.shape
        H, KV, D = self._h, self._kv, self._d

        def heads(proj, n):
            return proj(x).reshape((B, L, n, D)).transpose((0, 2, 1, 3))

        q = heads(self.q_proj, H) * self._q_scale
        ctx = F.contrib.masked_att_qkv(q, heads(self.k_proj, KV),
                                       heads(self.v_proj, KV), None,
                                       num_kv_groups=H // KV, causal=True)
        return self.o_proj(ctx.transpose((0, 2, 1, 3)).reshape((B, L, H * D)))


class GraniteMLP(HybridBlock):
    """``(silu(g) * u) W_out`` with ``[g | u] = x W_in``."""

    def __init__(self, units, hidden, **kwargs):
        super().__init__(**kwargs)
        self._hidden = hidden
        with self.name_scope():
            self.input_linear = _dense(2 * hidden, units, "input_linear_")
            self.output_linear = _dense(units, hidden, "output_linear_")

    def hybrid_forward(self, F, x):
        gu = self.input_linear(x)
        return self.output_linear(F.silu(gu[:, :, :self._hidden])
                                  * gu[:, :, self._hidden:])


class GraniteDecoderLayer(HybridBlock):
    """One pre-norm block with scaled branches: ``mamba`` (Mamba2Mixer's
    arguments) or ``attention`` (GraniteAttention's), never both, and the
    SwiGLU."""

    def __init__(self, units, hidden, residual_multiplier, mamba=None,
                 attention=None, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        if (mamba is None) == (attention is None):
            raise ValueError("a layer has mamba or attention, one of them")
        self._r = float(residual_multiplier)
        with self.name_scope():
            self.in_norm = RMSNorm(units, eps=eps, prefix="in_norm_")
            self.mamba = self.attn = None
            if mamba is not None:
                self.mamba = Mamba2Mixer(units, eps=eps, prefix="mamba_",
                                         **mamba)
            else:
                self.attn = GraniteAttention(units, prefix="attn_",
                                             **attention)
            self.post_norm = RMSNorm(units, eps=eps, prefix="post_norm_")
            self.mlp = GraniteMLP(units, hidden, prefix="mlp_")

    def hybrid_forward(self, F, x):
        mixer = self.attn if self.mamba is None else self.mamba
        a = x + self._r * mixer(self.in_norm(x))
        return a + self._r * self.mlp(self.post_norm(a))


class _Layers(_PlainLayers):
    """The decoder layers in order, under one scope (``layers``); while
    recording each runs under ``remat_call`` and keeps its input alone."""

    def hybrid_forward(self, F, x):
        recording = autograd.is_recording()
        for layer in self._layers:
            x = remat_call(layer, x) if recording else layer(x)
        return x


class _TiedHead(HybridBlock):
    """``x E^T / scaling``: the head's product with the embedding's own
    weight, handed in; it holds no parameter."""

    def __init__(self, vocab_size, scaling, **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._scaling = vocab_size, float(scaling)

    def hybrid_forward(self, F, x, weight):
        logits = F.FullyConnected(x, weight, None, num_hidden=self._vocab,
                                  flatten=False, no_bias=True)
        return logits / self._scaling


class GraniteHybridModel(HybridBlock):
    """tokens (B, L) int32 -> logits (B, L, vocab_size).

    ``layer_types``: ``"mamba"`` or ``"attention"`` a layer, in order.
    ``mamba``: Mamba2Mixer's arguments less ``units`` and ``eps``
    (``heads``, ``head_dim``, ``groups``, ``state``, ``conv_taps``,
    ``chunk``: the scan's chunk of positions);
    ``attention``: GraniteAttention's less ``units`` (``heads``,
    ``kv_heads``, ``head_dim``, ``multiplier``)."""

    def __init__(self, vocab_size, layer_types, units, hidden, mamba,
                 attention, embedding_multiplier, residual_multiplier,
                 logits_scaling, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._embed_scale = float(embedding_multiplier)
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="embed_")
            self.layers = _Layers([
                GraniteDecoderLayer(
                    units, hidden, residual_multiplier, eps=eps,
                    prefix=f"layer{i}_",
                    **({"mamba": mamba} if kind == "mamba"
                       else {"attention": attention}))
                for i, kind in enumerate(layer_types)], prefix="")
            self.norm = RMSNorm(units, eps=eps, prefix="final_norm_")
            self.lm_head = _TiedHead(vocab_size, logits_scaling,
                                     prefix="lm_head_")

    def hybrid_forward(self, F, tokens):
        x = self.embed(tokens) * self._embed_scale
        return self.lm_head(self.norm(self.layers(x)),
                            self.embed.weight.data(tokens.ctx))
