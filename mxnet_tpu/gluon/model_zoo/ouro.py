"""Looped language model through the Gluon HybridBlock API (``model_type``
``ouro``, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; the benchmark's configuration ``ouro_2_6b`` is one such
model): ONE stack of decoder layers run ``loop_steps`` times over the same
weights, the model's final norm at the end of every loop step, an exit
gate that reads every step's output, and a loss over all the exits.

Decoder layer, sandwich norms (four RMSNorms a layer, the Hugging Face
``modeling_ouro`` order)::

    a = x + N2(Attn(N1(x)))
    y = a + N4(W_down(silu(W_gate N3(a)) * W_up N3(a)))

``Attn``: q, k, v projections split in ``heads`` heads of ``head_dim``
(no grouping), rotate-half rotary on q and k over the whole head, causal
softmax attention through ``contrib.masked_att_qkv`` (on a TPU the flash
kernel), heads merged, ``W_o``; no bias anywhere.

The loop: ``h_0 = E[tokens]``; ``h_t = Nf(M(h_{t-1}))`` for ``t = 1 …
loop_steps``, ``M`` the same layers every time; ``logits_t = h_t W_head``.
The exit gate, one ``Linear(units, 1)`` with bias shared by the steps:
``lam_t = sigmoid(h_t w_g + b_g)``, ``S_0 = 1``, ``S_t = S_{t-1} (1 -
lam_t)``, ``p_t = lam_t S_{t-1}`` and the last step takes what is left,
``p_T = S_{T-1}``: a position's ``p`` sums to 1.  ``expected_exit_loss`` is
the paper's training loss: ``sum_t p_t CE(logits_t, label) - beta H(p)`` a
position, the mean over positions.

Activations: a step's activations are ``loop_steps`` times as deep as its
weights.  Under ``autograd.is_recording()`` the layer applications of the
loop steps before the last run under ``gluon.utils.remat_call`` with the
policy ``_KEEP`` (kept: the application's input, its seven products and
the attention kernel's output and ``lse``; the norms, RoPE, the head split
and merge, the SwiGLU's product and the residual sums are made again in
the backward), the last step's are called plainly, since their backward
runs first.  A rule of the model: no argument, no environment variable.

Children are registered under names the benchmark's region file tells
apart: ``embed``, ``layers/layer<i>/{attn/{in_norm, q_proj, k_proj,
v_proj, o_proj, out_norm}, mlp/{in_norm, gate, up, down, out_norm}}``,
``norm``, ``lm_head``, ``exit_gate``; each loop step's layers and norm run
under the scope ``loop<t>``, so an instruction reads
``ouro/loop2/layers/layer3/attn/…``.  A traced forward grows
``mxnet_loop_layer_passes_total{model, kind="kept"}`` by one an
application: every application's products and attention kernel run once,
under ``remat_call`` too (what its backward makes again is elementwise).
Under a ``parallel.TrainStep`` the gate
reports ``mxnet_loop_exit_mass{step}``, the mean of ``p_t`` over the
positions of the dispatch fetched last.
"""

from __future__ import annotations

from jax import checkpoint_policies

from ... import autograd, regions
from ...telemetry import metrics as _metrics
from ..block import HybridBlock
from ..nn import Dense, Embedding
from ..utils import remat_call
from .llama import RMSNorm, _rope
from .mla_moe import _Layers as _PlainLayers
from .mla_moe import _dense

__all__ = ["OuroAttention", "OuroMLP", "OuroDecoderLayer", "OuroModel",
           "expected_exit_loss"]


class OuroAttention(HybridBlock):
    """``N2(Attn(N1(x)))``; x (B, L, units) -> (B, L, units)."""

    def __init__(self, units, heads, head_dim, eps=1e-6, rope_base=1e6,
                 **kwargs):
        super().__init__(**kwargs)
        self._h, self._d, self._base = heads, head_dim, float(rope_base)
        with self.name_scope():
            self.in_norm = RMSNorm(units, eps=eps, prefix="in_norm_")
            self.q_proj = _dense(heads * head_dim, units, "q_")
            self.k_proj = _dense(heads * head_dim, units, "k_")
            self.v_proj = _dense(heads * head_dim, units, "v_")
            self.o_proj = _dense(units, heads * head_dim, "o_")
            self.out_norm = RMSNorm(units, eps=eps, prefix="out_norm_")

    def hybrid_forward(self, F, x):
        B, L, _ = x.shape
        H, D = self._h, self._d
        h = self.in_norm(x)

        def heads(proj):
            return proj(h).reshape((B, L, H, D)).transpose((0, 2, 1, 3))

        q = _rope(F, heads(self.q_proj), self._base)
        k = _rope(F, heads(self.k_proj), self._base)
        ctx = F.contrib.masked_att_qkv(q, k, heads(self.v_proj), None,
                                       num_kv_groups=1, causal=True)
        ctx = ctx.transpose((0, 2, 1, 3)).reshape((B, L, H * D))
        return self.out_norm(self.o_proj(ctx))


class OuroMLP(HybridBlock):
    """``N4(W_down(silu(W_gate N3(a)) * W_up N3(a)))``."""

    def __init__(self, units, hidden, eps=1e-6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_norm = RMSNorm(units, eps=eps, prefix="in_norm_")
            self.gate = _dense(hidden, units, "gate_")
            self.up = _dense(hidden, units, "up_")
            self.down = _dense(units, hidden, "down_")
            self.out_norm = RMSNorm(units, eps=eps, prefix="out_norm_")

    def hybrid_forward(self, F, a):
        h = self.in_norm(a)
        return self.out_norm(self.down(F.silu(self.gate(h)) * self.up(h)))


class OuroDecoderLayer(HybridBlock):
    """One sandwich-norm block: ``a = x + attn(x); y = a + mlp(a)``, the
    norms inside the two children."""

    def __init__(self, units, hidden, heads, head_dim, eps=1e-6,
                 rope_base=1e6, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn = OuroAttention(units, heads, head_dim, eps=eps,
                                      rope_base=rope_base, prefix="attn_")
            self.mlp = OuroMLP(units, hidden, eps=eps, prefix="mlp_")

    def hybrid_forward(self, F, x):
        a = x + self.attn(x)
        return a + self.mlp(a)


# What a made-again application keeps beside its input: every product with
# no batch dimensions (q, k, v, o, gate, up, down) and the attention
# kernel's two outputs.
_KEEP = checkpoint_policies.save_from_both_policies(
    checkpoint_policies.dots_with_no_batch_dims_saveable,
    checkpoint_policies.save_only_these_names("flash_out", "flash_lse"))


class _Layers(_PlainLayers):
    """The decoder layers in order, under one scope (``layers``);
    ``made_again``: each application under ``remat_call`` keeping its
    products and the attention kernel's outputs (``_KEEP``)."""

    def hybrid_forward(self, F, x, made_again=False):
        if regions.tracing():
            _metrics.counter(
                "mxnet_loop_layer_passes_total",
                "Forward passes of a decoder layer's products and attention "
                "a traced step of a looped model makes: one an application.",
                labels={"model": regions.current().split("/")[0],
                        "kind": "kept"}).inc(len(self._layers))
        for layer in self._layers:
            x = remat_call(layer, x, keep=_KEEP) if made_again else layer(x)
        return x


class _ExitGate(HybridBlock):
    """The exit distribution of stacked step outputs: hs (T, B, L, units)
    -> p (T, B, L) float32, a position's T values summing to 1."""

    def __init__(self, units, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.proj = Dense(1, flatten=False, use_bias=True,
                              in_units=units, prefix="")

    def hybrid_forward(self, F, hs):
        T, B, L, _ = hs.shape
        lam = F.sigmoid(self.proj(hs).astype("float32").reshape((T, B, L)))
        left, p = F.ones_like(lam[0]), []   # left: S_{t-1}, not yet exited
        for t in range(T - 1):
            p.append(lam[t] * left)
            left = left * (1.0 - lam[t])
        p.append(left)
        return F.stack(*p, axis=0)


class OuroModel(HybridBlock):
    """tokens (B, L) int32 -> ``(logits, p)``: the ``loop_steps`` exits'
    logits stacked (T, B, L, vocab_size) and the exit distribution (T, B,
    L) float32, what ``expected_exit_loss`` takes."""

    def __init__(self, vocab_size, num_layers, units, hidden, heads,
                 head_dim, loop_steps=4, eps=1e-6, rope_base=1e6, **kwargs):
        super().__init__(**kwargs)
        self._steps = int(loop_steps)
        with self.name_scope():
            self.embed = Embedding(vocab_size, units, prefix="tok_")
            self.layers = _Layers([
                OuroDecoderLayer(units, hidden, heads, head_dim, eps=eps,
                                 rope_base=rope_base, prefix=f"layer{i}_")
                for i in range(num_layers)], prefix="")
            self.norm = RMSNorm(units, eps=eps, prefix="final_norm_")
            self.lm_head = _dense(vocab_size, units, "lm_head_")
            self.exit_gate = _ExitGate(units, prefix="exit_gate_")

    def hybrid_forward(self, F, tokens):
        from ... import parallel
        recording = autograd.is_recording()
        x, exits = self.embed(tokens), []
        for t in range(1, self._steps + 1):
            with regions.scope(f"loop{t}"):
                x = self.norm(self.layers(
                    x, made_again=recording and t < self._steps))
            exits.append(x)
        hs = F.stack(*exits, axis=0)
        p = self.exit_gate(hs)
        for t in range(self._steps):
            parallel.report_counter("mxnet_loop_exit_mass", p[t],
                                    labels={"step": str(t + 1)}, kind="mean")
        return self.lm_head(hs), p


def expected_exit_loss(out, labels, beta):
    """The entropy-regularised expected loss over the exit step of what
    ``OuroModel`` returns against ``labels`` (B, L): the mean over
    positions of ``sum_t p_t CE(logits_t, label) - beta H(p)``, ``H(p) =
    -sum_t p_t log p_t``.  The cross-entropy is taken a row at a time
    (``softmax_cross_entropy(per_row=True)``: the logits as they came, a
    per-row logsumexp and the label are all it keeps)."""
    from ... import nd
    logits, p = out
    steps, vocab = logits.shape[0], logits.shape[-1]
    rows = nd.tile(labels.reshape((1, -1)), reps=(steps, 1))
    ce = nd.softmax_cross_entropy(logits.reshape((-1, vocab)),
                                  rows.reshape((-1,)), per_row=True)
    p = p.reshape((steps, -1))
    expected = (p * ce.reshape((steps, -1))).sum(axis=0)
    entropy = -(p * nd.log(nd.clip(p, a_min=1e-30, a_max=1.0))).sum(axis=0)
    return (expected - beta * entropy).mean()
