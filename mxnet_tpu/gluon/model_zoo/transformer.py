"""Transformer-base MT (encoder-decoder) — BASELINE config 3's second half
("GluonNLP: BERT-base pretrain + Transformer-base MT").

Reference anchors: the attention fast paths consume the fused contrib ops
mirroring src/operator/contrib/transformer.cc — self-attention via
``contrib.masked_selfatt`` (interleaved qkv layout) and cross-attention via
``contrib.masked_encdec_att`` (the encdec qk/valatt chain's fused form);
the block structure follows GluonNLP's transformer.py (external repo — the
reference keeps no transformer model in-tree, SURVEY §5.7/§1 L11).

Architecture = Vaswani et al. transformer-base: 6+6 layers, d=512,
ffn=2048, 8 heads, post-norm, sinusoidal positions, shared target
embedding / output projection.  TPU-native notes: time-major (L, B, C)
through the cells (the fused ops' layout contract); the causal decoder
mask is a static fact (no mask tensors); label smoothing lives in
``gluon.loss.LabelSmoothedCELoss``.
"""

from __future__ import annotations

import numpy as _np

from ..block import HybridBlock
from ..nn import Dense, Dropout, LayerNorm

__all__ = ["TransformerEncoderCell", "TransformerDecoderCell",
           "TransformerEncoder", "TransformerDecoder", "TransformerModel",
           "transformer_model", "greedy_decode", "beam_search_decode"]


def _positional_encoding(max_len, units):
    """Sinusoidal position table (transformer-base; no learned table)."""
    pos = _np.arange(max_len)[:, None]
    dim = _np.arange(0, units, 2)[None, :]
    angle = pos / _np.power(10000.0, dim / units)
    enc = _np.zeros((max_len, units), _np.float32)
    enc[:, 0::2] = _np.sin(angle)
    enc[:, 1::2] = _np.cos(angle)
    return enc


class TransformerEncoderCell(HybridBlock):
    """Post-norm encoder block over the fused self-attention op."""

    def __init__(self, units=512, hidden_size=2048, num_heads=8,
                 dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._num_heads = num_heads
        with self.name_scope():
            self.attn_qkv = Dense(3 * units, flatten=False, in_units=units,
                                  prefix="attn_qkv_")
            self.attn_proj = Dense(units, flatten=False, in_units=units,
                                   prefix="attn_proj_")
            self.ffn_1 = Dense(hidden_size, flatten=False, in_units=units,
                               prefix="ffn1_")
            self.ffn_2 = Dense(units, flatten=False, in_units=hidden_size,
                               prefix="ffn2_")
            self.ln_att = LayerNorm(in_channels=units, prefix="ln1_")
            self.ln_ffn = LayerNorm(in_channels=units, prefix="ln2_")
            self.drop = Dropout(dropout)

    def hybrid_forward(self, F, x, valid_length=None):
        qkv = self.attn_qkv(x)                        # (L, B, 3C)
        ctx = F.contrib.masked_selfatt(qkv, valid_length,
                                       heads=self._num_heads)
        out = self.ln_att(x + self.drop(self.attn_proj(ctx)))
        h = self.ffn_2(F.relu(self.ffn_1(out)))       # base uses ReLU ffn
        return self.ln_ffn(out + self.drop(h))


class TransformerDecoderCell(HybridBlock):
    """Post-norm decoder block: causal self-attention + fused
    cross-attention over the encoder memory."""

    def __init__(self, units=512, hidden_size=2048, num_heads=8,
                 dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._num_heads = num_heads
        with self.name_scope():
            self.attn_qkv = Dense(3 * units, flatten=False, in_units=units,
                                  prefix="self_qkv_")
            self.attn_proj = Dense(units, flatten=False, in_units=units,
                                   prefix="self_proj_")
            self.cross_q = Dense(units, flatten=False, in_units=units,
                                 prefix="cross_q_")
            # one fused [k,v] projection of the memory — the encdec layout
            self.cross_kv = Dense(2 * units, flatten=False, in_units=units,
                                  prefix="cross_kv_")
            self.cross_proj = Dense(units, flatten=False, in_units=units,
                                   prefix="cross_proj_")
            self.ffn_1 = Dense(hidden_size, flatten=False, in_units=units,
                               prefix="ffn1_")
            self.ffn_2 = Dense(units, flatten=False, in_units=hidden_size,
                               prefix="ffn2_")
            self.ln_self = LayerNorm(in_channels=units, prefix="ln1_")
            self.ln_cross = LayerNorm(in_channels=units, prefix="ln2_")
            self.ln_ffn = LayerNorm(in_channels=units, prefix="ln3_")
            self.drop = Dropout(dropout)

    def hybrid_forward(self, F, x, mem, mem_valid_length=None):
        # x (Lt, B, C) target stream; mem (Ls, B, C) encoder output
        qkv = self.attn_qkv(x)
        ctx = F.contrib.masked_selfatt(qkv, None, heads=self._num_heads,
                                       causal=True)
        out = self.ln_self(x + self.drop(self.attn_proj(ctx)))
        cross = F.contrib.masked_encdec_att(
            self.cross_q(out), self.cross_kv(mem), mem_valid_length,
            heads=self._num_heads)
        out = self.ln_cross(out + self.drop(self.cross_proj(cross)))
        h = self.ffn_2(F.relu(self.ffn_1(out)))
        return self.ln_ffn(out + self.drop(h))


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers=6, units=512, hidden_size=2048,
                 num_heads=8, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self.cells = []
        with self.name_scope():
            for i in range(num_layers):
                cell = TransformerEncoderCell(units, hidden_size, num_heads,
                                              dropout, prefix=f"layer{i}_")
                self.register_child(cell, f"layer{i}")
                self.cells.append(cell)

    def hybrid_forward(self, F, x, valid_length=None):
        for cell in self.cells:
            x = cell(x) if valid_length is None else cell(x, valid_length)
        return x


class TransformerDecoder(HybridBlock):
    def __init__(self, num_layers=6, units=512, hidden_size=2048,
                 num_heads=8, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self.cells = []
        with self.name_scope():
            for i in range(num_layers):
                cell = TransformerDecoderCell(units, hidden_size, num_heads,
                                              dropout, prefix=f"layer{i}_")
                self.register_child(cell, f"layer{i}")
                self.cells.append(cell)

    def hybrid_forward(self, F, x, mem, mem_valid_length=None):
        for cell in self.cells:
            x = cell(x, mem, mem_valid_length)
        return x


class TransformerModel(HybridBlock):
    """Encoder-decoder MT model.

    ``forward(src_tokens, tgt_tokens[, src_valid_length])`` takes
    batch-major (B, Ls)/(B, Lt) int tokens (tgt already shifted right by
    the caller: BOS-prefixed) and returns (B, Lt, V) next-token logits.
    Source padding is masked via ``src_valid_length`` (B,); target padding
    is the LOSS's job (label smoothing + padding weight), matching the
    GluonNLP training contract.

    The token embedding is ONE (vocab, units) table shared by source,
    target, AND the output softmax projection (the three-way tying of the
    transformer-base recipe), declared model-level the same way bert.py
    declares position_weight so the tie survives hybridize/CachedOp.
    """

    def __init__(self, vocab_size=32768, num_layers=6, units=512,
                 hidden_size=2048, num_heads=8, max_length=1024,
                 dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._vocab = vocab_size
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab_size, units), init=None)
            self.encoder = TransformerEncoder(num_layers, units, hidden_size,
                                              num_heads, dropout,
                                              prefix="enc_")
            self.decoder = TransformerDecoder(num_layers, units, hidden_size,
                                              num_heads, dropout,
                                              prefix="dec_")
            self.drop = Dropout(dropout)
        self._pos = _positional_encoding(max_length, units)

    def _embed(self, F, weight, tokens):
        # gather, scale by sqrt(d), add sinusoids (transformer-base recipe)
        x = F.Embedding(tokens, weight, input_dim=self._vocab,
                        output_dim=self._units) * float(self._units) ** 0.5
        pos = F.array(self._pos[:tokens.shape[1]]).astype(x.dtype)
        x = x + F.expand_dims(pos, axis=0)
        return F.transpose(self.drop(x), axes=(1, 0, 2))   # (L, B, C)

    def _encode_impl(self, F, embed_weight, src_tokens, src_valid_length):
        mem = self._embed(F, embed_weight, src_tokens)
        return self.encoder(mem) if src_valid_length is None \
            else self.encoder(mem, src_valid_length)

    def _decode_impl(self, F, embed_weight, mem, tgt_tokens,
                     src_valid_length):
        y = self._embed(F, embed_weight, tgt_tokens)
        y = self.decoder(y, mem, src_valid_length)
        y = F.transpose(y, axes=(1, 0, 2))                 # (B, Lt, C)
        # tied output projection: logits = y @ embed^T
        logits = F.dot(y.reshape((-1, self._units)), embed_weight,
                       transpose_b=True)
        return logits.reshape((tgt_tokens.shape[0], tgt_tokens.shape[1], -1))

    def hybrid_forward(self, F, src_tokens, tgt_tokens,
                       src_valid_length=None, embed_weight=None):
        mem = self._encode_impl(F, embed_weight, src_tokens,
                                src_valid_length)
        return self._decode_impl(F, embed_weight, mem, tgt_tokens,
                                 src_valid_length)

    def encode(self, src_tokens, src_valid_length=None):
        """Run the encoder ONCE and return its memory (Ls, B, C) — the
        half of ``hybrid_forward`` whose inputs never change during
        autoregressive decode.  Pair with :meth:`decode_from_memory`."""
        from ... import ndarray as F
        return self._encode_impl(F, self.embed_weight.data(), src_tokens,
                                 src_valid_length)

    def decode_from_memory(self, mem, tgt_tokens, src_valid_length=None):
        """Decoder + tied projection over a cached encoder memory:
        identical math (and logits) to ``self(src, tgt, vl)`` when ``mem``
        came from :meth:`encode` on the same source — the decode loops
        call this every step so the encoder runs once per sentence, not
        once per emitted token."""
        from ... import ndarray as F
        return self._decode_impl(F, self.embed_weight.data(), mem,
                                 tgt_tokens, src_valid_length)


_CONFIGS = {
    # name: (layers, units, hidden, heads)
    "transformer_base": (6, 512, 2048, 8),
    "transformer_big": (6, 1024, 4096, 16),
    "transformer_test": (2, 64, 128, 4),     # tiny (unit tests)
}


def transformer_model(name="transformer_base", vocab_size=32768,
                      max_length=1024, dropout=0.1, **kwargs):
    if name not in _CONFIGS:
        raise ValueError(f"unknown transformer config {name!r}; "
                         f"known {sorted(_CONFIGS)}")
    L, U, H, A = _CONFIGS[name]
    return TransformerModel(vocab_size=vocab_size, num_layers=L, units=U,
                            hidden_size=H, num_heads=A,
                            max_length=max_length, dropout=dropout, **kwargs)


def greedy_decode(model, src_tokens, bos_id, eos_id, max_len=64,
                  src_valid_length=None):
    """Greedy autoregressive decode: argmax next token until EOS/max_len.

    The target rides a FIXED (B, max_len) buffer and every step runs the
    same compiled shape — decoder causality makes the PAD tail beyond the
    current position invisible to the positions that matter, so the
    growing-prefix retrace (a fresh XLA compile per emitted token) never
    happens.  The source is encoded ONCE and every step decodes against
    the cached memory; the decoder itself still re-runs the full buffer
    per step (the example/eval path — ``mx.serving`` is the production
    path with a paged k/v cache and O(L) decode).  Returns (B, <=max_len)
    int32 including BOS, stopping early only when EVERY sequence has
    emitted EOS.
    """
    import numpy as np
    from ... import ndarray as mxnd
    B = src_tokens.shape[0]
    # the fixed buffer embeds positions 0..max_len-1 every step, so it
    # must fit the model's position table (the growing-prefix variant
    # only failed if decoding actually REACHED the limit)
    cap = getattr(model, "_pos", None)
    if cap is not None:
        max_len = min(max_len, cap.shape[0])
    buf = np.full((B, max_len), eos_id, np.int32)   # pad tail = EOS id
    buf[:, 0] = bos_id
    done = np.zeros((B,), bool)
    n = 1
    # the source never changes across steps: encode ONCE and decode every
    # step against the cached memory (identical logits to the full call)
    mem = model.encode(src_tokens, src_valid_length)
    for t in range(max_len - 1):
        logits = model.decode_from_memory(mem, mxnd.array(buf),
                                          src_valid_length)
        nxt = np.asarray(logits.asnumpy()[:, t].argmax(-1), np.int32)
        nxt = np.where(done, eos_id, nxt)
        buf[:, t + 1] = nxt
        done |= nxt == eos_id
        n = t + 2
        if done.all():
            break
    return buf[:, :n]


def beam_search_decode(model, src_tokens, bos_id, eos_id, beam_size=4,
                       max_len=64, alpha=0.6, src_valid_length=None):
    """Beam-search decode (the GluonNLP BeamSearchSampler role for MT).

    Length-normalized scores use the GNMT penalty
    ``((5 + len) / 6) ** alpha``; hypotheses that emit EOS move to a
    COMPLETED pool at their normalized score (so a short finished
    hypothesis is never evicted by longer raw-score competitors — the
    BeamSearchScorer contract), and the search stops early once every
    live beam is worse than the pool even with the best possible
    remaining score.  Same fixed-shape discipline as ``greedy_decode``:
    one (B*K, max_len) buffer, one compiled shape per step (causality
    hides the pad tail), the replicated source encoded ONCE up front.
    Host-side numpy picks the beams — the example/eval path; production
    serving (``mx.serving``) jits the loop with paged k/v caches.
    Returns (best (B, <=max_len) int32 incl. BOS, scores (B,)
    length-normalized log-probs).
    """
    import numpy as np
    from ... import ndarray as mxnd
    B = src_tokens.shape[0]
    K = beam_size
    cap = getattr(model, "_pos", None)
    if cap is not None:
        max_len = min(max_len, cap.shape[0])
    src_np = src_tokens.asnumpy() if hasattr(src_tokens, "asnumpy") \
        else np.asarray(src_tokens)
    # each batch row replicated K times: (B*K, Ls), beams vary the target
    src_rep = mxnd.array(np.repeat(src_np, K, axis=0))
    vl_rep = None
    if src_valid_length is not None:
        vl_np = src_valid_length.asnumpy() \
            if hasattr(src_valid_length, "asnumpy") \
            else np.asarray(src_valid_length)
        vl_rep = mxnd.array(np.repeat(vl_np, K, axis=0))

    def penalty(length):
        return ((5.0 + length) / 6.0) ** alpha

    buf = np.full((B, K, max_len), eos_id, np.int32)
    buf[:, :, 0] = bos_id
    scores = np.full((B, K), -np.inf, np.float64)
    scores[:, 0] = 0.0            # beams start identical: keep one live
    # completed pool: per batch row, the best (normalized_score, tokens)
    best_done = [(-np.inf, None)] * B
    n = 1
    # the replicated source is step-invariant: one encoder pass feeds
    # every decode step (and every beam reshuffle — beams share a row's
    # memory by construction)
    mem = model.encode(src_rep, vl_rep)
    for t in range(max_len - 1):
        flat = mxnd.array(buf.reshape(B * K, max_len))
        logits = model.decode_from_memory(mem, flat, vl_rep)
        # slice + log_softmax ON DEVICE (the registered op — one
        # log-softmax implementation in the codebase), then pull only the
        # (B*K, V) step slice to the host
        logp = mxnd.log_softmax(logits[:, t], axis=-1).asnumpy() \
            .astype(np.float64)
        V = logp.shape[-1]
        logp = logp.reshape(B, K, V)
        # EOS continuations COMPLETE a hypothesis: score it normalized
        # into the pool, then exclude EOS from the live expansion
        for b in range(B):
            for k in range(K):
                if not np.isfinite(scores[b, k]):
                    continue
                fin = (scores[b, k] + logp[b, k, eos_id]) / penalty(t + 1)
                if fin > best_done[b][0]:
                    seq = buf[b, k, :t + 2].copy()
                    seq[t + 1] = eos_id
                    best_done[b] = (fin, seq)
        logp[:, :, eos_id] = -np.inf
        cand = scores[:, :, None] + logp            # (B, K, V)
        flat_cand = cand.reshape(B, K * V)
        part = np.argpartition(-flat_cand, K - 1, axis=1)[:, :K]
        part_scores = np.take_along_axis(flat_cand, part, 1)
        order = np.argsort(-part_scores, axis=1)
        top = np.take_along_axis(part, order, 1)     # (B, K) best-first
        new_scores = np.take_along_axis(flat_cand, top, 1)
        beam_idx, tok_idx = top // V, top % V
        buf = np.take_along_axis(
            buf, beam_idx[:, :, None].astype(np.int64), axis=1)
        buf[:, :, t + 1] = tok_idx.astype(np.int32)
        scores = new_scores
        n = t + 2
        # early stop: even a perfect (0 log-prob) continuation cannot
        # beat the completed pool for any row
        bound = scores[:, 0] / penalty(max_len - 1)
        if all(best_done[b][0] >= bound[b] for b in range(B)):
            break
    out = np.full((B, n), eos_id, np.int32)
    final = np.empty((B,), np.float64)
    for b in range(B):
        sc, seq = best_done[b]
        if seq is None:
            # no hypothesis ever finished: fall back to the best live beam
            seq = buf[b, 0, :n]
            sc = scores[b, 0] / penalty(n - 1)
        out[b, :len(seq)] = seq[:n]
        final[b] = sc
    return out, final
