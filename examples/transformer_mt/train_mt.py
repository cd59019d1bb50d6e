#!/usr/bin/env python
"""Transformer-base MT example (BASELINE config 3's second half).

Trains an encoder-decoder transformer (gluon.model_zoo.transformer — the
fused contrib attention ops underneath) on a synthetic
sequence-reversal "translation" task: the target sentence is the source
reversed.  This exercises exactly what real MT needs — cross-attention
must learn a (reversed) source-position alignment, causal self-attention
the autoregressive shift — while staying dataset-free (reference example
anchor: the GluonNLP machine_translation/train_transformer.py lane).

Pipeline: label-smoothed CE (gluon.loss.LabelSmoothedCELoss, padding
ignored via ignore_index), Adam + inverse-sqrt warmup, greedy decode
eval reporting exact-token accuracy.

Usage:
  python examples/transformer_mt/train_mt.py            # tiny demo run
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

PAD, BOS, EOS = 0, 1, 2
SPECIAL = 3


def make_batch(rng, batch, vocab, min_len=4, max_len=12):
    """Variable-length reversal pairs padded to the STATIC max_len (one
    compiled shape — XLA retraces on every new shape, so examples pad to
    a fixed bucket exactly like the reference's bucketing iterators);
    returns src, src_vl, tgt_in (BOS-shifted), tgt_out (EOS-terminated)."""
    lens = rng.randint(min_len, max_len + 1, batch)
    L = int(max_len)
    src = np.full((batch, L), PAD, np.int32)
    tgt_in = np.full((batch, L + 1), PAD, np.int32)
    tgt_out = np.full((batch, L + 1), PAD, np.int32)
    for i, n in enumerate(lens):
        words = rng.randint(SPECIAL, vocab, n)
        src[i, :n] = words
        rev = words[::-1]
        tgt_in[i, 0] = BOS
        tgt_in[i, 1:n + 1] = rev
        tgt_out[i, :n] = rev
        tgt_out[i, n] = EOS
    return src, lens.astype(np.int32), tgt_in, tgt_out


def _token_acc(out, vl, tgt_out):
    """Exact-token accuracy of decoded rows vs the reversal ground truth."""
    correct = total = 0
    for i, n in enumerate(vl):
        want = tgt_out[i, :n]
        got = out[i, 1:n + 1] if out.shape[1] > n else out[i, 1:]
        m = min(len(want), len(got))
        correct += int((want[:m] == got[:m]).sum())
        total += int(n)
    return correct / max(total, 1)


def run(vocab=40, layers=2, units=64, hidden=128, heads=4, batch=32,
        steps=300, lr=3e-3, warmup=30, seed=0, log=True, decode_samples=8,
        beam_size=0):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo import transformer

    rng = np.random.RandomState(seed)
    mx.random.seed(seed)
    model = transformer.TransformerModel(
        vocab_size=vocab, num_layers=layers, units=units,
        hidden_size=hidden, num_heads=heads, max_length=32, dropout=0.0)
    model.initialize(mx.initializer.Xavier())
    loss_fn = gluon.loss.LabelSmoothedCELoss(smoothing=0.1,
                                             ignore_index=PAD)
    trainer = gluon.Trainer(model.collect_params(), "adam",
                            {"learning_rate": lr})

    first_loss = last_loss = None
    t0 = time.time()
    for step in range(steps):
        # inverse-sqrt warmup schedule (transformer-base recipe)
        scale = min((step + 1) / warmup, ((warmup / (step + 1)) ** 0.5))
        trainer.set_learning_rate(lr * scale)
        src, vl, tgt_in, tgt_out = make_batch(rng, batch, vocab)
        s, v, ti, to = (mx.nd.array(a) for a in (src, vl, tgt_in, tgt_out))
        with autograd.record():
            logits = model(s, ti, v)
            loss = loss_fn(logits, to).mean()
        loss.backward()
        trainer.step(1)
        lv = float(loss.asnumpy())
        if first_loss is None:
            first_loss = lv
        last_loss = lv
        if log and (step % 50 == 0 or step == steps - 1):
            print(f"step {step:4d}  loss {lv:.4f}  lr {lr * scale:.2e}")

    # greedy-decode eval: exact token accuracy on fresh pairs
    src, vl, _, tgt_out = make_batch(rng, decode_samples, vocab)
    out = transformer.greedy_decode(
        model, mx.nd.array(src), BOS, EOS,
        max_len=src.shape[1] + 2, src_valid_length=mx.nd.array(vl))
    acc = _token_acc(out, vl, tgt_out)
    rec = {"first_loss": first_loss, "last_loss": last_loss,
           "decode_acc": acc}
    if beam_size >= 1:
        bout, _ = transformer.beam_search_decode(
            model, mx.nd.array(src), BOS, EOS, beam_size=beam_size,
            max_len=src.shape[1] + 2, src_valid_length=mx.nd.array(vl))
        rec["beam_decode_acc"] = _token_acc(bout, vl, tgt_out)
    if log:
        print(f"greedy decode token acc: {acc:.3f}"
              + (f"  beam-{beam_size} acc: {rec['beam_decode_acc']:.3f}"
                 if beam_size >= 1 else "")
              + f" ({time.time() - t0:.1f}s total)")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None, choices=["cpu"],
                    help="pin the jax platform IN-PROCESS, before "
                         "the backend initializes")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--beam", type=int, default=0,
                    help="also report beam-search decode accuracy")
    args = ap.parse_args(argv)
    if args.platform or os.environ.get("JAX_PLATFORMS") == "cpu":
        import jax
        jax.config.update("jax_platforms", args.platform or "cpu")
    rec = run(steps=args.steps, batch=args.batch, lr=args.lr,
              beam_size=args.beam)
    ok = rec["last_loss"] < rec["first_loss"]
    print(f"loss {rec['first_loss']:.3f} -> {rec['last_loss']:.3f}  "
          f"decode_acc {rec['decode_acc']:.3f}  {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
