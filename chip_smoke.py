#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on a TPU.

Drives the system's main paths once on one TPU, through the entry points a
user calls (``import mxnet_tpu as mx``), at the full width of models the repo
supports, with seeded random weights:

  native      were the C++ input-pipeline libraries built on this machine
              (reported, does not decide the result; touches no device)
  imperative  mx.nd + autograd + sgd_update on mx.tpu(): loss falls
  gluon       gluon.nn + hybridize() + gluon.Trainer on mx.tpu():
              imperative and hybridized agree
  serve       ServingEngine over an 8-layer, 2048-wide LlamaModel (bf16,
              vocab 32000): mixed-length requests complete, greedy output
              equals a plain full-recompute greedy decode, nothing
              compiles after warm-up
  train       BERT-base (bert_12_768_12, vocab 30522) bf16 + Adam
              multi_precision through parallel.TrainStep, batch 32 x seq
              512: losses finite and falling, parameters change, and the
              compiled step contains the Pallas flash kernel
              (``tpu_custom_call``), not the dense path

Each phase prints one JSON line (phase, seconds, compile seconds, what it
checked); the first failure ends the run non-zero at once.  The LAST stdout
line of a good run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

It needs one chip and refuses anything else: no TPU, no result.  One
process owns the chip, so everything runs in this process and no child is
started.  ``--chips 4`` runs ONLY the multi-chip phase and what it is
compared with: the same BERT-base TrainStep on one device, on a 4-device
``dp`` mesh and on a (2, 2) ``dp x tp`` mesh, in this one process.

The compile cache is the one mxnet_tpu sets up at import
(JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache): a second run of the
same command reports smaller compile seconds.
"""

import argparse
import contextlib
import gc
import json
import sys
import time

import numpy as np

SEED = 0

# Full sizes: what main() runs on the chip.  tests/test_chip_compile.py runs
# the same phase functions at a tiny size on the CPU.
TRAIN_FULL = dict(name="bert_12_768_12", vocab=30522, seq=512, batch=32,
                  scan_steps=4, dispatches=2, dtype="bfloat16")
# bench.py's llama lane width (8 layers, 2048 units, 5504 hidden, 16 heads,
# 8 kv heads) with the zoo's default vocabulary; the zoo's one public
# llama, llama3_8b, does not fit one 16 GB chip
SERVE_FULL = dict(layers=8, units=2048, hidden=5504, heads=16, kv_heads=8,
                  vocab=32000, dtype="bfloat16", max_batch=4,
                  block_tokens=16, max_seq=128, prefill_tokens=64,
                  prompt_lens=(3, 17, 40, 9, 26), max_new_tokens=12)


class PhaseFailed(AssertionError):
    """A phase's check did not hold."""


def check(cond, what):
    if not cond:
        raise PhaseFailed(what)


def emit(row):
    print(json.dumps(row), flush=True)


class CompileClock:
    """Compilation as jax.monitoring reports it: seconds spent tracing,
    lowering and compiling (a persistent-cache hit counts its retrieval),
    and the cache's own hit/miss counts."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as monitoring
        self.seconds = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_kw):
        if event in self._DURATIONS:
            self.seconds += seconds

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def run_phase(name, clock, fn, *args, **kwargs):
    """Run one phase and print its row; a failure prints the row with the
    error and raises SystemExit(1) at once."""
    s0, h0, m0 = clock.snapshot()
    t0 = time.perf_counter()
    row = {"phase": name}
    try:
        row.update(fn(*args, **kwargs))
    except Exception as e:  # noqa: BLE001 — boundary: report, then fail
        import traceback
        traceback.print_exc(file=sys.stderr)
        row["error"] = f"{type(e).__name__}: {e}"[:500]
    s1, h1, m1 = clock.snapshot()
    row.update(seconds=round(time.perf_counter() - t0, 3),
               compile_seconds=round(s1 - s0, 3),
               cache_hits=h1 - h0, cache_misses=m1 - m0)
    emit(row)
    if "error" in row:
        raise SystemExit(1)
    gc.collect()    # a phase's model and engine leave the device with it
    return row


def platforms_of(arr):
    """Platforms of the devices that really hold an NDArray's buffer."""
    return sorted({d.platform for d in arr._data.devices()})


# -- phases ------------------------------------------------------------------

def phase_native():
    import mxnet_tpu as mx
    return {"available": {
        "recordio": bool(mx.native.native_available()),
        "jpeg": bool(mx.native.jpeg_decode_available())}}


def phase_imperative(ctx, platform):
    """The canonical flow of the verify skill on ``ctx``."""
    import mxnet_tpu as mx
    r = np.random.RandomState(SEED)
    a = mx.nd.array(r.randn(64, 32).astype("float32"), ctx=ctx)
    w = mx.nd.array(r.randn(32, 10).astype("float32") * 0.1, ctx=ctx)
    w.attach_grad()
    lbl = mx.nd.array(r.randint(0, 10, (64,)), ctx=ctx)
    losses = []
    for _ in range(3):
        with mx.autograd.record():
            loss = -mx.nd.pick(mx.nd.log_softmax(mx.nd.dot(a, w)),
                               lbl).mean()
        loss.backward()
        mx.nd.sgd_update(w, w.grad, lr=0.5, out=w)
        losses.append(float(loss.asnumpy()))
    where = {n: platforms_of(x) for n, x in
             (("data", a), ("weight", w), ("grad", w.grad), ("loss", loss))}
    check(all(p == [platform] for p in where.values()),
          f"arrays not on {platform}: {where}")
    check(str(w.ctx) == str(ctx), f"weight ctx {w.ctx} != {ctx}")
    check(np.all(np.isfinite(losses)) and losses[0] > losses[1] > losses[2],
          f"loss does not fall: {losses}")
    return {"losses": losses, "array_platforms": where}


def phase_gluon(ctx, platform):
    """gluon.nn + Trainer for 3 steps, imperative vs hybridized."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    r = np.random.RandomState(SEED)
    x = mx.nd.array(r.randn(32, 20).astype("float32"), ctx=ctx)
    y = mx.nd.array(r.randint(0, 5, (32,)).astype("float32"), ctx=ctx)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def train(hybridize):
        mx.random.seed(SEED)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(64, activation="relu"), gluon.nn.Dense(5))
        net.initialize(mx.initializer.Xavier(), ctx=ctx)
        if hybridize:
            net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.5})
        losses = []
        for _ in range(3):
            with mx.autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(32)
            losses.append(float(loss.mean().asnumpy()))
        params = [p.data() for p in net.collect_params().values()]
        return losses, params

    imp_losses, imp_params = train(False)
    hyb_losses, hyb_params = train(True)
    where = sorted({p for a in imp_params + hyb_params
                    for p in platforms_of(a)})
    check(where == [platform], f"params not on {platform}: {where}")
    check(imp_losses[-1] < imp_losses[0], f"loss does not fall: {imp_losses}")
    check(np.allclose(imp_losses, hyb_losses, rtol=1e-3, atol=1e-4),
          f"imperative {imp_losses} != hybridized {hyb_losses}")
    for a, b in zip(imp_params, hyb_params):
        check(np.allclose(a.asnumpy(), b.asnumpy(), rtol=1e-3, atol=1e-4),
              "imperative and hybridized parameters differ")
    return {"losses": imp_losses, "hybridized_losses": hyb_losses,
            "param_platforms": where}


def build_bert_step(cfg, mesh, tp_axis=None):
    """BERT + Adam multi_precision in a TrainStep, as bench.py builds it
    (seeded init on the default context, bf16 cast, loss on the MLM
    logits); ``tp_axis`` adds the zoo's tensor-parallel layout."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import bert
    mx.random.seed(SEED)
    np.random.seed(SEED)
    model = bert.bert_model(cfg["name"], vocab_size=cfg["vocab"],
                            max_length=cfg["seq"], dropout=0.0)
    model.initialize(mx.initializer.Normal(0.02))
    if cfg["dtype"] == "bfloat16":
        import ml_dtypes
        model.cast(ml_dtypes.bfloat16)
    if tp_axis:
        bert.apply_tp_shardings(model, axis=tp_axis)

    def loss_fn(out, labels):
        _, _, logits = out
        return mx.nd.softmax_cross_entropy(
            logits.reshape((-1, logits.shape[-1])).astype("float32"),
            labels.reshape((-1,))) / labels.size

    opt = mx.optimizer.Adam(learning_rate=1e-4,
                            multi_precision=(cfg["dtype"] == "bfloat16"))
    return model, parallel.TrainStep(model, loss_fn, opt, mesh=mesh)


def bert_batches(cfg):
    """One seeded batch repeated scan_steps times (stacked, as bench.py
    feeds run()); the label of each position is its own token, so a few
    Adam steps on the repeated batch must lower the loss."""
    from mxnet_tpu import nd
    r = np.random.RandomState(SEED + 1)
    one = r.randint(0, cfg["vocab"], (cfg["batch"], cfg["seq"]))
    toks = np.broadcast_to(one, (cfg["scan_steps"],) + one.shape) \
        .astype(np.int32)
    return nd.array(toks), nd.array(toks)


@contextlib.contextmanager
def bf16_matmuls(dtype):
    """bench.py runs its bf16 lanes at XLA's default matmul precision
    (the package default is 'highest', for float32 parity); scoped, so
    the other phases keep the package default."""
    import jax
    if dtype == "bfloat16":
        with jax.default_matmul_precision("default"):
            yield
    else:
        yield


def train_and_check(cfg, mesh, platform, tp_axis=None):
    """Run the dispatches of one BERT TrainStep and check what every
    variant must show; returns (row, losses, model)."""
    import jax
    with bf16_matmuls(cfg["dtype"]):
        model, step = build_bert_step(cfg, mesh, tp_axis)
        toks, labs = bert_batches(cfg)
        before = {n: p.data().asnumpy()
                  for n, p in model.collect_params().items()}
        losses, dispatch_s = [], []
        for _ in range(cfg["dispatches"]):
            t0 = time.perf_counter()
            out = step.run(toks, labs).asnumpy()     # fetch = barrier
            dispatch_s.append(round(time.perf_counter() - t0, 3))
            losses += [float(v) for v in out]
        # the same program compiled ahead of time, to read what is in it
        # (its cache key differs from the dispatched one: a second compile)
        t0 = time.perf_counter()
        compiled = step.lowered(toks, labs).compile()
        text, mem = compiled.as_text(), compiled.memory_analysis()
        hlo_s = round(time.perf_counter() - t0, 3)
    params = model.collect_params()
    changed = sum(bool(np.any(before[n] != p.data().asnumpy()))
                  for n, p in params.items())
    where = sorted({p for q in params.values()
                    for p in platforms_of(q.data())})
    check(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss does not fall: {losses}")
    check(changed >= len(before) // 2,
          f"only {changed} of {len(before)} parameters changed")
    check(where == [platform], f"parameters not on {platform}: {where}")
    row = {"losses": [round(v, 5) for v in losses],
           "dispatch_seconds": dispatch_s, "hlo_seconds": hlo_s,
           "params_changed": f"{changed}/{len(before)}",
           "pallas_calls": text.count("tpu_custom_call"),
           "program_bytes": {
               "arguments": int(mem.argument_size_in_bytes),
               "temporaries": int(mem.temp_size_in_bytes),
               "outputs": int(mem.output_size_in_bytes),
               "aliased": int(mem.alias_size_in_bytes)},
           "collectives": {k: text.count(f" {k}(") + text.count(f" {k}-start(")
                           for k in ("all-reduce", "all-gather",
                                     "reduce-scatter", "all-to-all",
                                     "collective-permute")}}
    stats = jax.devices()[0].memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        row["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
    return row, losses, model


def phase_train(cfg, platform, expect_kernel):
    from mxnet_tpu import parallel
    row, _, _ = train_and_check(cfg, parallel.make_mesh(), platform)
    if expect_kernel:
        check(row["pallas_calls"] > 0,
              "the compiled step has no tpu_custom_call: attention took "
              "the dense path, not the Pallas flash kernel")
    return {**{k: cfg[k] for k in ("name", "batch", "seq", "dtype")}, **row}


def ref_greedy(net, prompt, max_new, pad_to, ctx):
    """Plain greedy decode: re-encode the whole sequence for every token
    on one fixed (1, pad_to) buffer (causality hides the tail).  Returns
    the tokens and, per step, the reference logits row."""
    import mxnet_tpu as mx
    buf = np.zeros((1, pad_to), np.int32)
    buf[0, :len(prompt)] = prompt
    n, out, rows = len(prompt), [], []
    for _ in range(max_new):
        logits = net(mx.nd.array(buf, ctx=ctx)).asnumpy()[0, n - 1] \
            .astype(np.float32)
        nxt = int(logits.argmax())
        out.append(nxt)
        rows.append(logits)
        buf[0, n] = nxt
        n += 1
    return out, rows


def phase_serve(cfg, ctx, platform):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.llama import LlamaModel
    mx.random.seed(SEED)
    np.random.seed(SEED)
    net = LlamaModel(vocab_size=cfg["vocab"], num_layers=cfg["layers"],
                     units=cfg["units"], hidden=cfg["hidden"],
                     heads=cfg["heads"], kv_heads=cfg["kv_heads"])
    net.initialize(mx.initializer.Normal(0.02), ctx=ctx)
    if cfg["dtype"] == "bfloat16":
        import ml_dtypes
        net.cast(ml_dtypes.bfloat16)
    net.hybridize()     # the reference decode below is one program a token
    net(mx.nd.array(np.zeros((1, 4), np.int32), ctx=ctx))  # deferred init

    # eos outside the vocabulary: a random model must not end a request
    # early, every request owes exactly max_new_tokens
    eng = mx.serving.ServingEngine(
        net, eos_id=-1, max_batch=cfg["max_batch"],
        block_tokens=cfg["block_tokens"], max_seq=cfg["max_seq"],
        prefill_tokens=cfg["prefill_tokens"])
    r = np.random.RandomState(SEED + 2)
    prompts = [[int(t) for t in r.randint(3, cfg["vocab"], n)]
               for n in cfg["prompt_lens"]]
    new = cfg["max_new_tokens"]
    t0 = time.perf_counter()
    eng.generate(prompts[:2], max_new_tokens=new)            # warm-up
    warm_s = round(time.perf_counter() - t0, 3)
    t0 = time.perf_counter()
    with mx.analysis.no_retrace():   # raises if anything compiles here
        outs = eng.generate(prompts, max_new_tokens=new)
    steady_s = round(time.perf_counter() - t0, 3)
    check(len(outs) == len(prompts)
          and all(len(o) == new for o in outs),
          f"requests did not all return {new} tokens: "
          f"{[len(o) for o in outs]}")
    where = sorted({d.platform for a in (eng.adapter.weights.embed,
                                         eng.adapter._kv[0][0])
                    for d in a.devices()})
    check(where == [platform], f"weights/KV pool not on {platform}: {where}")

    # engine output vs full-recompute greedy decode.  At bf16 a random
    # model has near-ties, where paged decode and re-encode may each pick
    # a different maximum; such a step passes only if the reference holds
    # the engine's token within bf16 resolution of its own maximum, and at
    # least one request must match token for token
    exact = 0
    near_ties = []
    pad_to = max(cfg["prompt_lens"]) + new
    for prompt, got in zip(prompts, outs):
        want, rows = ref_greedy(net, prompt, new, pad_to, ctx)
        if got == want:
            exact += 1
            continue
        k = next(i for i in range(new) if got[i] != want[i])
        gap = float(rows[k].max() - rows[k][got[k]])
        tol = 2.0 ** -6 * float(np.abs(rows[k]).max())
        check(gap <= tol,
              f"request of {len(prompt)} tokens diverges at step {k}: "
              f"engine {got[k]} vs reference {want[k]}, logit gap {gap} "
              f"> {tol}")
        near_ties.append({"prompt_len": len(prompt), "step": k,
                          "logit_gap": gap})
    check(exact >= 1, "no request matched the full-recompute greedy decode")
    return {"requests": len(prompts), "prompt_lens": list(cfg["prompt_lens"]),
            "tokens_each": new, "exact_matches": exact,
            "near_tie_divergences": near_ties, "warmup_seconds": warm_s,
            "steady_seconds": steady_s, "compiles_after_warmup": 0,
            "platforms": where}


def phase_multichip(cfg, platform, devices):
    """The same TrainStep on one device, on a 4-device dp mesh and on a
    (2, 2) dp x tp mesh: same global batch and seed."""
    from mxnet_tpu import parallel
    check(len(devices) >= 4, f"need 4 devices, found {len(devices)}")
    devices = list(devices[:4])
    variants = (
        ("one", dict(shape=(1,), axis_names=("dp",), devices=devices[:1]),
         None),
        ("dp4", dict(shape=(4,), axis_names=("dp",), devices=devices), None),
        ("dp2xtp2", dict(shape=(2, 2), axis_names=("dp", "tp"),
                         devices=devices), "tp"),
    )
    rows, ref = {}, None
    for label, mesh_kw, tp_axis in variants:
        t0 = time.perf_counter()
        row, losses, model = train_and_check(
            cfg, parallel.make_mesh(**mesh_kw), platform, tp_axis)
        arrays = [p.data()._data for p in model.collect_params().values()]
        on = sorted({s.device.id for a in arrays
                     for s in a.addressable_shards})
        split = sum(a.addressable_shards[0].data.shape != a.shape
                    for a in arrays)
        row.update(seconds=round(time.perf_counter() - t0, 3),
                   param_device_ids=on, params_split=split)
        n_coll = sum(row["collectives"].values())
        if label == "one":
            ref = losses
            check(len(on) == 1 and n_coll == 0,
                  f"one-device step spans {on} with {n_coll} collectives")
        else:
            check(len(on) == 4,
                  f"{label}: parameters live on devices {on}, not on four")
            check(row["collectives"]["all-reduce"] > 0,
                  f"{label}: no all-reduce in the compiled step")
            check(np.allclose(losses, ref, rtol=2e-2),
                  f"{label} losses {losses} != one-device {ref}")
            check((split > 0) == bool(tp_axis),
                  f"{label}: {split} parameters are split across devices")
        rows[label] = row
        del model
    check(sum(rows["dp2xtp2"]["collectives"].values())
          > sum(rows["dp4"]["collectives"].values()),
          "tensor parallelism added no collective over data parallelism")
    return {**{k: cfg[k] for k in ("name", "batch", "seq", "dtype")}, **rows}


# -- entry -------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the multi-chip train phase and the "
                         "one-device run it is compared with")
    args = ap.parse_args(argv)

    import jax
    import mxnet_tpu as mx
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX found {devices[0].platform!r} "
              f"({devices[0].device_kind}) and this script never falls back "
              "to it", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    clock = CompileClock()
    emit({"phase": "start", "jax": jax.__version__,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir,
          "device_kind": devices[0].device_kind, "devices": len(devices)})
    if args.chips == 4:
        run_phase("multichip", clock, phase_multichip, TRAIN_FULL, "tpu",
                  devices)
    else:
        ctx = mx.tpu()
        run_phase("native", clock, phase_native)
        run_phase("imperative", clock, phase_imperative, ctx, "tpu")
        run_phase("gluon", clock, phase_gluon, ctx, "tpu")
        run_phase("serve", clock, phase_serve, SERVE_FULL, ctx, "tpu")
        run_phase("train", clock, phase_train, TRAIN_FULL, "tpu",
                  expect_kernel=True)
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
