"""Driver benchmark: flagship BERT-base training-step throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device"}
— always the LAST stdout line.  Per-lane progress/error rows ({"lane",
"status", ...}) stream out (flushed) as each lane finishes, so a bench
killed from outside still leaves a partial evidence trail.  Every row
names the device it ran on (platform, device_kind, count).

This measures the TPU and nothing else: a lane that finds no TPU fails
instead of timing the CPU, each lane runs its configuration once as
given (no retry, no dense-attention re-pin, no halved batch), a lane that
fails records its error, and the process then exits non-zero.

The measured config mirrors BASELINE's north star (BERT-base pretrain):
batch x seq MLM step — forward + backward + Adam, fused into a single XLA
program by parallel.TrainStep, with MXNET_BENCH_SCAN_STEPS steps scanned
inside each dispatch (lax.scan) so per-dispatch host latency is amortized
away.  vs_baseline is measured MFU / 0.45 (the BASELINE target: >= 45%
MFU => vs_baseline >= 1.0).

MFU accounting follows the PaLM convention: matmul params only (embedding
and position tables are gathers, not matmuls — excluded from the 6N term;
the untied MLM decoder matmul is kept) plus the 12*l*C*S attention term.
Peaks come from telemetry/costmodel.py's device_kind table.

Env knobs:
  MXNET_BENCH_MODEL       bert_12_768_12 (default) | bert_6_512_8 |
                          bert_3_128_2 | any model_zoo.vision name
                          (resnet50_v1 → the BASELINE images/sec lane)
  MXNET_BENCH_BATCH       default 64
  MXNET_BENCH_SEQLEN      default 128
  MXNET_BENCH_DTYPE       bfloat16 (default) | float32
  MXNET_BENCH_SCAN_STEPS  steps fused per dispatch, default 128
  MXNET_BENCH_DISPATCHES  timed dispatches, default 2
  MXNET_BENCH_LANES       all (default) = headline + seq-512 + llama-2048
                          + resnet50 + io lanes in extra.lanes; anything
                          else = just the headline config
  MXNET_BENCH_HEADLINE_TIMEOUT  wall-clock cap (s, default 2100) on the
                          headline child process — a hung child records
                          an error row instead of wedging the bench
  MXNET_BENCH_TOTAL_BUDGET_S  hard cap (s, default 3300) on the WHOLE
                          orchestration: lane timeouts shrink to the
                          remaining budget and lanes that no longer fit
                          are skipped with an error row, keeping total
                          wall below the driver's own kill timeout
  MXNET_BENCH_CHILD       internal: set by the parent shell; children
                          measure, the parent orchestrates
"""

import json
import os
import sys
import time
import traceback

# The parent process must stay JAX-free (numpy only): the chip belongs to
# one process at a time, and a parent that had touched JAX would hold it
# while every lane child failed or hung.  JAX and mxnet_tpu are imported
# inside the child-only functions below.
import numpy as np


def _lane_telemetry():
    """Per-lane telemetry snapshot (ISSUE 10 satellite): key counters +
    step-phase medians ride in every BENCH row, so trajectory files carry
    bottleneck attribution (was the lane dispatch-bound? input-bound? did
    it retrace?) and not just wall time.  Each TrainStep.run dispatch is
    one StepClock "step" — phase medians are per-dispatch."""
    try:
        from mxnet_tpu import telemetry
        counters = {}
        for k in ("mxnet_sharding_step_dispatches_total",
                  "mxnet_sharding_retraces_total",
                  "mxnet_op_dispatch_total",
                  "mxnet_trainer_steps_total"):
            m = telemetry.REGISTRY.get(k)
            if m is not None and m.value:
                counters[k] = m.value
        s = telemetry.STEP_CLOCK.summary()
        phases = {p: round(v["median"] * 1e3, 3)
                  for p, v in s.get("phases", {}).items()}
        return {"counters": counters, "step_phase_median_ms": phases,
                "verdict": s.get("verdict", "idle")}
    except Exception as e:  # noqa: BLE001 — attribution must not kill a lane
        return {"error": f"{type(e).__name__}: {e}"[:120]}


def _telemetry_on():
    """Enable telemetry + the cost ledger for the measured lane, starting
    from a clean slate.  (Host-side spans/counters only; with scan_steps
    fused per dispatch the per-dispatch overhead is noise next to the XLA
    program.  The armed ledger adds one AOT analysis per NEW executable —
    compile-time, not steady-state, cost.)"""
    from mxnet_tpu import telemetry
    telemetry.enable()
    telemetry.costmodel.arm()    # analytic flops/bytes/HBM per executable
    telemetry.clear()            # spans + ledgers + step-clock window
    telemetry.REGISTRY.reset()   # counters attribute THIS attempt only


def _peak_flops(dtype):
    """Per-chip peak for MFU accounting (costmodel's device table)."""
    from mxnet_tpu.telemetry import costmodel
    return costmodel.peak_flops(dtype)


def _lane_cost(step_seconds, dtype):
    """The analytic cost block every BENCH row embeds (ISSUE 12): the
    TrainStep executable's XLA-counted per-step flops/bytes (a scanned
    program's loop body is analyzed once, so its cost IS one step's),
    analytic MFU against the measured per-step wall time, the roofline
    verdict, and the per-device peak-HBM estimate.  Analytic MFU counts
    ALL flops XLA emits (cost_analysis), so it sits a few % above the
    hand-derived PaLM-convention `mfu` field — both ride the row."""
    try:
        from mxnet_tpu.telemetry import costmodel
        c = costmodel.lane_summary(step_seconds=step_seconds, dtype=dtype)
        keep = ("flops", "bytes_accessed", "arithmetic_intensity",
                "ridge_flops_per_byte", "verdict", "roofline_mfu_bound",
                "analytic_mfu", "peak_hbm_bytes", "compile_s",
                "executables", "error")
        return {k: c[k] for k in keep if k in c}
    except Exception as e:  # noqa: BLE001 — the ledger must not kill a lane
        return {"error": f"{type(e).__name__}: {e}"[:120]}


def run_vision_once(name, batch, dtype, scan_steps, dispatches):
    """Secondary lane (BASELINE config 2): vision-zoo train step, images/sec.

    vs_baseline compares against the reference's era-typical 1xV100 fp32
    ResNet-50 number (~400 img/s, BASELINE.md — UNVERIFIED, indicative)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon.model_zoo import get_model

    size = 299 if "inception" in name else 224
    classes = 1000
    mx.random.seed(0)
    np.random.seed(0)
    model = get_model(name, classes=classes)
    model.initialize(mx.initializer.Xavier())
    img_dt = np.float32
    if dtype == "bfloat16":
        import jax
        jax.config.update("jax_default_matmul_precision", "default")
        import ml_dtypes
        model.cast(ml_dtypes.bfloat16)
        img_dt = ml_dtypes.bfloat16

    def loss_fn(out, labels):
        return mx.nd.softmax_cross_entropy(
            out.astype("float32"), labels.reshape((-1,))) / labels.size

    mesh = parallel.make_mesh()
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                           multi_precision=(dtype == "bfloat16"))
    step = parallel.TrainStep(model, loss_fn, opt, mesh=mesh)
    _telemetry_on()

    # one on-device batch scanned scan_steps times per dispatch: synthetic
    # data must not meter host->device bandwidth (a 224x224 batch is ~10MB;
    # the token-based BERT lane ships ~KBs) — the input pipeline is measured
    # separately by the io benchmarks, as in the reference perf.md tables
    r = np.random.RandomState(0)
    imgs = nd.array(r.randn(batch, 3, size, size).astype(img_dt))
    labs = nd.array(r.randint(0, classes, (batch,)).astype(np.int32))

    losses = step.run(imgs, labs, steps=scan_steps)
    float(np.asarray(losses.asnumpy()[-1]))

    t0 = time.perf_counter()
    for _ in range(dispatches):
        losses = step.run(imgs, labs, steps=scan_steps)
    last_loss = float(np.asarray(losses.asnumpy()[-1], np.float64))
    dt = time.perf_counter() - t0
    n_steps = scan_steps * dispatches
    images_per_sec = batch * n_steps / dt
    # the 400 img/s V100-era figure is a ResNet-50 number: only that lane
    # gets a meaningful ratio
    vs = round(images_per_sec / 400.0, 4) if name.startswith("resnet50") \
        else 0.0
    extra = {"dtype": dtype, "batch": batch, "size": size,
             "step_ms": round(1000 * dt / n_steps, 2), "loss": last_loss,
             "telemetry": _lane_telemetry(),
             "cost": _lane_cost(dt / n_steps, dtype)}
    if not name.startswith("resnet50"):
        extra["baseline_note"] = "no reference baseline for this model"
    return {
        "metric": f"{name}_train_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/s",
        "vs_baseline": vs,
        "extra": extra,
    }


def run_once(name, batch, seq_len, dtype, scan_steps, dispatches):
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon.model_zoo import bert

    vocab = 30522
    if dtype == "bfloat16":
        import jax
        jax.config.update("jax_default_matmul_precision", "default")

    mx.random.seed(0)
    np.random.seed(0)
    model = bert.bert_model(name, vocab_size=vocab, max_length=seq_len,
                            dropout=0.0)
    model.initialize(mx.initializer.Normal(0.02))
    if dtype == "bfloat16":
        import ml_dtypes
        model.cast(ml_dtypes.bfloat16)

    def loss_fn(out, labels):
        _, _, logits = out
        return mx.nd.softmax_cross_entropy(
            logits.reshape((-1, logits.shape[-1])).astype("float32"),
            labels.reshape((-1,))) / labels.size

    mesh = parallel.make_mesh()  # all local devices (1 on the bench chip)
    opt = mx.optimizer.Adam(learning_rate=1e-4,
                            multi_precision=(dtype == "bfloat16"))
    step = parallel.TrainStep(model, loss_fn, opt, mesh=mesh)
    _telemetry_on()

    # per-step batches (stacked, scanned over) so every step sees fresh data
    def mk_batches(seed):
        r = np.random.RandomState(seed)
        toks = r.randint(0, vocab, (scan_steps, batch, seq_len)).astype(np.int32)
        labs = r.randint(0, vocab, (scan_steps, batch, seq_len)).astype(np.int32)
        return nd.array(toks), nd.array(labs)

    warm_t, warm_l = mk_batches(0)
    losses = step.run(warm_t, warm_l)           # compile + warmup dispatch
    float(np.asarray(losses.asnumpy()[-1]))      # full fetch barrier

    batches = [mk_batches(i + 1) for i in range(dispatches)]
    t0 = time.perf_counter()
    for t, l in batches:
        losses = step.run(t, l)
    last_loss = float(np.asarray(losses.asnumpy()[-1], np.float64))  # barrier
    dt = time.perf_counter() - t0

    n_steps = scan_steps * dispatches
    samples_per_sec = batch * n_steps / dt

    # MFU: matmul-param 6N term (no embedding/position gathers) + attention
    cfg = bert._BERT_CONFIGS[name]
    n_layers, units, hidden, _heads = cfg
    n_matmul = 0
    for pname, p in model.collect_params().items():
        if p.shape is None:
            continue
        if "word_" in pname or "position_weight" in pname:
            continue  # gather tables, not matmuls (PaLM MFU convention)
        n_matmul += int(np.prod(p.shape))
    flops_per_token = 6 * n_matmul + 12 * n_layers * units * seq_len
    tokens_per_sec = samples_per_sec * seq_len
    mfu = tokens_per_sec * flops_per_token / _peak_flops(dtype)

    return {
        "metric": f"{name}_train_samples_per_sec_per_chip",
        "value": round(samples_per_sec, 3),
        "unit": "samples/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {"mfu": round(mfu, 4), "dtype": dtype, "batch": batch,
                  "seq_len": seq_len, "scan_steps": scan_steps,
                  "step_ms": round(1000 * dt / n_steps, 2),
                  "loss": last_loss, "telemetry": _lane_telemetry(),
                  "cost": _lane_cost(dt / n_steps, dtype)},
    }


def run_llama_once(batch, seq_len, dtype, scan_steps, dispatches):
    """Long-sequence causal-LM lane: a llama at seq >= 2048, where dense
    O(L^2) attention would blow the arithmetic budget — this lane runs
    the in-house Pallas flash path end to end and must not OOM.

    The lane model is 8L/2048u/5504h (390M params) at batch 4: wide
    matmuls fill the MXU where the earlier 4L/512u and 8L/1024u toys did
    not.  Remat (gluon.utils.remat_call, the MXNET_BACKWARD_DO_MIRROR
    analog) is OFF by default — this config fits v5e HBM without it; flip
    the 6th arch field to 1 for configs that only fit WITH it.  Override
    via MXNET_BENCH_LLAMA_ARCH="layers,units,hidden,heads,kv_heads[,remat]".
    """
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon.model_zoo.llama import LlamaModel

    vocab = 8192   # bench vocab: keeps the LM head from dominating flops
    arch = os.environ.get("MXNET_BENCH_LLAMA_ARCH", "8,2048,5504,16,8,0")
    parts = [int(x) for x in arch.split(",")]
    layers, units, hidden, heads, kv_heads = parts[:5]
    remat = bool(parts[5]) if len(parts) > 5 else False
    mx.random.seed(0)
    np.random.seed(0)
    model = LlamaModel(vocab_size=vocab, num_layers=layers, units=units,
                       hidden=hidden, heads=heads, kv_heads=kv_heads,
                       remat=remat)
    model.initialize(mx.initializer.Normal(0.02))
    if dtype == "bfloat16":
        import jax
        jax.config.update("jax_default_matmul_precision", "default")
        import ml_dtypes
        model.cast(ml_dtypes.bfloat16)

    def loss_fn(out, labels):
        return mx.nd.softmax_cross_entropy(
            out.reshape((-1, out.shape[-1])).astype("float32"),
            labels.reshape((-1,))) / labels.size

    mesh = parallel.make_mesh()
    opt = mx.optimizer.Adam(learning_rate=1e-4,
                            multi_precision=(dtype == "bfloat16"))
    step = parallel.TrainStep(model, loss_fn, opt, mesh=mesh)
    _telemetry_on()

    def mk_batches(seed):
        r = np.random.RandomState(seed)
        toks = r.randint(0, vocab, (scan_steps, batch, seq_len)) \
            .astype(np.int32)
        labs = r.randint(0, vocab, (scan_steps, batch, seq_len)) \
            .astype(np.int32)
        return nd.array(toks), nd.array(labs)

    warm_t, warm_l = mk_batches(0)
    losses = step.run(warm_t, warm_l)
    float(np.asarray(losses.asnumpy()[-1]))

    batches = [mk_batches(i + 1) for i in range(dispatches)]
    t0 = time.perf_counter()
    for t, l in batches:
        losses = step.run(t, l)
    last_loss = float(np.asarray(losses.asnumpy()[-1], np.float64))
    dt = time.perf_counter() - t0

    n_steps = scan_steps * dispatches
    samples_per_sec = batch * n_steps / dt
    n_matmul = 0
    for pname, p in model.collect_params().items():
        if p.shape is None or "tok_" in pname:
            continue  # embedding gather excluded (PaLM MFU convention)
        n_matmul += int(np.prod(p.shape))
    # causal attention does half the pair work: 6*l*C*S instead of 12.
    # NOTE MFU counts the ALGORITHM's flops — remat's recompute is real
    # chip work but not useful math, so it is (correctly) not credited
    flops_per_token = 6 * n_matmul + 6 * layers * units * seq_len
    mfu = samples_per_sec * seq_len * flops_per_token / _peak_flops(dtype)
    return {
        "metric": f"llama{layers}L{units}_train_samples_per_sec_per_chip",
        "value": round(samples_per_sec, 3),
        "unit": "samples/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {"mfu": round(mfu, 4), "dtype": dtype, "batch": batch,
                  "seq_len": seq_len, "scan_steps": scan_steps,
                  "step_ms": round(1000 * dt / n_steps, 2),
                  "loss": last_loss, "telemetry": _lane_telemetry(),
                  "cost": _lane_cost(dt / n_steps, dtype)},
    }


def _device():
    """The device block every row carries; raises unless the process's
    first device is a TPU — this benchmark never times the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the TPU and found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}); numbers from "
            "another backend are not written under device metrics")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main():
    name = os.environ.get("MXNET_BENCH_MODEL", "bert_12_768_12")
    if os.environ.get("MXNET_BENCH_CHILD") != "1":
        # WATCHDOG SHELL: every device-touching measurement (headline
        # included) runs in a subprocess with a hard wall-clock cap, so a
        # hung lane records an error row instead of wedging the bench.
        # The child re-enters main() below.
        return _orchestrate(name)
    # batch 64 / scan 64 was the sweet spot on the v5e chip through the
    # earlier chip access (smaller batch keeps the fused step resident
    # while the scan amortizes dispatch); not re-measured on today's code
    batch = int(os.environ.get("MXNET_BENCH_BATCH", "64"))
    seq_len = int(os.environ.get("MXNET_BENCH_SEQLEN", "128"))
    dtype = os.environ.get("MXNET_BENCH_DTYPE", "bfloat16")
    scan_steps = int(os.environ.get("MXNET_BENCH_SCAN_STEPS", "128"))
    dispatches = int(os.environ.get("MXNET_BENCH_DISPATCHES", "2"))

    llama_lane, vision = _bench_kind(name)
    try:
        device = _device()
        if llama_lane:
            result = run_llama_once(batch, seq_len, dtype, scan_steps,
                                    dispatches)
        elif vision:
            result = run_vision_once(name, batch, dtype, scan_steps,
                                     dispatches)
        else:
            result = run_once(name, batch, seq_len, dtype, scan_steps,
                              dispatches)
    except Exception as e:  # noqa: BLE001 — boundary: report, then fail
        traceback.print_exc(file=sys.stderr)
        print(json.dumps(_error_result(name, vision, e)))
        return 1
    result["device"] = device
    print(json.dumps(result))
    return 0


def _bench_kind(name):
    llama_lane = name == "llama_longseq"
    vision = not name.startswith("bert") and not llama_lane
    return llama_lane, vision


def _error_result(name, vision, err):
    return {
        "metric": f"{name}_train_"
                  f"{'images' if vision else 'samples'}_per_sec_per_chip",
        "value": 0.0, "unit": f"{'images' if vision else 'samples'}/s",
        "vs_baseline": 0.0,
        "extra": {"error": f"{type(err).__name__}: {err}"[:300]},
    }


def _orchestrate(name):
    """Parent shell: headline in a capped subprocess, then the extra
    lanes — BERT at the phase-2 seq 512, a long-sequence (2048) causal
    llama that only exists because the flash path is O(L) in memory, the
    BASELINE config-2 vision lane and the input-pipeline rate.  Every lane
    is a SUBPROCESS with a hard timeout.  A lane that fails records its
    error row, the remaining lanes still run, and the process exits
    non-zero.

    Every lane emits an incremental flushed progress/error JSON row the
    moment it finishes, so a driver-level kill (rc=124) still leaves
    partial rows on stdout; and the whole orchestration runs under
    MXNET_BENCH_TOTAL_BUDGET_S (default 3300 s) — lane timeouts shrink to
    the remaining budget and lanes that no longer fit are skipped with an
    error row instead of overrunning.  The LAST stdout line remains the
    single combined result (the driver contract)."""
    llama_lane, vision = _bench_kind(name)
    t_start = time.monotonic()
    budget = float(os.environ.get("MXNET_BENCH_TOTAL_BUDGET_S", "3300"))

    def remaining():
        return budget - (time.monotonic() - t_start)

    def emit(row):
        # incremental progress row: flushed immediately so a killed bench
        # still leaves a partial trail instead of an empty tail
        row["elapsed_s"] = round(time.monotonic() - t_start, 1)
        print(json.dumps(row), flush=True)

    def ok_row(label, r):
        return {"lane": label, "status": "ok", "metric": r.get("metric"),
                "value": r.get("value"),
                "vs_baseline": r.get("vs_baseline"),
                "device": r.get("device")}

    timeout = int(os.environ.get("MXNET_BENCH_HEADLINE_TIMEOUT", "2100"))
    timeout = max(60, min(timeout, int(remaining()) - 120))
    try:
        result = _lane_subprocess({}, timeout=timeout)
        emit(ok_row("headline", result))
    except Exception as e:  # noqa: BLE001 — boundary: report, then fail
        traceback.print_exc(file=sys.stderr)
        emit({"lane": "headline", "status": "error",
              "error": f"{type(e).__name__}: {e}"[:200]})
        print(json.dumps(_error_result(name, vision, e)), flush=True)
        return 1
    failed = False
    if os.environ.get("MXNET_BENCH_LANES", "all") == "all" and not vision:
        lanes = []

        def run_lane(label, fn, cap):
            nonlocal failed
            lane_cap = int(min(cap, remaining() - 60))
            if lane_cap < 60:
                failed = True
                row = {"lane": label,
                       "error": "skipped: MXNET_BENCH_TOTAL_BUDGET_S "
                                "exhausted"}
                lanes.append(row)
                emit({**row, "status": "skipped"})
                return
            try:
                r = fn(lane_cap)
                r["lane"] = label
                lanes.append(r)
                emit(ok_row(label, r))
            except Exception as e:  # noqa: BLE001 — boundary: report
                failed = True
                traceback.print_exc(file=sys.stderr)
                row = {"lane": label,
                       "error": f"{type(e).__name__}: {e}"[:200]}
                lanes.append(row)
                emit({**row, "status": "error"})

        for label, envs in [
            ("bert_seq512", {"MXNET_BENCH_SEQLEN": "512",
                             "MXNET_BENCH_BATCH": "32",
                             "MXNET_BENCH_SCAN_STEPS": "32"}),
            ("llama_seq2048", {"MXNET_BENCH_MODEL": "llama_longseq",
                               "MXNET_BENCH_SEQLEN": "2048",
                               "MXNET_BENCH_BATCH": "4",
                               "MXNET_BENCH_SCAN_STEPS": "8"}),
            # the NARROW llama row: 8L/1024u stays in lane extras so the
            # headline can't quietly ride config width — the 2048u lane
            # fills the MXU, this one documents what the small-matmul
            # regime still costs
            ("llama_8L1024", {"MXNET_BENCH_MODEL": "llama_longseq",
                              "MXNET_BENCH_LLAMA_ARCH": "8,1024,2752,16,8,0",
                              "MXNET_BENCH_SEQLEN": "2048",
                              "MXNET_BENCH_BATCH": "8",
                              "MXNET_BENCH_SCAN_STEPS": "8"}),
            ("resnet50", {"MXNET_BENCH_MODEL": "resnet50_v1",
                          "MXNET_BENCH_BATCH": "64",
                          "MXNET_BENCH_SCAN_STEPS": "32"}),
        ]:
            run_lane(label,
                     lambda cap, _envs=envs: _lane_subprocess(_envs,
                                                              timeout=cap),
                     1500)
        run_lane("io_pipeline",
                 lambda cap: _io_bench_subprocess(timeout=cap), 900)
        result["extra"]["lanes"] = lanes

    print(json.dumps(result))
    return 1 if failed else 0


def _io_bench_subprocess(timeout=900):
    """Run benchmark/io_bench.py (host decode pipeline img/s) and return
    its best-rate JSON row.  A host lane by design: its child is pinned to
    the CPU platform so it never asks for the chip, and its row names the
    host as its device."""
    import subprocess
    n = os.cpu_count() or 1
    threads = ",".join(str(t) for t in {1, n} if t)
    p = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "benchmark", "io_bench.py"),
         "--images", "1024", "--threads", threads],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    rows = [json.loads(ln) for ln in p.stdout.strip().splitlines()
            if ln.startswith("{")]
    best = [r for r in rows
            if r.get("metric") == "image_record_iter_best_images_per_sec"]
    if not best:
        raise RuntimeError(f"io_bench produced no summary "
                           f"(rc={p.returncode}): {p.stderr.strip()[-200:]}")
    return {**best[-1], "device": {"platform": "host", "kind": "cpu",
                                   "count": n}}


def _lane_subprocess(env_overrides, timeout=1500):
    """Run one bench lane as `python bench.py` with env overrides and a
    hard wall-clock cap; returns its parsed JSON line, raising when the
    child failed (non-zero exit) or printed none."""
    import subprocess
    env = {**os.environ, **env_overrides,
           "MXNET_BENCH_LANES": "headline",   # no recursive lane fan-out
           "MXNET_BENCH_CHILD": "1"}          # children measure
    p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    if p.stderr:
        sys.stderr.write(p.stderr[-8192:])    # keep the child's traceback
    lines = [ln for ln in p.stdout.strip().splitlines()
             if ln.startswith("{")]
    row = json.loads(lines[-1]) if lines else {}
    if p.returncode != 0 or not lines:
        raise RuntimeError(
            f"lane failed (rc={p.returncode}): "
            f"{row.get('extra', {}).get('error') or p.stderr.strip()[-200:]}")
    return row


if __name__ == "__main__":
    sys.exit(main())
