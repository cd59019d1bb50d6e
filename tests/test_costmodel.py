"""Analytic performance observatory (ISSUE 12): the per-executable
cost/memory ledger, hardware-free MFU/roofline reports, the
fits-per-shape estimator, and the live HTTP plane.

Everything here runs with JAX_PLATFORMS=cpu on the virtual 8-device
platform — the whole point of the observatory is that XLA's cost model
needs no hardware attached.
"""

import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, telemetry
from mxnet_tpu.telemetry import costmodel, httpd


@pytest.fixture
def armed():
    """Arm the ledger for one test, restoring the disarmed default and a
    clean ledger afterwards (the registry rearm hook re-clears op jit
    caches on both transitions)."""
    costmodel.LEDGER.clear()
    costmodel.arm()
    yield costmodel.LEDGER
    costmodel.disarm()
    costmodel.LEDGER.clear()


def _tiny_step(donate=False, mesh=None, rules=None, data_spec=None,
               seed=3):
    from mxnet_tpu.gluon.model_zoo.llama import llama_model
    mx.random.seed(seed)
    net = llama_model("llama_tiny", vocab_size=64)
    net.initialize(mx.initializer.Normal(0.05))

    def loss_fn(o, l):
        return mx.nd.softmax_cross_entropy(
            o.reshape((-1, o.shape[-1])), l.reshape((-1,))) / l.size

    step = parallel.TrainStep(
        net, loss_fn, mx.optimizer.Adam(learning_rate=1e-3),
        mesh=mesh, donate=donate, partition_rules=rules,
        data_spec=data_spec)
    r = np.random.RandomState(seed)
    toks = r.randint(0, 64, (8, 16)).astype("int32")
    labs = np.roll(toks, -1, 1).astype("int32")
    return net, step, toks, labs


# ---------------------------------------------------------------------------
# the wrapper + ledger
# ---------------------------------------------------------------------------

def test_wrap_jit_records_entries_and_calls(armed):
    import jax
    import jax.numpy as jnp
    w = costmodel.wrap_jit(jax.jit(lambda x: (x @ x).sum()), "t.site")
    x = jnp.ones((32, 32), jnp.float32)
    for _ in range(3):
        w(x)
    ents = armed.entries("t.site")
    assert len(ents) == 1
    e = ents[0]
    assert e["flops"] > 0 and e["bytes_accessed"] > 0
    # memory_analysis ran: args = exactly the one 32x32 f32 input
    assert e["arg_bytes"] == x.nbytes == 32 * 32 * 4
    assert e["peak_bytes"] >= e["arg_bytes"]
    assert armed.calls("t.site") == 3
    # a second shape = a second executable at the same site
    w(jnp.ones((16, 16), jnp.float32))
    assert len(armed.entries("t.site")) == 2


def test_wrap_jit_disarmed_records_nothing():
    import jax
    import jax.numpy as jnp
    costmodel.LEDGER.clear()
    assert not costmodel.armed()
    w = costmodel.wrap_jit(jax.jit(lambda x: x + 1), "t.off")
    np.testing.assert_allclose(np.asarray(w(jnp.ones(4))), 2.0)
    assert costmodel.LEDGER.entries("t.off") == []
    assert costmodel.LEDGER.calls("t.off") == 0


def test_late_arming_analyzes_existing_executable():
    """An executable built BEFORE arm() is recorded lazily on its next
    armed dispatch (the first-call cache probe)."""
    import jax
    import jax.numpy as jnp
    costmodel.LEDGER.clear()
    w = costmodel.wrap_jit(jax.jit(lambda x: x * 2), "t.late")
    x = jnp.ones((8, 8))
    w(x)                                    # compiled while disarmed
    assert costmodel.LEDGER.entries("t.late") == []
    costmodel.arm()
    try:
        w(x)
        ents = costmodel.LEDGER.entries("t.late")
        assert len(ents) == 1 and ents[0]["flops"] >= 0
    finally:
        costmodel.disarm()
        costmodel.LEDGER.clear()


def test_registry_dispatch_ledger(armed):
    """Armed, imperative op dispatch records per-op sites; the rearm hook
    rebuilt the jit cache so the wrapper is actually in the path."""
    a = nd.array(np.random.randn(16, 16).astype(np.float32))
    (a @ a).asnumpy()
    sites = {e["site"] for e in armed.entries()}
    assert any(s.startswith("op:") for s in sites), sites


def test_trainstep_entry_and_lane_summary(armed):
    _net, step, toks, labs = _tiny_step()
    for _ in range(2):
        step(nd.array(toks, dtype="int32"), nd.array(labs, dtype="int32"))
    ents = armed.entries("parallel.TrainStep")
    assert len(ents) == 1, [e["site"] for e in armed.entries()]
    e = ents[0]
    assert e["flops"] > 1e6 and e["bytes_accessed"] > 1e6
    assert e["temp_bytes"] > 0 and e["arg_bytes"] > 0
    assert e["compile_s"] > 0           # attributed from jax.monitoring
    lane = costmodel.lane_summary(step_seconds=0.01, dtype="float32")
    assert lane["flops"] == e["flops"]
    assert lane["verdict"] in ("compute-bound", "memory-bound")
    assert lane["analytic_mfu"] > 0
    assert lane["peak_hbm_bytes"] == e["peak_bytes"]
    assert lane["executables"] == 1
    # steady state: dispatches grew, executables did not
    assert armed.calls("parallel.TrainStep") == 2


def test_report_cost_renders_table(armed):
    _net, step, toks, labs = _tiny_step()
    step(nd.array(toks, dtype="int32"), nd.array(labs, dtype="int32"))
    out = telemetry.report(cost=True)
    assert "cost ledger" in out
    assert "parallel.TrainStep" in out
    assert "verdict" not in costmodel.report_text().splitlines()[0]
    # without cost the table stays out
    assert "cost ledger" not in telemetry.report()


def test_roofline_and_peak_knobs(monkeypatch):
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("MXNET_PEAK_HBM_GBS", "100")   # 1e11 B/s
    r = costmodel.roofline(2e9, 1e9, seconds=0.01, dtype="bfloat16")
    assert r["peak_flops"] == 1e12
    assert r["peak_hbm_bytes_per_s"] == 1e11
    assert r["ridge_flops_per_byte"] == 10.0
    assert r["arithmetic_intensity"] == 2.0
    assert r["verdict"] == "memory-bound"
    assert r["roofline_mfu_bound"] == 0.2
    assert r["analytic_mfu"] == pytest.approx(2e9 / (0.01 * 1e12))
    # above the ridge: compute-bound
    assert costmodel.roofline(2e10, 1e9)["verdict"] == "compute-bound"


def test_telemetry_clear_clears_ledger(armed):
    import jax
    import jax.numpy as jnp
    w = costmodel.wrap_jit(jax.jit(lambda x: x + 1), "t.clear")
    w(jnp.ones(4))
    assert armed.entries("t.clear")
    telemetry.clear()
    assert armed.entries() == []
    assert armed.calls("t.clear") == 0


# ---------------------------------------------------------------------------
# the peak table: keyed by device_kind, no default for an unknown device
# ---------------------------------------------------------------------------

class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize("kind,bf16,bw", [
    ("TPU v5 lite", 197e12, 819e9),
    ("TPU v4", 275e12, 1228e9),
    ("TPU v5", 459e12, 2765e9),
])
def test_peaks_come_from_the_device_kind_table(monkeypatch, kind, bf16, bw):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", kind)])
    assert costmodel.peak_flops("bfloat16") == bf16
    assert costmodel.peak_flops("float32") == bf16 / 4
    assert costmodel.peak_hbm_bytes_per_s() == bw


def test_unknown_tpu_kind_raises_instead_of_defaulting(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", "TPU v9 imaginary")])
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        costmodel.peak_flops()
    with pytest.raises(ValueError, match="no peak"):
        costmodel.peak_hbm_bytes_per_s()


def test_unreachable_backend_raises_instead_of_reading_as_cpu(monkeypatch):
    import jax

    def no_backend(*a):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        costmodel.peak_flops()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        costmodel.roofline(1e9, 1e6)


# ---------------------------------------------------------------------------
# fits-per-shape estimator vs memory_analysis (the auto-sharder contract)
# ---------------------------------------------------------------------------

def test_estimate_memory_matches_memory_analysis_2x2x2(armed):
    """ISSUE 12 acceptance: the analytic estimate lands within 10% of the
    compiled memory_analysis on the (2,2,2) llama lane, and the exact
    (params + optimizer state + batch) portion matches the executable's
    argument bytes to within the traced scalars."""
    from mxnet_tpu import sharding as shd
    mesh = parallel.DeviceMesh(shape=(2, 2, 2),
                               axis_names=("dp", "tp", "sp"))
    net, step, toks, labs = _tiny_step(
        donate=True, mesh=mesh, rules=shd.llama_rules(),
        data_spec=("dp", "sp"))
    step(nd.array(toks, dtype="int32"), nd.array(labs, dtype="int32"))
    e = [x for x in armed.entries("parallel.TrainStep")
         if not x.get("error")][-1]
    est = costmodel.estimate_memory(
        net, {"dp": 2, "tp": 2, "sp": 2}, "llama", batch=8, seq=16)
    rel = abs(est["total_bytes"] - e["peak_bytes"]) / e["peak_bytes"]
    assert rel <= 0.10, (est, e)
    args_est = (est["params_bytes"] + est["opt_state_bytes"]
                + est["batch_bytes"])
    # args are exact modulo the traced step scalars (key/t/lr_vec/rescale)
    assert abs(args_est - e["arg_bytes"]) < 4096, (args_est, e["arg_bytes"])


def test_estimate_memory_single_device(armed):
    """Replicated single-chip case: the first-order activation model is
    looser here (XLA fusion workspace and fp32 attention intermediates
    are invisible to it; measured ~15% under on this config) — documented
    bound 20%.  The 10% contract is pinned on the (2,2,2) lane above."""
    import jax
    mesh = parallel.DeviceMesh(shape=(1,), axis_names=("dp",),
                               devices=jax.devices()[:1])
    net, step, toks, labs = _tiny_step(donate=True, mesh=mesh)
    step(nd.array(toks, dtype="int32"), nd.array(labs, dtype="int32"))
    e = [x for x in armed.entries("parallel.TrainStep")
         if not x.get("error")][-1]
    est = costmodel.estimate_memory(net, {"dp": 1}, None, batch=8, seq=16)
    rel = abs(est["total_bytes"] - e["peak_bytes"]) / e["peak_bytes"]
    assert rel <= 0.20, (est, e)


def test_estimate_memory_shape_semantics():
    """Sharding arithmetic only — no compiles: tp halves column-parallel
    params, absent axes degrade to unsharded, dp/sp shard the tokens."""
    params = {
        "llama0_layer0_q_weight": (64, 64),
        "llama0_layer0_o_weight": (64, 64),
        "llama0_norm_weight": (64,),
        "llama0_tok_weight": (128, 64),
    }
    base = costmodel.estimate_memory(params, {"dp": 2}, "llama",
                                     batch=8, seq=16)
    tp = costmodel.estimate_memory(params, {"dp": 1, "tp": 2}, "llama",
                                   batch=8, seq=16)
    # q (tp, None), o (None, tp), tok (tp, None) halve; the 1-d norm
    # replicates => params shrink by exactly the three 2-d tables' halves
    halved = (64 * 64 + 64 * 64 + 128 * 64) * 4 // 2
    assert base["params_bytes"] - tp["params_bytes"] == halved
    assert tp["opt_state_bytes"] == 2 * tp["params_bytes"]
    # tokens shard over dp*sp only
    assert base["tokens_per_device"] == 8 * 16 // 2
    sp = costmodel.estimate_memory(params, {"dp": 2, "sp": 2}, "llama",
                                   batch=8, seq=16)
    assert sp["tokens_per_device"] == 8 * 16 // 4
    # an indivisible dim refuses to shard (resolve_spec degradation)
    odd = costmodel.estimate_memory({"a_q_weight": (63, 64)},
                                    {"tp": 2}, "llama", batch=1, seq=1)
    assert odd["params_bytes"] == 63 * 64 * 4
    with pytest.raises(ValueError):
        costmodel.estimate_memory(params, {"dp": 2}, "llama", batch=8,
                                  seq=16, optimizer="rmsprop")


# ---------------------------------------------------------------------------
# the live HTTP plane
# ---------------------------------------------------------------------------

@pytest.fixture
def server():
    port = httpd.start(port=0, host="127.0.0.1")
    yield port
    httpd.stop()


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
        return r.status, r.headers.get("Content-Type"), r.read().decode()


def test_httpd_metrics_identical_under_concurrent_scrape(server):
    telemetry.counter("mxnet_test_httpd_total", "t").inc(7)
    want = telemetry.to_prometheus()
    results, errors = [], []

    def scrape():
        try:
            results.append(_get(server, "/metrics"))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=scrape) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errors
    assert len(results) == 8
    for status, ctype, body in results:
        assert status == 200
        assert ctype.startswith("text/plain")
        assert body == want             # exposition identical to registry
    assert "mxnet_test_httpd_total 7" in want


def test_httpd_statusz_and_ledger(server, armed):
    import jax
    import jax.numpy as jnp
    costmodel.wrap_jit(jax.jit(lambda x: x + 1), "t.http")(jnp.ones(4))
    status, ctype, body = _get(server, "/statusz")
    assert status == 200 and ctype == "application/json"
    s = json.loads(body)
    assert s["pid"] == os.getpid()
    assert s["costmodel_armed"] is True
    assert "MXNET_TELEMETRY_PORT" in s["knobs"]
    assert s["stepclock"]["verdict"] in (
        "idle", "input-bound", "comms-bound", "compute-bound")
    status, _ctype, body = _get(server, "/ledger.json")
    led = json.loads(body)
    assert any(e["site"] == "t.http"
               for e in led["costmodel"]["entries"])
    assert "t.http" in led["costmodel_sites"]
    status, _c, body = _get(server, "/")
    assert "/metrics" in body


def test_httpd_healthz_probe(server, monkeypatch, tmp_path, fresh_heartbeat):
    """ISSUE 13 satellite: /healthz is the router's liveness probe —
    200 with no heartbeat armed (the reply itself proves liveness), 200
    + {phase, heartbeat_age_s} while the armed beater is fresh, 503 once
    it goes stale past MXNET_ROUTER_HANG_S."""
    hb = fresh_heartbeat
    status, ctype, body = _get(server, "/healthz")
    rec = json.loads(body)
    assert status == 200 and ctype == "application/json"
    assert rec["ok"] and not rec["armed"]
    monkeypatch.setenv("MXNET_ELASTIC_HEARTBEAT_DIR", str(tmp_path))
    try:
        # long interval: exactly one beat lands, then we age it by hand
        assert hb.start(interval_s=600)
        status, _c, body = _get(server, "/healthz")
        rec = json.loads(body)
        assert status == 200 and rec["ok"] and rec["armed"]
        assert rec["phase"] == "spawned"
        assert rec["heartbeat_age_s"] < 30
        import time as _time
        monkeypatch.setattr(hb, "_last_beat",
                            _time.monotonic() - 10_000)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(server, "/healthz")
        assert ei.value.code == 503
        stale = json.loads(ei.value.read())
        assert not stale["ok"] and stale["heartbeat_age_s"] > 100
    finally:
        hb.stop()


def test_httpd_404_and_stop():
    port = httpd.start(port=0, host="127.0.0.1")
    assert httpd.running() and httpd.port() == port
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(port, "/nope")
    assert ei.value.code == 404
    httpd.stop()
    assert not httpd.running() and httpd.port() is None
    # idempotent
    httpd.stop()


# ---------------------------------------------------------------------------
# export plane: shard snapshot + offline report CLI
# ---------------------------------------------------------------------------

def test_snapshot_carries_costmodel_and_cli_reports_it(armed, tmp_path):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.telemetry import aggregate
    costmodel.wrap_jit(jax.jit(lambda x: (x @ x)), "t.cli")(
        jnp.ones((8, 8)))
    snap = aggregate.snapshot()
    assert any(e["site"] == "t.cli" for e in snap["costmodel"]["entries"])
    path = aggregate.export_snapshot(directory=str(tmp_path))
    assert path is not None

    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "telemetry_report.py"),
         "--dir", str(tmp_path), "--cost", "--json"],
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    rep = json.loads(p.stdout)
    cost = rep["ranks"][0]["cost"]
    assert "t.cli" in cost
    assert cost["t.cli"]["executables"] == 1
    assert cost["t.cli"]["verdict"] in ("compute-bound", "memory-bound")
