"""Serving-router failure matrix (ISSUE 13) — fast, jax-free tier.

Every test drives the REAL router + the real ``ReplicaServer`` protocol
code; only the engine behind each replica is the deterministic stub in
``tests/_stub_replica.py`` (oracle tokens, millisecond latencies), so
the whole matrix — death mid-decode, death in the ``serving.reply`` ack
window, hedging with loser cancellation, admission-control shedding,
hang SIGKILL, rolling-restart drain, and router-death re-adoption —
runs inside the tier-1 budget.  The real-llama twin of the headline
rows lives in tests/test_router_chaos.py (slow, the router-chaos CI
lane).
"""

import json
import os
import subprocess
import sys
import time

import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.resilience import chaos
from mxnet_tpu.serving import engine as serving_engine
from mxnet_tpu.serving.router import (
    ReplicaDeadError, Router, RouterOverloaded, STATE_FILE,
)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _stub_replica import oracle_tokens  # noqa: E402

STUB = [sys.executable,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "_stub_replica.py")]
FAST_HB = {"MXNET_ELASTIC_HEARTBEAT_S": "0.1"}


def _counter(name):
    m = telemetry.REGISTRY.get(name)
    return 0 if m is None else m.value


def _router(tmp_path, n=2, **kw):
    kw.setdefault("env_extra", dict(FAST_HB))
    kw.setdefault("queue_max", 64)
    return Router(STUB, n, str(tmp_path), **kw).start()


def _wait(pred, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def test_router_dispatch_results_and_balance(tmp_path):
    """Basic tier: results are oracle-identical and least-loaded
    dispatch spreads concurrent work over BOTH replicas."""
    telemetry.enable()
    d0 = _counter("mxnet_router_dispatched_total")
    r = _router(tmp_path, env_extra={"STUB_TOKEN_DELAY_S": "0.02",
                                     **FAST_HB})
    try:
        assert r.wait_up() == 2
        prompts = [[i, i + 1, 5] for i in range(8)]
        hs = [r.submit(p, max_new_tokens=4) for p in prompts]
        res = [h.result(timeout=30) for h in hs]
        for p, got in zip(prompts, res):
            assert got == oracle_tokens(p, 4), p
        assert _counter("mxnet_router_dispatched_total") - d0 == 8
        served = {e["args"]["replica"]
                  for e in telemetry.get_tracer().events()
                  if e.get("cat") == "router.request"
                  and e.get("name") == "dispatched"}
        assert served == {0, 1}, served
    finally:
        r.stop()
        if not telemetry.env_enabled():
            telemetry.disable()


def test_router_prefix_affinity_dispatch(tmp_path):
    """ISSUE 15 satellite: least-loaded TIES prefer the replica that
    last served the same prompt-prefix hash (so the tier hits the
    per-replica paged-KV prefix cache), distinct prefixes still rotate,
    and a drained affinity target falls back cleanly to a survivor."""
    telemetry.enable()
    r = _router(tmp_path, affinity_tokens=4)
    try:
        assert r.wait_up() == 2

        def served_by(handle):
            for e in telemetry.get_tracer().events():
                if e.get("cat") == "router.request" \
                        and e.get("name") == "dispatched" \
                        and e.get("id") == handle.rid:
                    return e["args"]["replica"]
            raise AssertionError(f"no dispatch event for {handle.rid}")

        base = [3, 1, 4, 1]
        homes = []
        for i in range(6):          # sequential: replicas tie on load
            p = base + [10 + i]
            h = r.submit(p, max_new_tokens=3)
            assert h.result(timeout=30) == oracle_tokens(p, 3)
            homes.append(served_by(h))
        # every shared-prefix request stuck to ONE replica
        assert len(set(homes)) == 1, homes
        # distinct prefixes keep rotating over the tier
        spread = []
        for i in range(4):
            p = [50 + i, 60 + i, 70 + i, 80 + i, 1]
            h = r.submit(p, max_new_tokens=3)
            assert h.result(timeout=30) == oracle_tokens(p, 3)
            spread.append(served_by(h))
        assert set(spread) == {0, 1}, spread
        # fallback: the affinity target goes away -> survivor serves
        assert r.drain(homes[0], restart=False)
        p = base + [99]
        h = r.submit(p, max_new_tokens=3)
        assert h.result(timeout=30) == oracle_tokens(p, 3)
        assert served_by(h) == 1 - homes[0]
    finally:
        r.stop()
        if not telemetry.env_enabled():
            telemetry.disable()


def test_replica_death_mid_decode_retry_token_identical(tmp_path):
    """A replica dying BEFORE it computes (the mid-decode death shape)
    has its request transparently resubmitted to the survivor, which
    returns oracle-identical tokens; the corpse respawns on budget."""
    deaths0 = _counter("mxnet_router_replica_deaths_total")
    retries0 = _counter("mxnet_router_retries_total")
    r = _router(tmp_path, env_extra={
        "STUB_DIE_TOKEN": "77",
        "STUB_ONCE_MARKER": str(tmp_path / "die.marker"), **FAST_HB})
    try:
        killer = [77, 3, 9]
        hs = [r.submit(p, max_new_tokens=5)
              for p in (killer, [4, 5], [6, 7])]
        res = [h.result(timeout=30) for h in hs]
        for p, got in zip((killer, [4, 5], [6, 7]), res):
            assert got == oracle_tokens(p, 5), p
        assert _counter("mxnet_router_replica_deaths_total") > deaths0
        assert _counter("mxnet_router_retries_total") > retries0
        # the corpse comes back: both replicas up again
        _wait(lambda: all(s["state"] == "up"
                          for s in r.replica_status()),
              msg="respawn after death")
    finally:
        r.stop()


def test_reply_ack_window_death_no_duplicate_tokens(tmp_path):
    """serving.reply chaos: replica 0 computes the result, then dies
    BEFORE acking.  The retry on the survivor must hand the client the
    tokens exactly once, token-identical — never a duplicate/concat."""
    retries0 = _counter("mxnet_router_retries_total")
    r = _router(tmp_path, env_per_replica={
        0: {"MXNET_CHAOS": "1",
            "MXNET_CHAOS_SITES": "serving.reply:exit:1"}})
    try:
        assert r.wait_up() == 2
        p = [9, 8, 7]
        # tie-break dispatches the first request to replica 0 (the
        # chaos-armed one): it computes, hits serving.reply, and dies
        got = r.submit(p, max_new_tokens=6).result(timeout=30)
        assert got == oracle_tokens(p, 6)
        assert _counter("mxnet_router_retries_total") > retries0
    finally:
        r.stop()


def test_hedge_fires_and_loser_cancelled(tmp_path):
    """A straggling dispatch is duplicated after MXNET_ROUTER_HEDGE_S;
    the fast twin wins, and the slow loser receives a cancel (visible in
    its replica-side cancel log)."""
    hedges0 = _counter("mxnet_router_hedges_total")
    r = _router(tmp_path, hedge_s=0.25,
                env_per_replica={0: {"STUB_TOKEN_DELAY_S": "0.5"}})
    try:
        assert r.wait_up() == 2
        p = [11, 12]
        t0 = time.monotonic()
        h = r.submit(p, max_new_tokens=4)     # tie-break -> slow replica 0
        got = h.result(timeout=30)
        wall = time.monotonic() - t0
        assert got == oracle_tokens(p, 4)
        assert _counter("mxnet_router_hedges_total") == hedges0 + 1
        assert h.stats()["hedged"]
        assert wall < 1.5, f"hedge should beat the 2s straggler: {wall}"
        cancel_log = tmp_path / "cancels-0000.log"
        # the replica opens the log, then writes the line: wait for the
        # line, not for the file (read between the two it is empty)
        _wait(lambda: cancel_log.exists()
              and h.rid in cancel_log.read_text().split(),
              msg="loser cancel log")
    finally:
        r.stop()


def test_admission_shed_fails_fast_and_bounded(tmp_path):
    """Overload: submits beyond MXNET_ROUTER_QUEUE shed IMMEDIATELY with
    RouterOverloaded (never hang), and every admitted request still
    completes with a bounded e2e."""
    sheds0 = _counter("mxnet_router_shed_total")
    r = _router(tmp_path, n=1, queue_max=4,
                env_extra={"STUB_TOKEN_DELAY_S": "0.05", **FAST_HB})
    try:
        admitted, shed = [], 0
        for i in range(12):
            t0 = time.monotonic()
            try:
                admitted.append((i, r.submit([i, 2], max_new_tokens=4)))
            except RouterOverloaded:
                shed += 1
                assert time.monotonic() - t0 < 0.1, "shed must not block"
        assert shed >= 6 and len(admitted) >= 4
        assert _counter("mxnet_router_shed_total") - sheds0 == shed
        for i, h in admitted:
            assert h.result(timeout=30) == oracle_tokens([i, 2], 4)
            assert h.stats()["e2e_s"] < 10.0
    finally:
        r.stop()


def test_deadline_propagates_to_replica(tmp_path):
    """The remaining budget rides the dispatch: a request that cannot
    finish inside its deadline fails with RequestDeadlineExceeded
    promptly (not the full result timeout)."""
    r = _router(tmp_path, n=1,
                env_extra={"STUB_TOKEN_DELAY_S": "0.1", **FAST_HB})
    try:
        h = r.submit([3, 4], max_new_tokens=20, deadline_s=0.3)
        t0 = time.monotonic()
        with pytest.raises(serving_engine.RequestDeadlineExceeded):
            h.result(timeout=30)
        assert time.monotonic() - t0 < 5.0
    finally:
        r.stop()


def test_drain_rolling_restart(tmp_path):
    """drain() stops dispatch, lets in-flight finish, restarts the
    replica with a fresh pid, and the tier keeps serving — the
    rolling-restart primitive."""
    r = _router(tmp_path, env_extra={"STUB_TOKEN_DELAY_S": "0.02",
                                     **FAST_HB})
    try:
        assert r.wait_up() == 2
        hs = [r.submit([i, 9], max_new_tokens=4) for i in range(4)]
        pid0 = r.replica_status()[0]["pid"]
        assert r.drain(0, restart=True, timeout_s=30)
        for i, h in enumerate(hs):
            assert h.result(timeout=30) == oracle_tokens([i, 9], 4)
        _wait(lambda: r.replica_status()[0]["state"] == "up",
              msg="replica 0 back up after drain")
        assert r.replica_status()[0]["pid"] != pid0
        h = r.submit([42], max_new_tokens=3)
        assert h.result(timeout=30) == oracle_tokens([42], 3)
    finally:
        r.stop()


def test_hung_replica_sigkilled_and_request_retried(tmp_path):
    """A wedged replica (heartbeat stale, RPC thread blocked) is
    SIGKILLed on MXNET_ROUTER_HANG_S and its request retried."""
    deaths0 = _counter("mxnet_router_replica_deaths_total")
    r = _router(tmp_path, hang_s=1.0, env_extra={
        "STUB_WEDGE_TOKEN": "88",
        "STUB_ONCE_MARKER": str(tmp_path / "wedge.marker"), **FAST_HB})
    try:
        p = [88, 5]
        got = r.submit(p, max_new_tokens=4).result(timeout=30)
        assert got == oracle_tokens(p, 4)
        assert _counter("mxnet_router_replica_deaths_total") > deaths0
    finally:
        r.stop()


def test_replica_spawn_chaos_transient_absorbed(tmp_path):
    """router.replica_spawn chaos: a transient spawn fault is absorbed
    by the Retry policy and the tier still comes up."""
    chaos.inject("router.replica_spawn", kind="transient", times=1)
    try:
        r = _router(tmp_path, n=1)
        try:
            assert chaos.fault_count("router.replica_spawn") >= 1
            h = r.submit([5, 6], max_new_tokens=3)
            assert h.result(timeout=30) == oracle_tokens([5, 6], 3)
        finally:
            r.stop()
    finally:
        chaos.clear("router.replica_spawn")


def test_retry_budget_exhaustion_fails_not_hangs(tmp_path):
    """When every dispatch dies and the budgets are spent, the handle
    fails with ReplicaDeadError promptly instead of hanging."""
    r = _router(tmp_path, n=1, max_retries=1, max_respawns=1,
                env_extra={"STUB_DIE_TOKEN": "77", **FAST_HB})
    try:
        # no once-marker: the respawned replica dies on the retry too
        h = r.submit([77], max_new_tokens=3)
        with pytest.raises(ReplicaDeadError):
            h.result(timeout=60)
    finally:
        r.stop()


def test_router_death_mid_dispatch_readoption(tmp_path):
    """The headline crash window: the router dies (chaos 'exit' at
    router.dispatch) with requests journaled but unsent and replicas
    mid-compute.  A restarted router on the same workdir re-adopts the
    LIVE replicas through their port files and re-dispatches the
    journal: every accepted request resolves oracle-identically."""
    here = os.path.dirname(os.path.abspath(__file__))
    reqs = [{"tag": f"t{i}", "prompt": [i, 3], "max_new_tokens": 4}
            for i in range(6)]
    req_file = tmp_path / "reqs.json"
    req_file.write_text(json.dumps(reqs))
    out_file = tmp_path / "out.json"
    env = dict(os.environ, STUB_TOKEN_DELAY_S="0.1",
               **FAST_HB)
    base = [sys.executable, os.path.join(here, "_router_driver.py"),
            "--workdir", str(tmp_path), "-n", "2",
            "--requests", str(req_file), "--out", str(out_file),
            "--queue-max", "16"]
    p1 = subprocess.run(base + ["--dispatch-exit-after", "2",
                                "--keep-replicas"],
                        env=env, timeout=60)
    assert p1.returncode != 0          # chaos exit killed it mid-dispatch
    assert not out_file.exists()
    st = json.loads((tmp_path / STATE_FILE).read_text())
    assert st["phase"] == "running" and st["requests"]
    pids1 = {r["index"]: r["pid"] for r in st["replicas"]}
    p2 = subprocess.run(base + ["--resume"], env=env, timeout=120)
    assert p2.returncode == 0, "resumed driver failed"
    out = json.loads(out_file.read_text())
    for rec in reqs:
        got = out["results"][rec["tag"]]
        assert got.get("tokens") == oracle_tokens(rec["prompt"], 4), \
            (rec["tag"], got)
    # the journal's live pids were re-adopted, not respawned
    adopted = {r["index"]: r for r in
               ({s["index"]: s for s in out["replicas"]}.values())}
    assert any(r["adopted"] and r["pid"] == pids1[r["index"]]
               for r in adopted.values()), out["replicas"]
