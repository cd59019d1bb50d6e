"""Resilience subsystem tests (ISSUE 3): retry/deadline policies, chaos
fault injection, graceful degradation in DataLoader and the fused kvstore
path, preemption-safe checkpointing, and the chaos end-to-end acceptance
run (mid-run fault → auto_resume → bit-identical parameters).

Every blocking path exercised here is deadline-bounded — the suite must
never hang.  The CI chaos lane re-runs this file with MXNET_CHAOS=1.
"""

import os
import signal
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.base import MXNetError
from mxnet_tpu.resilience import (
    ChaosTransientError, ChaosWorkerDeath, Deadline, KVStoreTimeoutError,
    Retry, RetryExhaustedError, chaos, policies,
)
from mxnet_tpu.telemetry import REGISTRY


def _metric(name):
    m = REGISTRY.get(name)
    return m.value if m is not None else 0


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.clear()
    yield
    chaos.clear()


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------

def test_retry_absorbs_transient():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ChaosTransientError("flake")
        return "ok"

    before = _metric("mxnet_resilience_retries_total")
    r = Retry(max_retries=3, backoff_s=0.001, backoff_max_s=0.01, site="t")
    assert r.call(flaky) == "ok"
    assert len(calls) == 3
    assert _metric("mxnet_resilience_retries_total") == before + 2


def test_retry_exhausts_and_chains_cause():
    r = Retry(max_retries=2, backoff_s=0.001, site="t")
    with pytest.raises(RetryExhaustedError) as ei:
        r.call(lambda: (_ for _ in ()).throw(ChaosTransientError("always")))
    assert isinstance(ei.value.__cause__, ChaosTransientError)


def test_retry_does_not_retry_permanent_errors():
    calls = []

    def fatal():
        calls.append(1)
        raise ChaosWorkerDeath("dead")

    r = Retry(max_retries=5, backoff_s=0.001, site="t")
    with pytest.raises(ChaosWorkerDeath):
        r.call(fatal)
    assert len(calls) == 1  # no retry: the failure is not transient


def test_retry_env_defaults(monkeypatch):
    monkeypatch.setenv("MXNET_RESILIENCE_MAX_RETRIES", "7")
    monkeypatch.setenv("MXNET_RESILIENCE_BACKOFF_S", "0.125")
    r = Retry()
    assert r.max_retries == 7
    assert r.backoff_s == 0.125


def test_deadline_bounds_a_hung_call():
    d = Deadline(timeout_s=0.2, site="unit")
    before = _metric("mxnet_resilience_deadline_exceeded_total")
    t0 = time.monotonic()
    with pytest.raises(KVStoreTimeoutError, match="deadline"):
        d.call(time.sleep, 30)
    assert time.monotonic() - t0 < 5  # bounded, not 30s
    assert _metric("mxnet_resilience_deadline_exceeded_total") == before + 1


def test_deadline_passes_values_and_exceptions():
    d = Deadline(timeout_s=5, site="unit")
    assert d.call(lambda: 41 + 1) == 42
    with pytest.raises(ValueError, match="boom"):
        d.call(lambda: (_ for _ in ()).throw(ValueError("boom")))
    # disabled deadline = direct call
    assert Deadline(timeout_s=0).call(lambda: "direct") == "direct"


def test_deadline_reuses_worker_and_recovers_after_timeout():
    d = Deadline(timeout_s=0.5, site="unit")
    assert d.call(lambda: 1) == 1
    worker = d._worker
    assert d.call(lambda: 2) == 2
    assert d._worker is worker  # persistent: no per-call thread spawn
    with pytest.raises(KVStoreTimeoutError):
        d.call(time.sleep, 30)
    assert d.call(lambda: 3) == 3  # fresh worker after the wedged one
    assert d._worker is not worker
    d.close()


def test_timeout_is_not_retried():
    """Retry must not re-enter a timed-out collective (desync hazard)."""
    calls = []

    def wedged():
        calls.append(1)
        raise KVStoreTimeoutError("peer gone")

    with pytest.raises(KVStoreTimeoutError):
        Retry(max_retries=3, backoff_s=0.001).call(wedged)
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# chaos
# ---------------------------------------------------------------------------

def test_chaos_deterministic_counts():
    before = _metric("mxnet_resilience_faults_injected_total")
    chaos.inject("unit.site", kind="transient", times=2, after=1)
    chaos.hit("unit.site")  # hit 1: within `after`, passes
    with pytest.raises(ChaosTransientError):
        chaos.hit("unit.site")  # hit 2 fires
    with pytest.raises(ChaosTransientError):
        chaos.hit("unit.site")  # hit 3 fires
    chaos.hit("unit.site")  # times exhausted, passes
    assert chaos.fault_count("unit.site") >= 2
    assert _metric("mxnet_resilience_faults_injected_total") == before + 2
    chaos.clear("unit.site")
    assert not chaos.active()
    chaos.hit("unit.site")  # disarmed: no-op


def test_chaos_delay_kind():
    chaos.inject("unit.delay", kind="delay", times=1, delay_s=0.05)
    t0 = time.monotonic()
    chaos.hit("unit.delay")
    assert time.monotonic() - t0 >= 0.04


def test_chaos_env_arming_survives_malformed_spec(monkeypatch):
    monkeypatch.setenv("MXNET_CHAOS", "1")
    monkeypatch.setenv("MXNET_CHAOS_SITES",
                       "bad.site:transient:two,good.site:delay:1:0.001")
    with pytest.warns(UserWarning, match="malformed MXNET_CHAOS_SITES"):
        chaos._arm_from_env()  # a spec typo must not raise (import-time)
    try:
        assert "good.site" in chaos.sites()
        assert "bad.site" not in chaos.sites()
    finally:
        chaos.clear()


def test_chaos_env_arming(monkeypatch):
    monkeypatch.setenv("MXNET_CHAOS", "1")
    monkeypatch.setenv("MXNET_CHAOS_SITES",
                       "env.site:transient:2,env.other:delay:1:0.001")
    chaos._arm_from_env()
    try:
        assert "env.site" in chaos.sites()
        assert "env.other" in chaos.sites()
        with pytest.raises(ChaosTransientError):
            chaos.hit("env.site")
    finally:
        chaos.clear()


# ---------------------------------------------------------------------------
# kvstore wiring
# ---------------------------------------------------------------------------

def test_dist_kvstore_retry_absorbs_transient_faults():
    """Acceptance: injected transient kvstore faults are absorbed by
    retry with mxnet_resilience_retries_total > 0."""
    kv = mx.kv.create("dist_tpu_sync")
    kv._retry.backoff_s = 0.001
    kv.init(0, mx.nd.zeros((3,)))
    before = _metric("mxnet_resilience_retries_total")
    chaos.inject("kvstore.allreduce", kind="transient", times=2)
    kv.push(0, mx.nd.ones((3,)))
    out = mx.nd.zeros((3,))
    kv.pull(0, out)
    np.testing.assert_allclose(out.asnumpy(), 1.0)
    assert _metric("mxnet_resilience_retries_total") == before + 2


def test_dist_kvstore_retry_exhaustion_raises():
    kv = mx.kv.create("dist_tpu_sync")
    kv._retry = Retry(max_retries=1, backoff_s=0.001,
                      site="kvstore.allreduce")
    kv.init(1, mx.nd.zeros((2,)))
    chaos.inject("kvstore.allreduce", kind="transient", times=0)  # unbounded
    with pytest.raises(RetryExhaustedError):
        kv.push(1, mx.nd.ones((2,)))


def test_dist_barrier_chaos_site_and_timeout_message(monkeypatch):
    kv = mx.kv.create("dist_tpu_sync")
    # armed fault at the named site fires from barrier()
    chaos.inject("dist.barrier", kind="fatal", times=1)
    with pytest.raises(ChaosWorkerDeath):
        kv.barrier()
    chaos.clear()
    # a deadline expiry surfaces as KVStoreTimeoutError naming the rank
    # set a peer could be missing from (simulated multi-process)
    import jax
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(
        kv, "_allreduce",
        lambda arr: (_ for _ in ()).throw(KVStoreTimeoutError("deadline")))
    with pytest.raises(KVStoreTimeoutError, match=r"rank 0 .* ranks \[1\]"):
        kv.barrier()


def test_dist_bringup_timeout_names_rank(monkeypatch):
    """_ensure_dist with an unreachable coordinator must raise a clear
    KVStoreTimeoutError instead of hanging (satellite: _barrier/_ensure_dist
    hanging forever when a peer never arrives)."""
    import jax

    def fake_initialize(**kwargs):
        assert kwargs.get("initialization_timeout") == 2
        raise RuntimeError("rendezvous timed out waiting for peers")

    monkeypatch.setenv("MXNET_DIST_COORDINATOR", "127.0.0.1:1")
    monkeypatch.setenv("MXNET_DIST_NUM_WORKERS", "2")
    monkeypatch.setenv("MXNET_DIST_RANK", "0")
    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    kv = mx.kv.create("dist_tpu_sync")
    kv._deadline = Deadline(timeout_s=2, site="kvstore.allreduce")
    with pytest.raises(KVStoreTimeoutError, match="rank 0 .* 2 workers"):
        kv._ensure_dist()


def test_fused_bucket_failure_falls_back_per_key(monkeypatch):
    """Graceful degradation: a failing fused bucket replays per-key with
    the same result."""
    from mxnet_tpu.kvstore import fusion

    kv = mx.kv.create("local")
    kv.init([0, 1], [mx.nd.zeros((4,)), mx.nd.zeros((3,))])
    vals = [[mx.nd.ones((4,)), mx.nd.ones((4,)) * 2],
            [mx.nd.ones((3,)) * 3, mx.nd.ones((3,)) * 4]]
    outs = [mx.nd.zeros((4,)), mx.nd.zeros((3,))]

    def boom(self, bucket, arrays):
        raise RuntimeError("bucket executable failed")

    monkeypatch.setattr(fusion.GradBucketer, "reduce_bucket", boom)
    before = _metric("mxnet_resilience_fallbacks_total")
    with pytest.warns(UserWarning, match="falling back to per-key"):
        kv.pushpull_list([0, 1], vals, outs)
    np.testing.assert_allclose(outs[0].asnumpy(), 3.0)  # 1 + 2
    np.testing.assert_allclose(outs[1].asnumpy(), 7.0)  # 3 + 4
    # one degradation EVENT (the bucket); per-key detail rides the fused
    # fallback-keys counter
    assert _metric("mxnet_resilience_fallbacks_total") == before + 1
    assert _metric("mxnet_kvstore_fused_fallback_keys_total") >= 2


# ---------------------------------------------------------------------------
# DataLoader degradation
# ---------------------------------------------------------------------------

class _ArangeDataset(gluon.data.Dataset):
    def __init__(self, n=8):
        self._n = n

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        return mx.nd.array(np.full((2,), i, np.float32))


def _batch_values(loader):
    return [b.asnumpy()[:, 0].tolist() for b in loader]


def test_dataloader_transient_fault_refetches_in_process():
    loader = gluon.data.DataLoader(_ArangeDataset(8), batch_size=2,
                                   num_workers=2, timeout=30)
    before = _metric("mxnet_resilience_fallbacks_total")
    chaos.inject("dataloader.fetch", kind="transient", times=1)
    with pytest.warns(UserWarning, match="refetched in-process"):
        vals = _batch_values(loader)
    assert vals == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert _metric("mxnet_resilience_fallbacks_total") == before + 1
    loader._shutdown_pool()


def test_dataloader_degrades_to_single_process(monkeypatch):
    monkeypatch.setenv("MXNET_DATALOADER_RETRIES", "1")
    loader = gluon.data.DataLoader(_ArangeDataset(12), batch_size=2,
                                   num_workers=2, timeout=30)
    assert loader._pool is not None
    # one fault = the full retry budget (retries=1): absorbed in-process,
    # then the loader degrades permanently to single-process loading
    chaos.inject("dataloader.fetch", kind="transient", times=1)
    with pytest.warns(UserWarning):
        vals = _batch_values(loader)
    # order and values survive the degradation, and the pool is gone
    assert vals == [[0, 1], [2, 3], [4, 5], [6, 7], [8, 9], [10, 11]]
    assert loader._pool is None


def test_dataloader_workers_give_the_in_process_batches():
    rng = np.random.RandomState(0)
    ds = gluon.data.ArrayDataset(rng.randn(10, 3).astype(np.float32),
                                 np.arange(10, dtype=np.float32))
    loaders = [gluon.data.DataLoader(ds, batch_size=3, num_workers=n,
                                     timeout=30) for n in (0, 2)]
    assert loaders[1]._pool is not None
    before = _metric("mxnet_resilience_fallbacks_total")
    try:
        alone, pooled = ([(d.asnumpy(), l.asnumpy()) for d, l in loader]
                         for loader in loaders)
    finally:
        loaders[1]._shutdown_pool()
    # every batch came from a worker: none was refetched in-process
    assert _metric("mxnet_resilience_fallbacks_total") == before
    assert len(alone) == len(pooled) == 4
    for (d0, l0), (d2, l2) in zip(alone, pooled):
        np.testing.assert_array_equal(d0, d2)
        np.testing.assert_array_equal(l0, l2)


def test_dataloader_workers_refuse_a_dataset_that_does_not_pickle():
    ds = gluon.data.SimpleDataset(list(range(8))).transform(lambda x: 2 * x)
    with pytest.raises(TypeError, match="_LazyTransformDataset.* does not "
                                        "pickle"):
        gluon.data.DataLoader(ds, batch_size=2, num_workers=2)
    # in-process loading needs no pickle
    loader = gluon.data.DataLoader(ds, batch_size=2, num_workers=0)
    assert [b.asnumpy().tolist() for b in loader][0] == [0, 2]


class _WorkerKillerDataset(gluon.data.Dataset):
    """__getitem__(0) kills the WORKER process (never the parent)."""

    def __init__(self, n=6):
        self._n = n
        self._parent = os.getpid()

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        if i == 0 and os.getpid() != self._parent:
            os._exit(1)  # real worker death
        return mx.nd.array(np.full((2,), i, np.float32))


@pytest.mark.slow
def test_dataloader_survives_real_worker_death():
    loader = gluon.data.DataLoader(_WorkerKillerDataset(6), batch_size=2,
                                   num_workers=1, timeout=3)
    with pytest.warns(UserWarning, match="refetched in-process"):
        vals = _batch_values(loader)
    assert vals == [[0, 1], [2, 3], [4, 5]]
    loader._shutdown_pool()


# ---------------------------------------------------------------------------
# checkpoint atomicity + SIGTERM + elastic resume
# ---------------------------------------------------------------------------

def test_killed_save_is_invisible_and_replayable(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path / "ck"), max_to_keep=5)
    mgr.save(0, extra={"x": mx.nd.array([1.0])})
    chaos.inject("checkpoint.save", kind="fatal", times=1)
    with pytest.raises(ChaosWorkerDeath):
        mgr.save(1, extra={"x": mx.nd.array([2.0])})
    chaos.clear()
    # the half-committed step is invisible...
    assert mgr.latest_step() == 0
    assert 1 in mgr.all_steps()  # ...even though its data is on disk
    # ...and the replayed save lands over the orphan
    mgr.save(1, extra={"x": mx.nd.array([2.5])})
    step, extra = mgr.restore()
    assert step == 1
    assert float(extra["x"].asnumpy()[0]) == 2.5


def _make_net_trainer(kvstore=None, lr=0.05):
    mx.random.seed(7)
    net = gluon.nn.Dense(4, in_units=6, prefix="net_")
    net.initialize(mx.initializer.Xavier())
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": lr},
                       kvstore=kvstore if kvstore is not None else "device")
    return net, tr


def _step(net, tr, x, y, lossf):
    with autograd.record():
        loss = lossf(net(x), y)
    loss.backward()
    tr.step(x.shape[0])
    return float(loss.mean().asnumpy())


def test_sigterm_triggers_emergency_save_and_clean_stop(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    r = np.random.RandomState(3)
    X = mx.nd.array(r.randn(8, 6).astype(np.float32))
    Y = mx.nd.array(r.randint(0, 4, (8,)))
    net, tr = _make_net_trainer()
    ckdir = str(tmp_path / "sig")

    def run(step):
        _step(net, tr, X, Y, lossf)
        if step == 2:
            os.kill(os.getpid(), signal.SIGTERM)  # preemption notice
        return step < 50  # would run long — SIGTERM must stop it

    prev_handler = signal.getsignal(signal.SIGTERM)
    with pytest.warns(UserWarning, match="SIGTERM"):
        last = mx.checkpoint.auto_resume(run, ckdir, net=net, trainer=tr,
                                         save_every=10)
    assert last == 2  # stopped at the preempted step, not 50
    mgr = mx.checkpoint.CheckpointManager(ckdir)
    assert mgr.latest_step() == 2  # emergency save happened off-cadence
    # prior SIGTERM disposition restored after auto_resume (SIG_DFL
    # historically; the flight recorder's chaining dump handler since
    # ISSUE 10 armed it at import)
    assert signal.getsignal(signal.SIGTERM) == prev_handler


def test_sigterm_during_fault_stops_without_replay(tmp_path):
    """Preemption + a failing step (peers already gone) must stop at the
    last checkpoint instead of replaying into a wedged collective."""
    pytest.importorskip("orbax.checkpoint")
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    X, Y = mx.nd.ones((4, 6)), mx.nd.array(np.zeros(4))
    net, tr = _make_net_trainer()

    def run(step):
        if step == 0:
            _step(net, tr, X, Y, lossf)
            return True  # step 0 completes and checkpoints
        os.kill(os.getpid(), signal.SIGTERM)  # preemption lands...
        raise RuntimeError("collective died during preemption")

    with pytest.warns(UserWarning, match="without replay"):
        last = mx.checkpoint.auto_resume(run, str(tmp_path / "sf"), net=net,
                                         trainer=tr, save_every=1)
    assert last == 0  # stopped at the checkpointed step, no replay loop


def test_auto_resume_restart_policy_replays_from_last_good(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    r = np.random.RandomState(1)
    X = mx.nd.array(r.randn(8, 6).astype(np.float32))
    Y = mx.nd.array(r.randint(0, 4, (8,)))
    net, tr = _make_net_trainer()
    steps_run = []

    def run(step):
        if step == 2 and steps_run.count(2) == 0:
            steps_run.append(step)
            raise RuntimeError("simulated worker fault")
        steps_run.append(step)
        _step(net, tr, X, Y, lossf)
        return step < 3

    before = _metric("mxnet_resilience_resumes_total")
    with pytest.warns(UserWarning, match="resumed from checkpoint step 1"):
        last = mx.checkpoint.auto_resume(run, str(tmp_path / "rs"), net=net,
                                         trainer=tr, save_every=1)
    assert last == 3
    assert steps_run == [0, 1, 2, 2, 3]  # step 2 replayed after the fault
    assert _metric("mxnet_resilience_resumes_total") == before + 1


def test_auto_resume_fault_before_first_checkpoint_reraises(tmp_path):
    pytest.importorskip("orbax.checkpoint")

    def run(step):
        raise RuntimeError("dead on arrival")

    with pytest.raises(RuntimeError, match="dead on arrival"):
        mx.checkpoint.auto_resume(run, str(tmp_path / "doa"))


def test_auto_resume_restarts_bounded(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    net, tr = _make_net_trainer()
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    X, Y = mx.nd.ones((4, 6)), mx.nd.array(np.zeros(4))
    calls = []

    def run(step):
        if step == 0 and not calls:
            calls.append("ok")
            _step(net, tr, X, Y, lossf)
            return True
        raise RuntimeError("permanent fault")

    with pytest.raises(RuntimeError, match="permanent fault"), \
            pytest.warns(UserWarning):
        mx.checkpoint.auto_resume(run, str(tmp_path / "bd"), net=net,
                                  trainer=tr, save_every=1, max_restarts=2)


# ---------------------------------------------------------------------------
# acceptance: chaos end-to-end
# ---------------------------------------------------------------------------

def test_chaos_e2e_mid_run_fault_resumes_bit_identical(tmp_path):
    """ISSUE 3 acceptance: a Gluon train loop with an injected mid-run
    worker fault resumes via auto_resume from the last atomic checkpoint
    and reaches parameters BIT-identical to an uninterrupted run with the
    same RNG seed; injected transient kvstore faults are absorbed by
    retry; every blocking path is deadline-bounded (no hangs)."""
    pytest.importorskip("orbax.checkpoint")
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    r = np.random.RandomState(0)
    X = mx.nd.array(r.randn(8, 6).astype(np.float32))
    Y = mx.nd.array(r.randint(0, 4, (8,)))
    total = 6

    def make_state():
        kv = mx.kv.create("dist_tpu_sync")
        kv.set_bucket_size(0)  # per-key path → every push crosses the
        kv._retry.backoff_s = 0.001  # kvstore.allreduce chaos site
        return _make_net_trainer(kvstore=kv)

    def params_of(net):
        return {k: p.data().asnumpy().copy()
                for k, p in net.collect_params().items()}

    # uninterrupted reference run
    net_r, tr_r = make_state()
    for _ in range(total):
        _step(net_r, tr_r, X, Y, lossf)
    ref = params_of(net_r)

    # chaos run: transient kvstore faults early + a fatal worker fault
    # mid-run (fires inside Trainer.step on the 4th step)
    retries_before = _metric("mxnet_resilience_retries_total")
    chaos.inject("kvstore.allreduce", kind="transient", times=2)
    chaos.inject("trainer.step", kind="fatal", times=1, after=3)
    net_c, tr_c = make_state()

    def run(step):
        _step(net_c, tr_c, X, Y, lossf)
        return step < total - 1

    with pytest.warns(UserWarning, match="resumed from checkpoint step 2"):
        last = mx.checkpoint.auto_resume(run, str(tmp_path / "e2e"),
                                         net=net_c, trainer=tr_c,
                                         save_every=1)
    assert last == total - 1
    assert _metric("mxnet_resilience_retries_total") > retries_before
    got = params_of(net_c)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
