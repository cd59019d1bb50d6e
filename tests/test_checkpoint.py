"""Checkpoint tests: dmlc .params byte format + orbax manager +
kill-and-resume loss-curve reproduction."""

import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.base import MXNetError


# ---------------------------------------------------------------------------
# dmlc .params byte format
# ---------------------------------------------------------------------------

def test_dmlc_roundtrip_dict(tmp_path):
    f = str(tmp_path / "x.params")
    data = {"arg:w": mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3)),
            "arg:b": mx.nd.array(np.array([1.5], np.float64)),
            "aux:i": mx.nd.array(np.array([[7, 8]], np.int64))}
    mx.nd.save(f, data, format="dmlc")
    out = mx.nd.load(f)
    assert set(out) == set(data)
    for k in data:
        np.testing.assert_array_equal(out[k].asnumpy(), data[k].asnumpy())
        assert out[k].dtype == data[k].dtype


def test_dmlc_roundtrip_list(tmp_path):
    f = str(tmp_path / "l.params")
    data = [mx.nd.ones((3,)), mx.nd.zeros((2, 2))]
    mx.nd.save(f, data, format="dmlc")
    out = mx.nd.load(f)
    assert isinstance(out, list) and len(out) == 2
    np.testing.assert_array_equal(out[0].asnumpy(), 1.0)


def test_dmlc_exact_golden_bytes(tmp_path):
    # pin the byte layout (reference ndarray.cc NDArray::Save): any format
    # drift breaks interchange silently — assert the exact bytes
    from mxnet_tpu import dmlc_params
    arr = np.array([[1.0, 2.0]], np.float32)
    blob = dmlc_params.save_bytes([arr], ["arg:w"])
    expect = b"".join([
        struct.pack("<QQ", 0x112, 0),          # list magic + reserved
        struct.pack("<Q", 1),                  # one array
        struct.pack("<I", 0xF993FAC9),         # NDArray V2 magic
        struct.pack("<i", 0),                  # dense stype
        struct.pack("<I", 2),                  # ndim
        struct.pack("<qq", 1, 2),              # int64 dims
        struct.pack("<ii", 1, 0),              # cpu:0
        struct.pack("<i", 0),                  # type_flag f32
        arr.tobytes(),
        struct.pack("<Q", 1),                  # one name
        struct.pack("<Q", 5), b"arg:w",
    ])
    assert blob == expect
    back, names = dmlc_params.load_bytes(blob)
    np.testing.assert_array_equal(back[0], arr)
    assert names == ["arg:w"]


def test_dmlc_reads_v1_era_32bit_dims():
    # V1-era files carried 32-bit dims; the reader probes both widths
    from mxnet_tpu import dmlc_params
    arr = np.array([3.0, 4.0, 5.0], np.float32)
    blob = b"".join([
        struct.pack("<QQ", 0x112, 0), struct.pack("<Q", 1),
        struct.pack("<I", 0xF993FAC9), struct.pack("<i", 0),
        struct.pack("<I", 1), struct.pack("<i", 3),   # 32-bit dim
        struct.pack("<ii", 1, 0), struct.pack("<i", 0),
        arr.tobytes(), struct.pack("<Q", 0),
    ])
    back, names = dmlc_params.load_bytes(blob)
    np.testing.assert_array_equal(back[0], arr)


def test_dmlc_reads_v1_era_2d_f64():
    # the width probe must not let int64 parsing swallow a 2-D 32-bit-dims
    # header (code-review regression: f64 (3,4) misparsed as a huge shape)
    from mxnet_tpu import dmlc_params
    arr = np.zeros((3, 4), np.float64)
    arr[0, 1] = 2.5
    blob = b"".join([
        struct.pack("<QQ", 0x112, 0), struct.pack("<Q", 1),
        struct.pack("<I", 0xF993FAC9), struct.pack("<i", 0),
        struct.pack("<I", 2), struct.pack("<ii", 3, 4),  # 32-bit dims
        struct.pack("<ii", 1, 0), struct.pack("<i", 1),  # f64
        arr.tobytes(), struct.pack("<Q", 0),
    ])
    back, _ = dmlc_params.load_bytes(blob)
    np.testing.assert_array_equal(back[0], arr)


def test_dmlc_rejects_garbage():
    from mxnet_tpu import dmlc_params
    with pytest.raises(MXNetError, match="magic"):
        dmlc_params.load_bytes(b"\x00" * 64)
    assert not dmlc_params.is_dmlc_params(b"PK\x03\x04....")


def test_npz_default_unchanged(tmp_path):
    f = str(tmp_path / "y.params")
    mx.nd.save(f, {"w": mx.nd.ones((2,))})
    with open(f, "rb") as fh:
        assert fh.read(2) == b"PK"  # zip container (np.savez)
    out = mx.nd.load(f)
    np.testing.assert_array_equal(out["w"].asnumpy(), 1.0)


# ---------------------------------------------------------------------------
# orbax manager + auto-resume
# ---------------------------------------------------------------------------

def _make_net_trainer(lr=0.05):
    mx.random.seed(7)
    # fixed prefix: checkpoint keys are structural names, and the global
    # name counter would otherwise differ between the two "processes"
    net = gluon.nn.Dense(4, in_units=6, prefix="net_")
    net.initialize(mx.initializer.Xavier())
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": lr})
    return net, tr


def _step(net, tr, x, y, lossf):
    with autograd.record():
        loss = lossf(net(x), y)
    loss.backward()
    tr.step(x.shape[0])
    return float(loss.mean().asnumpy())


def test_checkpoint_manager_roundtrip(tmp_path):
    net, tr = _make_net_trainer()
    x = mx.nd.ones((8, 6))
    y = mx.nd.array(np.arange(8) % 4)
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    _step(net, tr, x, y, lossf)
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path / "ck"), max_to_keep=2)
    assert mgr.latest_step() is None
    mgr.save(0, net=net, trainer=tr, extra={"epoch": mx.nd.array([3.0])})
    w0 = list(net.collect_params().values())[0].data().asnumpy().copy()
    _step(net, tr, x, y, lossf)  # mutate
    step, extra = mgr.restore(net=net, trainer=tr)
    assert step == 0
    np.testing.assert_allclose(
        list(net.collect_params().values())[0].data().asnumpy(), w0)
    assert float(extra["epoch"].asnumpy()[0]) == 3.0


def test_manifest_world_audit_on_resized_restore(tmp_path):
    """Resume-with-different-n audit (ISSUE 11): the manifest records
    the world that committed each step; restoring into a different world
    warns (a documented resize point), counts, and still restores the
    topology-free params."""
    import json
    import os
    import warnings
    from mxnet_tpu.telemetry import REGISTRY

    net, _tr = _make_net_trainer()
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path / "ck"))
    mgr.save(0, net=net)
    # single-process save records world n=1, unsharded
    assert mgr.world_size(0) == 1
    man_path = os.path.join(str(tmp_path / "ck"), "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    assert man["world"]["0"] == {"n": 1, "sharded": False}
    # same-world restore: silent, uncounted
    before = REGISTRY.get("mxnet_checkpoint_resize_restores_total").value
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mgr.restore(net=net)
    assert REGISTRY.get(
        "mxnet_checkpoint_resize_restores_total").value == before
    # pretend a 4-rank world committed step 0 → elastic resize point
    man["world"]["0"] = {"n": 4, "sharded": False}
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.warns(UserWarning, match="elastic resize point"):
        step, _ = mgr.restore(net=net)
    assert step == 0
    assert REGISTRY.get(
        "mxnet_checkpoint_resize_restores_total").value == before + 1
    # a SHARDED save restoring elsewhere gets the louder warning
    man["world"]["0"] = {"n": 4, "sharded": True}
    with open(man_path, "w") as f:
        json.dump(man, f)
    with pytest.warns(UserWarning, match="topology-bound"):
        mgr.restore(net=net)
    # pre-audit manifests (no world map) stay silent
    del man["world"]
    with open(man_path, "w") as f:
        json.dump(man, f)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mgr.restore(net=net)
    assert mgr.world_size(0) is None


def test_kill_and_resume_reproduces_loss_curve(tmp_path):
    # acceptance: kill mid-training and resume; the resumed curve
    # must equal the unkilled one (params + adam state + step counts)
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    r = np.random.RandomState(0)
    X = mx.nd.array(r.randn(8, 6).astype(np.float32))
    Y = mx.nd.array(r.randint(0, 4, (8,)))
    total = 8

    # unkilled reference run
    net, tr = _make_net_trainer()
    ref = [_step(net, tr, X, Y, lossf) for _ in range(total)]

    # killed run: stop after 3 steps...
    ckdir = str(tmp_path / "resume")
    losses_a = []

    def run_a(step):
        losses_a.append(_step(*state_a, X, Y, lossf))
        return step < 2  # steps 0,1,2 then stop (simulated preemption)

    state_a = _make_net_trainer()
    mx.checkpoint.auto_resume(run_a, ckdir, net=state_a[0],
                              trainer=state_a[1], save_every=1)

    # ...new process: fresh objects, resume from the checkpoint dir
    losses_b = []

    def run_b(step):
        losses_b.append(_step(*state_b, X, Y, lossf))
        return step < total - 1

    state_b = _make_net_trainer()  # fresh (different) init — must be overwritten
    last = mx.checkpoint.auto_resume(run_b, ckdir, net=state_b[0],
                                     trainer=state_b[1], save_every=1)
    assert last == total - 1
    curve = losses_a + losses_b
    assert len(curve) == total
    np.testing.assert_allclose(curve, ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# edge cases (ISSUE 3 satellites): pruning order, corruption fallback,
# trainer-state round-trip
# ---------------------------------------------------------------------------

def test_max_to_keep_prunes_oldest_first(tmp_path):
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path / "keep"),
                                          max_to_keep=2)
    for s in range(5):
        mgr.save(s, extra={"v": mx.nd.array([float(s)])})
    # oldest steps pruned, newest retained, in order
    assert mgr.all_steps() == [3, 4]
    assert mgr.committed_steps() == [3, 4]
    assert mgr.latest_step() == 4
    # the manifest never references pruned steps
    step, extra = mgr.restore()
    assert step == 4 and float(extra["v"].asnumpy()[0]) == 4.0


def test_restore_falls_back_past_corrupted_latest(tmp_path):
    import glob
    import os as _os
    d = str(tmp_path / "corrupt")
    mgr = mx.checkpoint.CheckpointManager(d, max_to_keep=4)
    mgr.save(0, extra={"v": mx.nd.array([10.0])})
    mgr.save(1, extra={"v": mx.nd.array([11.0])})
    # trash every data file of the latest step
    for f in glob.glob(_os.path.join(d, "1", "**", "*"), recursive=True):
        if _os.path.isfile(f):
            with open(f, "wb") as fh:
                fh.write(b"garbage")
    with pytest.warns(UserWarning, match="falling back"):
        step, extra = mgr.restore()
    assert step == 0
    assert float(extra["v"].asnumpy()[0]) == 10.0
    # an EXPLICITLY requested corrupted step still errors
    with pytest.raises(Exception):
        mgr.restore(step=1)


def test_trainer_state_roundtrip_equality(tmp_path):
    import tempfile
    from mxnet_tpu import autograd, gluon  # noqa: F811

    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    r = np.random.RandomState(5)
    X = mx.nd.array(r.randn(8, 6).astype(np.float32))
    Y = mx.nd.array(r.randint(0, 4, (8,)))
    net, tr = _make_net_trainer()
    _step(net, tr, X, Y, lossf)  # adam state becomes non-trivial
    _step(net, tr, X, Y, lossf)

    def state_bytes(trainer):
        with tempfile.NamedTemporaryFile(suffix=".states") as f:
            trainer.save_states(f.name)
            with open(f.name, "rb") as fh:
                return fh.read()

    mgr = mx.checkpoint.CheckpointManager(str(tmp_path / "tr"))
    mgr.save(0, net=net, trainer=tr)
    want = state_bytes(tr)
    _step(net, tr, X, Y, lossf)  # mutate optimizer state past the save
    assert state_bytes(tr) != want
    step, _ = mgr.restore(net=net, trainer=tr)
    assert step == 0
    assert state_bytes(tr) == want  # byte-exact optimizer state round-trip
