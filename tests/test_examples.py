"""Example-lane smoke tests: every script in examples/ must run end-to-end
with tiny settings and actually learn (reference: tests/python/train/ +
the CI example runners in ci/docker/runtime_functions.sh)."""

import importlib.util
import os
import sys

import numpy as np
import pytest

_EX = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


def _load(rel):
    path = os.path.join(_EX, rel)
    spec = importlib.util.spec_from_file_location(
        rel.replace("/", "_").replace(".py", ""), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_mnist_learns():
    mod = _load("image_classification/train_mnist.py")
    hist = mod.run(ctx_name="cpu", epochs=2, batch_size=32, lr=0.02,
                   log=False, synthetic_samples=256)
    assert hist[-1]["acc"] > hist[0]["acc"] or hist[-1]["acc"] > 0.5
    assert hist[-1]["loss"] < hist[0]["loss"]


@pytest.mark.slow  # >10s on the tier-1 budget clock (r7 audit); runs in the CI slow lane
def test_train_resnet_reports_throughput():
    mod = _load("image_classification/train_resnet.py")
    rec = mod.run(model="resnet18_v1", batch_size=4, image_size=32,
                  steps=2, warmup=1, classes=10, log=False)
    assert rec["images_per_sec"] > 0


@pytest.mark.slow  # >10s on the tier-1 budget clock (r7 audit); runs in the CI slow lane
def test_bert_pretrain_loss_drops():
    mod = _load("bert/pretrain.py")
    rec = mod.run(num_layers=2, units=64, heads=4, batch=8, seq_len=32,
                  vocab=200, steps=6, warmup=1, lr=5e-3, log=False)
    assert rec["last_loss"] < rec["first_loss"]


@pytest.mark.slow  # compile-heavy; excluded from the tier-1 timing budget
def test_lstm_lm_perplexity_drops():
    mod = _load("rnn/lstm_lm.py")
    hist = mod.run(vocab=32, emb=16, hidden=32, layers=1, bptt=8,
                   batch_size=4, epochs=2, corpus_len=1024, log=False)
    assert hist[-1]["perplexity"] < hist[0]["perplexity"]


@pytest.mark.slow  # >10s on the tier-1 budget clock (r7 audit); runs in the CI slow lane
def test_ssd_trains_and_detects():
    mod = _load("ssd/train_ssd.py")
    rec = mod.run(batch=16, steps=40, log=False)
    assert rec["last_loss"] < rec["first_loss"]
    assert rec["mean_top_iou"] > 0.05     # detections overlap ground truth


def test_pipeline_example_dp_pp():
    mod = _load("pipeline/train_pipeline.py")
    rec = mod.run(depth=4, pp=4, dp=2, steps=15, log=False)
    assert rec["last_loss"] < rec["first_loss"]
    assert rec["bubble_fraction"] < 0.5


def test_moe_example_expert_parallel():
    mod = _load("moe/train_moe.py")
    rec = mod.run(steps=12, dp=2, ep=4, log=False)
    assert rec["last_loss"] < rec["first_loss"]


@pytest.mark.slow  # >10s on the tier-1 budget clock (r7 audit); runs in the CI slow lane
def test_quantize_net_example():
    mod = _load("quantization/quantize_net.py")
    rec = mod.run(model="resnet18_v1", batch=4, image_size=32, classes=10,
                  calib_mode="naive", calib_batches=2, log=False)
    assert rec["top1_agreement"] >= 0.75
    assert rec["max_rel_err"] < 0.2


def test_matrix_factorization_model_parallel():
    mod = _load("model_parallel/matrix_factorization.py")
    rec = mod.run(num_users=64, num_items=64, factor=16, batch=64,
                  steps=10, mp=2, lr=0.1, log=False)
    assert rec["last_loss"] < rec["first_loss"]
    # single-device run matches the mp=2 run step-for-step
    rec1 = mod.run(num_users=64, num_items=64, factor=16, batch=64,
                   steps=10, mp=1, lr=0.1, log=False)
    np.testing.assert_allclose(rec["last_loss"], rec1["last_loss"],
                               rtol=1e-4)


@pytest.mark.slow  # >10s on the tier-1 budget clock (r7 audit); runs in the CI slow lane
def test_dist_train_example_two_workers():
    """The examples/distributed lane end-to-end: 2 localhost workers via
    tools/launch.py, dist_tpu_sync Trainer, loss drops, exact grad-sum
    (reference tools/launch.py + dist_sync flow)."""
    import subprocess
    root = os.path.dirname(_EX)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "launch.py"),
         "-n", "2", "--cpu-devices", "1",
         sys.executable, os.path.join(_EX, "distributed", "dist_train.py"),
         "--steps", "15"],
        capture_output=True, text=True, timeout=420, cwd=root)
    assert r.returncode == 0, r.stdout[-800:] + r.stderr[-800:]
    assert "OK" in r.stdout


def test_launch_ssh_command_construction():
    """ssh launcher builds per-rank commands with coordinator/rank env
    inlined (dmlc_tracker/ssh.py role) and round-robins hosts."""
    import importlib.util
    root = os.path.dirname(_EX)
    spec = importlib.util.spec_from_file_location(
        "launch", os.path.join(root, "tools", "launch.py"))
    launch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launch)
    cmds = launch.build_ssh_commands(
        3, ["hostA", "hostB"], ["python", "train.py", "--lr", "0.1"],
        port=12345)
    assert len(cmds) == 3
    assert cmds[0][-2] == "hostA" and cmds[1][-2] == "hostB" \
        and cmds[2][-2] == "hostA"          # round-robin
    for rank, c in enumerate(cmds):
        assert c[0] == "ssh"
        remote = c[-1]
        assert f"MXNET_DIST_RANK={rank}" in remote
        assert "MXNET_DIST_COORDINATOR=hostA:12345" in remote
        assert "MXNET_DIST_NUM_WORKERS=3" in remote
        assert remote.endswith("python train.py --lr 0.1")
    # dry-run path prints and reports success without spawning
    codes = launch.launch_ssh(2, ["h1"], ["echo", "hi"], dry_run=True)
    assert codes == [0, 0]


@pytest.mark.slow  # >10s on the tier-1 budget clock (r7 audit); runs in the CI slow lane
def test_transformer_mt_learns():
    mod = _load("transformer_mt/train_mt.py")
    rec = mod.run(vocab=24, layers=1, units=32, hidden=64, heads=2,
                  batch=8, steps=30, lr=3e-3, warmup=10, log=False,
                  decode_samples=2)
    assert rec["last_loss"] < rec["first_loss"]


@pytest.mark.slow  # compile-heavy; excluded from the tier-1 timing budget
def test_yolo3_trains_and_detects():
    mod = _load("yolo/train_yolo.py")
    rec = mod.run(batch=8, steps=25, log=False)
    assert rec["last_loss"] < rec["first_loss"]
