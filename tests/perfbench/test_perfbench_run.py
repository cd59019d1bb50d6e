"""The harness: what it refuses, what a result line holds, and that
``correct`` comes out false when the timed path is broken underneath."""

import json
import os
import subprocess
import sys
import time
import types

import jax
import pytest

from perfbench import run as harness

import perfbench_tiny as tiny

SEED = (1 << 31) + 5


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


PEAKS = harness.load_json(harness.HERE, "peaks.json")


@pytest.mark.parametrize("devices, chips, says", [
    ([_device("cpu", "cpu")], 1, "no accelerator"),
    ([_device("tpu", "TPU v9 imaginary")], 1, "does not list"),
    ([_device("cpu", "TPU v5 lite")], 1, "no accelerator"),
    ([_device("tpu", "TPU v5 lite")], 4, "needs 4 chips"),
])
def test_refuses_the_wrong_devices(devices, chips, says):
    with pytest.raises(harness.Refused, match=says):
        harness.check_devices(devices, chips, PEAKS)


def test_accepts_the_chip_it_has_a_peak_for():
    row = harness.check_devices([_device("tpu", "TPU v5 lite")] * 4, 4,
                                PEAKS)
    assert row["bf16_flops_per_s"] == 197e12


def test_command_exits_nonzero_without_a_tpu_and_prints_no_result():
    bench = tiny.bench()
    cmd = [sys.executable if c == "python3" else c for c in bench["command"]]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        cmd + ["--workload", bench["workloads"][0]["name"], "--seed", "1",
               "--seconds", "1", "--trace", "0"],
        cwd=tiny.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no accelerator" in out.stderr


def test_unknown_workload_is_refused():
    with pytest.raises(harness.Refused, match="no workload"):
        harness.load_cell("no_such.cell")


def test_every_cell_of_the_benchmark_loads_and_names_existing_files():
    bench = tiny.bench()
    for w in bench["workloads"]:
        _, cell = harness.load_cell(w["name"], bench)
        assert cell["chips"] == w["chips"]
        assert set(cell["limits"]) == {"loss", "grad", "update"}
        assert cell["traffic"]["batch"] % cell["reference_block_rows"] == 0
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(
            harness.HERE, "metrics", m["name"] + ".py")), m["name"]
        for name in m.get("workloads", ()):
            assert name in {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        cfg = harness.load_json(tiny.ROOT, c["file"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]


def _run(cell, **kw):
    return harness.run_cell(tiny.bench(), cell, SEED, 0.5, None,
                            jax.devices(), tiny.PEAK,
                            start=time.perf_counter(), **kw)


def test_sound_run_is_correct_and_its_line_has_the_contracts_keys(capfd):
    result = _run(tiny.cell())
    assert result["correct"] is True
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for m in result["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    dev = result["device"]
    assert (dev["platform"], dev["kind"]) == ("cpu", "cpu")
    assert dev["count"] == len(jax.devices())
    assert dev["memory_peak_bytes"] >= dev["program_planned_bytes"] > 0
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]
    json.dumps(result)
    err = capfd.readouterr().err.strip().splitlines()
    assert err[-1] == "correct true"
    assert any(line.startswith("compared loss ") and " limit " in line
               for line in err)


class _HalfBatch:
    """Half of the batch left out, the mean taken over the rest."""

    def __init__(self, program):
        self._program = program

    def run(self, tokens, labels):
        half = tokens.shape[1] // 2
        return self._program.run(tokens[:, :half], labels[:, :half])

    def __getattr__(self, name):
        return getattr(self._program, name)


def test_half_batch_left_out_is_not_correct(monkeypatch):
    from perfbench.builders import bert_zoo
    real = bert_zoo.Program
    monkeypatch.setattr(bert_zoo, "Program",
                        lambda *a, **k: _HalfBatch(real(*a, **k)))
    result = _run(tiny.cell())
    assert result["correct"] is False
    grad = result["compared"]["grad"]
    assert grad["value"] > 10 * grad["limit"]


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from mxnet_tpu import optimizer_fusion
    monkeypatch.setattr(optimizer_fusion, "traced_update",
                        lambda *a, **k: None)
    result = _run(tiny.cell())
    assert result["correct"] is False
    assert result["compared"]["update"]["value"] == pytest.approx(1.0)
    assert result["compared"]["grad"]["value"] == pytest.approx(1.0)


def test_altered_loss_is_not_correct(monkeypatch):
    """An answer altered where it is produced: the losses the step hands
    back are not those of the reference's batches."""
    from perfbench.builders import bert_zoo
    monkeypatch.setattr(bert_zoo.Program, "losses",
                        staticmethod(lambda h: 1.001 * h.asnumpy()))
    result = _run(tiny.cell())
    assert result["correct"] is False
    assert result["compared"]["loss"]["value"] > \
        result["compared"]["loss"]["limit"]
