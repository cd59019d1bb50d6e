"""The harness driven with the looped language model's builder at a tiny
size: a sound run is ``correct``, and comes out false when the timed path
is broken underneath; the cell's files give the builder what it needs, and
BENCHMARK.json declares the cell and its metrics where the issue put
them."""

import json
import os
import time

import jax
import pytest

from perfbench import run as harness
from perfbench.builders import ouro_zoo

import perfbench_tiny as tiny_bench
import perfbench_tiny_loop_lm as tiny

SEED = (1 << 31) + 9
CELL = "ouro_2_6b.train_s4096"
NEW = {"loop_attn_proj_ms_per_step": ("ms", "lower", "device_trace",
                                      "attention projections"),
       "loop_step_mfu": ("%", "higher", "host_clock", "step program"),
       "loop_flash_roofline": ("%", "higher", "device_trace",
                               "attention kernel"),
       "loop_layer_passes_per_step": ("count", "lower", "program_counter",
                                      "step program")}
LISTED = ["compiles_in_window", "step_ms", "program_hbm_gb",
          "device_idle_pct", "attention_ms_per_step",
          "head_loss_ms_per_step", "optimizer_ms_per_step",
          "encoder_dense_ms_per_step", "scope_unattributed_pct",
          "step_trace_s", "step_lower_s", "step_load_s",
          "mla_flash_ms_per_step"]
# the benchmark's cells as PR 34 left them, in their order
ACCEPTED = ["bert_base.train_s512", "bert_base.train_s128",
            "bert_large.train_s512", "bert_base.train_s512_dp4",
            "kanana_2_30b_a3b.train_s4096",
            "qwen3_next_80b_a3b.train_s8192"]


def _run(cell):
    return harness.run_cell(tiny_bench.bench(), cell, SEED, 0.5, None,
                            jax.devices(), tiny_bench.PEAK,
                            start=time.perf_counter())


def test_sound_run_is_correct_and_counts_its_passes(capfd):
    from mxnet_tpu import telemetry
    from perfbench import counters_loop, counts_loop_lm
    telemetry.REGISTRY.reset()
    cell = tiny.cell()
    result = _run(cell)
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]
    json.dumps(result)
    assert capfd.readouterr().err.strip().splitlines()[-1] == "correct true"
    # 2 layers x 4 loop steps: 8 applications, 6 of them made again
    applications = counts_loop_lm.layer_applications(cell["config"])
    assert applications == 8
    assert counters_loop.layer_passes_per_step(applications) == 8 + 6
    mass = {dict(m.labels)["step"]: m.value
            for m in telemetry.REGISTRY.collect()
            if m.name == "mxnet_loop_exit_mass"}
    assert sorted(mass) == ["1", "2", "3", "4"]
    assert sum(mass.values()) == pytest.approx(1.0, abs=1e-5)
    assert all(0.02 < v < 0.9 for v in mass.values())   # every exit is used


def _build_with(**changed):
    real = ouro_zoo.build_model
    return lambda cfg: real(dict(cfg, **changed))


@pytest.mark.parametrize("changed", [
    {"total_ut_steps": 3},          # a loop step left out
    {"rope_theta": 10000},          # another rotary base
], ids=["three_loop_steps", "rope_base"])
def test_a_model_built_wrong_is_not_correct(monkeypatch, changed):
    monkeypatch.setattr(ouro_zoo, "build_model", _build_with(**changed))
    result = _run(tiny.cell())
    assert result["correct"] is False, result["compared"]


def test_more_layers_than_the_reference_has_is_refused(monkeypatch):
    monkeypatch.setattr(ouro_zoo, "build_model",
                        _build_with(num_hidden_layers=3))
    with pytest.raises(RuntimeError, match="disagree on the leaves"):
        _run(tiny.cell())


@pytest.mark.parametrize("broken", ["state_unchanged", "entropy_left_out",
                                    "last_exit_alone", "post_norms_left_out"])
def test_a_timed_path_broken_underneath_is_not_correct(monkeypatch, broken):
    """The program's own pieces broken underneath the harness: an optimizer
    that moves nothing, a loss without its entropy term or of the last
    exit alone, a layer without its two post-norms."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import ouro
    if broken == "state_unchanged":
        real = mx.optimizer.Adam
        monkeypatch.setattr(
            mx.optimizer, "Adam",
            lambda **kw: real(**dict(kw, learning_rate=0.0)))
    elif broken == "entropy_left_out":
        real = ouro.expected_exit_loss
        monkeypatch.setattr(ouro, "expected_exit_loss",
                            lambda out, labels, beta: real(out, labels, 0.0))
    elif broken == "last_exit_alone":
        def last_exit(out, labels, beta):
            logits = out[0][-1]
            return mx.nd.softmax_cross_entropy(
                logits.reshape((-1, logits.shape[-1])),
                labels.reshape((-1,))) / labels.size
        monkeypatch.setattr(ouro, "expected_exit_loss", last_exit)
    else:
        real = ouro.RMSNorm.hybrid_forward

        def no_post_norm(self, F, x, weight):
            return x if self.name.endswith("out_norm") \
                else real(self, F, x, weight)
        monkeypatch.setattr(ouro.RMSNorm, "hybrid_forward", no_post_norm)
    result = _run(tiny.cell())
    assert result["correct"] is False, result["compared"]


def test_the_cell_files_give_the_builder_what_it_needs():
    bench, cell = harness.load_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    assert (traffic["batch"], traffic["seq"], traffic["scan_steps"]) == \
        (1, 4096, 2)
    assert traffic["mesh"] == {"shape": [1], "axes": ["dp"]}
    assert traffic["tokens"] == traffic["labels"] == "uniform"
    assert "made again, by layer application" in traffic["why"]
    assert cell["chips"] == 1 and cell["reference_block_rows"] == 1
    assert cfg["builder"] == "ouro_zoo" and cfg["reference"] == \
        "loop_lm_train"
    assert cfg["exit_entropy_beta"] == 0.05 and "init_scale" not in cfg
    assert cfg["published"] == {"num_hidden_layers": 48, "vocab_size": 49152}
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    assert 8 * cfg["num_hidden_layers"] == \
        cfg["published"]["num_hidden_layers"]
    assert "eight chips" in cfg["deployment"]
    assert {"norm_order", "exit_gate", "loss", "gate_training", "weights",
            "labels", "optimizer", "matmul_precision"} <= set(cfg["assumed"])
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_2_6b")
    assert bench["configs"][-1] is entry
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert set(cell["limits"]) == {"loss", "grad", "update"}
    assert set(cell["limits"]) <= set(cell["why"])
    from perfbench import scopes
    regions = [r for r, _ in scopes.load_regions(cfg["builder"])]
    assert regions == ["attention", "optimizer", "head_loss", "attn_proj",
                       "encoder_dense", "other"]


def test_the_configuration_holds_the_catalog_entrys_numbers():
    """Every number of the published config.json under its own key; only
    the depth and the vocabulary slice differ, and no width is among
    them."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    _, cell = harness.load_cell(CELL)
    cfg = cell["config"]
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"])
    assert {k: published[k] for k in differ} == cfg["published"]


def test_the_cell_and_its_metrics_are_declared_after_the_accepted_ones():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    assert names.count(CELL) == 1
    # the accepted cells stand where they stood, in their old order: a
    # new entry goes after them (the driver refuses one put in between)
    assert names[:len(ACCEPTED)] == ACCEPTED
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("ouro_2_6b", "train_s4096_b1", 1)
    assert len(entry["why"]) <= 200 and "made again" in entry["why"]
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == \
        ["bert_base.train_s512_dp4"]
    declared = {m["name"]: m for m in bench["per_layer"]}
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())]
    assert sorted(mine) == sorted(LISTED + list(NEW))
    for name in LISTED:
        cells = declared[name]["workloads"]
        accepted = [c for c in ACCEPTED if c in cells]
        assert cells[:len(accepted)] == accepted, name
        assert cells.index(CELL) >= len(accepted), name
    # the four new metrics: this cell alone, at the end of the list
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    for name, (unit, better, source, layer) in NEW.items():
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (unit, better, source, layer)
        assert m["moves"] == "train_tokens_per_s"
        assert m["workloads"] == [CELL]
    for name in mine:           # each has a reader
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           name + ".py")), name
