"""The harness driven with the hybrid Mamba-2 + attention builder at a tiny
size: a sound run is ``correct``, and comes out false when the timed path
is built or broken wrong underneath; the cell's files give the builder what
it needs, the configuration holds the catalog entry's numbers, and
BENCHMARK.json declares the cell and its metrics after the accepted ones."""

import json
import os
import time

import jax
import pytest

from perfbench import run as harness
from perfbench.builders import granite_zoo

import perfbench_tiny as tiny_bench
import perfbench_tiny_ssm_hybrid as tiny

SEED = (1 << 31) + 9
CELL = "granite_4_0_h_micro.train_s4096"
NEW = {"ssd_scan_ms_per_step": ("ms", "lower", "device_trace",
                                "state-space scan"),
       "ssd_scan_roofline": ("%", "higher", "device_trace",
                             "state-space scan"),
       "ssm_proj_ms_per_step": ("ms", "lower", "device_trace",
                                "state-space projections"),
       "ssm_step_mfu": ("%", "higher", "host_clock", "step program")}
LISTED = ["compiles_in_window", "step_ms", "program_hbm_gb",
          "device_idle_pct", "attention_ms_per_step",
          "head_loss_ms_per_step", "optimizer_ms_per_step",
          "encoder_dense_ms_per_step", "scope_unattributed_pct",
          "step_trace_s", "step_lower_s", "step_load_s",
          "mla_flash_ms_per_step", "host_ms_per_dispatch", "host_busy_pct",
          "unfed_dispatches_in_window", "dispatch_tail_ratio",
          "step_resolve_s", "hbm_unread_gb"]
# the benchmark's cells before this one, in their order
ACCEPTED = ["bert_base.train_s512", "bert_base.train_s128",
            "bert_large.train_s512", "bert_base.train_s512_dp4",
            "kanana_2_30b_a3b.train_s4096", "qwen3_next_80b_a3b.train_s8192",
            "ouro_2_6b.train_s4096"]


def _run(cell):
    return harness.run_cell(tiny_bench.bench(), cell, SEED, 0.5, None,
                            jax.devices(), tiny_bench.PEAK,
                            start=time.perf_counter())


def test_sound_run_is_correct_and_counts_its_chunks(capfd):
    from mxnet_tpu import telemetry
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
    # 2 Mamba-2 layers x 2 rows x 4 heads x 4 chunks of 8, a trace
    chunks = [m.value for m in telemetry.REGISTRY.collect()
              if m.name == "mxnet_ssd_chunks_total" and m.value]
    assert len(chunks) == 2 and len(set(chunks)) == 1
    assert chunks[0] % (2 * 4 * 4) == 0


def _build_with(**changed):
    real = granite_zoo.build_model
    return lambda cfg: real(dict(cfg, **changed))


@pytest.mark.parametrize("changed", [
    {"residual_multiplier": 1.0},           # the branches at full weight
    {"attention_multiplier": 0.125},        # 1/sqrt(d), not the published
    {"embedding_multiplier": 1},            # the embedding not scaled
    {"logits_scaling": 1},                  # the logits not scaled
], ids=["residual_multiplier", "attention_multiplier",
        "embedding_multiplier", "logits_scaling"])
def test_a_model_built_wrong_is_not_correct(monkeypatch, changed):
    monkeypatch.setattr(granite_zoo, "build_model", _build_with(**changed))
    result = _run(tiny.cell())
    assert result["correct"] is False, result["compared"]


def test_more_layers_than_the_reference_has_is_refused(monkeypatch):
    monkeypatch.setattr(granite_zoo, "build_model",
                        _build_with(num_hidden_layers=4,
                                    layer_types=["mamba", "attention"] * 2))
    with pytest.raises(RuntimeError, match="disagree on the leaves"):
        _run(tiny.cell())


@pytest.mark.parametrize("broken", ["state_unchanged", "norm_then_gate",
                                    "state_not_carried", "skip_left_out"])
def test_a_timed_path_broken_underneath_is_not_correct(monkeypatch, broken):
    """The program's own pieces broken underneath the harness: an optimizer
    that moves nothing, the mixer's gated norm in the other order, a scan
    whose state starts at zero in every chunk, a scan without ``D x``."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import granite_hybrid
    if broken == "state_unchanged":
        real = mx.optimizer.Adam
        monkeypatch.setattr(
            mx.optimizer, "Adam",
            lambda **kw: real(**dict(kw, learning_rate=0.0)))
    elif broken == "norm_then_gate":
        def by_head(self, F, y, z, weight):
            B, L, inner = y.shape
            yh = y.astype("float32").reshape((B, L, 4, inner // 4))
            yh = yh * F.rsqrt((yh * yh).mean(axis=-1, keepdims=True)
                              + self._eps)
            return (yh.reshape((B, L, inner)) * weight.astype("float32")
                    * F.silu(z.astype("float32"))).astype(y.dtype)
        monkeypatch.setattr(granite_hybrid._GatedNorm, "hybrid_forward",
                            by_head)
    else:
        real = granite_hybrid._Scan.hybrid_forward
        if broken == "state_not_carried":
            def scan(self, F, xbc, dt, A_log, D, dt_bias):
                q = self._chunk         # every chunk a row of its own
                return F.concat(*[
                    real(self, F, xbc[:, i:i + q], dt[:, i:i + q], A_log, D,
                         dt_bias) for i in range(0, xbc.shape[1], q)], dim=1)
        else:
            def scan(self, F, xbc, dt, A_log, D, dt_bias):
                return real(self, F, xbc, dt, A_log, 0.0 * D, dt_bias)
        monkeypatch.setattr(granite_hybrid._Scan, "hybrid_forward", scan)
    result = _run(tiny.cell())
    assert result["correct"] is False, result["compared"]


def test_the_cell_files_give_the_builder_what_it_needs():
    bench, cell = harness.load_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    assert (traffic["batch"], traffic["seq"], traffic["scan_steps"]) == \
        (1, 4096, 2)
    assert traffic["mesh"] == {"shape": [1], "axes": ["dp"]}
    assert traffic["tokens"] == traffic["labels"] == "uniform"
    assert cell["chips"] == 1 and cell["reference_block_rows"] == 1
    assert cfg["builder"] == "granite_zoo" and cfg["reference"] == \
        "ssm_hybrid_train"
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "vocab_size": 100352}
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    # one whole period of the layer pattern: nine Mamba-2, one attention
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    assert kinds.count("attention") == 1 and len(kinds) == 10
    assert "eight chips" in cfg["deployment"]
    assert set(cfg["init_scale"]) == {"A_log", "dt_bias", "conv_w"}
    assert {"init_scale", "weights", "labels", "layer_types", "ssd_chunk",
            "mamba_inner", "attention", "experts", "optimizer",
            "matmul_precision", "recomputation"} <= set(cfg["assumed"])
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite_4_0_h_micro")
    assert bench["configs"][-1] is entry
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert set(cell["limits"]) == {"loss", "grad", "update"}
    assert set(cell["limits"]) | {"not_seen"} == set(cell["why"])
    from perfbench import scopes
    regions = [r for r, _ in scopes.load_regions(cfg["builder"])]
    assert regions == ["attention", "optimizer", "head_loss", "ssd_scan",
                       "ssm_proj", "attn_proj", "encoder_dense", "other"]


def test_the_configuration_holds_the_catalog_entrys_numbers():
    """Every key of the published config.json under its own key; only the
    depth and the vocabulary slice differ, and no width is among them."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    _, cell = harness.load_cell(CELL)
    cfg = cell["config"]
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"])
    assert {k: published[k] for k in differ} == cfg["published"]


def test_the_cell_and_its_metrics_are_declared_after_the_accepted_ones():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    assert names.count(CELL) == 1
    # the accepted cells stand where they stood, in their old order: a new
    # entry goes after them (the benchmark's check refuses one put in
    # between)
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names.index(CELL) >= len(ACCEPTED)
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("granite_4_0_h_micro", "train_s4096_b1", 1)
    assert len(entry["why"]) <= 200 and "SSD scan" in entry["why"]
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
    for name, (unit, better, source, layer) in NEW.items():
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (unit, better, source, layer)
        assert m["moves"] == "train_tokens_per_s"
        assert m["workloads"] == [CELL]
        # after every metric the accepted benchmark had
        assert list(declared).index(name) > list(declared).index(
            "hbm_unread_gb")
    for name in mine:           # each has a reader
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           name + ".py")), name
