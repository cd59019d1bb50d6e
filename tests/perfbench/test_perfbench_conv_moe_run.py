"""The harness driven with the short-convolution + attention MoE builder at
a tiny size: a sound run is ``correct``, collects the routed layers'
counters and the convolution's passes through the remake, and comes out
false when the timed path is built or broken wrong underneath; the cell's
files give the builder what it needs, the configuration holds the catalog
entry's numbers, and BENCHMARK.json declares the cell and its metrics after
the accepted ones."""

import json
import os
import time

import jax
import pytest

from perfbench import run as harness
from perfbench.builders import lfm2_zoo

import perfbench_tiny as tiny_bench
import perfbench_tiny_conv_moe as tiny

SEED = (1 << 31) + 9
CELL = "lfm2_24b_a2b.train_s8192"
NEW = {"short_conv_ms_per_step": ("ms", "lower", "device_trace",
                                  "short convolution"),
       "gated_conv_roofline": ("%", "higher", "device_trace",
                               "gated convolution"),
       "conv_moe_step_mfu": ("%", "higher", "host_clock", "step program"),
       "short_conv_passes_per_step": ("count", "lower", "program_counter",
                                      "step program")}
LISTED = ["compiles_in_window", "step_ms", "program_hbm_gb",
          "device_idle_pct", "attention_ms_per_step",
          "head_loss_ms_per_step", "optimizer_ms_per_step",
          "encoder_dense_ms_per_step", "scope_unattributed_pct",
          "step_trace_s", "step_lower_s", "step_load_s",
          "mla_flash_ms_per_step", "host_ms_per_dispatch", "host_busy_pct",
          "unfed_dispatches_in_window", "dispatch_tail_ratio",
          "step_resolve_s", "hbm_unread_gb", "moe_route_ms_per_step",
          "moe_experts_ms_per_step", "moe_grouped_roofline",
          "moe_pairs_per_token", "moe_load_max_over_mean"]
# the benchmark's cells before this one, in their order
ACCEPTED = ["bert_base.train_s512", "bert_base.train_s128",
            "bert_large.train_s512", "bert_base.train_s512_dp4",
            "kanana_2_30b_a3b.train_s4096", "qwen3_next_80b_a3b.train_s8192",
            "ouro_2_6b.train_s4096", "granite_4_0_h_micro.train_s4096"]


def _run(cell):
    return harness.run_cell(tiny_bench.bench(), cell, SEED, 0.5, None,
                            jax.devices(), tiny_bench.PEAK,
                            start=time.perf_counter())


def _counted(name):
    from mxnet_tpu import telemetry
    return {(dict(m.labels).get("layer"), dict(m.labels).get("kind")):
            m.value for m in telemetry.REGISTRY.collect()
            if m.name == name and m.value}


def test_sound_run_is_correct_and_counts_through_the_remake(capfd):
    """Every layer runs under ``remat_call``: the routed layers' counters
    leave the checkpointed forward (once a traced step, not again for the
    pass the backward makes), and the convolution counts two passes a
    layer and trace.  Half the experts held: 2 pairs a token of 4."""
    from mxnet_tpu import telemetry
    from perfbench import counters_moe
    telemetry.REGISTRY.reset()
    cell = tiny.cell(held=(0, 8))
    result = _run(cell)
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]
    json.dumps(result)
    assert capfd.readouterr().err.strip().splitlines()[-1] == "correct true"
    routed = counters_moe.routed()
    assert sorted(routed["pairs"]) == ["lfm2/layers/layer1/moe",
                                       "lfm2/layers/layer2/moe"]
    # every dispatch of the process is counted, set-up's two included
    tokens_a_dispatch = 2 * 2 * 32
    dispatches = routed["tokens"] / (2 * tokens_a_dispatch)
    assert dispatches == int(dispatches) >= 4
    assert 1.0 <= counters_moe.pairs_per_token() <= 3.0
    passes = _counted("mxnet_short_conv_passes_total")
    assert passes == {("lfm2/layers/layer0", "made_again"): 2,
                      ("lfm2/layers/layer2", "made_again"): 2}
    assert harness.read_metric("short_conv_passes_per_step",
                               {"cell": cell}) == 4.0   # 2 layers here


def _build_with(**changed):
    real = lfm2_zoo.build_model
    return lambda cfg: real(dict(cfg, **changed))


@pytest.mark.parametrize("changed", [
    {"rope_parameters": {"rope_theta": 10000, "rope_type": "default"}},
    {"router_norm_eps": 1.0},               # the weights' sum given 1
    {"norm_eps": 0.1},                      # the norms' eps not published
], ids=["rope_theta", "router_norm_eps", "norm_eps"])
def test_a_model_built_wrong_is_not_correct(monkeypatch, changed):
    monkeypatch.setattr(lfm2_zoo, "build_model", _build_with(**changed))
    result = _run(tiny.cell())
    assert result["correct"] is False, result["compared"]


def test_more_layers_than_the_reference_has_is_refused(monkeypatch):
    monkeypatch.setattr(lfm2_zoo, "build_model",
                        _build_with(num_hidden_layers=4,
                                    layers_held=[0, 2, 3, 4]))
    with pytest.raises(RuntimeError, match="disagree on the leaves"):
        _run(tiny.cell())


@pytest.mark.parametrize("broken", ["state_unchanged", "gate_left_out",
                                    "conv_one_tap", "bias_in_weights"])
def test_a_timed_path_broken_underneath_is_not_correct(monkeypatch, broken):
    """The program's own pieces broken underneath the harness: an optimizer
    that moves nothing, the op without its gate ``C *``, the op's
    convolution without its shift, the router's weights taken with the
    expert bias."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import lfm2_moe
    if broken == "state_unchanged":
        real = mx.optimizer.Adam
        monkeypatch.setattr(
            mx.optimizer, "Adam",
            lambda **kw: real(**dict(kw, learning_rate=0.0)))
    elif broken in ("gate_left_out", "conv_one_tap"):
        def conv(self, F, bcx, weight):
            u = weight.shape[0]
            b, c, x = bcx[:, :, :u], bcx[:, :, u:2 * u], bcx[:, :, 2 * u:]
            if broken == "gate_left_out":
                return F.contrib.causal_conv1d(b * x, weight)
            return c * (b * x) * weight[:, -1]
        monkeypatch.setattr(lfm2_moe._GatedConv, "hybrid_forward", conv)
    else:
        from mxnet_tpu.gluon.contrib import moe

        def router(self, F, x, weight, bias=None):
            w, chosen = F.contrib.moe_router(x, weight, bias, **self._attrs)
            return w + F.take(bias, chosen).astype("float32"), chosen
        monkeypatch.setattr(moe._Router, "hybrid_forward", router)
    result = _run(tiny.cell())
    assert result["correct"] is False, result["compared"]


def test_the_cell_files_give_the_builder_what_it_needs():
    bench, cell = harness.load_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    assert (traffic["batch"], traffic["seq"], traffic["scan_steps"]) == \
        (4, 8192, 2)
    assert traffic["mesh"] == {"shape": [1], "axes": ["dp"]}
    assert traffic["tokens"] == traffic["labels"] == "uniform"
    assert cell["chips"] == 1 and cell["reference_block_rows"] == 1
    assert cfg["builder"] == "lfm2_zoo" and cfg["reference"] == \
        "conv_moe_train"
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_dense_layers": 2, "num_experts": 64,
                                "vocab_size": 65536}
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    assert cfg["router_width"] == cfg["published"]["num_experts"]
    assert cfg["experts_held"] == [0, cfg["num_experts"]] == [0, 8]
    assert cfg["first_k_dense_replace"] == cfg["num_dense_layers"] == 1
    # one leading dense layer and one whole period: three conv, one
    # attention, in the published order of model layers 2-5
    held = cfg["layers_held"]
    assert held == [0, 2, 3, 4, 5] and len(held) == cfg["num_hidden_layers"]
    kinds = [cfg["layer_types"][i] for i in held]
    assert kinds == ["conv", "full_attention", "conv", "conv", "conv"]
    assert "eight chips" in cfg["deployment"]
    assert cfg["init_scale"] == {"conv_w": 16}
    assert {"layers_held", "first_k_dense_replace", "tied_head",
            "init_scale", "weights", "expert_bias", "router", "short_conv",
            "attention", "labels", "optimizer", "matmul_precision",
            "recomputation"} <= set(cfg["assumed"])
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert set(cell["limits"]) == {"loss", "grad", "update"}
    assert set(cell["limits"]) | {"not_seen"} == set(cell["why"])
    from perfbench import scopes
    regions = [r for r, _ in scopes.load_regions(cfg["builder"])]
    assert regions == ["attention", "optimizer", "head_loss", "gated_conv",
                       "short_conv", "moe_route", "moe_experts", "attn_proj",
                       "encoder_dense", "other"]


def test_the_configuration_holds_the_catalog_entrys_numbers():
    """Every key of the published config.json under its own key; only the
    depth, the leading dense layers, the experts held and the vocabulary
    slice differ, and no width is among them."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776,
        "layer_types": ["conv", "conv", "full_attention"]
        + ["conv", "conv", "conv", "full_attention"] * 9 + ["conv"],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    _, cell = harness.load_cell(CELL)
    cfg = cell["config"]
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"])
    assert {k: published[k] for k in differ} == cfg["published"]
    assert cfg["layer_types"].count("full_attention") == 10


def test_the_cell_and_its_metrics_are_declared_after_the_accepted_ones():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    assert names.count(CELL) == 1
    # the accepted cells stand where they stood, in their old order: a new
    # entry goes after them (the benchmark's check refuses one put in
    # between)
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert names.index(CELL) >= len(ACCEPTED)
    assert [c["name"] for c in bench["configs"]].index("lfm2_24b_a2b") >= 6
    entry = bench["workloads"][names.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == \
        ("lfm2_24b_a2b", "train_s8192_b4", 1)
    assert len(entry["why"]) <= 200 and "gated short conv" in entry["why"]
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
            "ssm_step_mfu")
    for name in mine:           # each has a reader
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           name + ".py")), name
