"""The harness driven with the hybrid Gated DeltaNet + MoE builder at a tiny
size: a sound run is ``correct``, and comes out false when the model is
built wrong underneath; the cell's files give the builder what it needs."""

import json
import os
import time

import jax
import pytest

from perfbench import run as harness
from perfbench.builders import qwen3_next_zoo

import perfbench_tiny as tiny_bench
import perfbench_tiny_gdn_moe as tiny

SEED = (1 << 31) + 9
CELL = "qwen3_next_80b_a3b.train_s8192"


def _run(cell):
    return harness.run_cell(tiny_bench.bench(), cell, SEED, 0.5, None,
                            jax.devices(), tiny_bench.PEAK,
                            start=time.perf_counter())


def test_sound_run_is_correct_and_counts_its_pairs(capfd):
    from mxnet_tpu import telemetry
    from perfbench import counters_moe
    telemetry.REGISTRY.reset()
    result = _run(tiny.cell())
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for row in result["compared"].values():
        assert row["value"] <= row["limit"]
    json.dumps(result)
    assert capfd.readouterr().err.strip().splitlines()[-1] == "correct true"
    # all 16 experts held, top 3: three pairs a token, to the digit
    assert counters_moe.pairs_per_token() == pytest.approx(3.0)
    routed = counters_moe.routed()
    assert len(routed["pairs"]) == 4 and len(routed["max"]) == 4


def _build_with(**changed):
    real = qwen3_next_zoo.build_model
    return lambda cfg: real(dict(cfg, **changed))


@pytest.mark.parametrize("changed", [
    {"partial_rotary_factor": 0.5},     # rotary on twice the dims
    {"num_experts_per_tok": 2},         # one expert fewer a token
    {"full_attention_interval": 2},     # the wrong layers run attention
], ids=["rotary_too_wide", "top2_of_3", "wrong_layer_pattern"])
def test_a_model_built_wrong_is_not_correct(monkeypatch, changed):
    if "full_attention_interval" in changed:
        # the leaves differ, so the builder itself must refuse
        monkeypatch.setattr(qwen3_next_zoo, "build_model",
                            _build_with(**changed))
        with pytest.raises(RuntimeError, match="disagree on the leaves"):
            _run(tiny.cell())
        return
    monkeypatch.setattr(qwen3_next_zoo, "build_model", _build_with(**changed))
    result = _run(tiny.cell())
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("op, broken", [
    ("contrib.gated_delta_rule", "decay_left_out"),
    ("contrib.causal_conv1d", "conv_left_out"),
])
def test_a_broken_op_is_not_correct(monkeypatch, op, broken):
    """The program's own op broken underneath the harness: the scan without
    its decay, the convolution left out."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import registry
    entry = registry.get(op)
    real = entry.fn

    def decay_left_out(q, k, v, g, beta, **kw):
        return real(q, k, v, jnp.zeros_like(g), beta, **kw)

    def conv_left_out(x, weight):
        return x

    monkeypatch.setattr(entry, "fn", {"decay_left_out": decay_left_out,
                                      "conv_left_out": conv_left_out}[broken])
    registry._costmodel_rearm()         # drop the op's cached callables
    try:
        result = _run(tiny.cell())
    finally:
        monkeypatch.undo()
        registry._costmodel_rearm()
    assert result["correct"] is False, result["compared"]


def test_the_cell_files_give_the_builder_what_it_needs():
    bench, cell = harness.load_cell(CELL)
    cfg, traffic = cell["config"], cell["traffic"]
    assert (traffic["batch"], traffic["seq"], traffic["scan_steps"]) == \
        (1, 8192, 2)
    assert traffic["mesh"] == {"shape": [1], "axes": ["dp"]}
    assert cell["reference_block_rows"] == 1 and cell["chips"] == 1
    assert cfg["experts_held"] == [0, 32] and cfg["router_width"] == 512
    assert cfg["num_experts"] == 32 and cfg["vocab_size"] == 18992
    assert cfg["num_hidden_layers"] == 4
    assert cfg["first_k_dense_replace"] == 0
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert 8 * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3_next_80b_a3b")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert set(cell["limits"]) == {"loss", "grad", "update"}
    assert set(cell["limits"]) <= set(cell["why"])
    from perfbench import scopes
    regions = [r for r, _ in scopes.load_regions(cfg["builder"])]
    assert regions.index("gdn_scan") < regions.index("gdn_proj") \
        < regions.index("encoder_dense")
    assert regions.index("attention") < regions.index("attn_proj")
    assert regions.index("moe_route") < regions.index("moe_experts") \
        < regions.index("encoder_dense")


def test_the_configuration_holds_the_catalog_entrys_numbers():
    """Every number of the published config.json under its own key; only
    depth, the experts held and the vocabulary slice differ, and no width
    is among them."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    _, cell = harness.load_cell(CELL)
    cfg = cell["config"]
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"])
    assert {k: published[k] for k in differ} == cfg["published"]
    from mxnet_tpu.gluon.model_zoo import qwen3_next
    assert qwen3_next._CHUNK == cfg["gdn_chunk_size"] == 64


def test_the_cell_reports_every_per_layer_metric_that_names_it():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    new = {m["name"] for m in mine if m["workloads"] == [CELL]}
    assert new == {"hybrid_step_mfu", "gdn_scan_ms_per_step",
                   "gdn_scan_roofline", "gdn_proj_ms_per_step",
                   "gated_attn_flash_roofline",
                   "gated_attn_proj_ms_per_step"}
    assert all(m["moves"] == "train_tokens_per_s" for m in mine
               if m["name"] in new)
    # the flash kernels' time is the accepted reader's, not a second name
    assert "mla_flash_ms_per_step" in {m["name"] for m in mine}
    for m in mine:          # each has a reader
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py")), m["name"]
    # the accepted entries stand where they stood: the cell is appended
    names = [w["name"] for w in bench["workloads"]]
    assert names[-1] == CELL and names.count(CELL) == 1
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
