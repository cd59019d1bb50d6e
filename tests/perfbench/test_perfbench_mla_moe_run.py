"""The harness driven with the MLA + MoE builder at a tiny size: a sound run
is ``correct``, and comes out false when the model is broken underneath."""

import json
import time

import jax
import pytest

from perfbench import run as harness
from perfbench.builders import mla_moe_zoo

import perfbench_tiny as tiny_bench
import perfbench_tiny_mla_moe as tiny

SEED = (1 << 31) + 9


def _run(cell, trace=False):
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
    assert len(routed["pairs"]) == 2 and len(routed["max"]) == 2


def _build_with(**changed):
    real = mla_moe_zoo.build_model
    return lambda cfg: real(dict(cfg, **changed))


@pytest.mark.parametrize("changed", [
    {"routed_scaling_factor": 1.0},     # the router's scaling left out
    {"rope_interleave": False},         # RoPE not interleaved
], ids=["scaling_left_out", "rope_not_interleaved"])
def test_a_model_built_wrong_is_not_correct(monkeypatch, changed):
    monkeypatch.setattr(mla_moe_zoo, "build_model", _build_with(**changed))
    result = _run(tiny.cell())
    assert result["correct"] is False, result["compared"]


def test_one_held_expert_skipped_is_not_correct(monkeypatch):
    """The pairs of one held expert sent nowhere: its tokens keep the
    shared experts' output alone, as a capacity-bound layer's dropped
    tokens would."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import registry
    op = registry.get("contrib.moe_experts")
    real = op.fn

    def skipping(x, weights, experts, *rest, **kw):
        return real(x, weights, jnp.where(experts == 5, 1 << 20, experts),
                    *rest, **kw)

    monkeypatch.setattr(op, "fn", skipping)
    registry._costmodel_rearm()         # drop the op's cached callables
    try:
        result = _run(tiny.cell())
    finally:
        monkeypatch.undo()
        registry._costmodel_rearm()
    assert result["correct"] is False, result["compared"]


def test_the_cell_files_give_the_builder_what_it_needs():
    bench, cell = harness.load_cell("kanana_2_30b_a3b.train_s4096")
    cfg, traffic = cell["config"], cell["traffic"]
    assert (traffic["batch"], traffic["seq"], traffic["scan_steps"]) == \
        (2, 4096, 2)
    assert cell["reference_block_rows"] == 1 and cell["chips"] == 1
    assert cfg["experts_held"] == [0, 16] and cfg["router_width"] == 128
    assert cfg["n_routed_experts"] == 16 and cfg["vocab_size"] == 16032
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kanana_2_30b_a3b")
    catalog_widths = {"hidden_size": 2048, "intermediate_size": 6144,
                      "moe_intermediate_size": 768, "kv_lora_rank": 512,
                      "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                      "v_head_dim": 128, "num_attention_heads": 32,
                      "num_experts_per_tok": 6, "n_shared_experts": 2,
                      "routed_scaling_factor": 2.448, "rope_theta": 1000000}
    for key, value in catalog_widths.items():
        assert cfg[key] == value and key not in entry["reduced"], key
    from perfbench import scopes
    regions = [r for r, _ in scopes.load_regions(cfg["builder"])]
    assert regions.index("moe_route") < regions.index("moe_experts") \
        < regions.index("mla_proj") < regions.index("encoder_dense")


def test_dp4_cell_is_the_only_four_chip_cell_and_shares_limits():
    bench, cell = harness.load_cell("bert_base.train_s512_dp4")
    _, one = harness.load_cell("bert_base.train_s512")
    assert cell["limits"] == one["limits"]
    assert cell["traffic"]["mesh"] == {"shape": [4], "axes": ["dp"]}
    assert cell["traffic"]["batch"] == 4 * one["traffic"]["batch"]
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == \
        ["bert_base.train_s512_dp4"]
