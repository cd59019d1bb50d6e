"""The plain MLA + MoE reference against the zoo's MLAMoEModel + TrainStep
at a tiny size on the CPU, through the benchmark's own comparison; the same
step in a lower precision and the faults a training cell can have fail it.
(The shares of an expert-parallel layer: test_perfbench_mla_moe_shares.py.)"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, weights
from perfbench.builders import mla_moe_zoo
from perfbench.feed import TokenFeed
from perfbench.reference import mla_moe_train as ref
from perfbench.runners import train_step

import perfbench_tiny_mla_moe as tiny

SEED = (1 << 31) + 77       # the driver's seeds pass 32 signed bits


def _sides(cell, seed=SEED):
    """(program observation, reference(**fault))."""
    cfg, traffic = cell["config"], cell["traffic"]
    _ref, builder, shapes = train_step.sides(cfg)
    dev = jax.devices()[0]
    dtype = cfg["run"]["dtype"]
    first = TokenFeed(traffic, cfg["vocab_size"], seed).next()
    program = builder.Program(
        cfg, traffic, weights.make_weights(shapes, seed, dtype, dev),
        jax.devices())
    losses = program.losses(program.run(*first))
    obs = train_step.observe_program(program, shapes, seed, dtype, dev,
                                     losses)
    program.close()
    del program
    gc.collect()

    def reference(**kw):
        return train_step.observe_reference(
            ref, cfg, cell, shapes, seed, dtype, dev, *first, **kw)

    return obs, reference


@pytest.fixture(scope="module")
def float32_sides():
    cell = tiny.cell("float32")
    obs, reference = _sides(cell)
    return cell, obs, reference(), reference


def test_reference_agrees_with_zoo_trainstep(float32_sides):
    cell, obs, ref_obs, _ = float32_sides
    ok, rows = compare.judge(compare.numbers(obs, ref_obs), cell["limits"])
    assert ok, rows
    np.testing.assert_allclose(obs["losses"], ref_obs["losses"], rtol=1e-5)
    for leaf, want in ref_obs["m"].items():
        assert obs["m"][leaf] == pytest.approx(want, rel=5e-3, abs=1e-12)
    for leaf, want in ref_obs["delta"].items():
        assert obs["delta"][leaf] == pytest.approx(want, rel=5e-3, abs=1e-7)
    # the router's bias is no leaf of the optimizer on either side
    assert not any(k.endswith("router_b") for k in ref_obs["m"])
    assert set(obs["m"]) == set(ref_obs["m"])


def test_logits_of_a_forward_pass_agree(float32_sides):
    cell, _, _, _ = float32_sides
    import mxnet_tpu as mx
    cfg = cell["config"]
    w = weights.make_weights(ref.param_shapes(cfg), SEED, "float32")
    model = mla_moe_zoo.build_model(cfg)
    for name, p in model.collect_params().items():
        leaf = next(k for k in w if mla_moe_zoo._zoo_name(k) == name)
        p.set_data(mx.nd.NDArray._from_data(w[leaf]))
    tokens = TokenFeed(cell["traffic"], cfg["vocab_size"], SEED).next()[0][0]
    got = model(mx.nd.array(tokens, dtype="int32")).asnumpy()
    want = np.asarray(ref.logits(w, jnp.asarray(tokens), cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_same_step_in_bfloat16_fails_the_same_comparison():
    cell = tiny.cell("float32")
    obs16, reference16 = _sides(tiny.cell("bfloat16"))
    ok, rows = compare.judge(compare.numbers(obs16, reference16()),
                             cell["limits"])
    assert not ok, rows


@pytest.mark.parametrize("fault, number", [
    ({"matmul": ref.FP8_MATMUL}, "loss"),
    ({"rows": 2}, "grad"),              # half of the batch left out
    ({"skip_update": True}, "update"),  # the state returned unchanged
])
def test_control_and_faults_in_the_reference_fail(float32_sides, fault,
                                                  number):
    cell, _, ref_obs, reference = float32_sides
    nums = compare.numbers(reference(**fault), ref_obs)
    ok, rows = compare.judge(nums, cell["limits"])
    assert not ok, rows
    assert nums[number][0] > cell["limits"][number], rows


@pytest.mark.parametrize("fault", [
    "rope_not_interleaved", "attn_dq_diagonal_skipped",
    "attn_dkv_diagonal_skipped"])
def test_faults_planted_in_the_attention_fail(float32_sides, fault):
    """The faults perfbench/tools/faults_mla_moe.py plants at the cell's
    size, here at the tiny one (key blocks of 8): the attention branch is
    open from the first step, so its backward is in what ``correct`` sees."""
    from perfbench.tools import faults_mla_moe
    cell, _, ref_obs, reference = float32_sides
    floor = 1e-3 * np.median(list(ref_obs["grad1"].values()))
    for leaf in ("q_w", "kv_a_w", "kv_a_norm", "kv_b_w", "o_w"):
        assert ref_obs["grad1"]["layer1." + leaf] > floor, leaf
    with faults_mla_moe.planted(ref, fault, block=8) as change:
        cfg = dict(cell["config"], **change)
        obs = train_step.observe_reference(
            ref, cfg, cell, ref.param_shapes(cfg), SEED, "float32",
            jax.devices()[0],
            *TokenFeed(cell["traffic"], cfg["vocab_size"], SEED).next())
    assert ref._attend.__module__ == ref.__name__      # the patch is gone
    ok, rows = compare.judge(compare.numbers(obs, ref_obs), cell["limits"])
    assert not ok, rows


def test_blocks_of_rows_add_up(float32_sides):
    cell, _, ref_obs, _ = float32_sides
    whole = dict(cell, reference_block_rows=cell["traffic"]["batch"])
    cfg = cell["config"]
    first = TokenFeed(cell["traffic"], cfg["vocab_size"], SEED).next()
    one = train_step.observe_reference(
        ref, cfg, whole, ref.param_shapes(cfg), SEED, "float32",
        jax.devices()[0], *first)
    np.testing.assert_allclose(one["losses"], ref_obs["losses"], rtol=1e-6)
    for leaf, want in ref_obs["m"].items():
        assert one["m"][leaf] == pytest.approx(want, rel=1e-4, abs=1e-12)


def test_first_gradient_comes_back_as_norms_and_weights_stay():
    cell = tiny.cell("float32")
    cfg = cell["config"]
    w0 = weights.make_weights(ref.param_shapes(cfg), SEED, "float32")
    keep = {k: np.asarray(v) for k, v in w0.items()}
    tok, lab = TokenFeed(cell["traffic"], cfg["vocab_size"], SEED).next()
    _l, first, m, _v, w = ref.train_steps(
        w0, jnp.asarray(tok), jnp.asarray(lab), cfg, cfg["run"]["optimizer"],
        block_rows=2)
    assert all(g.shape == (1,) for g in first.values())
    assert compare.leaf_norms(first)["embed"] == pytest.approx(
        float(first["embed"][0]))
    for k, v in w0.items():             # the caller's copy was not donated
        assert np.array_equal(np.asarray(v), keep[k])
    assert set(w) == set(m) == {k for k in w0 if not k.endswith("router_b")}
