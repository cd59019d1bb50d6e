"""The plain reference against the zoo's BERT + TrainStep at a tiny size on
the CPU, through the benchmark's own comparison; the same step in a lower
precision, and the faults a training cell can have, fail it."""

import gc

import jax
import numpy as np
import pytest

from perfbench import compare, weights
from perfbench.runners import train_step
from perfbench.feed import TokenFeed

import perfbench_tiny as tiny

SEED = (1 << 31) + 77       # the driver's seeds pass 32 signed bits


def _sides(cell, seed=SEED):
    """(program observation, reference, the pieces a control needs)."""
    cfg, traffic = cell["config"], cell["traffic"]
    ref, builder, shapes = train_step.sides(cfg)
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

    return obs, reference, ref


@pytest.fixture(scope="module")
def float32_sides():
    cell = tiny.cell("float32")
    obs, reference, ref = _sides(cell)
    return cell, obs, reference(), reference, ref


def test_reference_agrees_with_zoo_trainstep(float32_sides):
    cell, obs, ref_obs, _, _ = float32_sides
    ok, rows = compare.judge(compare.numbers(obs, ref_obs), cell["limits"])
    assert ok, rows
    # losses step by step, and every leaf's change, not only the worst
    np.testing.assert_allclose(obs["losses"], ref_obs["losses"], rtol=1e-5)
    for leaf, want in ref_obs["delta"].items():
        assert obs["delta"][leaf] == pytest.approx(want, rel=5e-3, abs=1e-7)
    # the pooler is not reached by the loss: no gradient, no change
    assert ref_obs["grad1"]["pooler_w"] == 0.0
    assert obs["delta"]["pooler_w"] == 0.0


def test_same_step_in_bfloat16_fails_the_same_comparison(float32_sides):
    cell, _, _, _, _ = float32_sides
    # weights are made in the type they are served in, so the bfloat16
    # step has a reference of its own (float32 from the bfloat16 values)
    obs16, reference16, _ = _sides(tiny.cell("bfloat16"))
    ok, rows = compare.judge(compare.numbers(obs16, reference16()),
                             cell["limits"])
    assert not ok, rows


@pytest.mark.parametrize("fault, number", [
    ({"matmul": "FP8_MATMUL"}, "loss"),
    ({"rows": 4}, "grad"),              # half of the batch left out
    ({"skip_update": True}, "update"),  # the state returned unchanged
])
def test_control_and_faults_in_the_reference_fail(float32_sides, fault,
                                                  number):
    cell, _, ref_obs, reference, ref = float32_sides
    if "matmul" in fault:
        fault = {"matmul": getattr(ref, fault["matmul"])}
    nums = compare.numbers(reference(**fault), ref_obs)
    ok, rows = compare.judge(nums, cell["limits"])
    assert not ok, rows
    assert nums[number][0] > cell["limits"][number], rows


def test_state_unchanged_reads_one(float32_sides):
    _, _, ref_obs, reference, _ = float32_sides
    nums = compare.numbers(reference(skip_update=True), ref_obs)
    assert nums["grad"][0] == pytest.approx(1.0)
    assert nums["update"][0] == pytest.approx(1.0)


def test_blocks_of_rows_add_up(float32_sides):
    cell, _, ref_obs, reference, _ = float32_sides
    whole = dict(cell, reference_block_rows=cell["traffic"]["batch"])
    cfg = cell["config"]
    ref, _, shapes = train_step.sides(cfg)
    first = TokenFeed(cell["traffic"], cfg["vocab_size"], SEED).next()
    one = train_step.observe_reference(
        ref, cfg, whole, shapes, SEED, cfg["run"]["dtype"],
        jax.devices()[0], *first)
    np.testing.assert_allclose(one["losses"], ref_obs["losses"], rtol=1e-6)
    for leaf, want in ref_obs["m"].items():
        assert one["m"][leaf] == pytest.approx(want, rel=1e-4, abs=1e-12)


def test_weights_are_a_function_of_the_seed_alone():
    shapes = {"a": ((4, 8), "normal"), "b": ((8,), "zeros"),
              "c": ((8,), "ones")}
    one = weights.make_weights(shapes, SEED, "bfloat16")
    two = weights.make_weights(shapes, SEED, "bfloat16")
    other = weights.make_weights(shapes, SEED + 1, "bfloat16")
    assert str(one["a"].dtype) == "bfloat16"
    assert np.array_equal(np.asarray(one["a"], np.float32),
                          np.asarray(two["a"], np.float32))
    assert not np.array_equal(np.asarray(one["a"], np.float32),
                              np.asarray(other["a"], np.float32))
    assert float(one["b"].sum()) == 0 and float(one["c"].sum()) == 8


def test_feed_draws_the_same_batches_for_a_seed():
    traffic = tiny.cell()["traffic"]
    a, b = TokenFeed(traffic, 512, SEED), TokenFeed(traffic, 512, SEED)
    t1, l1 = a.next()
    t2, l2 = b.next()
    assert t1.shape == (3, 8, 64) and t1.dtype == np.int32
    assert np.array_equal(t1, t2) and np.array_equal(l1, l2)
    assert not np.array_equal(t1, a.next()[0])
    assert 0 <= t1.min() and t1.max() < 512
    # rows all differ
    assert len({r.tobytes() for r in t1.reshape(-1, 64)}) == 24
