"""The plain Gated DeltaNet + gated attention + MoE reference against the
zoo's Qwen3NextModel + TrainStep at a tiny size on the CPU, through the
benchmark's own comparison (loss, first gradients, Adam's update); the same
step in a lower precision and the faults the cell's limits are set against
fail it.  (The shares of an expert-parallel layer:
test_perfbench_gdn_moe_shares.py.)"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, weights
from perfbench.builders import qwen3_next_zoo
from perfbench.feed import TokenFeed
from perfbench.reference import gdn_moe_train as ref
from perfbench.runners import train_step
from perfbench.tools import faults_gdn_moe

import perfbench_tiny_gdn_moe as tiny

SEED = (1 << 31) + 77       # the driver's seeds pass 32 signed bits


def _sides(cell, seed=SEED):
    """(program observation, reference(config=None, **fault))."""
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

    def reference(config=None, **kw):
        return train_step.observe_reference(
            ref, config or cfg, cell, shapes, seed, dtype, dev, *first, **kw)

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
    assert set(obs["m"]) == set(ref_obs["m"]) == set(
        ref.param_shapes(cell["config"]))


def test_every_leaf_takes_a_gradient_from_the_first_step(float32_sides):
    """Every branch is open: no leaf of either mixer, of the router or of
    the shared expert's gate sits under the comparison's floor."""
    _, _, ref_obs, _ = float32_sides
    floor = 1e-3 * np.median(list(ref_obs["grad1"].values()))
    assert not [k for k, g in ref_obs["grad1"].items() if g < floor]


def test_logits_of_a_forward_pass_agree(float32_sides):
    cell, _, _, _ = float32_sides
    import mxnet_tpu as mx
    cfg = cell["config"]
    w = ref.at_init(weights.make_weights(ref.param_shapes(cfg), SEED,
                                         "float32"), cfg)
    model = qwen3_next_zoo.build_model(cfg)
    for name, p in model.collect_params().items():
        leaf = next(k for k in w if qwen3_next_zoo._zoo_name(k) == name)
        p.set_data(mx.nd.NDArray._from_data(w[leaf]))
    tokens = TokenFeed(cell["traffic"], cfg["vocab_size"], SEED).next()[0][0]
    got = model(mx.nd.array(tokens, dtype="int32")).asnumpy()
    want = np.asarray(ref.logits(w, jnp.asarray(tokens), cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_init_scale_moves_the_named_leaves_and_reports_their_change():
    cfg = tiny.config()
    assert cfg["init_scale"] == {"A_log": -5, "conv_w": 16}
    w = weights.make_weights(ref.param_shapes(cfg), SEED, "float32")
    start = ref.at_init(w, cfg)
    assert np.array_equal(start["layer0.A_log"], np.full(4, -5.0))
    np.testing.assert_allclose(start["layer1.conv_w"],
                               16 * np.asarray(w["layer1.conv_w"]))
    assert start["layer0.out_w"] is w["layer0.out_w"]
    # what `train_steps` returns stands where the harness's own values do
    moved = {k: v + 0.5 for k, v in start.items()}
    back = ref._as_given(moved, w, cfg)
    for k in ("layer0.A_log", "layer1.conv_w", "layer3.o_w"):
        np.testing.assert_allclose(np.asarray(back[k]) - np.asarray(w[k]),
                                   0.5, rtol=1e-5)


def test_same_step_in_bfloat16_fails_the_same_comparison():
    cell = tiny.cell("float32")
    obs16, reference16 = _sides(tiny.cell("bfloat16"))
    ok, rows = compare.judge(compare.numbers(obs16, reference16()),
                             cell["limits"])
    assert not ok, rows


@pytest.mark.parametrize("fault", [
    f for f in faults_gdn_moe.FAULTS if f != "top9_of_10"] + ["top2_of_3"])
def test_planted_faults_and_the_control_fail(float32_sides, fault):
    """The faults perfbench/tools/faults_gdn_moe.py plants at the cell's
    size, here at the tiny one (96 positions: a chunk and a half)."""
    cell, _, ref_obs, reference = float32_sides
    cell = dict(cell, traffic=dict(cell["traffic"]))
    name = "top9_of_10" if fault == "top2_of_3" else fault
    with faults_gdn_moe.planted(ref, name, chunk=32) as change:
        if fault == "top2_of_3":
            change["num_experts_per_tok"] = 2
        obs = reference(dict(cell["config"], **change),
                        **faults_gdn_moe.arguments(ref, name,
                                                   cell["traffic"]))
    assert ref.recurrence.__module__ == ref.__name__    # the patch is gone
    ok, rows = compare.judge(compare.numbers(obs, ref_obs), cell["limits"])
    assert not ok, rows


def test_half_of_the_rows_left_out_fails_too(float32_sides):
    cell, _, ref_obs, reference = float32_sides
    nums = compare.numbers(reference(rows=2), ref_obs)
    assert nums["grad"][0] > cell["limits"]["grad"]


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
    assert set(w) == set(m) == set(w0)


def test_the_reference_recurrence_is_the_token_by_token_rule():
    """``recurrence`` against a Python loop over positions in float64, at a
    length its blocks of positions do not divide evenly into 128."""
    rs = np.random.RandomState(0)
    b, s, h, dk, dv = 1, 40, 2, 8, 4
    q, k = rs.randn(b, s, h, dk), rs.randn(b, s, h, dk)
    v = rs.randn(b, s, h, dv)
    g, beta = -rs.uniform(0, 2, (b, s, h)), rs.uniform(0, 1, (b, s, h))
    state, want = np.zeros((b, h, dk, dv)), np.zeros((b, s, h, dv))
    for t in range(s):
        state = state * np.exp(g[:, t])[..., None, None]
        seen = np.einsum("bhkv,bhk->bhv", state, k[:, t])
        state += np.einsum("bhk,bhv->bhkv", k[:, t],
                           beta[:, t][..., None] * (v[:, t] - seen))
        want[:, t] = np.einsum("bhkv,bhk->bhv", state, q[:, t])
    got = ref.recurrence(*(jnp.asarray(x, jnp.float32)
                           for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(ref))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not [n for n in names if n.startswith("mxnet_tpu")]
