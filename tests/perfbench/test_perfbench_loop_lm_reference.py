"""The plain looped-language-model reference against the zoo's OuroModel +
TrainStep at a tiny size on the CPU, through the benchmark's own comparison
(loss, first gradients, Adam's update); the same step in a lower precision
and the faults the cell's limits are set against fail it.  (The shares of
the vocabulary and of the depth: test_perfbench_loop_lm_shares.py.)"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, weights
from perfbench.builders import ouro_zoo
from perfbench.feed import TokenFeed
from perfbench.reference import loop_lm_train as ref
from perfbench.runners import train_step
from perfbench.tools import faults_loop_lm

import perfbench_tiny_loop_lm as tiny

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
    """No init_scale: the gate, every norm and every projection sit over
    the comparison's floor from the harness's own draw."""
    _, _, ref_obs, _ = float32_sides
    floor = 1e-3 * np.median(list(ref_obs["grad1"].values()))
    assert not [k for k, g in ref_obs["grad1"].items() if g < floor]


def test_exits_and_exit_distribution_of_a_forward_pass_agree(float32_sides):
    cell, _, _, _ = float32_sides
    import mxnet_tpu as mx
    cfg = cell["config"]
    w = weights.make_weights(ref.param_shapes(cfg), SEED, "float32")
    model = ouro_zoo.build_model(cfg)
    for name, p in model.collect_params().items():
        leaf = next(k for k in w if ouro_zoo._zoo_name(k) == name)
        p.set_data(mx.nd.NDArray._from_data(w[leaf]))
    tokens = TokenFeed(cell["traffic"], cfg["vocab_size"], SEED).next()[0][0]
    logits, p = model(mx.nd.array(tokens, dtype="int32"))
    hs = ref.exits(w, jnp.asarray(tokens), cfg)
    want = jnp.einsum("tbsu,vu->tbsv", hs, w["lm_head_w"],
                      precision="highest")
    np.testing.assert_allclose(logits.asnumpy(), want, rtol=1e-4, atol=1e-6)
    lam = jax.nn.sigmoid(jnp.einsum("tbsu,eu->tbse", hs, w["exit_w"],
                                    precision="highest")[..., 0]
                         + w["exit_b"][0])
    want_p = ref.exit_distribution(lam)
    np.testing.assert_allclose(p.asnumpy(), want_p, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(want_p).sum(0), 1.0, atol=1e-6)


def test_same_step_in_bfloat16_fails_the_same_comparison():
    cell = tiny.cell("float32")
    obs16, reference16 = _sides(tiny.cell("bfloat16"))
    ok, rows = compare.judge(compare.numbers(obs16, reference16()),
                             cell["limits"])
    assert not ok, rows


@pytest.mark.parametrize("fault", faults_loop_lm.FAULTS)
def test_planted_faults_and_the_control_fail(float32_sides, fault):
    """The faults perfbench/tools/faults_loop_lm.py plants at the cell's
    size, here at the tiny one."""
    cell, _, ref_obs, reference = float32_sides
    with faults_loop_lm.planted(ref, fault) as change:
        obs = reference(dict(cell["config"], **change),
                        **faults_loop_lm.arguments(ref, fault,
                                                   cell["traffic"]))
    for name in ("_weights_of_use", "_carried", "_post_norm",
                 "position_loss", "exit_distribution"):
        assert getattr(ref, name).__module__ == ref.__name__    # patch gone
    ok, rows = compare.judge(compare.numbers(obs, ref_obs), cell["limits"])
    assert not ok, rows


def test_blocks_of_rows_and_of_query_rows_add_up(float32_sides, monkeypatch):
    cell, _, ref_obs, _ = float32_sides
    # the whole batch at once, and attention 8 query rows at a time (the
    # fixture's: a row at a time, a row's 32 positions in one block)
    whole = dict(cell, reference_block_rows=cell["traffic"]["batch"])
    monkeypatch.setattr(ref, "_QUERY_ROWS", 8)
    cfg = dict(cell["config"], traced_again=1)
    first = TokenFeed(cell["traffic"], cfg["vocab_size"], SEED).next()
    one = train_step.observe_reference(
        ref, cfg, whole, ref.param_shapes(cfg), SEED, "float32",
        jax.devices()[0], *first)
    np.testing.assert_allclose(one["losses"], ref_obs["losses"], rtol=1e-6)
    for leaf, want in ref_obs["m"].items():
        assert one["m"][leaf] == pytest.approx(want, rel=1e-4, abs=1e-12)
    with pytest.raises(ValueError, match="do not divide into blocks"):
        train_step.observe_reference(
            ref, cfg, dict(cell, reference_block_rows=3),
            ref.param_shapes(cfg), SEED, "float32", jax.devices()[0], *first)


def test_first_gradient_comes_back_as_norms_and_weights_stay():
    cell = tiny.cell("float32")
    cfg = cell["config"]
    w0 = weights.make_weights(ref.param_shapes(cfg), SEED, "float32")
    keep = {k: np.asarray(v) for k, v in w0.items()}
    tok, lab = TokenFeed(cell["traffic"], cfg["vocab_size"], SEED).next()
    _l, first, m, _v, w = ref.train_steps(
        w0, jnp.asarray(tok), jnp.asarray(lab), cfg, cfg["run"]["optimizer"],
        block_rows=1)
    assert all(g.shape == (1,) for g in first.values())
    assert compare.leaf_norms(first)["embed"] == pytest.approx(
        float(first["embed"][0]))
    for k, v in w0.items():             # the caller's copy was not donated
        assert np.array_equal(np.asarray(v), keep[k])
    assert set(w) == set(m) == set(w0)


def test_the_expected_exit_loss_is_the_papers():
    """``position_loss`` on numbers a hand can follow: two exits, p = (1/4,
    3/4), losses (2, 4): 3.5 expected, entropy 0.5623, beta 0.1."""
    p = jnp.asarray([0.25, 0.75])
    got = float(ref.position_loss(p, jnp.asarray([2.0, 4.0]), 0.1))
    entropy = -(0.25 * np.log(0.25) + 0.75 * np.log(0.75))
    assert got == pytest.approx(3.5 - 0.1 * entropy, rel=1e-6)
    # an exit nothing reaches adds nothing (0 log 0 = 0), and its gradient
    # is finite
    g = jax.grad(lambda q: ref.position_loss(q, jnp.asarray([2.0, 4.0]),
                                             0.1))(jnp.asarray([0.0, 1.0]))
    assert np.isfinite(np.asarray(g)).all()
    lam = jnp.asarray([[0.5], [0.5], [0.5]])
    np.testing.assert_allclose(ref.exit_distribution(lam)[:, 0],
                               [0.5, 0.25, 0.25])


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(ref))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not [n for n in names if n.startswith("mxnet_tpu")]
