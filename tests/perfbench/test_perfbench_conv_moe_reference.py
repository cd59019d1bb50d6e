"""The plain short-convolution + attention MoE reference against the zoo's
Lfm2MoeModel + TrainStep at a tiny size on the CPU, through the benchmark's
own comparison (loss, first gradients, Adam's update); the same step in a
lower precision and the faults the cell's limits are set against fail it;
the reference's convolution (``lax.conv_general_dilated``) against the
program's op.  (The shares of the experts and the vocabulary:
test_perfbench_conv_moe_shares.py.)"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, weights
from perfbench.feed import TokenFeed
from perfbench.reference import conv_moe_train as ref
from perfbench.runners import train_step
from perfbench.tools import faults_conv_moe

import perfbench_tiny_conv_moe as tiny

SEED = (1 << 31) + 77       # the benchmark's seeds pass 32 signed bits


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
    # every leaf but the expert bias, which takes no gradient
    trained = {k for k in ref.param_shapes(cell["config"])
               if not k.endswith(".router_b")}
    assert set(obs["m"]) == set(ref_obs["m"]) == trained


def test_same_step_in_bfloat16_fails_the_same_comparison():
    cell = tiny.cell("float32")
    obs16, reference16 = _sides(tiny.cell("bfloat16"))
    ok, rows = compare.judge(compare.numbers(obs16, reference16()),
                             cell["limits"])
    assert not ok, rows


@pytest.mark.parametrize("fault", faults_conv_moe.FAULTS)
def test_planted_faults_and_the_control_fail(float32_sides, fault):
    """The faults perfbench/tools/faults_conv_moe.py plants at the cell's
    size, here at the tiny one."""
    cell, _, ref_obs, reference = float32_sides
    cfg = cell["config"]
    with faults_conv_moe.planted(ref, fault, cfg) as change:
        obs = reference(dict(cfg, **change),
                        **faults_conv_moe.arguments(ref, fault,
                                                    cell["traffic"]))
    for name in ("_conv", "_gate", "_weights_of", "_qk_norm"):
        assert getattr(ref, name).__module__ == ref.__name__    # patch gone
    ok, rows = compare.judge(compare.numbers(obs, ref_obs), cell["limits"])
    assert not ok, rows


def test_blocks_of_rows_and_of_query_rows_add_up(float32_sides, monkeypatch):
    cell, _, ref_obs, _ = float32_sides
    # the whole batch at once, and attention 8 query rows at a time (the
    # fixture's: a row at a time, a row's 32 positions in one)
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


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_references_convolution_is_the_programs(taps):
    """``lax.conv_general_dilated`` with a group a channel against the
    program's op (a sum of shifted rows): two algorithms, one definition,
    gradients too."""
    from mxnet_tpu.ops import short_conv
    rs = np.random.RandomState(taps)
    b, s, u = 2, 11, 6
    bcx = jnp.asarray(rs.randn(b, s, 3 * u), jnp.float32)
    w = jnp.asarray(rs.randn(u, taps), jnp.float32)

    def plain(bcx, w):
        return bcx[..., u:2 * u] * ref._conv(bcx[..., :u] * bcx[..., 2 * u:],
                                             w)

    np.testing.assert_allclose(short_conv._gated_short_conv(bcx, w),
                               plain(bcx, w), rtol=1e-5, atol=1e-6)
    grads = [jax.grad(lambda a, c: (f(a, c) ** 2).sum(), argnums=(0, 1))(
        bcx, w) for f in (short_conv._gated_short_conv, plain)]
    for got, want in zip(*grads):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


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
    assert set(w) == set(m) == {k for k in w0 if not k.endswith(".router_b")}


def test_init_scale_sets_the_taps_the_file_states():
    """The convolution's taps start at 16 times the harness's width, the
    width of a default ``Conv1d`` start of fan-in 3; the harness's values
    are what the change is taken from."""
    cfg = tiny.config()
    assert cfg["init_scale"] == {"conv_w": 16}
    w = weights.make_weights(ref.param_shapes(cfg), SEED, "float32")
    start = ref.at_init(w, cfg)
    np.testing.assert_allclose(start["layer0.conv_w"], 16 * w["layer0.conv_w"])
    assert 16 * weights.INIT_STD == pytest.approx((1 / 3) ** 0.5 / 3 ** 0.5,
                                                  rel=0.05)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(ref))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not [n for n in names if n.startswith("mxnet_tpu")]
