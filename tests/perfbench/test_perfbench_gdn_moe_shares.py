"""The shares of an expert-parallel layer add up: for every split of the
tiny configuration's 16 experts into shares, the routed parts that the
shares give, plus the gated shared expert counted once, equal the uncut
layer, in the plain reference and in the zoo's DroplessMoE with the softmax
router; and a chip that holds experts 4-11 alone trains as the reference
says."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, weights
from perfbench.reference import gdn_moe_train as ref

import perfbench_tiny_gdn_moe as tiny
from test_perfbench_gdn_moe_reference import _sides


def test_a_share_of_the_experts_trains_as_the_reference_says():
    cell = tiny.cell("float32", held=(4, 8))
    obs, reference = _sides(cell)
    ref_obs = reference()
    ok, rows = compare.judge(compare.numbers(obs, ref_obs), cell["limits"])
    assert ok, rows
    np.testing.assert_allclose(obs["losses"], ref_obs["losses"], rtol=1e-5)
    assert ref_obs["grad1"]["layer1.experts_gate"] > 0


def _layer_leaves(cfg, seed=5):
    shapes = {k.split(".", 1)[1]: v
              for k, v in ref.param_shapes(cfg).items()
              if k.startswith("layer1.")}
    return weights.make_weights(shapes, seed, "float32")


def _splits(n):
    """Every way to cut 0..n-1 into contiguous shares of sizes from
    (1, 2, 4, 8, 16) that a power-of-two expert-parallel degree gives
    (16 shares of one expert: the deployment's ep = 16), and a few uneven
    ones."""
    even = [[(i, size) for i in range(0, n, size)] for size in (1, 2, 4, 8,
                                                                n)]
    uneven = [[(0, 3), (3, 5), (8, 8)], [(0, 1), (1, 15)],
              [(0, 7), (7, 2), (9, 7)]]
    return even + uneven


@pytest.mark.parametrize("shares", _splits(16),
                         ids=lambda s: "+".join(str(c) for _f, c in s))
def test_the_shares_add_up(shares):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.contrib.moe import DroplessMoE
    cfg = tiny.config()
    p = _layer_leaves(cfg)
    x = jnp.asarray(np.random.RandomState(0).randn(48, 64), jnp.float32)
    whole = ref.moe_ffn(x, p, cfg, ref.HIGHEST_MATMUL)

    def share_of(first, count):
        return dict(p, **{k: p[k][first:first + count]
                          for k in ("experts_gate", "experts_up",
                                    "experts_down")})

    parts = [ref.moe_ffn(x, share_of(f, c), cfg, ref.HIGHEST_MATMUL,
                         held=(f, c), shared=False) for f, c in shares]
    shared = ref.moe_ffn(x, share_of(0, 1), cfg, ref.HIGHEST_MATMUL,
                         held=(0, 1), shared=True) \
        - ref.moe_ffn(x, share_of(0, 1), cfg, ref.HIGHEST_MATMUL,
                      held=(0, 1), shared=False)
    assert float(jnp.abs(shared).max()) > 0
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-4,
                               atol=1e-7)

    def moe_of(first, count, shared_experts):
        moe = DroplessMoE(
            64, cfg["moe_intermediate_size"], cfg["router_width"],
            cfg["num_experts_per_tok"], experts_held=(first, count),
            num_shared_experts=shared_experts, score="softmax",
            shared_gate=bool(shared_experts))
        moe.initialize()
        leaves = share_of(first, count)
        names = [("router.weight", "router_w"),
                 ("experts.gate", "experts_gate"),
                 ("experts.up", "experts_up"),
                 ("experts.down", "experts_down")]
        if shared_experts:
            names += [("shared.gate.weight", "shared_gate_w"),
                      ("shared.up.weight", "shared_up_w"),
                      ("shared.down.weight", "shared_down_w"),
                      ("shared_gate.weight", "shgate_w")]
        for name, leaf in names:
            at = moe
            for part in name.split("."):
                at = getattr(at, part)
            at.set_data(mx.nd.NDArray._from_data(leaves[leaf]))
        return moe(mx.nd.NDArray._from_data(x)).asnumpy()

    # the zoo's layer share by share, the gated shared expert on the first
    total = sum(moe_of(first, count, int(n == 0))
                for n, (first, count) in enumerate(shares))
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-7)
