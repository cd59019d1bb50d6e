"""What can be known about the chip without the chip.

1. Compiles for a DESCRIBED TPU v5e (the chip's compiler is installed here
   and needs no device): the flash kernel at the shapes of the real lanes,
   one whole TrainStep program at BERT-base width, the same step on a
   4-device mesh, and the serving executables at the chip_smoke.py width.
   They raise what the chip's compiler would raise — interpret-mode tests
   cannot see an illegal block, a kernel GSPMD cannot partition or a program
   that does not fit.  A compile that passes is not a chip run.  The four
   steps of whole benchmark cells at their published widths (4.5-5.0 GB of
   host memory each, 70-160 s) are ``slow``: out of the tier-1 lane, run
   with ``-m slow``; the cells' own runs on the chip read what they check
   (``program_hbm_gb`` and the kernels' per-layer counts).
2. chip_smoke.py's phase functions at a tiny size on the CPU, and the
   refusal: chip_smoke.py never falls back to the CPU.

The topology is described inside a module-scoped fixture (never at import,
in a skipif or in parametrize): only the worker that runs this file loads
the TPU library, and it compiles in its own process.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E_HBM_BYTES = 16e9


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load("chip_smoke")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-topology executable is written to the persistent cache
    # but cannot be read back without a chip: keep the cache off here
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


# -- 1a. the flash kernel, forward and backward ------------------------------

FLASH_SHAPES = [
    # (B, H, L, D), dtype, causal, segment ids, forced block
    pytest.param((32, 12, 512, 64), "bfloat16", False, True, None,
                 id="bert_seq512_b32_segids"),
    pytest.param((4, 16, 2048, 128), "bfloat16", True, False, None,
                 id="llama_seq2048_d128_causal"),
    pytest.param((8, 16, 2048, 64), "bfloat16", True, False, None,
                 id="llama_seq2048_d64_causal"),
    pytest.param((2, 16, 4096, 128), "bfloat16", True, False, None,
                 id="seq4096_streaming"),
    pytest.param((2, 2, 128, 64), "float32", True, True, 64,
                 id="block64_segids_f32"),
    pytest.param((2, 2, 128, 64), "bfloat16", False, True, 64,
                 id="block64_segids_bf16"),
    pytest.param((2, 2, 128, 64), "bfloat16", True, True, 8,
                 id="block8_segids_bf16"),
]


@pytest.mark.parametrize("shape,dtype,causal,seg,block", FLASH_SHAPES)
def test_flash_kernel_compiles_for_v5e(one_chip, shape, dtype, causal, seg,
                                       block):
    from mxnet_tpu.kernels.flash_attention import flash_attention
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    s = jax.ShapeDtypeStruct((shape[0], shape[2]), jnp.int32,
                             sharding=one_chip)
    blocks = {} if block is None else {"block_q": block, "block_k": block}

    def loss(q, k, v, *segs):
        sq = segs[0] if segs else None
        out = flash_attention(q, k, v, sq, sq, causal, 0.125, **blocks)
        return out.astype(jnp.float32).sum()

    args = (x, x, x) + ((s,) if seg else ())
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_kernels_compile_with_192_wide_keys_and_128_wide_values(
        one_chip):
    """Latent attention's shapes at the benchmark's size: causal, seq 4096,
    so the two streaming kernels, forward and the one backward, each over
    the scheduled tiles alone: 36 of a head's 8 x 8, 2,304 of the
    rectangle's 4,096 a call (``mxnet_flash_tiles_total``, banked where the
    kernel is built).  The backward keeps dq for all 4,096 query rows in
    VMEM and asks for the bytes it plans (``_bwd_vmem_bytes``): a compile
    that passes here is the proof of that plan before any chip call."""
    from mxnet_tpu.kernels.flash_attention import flash_attention
    from mxnet_tpu.telemetry import metrics

    def tiles(kernel):
        return {kind: getattr(metrics.REGISTRY.get(
            "mxnet_flash_tiles_total",
            labels={"kernel": kernel, "kind": kind}), "value", 0)
            for kind in ("masked", "unmasked", "skipped")}

    kernels = ("flash_fwd", "flash_bwd")
    before = {kernel: tiles(kernel) for kernel in kernels}
    qk = jax.ShapeDtypeStruct((2, 32, 4096, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, 32, 4096, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, None, None, True, 192 ** -0.5)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(qk, qk, v).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for kernel in kernels:
        assert f"({kernel})" in text, kernel    # op_name: …jvp(flash_fwd)…
        grown = {kind: n - before[kernel][kind]
                 for kind, n in tiles(kernel).items()}
        assert grown == {"masked": 512, "unmasked": 1792, "skipped": 1792}, \
            (kernel, grown)
    for gone in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert gone not in text, gone


def test_flash_kernels_compile_at_256_wide_heads_and_seq_8192(one_chip):
    """The gated full-attention layer's shapes at the benchmark's size: 16
    query heads of 256 (K and V already repeated to them), causal, seq
    8192: the two streaming kernels over 136 of a head's 16 x 16 tiles.
    The backward keeps dq for all 8,192 query rows of a head in VMEM (8 MiB
    float32) and asks the compiler for what ``_bwd_vmem_bytes`` plans, 27
    MiB at one head a step: under the chip's 128 MiB and over the 16 MiB
    default, so a compile that passes is the proof of the plan."""
    import importlib
    fa = importlib.import_module("mxnet_tpu.kernels.flash_attention")
    bq = fa._pick_block(8192, 512)
    hb = fa._pick_block_h(16, bq, bq)
    assert (bq, hb) == (512, 1)
    planned = fa._bwd_vmem_bytes(hb, bq, bq, 8192, 256, 256, 2)
    assert 16 * 2 ** 20 < planned == 27 * 2 ** 20 < 128 * 2 ** 20
    x = jax.ShapeDtypeStruct((1, 16, 8192, 256), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, None, None, True, 256 ** -0.5)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for kernel in ("flash_fwd", "flash_bwd"):
        assert f"({kernel})" in text, kernel
    # the backward call carries the plan as its scoped VMEM
    bwd = next(line for line in text.splitlines()
               if "(flash_bwd)" in line and "custom-call(" in line)
    assert f'"size":"{planned}"' in bwd


GDN_KERNELS = ("gdn_solve", "gdn_fwd")


def _gdn_cell_shapes(one_chip):
    """The benchmark's size: one row of 8,192 positions, 32 value heads of
    128 / 128, bfloat16 in."""
    wide = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                                sharding=one_chip)
    head = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32,
                                sharding=one_chip)
    return wide, wide, wide, head, head


def _gdn_compiled(rule, shapes):
    def loss(q, k, v, g, beta):
        return (rule(q, k, v, g, beta).astype(jnp.float32) ** 2).sum()

    with jax.default_matmul_precision("default"):
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))) \
            .lower(*shapes).compile()


def _gdn_calls(text):
    """How many Mosaic calls of each kernel's name stand in a compiled
    module's text under the ``gdn_scan`` scope."""
    import re
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*', text)
    return {name: sum(bool(re.search(
        r"gdn_scan\)*/[^\"]*/%s/" % name, c)) for c in calls)
        for name in GDN_KERNELS}


def test_gated_delta_rule_compiles_at_the_cells_shape_for_v5e(one_chip):
    """The op at the benchmark's size, forward and backward, compiled for a
    TPU: the forward is the Mosaic calls ``gdn_solve`` and ``gdn_fwd`` under
    the ``gdn_scan`` scope, the backward ``gdn_solve`` once more (in
    float32) and the chunked form made again around its inverses (the
    form's ``while`` loops, none of the compiler's triangular solves); the
    temporaries stay under 2 GB (the chunked backward's)."""
    from mxnet_tpu.ops.linear_attention import _gated_delta_rule
    compiled = _gdn_compiled(
        lambda *a: _gated_delta_rule(*a, chunk=64), _gdn_cell_shapes(one_chip))
    text = compiled.as_text()
    assert _gdn_calls(text) == {"gdn_solve": 2, "gdn_fwd": 1}
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    solves = [line for line in text.splitlines()
              if "custom-call(" in line and "/gdn_solve/" in line]
    assert sorted(line.split("=", 1)[1].split("[", 1)[0].strip()
                  for line in solves) == ["bf16", "f32"]
    assert " while(" in text and "Triangular" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


@pytest.mark.parametrize("heads, kernels", [(8, True), (6, False)])
def test_gated_delta_rule_compiles_per_shard_on_a_dp2_tp2_mesh(topo, heads,
                                                               kernels):
    """Under a step traced over several devices the kernels run inside a
    ``shard_map`` (GSPMD cannot partition a Mosaic call): rows over ``dp``,
    heads over ``tp``, each device its own block, no collective in the
    forward (the backward is the chunked form, GSPMD's as before).  The
    tiling is asked about a device's heads: 3 of 6 stack in no pairs, and
    the op is the chunked form there."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops import registry
    from mxnet_tpu.ops.linear_attention import _gated_delta_rule
    mesh = parallel.make_mesh(shape=(2, 2), axis_names=("dp", "tp"),
                              devices=list(topo.devices))
    wide = jax.ShapeDtypeStruct(
        (4, 512, heads, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh.mesh, P("dp", None, "tp", None)))
    head = jax.ShapeDtypeStruct(
        (4, 512, heads), jnp.float32,
        sharding=NamedSharding(mesh.mesh, P("dp", None, "tp")))

    def forward(q, k, v, g, beta):
        with registry.step_layout_scope(mesh, ("dp",)):
            return _gated_delta_rule(q, k, v, g, beta, chunk=64)

    def gradients(*a):      # the backward's solve runs per shard too
        return jax.grad(lambda *a: forward(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2, 3, 4))(*a)

    with jax.default_matmul_precision("default"):
        text = jax.jit(forward).lower(wide, wide, wide, head, head) \
            .compile().as_text()
        backward = jax.jit(gradients).lower(wide, wide, wide, head, head) \
            .compile().as_text()
    if not kernels:
        assert "tpu_custom_call" not in text + backward
        return
    assert _gdn_calls(text) == {"gdn_solve": 1, "gdn_fwd": 1}
    # a device's block: 2 rows of 512 positions, 4 heads
    assert "bf16[2,512,512]" in text
    assert "all-gather" not in text and "all-reduce(" not in text
    assert _gdn_calls(backward)["gdn_solve"] >= 1


@pytest.mark.parametrize("k, held, hidden, temp_limit", [
    (6, 16, 768, 0.85e9), (10, 32, 512, 1.55e9)],
    ids=["mla_moe_cell", "hybrid_cell"])
def test_routed_experts_compile_to_grouped_kernels_for_v5e(
        one_chip, k, held, hidden, temp_limit):
    """``contrib.moe_experts`` at both MoE cells' widths (8,192 tokens, so
    49,152 and 81,920 pairs a layer), under jax_enable_x64 as the package
    runs: the compiler turns each ``ragged_dot`` into a Mosaic call of its
    own, the forward and the backward each carry a buffer of all N*k rows
    through a loop over the windows, and the planned temporaries stay under
    what the change planned with room (790 and 1,461 MB; the parent, whose
    every pair buffer had N*k rows, planned 1,216 and 2,155 MB)."""
    import re
    from mxnet_tpu.ops.moe import _moe_experts

    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, w, gate, up, down, experts):
        y = _moe_experts(x, w, experts, gate, up, down, first=0)[0]
        return y.astype(jnp.float32).sum()

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4))).lower(
            struct((8192, 2048)), struct((8192, k), jnp.float32),
            struct((held, 2048, hidden)), struct((held, 2048, hidden)),
            struct((held, hidden, 2048)), struct((8192, k), jnp.int32)) \
            .compile()
    text = compiled.as_text()
    # 3 forward + 9 backward products, inline for the first window and
    # again in the loops' bodies
    assert text.count('custom_call_target="tpu_custom_call"') >= 24
    assert "ragged-dot" in text
    loops = re.findall(
        r"= \(.*bf16\[%d,2048\].*\) while\(" % (8192 * k), text)
    assert len(loops) == 2, len(loops)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit


def test_illegal_block_raises_not_falls_back(one_chip):
    """A block the TPU lowering cannot take (4 rows: not a multiple of 8)
    raises at compile time — nothing switches to the dense path."""
    from mxnet_tpu.kernels.flash_attention import flash_attention
    x = jax.ShapeDtypeStruct((2, 2, 128, 64), jnp.float32,
                             sharding=one_chip)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        jax.jit(lambda q: flash_attention(q, q, q, None, None, False, 0.125,
                                          block_q=4, block_k=4)) \
            .lower(x).compile()


# -- 1b. whole programs ------------------------------------------------------

@pytest.fixture
def bert_2layer(smoke, monkeypatch):
    """chip_smoke.py's train config at BERT-base WIDTH (768 units, 12
    heads of 64, seq 512, batch 32, bf16), depth cut to 2 layers so the
    compile stays in seconds."""
    from mxnet_tpu.gluon.model_zoo import bert
    monkeypatch.setitem(bert._BERT_CONFIGS, "bert_2_768_12",
                        (2, 768, 3072, 12))
    return dict(smoke.TRAIN_FULL, name="bert_2_768_12")


def _compile_step(smoke, cfg, mesh, tp_axis=None):
    with smoke.bf16_matmuls(cfg["dtype"]):
        model, step = smoke.build_bert_step(cfg, mesh, tp_axis)
        model(nd.array(np.zeros((1, 8), np.int32)))   # deferred init
        batch = jax.ShapeDtypeStruct(
            (cfg["scan_steps"], cfg["batch"], cfg["seq"]), np.int32)
        return step.lowered(batch, batch).compile()


def test_bert_width_trainstep_compiles_with_the_kernel(topo, smoke,
                                                       bert_2layer):
    mesh = parallel.make_mesh(shape=(1,), devices=list(topo.devices[:1]))
    compiled = _compile_step(smoke, bert_2layer, mesh)
    # forward + fused backward kernel per layer: attention is the Pallas
    # kernel, not the dense path
    assert compiled.as_text().count("tpu_custom_call") == 4
    assert mx.telemetry.costmodel.peak_bytes(
        compiled.memory_analysis()) < V5E_HBM_BYTES


@pytest.mark.slow
def test_hybrid_cells_step_compiles_under_the_chips_memory(topo):
    """The step of ``qwen3_next_80b_a3b.train_s8192`` as the benchmark's
    builder makes it, at the published widths, the cell's depth and one row
    of 8,192 positions, for a described v5e: both streaming flash kernels,
    the grouped products and the scan's forward kernels, once each a linear
    layer, in one program that fits.  Built on
    the CPU with 8 of the cell's 32 held experts (the suite's workers share
    this machine's memory; 323M parameters with Adam's state are 4.5 GB of
    host memory, the cell's 626M would be 8.8).  What fits here is the
    state it holds beside the whole row's temporaries; the 24 absent
    experts a layer are state alone, 4.8 GB more at 14 B a parameter with
    2 B of gradient, and the compiler then plans the temporaries it has
    room for (7.7 GB with 16 held, 6.4 with all 32: 15.2 GB in all, read
    with the verify skill's recipe and as ``program_hbm_gb`` on the
    chip)."""
    from perfbench import run as harness
    from perfbench import weights
    from perfbench.builders import qwen3_next_zoo
    from perfbench.reference import gdn_moe_train
    _bench, cell = harness.load_cell("qwen3_next_80b_a3b.train_s8192")
    cfg, traffic = cell["config"], cell["traffic"]
    held = 8
    cfg = dict(cfg, experts_held=[0, held], num_experts=held)
    w0 = weights.make_weights(gdn_moe_train.param_shapes(cfg), 1,
                              cfg["run"]["dtype"], jax.devices()[0])
    program = qwen3_next_zoo.Program(cfg, traffic, w0, jax.devices())
    del w0
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=list(topo.devices[:1]))
    held_step = program.step
    step = parallel.TrainStep(program.model, held_step.loss_fn,
                              held_step.optimizer, mesh=mesh)
    batch = jax.ShapeDtypeStruct(
        (traffic["scan_steps"], traffic["batch"], traffic["seq"]), np.int32)
    with jax.default_matmul_precision("default"):
        compiled = step.lowered(batch, batch).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd", "ragged-dot", "gdn_scan"):
        assert name in text, name
    linear = cfg["num_hidden_layers"] \
        - cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    assert linear == 3 and _gdn_calls(text) == {
        "gdn_solve": 2 * linear, "gdn_fwd": linear}
    planned = mx.telemetry.costmodel.peak_bytes(compiled.memory_analysis())
    # 12.3 GB (4.53 of arguments, 7.55 of temporaries, the chunked
    # backward's as before the forward kernels): what the 24 absent experts
    # a layer need stays free
    assert 0.25 * V5E_HBM_BYTES < planned < 13.0e9
    program.close()


@pytest.mark.slow
def test_looped_cells_step_compiles_with_its_flash_calls_and_fits(topo):
    """The step of ``ouro_2_6b.train_s4096`` as the benchmark's builder
    makes it, at the published widths, the cell's depth and one row of
    4,096 positions, for a described v5e: the 128-wide ungrouped flash
    kernels inside ``jax.checkpoint``, whose policy keeps the forward's out
    and lse: 24 forward and 24 backward calls, none made again, in one
    program that fits with the made-again applications' products kept
    (10.6 GB planned).  334M parameters with Adam's state are 4.7 GB of
    host memory."""
    import re
    from perfbench import run as harness
    from perfbench import weights
    from perfbench.builders import ouro_zoo
    from perfbench.reference import loop_lm_train
    _bench, cell = harness.load_cell("ouro_2_6b.train_s4096")
    cfg, traffic = cell["config"], cell["traffic"]
    w0 = weights.make_weights(loop_lm_train.param_shapes(cfg), 1,
                              cfg["run"]["dtype"], jax.devices()[0])
    program = ouro_zoo.Program(cfg, traffic, w0, jax.devices())
    del w0
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=list(topo.devices[:1]))
    held_step = program.step
    step = parallel.TrainStep(program.model, held_step.loss_fn,
                              held_step.optimizer, mesh=mesh)
    batch = jax.ShapeDtypeStruct(
        (traffic["scan_steps"], traffic["batch"], traffic["seq"]), np.int32)
    with jax.default_matmul_precision("default"):
        compiled = step.lowered(batch, batch).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(flash_[a-z]+)[.\d]* = ", text)
    layers, steps = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    assert calls.count("flash_fwd") == layers * steps
    assert calls.count("flash_bwd") == layers * steps
    assert text.count("tpu_custom_call") == len(calls) == 48
    planned = mx.telemetry.costmodel.peak_bytes(compiled.memory_analysis())
    assert 0.25 * V5E_HBM_BYTES < planned < 13.0e9
    program.close()


@pytest.mark.slow
def test_ssm_hybrid_cells_step_compiles_and_its_layers_fit(topo):
    """The step of ``granite_4_0_h_micro.train_s4096`` as the benchmark's
    builder makes it, at the published widths, the vocabulary slice and one
    row of 4,096 positions, for a described v5e, with four of its ten
    layers (three Mamba-2 and the attention layer: 315M parameters, 5.0 GB
    of host memory with Adam's state; the ten are 12.4): every layer under
    ``jax.checkpoint`` keeping its input, the scan in ``jax.numpy`` (no
    Pallas call of its own) and the 64-wide flash kernels over 32 / 8
    heads, one forward and one backward call.  A layer's temporaries do
    not grow with the depth, so what the four plan beside their state is
    what the ten need beside theirs: it must fit in what the cell's state
    (10.81 GB) and gradient buffers (1.54 GB) leave of the chip."""
    import re
    from perfbench import run as harness
    from perfbench import weights
    from perfbench.builders import granite_zoo
    from perfbench.reference import ssm_hybrid_train
    _bench, cell = harness.load_cell("granite_4_0_h_micro.train_s4096")
    cfg, traffic = cell["config"], cell["traffic"]
    cfg = dict(cfg, num_hidden_layers=4,
               layer_types=["mamba"] * 3 + ["attention"])
    w0 = weights.make_weights(ssm_hybrid_train.param_shapes(cfg), 1,
                              cfg["run"]["dtype"], jax.devices()[0])
    program = granite_zoo.Program(cfg, traffic, w0, jax.devices())
    del w0
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=list(topo.devices[:1]))
    held_step = program.step
    step = parallel.TrainStep(program.model, held_step.loss_fn,
                              held_step.optimizer, mesh=mesh)
    batch = jax.ShapeDtypeStruct(
        (traffic["scan_steps"], traffic["batch"], traffic["seq"]), np.int32)
    with jax.default_matmul_precision("default"):
        compiled = step.lowered(batch, batch).compile()
    text = compiled.as_text()
    assert "ssd_scan" in text
    calls = re.findall(r"%(flash_[a-z]+)[.\d]* = ", text)
    assert sorted(set(calls)) == ["flash_bwd", "flash_fwd"]
    assert text.count("tpu_custom_call") == len(calls)
    mem = compiled.memory_analysis()
    temporaries = mem.temp_size_in_bytes
    # the cell: 10.81 GB of state + 1.54 GB of gradient buffers + these
    assert 10.81e9 + 1.54e9 + temporaries < 16.9e9, temporaries
    assert mx.telemetry.costmodel.peak_bytes(mem) < 0.6 * V5E_HBM_BYTES
    program.close()


@pytest.mark.slow
def test_conv_moe_cells_step_compiles_and_its_layers_fit(topo):
    """The step of ``lfm2_24b_a2b.train_s8192`` as the benchmark's builder
    makes it, at the published widths, the vocabulary slice and four rows
    of 8,192 positions, for a described v5e, with three of its five layers
    (the dense short convolution, the attention layer and a short
    convolution over the MoE: 284M parameters, ~4.5 GB of host memory with
    Adam's state): every layer under ``jax.checkpoint`` keeping its input,
    the gated convolution in ``jax.numpy`` under its own scope, the 64-wide
    flash kernels over 32 / 8 heads (forward twice, backward once) and the
    compiler's grouped products.  A layer's temporaries barely grow with
    the depth (its kept input, 0.13 GB), so what the three plan beside
    their state is what the five need beside theirs: it must fit in what
    the cell's state (6.57 GB) and gradient buffers (0.94 GB) leave of the
    chip, at batch 4 (the whole step planned 11.33 GB here)."""
    import re
    from perfbench import run as harness
    from perfbench import weights
    from perfbench.builders import lfm2_zoo
    from perfbench.reference import conv_moe_train
    _bench, cell = harness.load_cell("lfm2_24b_a2b.train_s8192")
    cfg, traffic = cell["config"], cell["traffic"]
    cfg = dict(cfg, num_hidden_layers=3, layers_held=[0, 2, 3])
    w0 = weights.make_weights(conv_moe_train.param_shapes(cfg), 1,
                              cfg["run"]["dtype"], jax.devices()[0])
    program = lfm2_zoo.Program(cfg, traffic, w0, jax.devices())
    del w0
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=list(topo.devices[:1]))
    held_step = program.step
    step = parallel.TrainStep(program.model, held_step.loss_fn,
                              held_step.optimizer, mesh=mesh)
    batch = jax.ShapeDtypeStruct(
        (traffic["scan_steps"], traffic["batch"], traffic["seq"]), np.int32)
    with jax.default_matmul_precision("default"):
        compiled = step.lowered(batch, batch).compile()
    text = compiled.as_text()
    assert "gated_short_conv" in text and "ragged" in text
    calls = re.findall(r"%(flash_[a-z]+)[.\d]* = ", text)
    assert sorted(calls) == ["flash_bwd", "flash_fwd", "flash_fwd"]
    mem = compiled.memory_analysis()
    temporaries = mem.temp_size_in_bytes
    assert 6.57e9 + 0.94e9 + temporaries < 15.5e9, temporaries
    program.close()


def test_trainstep_compiles_for_a_four_chip_mesh(topo, smoke, bert_2layer):
    """GSPMD cannot partition a Mosaic kernel: under a mesh the kernel
    runs inside ops.contrib._flash's shard_map (batch over dp, heads over
    tp), and the step compiles — also right after a one-device step of
    the same shapes, whose cached op traces must not be replayed."""
    mesh = parallel.make_mesh(shape=(2, 2), axis_names=("dp", "tp"),
                              devices=list(topo.devices))
    text = _compile_step(smoke, bert_2layer, mesh, "tp").as_text()
    assert text.count("tpu_custom_call") == 4
    assert " all-reduce(" in text or " all-reduce-start(" in text


def test_serving_executables_compile_for_v5e(one_chip, smoke):
    """Prefill (1, P) and decode (B, 1) at the chip_smoke.py serve width,
    depth cut to 2 layers; weights as shapes only."""
    from mxnet_tpu.serving import models as sm
    c = dict(smoke.SERVE_FULL, layers=2)
    hd = c["units"] // c["heads"]

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    U, Hd, KV = c["units"], c["hidden"], c["kv_heads"] * hd
    block = sm.LlamaBlockW(
        attn_norm=arr((U,)), q=arr((U, U)), k=arr((KV, U)), v=arr((KV, U)),
        o=arr((U, U)), mlp_norm=arr((U,)), gate=arr((Hd, U)),
        up=arr((Hd, U)), down=arr((U, Hd)))
    weights = sm.LlamaW(embed=arr((c["vocab"], U)),
                        blocks=(block,) * c["layers"], norm=arr((U,)),
                        lm_head=arr((c["vocab"], U)))
    cfg = sm.LlamaCfg(layers=c["layers"], units=U, heads=c["heads"],
                      kv_heads=c["kv_heads"], head_dim=hd, eps=1e-5,
                      rope_base=500000.0)
    table = -(-c["max_seq"] // c["block_tokens"])
    pool = arr((c["max_batch"] * table + 1, c["block_tokens"],
                c["kv_heads"], hd), jnp.float32)
    kv = ((pool, pool),) * c["layers"]
    B = c["max_batch"]

    def i32(*shape):
        return arr(shape, jnp.int32)

    jits = sm._jitted()
    jits["llama_prefill"].lower(
        cfg, weights, kv, i32(1, c["prefill_tokens"]), i32(1),
        i32(table)).compile()
    jits["llama_decode"].lower(
        cfg, weights, kv, i32(B), i32(B, table), i32(B),
        arr((B,), jnp.bool_)).compile()


# -- 2. chip_smoke.py on the CPU at a tiny size, and the refusals ------------

TINY_TRAIN = dict(name="bert_3_128_2", vocab=200, seq=32, batch=8,
                  scan_steps=2, dispatches=2, dtype="bfloat16")
TINY_SERVE = dict(layers=2, units=64, hidden=172, heads=4, kv_heads=2,
                  vocab=101, dtype="bfloat16", max_batch=4, block_tokens=4,
                  max_seq=64, prefill_tokens=16,
                  prompt_lens=(3, 9, 5, 12, 7), max_new_tokens=6)


@pytest.fixture(scope="module")
def clock(smoke):
    return smoke.CompileClock()


def test_smoke_small_phases_on_cpu(smoke, clock):
    assert set(smoke.run_phase("native", clock,
                               smoke.phase_native)["available"]) \
        == {"recordio", "jpeg"}
    row = smoke.run_phase("imperative", clock, smoke.phase_imperative,
                          mx.cpu(), "cpu")
    assert row["losses"][2] < row["losses"][0]
    assert row["compile_seconds"] > 0      # the clock saw the compiles
    row = smoke.run_phase("gluon", clock, smoke.phase_gluon, mx.cpu(), "cpu")
    assert row["param_platforms"] == ["cpu"]


def test_smoke_serve_phase_on_cpu(smoke, clock):
    row = smoke.run_phase("serve", clock, smoke.phase_serve, TINY_SERVE,
                          mx.cpu(), "cpu")
    assert row["exact_matches"] == row["requests"] == 5


def test_smoke_train_phase_on_cpu(smoke, clock):
    row = smoke.run_phase("train", clock, smoke.phase_train, TINY_TRAIN,
                          "cpu", expect_kernel=False)
    assert row["pallas_calls"] == 0 and len(row["losses"]) == 4


def test_smoke_train_phase_fails_without_the_kernel(smoke, clock, capsys,
                                                    monkeypatch):
    """Told to expect the kernel, the phase fails the run at once when the
    compiled program holds the dense path."""
    monkeypatch.setattr(smoke, "train_and_check",
                        lambda *a, **k: ({"pallas_calls": 0}, [], None))
    with pytest.raises(SystemExit):
        smoke.run_phase("train", clock, smoke.phase_train, TINY_TRAIN,
                        "cpu", expect_kernel=True)
    assert "no tpu_custom_call" in capsys.readouterr().out


def test_smoke_multichip_phase_on_four_virtual_devices(smoke, clock):
    row = smoke.run_phase("multichip", clock, smoke.phase_multichip,
                          TINY_TRAIN, "cpu", jax.devices()[:4])
    assert row["dp4"]["param_device_ids"] == [0, 1, 2, 3]
    assert row["dp2xtp2"]["params_split"] > 0


def test_smoke_phase_wrong_platform_fails(smoke, clock, capsys):
    """A phase whose arrays are not where it was told fails the run."""
    with pytest.raises(SystemExit):
        smoke.run_phase("imperative", clock, smoke.phase_imperative,
                        mx.cpu(), "tpu")
    assert "arrays not on tpu" in capsys.readouterr().out


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_smoke_main_refuses_the_cpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def _run(code, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env={**base, **env}, capture_output=True,
                          text=True, timeout=120)


@pytest.mark.parametrize("module", ["mxnet_tpu.resilience.controller",
                                    "mxnet_tpu.serving.router"])
def test_launcher_parents_leave_the_chip_alone(module):
    """The multi-process launchers' parent side initializes no JAX
    backend: a parent that did would hold the chip its children need."""
    p = _run(f"import {module}; from jax._src import xla_bridge; "
             "print(len(xla_bridge._backends))")
    assert p.stdout.strip() == "0", p.stderr


def test_compile_cache_dir_is_env_or_fixed_checkout_path(tmp_path):
    code = ("import mxnet_tpu, jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    assert _run(code).stdout.strip() == os.path.join(ROOT, ".jax_cache")
    given = str(tmp_path / "cc")
    assert _run(code, JAX_COMPILATION_CACHE_DIR=given).stdout.strip() \
        == given
