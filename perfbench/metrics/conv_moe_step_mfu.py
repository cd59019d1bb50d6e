"""The whole step's share of the chip's peak for a short-convolution +
attention MoE decoder: model FLOPs per token of one chip's share
(perfbench/counts_conv_moe.py: 6 per matmul weight a token touches, the
convolution's taps, the router and the tied head included, the routed
experts at the pairs an even router puts on the held ones, causal attention
in the attention layers; a layer's forward made again in the backward is
not counted) times tokens per second, over chips times the table's bf16
peak.  The other ``*_step_mfu`` count other models' operations and do not
list these cells."""

from perfbench import counts_conv_moe as counts


def read(run):
    cell = run["cell"]
    flops = counts.train_flops_per_token(cell["config"],
                                         cell["traffic"]["seq"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * flops * rate / (cell["chips"]
                                   * run["peak"]["bf16_flops_per_s"])
