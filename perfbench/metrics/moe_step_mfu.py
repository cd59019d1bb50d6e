"""The whole step's share of the chip's peak for an MLA + MoE decoder: model
FLOPs per token of one chip's share (perfbench/counts_mla_moe.py: 6 per
matmul weight a token touches, the routed experts at the expected pairs a
token, plus causal attention) times tokens per second, over chips times the
table's bf16 peak.  ``step_mfu`` counts BERT's operations and does not list
these cells."""

from perfbench import counts_mla_moe as counts


def read(run):
    cell = run["cell"]
    flops = counts.train_flops_per_token(cell["config"],
                                         cell["traffic"]["seq"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * flops * rate / (cell["chips"]
                                   * run["peak"]["bf16_flops_per_s"])
