"""The whole step's share of the chip's peak for a hybrid Mamba-2 +
attention decoder: model FLOPs per token of one chip's share
(perfbench/counts_ssm_hybrid.py: 6 per matmul weight a token touches, the
tied head included, causal attention in the attention layers, the chunked
scan in the Mamba-2 ones; a layer's forward made again in the backward is
not counted) times tokens per second, over chips times the table's bf16
peak.  The other ``*_step_mfu`` count other models' operations and do not
list these cells."""

from perfbench import counts_ssm_hybrid as counts


def read(run):
    cell = run["cell"]
    flops = counts.train_flops_per_token(cell["config"],
                                         cell["traffic"]["seq"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * flops * rate / (cell["chips"]
                                   * run["peak"]["bf16_flops_per_s"])
