"""The whole step's share of the chip's peak for a hybrid Gated DeltaNet +
gated attention + MoE decoder: model FLOPs per token of one chip's share
(perfbench/counts_gdn_moe.py: 6 per matmul weight a token touches, the
routed experts at the expected pairs a token, causal attention in the full
layers, the chunked gated delta rule in the linear ones) times tokens per
second, over chips times the table's bf16 peak.  ``step_mfu`` and
``moe_step_mfu`` count other models' operations and do not list these
cells."""

from perfbench import counts_gdn_moe as counts


def read(run):
    cell = run["cell"]
    flops = counts.train_flops_per_token(cell["config"],
                                         cell["traffic"]["seq"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * flops * rate / (cell["chips"]
                                   * run["peak"]["bf16_flops_per_s"])
