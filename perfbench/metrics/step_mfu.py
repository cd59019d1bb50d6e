"""The whole step's share of the chips' peak: model FLOPs per token
(forward + backward, nothing recomputed, from the configuration's published
sizes) times tokens per second, over chips times the table's bf16 peak."""

from perfbench import counts


def read(run):
    cell = run["cell"]
    flops = counts.train_flops_per_token(cell["config"],
                                         cell["traffic"]["seq"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * flops * rate / (cell["chips"]
                                   * run["peak"]["bf16_flops_per_s"])
