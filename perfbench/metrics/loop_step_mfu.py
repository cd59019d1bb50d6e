"""The whole step's share of the chip's peak for a looped language model:
model FLOPs per token of one chip's share (perfbench/counts_loop_lm.py: 6
per matmul weight of a layer application and causal attention, times the
applications of the loop, the head and the gate once a loop step; a forward
pass made again in the backward is not counted) times tokens per second,
over chips times the table's bf16 peak.  The other ``*_step_mfu`` count
other models' operations and do not list these cells."""

from perfbench import counts_loop_lm as counts


def read(run):
    cell = run["cell"]
    flops = counts.train_flops_per_token(cell["config"],
                                         cell["traffic"]["seq"])
    rate = run["tokens"] / run["window_s"]
    return 100.0 * flops * rate / (cell["chips"]
                                   * run["peak"]["bf16_flops_per_s"])
