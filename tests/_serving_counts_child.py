"""Child of tests/test_serving.py::test_scheduler_counts_are_exact: one
serving workload in a process of its own, because what it counts (programs
built, compiles in the steady window) depends on what the process has
compiled before.  One warm request, then the workload under the armed cost
ledger; prints one JSON line of counts.  Counts only: a CPU run says nothing
about time."""

import json
import sys

from mxnet_tpu import serving, telemetry
from mxnet_tpu.analysis import runtime
from mxnet_tpu.telemetry import REGISTRY, costmodel

from test_serving import _llama_tiny

SYS8 = [40 + i for i in range(8)]          # two full blocks shared by 2 prompts
WORKLOADS = {
    # continuous batching over the prefix cache: 6 requests, 4 slots
    "continuous": (dict(prefix_cache=True),
                   [SYS8 + [70], SYS8 + [71, 72], [5, 9, 11],
                    [7, 8, 9, 10, 3, 4], [12] * 9, [90]]),
    # speculative decode, k = 3, a draft of other weights: 4 requests
    "spec_decode": (dict(spec_k=3),
                    [[5, 9, 11], [7, 8, 9, 10, 3, 4], [40, 41], [12] * 9]),
}


def main(name):
    kwargs, prompts = WORKLOADS[name]
    if "spec_k" in kwargs:
        kwargs = dict(kwargs, draft_model=_llama_tiny(23))
    eng = serving.ServingEngine(_llama_tiny(7), eos_id=-1, max_batch=4,
                                block_tokens=4, max_seq=64, prefill_tokens=16,
                                **kwargs)
    telemetry.enable()
    costmodel.arm()
    costmodel.LEDGER.clear()
    REGISTRY.reset()
    eng.generate([[1, 2, 3]], max_new_tokens=2)       # builds what it reaches

    def calls():
        return sum(site["calls"] for site in
                   costmodel.LEDGER.site_summary().values())

    calls0, compiles0 = calls(), runtime.compile_count()
    eng.generate(prompts, max_new_tokens=8)
    counters = {m.name: m.value for m in REGISTRY.collect()
                if m.kind == "counter" and not m.labels
                and m.name.startswith("mxnet_serving_")}
    accepted = REGISTRY.get("mxnet_serving_accepted_draft_tokens")
    counters["mxnet_serving_accepted_draft_tokens_count"] = accepted.count
    counters["mxnet_serving_accepted_draft_tokens_sum"] = accepted.sum
    print(json.dumps({
        "dispatches": calls() - calls0,
        "compiles_in_workload": runtime.compile_count() - compiles0,
        "executables": {site: s["executables"] for site, s in
                        costmodel.LEDGER.site_summary().items()},
        "counters": counters}))


if __name__ == "__main__":
    main(sys.argv[1])
