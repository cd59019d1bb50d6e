"""perfbench/scopes.py on a small module text and ops table kept under
perfbench/testdata: the rules that turn instruction names into regions, and
the readers of the region metrics and build-stage counters built on them."""

import json
import os

import pytest

from perfbench import counters, scopes
from perfbench import run as harness

DATA = os.path.join(harness.HERE, "testdata")
REGION_METRICS = {"attention_ms_per_step": "attention",
                  "head_loss_ms_per_step": "head_loss",
                  "optimizer_ms_per_step": "optimizer",
                  "encoder_dense_ms_per_step": "encoder_dense"}
STAGE_METRICS = {"step_trace_s": "trace", "step_lower_s": "lower",
                 "step_load_s": "load"}


class Module:
    def __init__(self, text):
        self._text = text

    def to_string(self):
        return self._text


@pytest.fixture(scope="module")
def text():
    with open(os.path.join(DATA, "scopes_module.txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def table():
    with open(os.path.join(DATA, "scopes_ops.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rules():
    return scopes.load_regions("bert_zoo")


@pytest.fixture(scope="module")
def assigned(text, rules):
    return scopes.regions_of([text], rules)


def traced_run(text, table):
    return {"trace": {"ops": {"/device:TPU:0": table["ops"]}},
            "cell": {"config": {"builder": "bert_zoo"}},
            "program": {"hlo_modules": [Module(text)]},
            "steps": table["steps"]}


@pytest.mark.parametrize("op_name, path", [
    ("jit(train_steps)/while/body/closed_call/bert/encoder/layer3/"
     "jvp(jit(wrapper))/attention/transpose",
     ["while", "body", "closed_call", "bert", "encoder", "layer3",
      "attention", "transpose"]),
    ("a/transpose(jvp(jit(wrapper)))/jit(_var)/mul", ["a", "mul"]),
    ("layer0/ffn/transpose(jvp())/mul", ["layer0", "ffn", "mul"]),
    ("jvp(attention)/transpose(jvp(loss))/x", ["attention", "loss", "x"]),
    ("", []),
])
def test_scope_path_takes_the_wrappers_off(op_name, path):
    assert scopes.scope_path(op_name) == path


@pytest.mark.parametrize("op_name, region", [
    ("closed_call/bert/encoder/layer3/jvp(jit(wrapper))/attention/slice",
     "attention"),
    ("closed_call/bert/encoder/layer3/ffn_1/transpose(jvp(jit(wrapper)))/"
     "dot_general", "encoder_dense"),
    ("closed_call/bert/decoder/jvp(jit(wrapper))/dot_general", "head_loss"),
    ("closed_call/loss/jvp(jit(wrapper))/jit(log_softmax)/sub", "head_loss"),
    ("closed_call/optimizer/mul", "optimizer"),
    ("closed_call/bert/embed_norm/jvp(jit(wrapper))/sub", "other"),
    ("closed_call/bert/jvp(jit(wrapper))/transpose", "other"),
    ("jit(train_steps)/while/body/dynamic_slice", None),
    ("jit(train_steps)/while/body/closed_call/jvp(jit(wrapper))/mul", None),
])
def test_first_rule_that_names_a_component_wins(rules, op_name, region):
    assert scopes.region_of_path(op_name, rules) == region


def test_a_missing_region_file_is_an_error():
    with pytest.raises(FileNotFoundError, match="no_such_builder"):
        scopes.load_regions("no_such_builder")


def test_parse_module_reads_opcode_metadata_and_calls(text):
    ins, comps = scopes.parse_module(text)
    call = ins["flash_fwd_single.48"]
    assert call["opcode"] == "custom-call"
    assert call["op_name"].endswith("flash_fwd_single/pallas_call")
    assert ins["fusion.2809"]["calls"] == "fused_computation.2809"
    assert ins["copy.5"] == {"opcode": "copy", "op_name": "", "calls": None,
                             "operand": "get-tuple-element.4",
                             "computation": "wide.body"}
    assert "convolution.77" in comps["fused_computation.2809"]
    assert ins["while.8"]["computation"] == "main.1"


@pytest.mark.parametrize("name, region, why", [
    ("fusion.2652", "optimizer", "rule 1: the fusion's own metadata"),
    ("fusion.2809", "head_loss",
     "rule 2: mixed, so the matmul's region, not the root's (optimizer)"),
    ("fusion.7", "encoder_dense",
     "mixed without a matmul keeps its own region"),
    ("fusion.30", "attention",
     "rule 3: no metadata of its own, most of its instructions"),
    ("flash_fwd_single.48", "attention", "the named Pallas call"),
    ("copy.5", "attention", "rule 4: through the get-tuple-element"),
    ("copy-done.1", "attention", "rule 4: through start and copy"),
    ("dynamic-slice.3", None, "the scan's own slicing stays unnamed"),
    ("while.8", None, "the scan itself"),
    ("copy.99", None, "rule 4 finds a parameter and stops"),
])
def test_rules_for_fusions_and_unnamed_instructions(assigned, name, region,
                                                    why):
    assert assigned[0][name] == region, why


def test_mixed_fusions_are_reported(assigned):
    assert assigned[1] == {"fusion.2809", "fusion.7", "fusion.30"}


def test_custom_call_suffix_is_taken_off():
    assert scopes.instruction_of(
        "flash_fwd_single.48:tpu_custom_call") == "flash_fwd_single.48"
    assert scopes.instruction_of("fusion.7") == "fusion.7"


def test_region_sums_add_up_to_the_busy_time(assigned, table):
    by = scopes.seconds_by_region(table["ops"], assigned[0])
    total = sum(t for _n, t in table["ops"].values())
    assert sum(by.values()) == pytest.approx(total, rel=1e-12)
    assert set(by) == {"attention", "head_loss", "optimizer",
                       "encoder_dense", "other", scopes.UNATTRIBUTED}
    # the kernel (by its suffixed name), the head split's fusion and the
    # copies the compiler put behind the kernel
    assert by["attention"] == pytest.approx(
        0.002 + 0.00164 + 0.0013 + 0.00001 + 0.0002)
    assert by["head_loss"] == pytest.approx(0.0122 + 0.014)
    # while, dynamic-slice, the entry's copy, and a name no module holds
    assert by[scopes.UNATTRIBUTED] == pytest.approx(
        0.00025 + 0.0005 + 0.00001 + 0.0004)


def test_split_of_a_traced_run(text, table):
    run = traced_run(text, table)
    s = scopes.split(run)
    assert s["steps"] == 2
    assert s["busy_s"] == pytest.approx(sum(s["by_region"].values()))
    assert s["mixed_s"] == pytest.approx(0.014 + 0.0022 + 0.002)
    assert scopes.split(run) is s               # parsed once a run


def test_split_takes_the_fullest_device(text, table):
    run = traced_run(text, table)
    run["trace"]["ops"]["/device:TPU:1"] = {"fusion.2652": [1, 1e-6]}
    assert scopes.split(run)["busy_s"] == pytest.approx(
        sum(t for _n, t in table["ops"].values()))


@pytest.mark.parametrize("metric, region", sorted(REGION_METRICS.items()))
def test_region_metric_readers(text, table, metric, region):
    run = traced_run(text, table)
    want = 1e3 * scopes.split(run)["by_region"][region] / 2
    assert harness.read_metric(metric, run) == pytest.approx(want)


def test_unattributed_share_reader(text, table):
    run = traced_run(text, table)
    assert harness.read_metric("scope_unattributed_pct", run) == \
        pytest.approx(100 * 0.00116 / 0.03951)


@pytest.mark.parametrize("metric", sorted(REGION_METRICS)
                         + ["scope_unattributed_pct"])
def test_readers_are_silent_without_scopes_or_trace(text, table, metric):
    """A program from before the scopes (the parent of the PR that brought
    them) carries no region at all: the readers return nothing, they do
    not report a step that is 100% unattributed.  Nor is an untraced run
    read."""
    bare = "\n".join(line.split(", metadata=")[0]
                     for line in text.splitlines())
    assert harness.read_metric(metric, traced_run(bare, table)) is None
    untraced = dict(traced_run(text, table), trace=None)
    assert harness.read_metric(metric, untraced) is None


@pytest.mark.parametrize("metric, stage", sorted(STAGE_METRICS.items()))
def test_build_stage_readers(monkeypatch, metric, stage):
    from mxnet_tpu import telemetry
    registry = telemetry.MetricsRegistry()
    monkeypatch.setattr(telemetry, "REGISTRY", registry)
    assert harness.read_metric(metric, {}) is None      # no such counter
    registry.gauge("mxnet_jit_build_seconds", "",
                     labels={"site": "parallel.TrainStep",
                             "stage": stage}).inc(1.25)
    registry.gauge("mxnet_jit_build_seconds", "",
                     labels={"site": "serving.prefill",
                             "stage": stage}).inc(7.0)
    assert harness.read_metric(metric, {}) == 1.25
    assert counters.build_seconds(stage, site="serving.prefill") == 7.0


def test_the_new_metrics_are_declared_for_every_training_cell():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in list(REGION_METRICS) + ["scope_unattributed_pct"]:
        assert declared[name]["moves"] == "train_tokens_per_s"
        assert declared[name]["source"] == "device_trace"
        assert declared[name]["workloads"] == cells
    for name in STAGE_METRICS:
        assert declared[name]["moves"] == "setup_s"
        assert declared[name]["source"] == "program_counter"
        assert declared[name]["workloads"] == cells


# -- the tool's tables ---------------------------------------------------------

def test_region_report_tables(text, table):
    from perfbench.tools import region_report
    out = region_report.report(table["ops"], [text], "bert_zoo",
                               table["steps"])
    assert sum(r["busy_pct"] for r in out["regions"].values()) == \
        pytest.approx(100.0)
    assert out["regions"]["head_loss"]["ms_per_step"] == pytest.approx(13.1)
    assert out["mixed_busy_pct"] == pytest.approx(
        100 * (0.014 + 0.0022 + 0.002) / 0.03951)
    assert out["mixed_ms_per_step"] == {
        "head_loss <- head_loss+optimizer": pytest.approx(7.0),
        "encoder_dense <- encoder_dense+head_loss": pytest.approx(1.1),
        "attention <- attention+encoder_dense": pytest.approx(1.0)}
    assert out["pallas_kernels"] == {"flash_fwd_single": {
        "calls_per_step": 1.0, "ms_per_step": pytest.approx(0.82)}}
    assert [row[0] for row in out["unnamed_top_ms_per_step"]][:2] == \
        ["dynamic-slice.3", "fusion.4242"]


def test_program_spans_inside_the_window_and_the_enqueue_span():
    from perfbench.tools import region_report
    host = [["perfbench_window", 100, 1000],
            ["perfbench_enqueue", 150, 300],
            ["trainstep.h2d", 160, 40], ["trainstep.enqueue", 210, 200],
            ["trainstep.h2d", 900, 50],          # outside any enqueue span
            ["trainstep.h2d", 2000, 50],         # outside the window
            ["other", 300, 10]]
    trace = {"planes": [{"name": "/host:CPU",
                         "lines": [{"name": "python3", "events": host}]}]}
    assert region_report.program_spans(trace) == {
        "trainstep.h2d": (2, pytest.approx(90e-9), 1),
        "trainstep.enqueue": (1, pytest.approx(200e-9), 1)}


# -- the whole path on the CPU: the real program's modules, the real readers --

class _EveryInstructionOnce:
    """Stands where the harness's Tracer stands and 'traces' a window in
    which every instruction of the dispatched program's loop body ran once
    for a microsecond on a device plane: names as the program's own
    modules have them, which is what the readers must cope with."""

    def __init__(self):
        self.ops = {}

    def window(self):
        import contextlib
        return contextlib.nullcontext()

    span = staticmethod(lambda name: __import__("contextlib").nullcontext())

    def reduce(self):
        busy = 1e-6 * len(self.ops)
        return {"busy_s": busy, "window_s": busy, "idle_share_max": 0.0,
                "ops": {"/device:TPU:0": self.ops}, "device_ops": [],
                "idle_gaps": []}

    def close(self):
        pass


def test_all_eight_metrics_from_a_real_step_program(monkeypatch):
    import time

    import jax

    from perfbench.runners import train_step

    import perfbench_tiny as tiny

    tracer = _EveryInstructionOnce()
    stats_of = train_step.program_stats

    def stats_and_ops(client):
        stats = stats_of(client)
        for module in stats["hlo_modules"]:
            ins, _comps = scopes.parse_module(module.to_string())
            tracer.ops.update({
                name: [1, 1e-6] for name, i in ins.items()
                if not i["computation"].startswith(("fused_", "region_"))
                and i["opcode"] not in ("parameter", "constant", "tuple",
                                        "get-tuple-element")})
        return stats

    monkeypatch.setattr(train_step, "program_stats", stats_and_ops)
    bench = tiny.bench()
    cell = dict(tiny.cell(), name=bench["workloads"][0]["name"])
    result = harness.run_cell(bench, cell, 7, 0.3, tracer, jax.devices(),
                              tiny.PEAK, start=time.perf_counter())
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(REGION_METRICS) | set(STAGE_METRICS) | {
        "scope_unattributed_pct"} <= set(got)
    for name in list(REGION_METRICS) + list(STAGE_METRICS):
        assert got[name] > 0, name
    steps = result["attempted"]
    named_s = sum(got[m] for m in REGION_METRICS) * steps / 1e3
    busy = result["device"]["busy_s"]
    assert named_s <= busy
    # every instruction weighs the same here, so this is a count: what
    # no region names is the scan's own slicing and copies, and the
    # reduce-windows and copies XLA:CPU wraps into computations of its own
    # without metadata (a v5e trace reads under 1%, PERF.md section 5)
    assert got["scope_unattributed_pct"] < 25
    other_s = busy - named_s - got["scope_unattributed_pct"] * busy / 100
    assert 0 <= other_s < 0.1 * busy            # embeddings and pooler
