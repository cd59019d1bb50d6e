"""From the step program's own optimized HLO to regions of the step: which
part of the model each device instruction belongs to, by the scopes the
program put into ``metadata={op_name="…"}`` (``mxnet_tpu.regions``).

The trace names a device event by its HLO instruction (``fusion.2809``,
``flash_fwd_single.48:tpu_custom_call``): names that change with every
compile.  The dispatched executable's modules (``run["program"]
["hlo_modules"]``) say, for each instruction, the path it was traced under:
``jit(train_steps)/while/body/closed_call/bert/encoder/layer3/jvp(jit(wrapper))
/attention/…``.  ``scope_path`` takes the transformation wrappers off
(``jvp(…)``, ``transpose(…)``; a ``jit(<function>)`` component names a
function, not a scope, and is dropped), and a region file
(``perfbench/regions/<builder>.json``) maps the scope names to regions: its
rules are tried in order and the first one that names a component of the
path wins.  No rule matches: the instruction is unattributed.

Rules for instructions that are not plain (written down here because the
numbers depend on them):

1. A fusion takes the region of its own metadata (the compiler gives a fusion
   the metadata of its root).
2. A *mixed* fusion — its own region and those of the instructions of its
   fused computation are not all one region — that holds a ``dot`` or
   ``convolution`` takes that instruction's region: the matmul is what the
   time is spent on, the other region's elementwise work rides on it
   (``fusion.2809`` of PERF.md: the decoder weight's gradient matmul with the
   Adam update fused on is ``head_loss``, not ``optimizer``).  ``regions_of``
   reports which instructions are mixed, so that their share of the busy
   time can be given beside the numbers.
3. A fusion whose own metadata names no region takes the region most of its
   fused computation's instructions carry.
4. An instruction that still has none (a layout ``copy``, the start and done
   of an asynchronous copy, a ``bitcast``: the compiler put them in and gave
   them no metadata) takes the region of the instruction that produced its
   first operand, followed through such instructions.
"""

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
UNATTRIBUTED = "unattributed"

_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|[ )])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPER = re.compile(r"^([\w\-]+)\((.*)\)$")
_MATMULS = ("dot", "convolution")


def scope_path(op_name):
    """The scope names of an ``op_name``, outermost first:
    ``a/jvp(jit(f))/transpose(jvp(b))/mul`` -> ``["a", "b", "mul"]``."""
    out = []
    for part in op_name.split("/"):
        while True:
            m = _WRAPPER.match(part)
            if m is None:
                break
            part = "" if m.group(1) in ("jit", "pjit") else m.group(2)
        if part:
            out.append(part)
    return out


def load_regions(builder):
    """The region rules of a builder: ``[(region, {scope names}), …]`` in
    the file's order.  A builder without a file is an error: a silent zero
    would read as a region that costs nothing."""
    path = os.path.join(HERE, "regions", builder + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no region file for the builder {builder!r}: {path}")
    with open(path) as f:
        rules = json.load(f)["regions"]
    return [(r["region"], frozenset(r["scopes"])) for r in rules]


def region_of_path(op_name, rules):
    """The region of one ``op_name``, or None where no rule matches."""
    parts = set(scope_path(op_name))
    for region, scopes in rules:
        if parts & scopes:
            return region
    return None


def parse_module(text):
    """``{instruction name: {"opcode", "op_name", "calls", "operand",
    "computation"}}`` and ``{computation name: [instruction names]}`` of one
    module's text (``HloModule.to_string()`` / ``Compiled.as_text()``)."""
    instructions, computations = {}, {}
    current = None
    for line in text.splitlines():
        if not line.startswith(" "):
            if line.endswith("{"):
                head = line.split(" (", 1)[0].split()
                current = head[-1].lstrip("%") if head else None
                computations[current] = []
            elif line.startswith("}"):
                current = None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        name, rest = m.groups()
        body = rest.split(", metadata=", 1)[0]
        opcode = _OPCODE.search(body)
        if opcode is None:
            continue
        op_name = _OP_NAME.search(rest)
        calls = _CALLS.search(body)
        operand = _OPERAND.search(body, opcode.end())
        instructions[name] = {
            "opcode": opcode.group(1),
            "op_name": op_name.group(1) if op_name else "",
            "calls": calls.group(1) if calls else None,
            "operand": operand.group(1) if operand else None,
            "computation": current}
        computations[current].append(name)
    return instructions, computations


def parse_modules(texts):
    """``parse_module`` over several modules' texts, merged."""
    instructions, computations = {}, {}
    for text in texts:
        ins, comps = parse_module(text)
        instructions.update(ins)
        computations.update(comps)
    return instructions, computations


def regions_of(texts, rules):
    """``({instruction name: region or None}, {names of mixed fusions})``
    over the modules' texts, by the rules of the module's docstring."""
    instructions, computations = parse_modules(texts)
    own = {name: region_of_path(i["op_name"], rules)
           for name, i in instructions.items()}
    out, mixed = dict(own), set()
    for name, i in instructions.items():
        if i["opcode"] != "fusion" or i["calls"] not in computations:
            continue
        inner = [n for n in computations[i["calls"]] if own[n] is not None]
        found = {own[n] for n in inner}
        if own[name] is not None:
            found.add(own[name])
        if len(found) > 1:
            mixed.add(name)
            matmul = next((n for n in inner
                           if instructions[n]["opcode"] in _MATMULS), None)
            if matmul is not None:
                out[name] = own[matmul]
                continue
        if own[name] is None and inner:
            counts = {}
            for n in inner:
                counts[own[n]] = counts.get(own[n], 0) + 1
            out[name] = max(counts, key=counts.get)
    for name in instructions:
        seen, at = set(), name
        while out.get(at) is None and at not in seen:
            seen.add(at)
            at = instructions.get(at, {}).get("operand")
            if at is None:
                break
        if at is not None and out.get(at) is not None:
            out[name] = out[at]
    return out, mixed


def instruction_of(event_name):
    """The HLO instruction of a trace event's name as
    ``trace_reduce.short_name`` gives it: a custom call's
    ``:<target>`` suffix taken off."""
    return event_name.split(":", 1)[0]


def seconds_by_region(ops, regions):
    """Self seconds of one device plane's ``ops`` (name -> [count, self
    seconds]) summed by region; what has none goes under
    ``UNATTRIBUTED``.  The sums add up to the plane's busy time."""
    out = {}
    for name, (_count, seconds) in ops.items():
        region = regions.get(instruction_of(name)) or UNATTRIBUTED
        out[region] = out.get(region, 0.0) + seconds
    return out


def split(run):
    """What the region metrics read from a traced run, or None where there
    is nothing to read: no trace, or a program that carries no region
    scope at all (a tree from before the scopes).  ``{"by_region": {region:
    seconds}, "busy_s", "mixed_s", "steps"}`` of the fullest device."""
    if run.get("trace") is None:
        return None
    if "_scopes_split" in run:
        return run["_scopes_split"]
    rules = load_regions(run["cell"]["config"]["builder"])
    texts = [m.to_string() for m in run["program"]["hlo_modules"]]
    regions, mixed = regions_of(texts, rules)
    result = None
    if any(r is not None for r in regions.values()):
        ops = max(run["trace"]["ops"].values(),
                  key=lambda o: sum(t for _n, t in o.values()))
        by_region = seconds_by_region(ops, regions)
        result = {"by_region": by_region,
                  "busy_s": sum(by_region.values()),
                  "mixed_s": sum(t for name, (_n, t) in ops.items()
                                 if instruction_of(name) in mixed),
                  "steps": run["steps"]}
    run["_scopes_split"] = result
    return result


def region_ms_per_step(run, region):
    """Device milliseconds a step spends in ``region``; None where
    ``split`` finds nothing to read."""
    s = split(run)
    if s is None:
        return None
    return 1e3 * s["by_region"].get(region, 0.0) / s["steps"]
