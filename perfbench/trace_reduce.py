"""From a profiler trace to numbers: device busy and idle time, per-name
device durations, the longest idle gaps and what the host was doing in them.

``load_xplane`` turns the profiler's ``.xplane.pb`` into plain data
(``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}``); everything else works on that form, so the tests feed
a small recorded trace kept as JSON under ``perfbench/testdata``.

What a v5e trace holds (looked at by hand, PR 26): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per executed HLO
instruction, named by the instruction's whole text, a ``while`` spanning the
events of its body (the lines ``Steps``, ``XLA Modules`` and ``Async XLA
Ops`` are not read); a host plane ``/host:CPU`` with one line per thread,
on the same clock.  A Pallas kernel is a ``custom-call`` whose
``custom_call_target`` is ``tpu_custom_call``.  The benchmark marks its
window with a host span named ``WINDOW_SPAN``.
"""

import glob
import gzip
import json
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"
WINDOW_SPAN = "perfbench_window"
SPAN_PREFIX = "perfbench_"


_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(text):
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``fusion.12``; a custom call
    keeps its target: ``branch_0_fun.97:tpu_custom_call``."""
    name = text.split(" = ", 1)[0].lstrip("%")
    target = _TARGET.search(text)
    return f"{name}:{target.group(1)}" if target else name


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path, keep_plane=lambda name: True):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not keep_plane(plane.name):
            continue
        lines = []
        for line in plane.lines:
            events = [[short_name(ev.name), int(ev.start_ns),
                       int(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_json(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def describe(trace, top=12):
    """What a trace holds, for a look by hand: planes, lines, event counts
    and the names that take most time on each line."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            by = {}
            for name, _start, dur in line["events"]:
                n, t = by.get(name, (0, 0))
                by[name] = (n + 1, t + dur)
            names = sorted(by.items(), key=lambda kv: -kv[1][1])[:top]
            out.append({"plane": plane["name"], "line": line["name"],
                        "events": len(line["events"]),
                        "top": [[k, n, t] for k, (n, t) in names]})
    return out


# -- the reduction -------------------------------------------------------------

def window_of(trace):
    """(start_ns, end_ns) of the benchmark's window span on the host; None
    where the trace has none."""
    for plane in trace["planes"]:
        if not plane["name"].startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW_SPAN:
                    return start, start + dur
    return None


def device_ops(trace):
    """plane name -> its op events [name, start, dur], sorted by start."""
    out = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out[plane["name"]] = sorted(
                    line["events"], key=lambda e: (e[1], -e[2]))
    return out


def clip(events, window):
    """Events cut to the window; those outside it dropped."""
    if window is None:
        return events
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def busy_intervals(events):
    """Union of the events' intervals, as sorted disjoint [start, end]."""
    merged = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return merged


def self_times(events):
    """name -> [count, ns] with an event's time less that of the events it
    spans (a ``while`` and its body), so that the sum over names is the
    busy time and nothing counts twice.  ``events`` sorted by start, an
    outer event before its first inner one."""
    out, stack = {}, []      # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, self_ns = stack.pop()
            n, t = out.get(name, (0, 0))
            out[name] = [n + 1, t + self_ns]

    for name, start, dur in events:
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def host_spans(trace, prefix=SPAN_PREFIX):
    """The benchmark's own host spans [name, start, dur], the window span
    left out."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            out += [e for e in line["events"]
                    if e[0].startswith(prefix) and e[0] != WINDOW_SPAN]
    return sorted(out, key=lambda e: e[1])


def idle_gaps(intervals, window, spans, top=10):
    """The longest gaps between busy intervals inside the window, each
    named by the benchmark's host span that covers most of it
    (``host_other`` where none does): [[name, seconds], ...]."""
    lo, hi = window
    edges = [lo] + [x for iv in intervals for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1 in gaps[:top]:
        best, cover = "host_other", 0
        for name, start, dur in spans:
            c = min(g1, start + dur) - max(g0, start)
            if c > cover:
                best, cover = name, c
        out.append([best, (g1 - g0) / 1e9])
    return out


def reduce(trace, top=10):
    """Everything the metric readers take from a trace.

    ``busy_s``/``window_s``: averaged over the device planes, as the
    contract's ``device`` object wants them; ``idle_share_max``: the idle
    share of the most idle device; ``ops``: per device plane, name ->
    [count, self seconds]; ``device_ops`` and ``idle_gaps``: the
    breakdown's two lists, of the most idle device."""
    per_dev = device_ops(trace)
    if not per_dev:
        raise ValueError("the trace has no device plane with an "
                         f"{OPS_LINE!r} line")
    window = window_of(trace)
    spans = host_spans(trace)
    devs = {}
    for plane, events in per_dev.items():
        events = clip(events, window)
        if not events:
            continue
        win = window or (events[0][1], max(s + d for _, s, d in events))
        iv = busy_intervals(events)
        busy = sum(e - s for s, e in iv)
        devs[plane] = {
            "busy_s": busy / 1e9, "window_s": (win[1] - win[0]) / 1e9,
            "ops": {k: [n, t / 1e9]
                    for k, (n, t) in self_times(events).items()},
            "idle_gaps": idle_gaps(iv, win, spans, top)}
    if not devs:
        raise ValueError("no operation ran on a device inside the window")
    idlest = max(devs, key=lambda p: 1 - devs[p]["busy_s"]
                 / devs[p]["window_s"])
    worst = devs[idlest]
    ops = sorted(worst["ops"].items(), key=lambda kv: -kv[1][1])[:top]
    return {
        "busy_s": sum(d["busy_s"] for d in devs.values()) / len(devs),
        "window_s": sum(d["window_s"] for d in devs.values()) / len(devs),
        "idle_share_max": 1 - worst["busy_s"] / worst["window_s"],
        "ops": {p: d["ops"] for p, d in devs.items()},
        "device_ops": [[k, t] for k, (_n, t) in ops],
        "idle_gaps": worst["idle_gaps"]}


def is_pallas_call(name):
    """A custom call into a Pallas (Mosaic) kernel, as ``short_name`` names
    it."""
    return name.endswith(":tpu_custom_call")


def seconds_of(reduced, match, which=max):
    """Self seconds of the ops whose name ``match`` accepts, per device
    plane, combined by ``which`` (the fullest device by default); None where
    no device ran such an op."""
    sums = [sum(t for name, (_n, t) in ops.items() if match(name))
            for ops in reduced["ops"].values()]
    sums = [s for s in sums if s > 0]
    return which(sums) if sums else None
