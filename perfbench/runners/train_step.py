"""Runner of a training cell: one process, one compiled step, one window.

Set-up makes the weights from the seed, builds the program, and drives it
through its first dispatch (``scan_steps`` steps, which compiles or finds the
program in the cache) and one more.  The same object then runs the window:
dispatch after dispatch of fresh seeded batches, at most one enqueued ahead
of the one whose losses are being fetched, every loss kept.  Once the window
has closed, the memory has been read and the program's state is freed, the
plain reference follows the first dispatch and ``compare`` holds the
program's losses, Adam moments and parameter changes against it.
"""

import contextlib
import gc
import importlib
import time

import numpy as np

from perfbench import compare, weights
from perfbench.feed import TokenFeed


def sides(cfg):
    """The plain reference and the builder a configuration names, and the
    leaves (name -> shape, init) they share."""
    ref = importlib.import_module("perfbench.reference." + cfg["reference"])
    builder = importlib.import_module("perfbench.builders." + cfg["builder"])
    return ref, builder, ref.param_shapes(cfg)


def observe_program(program, shapes, seed, dtype, device, losses):
    """The program's side of the comparison after its first dispatch.  The
    initial weights are made again from the seed (the program's own copies
    were donated to the step), used for the change's norms and dropped."""
    master, m = program.state()
    w0 = weights.make_weights(shapes, seed, dtype, device)
    return {"losses": [float(x) for x in losses],
            "m": compare.leaf_norms(m),
            "delta": compare.leaf_norms(master, minus=w0)}


def observe_reference(ref, cfg, cell, shapes, seed, dtype, device, tokens,
                      labels, matmul=None, **fault):
    """The reference's side (or, with ``matmul`` or a fault, a control's):
    float32 from the same seeded values, the same first batches."""
    import jax
    import jax.numpy as jnp
    w0 = weights.make_weights(shapes, seed, dtype, device)
    w0 = {k: v.astype(jnp.float32) for k, v in w0.items()}
    with jax.default_device(device):
        losses, grad1, m, _v, w = ref.train_steps(
            w0, jnp.asarray(tokens), jnp.asarray(labels), cfg,
            cfg["run"]["optimizer"],
            matmul=matmul or ref.HIGHEST_MATMUL,
            block_rows=cell["reference_block_rows"], **fault)
    return {"losses": [float(x) for x in np.asarray(losses)],
            "m": compare.leaf_norms(m),
            "delta": compare.leaf_norms(w, minus=w0),
            "grad1": compare.leaf_norms(grad1)}


def program_stats(client):
    """The dispatched step program as the runtime holds it: the live
    executable that plans the most memory.  Its own memory statistics and
    HLO modules, with no second compile."""
    best, best_exe = None, None
    for exe in client.live_executables():
        try:
            mem = exe.get_compiled_memory_stats()
        except Exception:       # noqa: BLE001 — not every executable has them
            continue
        planned = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        if best is None or planned > best["planned_bytes"]:
            best_exe = exe
            best = {"planned_bytes": int(planned),
                    "arguments": int(mem.argument_size_in_bytes),
                    "temporaries": int(mem.temp_size_in_bytes),
                    "outputs": int(mem.output_size_in_bytes),
                    "aliased": int(mem.alias_size_in_bytes)}
    if best is None:
        raise RuntimeError("the runtime holds no executable with memory "
                           "statistics")
    modules = best_exe.hlo_modules()
    best["name"] = modules[0].name if modules else ""
    best["hlo_modules"] = modules       # their text is long: read on demand
    return best


def run(cell, seed, seconds, tracer, devices, clock):
    """``cell``: config, traffic and the cell's own file merged by the
    harness.  ``tracer``: None or an object whose ``window()`` context wraps
    the measured window.  ``clock``: compile counter and process start."""
    cfg, traffic = cell["config"], cell["traffic"]
    ref, builder, shapes = sides(cfg)
    dtype = cfg["run"]["dtype"]
    dev0 = devices[0]

    stages = {"imports_s": time.perf_counter() - clock.start}

    def stage(name, since):
        stages[name] = time.perf_counter() - since
        return time.perf_counter()

    t = time.perf_counter()
    w0 = weights.make_weights(shapes, seed, dtype, dev0)
    t = stage("weights_s", t)
    program = builder.Program(cfg, traffic, w0, devices)
    del w0
    t = stage("build_s", t)
    feed = TokenFeed(traffic, cfg["vocab_size"], seed)
    first = feed.next()
    losses0 = program.losses(program.run(*first))
    t = stage("first_dispatch_s", t)
    obs = observe_program(program, shapes, seed, dtype, dev0, losses0)
    t = stage("observe_s", t)
    all_losses = [losses0, program.losses(program.run(*feed.next()))]
    stage("second_dispatch_s", t)

    nxt = feed.next()
    compiles0 = clock.compiles
    setup_s = time.perf_counter() - clock.start
    span = tracer.span if tracer else contextlib.nullcontext
    with (tracer.window() if tracer else contextlib.nullcontext()):
        t0 = time.perf_counter()
        pending, window_losses = None, []
        while True:
            with span("perfbench_enqueue"):
                handle = program.run(*nxt)
            if pending is not None:
                with span("perfbench_fetch"):
                    window_losses.append(program.losses(pending))
            pending = handle
            if time.perf_counter() - t0 >= seconds:
                break
            with span("perfbench_feed"):
                nxt = feed.next()
        with span("perfbench_fetch"):
            window_losses.append(program.losses(pending))
        window_s = time.perf_counter() - t0
    compiles = clock.compiles - compiles0

    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    peak_in_use = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    prog = program_stats(dev0.client)
    program.close()
    del program, pending, handle
    gc.collect()

    ref_obs = observe_reference(ref, cfg, cell, shapes, seed, dtype, dev0,
                                *first)
    in_window = np.concatenate(window_losses)
    nonfinite = int(np.sum(~np.isfinite(
        np.concatenate(all_losses + window_losses))))
    steps = len(in_window)
    ok, rows = compare.judge(
        compare.numbers(obs, ref_obs), cell["limits"],
        extra=[("compiles_in_window", compiles, 0),
               ("nonfinite_losses", nonfinite, 0)])
    return {"correct": ok, "rows": rows, "attempted": steps,
            "failed": int(np.sum(~np.isfinite(in_window))),
            "setup_s": setup_s, "setup_stages": stages,
            "window_s": window_s, "steps": steps,
            "tokens": steps * traffic["batch"] * traffic["seq"],
            "compiles_in_window": compiles,
            "peak_bytes_in_use": peak_in_use, "program": prog}
