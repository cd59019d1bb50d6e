"""The comparison that decides ``correct`` for a training cell.

An observation is what one side (the program, the reference, a control or a
planted fault) shows after the first dispatch of K steps from the seed:

    losses   (K,)   each step's loss
    m        name -> norm of Adam's first moment after the K steps: the
                     gradients as the optimizer got them, weighted
                     (1-b1)*b1^(K-k)
    delta    name -> norm of the float32 parameters' change over the K steps

``numbers`` holds an observation against the reference's.  Norms are compared
by the worst leaf, as the gap between the two norms (not the norm of the
difference) over the reference's norm of that leaf or of the median leaf,
whichever is larger.  Leaves whose first gradient in the reference is under
a thousandth of the median leaf's are left out of ``update``: under Adam
they move by round-off alone (the pooler, which the loss does not reach).
"""

import math
import statistics
import sys

import jax
import jax.numpy as jnp


@jax.jit
def _norms(tree, minus):
    def f32(x):
        return x.astype(jnp.float32)
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        f32(v) - f32(minus[k]) if minus else f32(v))))
        for k, v in tree.items()}


def leaf_norms(tree, minus=None):
    """name -> norm of each leaf (of ``tree - minus`` where given), one
    program and one fetch for the whole tree."""
    return {k: float(v)
            for k, v in jax.device_get(_norms(tree, minus)).items()}


def _worst_leaf(got, want, leaves):
    floor = statistics.median(want[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
        if not gap <= worst:        # a NaN gap is the worst there is
            worst, where = gap, k
    return worst, where


def numbers(obs, ref):
    """name -> (value, leaf or step it was read at) of each number compared.
    ``ref`` also carries ``grad1``: name -> norm of its first gradient."""
    out = {}
    gaps = [abs(float(a) - float(b)) / abs(float(b))
            for a, b in zip(obs["losses"], ref["losses"])]
    bad = [n for n, g in enumerate(gaps) if not math.isfinite(g)]
    out["loss"] = (math.inf, f"step{bad[0] + 1}") if bad else \
        (max(gaps), f"step{gaps.index(max(gaps)) + 1}")
    names = list(ref["m"])
    out["grad"] = _worst_leaf(obs["m"], ref["m"], names)
    g_floor = 1e-3 * statistics.median(ref["grad1"].values())
    moved = [k for k in names if ref["grad1"][k] >= g_floor]
    out["update"] = _worst_leaf(obs["delta"], ref["delta"], moved)
    return out


def judge(nums, limits, extra=()):
    """(correct, rows): every number beside its limit.  ``extra`` rows are
    (name, value, limit) counts that must not pass their limit either."""
    rows = [(k, v, limits[k], where) for k, (v, where) in nums.items()]
    rows += [(k, v, lim, "") for k, v, lim in extra]
    ok = all(math.isfinite(v) and v <= lim for _, v, lim, _ in rows)
    return ok, rows


def report(rows, correct, stream=None):
    """The run's last lines on standard error: each number and its limit."""
    stream = stream or sys.stderr
    for name, value, limit, where in rows:
        print(f"compared {name} {value:.6g} limit {limit:.6g}"
              + (f" at {where}" if where else ""), file=stream)
    print(f"correct {str(correct).lower()}", file=stream, flush=True)


def as_json(rows):
    # JSON has no infinity or NaN: a reading that is neither a number nor
    # finite is written as 1e30, far past any limit
    return {name: {"value": value if math.isfinite(value) else 1e30,
                   "limit": limit}
            for name, value, limit, _ in rows}
