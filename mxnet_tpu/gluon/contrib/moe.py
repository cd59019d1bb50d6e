"""Mixture-of-Experts layers with expert parallelism.

NEW capability relative to the reference: SURVEY §2.4 flags expert
parallelism / MoE ABSENT upstream (no MoE layers or ops anywhere in
apache/incubator-mxnet 1.x).  The TPU-native design follows the
GShard/Switch dense-dispatch recipe — static shapes and one-hot einsum
dispatch so XLA tiles everything onto the MXU, no dynamic gather/scatter:

 - router: per-token softmax over experts, top-k choices (k=1 Switch,
   k=2 GShard default);
 - capacity: each expert processes at most C = ceil(k·N/E · capacity_factor)
   tokens per batch; overflow tokens fall through the residual (standard
   GShard semantics);
 - dispatch/combine are (N, E, C) one-hot masks contracted with einsum —
   the whole layer is three batched matmuls plus elementwise glue;
 - expert parallelism: the stacked expert weights (E, …) carry
   ``Parameter.sharding = (expert_axis, …)`` hints; under
   ``parallel.TrainStep`` on a mesh with that axis, GSPMD shards experts
   across devices and inserts the all-to-alls over ICI;
 - auxiliary load-balance loss (Switch eq. 4): E · Σ_e f_e · p_e, returned
   alongside the output so callers add ``aux_weight * aux`` to their loss.

``DroplessMoE`` is the second design, the one today's large open MoE
decoders train with: a top-k router over ALL experts of the layer (sigmoid
scores with a choice-only bias, DeepSeek-V3's, or softmax scores with none,
``qwen3_next``'s), no capacity and no dropped token, bias-free SwiGLU experts
run as grouped matrix products over the (token, expert) pairs sorted by
expert (``ops/moe.py``), shared experts every token passes (as they are, or
each token's multiplied by ``sigmoid(x w_g)``: ``shared_gate``), and a notion
of the experts THIS device holds: the layer expert parallelism needs, run
without its exchange.
"""

from __future__ import annotations

import math

from ..block import HybridBlock
from ..nn import Dense
from ...base import MXNetError

__all__ = ["SparseMoE", "DroplessMoE", "SwiGLU"]


class SparseMoE(HybridBlock):
    """Sparsely-gated mixture-of-experts FFN (drop-in for a transformer FFN).

    Parameters
    ----------
    units : int — model width d.
    hidden_size : int — per-expert FFN hidden width.
    num_experts : int — E.
    num_experts_per_token : int — k (1 = Switch, 2 = GShard).
    capacity_factor : float — slack over the perfectly-balanced per-expert
        load; tokens beyond an expert's capacity are dropped (identity
        residual path, per GShard).
    activation : 'gelu' | 'relu' | 'silu'.
    expert_axis : mesh-axis name the expert dim shards over ('ep').

    ``__call__(x) -> (y, aux_loss)`` with x (B, L, units) or (N, units);
    y has x's shape, aux_loss is a scalar.
    """

    def __init__(self, units, hidden_size, num_experts,
                 num_experts_per_token=2, capacity_factor=1.25,
                 activation="gelu", expert_axis="ep", **kwargs):
        super().__init__(**kwargs)
        if num_experts_per_token > num_experts:
            raise MXNetError("num_experts_per_token > num_experts")
        self._units = units
        self._hidden = hidden_size
        self._E = int(num_experts)
        self._k = int(num_experts_per_token)
        self._cf = float(capacity_factor)
        self._act = activation
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(units, num_experts), init=None)
            self.expert_w1 = self.params.get(
                "expert_w1", shape=(num_experts, units, hidden_size),
                init=None)
            self.expert_b1 = self.params.get(
                "expert_b1", shape=(num_experts, hidden_size), init="zeros")
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(num_experts, hidden_size, units),
                init=None)
            self.expert_b2 = self.params.get(
                "expert_b2", shape=(num_experts, units), init="zeros")
        # expert-parallel sharding hints (consumed by parallel.TrainStep)
        for p in (self.expert_w1, self.expert_b1, self.expert_w2,
                  self.expert_b2):
            p.sharding = (expert_axis,) + (None,) * (len(p.shape) - 1)

    def _activate(self, F, h):
        if self._act == "relu":
            return F.relu(h)
        if self._act == "silu":
            return F.silu(h)
        return F.gelu(h)

    def hybrid_forward(self, F, x, gate_weight=None, expert_w1=None,
                       expert_b1=None, expert_w2=None, expert_b2=None):
        E, k = self._E, self._k
        in_shape = x.shape
        xf = F.reshape(x, shape=(-1, self._units))       # (N, d)
        N = xf.shape[0]
        C = max(1, int(math.ceil(k * N / E * self._cf)))

        logits = F.dot(xf, gate_weight)                  # (N, E)
        probs = F.softmax(logits, axis=-1)
        _, topi = F.topk(probs, k=k, ret_typ="both", axis=-1)  # (N, k)

        # sequential-position dispatch (GShard): choice-0 tokens claim
        # capacity slots first, later choices are offset by earlier counts.
        # Gate values are re-gathered from `probs` via the one-hot masks so
        # the router weight receives task-loss gradient (topk's outputs are
        # detached on the imperative tape — topk is non-differentiable).
        disps, raw_gates = [], []
        prev_count = F.zeros((1, E))
        f_frac = None                                    # top-1 load fraction
        for j in range(k):
            idx_j = F.reshape(F.slice_axis(topi, axis=1, begin=j, end=j + 1),
                              shape=(-1,))
            oh = F.one_hot(idx_j, depth=E)               # (N, E)
            if j == 0:
                f_frac = F.mean(oh, axis=0)              # (E,)
            pos = F.cumsum(oh, axis=0) - oh + prev_count  # 0-based slot
            prev_count = prev_count + F.sum(oh, axis=0, keepdims=True)
            slot = F.sum(pos * oh, axis=-1)              # (N,)
            keep = (slot < C).astype(xf.dtype)           # capacity mask
            slot_oh = F.one_hot(
                F.clip(slot, a_min=0, a_max=C - 1).astype("int32"),
                depth=C)                                 # (N, C)
            disps.append(
                F.expand_dims(oh * F.expand_dims(keep, axis=1), axis=2)
                * F.expand_dims(slot_oh, axis=1))        # (N, E, C)
            raw_gates.append(F.sum(probs * oh, axis=-1))  # (N,) differentiable

        # Switch (k=1) scales by the raw router prob — that's the router's
        # learning signal; GShard (k>1) normalizes over the chosen experts
        if k == 1:
            gate_vals = [raw_gates[0]]
        else:
            denom = raw_gates[0]
            for g in raw_gates[1:]:
                denom = denom + g
            gate_vals = [g / denom for g in raw_gates]

        combine = None
        for disp_j, gate_j in zip(disps, gate_vals):
            comb_j = disp_j * F.reshape(gate_j, shape=(-1, 1, 1))
            combine = comb_j if combine is None else combine + comb_j
        dispatch = (combine > 0).astype(xf.dtype)        # (N, E, C)

        # expert computation: three MXU-friendly batched contractions
        expert_in = F.einsum(dispatch, xf, subscripts="nec,nd->ecd")
        h = self._activate(
            F, F.einsum(expert_in, expert_w1, subscripts="ecd,edh->ech")
            + F.expand_dims(expert_b1, axis=1))
        out = F.einsum(h, expert_w2, subscripts="ech,ehd->ecd") \
            + F.expand_dims(expert_b2, axis=1)
        y = F.einsum(combine, out, subscripts="nec,ecd->nd")
        y = F.reshape(y, shape=in_shape)

        # Switch load-balance loss: E * sum_e (token fraction_e * prob mass_e)
        aux = F.sum(f_frac * F.mean(probs, axis=0)) * E
        return y, aux


class SwiGLU(HybridBlock):
    """Bias-free gated feed-forward: ``down(silu(gate x) * up x)``."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate = Dense(hidden_size, flatten=False, use_bias=False,
                              in_units=units, prefix="gate_")
            self.up = Dense(hidden_size, flatten=False, use_bias=False,
                            in_units=units, prefix="up_")
            self.down = Dense(units, flatten=False, use_bias=False,
                              in_units=hidden_size, prefix="down_")

    def hybrid_forward(self, F, x):
        return self.down(F.silu(self.gate(x)) * self.up(x))


class _Router(HybridBlock):
    """Top-k router; ``(weights (N, k) float32, experts (N, k) int32)`` of
    ``contrib.moe_router``.  ``score="sigmoid"`` comes with a choice-only
    bias, which takes no gradient (``grad_req="null"``): what moves it in
    the published recipe is a balancing rule outside the loss.
    ``score="softmax"`` has no bias."""

    def __init__(self, units, num_experts, k, scale, normalize,
                 score="sigmoid", **kwargs):
        super().__init__(**kwargs)
        self._attrs = dict(k=int(k), scale=float(scale),
                           normalize=bool(normalize))
        if score != "sigmoid":      # the default stays out of the op's
            self._attrs["score"] = score    # attributes: its cache key
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(num_experts, units), init=None)
            self.bias = self.params.get(
                "bias", shape=(num_experts,), init="zeros",
                grad_req="null") if score == "sigmoid" else None

    def hybrid_forward(self, F, x, weight, bias=None):
        return F.contrib.moe_router(x, weight, bias, **self._attrs)


class _Experts(HybridBlock):
    """The routed experts held here, stacked: ``gate``/``up`` (count, U, I)
    and ``down`` (count, I, U), the layout the grouped products take,
    sharded over ``expert_axis`` where a mesh has it."""

    def __init__(self, units, hidden_size, first, count, expert_axis,
                 **kwargs):
        super().__init__(**kwargs)
        self._first = int(first)
        with self.name_scope():
            self.gate = self.params.get(
                "gate", shape=(count, units, hidden_size), init=None)
            self.up = self.params.get(
                "up", shape=(count, units, hidden_size), init=None)
            self.down = self.params.get(
                "down", shape=(count, hidden_size, units), init=None)
        for p in (self.gate, self.up, self.down):
            p.sharding = (expert_axis, None, None)

    def hybrid_forward(self, F, x, weights, experts, gate, up, down):
        return F.contrib.moe_experts(x, weights, experts, gate, up, down,
                                     first=self._first)


class DroplessMoE(HybridBlock):
    """Dropless routed feed-forward with shared experts (DeepSeek-V3's,
    and with ``score="softmax"``, ``shared_gate=True`` ``qwen3_next``'s).

    Parameters
    ----------
    units : int — model width.
    hidden_size : int — width of one routed expert (a bias-free SwiGLU).
    num_experts : int — experts of the LAYER: the router's width.
    num_experts_per_token : int — k.
    experts_held : (first, count) — the experts this device holds; None =
        all of them.  A token's pairs on other experts add nothing here:
        their device would add them.
    num_shared_experts : int — shared experts, run as one SwiGLU of width
        ``num_shared_experts * hidden_size`` on every token (0 = none).
    routed_scaling_factor, norm_topk_prob : the router's scale, and whether
        the chosen weights are divided by their sum first.
    score : ``"sigmoid"`` (scores with a choice-only bias that takes no
        gradient) or ``"softmax"`` over the layer's experts (no bias).
    shared_gate : bool — each token's shared-expert output is multiplied
        by ``sigmoid(x w_g)``, ``w_g`` (units, 1).

    ``__call__(x) -> y`` with x (B, L, units) or (N, units); y = routed +
    shared.  No capacity, no dropped token, no auxiliary loss.  Children:
    ``router``, ``experts``, ``shared``, ``shared_gate``.  Under a ``parallel.TrainStep`` the
    layer reports, per step, ``mxnet_moe_pairs_total{layer}`` (pairs that
    fell on held experts), ``mxnet_moe_windows_total{layer}`` (windows of
    the sorted pair buffer the routed part walked to cover them: the trip
    count of ``contrib.moe_experts``' loop, 1 where the pairs that fell here
    are no more than the tokens), ``mxnet_moe_tokens_total`` (tokens routed,
    summed over layers) and ``mxnet_moe_expert_tokens_max{layer}`` (the
    fullest held expert of a step) through ``parallel.report_counter``.
    """

    def __init__(self, units, hidden_size, num_experts,
                 num_experts_per_token, experts_held=None,
                 num_shared_experts=0, routed_scaling_factor=1.0,
                 norm_topk_prob=True, score="sigmoid", shared_gate=False,
                 expert_axis="ep", **kwargs):
        super().__init__(**kwargs)
        first, count = experts_held or (0, num_experts)
        if num_experts_per_token > num_experts:
            raise MXNetError("num_experts_per_token > num_experts")
        if first < 0 or count < 1 or first + count > num_experts:
            raise MXNetError(
                f"experts_held {(first, count)} is not a range of the "
                f"layer's {num_experts} experts")
        if score not in ("sigmoid", "softmax"):
            raise MXNetError(f"score {score!r}: want sigmoid|softmax")
        if shared_gate and not num_shared_experts:
            raise MXNetError("shared_gate without a shared expert")
        self._units = units
        with self.name_scope():
            self.router = _Router(units, num_experts, num_experts_per_token,
                                  routed_scaling_factor, norm_topk_prob,
                                  score=score, prefix="router_")
            self.experts = _Experts(units, hidden_size, first, count,
                                    expert_axis, prefix="experts_")
            self.shared = SwiGLU(units, num_shared_experts * hidden_size,
                                 prefix="shared_") \
                if num_shared_experts else None
            self.shared_gate = Dense(
                1, flatten=False, use_bias=False, in_units=units,
                prefix="shgate_") if shared_gate else None

    def hybrid_forward(self, F, x):
        from ... import parallel, regions
        xf = F.reshape(x, shape=(-1, self._units))              # (N, U)
        weights, chosen = self.router(xf)
        y, tokens, windows = self.experts(xf, weights, chosen)
        layer = {"layer": regions.current()}
        parallel.report_counter("mxnet_moe_pairs_total", tokens,
                                labels=layer)
        parallel.report_counter("mxnet_moe_windows_total", windows,
                                labels=layer)
        parallel.report_counter("mxnet_moe_tokens_total", xf.shape[0])
        parallel.report_counter("mxnet_moe_expert_tokens_max", tokens,
                                labels=layer, kind="max")
        if self.shared_gate is not None:
            y = y + F.sigmoid(self.shared_gate(xf)) * self.shared(xf)
        elif self.shared is not None:
            y = y + self.shared(xf)
        return F.reshape(y, shape=x.shape)
