"""Fused optimizer-update operators.

Rebuild of src/operator/optimizer_op.cc (sgd_update, sgd_mom_update, adam,
nag, rmsprop, ftrl, signum, LAMB, multi-precision mp_* variants).  Each op is
one jitted XLA computation (the fused-kernel property that matters on TPU);
state updates are returned functionally and written back by
python/mxnet_tpu/optimizer.py.  Multi-tensor (`multi_*`) fusion is achieved at
the Trainer level by jitting one update over the whole param pytree, which
strictly generalizes the reference's fixed-arity multi_sgd kernels.
"""

from __future__ import annotations

from .registry import register

# per-step-varying scalars are traced jit args (no recompile per value)
_DYN = ("lr", "wd", "rescale_grad", "momentum", "t", "eta", "lamda1", "beta")


def _jnp():
    import jax.numpy as jnp
    return jnp


def _prep(grad, rescale_grad, clip_gradient):
    jnp = _jnp()
    g = grad * rescale_grad
    if clip_gradient is not None and clip_gradient >= 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    return g


@register("sgd_update", dynamic_attrs=_DYN)
def _sgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
                clip_gradient=-1.0, lazy_update=False):  # noqa: ARG001
    g = _prep(grad, rescale_grad, clip_gradient)
    return weight - lr * (g + wd * weight)


@register("sgd_mom_update", num_outputs=2, dynamic_attrs=_DYN)
def _sgd_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                    rescale_grad=1.0, clip_gradient=-1.0, lazy_update=False):  # noqa: ARG001
    g = _prep(grad, rescale_grad, clip_gradient)
    new_mom = momentum * mom - lr * (g + wd * weight)
    return weight + new_mom, new_mom


@register("nag_mom_update", num_outputs=2, dynamic_attrs=_DYN)
def _nag_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                    rescale_grad=1.0, clip_gradient=-1.0):
    g = _prep(grad, rescale_grad, clip_gradient) + wd * weight
    new_mom = momentum * mom + g
    return weight - lr * (g + momentum * new_mom), new_mom


@register("adam_update", num_outputs=3, dynamic_attrs=_DYN)
def _adam_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                 lazy_update=False):  # noqa: ARG001
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient) + wd * weight
    m = beta1 * mean + (1 - beta1) * g
    v = beta2 * var + (1 - beta2) * jnp.square(g)
    w = weight - lr * m / (jnp.sqrt(v) + epsilon)
    return w, m, v


@register("adamw_update", num_outputs=3, dynamic_attrs=_DYN)
def _adamw_update(weight, grad, mean, var, lr=0.001, beta1=0.9, beta2=0.999,
                  epsilon=1e-8, wd=0.0, eta=1.0, rescale_grad=1.0,
                  clip_gradient=-1.0):
    """reference src/operator/contrib/adamw.cc (decoupled weight decay)."""
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient)
    m = beta1 * mean + (1 - beta1) * g
    v = beta2 * var + (1 - beta2) * jnp.square(g)
    w = weight - eta * (lr * m / (jnp.sqrt(v) + epsilon) + wd * weight)
    return w, m, v


@register("rmsprop_update", num_outputs=2, dynamic_attrs=_DYN)
def _rmsprop_update(weight, grad, n, lr=0.001, gamma1=0.9, epsilon=1e-8,
                    wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                    clip_weights=-1.0):
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient) + wd * weight
    new_n = gamma1 * n + (1 - gamma1) * jnp.square(g)
    w = weight - lr * g / jnp.sqrt(new_n + epsilon)
    if clip_weights is not None and clip_weights > 0:
        w = jnp.clip(w, -clip_weights, clip_weights)
    return w, new_n


@register("rmspropalex_update", num_outputs=4, dynamic_attrs=_DYN)
def _rmspropalex_update(weight, grad, n, g_state, delta, lr=0.001, gamma1=0.9,
                        gamma2=0.9, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                        clip_gradient=-1.0, clip_weights=-1.0):
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient) + wd * weight
    new_n = gamma1 * n + (1 - gamma1) * jnp.square(g)
    new_g = gamma1 * g_state + (1 - gamma1) * g
    new_delta = gamma2 * delta - lr * g / jnp.sqrt(new_n - jnp.square(new_g) + epsilon)
    w = weight + new_delta
    if clip_weights is not None and clip_weights > 0:
        w = jnp.clip(w, -clip_weights, clip_weights)
    return w, new_n, new_g, new_delta


@register("ftrl_update", num_outputs=3, dynamic_attrs=_DYN)
def _ftrl_update(weight, grad, z, n, lr=0.1, lamda1=0.01, beta=1.0, wd=0.0,
                 rescale_grad=1.0, clip_gradient=-1.0):
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient)
    new_n = n + jnp.square(g)
    sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
    new_z = z + g - sigma * weight
    w = jnp.where(
        jnp.abs(new_z) <= lamda1,
        jnp.zeros_like(weight),
        -(new_z - jnp.sign(new_z) * lamda1)
        / ((beta + jnp.sqrt(new_n)) / lr + wd))
    return w, new_z, new_n


@register("signsgd_update", dynamic_attrs=_DYN)
def _signsgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
                    clip_gradient=-1.0):
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient)
    return weight - lr * (jnp.sign(g) + wd * weight)


@register("signum_update", num_outputs=2, dynamic_attrs=_DYN)
def _signum_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, wd_lh=0.0):
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient)
    new_mom = momentum * mom - (1 - momentum) * g
    w = (1 - lr * wd_lh) * weight + lr * jnp.sign(new_mom) \
        - lr * wd * weight
    return w, new_mom


@register("lamb_update_phase1", dynamic_attrs=_DYN)
def _lamb_update_phase1(weight, grad, mean, var, beta1=0.9, beta2=0.999,
                        epsilon=1e-6, t=1, bias_correction=True, wd=0.0,
                        rescale_grad=1.0, clip_gradient=-1.0):
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient)
    m = beta1 * mean + (1 - beta1) * g
    v = beta2 * var + (1 - beta2) * jnp.square(g)
    if bias_correction:
        mh = m / (1 - beta1 ** t)
        vh = v / (1 - beta2 ** t)
    else:
        mh, vh = m, v
    return mh / (jnp.sqrt(vh) + epsilon) + wd * weight


@register("lamb_update_phase2", dynamic_attrs=_DYN)
def _lamb_update_phase2(weight, g_update, r1, r2, lr=0.01,
                        lower_bound=-1.0, upper_bound=-1.0):
    jnp = _jnp()
    r1v = r1.reshape(())
    r2v = r2.reshape(())
    if lower_bound is not None and lower_bound >= 0:
        r1v = jnp.maximum(r1v, lower_bound)
    if upper_bound is not None and upper_bound >= 0:
        r1v = jnp.minimum(r1v, upper_bound)
    ratio = jnp.where(jnp.logical_and(r1v > 0, r2v > 0), r1v / r2v, 1.0)
    return weight - lr * ratio * g_update


@register("lamb_full_update", num_outputs=3, dynamic_attrs=_DYN)
def _lamb_full_update(weight, grad, mean, var, lr=0.01, beta1=0.9, beta2=0.999,
                      epsilon=1e-6, t=1, bias_correction=True, wd=0.0,
                      rescale_grad=1.0, clip_gradient=-1.0,
                      lower_bound=-1.0, upper_bound=-1.0):
    """Convenience fusion of phase1+phase2 (one XLA kernel per param)."""
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient)
    m = beta1 * mean + (1 - beta1) * g
    v = beta2 * var + (1 - beta2) * jnp.square(g)
    if bias_correction:
        mh = m / (1 - beta1 ** t)
        vh = v / (1 - beta2 ** t)
    else:
        mh, vh = m, v
    upd = mh / (jnp.sqrt(vh) + epsilon) + wd * weight
    r1 = jnp.sqrt(jnp.sum(jnp.square(weight)))
    if lower_bound is not None and lower_bound >= 0:
        r1 = jnp.maximum(r1, lower_bound)
    if upper_bound is not None and upper_bound >= 0:
        r1 = jnp.minimum(r1, upper_bound)
    r2 = jnp.sqrt(jnp.sum(jnp.square(upd)))
    ratio = jnp.where(jnp.logical_and(r1 > 0, r2 > 0), r1 / r2, 1.0)
    return weight - lr * ratio * upd, m, v


@register("adagrad_update", num_outputs=2, dynamic_attrs=_DYN)
def _adagrad_update(weight, grad, history, lr=0.01, epsilon=1e-7, wd=0.0,
                    rescale_grad=1.0, clip_gradient=-1.0):
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient)
    new_h = history + jnp.square(g)
    w = weight - lr * (g / jnp.sqrt(new_h + epsilon) + wd * weight)
    return w, new_h


@register("adadelta_update", num_outputs=3, dynamic_attrs=_DYN)
def _adadelta_update(weight, grad, acc_g, acc_delta, rho=0.9, epsilon=1e-5,
                     wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient)
    new_acc_g = rho * acc_g + (1 - rho) * jnp.square(g)
    delta = jnp.sqrt(acc_delta + epsilon) / jnp.sqrt(new_acc_g + epsilon) * g
    new_acc_delta = rho * acc_delta + (1 - rho) * jnp.square(delta)
    return weight - delta - wd * weight, new_acc_g, new_acc_delta


# ---------------------------------------------------------------------------
# multi-tensor fused updates (reference src/operator/optimizer_op.cc
# multi_sgd_update / multi_sgd_mom_update / multi_mp_sgd_*).  One registry dispatch updates N params: the per-param host
# dispatch loop becomes a single jitted XLA program.  Per-param lr/wd ride
# as INPUT vectors (traced, so schedules never recompile); the weight/grad
# (/mom/w32) tensors arrive interleaved like the reference kernels.
# ---------------------------------------------------------------------------


@register("multi_sgd_update", num_outputs=-1,
          dynamic_attrs=("rescale_grad",))
def _multi_sgd_update(*args, rescale_grad=1.0, clip_gradient=-1.0,
                      num_weights=0):
    """args = w0, g0, w1, g1, ..., lrs, wds -> (w0', w1', ...)."""
    lrs, wds = args[-2], args[-1]
    wg = args[:-2]
    n = int(num_weights) or len(wg) // 2
    outs = []
    for i in range(n):
        w, g = wg[2 * i], wg[2 * i + 1]
        g = _prep(g, rescale_grad, clip_gradient)
        # the f32 lr/wd vectors promote half dtypes; cast back so the
        # weight dtype (and checkpoints) match the per-param path
        outs.append((w - lrs[i] * (g + wds[i] * w)).astype(w.dtype))
    return tuple(outs)


@register("multi_sgd_mom_update", num_outputs=-1,
          dynamic_attrs=("rescale_grad", "momentum"))
def _multi_sgd_mom_update(*args, momentum=0.0, rescale_grad=1.0,
                          clip_gradient=-1.0, num_weights=0):
    """args = w0, g0, m0, w1, g1, m1, ..., lrs, wds ->
    (w0', m0', w1', m1', ...)."""
    lrs, wds = args[-2], args[-1]
    wgm = args[:-2]
    n = int(num_weights) or len(wgm) // 3
    outs = []
    for i in range(n):
        w, g, m = wgm[3 * i], wgm[3 * i + 1], wgm[3 * i + 2]
        g = _prep(g, rescale_grad, clip_gradient)
        new_m = (momentum * m - lrs[i] * (g + wds[i] * w)).astype(m.dtype)
        outs.extend(((w + new_m).astype(w.dtype), new_m))
    return tuple(outs)


@register("multi_mp_sgd_update", num_outputs=-1,
          dynamic_attrs=("rescale_grad",))
def _multi_mp_sgd_update(*args, rescale_grad=1.0, clip_gradient=-1.0,
                         num_weights=0):
    """args = w0, g0, w32_0, ... , lrs, wds -> (w0', w32_0', ...); the
    update runs in f32 master weights and casts back (reference mp_sgd)."""
    lrs, wds = args[-2], args[-1]
    wgw = args[:-2]
    n = int(num_weights) or len(wgw) // 3
    outs = []
    for i in range(n):
        w, g, w32 = wgw[3 * i], wgw[3 * i + 1], wgw[3 * i + 2]
        g32 = _prep(g.astype(w32.dtype), rescale_grad, clip_gradient)
        new_w32 = w32 - lrs[i] * (g32 + wds[i] * w32)
        outs.extend((new_w32.astype(w.dtype), new_w32))
    return tuple(outs)


@register("multi_mp_sgd_mom_update", num_outputs=-1,
          dynamic_attrs=("rescale_grad", "momentum"))
def _multi_mp_sgd_mom_update(*args, momentum=0.0, rescale_grad=1.0,
                             clip_gradient=-1.0, num_weights=0):
    """args = w0, g0, m0, w32_0, ..., lrs, wds ->
    (w0', m0', w32_0', ...)."""
    lrs, wds = args[-2], args[-1]
    wgmw = args[:-2]
    n = int(num_weights) or len(wgmw) // 4
    outs = []
    for i in range(n):
        w, g, m, w32 = (wgmw[4 * i], wgmw[4 * i + 1], wgmw[4 * i + 2],
                        wgmw[4 * i + 3])
        g32 = _prep(g.astype(w32.dtype), rescale_grad, clip_gradient)
        new_m = momentum * m - lrs[i] * (g32 + wds[i] * w32)
        new_w32 = w32 + new_m
        outs.extend((new_w32.astype(w.dtype), new_m, new_w32))
    return tuple(outs)


@register("lars_update", num_outputs=2, dynamic_attrs=_DYN)
def _lars_update(weight, grad, mom, lr=0.01, momentum=0.9, eta=0.001,
                 wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                 epsilon=1e-8):
    """LARS layer-wise adaptive update (reference optimizer_op.cc
    lars_* / multi_lars: You et al. 2017): the layer's lr scales by the
    trust ratio ||w|| / (||g|| + wd*||w|| + eps); zero norms fall back to
    ratio 1 (the reference guard)."""
    jnp = _jnp()
    g = _prep(grad, rescale_grad, clip_gradient)
    w_norm = jnp.sqrt(jnp.sum(weight.astype(jnp.float32) ** 2))
    g_norm = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
    denom = g_norm + wd * w_norm + epsilon
    # zero norms fall back to the PLAIN lr (reference guard: lars factor
    # 1.0 means lr itself; eta only scales inside the trust ratio)
    lr_eff = jnp.where((w_norm > 0) & (g_norm > 0),
                       lr * eta * (w_norm / denom),
                       lr).astype(jnp.float32)
    new_mom = momentum * mom + lr_eff * (g + wd * weight)
    return (weight - new_mom).astype(weight.dtype), \
        new_mom.astype(mom.dtype)
