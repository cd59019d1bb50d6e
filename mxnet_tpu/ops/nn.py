"""Neural-network primitive operators.

Rebuild of src/operator/nn/* (convolution.cc, fully_connected.cc, pooling.cc,
activation.cc, batch_norm.cc, layer_norm.cc, dropout.cc, softmax.cc, rnn.cc …).
The reference dispatches these to cuDNN/oneDNN kernels; here each lowers to
XLA HLO (conv_general_dilated / reduce_window / dot_general) which XLA tiles
onto the TPU MXU — the cuDNN-algo-search role is played by XLA autotuning.
Layouts follow the reference default NC(D)HW; kernels OIHW.
"""

from __future__ import annotations

import numpy as _np

from .registry import register


def _jnp():
    import jax.numpy as jnp
    return jnp


def _lax():
    import jax.lax as lax
    return lax


# -- dense ------------------------------------------------------------------

@register("FullyConnected")
def _fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                     flatten=True):  # noqa: ARG001
    jnp = _jnp()
    x = data.reshape(data.shape[0], -1) if flatten else data
    out = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# -- convolution ------------------------------------------------------------

_CONV_DIMS = {1: ("NCW", "OIW", "NCW"), 2: ("NCHW", "OIHW", "NCHW"),
              3: ("NCDHW", "OIDHW", "NCDHW")}


def _norm_tuple(v, n, default):
    if not v:
        return (default,) * n
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


@register("Convolution")
def _convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                 pad=(), num_filter=0, num_group=1, no_bias=False,
                 layout=None, workspace=0, cudnn_tune=None, cudnn_off=False):  # noqa: ARG001
    """reference src/operator/nn/convolution.cc — NCHW/OIHW conv."""
    lax = _lax()
    n = len(kernel) if kernel else data.ndim - 2
    stride = _norm_tuple(stride, n, 1)
    dilate = _norm_tuple(dilate, n, 1)
    pad = _norm_tuple(pad, n, 0)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _CONV_DIMS[n])
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * n)
    return out


@register("Deconvolution")
def _deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                   pad=(), adj=(), num_filter=0, num_group=1, no_bias=True,
                   layout=None, target_shape=None, workspace=0,
                   cudnn_tune=None, cudnn_off=False):  # noqa: ARG001
    """Transposed convolution (gradient of Convolution wrt data)."""
    lax = _lax()
    jnp = _jnp()
    n = len(kernel) if kernel else data.ndim - 2
    stride = _norm_tuple(stride, n, 1)
    dilate = _norm_tuple(dilate, n, 1)
    pad = _norm_tuple(pad, n, 0)
    adj = _norm_tuple(adj, n, 0)
    # weight layout for Deconvolution is (in_c, out_c/groups, *k)
    dn = lax.conv_dimension_numbers(
        data.shape, (weight.shape[1] * num_group, weight.shape[0] // num_group)
        + weight.shape[2:], _CONV_DIMS[n])
    # transposed conv = conv with lhs dilation, flipped kernel, swapped io
    w = jnp.swapaxes(weight, 0, 1)
    w = jnp.flip(w, axis=tuple(range(2, 2 + n)))
    if num_group > 1:
        # regroup (out_c/g, in_c, *k) for grouped transposed conv
        ic = data.shape[1]
        w = weight.reshape((num_group, ic // num_group) + weight.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((-1, ic // num_group) + weight.shape[2:])
        w = jnp.flip(w, axis=tuple(range(2, 2 + n)))
    padding = [(dilate[i] * (kernel[i] - 1) - pad[i],
                dilate[i] * (kernel[i] - 1) - pad[i] + adj[i])
               for i in range(n)]
    return lax.conv_general_dilated(
        data, w, window_strides=(1,) * n, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)


# -- pooling ----------------------------------------------------------------

@register("Pooling")
def _pooling(data, kernel=(), pool_type="max", global_pool=False,
             stride=(), pad=(), pooling_convention="valid",
             count_include_pad=True, cudnn_off=False, layout=None,
             p_value=2):  # noqa: ARG001
    """reference src/operator/nn/pooling.cc — max/avg/sum/lp over NC(D)HW."""
    lax = _lax()
    jnp = _jnp()
    n = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * n
        pad = (0,) * n
    kernel = _norm_tuple(kernel, n, 1)
    stride = _norm_tuple(stride, n, 1)
    pad = _norm_tuple(pad, n, 0)
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    hi_pad = list(pad)
    if pooling_convention == "full":
        # ceil output sizes (reference PoolingParam::pooling_convention):
        # grow the high-side padding so reduce_window's floor matches ceil
        for i in range(n):
            span = data.shape[2 + i] + 2 * pad[i] - kernel[i]
            rem = span % stride[i]
            if rem:
                hi_pad[i] = pad[i] + (stride[i] - rem)
    padding = ((0, 0), (0, 0)) + tuple(
        (p, hp) for p, hp in zip(pad, hi_pad))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return s / cnt
    if pool_type == "lp":
        p = float(p_value)
        s = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add, window,
                              strides, padding)
        return s ** (1.0 / p)
    raise ValueError(f"unknown pool_type {pool_type}")


# -- activations ------------------------------------------------------------

@register("Activation")
def _activation(data, act_type="relu"):
    import jax
    jnp = _jnp()
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jnp.logaddexp(data, 0.0)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    raise ValueError(f"unknown act_type {act_type}")


@register("LeakyReLU")
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334,
                approximate=None):  # noqa: ARG001
    import jax
    jnp = _jnp()
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) \
            if gamma is not None and gamma.ndim == 1 and data.ndim > 2 else gamma
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        return jax.nn.selu(data)
    if act_type == "gelu":
        if approximate is None:
            from .elemwise import _gelu_tanh_default
            approximate = _gelu_tanh_default()
        return jax.nn.gelu(data, approximate=approximate)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, mid * data)
    raise ValueError(f"unknown act_type {act_type}")


# -- softmax family ---------------------------------------------------------

@register("softmax")
def _softmax(data, length=None, axis=-1, temperature=None, dtype=None,
             use_length=False):
    import jax
    jnp = _jnp()
    x = data / temperature if temperature else data
    if use_length and length is not None:
        steps = jnp.arange(data.shape[axis])
        shape = [1] * data.ndim
        shape[axis] = -1
        mask = steps.reshape(shape) < length.reshape(
            length.shape + (1,) * (data.ndim - length.ndim))
        x = jnp.where(mask, x, -jnp.inf)
    r = jax.nn.softmax(x, axis=axis)
    if use_length and length is not None:
        r = jnp.where(jnp.isnan(r), 0.0, r)
    return r.astype(dtype) if dtype else r


@register("log_softmax")
def _log_softmax(data, axis=-1, temperature=None, dtype=None):
    import jax
    x = data / temperature if temperature else data
    r = jax.nn.log_softmax(x, axis=axis)
    return r.astype(dtype) if dtype else r


@register("softmin")
def _softmin(data, axis=-1, temperature=None, dtype=None):
    import jax
    x = -data
    if temperature:
        x = x / temperature
    r = jax.nn.softmax(x, axis=axis)
    return r.astype(dtype) if dtype else r


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance"):
    import jax
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label, per_row=False):
    """Sum over the rows of ``logsumexp(data) - data[label]``; with
    ``per_row`` each row's value instead, ``(rows,)`` in float32 (or wider:
    the type the reductions run in), for a loss that weighs its rows.

    reference src/operator/loss_binary_op.cc.  custom_vjp, so that no array
    of ``data``'s shape outlives the forward except ``data`` itself: the
    residuals are ``data`` as it arrived, the per-row logsumexp and the
    label, and the backward recomputes the softmax from them and writes
    ``(softmax - onehot) * g`` in one elementwise pass (``g`` a scalar, or
    with ``per_row`` a row's own cotangent).  The label's place is an iota
    comparison (no scatter, no one-hot array); reductions run in float32
    for narrower ``data``.  ``label`` holds class indices in [0, classes),
    integer or float, and gets no gradient.
    """
    import jax
    jnp = _jnp()
    lax = _lax()
    acc = jnp.promote_types(data.dtype, jnp.float32)

    def at_label(l, shape):
        classes = lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
        return classes == l.astype(jnp.int32).reshape(-1, 1)

    @jax.custom_vjp
    def f(x, l):
        return f_fwd(x, l)[0]

    def f_fwd(x, l):
        xf = x.astype(acc)
        lse = jax.nn.logsumexp(xf, axis=-1, keepdims=True)
        picked = jnp.sum(jnp.where(at_label(l, x.shape), xf, 0), axis=-1,
                         keepdims=True)
        if per_row:
            return (lse - picked)[:, 0], (x, lse, l)
        return jnp.sum(lse - picked).astype(x.dtype), (x, lse, l)

    def f_bwd(res, g):
        x, lse, l = res
        p = jnp.exp(x.astype(acc) - lse)
        g = g.astype(acc)
        if per_row:
            g = g.reshape(-1, 1)
        grad = jnp.where(at_label(l, x.shape), p - 1, p) * g
        return grad.astype(x.dtype), None

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


def _softmax_output_fwd(data, label, grad_scale, ignore_label,
                        use_ignore, multi_output, normalization,
                        out_grad_used, smooth_alpha):
    import jax
    return jax.nn.softmax(data, axis=-1)


@register("SoftmaxOutput")
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):  # noqa: ARG001
    """Legacy classifier head: forward = softmax; backward = p - onehot(label).

    reference src/operator/softmax_output.cc.  Implemented with custom_vjp so
    the fused backward matches reference semantics (incl. grad_scale and
    ignore_label masking).
    """
    import jax
    jnp = _jnp()

    @jax.custom_vjp
    def f(d, l):
        return jax.nn.softmax(d, axis=-1)

    def f_fwd(d, l):
        p = jax.nn.softmax(d, axis=-1)
        return p, (p, l)

    def f_bwd(res, g):  # noqa: ARG001 - out-grad ignored (loss head)
        p, l = res
        oh = jax.nn.one_hot(l.astype(jnp.int32), p.shape[-1], dtype=p.dtype)
        if smooth_alpha:
            oh = oh * (1 - smooth_alpha) + smooth_alpha / p.shape[-1]
        grad = p - oh
        if use_ignore:
            mask = (l != ignore_label).astype(p.dtype)
            grad = grad * mask[..., None]
        if normalization == "batch":
            grad = grad / p.shape[0]
        elif normalization == "valid" and use_ignore:
            n = jnp.maximum(jnp.sum(l != ignore_label), 1).astype(p.dtype)
            grad = grad / n
        return grad * grad_scale, jnp.zeros_like(l)

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


# -- normalization ----------------------------------------------------------

@register("BatchNorm", num_outputs=3, visible_outputs=1,
          mutate_inputs=((1, 3), (2, 4)), wrap_train="_training")
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False,
                _training=False):  # noqa: ARG001
    """reference src/operator/nn/batch_norm.cc.  Outputs (out, new_moving_mean,
    new_moving_var); the moving stats write back into inputs 3/4 (the aux
    states) — FMutateInputs parity."""
    jnp = _jnp()
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    red = tuple(i for i in range(data.ndim) if i != axis)
    shape = [1] * data.ndim
    shape[axis] = -1
    # normalize in float32 but return the INPUT dtype (cuDNN BN contract:
    # low-precision data + fp32 stats, reference cudnn_batch_norm.cc) —
    # mixed bf16-data/f32-gamma networks stay bf16 end to end
    in_dtype = data.dtype
    # upcast only narrower-than-f32 dtypes; f32/f64 keep full precision
    compute = jnp.float32 if in_dtype.itemsize < 4 else in_dtype
    xf = data.astype(compute)
    if _training and not use_global_stats:
        mean = jnp.mean(xf, axis=red)
        var = jnp.var(xf, axis=red)
        one_m = jnp.asarray(1 - momentum, moving_mean.dtype)
        new_mm = moving_mean * momentum + mean.astype(moving_mean.dtype) * one_m
        new_mv = moving_var * momentum + var.astype(moving_var.dtype) * one_m
    else:
        mean, var = moving_mean.astype(compute), moving_var.astype(compute)
        new_mm, new_mv = moving_mean, moving_var
    inv = 1.0 / jnp.sqrt(var + eps)
    out = (xf - mean.reshape(shape)) * inv.reshape(shape) \
        * g.astype(compute).reshape(shape) \
        + beta.astype(compute).reshape(shape)
    return out.astype(in_dtype), new_mm, new_mv


@register("LayerNorm")
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):  # noqa: ARG001
    jnp = _jnp()
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    shape = [1] * data.ndim
    shape[axis] = -1
    out = (data - mean) / jnp.sqrt(var + eps)
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("GroupNorm")
def _group_norm(data, gamma, beta, num_groups=1, eps=1e-5,
                output_mean_var=False):  # noqa: ARG001
    jnp = _jnp()
    n, c = data.shape[0], data.shape[1]
    x = data.reshape((n, num_groups, c // num_groups) + data.shape[2:])
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = (x - mean) / jnp.sqrt(var + eps)
    x = x.reshape(data.shape)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register("InstanceNorm")
def _instance_norm(data, gamma, beta, eps=1e-3):
    jnp = _jnp()
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) / jnp.sqrt(var + eps) * gamma.reshape(shape) \
        + beta.reshape(shape)


@register("L2Normalization")
def _l2_normalization(data, eps=1e-10, mode="instance"):
    jnp = _jnp()
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        kd = True
    elif mode == "channel":
        red, kd = (1,), True
    else:  # spatial
        red, kd = tuple(range(2, data.ndim)), True
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=kd) + eps)
    return data / norm


@register("LRN")
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    jnp = _jnp()
    sq = jnp.square(data)
    half = nsize // 2
    pad = [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2)
    sqp = jnp.pad(sq, pad)
    acc = jnp.zeros_like(data)
    for i in range(nsize):
        acc = acc + sqp[:, i:i + data.shape[1]]
    return data / jnp.power(knorm + alpha * acc / nsize, beta)


# -- dropout ----------------------------------------------------------------

@register("Dropout", wrap_key="_key", wrap_train="_training")
def _dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False,
             _key=None, _training=False):  # noqa: ARG001
    import jax
    jnp = _jnp()
    if (not _training and mode != "always") or p <= 0:
        return data
    shape = list(data.shape)
    if axes:
        for a in axes:
            shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(_key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# -- fused RNN (reference src/operator/rnn.cc; cuDNN-packed params) ---------

def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def _unpack_rnn_params(params, mode, num_layers, input_size, hidden, bidir):
    """Unpack the flat cuDNN-style parameter vector: all weights (layer-major,
    direction, i2h then h2h), then all biases (same order, i2h then h2h)."""
    jnp = _jnp()
    ng = _gates(mode)
    d = 2 if bidir else 1
    layers = []
    off = 0
    for l in range(num_layers):
        in_sz = input_size if l == 0 else hidden * d
        per_dir = []
        for _ in range(d):
            wi = params[off:off + ng * hidden * in_sz].reshape(ng * hidden, in_sz)
            off += ng * hidden * in_sz
            wh = params[off:off + ng * hidden * hidden].reshape(ng * hidden, hidden)
            off += ng * hidden * hidden
            per_dir.append([wi, wh, None, None])
        layers.append(per_dir)
    for l in range(num_layers):
        for dd in range(d):
            bi = params[off:off + ng * hidden]
            off += ng * hidden
            bh = params[off:off + ng * hidden]
            off += ng * hidden
            layers[l][dd][2] = bi
            layers[l][dd][3] = bh
    return layers


def _cell_step(mode, hidden):
    jnp = _jnp()
    import jax

    if mode == "lstm":
        def step(carry, xw, wh, bh):
            h, c = carry
            g = xw + jnp.matmul(h, wh.T) + bh
            i, f, gg, o = jnp.split(g, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            gg = jnp.tanh(gg)
            o = jax.nn.sigmoid(o)
            c2 = f * c + i * gg
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2
    elif mode == "gru":
        def step(carry, xw, wh, bh):
            h = carry[0]
            hw = jnp.matmul(h, wh.T)
            xr, xz, xn = jnp.split(xw, 3, axis=-1)
            hr, hz, hn = jnp.split(hw + bh, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h2 = (1 - z) * n + z * h
            return (h2,), h2
    else:
        act = jnp.tanh if mode == "rnn_tanh" else (lambda v: jnp.maximum(v, 0))

        def step(carry, xw, wh, bh):
            h = carry[0]
            h2 = act(xw + jnp.matmul(h, wh.T) + bh)
            return (h2,), h2
    return step


@register("RNN", num_outputs=-1, wrap_key="_key", wrap_train="_training")
def _rnn(data, parameters, state, state_cell=None, state_size=0,
         num_layers=1, mode="lstm", bidirectional=False, p=0.0,
         state_outputs=False, projection_size=None, use_sequence_length=False,
         sequence_length=None, lstm_state_clip_min=None,
         lstm_state_clip_max=None, _key=None, _training=False):  # noqa: ARG001
    """Fused multi-layer RNN, layout TNC (seq, batch, feature) like the
    reference default.  lax.scan over time keeps the whole stack one XLA
    computation (the TPU analog of the cuDNN fused kernel)."""
    import jax
    jnp = _jnp()
    lax = _lax()
    T, N, I = data.shape
    H = state_size
    d = 2 if bidirectional else 1
    layers = _unpack_rnn_params(parameters, mode, num_layers, I, H, bidirectional)
    step = _cell_step(mode, H)

    # state layout: (num_layers*d, N, H)
    hs = state
    cs = state_cell if mode == "lstm" else None
    out = data
    h_finals, c_finals = [], []
    for l, per_dir in enumerate(layers):
        outs_dir = []
        for dd, (wi, wh, bi, bh) in enumerate(per_dir):
            idx = l * d + dd
            h0 = hs[idx]
            carry = (h0, cs[idx]) if mode == "lstm" else (h0,)
            xin = out if dd == 0 else None
            seq = out if dd == 0 else jnp.flip(out, axis=0)
            xw = jnp.einsum("tni,gi->tng", seq, wi) + bi

            def body(c, x, wh=wh, bh=bh):
                return step(c, x, wh, bh)

            carry_f, ys = lax.scan(body, carry, xw)
            if dd == 1:
                ys = jnp.flip(ys, axis=0)
            outs_dir.append(ys)
            h_finals.append(carry_f[0])
            if mode == "lstm":
                c_finals.append(carry_f[1])
        out = outs_dir[0] if d == 1 else jnp.concatenate(outs_dir, axis=-1)
        if p > 0 and _training and l < num_layers - 1 and _key is not None:
            sub = jax.random.fold_in(_key, l)
            mask = jax.random.bernoulli(sub, 1 - p, out.shape).astype(out.dtype)
            out = out * mask / (1 - p)
    results = [out]
    if state_outputs:
        results.append(jnp.stack(h_finals, axis=0))
        if mode == "lstm":
            results.append(jnp.stack(c_finals, axis=0))
    return results if len(results) > 1 else results[0]


# -- argument-shape inference rules (FInferShape back-propagation role) -----
# Used by Symbol.infer_shape/simple_bind: given the data shape, derive the
# parameter shapes the same way the reference's InferShape pass does.

from .registry import get as _get_op
import numpy as _np_mod


def _prod(xs):
    p = 1
    for x in xs:
        p *= x
    return p


def _fc_infer(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    nh = attrs.get("num_hidden", 0)
    flat = attrs.get("flatten", True)
    in_units = _prod(data[1:]) if flat else data[-1]
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (nh, in_units)
    if len(out) > 2 and out[2] is None and not attrs.get("no_bias", False):
        out[2] = (nh,)
    return out


_get_op("FullyConnected").infer_args = _fc_infer


def _conv_infer(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    k = tuple(attrs.get("kernel", ()))
    nf = attrs.get("num_filter", 0)
    g = attrs.get("num_group", 1)
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (nf, data[1] // g) + k
    if len(out) > 2 and out[2] is None and not attrs.get("no_bias", False):
        out[2] = (nf,)
    return out


_get_op("Convolution").infer_args = _conv_infer


def _deconv_infer(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    k = tuple(attrs.get("kernel", ()))
    nf = attrs.get("num_filter", 0)
    g = attrs.get("num_group", 1)
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (data[1], nf // g) + k
    if len(out) > 2 and out[2] is None and not attrs.get("no_bias", True):
        out[2] = (nf,)
    return out


_get_op("Deconvolution").infer_args = _deconv_infer


def _bn_infer(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    c = data[attrs.get("axis", 1)]
    return [shapes[0]] + [(c,) if s is None else s for s in shapes[1:]]


_get_op("BatchNorm").infer_args = _bn_infer


def _chan_infer(shapes, attrs):  # noqa: ARG001 - LayerNorm/InstanceNorm/GroupNorm
    data = shapes[0]
    if data is None:
        return shapes
    axis = attrs.get("axis", -1)
    c = data[axis]
    return [shapes[0]] + [(c,) if s is None else s for s in shapes[1:]]


_get_op("LayerNorm").infer_args = _chan_infer
_get_op("GroupNorm").infer_args = \
    lambda shapes, attrs: [shapes[0]] + [
        (shapes[0][1],) if s is None else s for s in shapes[1:]] \
    if shapes[0] is not None else shapes
_get_op("InstanceNorm").infer_args = _get_op("GroupNorm").infer_args


def _embedding_infer(shapes, attrs):
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (attrs.get("input_dim", 0), attrs.get("output_dim", 0))
    return out


_get_op("Embedding").infer_args = _embedding_infer


def _rnn_infer(shapes, attrs):
    data = shapes[0]
    if data is None:
        return shapes
    mode = attrs.get("mode", "lstm")
    H = attrs.get("state_size", 0)
    L = attrs.get("num_layers", 1)
    d = 2 if attrs.get("bidirectional", False) else 1
    ng = _gates(mode)
    I = data[2]
    size = 0
    for l in range(L):
        in_sz = I if l == 0 else H * d
        size += d * (ng * H * in_sz + ng * H * H + 2 * ng * H)
    out = list(shapes)
    if len(out) > 1 and out[1] is None:
        out[1] = (size,)
    N = data[1]
    for i in (2, 3):
        if len(out) > i and out[i] is None:
            out[i] = (L * d, N, H)
    return out


_get_op("RNN").infer_args = _rnn_infer


# -- declared input names (reference nnvm FListInputNames): symbol
# composition auto-creates "<name>_<input>" variables for inputs not passed
# (src/operator/nn/fully_connected.cc lists data/weight/bias etc.) ---------

def _wire_inputs(opname, names, aux=(), omit=None):
    op = _get_op(opname)
    op.input_names = tuple(names)
    op.aux_names = frozenset(aux)
    op.omit_inputs = omit


_wire_inputs("FullyConnected", ("data", "weight", "bias"),
             omit=lambda attrs: {"bias"} if attrs.get("no_bias") else set())
_wire_inputs("Convolution", ("data", "weight", "bias"),
             omit=lambda attrs: {"bias"} if attrs.get("no_bias") else set())
_wire_inputs("Deconvolution", ("data", "weight", "bias"),
             omit=lambda attrs: {"bias"}
             if attrs.get("no_bias", True) else set())
_wire_inputs("BatchNorm",
             ("data", "gamma", "beta", "moving_mean", "moving_var"),
             aux=("moving_mean", "moving_var"))
_wire_inputs("LayerNorm", ("data", "gamma", "beta"))
_wire_inputs("InstanceNorm", ("data", "gamma", "beta"))
_wire_inputs("GroupNorm", ("data", "gamma", "beta"))
_wire_inputs("Embedding", ("data", "weight"))
_wire_inputs("RNN", ("data", "parameters", "state", "state_cell"),
             omit=lambda attrs: set()
             if attrs.get("mode", "lstm") == "lstm" else {"state_cell"})
_wire_inputs("SoftmaxOutput", ("data", "label"))


# -- Module-era loss heads (reference src/operator/regression_output.*,
# svm_output.*, center_loss — SURVEY §2.2 misc top-level) -------------------
#
# All three regression heads share the reference contract: forward is the
# prediction (identity / sigmoid), backward is the LOSS gradient
# BackwardOp(out, label) * grad_scale / num_output injected via custom_vjp
# (the head IS the loss — incoming out-grad is ignored), where num_output
# is the per-sample output count (reference regression_output-inl.h divides
# the gradient by data.Size()/batch).

def _regression_head(fwd_fn, bwd_fn):
    import jax
    jnp = _jnp()

    def head(data, label, grad_scale=1.0):
        @jax.custom_vjp
        def f(d, l):
            return fwd_fn(d)

        def f_fwd(d, l):
            out = fwd_fn(d)
            return out, (out, d, l)

        def f_bwd(res, g):  # noqa: ARG001 — loss head, out-grad ignored
            out, d, l = res
            num_output = max(int(_np.prod(d.shape[1:])), 1) if d.ndim > 1 \
                else 1
            grad = bwd_fn(out, l.reshape(d.shape).astype(out.dtype))
            return (grad * (grad_scale / num_output)).astype(d.dtype), \
                jnp.zeros_like(l)

        f.defvjp(f_fwd, f_bwd)
        return f(data, label)
    return head


@register("LinearRegressionOutput")
def _linear_regression_output(data, label, grad_scale=1.0):
    """L2 head: forward = identity, grad = (out - label).
    reference src/operator/regression_output.cc (LinearRegressionOutput)."""
    return _regression_head(lambda d: d, lambda o, l: o - l)(
        data, label, grad_scale)


@register("MAERegressionOutput")
def _mae_regression_output(data, label, grad_scale=1.0):
    """L1 head: forward = identity, grad = sign(out - label).
    reference src/operator/regression_output.cc (MAERegressionOutput)."""
    jnp = _jnp()
    return _regression_head(lambda d: d, lambda o, l: jnp.sign(o - l))(
        data, label, grad_scale)


@register("LogisticRegressionOutput")
def _logistic_regression_output(data, label, grad_scale=1.0):
    """Sigmoid CE head: forward = sigmoid, grad = (sigmoid(out) - label)
    (the cross-entropy-through-sigmoid gradient).
    reference src/operator/regression_output.cc (LogisticRegressionOutput)."""
    import jax
    return _regression_head(jax.nn.sigmoid, lambda o, l: o - l)(
        data, label, grad_scale)


@register("center_loss", num_outputs=2, visible_outputs=1,
          mutate_inputs=((1, 2),), wrap_train="_training")
def _center_loss(data, label, center, grad_scale=1.0, alpha=0.1,
                 _training=False):
    """Center loss (SURVEY §2.2 misc `center_loss`): per-sample
    0.5*||f_i - c_{y_i}||^2 * grad_scale.  The class centers are an AUX
    state (BatchNorm-style mutate-input): during training each touched
    center moves toward its class mean, c_j += alpha * sum(diff_j)/(1+n_j)
    — centers take NO loss gradient (stop_gradient), matching the
    reference's update-rule-not-SGD contract."""
    import jax
    jnp = _jnp()
    li = label.astype(jnp.int32).reshape(-1)
    c = jax.lax.stop_gradient(center)
    diff = data - c[li]                                    # (B, D)
    loss = 0.5 * jnp.sum(jnp.square(diff), axis=1) * grad_scale
    if _training:
        n = jnp.zeros((center.shape[0],), data.dtype).at[li].add(1.0)
        s = jnp.zeros_like(c).at[li].add(diff)
        new_center = c + alpha * s / (1.0 + n)[:, None]
    else:
        new_center = c
    return loss, new_center.astype(center.dtype)


def _im2col_patches(data, kernel, stride, dilate, pad):
    import jax
    nspatial = len(kernel)
    stride = tuple(stride) if stride else (1,) * nspatial
    dilate = tuple(dilate) if dilate else (1,) * nspatial
    pad = tuple(pad) if pad else (0,) * nspatial
    # conv_general_dilated_patches emits channel-major patch channels
    # (c, k1, k2, ...) — the reference im2col.h layout
    spec = "NCHW" if nspatial == 2 else ("NCW" if nspatial == 1 else "NCDHW")
    out = jax.lax.conv_general_dilated_patches(
        data, filter_shape=kernel,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=(spec, spec.replace("N", "O").replace("C", "I"),
                           spec))
    n, pc = out.shape[0], out.shape[1]
    return out.reshape(n, pc, -1)


@register("im2col")
def _im2col(data, kernel, stride=(), dilate=(), pad=()):
    """Unfold conv patches to a (N, C*prod(kernel), n_locations) matrix —
    reference src/operator/nn/im2col.h (the lowering both conv paths
    share upstream; first-class op here, XLA owns the conv lowering)."""
    return _im2col_patches(data, tuple(kernel), stride, dilate, pad)


@register("col2im")
def _col2im(data, output_size, kernel, stride=(), dilate=(), pad=()):
    """Fold a column matrix back to an image, scatter-ADDING overlapping
    patches — exactly im2col's transpose, so it is computed as im2col's
    VJP (reference src/operator/nn/im2col.h col2im)."""
    import jax
    jnp = _jnp()
    kernel = tuple(kernel)
    spatial = tuple(output_size)
    n = data.shape[0]
    c = data.shape[1] // int(_np.prod(kernel))
    ref = jnp.zeros((n, c) + spatial, data.dtype)
    _, vjp = jax.vjp(
        lambda x: _im2col_patches(x, kernel, stride, dilate, pad), ref)
    return vjp(data)[0]


_wire_inputs("LinearRegressionOutput", ("data", "label"))
_wire_inputs("MAERegressionOutput", ("data", "label"))
_wire_inputs("LogisticRegressionOutput", ("data", "label"))
_wire_inputs("center_loss", ("data", "label", "center"), aux=("center",))


@register("BatchNormWithReLU", num_outputs=3, visible_outputs=1,
          mutate_inputs=((1, 3), (2, 4)), wrap_train="_training")
def _batch_norm_with_relu(data, gamma, beta, moving_mean, moving_var,
                          **kwargs):
    """Fused BN+ReLU (reference batch_norm_relu.cc — the oneDNN/cuDNN
    fusion; XLA fuses the relu into the normalize anyway, so this is the
    API surface, same aux-state contract as BatchNorm)."""
    out, mm, mv = _batch_norm(data, gamma, beta, moving_mean, moving_var,
                              **kwargs)
    return _jnp().maximum(out, 0), mm, mv
