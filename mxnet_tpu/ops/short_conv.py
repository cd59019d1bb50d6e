"""The gated short convolution: the mixer of three in four layers of
``lfm2_moe`` (LFM2's convolution operator, Hugging Face ``Lfm2ShortConv``).

The layer's input projection gives three parts of the model's width, in the
order ``B, C, x``; the op computes::

    v = B * x
    z[t] = sum_j w[:, j] * v[t - (K - 1) + j]      (zero before the row's start)
    y = C * z

``z`` is a depthwise causal convolution of K taps with no bias.  No state
and no scan: a position reads the K - 1 before it.  The convolution is
``contrib.causal_conv1d``'s arithmetic, called as a function, so that op's
own lowering is what it was.  Products and sums are float32, the result in
the input's type.  The op runs under the region scope ``gated_short_conv``.
"""

from __future__ import annotations

from .. import regions
from .linear_attention import _causal_conv1d
from .registry import register


@register("contrib.gated_short_conv")
def _gated_short_conv(bcx, weight):
    """``C * conv(B * x)`` along a row.  ``bcx`` (B, L, 3 U): ``[B | C |
    x]``; ``weight`` (U, K), the taps of each channel.  Returns (B, L, U) in
    bcx's type."""
    import jax.numpy as jnp
    f32 = jnp.float32
    units = weight.shape[0]
    if bcx.shape[-1] != 3 * units:
        raise ValueError(f"gated_short_conv: the input's last dim "
                         f"{bcx.shape[-1]} is not 3 x the taps' {units} "
                         "channels")
    with regions.scope("gated_short_conv"):
        b, c, x = (bcx[..., i * units:(i + 1) * units].astype(f32)
                   for i in range(3))
        return (c * _causal_conv1d(b * x, weight)).astype(bcx.dtype)
