"""Gated short convolution — the LFM2 "conv" operator's middle.

Between its two projections (``in_proj`` E → 3E, ``out_proj`` E → E,
both plain matmuls in the layer) the operator does::

    b, c, x = split(u @ W_in, 3)
    z[t, ch] = sum_j w[ch, j] * (b * x)[t - (K - 1) + j, ch]
    y = c * z

a causal depthwise convolution over the sequence, ``K`` taps a
channel (K = 3 in the published models), gated before and after.  It
is written as K shifted multiply-adds over the left-padded product:
no ``lax.conv`` (a depthwise kernel of 3 is three fused elementwise
passes, memory-bound either way), and its backward pass is what
autodiff makes of slices and pads.  Arithmetic is float32 whatever
type the three streams arrive in.
"""

import jax.numpy as jnp


def causal_depthwise_conv(x, w):
    """``z[:, t, ch] = sum_j w[ch, j] * x[:, t - (K - 1) + j, ch]``
    with ``x[:, < 0] = 0``.  x: (B, S, C); w: (C, K); float32 out."""
    K = w.shape[-1]
    S = x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    z = xf[:, 0:S] * wf[:, 0]
    for j in range(1, K):
        z = z + xf[:, j:j + S] * wf[:, j]
    return z


def gated_short_conv(b, c, x, w):
    """``c * conv(b * x)``: the three (B, S, C) streams of the input
    projection and the (C, K) taps → (B, S, C) float32."""
    bx = b.astype(jnp.float32) * x.astype(jnp.float32)
    return c.astype(jnp.float32) * causal_depthwise_conv(bx, w)
