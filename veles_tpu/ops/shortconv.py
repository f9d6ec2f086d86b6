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

The gated delta operator runs the same taps over q, k and v with a
SiLU after them and no gates (:func:`conv_silu`), and TWO paths run
that, chosen by what the process can observe (:func:`_selects_pallas`:
the backend is a TPU, C a multiple of 128, S of the kernels' row step,
1 < K <= 8):

- the Pallas kernels of ``ops/pallas_shortconv.py``, ``shortconv_fwd``
  and, under their ``custom_vjp``, ``shortconv_bwd``: each reads its
  operands once and writes its results once, where XLA's form of the
  taps and their transposes made a dozen float32 passes over (S, C)
  (8,192 x 8,192 at ``qwen3-next.train-8k``: 11.7 ms a layer a tick
  against 1.6 that the bytes need; PERF.md §6, PR 38);
- XLA's form, ``silu(causal_depthwise_conv(x, w))``, everywhere else:
  the CPU's path and the kernels' oracle in the tests.

Each trace of either counts into ``shortconv.kernel.pallas`` /
``.xla``.  LFM2's gated form (:func:`gated_short_conv`) keeps XLA's
form on every backend: there XLA fuses both gates into the pass around
the taps, a kernel boundary would make it write ``b x`` and ``z`` in
float32, and the scope already runs within 2 x of its bytes (2.4 ms a
layer a tick against some 1.5 at ``lfm2-24b-a2b.train``).
"""

import jax
import jax.numpy as jnp

from .. import resilience
from ..backends import tpu_available
from . import pallas_shortconv as PS


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


def _selects_pallas(shape, k):
    """Whether the taps and SiLU at this geometry run the Pallas
    kernels: the backend is a TPU and the geometry is inside their
    contract (``pallas_shortconv.supports``).  A SELECTION by what the
    process can observe, made before a kernel is touched; once
    selected, a kernel that fails to lower raises."""
    return PS.supports(shape, k) and tpu_available()


def conv_silu(x, w):
    """``silu(causal_depthwise_conv(x[..., :C], w))``: x (B, S, C' >=
    C), w (C, K) -> (B, S, C) float32, through the kernels where
    :func:`_selects_pallas` says so (module docstring); they read the
    first C channels of a wider x in place."""
    C, k = w.shape
    if _selects_pallas(x.shape[:2] + (C,), k):
        resilience.stats.incr("shortconv.kernel.pallas")
        return PS.conv_silu(x, w)
    resilience.stats.incr("shortconv.kernel.xla")
    return jax.nn.silu(causal_depthwise_conv(x[..., :C], w))
