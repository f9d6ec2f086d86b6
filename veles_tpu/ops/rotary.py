"""Rotary positions (RoFormer, the rotate-half convention of the
public LM checkpoints) and the RMS norm that the same models put in
front of them, per head.  Both in float32: the angles reach
``S · θ^0`` radians and a bfloat16 cosine of 2047 is noise.
"""

import math

import jax.numpy as jnp


def rms_norm(x, gain, eps=1e-5):
    """``x · rsqrt(mean(x², -1) + eps) · gain`` over the last axis,
    computed in float32 and returned in ``x``'s type."""
    xf = x.astype(jnp.float32)
    scale = jnp.reciprocal(jnp.sqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + eps))
    return (xf * scale * gain).astype(x.dtype)


def rotary_tables(seq, head_dim, theta):
    """``(cos, sin)`` of shape (S, D / 2), float32, for positions
    0 … S − 1 and the inverse frequencies ``θ^(−2i / D)``."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def rotary(x, theta, fraction=None):
    """Rotates (B, S, H, D) by position: the halves ``x1 = x[..., :D/2]``
    and ``x2 = x[..., D/2:]`` go to ``(x1 cos − x2 sin, x2 cos + x1
    sin)``.  ``fraction`` (None: the whole head) rotates the FIRST
    ``D · fraction`` elements of a head so, halves and frequencies
    taken inside them, and passes the rest on untouched (the
    ``partial_rotary_factor`` of the public checkpoints).  Float32
    out."""
    D = x.shape[-1] if fraction is None else \
        math.floor(x.shape[-1] * fraction)
    cos, sin = rotary_tables(x.shape[1], D, theta)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :D // 2], xf[..., D // 2:D]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if D < x.shape[-1]:
        parts.append(xf[..., D:])
    return jnp.concatenate(parts, axis=-1)
