"""Pallas fused cross-channel LRN (forward + custom VJP).

Why a hand kernel (the reference's LRN lived in znicz's OpenCL/CUDA
normalization kernels; SURVEY §7 milestone 2 names the Pallas homes):
measured inside the AlexNet fused step on v5e, the banded-matmul
formulation (znicz/lrn.py) costs ~9 ms of a ~40 ms tick — ~3× the
minimal HBM traffic — because XLA materializes the square and the
f32 window-sum as full-size intermediates between the matmul and the
surrounding elementwise math.  This kernel does the whole chain

    y = x · (k + α/n · Σ_{j∈window} x_j²)^(−β)

in ONE pass per direction: a (rows × C) tile is read into VMEM, the
windowed channel sum rides the MXU as a tiny banded matmul against a
resident C×C 0/1 band, and only the result returns to HBM.  The
backward pass recomputes the denominator in-VMEM (FLOPs are free
here; traffic is not) so its only HBM traffic is x, dy in → dx out.

dx math: with d = k + (α/n)·S, S_j = Σ_i B[i,j] x_i²,

    dx_i = dy_i·d_i^{−β} − (2αβ/n)·x_i·Σ_j B[i,j]·dy_j·x_j·d_j^{−β−1}

(the window membership matrix B is the same band as forward; the
second term is one more in-VMEM banded matmul).
"""

import functools

import jax
import jax.numpy as jnp

from ..backends import tpu_available

#: Rows per grid step.  f32 working set ≈ 5 tiles × BP × 128 lanes
#: × 4 B ≈ 5 MB at 2048 — comfortably inside 16 MB VMEM.
_BLOCK_ROWS = 2048


def band_matrix(c, n, dtype=jnp.float32):
    """0/1 window-membership matrix B[i, j] = 1 iff input channel i
    falls in output channel j's window (asymmetric for even n,
    matching znicz's padded slice-add semantics)."""
    half = n // 2
    i = jnp.arange(c)
    d = i[:, None] - i[None, :]
    return ((d >= -half) & (d <= n - 1 - half)).astype(dtype)


def lrn_reference(x, n, alpha, beta, k):
    """Pure-jnp twin (CPU path + parity oracle): the banded-matmul
    formulation from znicz/lrn.py."""
    band = band_matrix(x.shape[-1], n, x.dtype)
    sq = x * x
    ssum = jnp.einsum("...c,cd->...d", sq, band,
                      preferred_element_type=jnp.float32)
    denom = (k + (alpha / n) * ssum) ** beta
    return (x.astype(jnp.float32) / denom).astype(x.dtype)


def _neg_pow(d, beta):
    """d^(−β) without exp/log where β allows: AlexNet's β = 0.75
    becomes rsqrt·sqrt(rsqrt) (hardware sqrt units), the generic case
    falls back to pow."""
    if abs(beta - 0.75) < 1e-12:
        inv = jax.lax.rsqrt(d)
        return inv * jnp.sqrt(inv)
    if abs(beta - 0.5) < 1e-12:
        return jax.lax.rsqrt(d)
    if abs(beta - 1.0) < 1e-12:
        return 1.0 / d
    return d ** -beta


def _window_sum(x, band_ref):
    """Σ_{j∈window} x_j² as a banded matmul on the MXU: bf16 operands
    (the band is exact 0/1 and the squares round to bf16 on the MXU
    regardless), f32 accumulation."""
    xb = x.astype(jnp.bfloat16)
    return jax.lax.dot(xb * xb, band_ref[:].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)


def _fwd_kernel(x_ref, band_ref, y_ref, *, k, coef, beta):
    x = x_ref[:].astype(jnp.float32)
    d = k + coef * _window_sum(x, band_ref)
    y_ref[:] = (x * _neg_pow(d, beta)).astype(y_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, band_ref, dx_ref, *, k, coef, beta):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    d = k + coef * _window_sum(x, band_ref)
    dpow = _neg_pow(d, beta)
    t = dy * x * dpow / d
    u = jax.lax.dot(t.astype(jnp.bfloat16),
                    band_ref[:].astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32)
    dx = dy * dpow - (2.0 * coef * beta) * x * u
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _call(kernel, args, c, out_dtype, interpret):
    """Runs a row-blocked (P, C) pallas kernel; the band rides along
    whole (it is C×C, tiny)."""
    from jax.experimental import pallas as pl
    p = args[0].shape[0]
    bp = min(_BLOCK_ROWS, p)
    grid = (-(-p // bp),)
    row_spec = pl.BlockSpec((bp, c), lambda i: (i, 0))
    band_spec = pl.BlockSpec((c, c), lambda i: (0, 0))
    specs = [row_spec] * (len(args) - 1) + [band_spec]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((p, c), out_dtype),
        grid=grid,
        in_specs=specs,
        out_specs=row_spec,
        interpret=interpret,
        name="lrn",
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_pallas(x, n, alpha, beta, k, interpret=False):
    y, _ = _lrn_fwd(x, n, alpha, beta, k, interpret)
    return y


def _lrn_fwd(x, n, alpha, beta, k, interpret):
    c = x.shape[-1]
    flat = x.reshape(-1, c)
    # Static nondiff config scalars (custom_vjp nondiff_argnums),
    # baked into the kernel — never traced values.
    kern = functools.partial(
        _fwd_kernel, k=float(k),                    # lint-ok: VL101
        coef=float(alpha) / n, beta=float(beta))    # lint-ok: VL101
    y = _call(kern, (flat, band_matrix(c, n, jnp.float32)), c,
              x.dtype, interpret)
    return y.reshape(x.shape), x


def _lrn_bwd(n, alpha, beta, k, interpret, res, dy):
    x = res
    c = x.shape[-1]
    kern = functools.partial(_bwd_kernel, k=float(k),
                             coef=float(alpha) / n, beta=float(beta))
    dx = _call(kern, (x.reshape(-1, c), dy.reshape(-1, c),
                      band_matrix(c, n, jnp.float32)), c,
               x.dtype, interpret)
    return (dx.reshape(x.shape),)


lrn_pallas.defvjp(_lrn_fwd, _lrn_bwd)


def lrn(x, n, alpha, beta, k):
    """Backend-dispatching LRN: the Pallas kernel on TPU, the banded
    reference elsewhere (Pallas TPU kernels do not run on the CPU
    backend outside interpret mode)."""
    if tpu_available():
        return lrn_pallas(x, n, alpha, beta, k)
    return lrn_reference(x, n, alpha, beta, k)
