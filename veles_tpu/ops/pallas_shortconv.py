"""The causal depthwise convolution and its SiLU as Pallas TPU kernels:
``silu(causal_depthwise_conv(x, w))`` of ``ops/shortconv.py`` (its
docstring has the convolution), the form the gated delta operator runs
over q, k and v (``znicz.attention._gated_delta_operator``).

Two kernels under one ``jax.custom_vjp`` (:func:`conv_silu`), each a
``pallas_call`` over the grid (batch, block of channels, block of rows)
whose row axis is walked in order, and each reads its operands once and
writes its results once:

``shortconv_fwd``
    ``y = silu(z)``, ``z[t] = w[0] x[t - K + 1] + … + w[K-1] x[t]``
    (the oracle's order of the sums), float32 out.  The last rows of a
    block's ``x`` stay in a VMEM scratch for the next block's first
    rows (nought before row 0): nothing is read twice.
``shortconv_bwd``
    the rows from the END: it rebuilds ``z`` from ``x`` (the rows
    before a block come in an extra block of :data:`_HALO_IN` rows, the
    one before it, nought at row 0), forms ``du = dy silu'(z)``, keeps
    the first rows of ``du`` for the block before it, writes ``dx[t] =
    w[K-1] du[t] + … + w[0] du[t + K - 1]`` in ``x``'s type and sums
    ``dw[j] = Σ_t x[t - K + 1 + j] du[t]`` in VMEM over the walk —
    written once a (batch, block of channels); XLA adds the batch's
    partials.

Taps, sums and the SiLU are float32, whatever type ``x`` arrives in:
the forward gives the oracle's bits (on the chip; PERF.md §6, PR 38).
A block's body walks it :data:`SUB` rows at a time in a loop: a Mosaic
kernel is compiled again at every program load
(``ops/pallas_gated_delta.py``, ``UNROLL``), so the body stays small;
the taps are static, unaligned row slices of an aligned load.
"""

import functools

import jax
import jax.numpy as jnp

#: The most taps the kernels serve: ``w`` travels as (8, C) float32,
#: one sublane tile, rows past ``K`` nought.
TAPS = 8
LANE = 128
#: Rows a step of a body's loop handles (a multiple of the halo).
SUB = 32
#: The bytes of ``x`` a grid step reads, at most; the largest blocks
#: inside it are taken.  Measured on one v5e at (1, 8192, 8192)
#: bfloat16, K = 4, ms a call forward | backward, rows x channels:
#: 512 x 1024 0.66 | 1.37, 1024 x 512 0.66 | 1.17, 512 x 512 0.71 |
#: 1.18, 256 x 2048 0.79 | 1.88; loops of 8 / 16 / 32 / 64 rows at 512
#: x 1024 0.86 / 0.67 / 0.66 / 0.70 forward (PERF.md §6, PR 38).
BLOCK_BYTES = 1 << 20
CHANNELS = 512
#: Rows of the float32 scratch before a block: the K - 1 <= 7 rows the
#: taps reach back, one sublane tile.
_HALO = 8
#: Rows of the backward's extra ``x`` block before its block: a
#: bfloat16 tile.
_HALO_IN = 16


def supports(shape, k):
    """Whether the kernels' geometry contract holds: x (B, S, C) with
    C a multiple of 128 and S of :data:`SUB`, and 1 < k <= 8 taps."""
    return (len(shape) == 3 and shape[1] > 0 and shape[1] % SUB == 0 and
            shape[2] % LANE == 0 and 1 < k <= TAPS)


def _blocks(shape, itemsize):
    """(rows, channels) of a grid step: the widest channel block up to
    :data:`CHANNELS` that divides C, then the most rows, a power of two
    times :data:`SUB` dividing S, inside :data:`BLOCK_BYTES`."""
    _, S, C = shape
    cb = max(c for c in range(LANE, CHANNELS + 1, LANE) if C % c == 0)
    rows = SUB
    while (S % (2 * rows) == 0 and
           2 * rows * cb * itemsize <= BLOCK_BYTES):
        rows *= 2
    return rows, cb


def _taps(ext, w_ref, k, n, first):
    """``Σ_j w[j] ext[first + j : first + j + n]``, summed in order."""
    z = ext[first:first + n] * w_ref[0:1, :]
    for j in range(1, k):
        z = z + ext[first + j:first + j + n] * w_ref[j:j + 1, :]
    return z


def _loop(rows, body):
    jax.lax.fori_loop(0, rows // SUB, body, 0)


def _fwd_kernel(x_ref, w_ref, y_ref, xs, *, k, rows):
    from jax.experimental import pallas as pl
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        xs[0:_HALO, :] = jnp.zeros((_HALO, xs.shape[1]), f32)

    def step(r, carry):
        at = pl.multiple_of(r * SUB, SUB)
        xs[pl.ds(at + _HALO, SUB), :] = x_ref[pl.ds(at, SUB), :].astype(f32)
        z = _taps(xs[pl.ds(at, SUB + _HALO), :], w_ref, k, SUB,
                  _HALO - (k - 1))
        y_ref[pl.ds(at, SUB), :] = jax.nn.silu(z)
        return carry

    _loop(rows, step)
    xs[0:_HALO, :] = xs[rows:rows + _HALO, :]


def _bwd_kernel(x_ref, before_ref, dy_ref, w_ref, dx_ref, dw_ref, xs, dus,
                acc, *, k, rows):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    i, n = pl.program_id(2), pl.num_programs(2)
    cb = xs.shape[1]

    @pl.when(i == 0)
    def _():
        dus[rows:rows + _HALO, :] = jnp.zeros((_HALO, cb), f32)
        acc[...] = jnp.zeros(acc.shape, f32)

    @pl.when(i == n - 1)
    def _():
        xs[0:_HALO, :] = jnp.zeros((_HALO, cb), f32)

    @pl.when(i < n - 1)
    def _():
        xs[0:_HALO, :] = before_ref[_HALO_IN - _HALO:, :].astype(f32)

    def grads(r, carry):
        at = pl.multiple_of(r * SUB, SUB)
        xs[pl.ds(at + _HALO, SUB), :] = x_ref[pl.ds(at, SUB), :].astype(f32)
        ext = xs[pl.ds(at, SUB + _HALO), :]
        first = _HALO - (k - 1)
        z = _taps(ext, w_ref, k, SUB, first)
        s = jax.nn.sigmoid(z)
        du = dy_ref[pl.ds(at, SUB), :] * (s * (1.0 + z * (1.0 - s)))
        dus[pl.ds(at, SUB), :] = du
        for j in range(k):
            acc[j] += (ext[first + j:first + j + SUB] * du).reshape(
                SUB // 8, 8, cb).sum(axis=0)
        return carry

    def inputs(r, carry):
        at = pl.multiple_of(r * SUB, SUB)
        later = dus[pl.ds(at, SUB + _HALO), :]
        dx = later[k - 1:k - 1 + SUB] * w_ref[0:1, :]
        for j in range(1, k):
            dx = dx + later[k - 1 - j:k - 1 - j + SUB] * w_ref[j:j + 1, :]
        dx_ref[pl.ds(at, SUB), :] = dx.astype(dx_ref.dtype)
        return carry

    _loop(rows, grads)
    _loop(rows, inputs)
    dus[rows:rows + _HALO, :] = dus[0:_HALO, :]

    @pl.when(i == n - 1)
    def _():
        for j in range(k):
            dw_ref[j:j + 1, :] = acc[j].sum(axis=0, keepdims=True)
        if k < TAPS:
            dw_ref[k:, :] = jnp.zeros((TAPS - k, cb), f32)


# -- the calls -------------------------------------------------------------


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward_call(x, w8, k, interpret):
    """x (B, S, C' >= C), w8 (8, C) -> y (B, S, C) float32 of x's
    first C channels."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, S, C = x.shape[:2] + w8.shape[1:]
    rows, cb = _blocks((B, S, C), x.dtype.itemsize)
    block = pl.BlockSpec((None, rows, cb), lambda b, c, i: (b, i, c))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, rows=rows),
        grid=(B, C // cb, S // rows),
        in_specs=[block, pl.BlockSpec((TAPS, cb), lambda b, c, i: (0, c))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, cb), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="shortconv_fwd",
    )(x, w8)


def _backward_call(x, dy, w8, k, interpret):
    """x (B, S, C' >= C), dy (B, S, C) float32 -> dx (B, S, C) in
    x's type and dw's partials (B, 8, C) float32, a batch row each."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    B, S, C = dy.shape
    rows, cb = _blocks(dy.shape, x.dtype.itemsize)
    n = S // rows

    def at(i):
        return n - 1 - i

    block = pl.BlockSpec((None, rows, cb), lambda b, c, i: (b, at(i), c))
    # the rows just before the block (clamped at block 0, where the
    # kernel reads nought in their place)
    before = pl.BlockSpec(
        (None, _HALO_IN, cb),
        lambda b, c, i: (b, jnp.maximum(at(i) * (rows // _HALO_IN) - 1, 0),
                         c))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, k=k, rows=rows),
        grid=(B, C // cb, n),
        in_specs=[block, before, block,
                  pl.BlockSpec((TAPS, cb), lambda b, c, i: (0, c))],
        out_specs=(block,
                   pl.BlockSpec((None, TAPS, cb), lambda b, c, i: (b, 0, c))),
        out_shape=(jax.ShapeDtypeStruct((B, S, C), x.dtype),
                   jax.ShapeDtypeStruct((B, TAPS, C), f32)),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, cb), f32),
                        pltpu.VMEM((rows + _HALO, cb), f32),
                        pltpu.VMEM((TAPS, 8, cb), f32)],
        compiler_params=_params(),
        interpret=interpret,
        name="shortconv_bwd",
    )(x, x, dy, w8)


# -- the differentiable entry point ----------------------------------------


def _rows_of(w):
    """(C, K) taps -> (8, C) float32: a tap a row of lanes."""
    return jnp.pad(w.astype(jnp.float32).T,
                   ((0, TAPS - w.shape[1]), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_silu(x, w, interpret):
    return _forward_call(x, _rows_of(w), w.shape[1], interpret)


def _conv_silu_fwd(x, w, interpret):
    return _conv_silu(x, w, interpret), (x, w)


def _conv_silu_bwd(interpret, res, dy):
    x, w = res
    C, k = w.shape
    dx, dw = _backward_call(x, dy.astype(jnp.float32), _rows_of(w), k,
                            interpret)
    # the channels past C were not read: nought, which XLA adds to
    # their own cotangent inside the products that read dx
    dx = jnp.pad(dx, ((0, 0), (0, 0), (0, x.shape[2] - C)))
    return dx, dw.sum(axis=0)[:k].T.astype(w.dtype)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def conv_silu(x, w, interpret=False):
    """``silu(causal_depthwise_conv(x[..., :C], w))`` through the
    kernels: x (B, S, C' >= C) of any float type, w (C, K) -> (B, S, C)
    float32, differentiable in both.  The kernels read x's first C
    channels in place, so a caller hands over a wider projection
    without a copy of its slice.  The caller has checked
    :func:`supports` at (B, S, C); ``interpret`` (the CPU's tests) runs
    the kernels as plain jax ops."""
    return _conv_silu(x, w, bool(interpret))
