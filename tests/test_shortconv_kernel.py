"""The causal taps and SiLU of the gated delta operator as Pallas
kernels (``ops/pallas_shortconv.py``, interpreted) against their
oracle, XLA's ``silu(causal_depthwise_conv(x, w))``: the value, ``dx``
and ``dw`` through ``jax.vjp``, over three row blocks so that the
carried rows cross two boundaries; the choice between the two paths
(``ops/shortconv._selects_pallas``); the CPU's path, bit for bit; and
a Gated DeltaNet layer under the layers' checkpoint through either.

Tolerances are float32 ulps (``EPS``) of what each result sums: the
taps' ``Σ |w_j x_j|`` plus ``|y|`` for the value, ``Σ |w_j dy|`` for
``dx`` and ``Σ_t |x dy|`` for ``dw`` — the sums the two paths make in
other orders (on the CPU, XLA contracts a multiply and an add where
the interpreter does not) — and, where ``dx`` is bfloat16, one of its
ulps more: two roundings of float32 values that differ in their last
bits."""

import functools

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import resilience
from veles_tpu.ops import pallas_shortconv as PS
from veles_tpu.ops import shortconv as SC
from veles_tpu.ops.shortconv import causal_depthwise_conv
from veles_tpu.znicz import attention as Z

EPS = float(jnp.finfo(jnp.float32).eps)
#: float32 ulps of the summed magnitudes a result may differ by
#: (measured: 1.5 for the value, 1.9 for ``dx``, 0.5 for ``dw``).
ULPS = 4
#: Three row blocks of :data:`PS.SUB` rows, two batch rows, five
#: channel blocks of 128.
B, S, C = 2, 3 * PS.SUB, 5 * PS.LANE
NAMES = ("shortconv.kernel.pallas", "shortconv.kernel.xla")


def oracle(x, w):
    return jax.nn.silu(causal_depthwise_conv(x, w))


def operands(k, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed * 16 + k), 3)
    x = jax.random.normal(keys[0], (B, S, C)).astype(dtype)
    w = 0.5 * jax.random.normal(keys[1], (C, k))
    dy = jax.random.normal(keys[2], (B, S, C))
    return x, w, dy


def within(got, want, scale, name, ulps=ULPS, rounding=None):
    got, want = (numpy.asarray(a, numpy.float32) for a in (got, want))
    room = ulps * EPS * numpy.asarray(scale, numpy.float32)
    if rounding is not None:
        room = room + numpy.abs(want) * float(jnp.finfo(rounding).eps)
    excess = numpy.abs(got - want) - room
    assert excess.max() <= 0.0, (name, float(excess.max()))


def counted(fn, *args):
    before = [resilience.stats.get(name) for name in NAMES]
    out = fn(*args)
    return out, {name: resilience.stats.get(name) - was
                 for name, was in zip(NAMES, before)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("k", [2, 3, 4, 8])
def test_the_kernels_match_the_oracle(k, dtype):
    """Value, ``dx`` (in x's type) and ``dw`` over three row blocks of
    32: the forward carries ``x``'s last rows across two boundaries,
    the backward ``du``'s first rows and reads ``x``'s rows before a
    block from the block before it — nought before row 0."""
    assert PS._blocks((B, S, C), jnp.dtype(dtype).itemsize) == \
        (PS.SUB, PS.LANE)
    x, w, dy = operands(k, jnp.dtype(dtype))
    got, vjp = jax.vjp(functools.partial(PS.conv_silu, interpret=True),
                       x, w)
    want, vjp_want = jax.vjp(oracle, x, w)
    assert got.dtype == jnp.float32 and got.shape == x.shape
    (dx, dw), (dx_want, dw_want) = vjp(dy), vjp_want(dy)
    assert dx.dtype == x.dtype and dw.dtype == w.dtype == dw_want.dtype
    taps, vjp_abs = jax.vjp(causal_depthwise_conv,
                            jnp.abs(x.astype(jnp.float32)), jnp.abs(w))
    dx_sums, dw_sums = vjp_abs(jnp.abs(dy))
    within(got, want, taps + jnp.abs(want), "y")
    within(dx, dx_want, 1.1 * dx_sums, "dx",
           rounding=None if dtype == "float32" else jnp.bfloat16)
    within(dw, dw_want, 1.1 * dw_sums, "dw")


def test_row_zero_sees_nought_and_the_carried_rows_are_the_last():
    """A ramp: ``y[t] = silu(Σ_j w[j] (t - K + 1 + j))`` with the
    terms before row 0 nought — at the start of the sequence and right
    after each of the two block boundaries, exactly."""
    k = 4
    x = jnp.broadcast_to(jnp.arange(S, dtype=jnp.float32)[None, :, None],
                         (B, S, C))
    w = jnp.broadcast_to(jnp.asarray([1.0, 2.0, 4.0, 8.0]), (C, k))
    y = PS.conv_silu(x, w, interpret=True)
    t = numpy.arange(S)
    z = sum(float(w[0, j]) * numpy.maximum(t - k + 1 + j, 0)
            for j in range(k))
    for row in (0, 1, 2, 3, PS.SUB - 1, PS.SUB, PS.SUB + 1, 2 * PS.SUB,
                2 * PS.SUB + 2, S - 1):
        numpy.testing.assert_allclose(
            y[:, row], numpy.full((B, C), z[row] / (1 + numpy.exp(
                -z[row]))), rtol=2 * EPS, err_msg=str(row))


@pytest.mark.parametrize("case,shape,k,selected", [
    ("the cell's", (1, 8192, 8192), 4, True),
    ("a narrow stream", (2, 64, 128), 2, True),
    ("a CPU", (1, 8192, 8192), 4, False),
    ("C not of 128", (B, S, 200), 4, False),
    ("S not of the row step", (B, PS.SUB + 16, C), 4, False),
    ("K = 1", (B, S, C), 1, False),
    ("K = 9", (B, S, C), 9, False)])
def test_the_path_is_chosen_by_platform_and_shape(monkeypatch, case, shape,
                                                  k, selected):
    monkeypatch.setattr(SC, "tpu_available", lambda: case != "a CPU")
    assert SC._selects_pallas(shape, k) is selected


@pytest.mark.parametrize("case,shape,k", [
    ("C not of 128", (B, S, 200), 4),
    ("S not of the row step", (B, PS.SUB + 16, C), 4),
    ("K = 1", (B, S, C), 1),
    ("K = 9", (B, S, C), 9)])
def test_a_refused_geometry_runs_xlas_form_on_a_tpu(monkeypatch, case,
                                                    shape, k):
    """Where the backend IS a TPU and the geometry is outside the
    kernels' contract, ``conv_silu`` runs the oracle's ops and counts
    ``shortconv.kernel.xla``."""
    monkeypatch.setattr(SC, "tpu_available", lambda: True)
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    w = jax.random.normal(jax.random.PRNGKey(2), (shape[2], k))
    y, n = counted(SC.conv_silu, x, w)
    assert n == {"shortconv.kernel.pallas": 0, "shortconv.kernel.xla": 1}
    numpy.testing.assert_array_equal(y, oracle(x, w))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_cpu_takes_xlas_form_bit_for_bit(dtype):
    """On the CPU the dispatcher IS the oracle: the same bits forward
    and backward, and one ``shortconv.kernel.xla`` a trace."""
    x, w, dy = operands(4, jnp.dtype(dtype), seed=1)

    def run(fn):
        y, vjp = jax.vjp(fn, x, w)
        return (y,) + vjp(dy)

    got, n = counted(jax.jit(functools.partial(run, SC.conv_silu)))
    assert n == {"shortconv.kernel.pallas": 0, "shortconv.kernel.xla": 1}
    for a, b in zip(got, jax.jit(functools.partial(run, oracle))()):
        assert a.dtype == b.dtype
        numpy.testing.assert_array_equal(a, b)


def test_a_gated_delta_layer_agrees_through_either_path(monkeypatch):
    """A ``gated_delta`` layer whose q, k and v are 128 channels, under
    ``Z.checkpointed`` with bfloat16 operands: its value and every
    parameter's gradient through the interpreted kernels against XLA's
    taps, each trace counted by the path it took (the rule stays XLA's
    form on the CPU)."""
    spec = Z.layer_spec(
        norm="rms", bias=False, norm_eps=1e-6, operator="gated_delta",
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=16,
        linear_value_dim=16, conv_kernel=4, n_heads=2, ffn="gated-mlp",
        ffn_dim=16)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 32))
    params = {name: 0.3 * jax.random.normal(next(keys), shape)
              for name, shape in Z.layer_param_shapes(spec, 32).items()}
    x = jax.random.normal(next(keys), (2, 128, 32))

    def loss(params, x):
        y = Z.checkpointed(lambda p, h: Z.layer_apply(
            spec, p, h, jnp.bfloat16)[0])(params, x)
        return (y * y).mean()

    def run():
        return counted(jax.jit(jax.value_and_grad(loss, argnums=(0, 1))),
                       params, x)

    (want, (wp, wx)), n = run()
    assert n == {"shortconv.kernel.pallas": 0, "shortconv.kernel.xla": 1}
    monkeypatch.setattr(SC, "tpu_available", lambda: True)
    monkeypatch.setattr(SC.PS, "conv_silu", functools.partial(
        PS.conv_silu, interpret=True))
    (got, (gp, gx)), n = run()
    assert n == {"shortconv.kernel.pallas": 1, "shortconv.kernel.xla": 0}
    numpy.testing.assert_allclose(got, want, rtol=1e-3)

    def close(a, b, name):
        assert float(jnp.linalg.norm(a - b)) <= \
            0.01 * float(jnp.linalg.norm(b)) + 1e-6, name

    close(gx, wx, "x")
    for name in wp:
        close(gp[name], wp[name], name)


def test_a_wider_projection_is_read_in_place():
    """x carries channels past w's (the operator hands over its whole
    ``[q | k | v | z]`` projection): both paths convolve the first C
    and give nought as the cotangent of the rest."""
    k, wide = 4, C + 2 * PS.LANE
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    x = jax.random.normal(keys[0], (B, S, wide)).astype(jnp.bfloat16)
    w = 0.5 * jax.random.normal(keys[1], (C, k))
    dy = jax.random.normal(keys[2], (B, S, C))
    want, vjp_want = jax.vjp(lambda x, w: oracle(x[..., :C], w), x, w)
    dx_want, dw_want = vjp_want(dy)
    got, vjp = jax.vjp(functools.partial(PS.conv_silu, interpret=True),
                       x, w)
    dx, dw = vjp(dy)
    assert dx.shape == x.shape and not dx[..., C:].any()
    taps, vjp_abs = jax.vjp(causal_depthwise_conv,
                            jnp.abs(x[..., :C].astype(jnp.float32)),
                            jnp.abs(w))
    dx_sums, dw_sums = vjp_abs(jnp.abs(dy))
    within(got, want, taps + jnp.abs(want), "y")
    within(dx[..., :C], dx_want[..., :C], 1.1 * dx_sums, "dx",
           rounding=jnp.bfloat16)
    within(dw, dw_want, 1.1 * dw_sums, "dw")
    cpu, vjp_cpu = jax.vjp(SC.conv_silu, x, w)
    for a, b in zip((cpu,) + vjp_cpu(dy), (want, dx_want, dw_want)):
        numpy.testing.assert_array_equal(a, b)
