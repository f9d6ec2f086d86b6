"""The gated delta rule — linear attention with a state carried along
the sequence (Gated DeltaNet, arXiv:2412.06464; the ``linear_attention``
layers of the ``qwen3_next`` checkpoints).

What DEFINES it is a recurrence, a token at a time.  A value head owns
a ``(Dk, Dv)`` float32 state ``S``, nought before the first token, and
row ``t`` — its query ``q_t`` and key ``k_t`` (Dk,), value ``v_t``
(Dv,), decay ``g_t <= 0`` and step ``beta_t`` in (0, 1) — does::

    S = exp(g_t) * S                       forget
    delta_t = beta_t * (v_t - S^T k_t)     what the key reads, corrected
    S = S + k_t delta_t^T                  a rank-one write
    o_t = S^T q_t                          read

Run so it is ``S`` dependent steps of a few hundred KFLOP each:
hopeless on a TPU.  :func:`gated_delta_rule` runs the CHUNKED form of
the same function (chunks of ``C`` = 64 rows; ``torch_chunk_gated_
delta_rule`` in ``transformers`` is its twin).  Inside a chunk, with
``G_i = g_1 + … + g_i`` cumulated from the chunk's first row,
``decay[i, j] = exp(G_i - G_j)`` for ``i >= j`` and ``K_b = beta * K``,
``V_b = beta * V``::

    A = -tril_{-1}((K_b K^T) * decay)      strictly lower: nilpotent
    T = (I - A)^{-1}                       unit lower triangular
    W = T (K_b * exp(G)),   U = T V_b

and along the chunks, carrying ``S`` (``S / C`` dependent steps, each
four products of 64 x 128 and 128 x 128 or 64 x 64 a head)::

    V' = U - W S
    O  = (Q * exp(G)) S + tril((Q K^T) * decay) V'
    S  = exp(G_last) S + (K * exp(G_last - G))^T V'

Every exponent above is ``<= 0``: a decay that kills the state inside
one row underflows to nought and overflows nowhere.  The decays, the
cumulated sums, the inverse (:func:`unit_lower_inverse`) and the state
are float32; the chunk products take their operands in the type ``q``
arrives in (bfloat16 on the training path; ``S`` is rounded to it where
a product reads it, never where it is carried) and accumulate in
float32.

TWO paths run that form, chosen by what the process can observe
(:func:`_selects_pallas`: the backend is a TPU, both head sizes are
multiples of 128, the chunk is 64 rows and divides the sequence):

- the Pallas kernels of ``ops/pallas_gated_delta.py`` — the inverse of
  every chunk in one call, then a sweep along the sequence that keeps a
  head's state in VMEM, and under their ``custom_vjp`` a reverse sweep
  that carries ``dS`` there and gives all five cotangents.  q and k are
  read by key head (no repeat), nothing ``(…, C, C)`` in float32 and no
  stack of per-chunk operands reaches HBM, and what the forward kernels
  produced — ``o``, ``T``, the states at the chunks' starts — carries
  ``checkpoint_name``s that the layers' checkpoint keeps
  (``znicz.attention.checkpointed``): its recompute runs no kernel;
- :func:`gated_delta_rule_xla`, everywhere else (a CPU, a head of 64,
  another chunk): XLA's ops and a ``lax.scan`` that carries the state,
  the kernels' oracle in the tests.  Its backward pass is autodiff's
  through the scan — its residuals a chunk are the rounded state and
  ``V'`` — but for the inverse, whose rule is two products (``dA = T^T
  dT T^T``).

The precisions above are both paths'.  Each TRACE of either counts
into ``linear_attention.kernel.pallas`` / ``.xla``.
"""

import functools

import jax
import jax.numpy as jnp

from .. import resilience
from ..backends import tpu_available
from . import pallas_gated_delta as PG

#: Rows of a chunk unless a caller says otherwise.
CHUNK = 64
#: A diagonal block this small is inverted by the product form.
_BASE = 8

_hi = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def _inverse(a):
    n = a.shape[-1]
    if n <= _BASE:
        # A is nilpotent: (I - A)^-1 = (I + A)(I + A^2)(I + A^4) …
        # The powers' entries grow like binomials before they cancel,
        # which eight rows keep far inside float32 and sixty-four
        # with like keys would not.
        t = jnp.eye(n, dtype=a.dtype) + a
        power, p = a, 2
        while p < n:
            power = _hi(power, power)
            t = t + _hi(t, power)
            p *= 2
        return t
    h = n // 2
    t11, t22 = _inverse(a[..., :h, :h]), _inverse(a[..., h:, h:])
    t21 = _hi(_hi(t22, a[..., h:, :h]), t11)
    top = jnp.concatenate([t11, jnp.zeros_like(a[..., :h, h:])], axis=-1)
    return jnp.concatenate(
        [top, jnp.concatenate([t21, t22], axis=-1)], axis=-2)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I - A)^{-1}`` of strictly lower triangular ``A`` (…, n, n),
    float32 at ``highest``: diagonal blocks of at most eight rows by
    the product form, merged two at a time (``T21 = T22 A21 T11``):
    small batched products and no row-by-row substitution."""
    return _inverse(a)


def _inverse_fwd(a):
    t = _inverse(a)
    return t, t


def _inverse_bwd(t, ct):
    tt = jnp.swapaxes(t, -1, -2)
    return (_hi(_hi(tt, ct), tt),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _selects_pallas(q_shape, v_shape, chunk):
    """Whether the rule at this geometry runs the Pallas kernels: the
    backend is a TPU and the geometry is inside their contract.  A
    SELECTION by what the process can observe, made before a kernel
    is touched (``ops.attention._selects_pallas``'s rule): once
    selected, a kernel that fails to lower raises."""
    return PG.supports(q_shape, v_shape, chunk) and tpu_available()


def gated_delta_rule(q, k, v, g, beta, chunk=CHUNK):
    """The gated delta rule over whole sequences, chunked (module
    docstring).  q, k: (B, S, Hk, Dk), already normalised and scaled;
    v: (B, S, Hv, Dv) with ``Hv`` a multiple of ``Hk`` — key head ``j``
    serves value heads ``j r … j r + r - 1``; g, beta: (B, S, Hv).
    Returns o (B, S, Hv, Dv) float32.  ``S`` must be a multiple of
    ``chunk``."""
    S, Hk, Hv = q.shape[1], q.shape[2], v.shape[2]
    if S % chunk or Hv % Hk:
        raise ValueError(
            "gated_delta_rule: a sequence of %d rows in chunks of %d, "
            "%d value heads over %d key heads" % (S, chunk, Hv, Hk))
    if _selects_pallas(q.shape, v.shape, chunk):
        resilience.stats.incr("linear_attention.kernel.pallas")
        return PG.gated_delta(q, k, v, g, beta)
    resilience.stats.incr("linear_attention.kernel.xla")
    return gated_delta_rule_xla(q, k, v, g, beta, chunk)


def gated_delta_rule_xla(q, k, v, g, beta, chunk=CHUNK):
    """:func:`gated_delta_rule` as XLA's ops and a scan (module
    docstring): the path off a TPU or outside the kernels' geometry."""
    f32 = jnp.float32
    B, S, Hk, _ = q.shape
    Hv = v.shape[2]
    cdt = q.dtype
    N = S // chunk
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)

    def chunks(x):
        # (B, S, H, …) -> (B, H, N, C, …)
        x = x.reshape((B, N, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    if Hv != Hk:
        q, k = (jnp.repeat(x, Hv // Hk, axis=2) for x in (q, k))
    q, k, v = chunks(q), chunks(k), chunks(v.astype(cdt))
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    with jax.named_scope("chunk%d" % chunk):
        gc = jnp.cumsum(g, axis=-1)                         # (B, H, N, C)
        seen = jnp.tril(jnp.ones((chunk, chunk), bool))
        before = jnp.tril(seen, -1)
        decay = jnp.where(seen, jnp.exp(jnp.where(
            seen, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
        kb = (k.astype(f32) * beta[..., None]).astype(cdt)
        vb = (v.astype(f32) * beta[..., None]).astype(cdt)
        a = -jnp.where(before,
                       dot("...id,...jd->...ij", kb, k) * decay, 0.0)
        t = unit_lower_inverse(a).astype(cdt)
        grow = jnp.exp(gc)[..., None]
        last = gc[..., -1:]                                 # (B, H, N, 1)
        w = dot("...ij,...jd->...id", t,
                (kb.astype(f32) * grow).astype(cdt)).astype(cdt)
        u = dot("...ij,...jd->...id", t, vb)
        qg = (q.astype(f32) * grow).astype(cdt)
        kd = (k.astype(f32) * jnp.exp(last - gc)[..., None]).astype(cdt)
        inner = jnp.where(seen, dot("...id,...jd->...ij", q, k) * decay,
                          0.0).astype(cdt)
        keep = jnp.exp(last)[..., None]                     # (B, H, N, 1, 1)

        def step(state, x):
            w, u, qg, kd, inner, keep = x
            read = state.astype(cdt)
            fresh = u - dot("bhck,bhkv->bhcv", w, read)
            o = dot("bhck,bhkv->bhcv", qg, read) + \
                dot("bhij,bhjv->bhiv", inner, fresh.astype(cdt))
            state = keep * state + dot("bhck,bhcv->bhkv", kd,
                                       fresh.astype(cdt))
            return state, o

        xs = tuple(jnp.moveaxis(x, 2, 0)
                   for x in (w, u, qg, kd, inner, keep))
        state = jnp.zeros((B, Hv, q.shape[-1], v.shape[-1]), f32)
        _, o = jax.lax.scan(step, state, xs)                # (N, B, H, C, Dv)
    return jnp.moveaxis(o, (0, 3), (1, 2)).reshape(B, S, Hv, -1)
