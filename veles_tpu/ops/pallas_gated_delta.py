"""The gated delta rule as Pallas TPU kernels: the chunked form of
``ops/linear_attention.py`` (its module docstring has the mathematics
and the names used below) with a head's ``(Dk, Dv)`` float32 state held
in VMEM across the chunks of its sequence, forward and backward.

Three kernels under one ``jax.custom_vjp`` (:func:`gated_delta`), each
a ``pallas_call`` over the grid (batch, key head, block of chunks) — a
program serves the ``r = Hv / Hk`` value heads of ONE key head, whose
q and k rows it reads once (the ``BlockSpec`` indexes them by key
head: nothing is repeated in HBM), and walks the chunks of its block
in a loop (:data:`UNROLL`):

``gated_delta_inv``
    what does not depend on the state: the cumulated ``g``, ``decay``,
    ``A`` and ``T = (I - A)^-1`` of every chunk and value head, written
    in the operands' type, the two heads of a key head side by side
    (64 x 128: a whole lane tile).  Chunks are independent here, so the
    dependent chain of an inverse — fifteen substitution steps and
    four 64-wide float32 products — is overlapped across the chunks
    of a block.
``gated_delta_fwd``
    the sweep along the sequence: ``W``, ``U``, ``inner``, ``V'``,
    ``O`` and the state's update are made in VMEM from the chunk's q,
    k, v, g, beta and ``T`` blocks; the state lives in a VMEM scratch
    across the sequential grid axis.  It writes ``o`` (float32) and the
    state at each chunk's START (float32, ``N x Hv x Dk x Dv``: 268 MB
    a layer at 8,192 rows of 32 heads of 128 x 128).
``gated_delta_bwd``
    the reverse sweep, carrying ``dS`` (float32, VMEM): it rebuilds a
    chunk's local terms from the same blocks, reads the state the
    forward sweep wrote, and gives the cotangents of ALL FIVE operands
    — ``dq`` and ``dk`` summed over the key head's value heads in the
    kernel, ``dg`` and ``dbeta`` a value head.

No ``(…, C, C)`` float32 array and no stack of per-chunk operands
(``W``, ``U``, ``inner``, ``Q·exp(G)``, …) reaches HBM.  What does —
``o``, ``T`` and the states — is everything the backward kernel reads
beside the operands, and carries a ``checkpoint_name``
(:data:`GATED_DELTA_KEPT`): a checkpoint that saves those names
(``znicz.attention.checkpointed``) has no call of the two forward
kernels left in its recompute.  The states are KEPT and not rebuilt by
a second forward sweep: writing and reading 268 MB is 0.7 ms at the
HBM peak where the sweep is milliseconds, and the program's needed
bytes still fall (PERF.md §6, PR 36).

Precisions are ``ops/linear_attention.py``'s: the decays, their
cumulated sums, the inverse (exact substitution and float32 products
at ``highest``), the
carried state, ``dS`` and ``o`` are float32; the chunk products take
their operands in the type ``q`` arrives in — the state, ``T`` and the
cotangents rounded to it where a product reads them, never where they
are carried — and accumulate in float32 (float32 operands multiply at
``highest``: a plain f32 ``dot`` in a Mosaic kernel is ONE bf16 pass).
The backward's ``dA = T^T dT T^T`` reads the kept, rounded ``T``.

Small vectors change between a row of lanes and a column of sublanes
by a masked sum against the identity, and ``cumsum`` is a masked sum
against the triangle: exact in float32, a few vector operations a
chunk, and no transpose or scalar extraction for Mosaic to refuse.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

#: Rows of a chunk: the only size the kernels serve.
CHUNK = 64
LANE = 128
#: Rows of a diagonal block that :func:`_inverse` makes by
#: substitution before it merges blocks by products.
_BASE = 16
#: Chunks a grid step walks, at most: a block of 512 rows keeps the
#: grid's 0.35 us a step far under a step's work and the
#: double-buffered blocks (2.2 MB forward, 3.3 MB backward at two
#: heads of 128) inside the scoped VMEM.
BLOCK_CHUNKS = 8

#: ``jax.ad_checkpoint.checkpoint_name``s of what the forward kernels
#: produce: ``o`` as (B, S, Hv·Dv) float32, ``T`` as (B, Hk, N, C,
#: r·C) in the operands' type, the states at the chunks' starts as
#: (B, Hv, N, Dk, Dv) float32 — the arrays the backward kernel (and,
#: for ``o``, the rest of the layer) reads.
GD_OUT = "gated_delta_out"
GD_INVERSE = "gated_delta_inverse"
GD_STATES = "gated_delta_states"
GATED_DELTA_KEPT = (GD_OUT, GD_INVERSE, GD_STATES)

_HIGHEST = jax.lax.Precision.HIGHEST


def supports(q_shape, v_shape, chunk):
    """Whether the kernels' geometry contract holds: q (B, S, Hk, Dk)
    and v (B, S, Hv, Dv) with ``Hv`` a multiple of ``Hk``, both head
    sizes lane-native (multiples of 128), chunks of 64 rows that
    divide S."""
    if len(q_shape) != 4 or len(v_shape) != 4:
        return False
    B, S, Hk, Dk = q_shape
    return (chunk == CHUNK and S % CHUNK == 0 and S > 0 and
            v_shape[:2] == (B, S) and v_shape[2] % Hk == 0 and
            Dk % LANE == 0 and v_shape[3] % LANE == 0)


def _block_chunks(n):
    """Largest divisor of ``n`` chunks up to :data:`BLOCK_CHUNKS`."""
    return max(c for c in range(1, BLOCK_CHUNKS + 1) if n % c == 0)


# -- what every kernel makes of a chunk ------------------------------------


def _dot(a, b, contract, precision=None):
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


def _products(dtype):
    """``a @ b``, ``a @ b^T`` and ``a^T @ b`` of chunk operands of
    ``dtype``, float32 out."""
    precision = _HIGHEST if dtype == jnp.float32 else None
    return tuple(functools.partial(_dot, contract=c, precision=precision)
                 for c in ((1, 0), (1, 1), (0, 0)))


def _masks():
    rows = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    return rows, cols


def _to_col(row, eye):
    """(1, C) along the lanes -> (C, 1) along the sublanes, exactly."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _decays(g_row, rows, cols):
    """Of a chunk's g (1, C): the cumulated sums as a column and as a
    row, ``decay`` (C, C; nought above the diagonal) and the chunk's
    whole sum as a column.  No exponent is positive."""
    seen = cols <= rows
    gc_col = jnp.sum(jnp.where(seen, g_row, 0.0), axis=1, keepdims=True)
    gc_row = _to_row(gc_col, rows == cols)
    decay = jnp.where(seen, jnp.exp(
        jnp.where(seen, gc_col - gc_row, 0.0)), 0.0)
    last_col = jnp.sum(jnp.broadcast_to(g_row, (CHUNK, CHUNK)), axis=1,
                       keepdims=True)
    return gc_col, decay, last_col


def _inverse(a, rows, cols):
    """``(I - A)^-1`` of strictly lower triangular ``A`` (C, C) in
    float32, on whole 64 x 64 tiles.  The four diagonal blocks of
    sixteen rows by exact substitution, all four at once: step ``j``
    adds to every row below row ``j`` of its block ``A[i, j]`` times
    that row, which is final by then — fifteen multiply-adds of the
    tile and no product.  Then ``ops.linear_attention.
    unit_lower_inverse``'s merges, two at a time at ``highest``: ``T
    <- T + T A_off T`` with ``A_off`` the blocks under the diagonal
    that the merge takes in.  (Measured on one v5e, the inverses of a
    layer's 4,096 chunk-heads, ms: blocks of 8 by the product form
    and three merges — ten float32 products, sixty bf16 passes — 6.13;
    substitution in blocks of 8 / 16 / 32 / 64 with 3 / 2 / 1 / 0
    merges 4.20 / 3.52 / 3.67 / 4.83: PERF.md §6, PR 36.)"""
    hi = _products(jnp.float32)[0]

    def same(block):
        shift = block.bit_length() - 1
        return (rows >> shift) == (cols >> shift)

    block = _BASE
    d = jnp.where(same(block), a, 0.0)
    within = cols & (block - 1)
    t = jnp.where(rows == cols, 1.0, 0.0)
    for j in range(block - 1):
        col = jnp.sum(jnp.where(within == j, d, 0.0), axis=1,
                      keepdims=True)
        row = jnp.concatenate([
            jnp.broadcast_to(t[m * block + j:m * block + j + 1, :],
                             (block, CHUNK))
            for m in range(CHUNK // block)], axis=0)
        t = t + col * row
    while block < CHUNK:
        off = jnp.where(same(2 * block) & ~same(block), a, 0.0)
        t = t + hi(hi(t, off), t)
        block *= 2
    return t


def _local(k, v, g_row, b_row, rows, cols):
    """A chunk's terms that read neither q, ``T`` nor the state."""
    f32 = jnp.float32
    cdt = k.dtype
    gc_col, decay, last_col = _decays(g_row, rows, cols)
    b_col = _to_col(b_row, rows == cols)
    kb = (k.astype(f32) * b_col).astype(cdt)
    vb = (v.astype(f32) * b_col).astype(cdt)
    grow = jnp.exp(gc_col)
    kg = (kb.astype(f32) * grow).astype(cdt)
    return gc_col, decay, last_col, b_col, kb, vb, grow, kg


def _keep_row(g_row, eye, width):
    """``exp`` of the chunk's summed g as a (1, width) row: what the
    state decays by over the chunk."""
    g_col = _to_col(g_row, eye)
    return jnp.exp(jnp.sum(jnp.broadcast_to(g_col, (CHUNK, width)),
                           axis=0, keepdims=True))


# -- the kernels -----------------------------------------------------------


#: Chunks a step of each kernel's loop over its block holds.  More of
#: them let the compiler overlap one chunk's vector work with
#: another's products; but a Mosaic kernel is compiled whenever a
#: process LOADS the program, from the persistent cache too, and that
#: time grows faster than the body — with 8 everywhere a start of
#: ``qwen3-next.train-8k`` took 10.6 s (12%) longer than with XLA's
#: form.  Measured on one v5e at 8,192 rows of 16 / 32 heads, seconds
#: to compile | ms a call, holding 8, 4, 2, 1: the inverse 3.79 | 3.51,
#: 1.47 | 3.60, 0.64 | 3.75, 0.33 | 4.08; the sweep 0.75 | 1.11, 0.55 |
#: 1.20, 0.27 | 1.29, 0.16 | 1.55; the reverse sweep 2.35 | 3.67, 1.10 |
#: 3.73, 0.56 | 3.93, 0.45 | 4.00 (PERF.md §6, PR 36).
UNROLL = {"gated_delta_inv": 2, "gated_delta_fwd": 8, "gated_delta_bwd": 4}


def _chunk_rows(c):
    from jax.experimental import pallas as pl
    return pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)


def _for_chunks(kernel, nc, body, reverse=False):
    """``body(c)`` for the ``nc`` chunks of a block in order (from the
    last where ``reverse``), :data:`UNROLL` of them a loop step."""
    held = math.gcd(nc, UNROLL[kernel])

    def step(i, carry):
        for c in range(held):
            c = i * held + c
            body(nc - 1 - c if reverse else c)
        return carry

    if held == nc:
        step(0, None)
    else:
        jax.lax.fori_loop(0, nc // held, step, None)


def _inv_kernel(k_ref, g_ref, b_ref, t_ref, *, r, nc):
    rows, cols = _masks()
    _, nt, _ = _products(k_ref.dtype)

    def chunk(c):
        k = k_ref[_chunk_rows(c), :]
        for j in range(r):
            decay = _decays(g_ref[j, c], rows, cols)[1]
            kb = (k.astype(jnp.float32) *
                  _to_col(b_ref[j, c], rows == cols)).astype(k.dtype)
            a = -jnp.where(cols < rows, nt(kb, k) * decay, 0.0)
            t_ref[c, :, j * CHUNK:(j + 1) * CHUNK] = _inverse(
                a, rows, cols).astype(t_ref.dtype)

    _for_chunks("gated_delta_inv", nc, chunk)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, o_ref, s_ref,
                state, *, r, nc, dv):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    cdt = q_ref.dtype
    rows, cols = _masks()
    seen, eye = cols <= rows, rows == cols
    nn, nt, tn = _products(cdt)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def chunk(c):
        at = _chunk_rows(c)
        q, k = q_ref[at, :], k_ref[at, :]
        qk = nt(q, k)
        for j in range(r):
            g_row = g_ref[j, c]
            gc_col, decay, last_col, _, _, vb, grow, kg = _local(
                k, v_ref[at, j * dv:(j + 1) * dv], g_row, b_ref[j, c],
                rows, cols)
            t = t_ref[c, :, j * CHUNK:(j + 1) * CHUNK]
            w = nn(t, kg).astype(cdt)
            u = nn(t, vb)
            qg = (q.astype(f32) * grow).astype(cdt)
            kd = (k.astype(f32) * jnp.exp(last_col - gc_col)).astype(cdt)
            inner = jnp.where(seen, qk * decay, 0.0).astype(cdt)
            carried = state[j]
            s_ref[j, c] = carried
            read = carried.astype(cdt)
            fresh = (u - nn(w, read)).astype(cdt)
            o_ref[at, j * dv:(j + 1) * dv] = \
                nn(qg, read) + nn(inner, fresh)
            state[j] = _keep_row(g_row, eye, dv) * carried + \
                tn(kd, fresh)

    _for_chunks("gated_delta_fwd", nc, chunk)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *, r, nc,
                dv):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    cdt = q_ref.dtype
    rows, cols = _masks()
    seen, before, eye = cols <= rows, cols < rows, rows == cols
    nn, nt, tn = _products(cdt)
    _, hi_nt, hi_tn = _products(f32)

    def total(x):
        return jnp.sum(x, axis=1, keepdims=True)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    def chunk(c):
        at = _chunk_rows(c)
        q, k = q_ref[at, :], k_ref[at, :]
        qf, kf = q.astype(f32), k.astype(f32)
        qk = nt(q, k)
        dq = jnp.zeros(qf.shape, f32)
        dk = jnp.zeros(kf.shape, f32)
        for j in range(r):
            head = slice(j * dv, (j + 1) * dv)
            v = v_ref[at, head]
            g_row = g_ref[j, c]
            gc_col, decay, last_col, b_col, kb, vb, grow, kg = _local(
                k, v, g_row, b_ref[j, c], rows, cols)
            # the forward sweep's terms, rebuilt
            t = t_ref[c, :, j * CHUNK:(j + 1) * CHUNK]
            w = nn(t, kg).astype(cdt)
            qg = (qf * grow).astype(cdt)
            fade = jnp.exp(last_col - gc_col)
            kd = (kf * fade).astype(cdt)
            pd = jnp.where(before, nt(kb, k) * decay, 0.0)      # -A
            md = jnp.where(seen, qk * decay, 0.0)
            carried = s_ref[j, c]
            read = carried.astype(cdt)
            fresh = (nn(t, vb) - nn(w, read)).astype(cdt)
            keep = _keep_row(g_row, eye, dv)
            # the sweep backwards
            do = do_ref[at, head].astype(cdt)
            ds = dstate[j]
            dsr = ds.astype(cdt)
            dfresh = tn(md.astype(cdt), do) + nn(kd, dsr)
            dfr = dfresh.astype(cdt)
            dqg = nt(do, read)
            dmd = jnp.where(seen, nt(do, fresh), 0.0)
            dkd = nt(fresh, dsr)
            dkeep = jnp.sum(total(ds * carried), axis=0, keepdims=True)
            dw = (-nt(dfr, read)).astype(cdt)
            dstate[j] = keep * ds + tn(qg, do) - tn(w, dfr)
            # through W = T (K_b exp G), U = T V_b and the inverse
            dt = nt(dw, kg) + nt(dfr, vb)
            dkg = tn(t, dw)
            dvb = tn(t, dfr)
            t32 = t.astype(f32)
            da = -jnp.where(before, hi_tn(t32, hi_nt(dt, t32)), 0.0)
            dpd = (da * decay).astype(cdt)
            dqk = (dmd * decay).astype(cdt)
            through = da * pd + dmd * md           # d decay * decay
            dkb = nn(dpd, k) + dkg * grow
            dk = dk + tn(dpd, kb) + tn(dqk, q) + dkd * fade + dkb * b_col
            dq = dq + nn(dqk, k) + dqg * grow
            dv_ref[at, head] = (dvb * b_col).astype(dv_ref.dtype)
            dbeta = total(dkb * kf) + total(dvb * v.astype(f32))
            dfade = total(dkd * kf) * fade
            dlast = jnp.sum(dfade, axis=0, keepdims=True) + \
                dkeep * keep[:, :1]
            dgc = (total(dkg * kb.astype(f32)) + total(dqg * qf)) * grow \
                - dfade + total(through) \
                - _to_col(jnp.sum(through, axis=0, keepdims=True), eye)
            dgc = dgc + jnp.where(rows[:, :1] == CHUNK - 1, dlast, 0.0)
            # g reaches gc through a cumulated sum: sum the rows after
            dg_ref[j, c] = jnp.sum(jnp.where(rows >= cols, dgc, 0.0),
                                   axis=0, keepdims=True)
            db_ref[j, c] = _to_row(dbeta, eye)
        dq_ref[at, :] = dq.astype(dq_ref.dtype)
        dk_ref[at, :] = dk.astype(dk_ref.dtype)

    _for_chunks("gated_delta_bwd", nc, chunk, reverse=True)


# -- the calls -------------------------------------------------------------


def _specs(B, S, Hk, Hv, Dk, Dv, backward):
    """The grid (B, Hk, blocks of chunks), ``r``, the chunks a block,
    and the ``BlockSpec``s of a key head's q / k rows, of its value
    heads' v / o rows, of their g / beta rows, of their ``T`` and of
    their states.  ``backward`` walks the blocks from the sequence's
    end."""
    from jax.experimental import pallas as pl
    r, N = Hv // Hk, S // CHUNK
    nc = _block_chunks(N)
    blocks = N // nc

    def at(i):
        return blocks - 1 - i if backward else i

    def rows_of(width):
        return pl.BlockSpec((None, nc * CHUNK, width),
                            lambda b, h, i: (b, at(i), h))

    def a_head(*block):
        return pl.BlockSpec((None,) + block,
                            lambda b, h, i: (b, h, at(i), 0, 0))

    return ((B, Hk, blocks), r, nc, rows_of(Dk), rows_of(r * Dv),
            a_head(r, nc, 1, CHUNK), a_head(None, nc, CHUNK, r * CHUNK),
            a_head(r, nc, Dk, Dv))


def _params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _inverse_call(k, g, beta, Hk, Hv, interpret):
    """k (B, S, Hk·Dk), g, beta (B, Hv, N, 1, C) -> T (B, Hk, N, C,
    r·C) in k's type."""
    from jax.experimental import pallas as pl
    B, S, _ = k.shape
    grid, r, nc, keys, _, gates, inverses, _ = _specs(
        B, S, Hk, Hv, k.shape[2] // Hk, 0, backward=False)
    return pl.pallas_call(
        functools.partial(_inv_kernel, r=r, nc=nc),
        grid=grid,
        in_specs=[keys, gates, gates],
        out_specs=inverses,
        out_shape=jax.ShapeDtypeStruct(
            (B, Hk, S // CHUNK, CHUNK, r * CHUNK), k.dtype),
        compiler_params=_params(),
        interpret=interpret,
        name="gated_delta_inv",
    )(k, g, beta)


def _sweep_call(q, k, v, g, beta, t, Hk, Hv, interpret):
    """q, k (B, S, Hk·Dk), v (B, S, Hv·Dv), g, beta, T -> o (B, S,
    Hv·Dv) float32 and the states at the chunks' starts (B, Hv, N, Dk,
    Dv) float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, S, _ = q.shape
    Dk, Dv = q.shape[2] // Hk, v.shape[2] // Hv
    grid, r, nc, keys, values, gates, inverses, states = _specs(
        B, S, Hk, Hv, Dk, Dv, backward=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, r=r, nc=nc, dv=Dv),
        grid=grid,
        in_specs=[keys, keys, values, gates, gates, inverses],
        out_specs=(values, states),
        out_shape=(jax.ShapeDtypeStruct((B, S, Hv * Dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, Hv, S // CHUNK, Dk, Dv),
                                        jnp.float32)),
        scratch_shapes=[pltpu.VMEM((r, Dk, Dv), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gated_delta_fwd",
    )(q, k, v, g, beta, t)


def _backward_call(q, k, v, g, beta, t, s, do, Hk, Hv, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, S, _ = q.shape
    Dk, Dv = q.shape[2] // Hk, v.shape[2] // Hv
    grid, r, nc, keys, values, gates, inverses, states = _specs(
        B, S, Hk, Hv, Dk, Dv, backward=True)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        functools.partial(_bwd_kernel, r=r, nc=nc, dv=Dv),
        grid=grid,
        in_specs=[keys, keys, values, gates, gates, inverses, states,
                  values],
        out_specs=(keys, keys, values, gates, gates),
        out_shape=(like(q.shape, q.dtype), like(k.shape, k.dtype),
                   like(v.shape, v.dtype), like(g.shape, jnp.float32),
                   like(beta.shape, jnp.float32)),
        scratch_shapes=[pltpu.VMEM((r, Dk, Dv), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="gated_delta_bwd",
    )(q, k, v, g, beta, t, s, do)


# -- the differentiable entry point ----------------------------------------


def _rows_of(x, N):
    """g or beta (B, S, Hv) -> (B, Hv, N, 1, C): a chunk's values a row
    of lanes."""
    B, _, Hv = x.shape
    return x.transpose(0, 2, 1).reshape(B, Hv, N, 1, CHUNK)


def _flat(q, k, v, g, beta):
    B, S, Hk, Dk = q.shape
    Hv = v.shape[2]
    N = S // CHUNK
    return (q.reshape(B, S, Hk * Dk), k.reshape(B, S, Hk * Dk),
            v.reshape(B, S, Hv * v.shape[3]), _rows_of(g, N),
            _rows_of(beta, N))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, interpret):
    return _rule_fwd(q, k, v, g, beta, interpret)[0]


def _rule_fwd(q, k, v, g, beta, interpret):
    q2, k2, v2, g2, b2 = _flat(q, k, v, g, beta)
    heads = (q.shape[2], v.shape[2], interpret)
    t = checkpoint_name(_inverse_call(k2, g2, b2, *heads), GD_INVERSE)
    o, s = _sweep_call(q2, k2, v2, g2, b2, t, *heads)
    o, s = checkpoint_name(o, GD_OUT), checkpoint_name(s, GD_STATES)
    # Everything below derives from the named values, and everything
    # the backward reads beside the operands is one of them: a
    # checkpoint that saves the names keeps neither forward kernel in
    # its recompute.  q, k, v, g and beta are not named — the taps and
    # gates that make them are recomputed.
    return o.reshape(v.shape), (q, k, v, g, beta, t, s)


def _rule_bwd(interpret, res, do):
    q, k, v, g, beta, t, s = res
    B, S, Hv, _ = v.shape
    dq, dk, dv, dg, db = _backward_call(
        *_flat(q, k, v, g, beta), t, s, do.reshape(B, S, -1),
        q.shape[2], Hv, interpret)

    def gate(x):
        return x.reshape(B, Hv, S).transpose(0, 2, 1)

    return (dq.reshape(q.shape), dk.reshape(k.shape),
            dv.reshape(v.shape), gate(dg), gate(db))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta(q, k, v, g, beta, interpret=False):
    """``ops.linear_attention.gated_delta_rule`` through the kernels:
    q, k (B, S, Hk, Dk), v (B, S, Hv, Dv), g, beta (B, S, Hv) -> o
    (B, S, Hv, Dv) float32, differentiable in all five.  The caller
    has checked :func:`supports`; ``interpret`` (the CPU's tests) runs
    the kernels as plain jax ops and lifts the lane-tile contract."""
    f32 = jnp.float32
    return _rule(q, k.astype(q.dtype), v.astype(q.dtype), g.astype(f32),
                 beta.astype(f32), bool(interpret))
