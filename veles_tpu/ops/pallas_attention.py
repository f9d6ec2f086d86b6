"""Pallas flash attention tuned to THIS repo's LM geometry.

Round-5 measured the two off-the-shelf Pallas kernels (flash, splash)
LOSING to XLA's fused full attention at the bench geometry
(B=8/S=1024/H=16/D=128: XLA 7.8 ms, flash 10.5, splash 10.9 fwd+bwd)
— their block shapes are tuned for large-batch GPU-style launches,
not a 128-lane head dim at batch 8.  This kernel makes the opposite
choices, for exactly one geometry family:

  * D is the FULL lane width (D % 128 == 0, up to 512) — one q/k/v
    row is one (or a few) native (8, 128) tiles, no head-dim blocking
    ever — or HALF of it, D = 64 (below);
  * k/v for a (batch·head) slice of ONE call live WHOLE in VMEM
    (``MAX_SEQ`` = 2048 × D=128 × 4 B = 1 MB each — a fraction of
    16 MB), so the only streaming dimension is the query block: grid
    (B·H, S/block_q), with the key loop a ``fori_loop`` over VMEM,
    never HBM.  ``MAX_SEQ`` bounds a CALL, not a sequence (ISSUE 33):
    a longer one, a multiple of it, is walked in chunks of
    ``MAX_SEQ`` queries and keys — one :func:`flash_chunk` call for
    every pair of chunks the mask leaves anything of, at the pair's
    global origins, the partials of a query chunk merged by lse
    (:func:`_chunked`: the ring's composition below, on one chip), so
    k/v stream from HBM a chunk at a time and no S × S array exists;
  * a CAUSAL call walks only the score tiles it can see (ISSUE 30):
    each query block's key loop ends at the last key block that
    holds a column ≤ its last row, and the dk/dv kernel's query loop
    starts at the first query block that holds a row ≥ its first
    column — the tiles past the diagonal are exact zeros (``p = 0``,
    the carry unchanged), so leaving them out gives the same bits.
    The walk is two stretches: the tiles wholly below the diagonal,
    with no causal mask at all, and the tiles the diagonal crosses.
    The bounds are computed IN the kernel from the global origins
    and ``program_id`` (:func:`_key_stretches`,
    :func:`_query_stretches`) because the ring's origins are traced;
    :func:`flash_tiles` is the same arithmetic on Python ints, and
    what a trace counts.  ``kv_len`` keeps its own mask on every
    tile and skips nothing;
  * a ``window`` beside ``causal`` (row ``i`` sees key ``j`` iff
    ``0 ≤ i − j < window``: sliding-window attention, ISSUE 33) gives
    the walk a LOWER bound as the diagonal gives it an upper one —
    three stretches: the tiles the window's edge crosses (masked),
    the tiles seen whole (no mask), the tiles the diagonal crosses
    (masked) — so a window layer's work follows the window: at (512,
    512) tiles a causal call at 8,192 visits 136 of 256 tiles, a
    call with a window of 2,048 visits 70;
  * matmul operands are bf16 (MXU-native), accumulation f32
    (``preferred_element_type``), the online-softmax statistics f32 —
    the same contract as ops/attention's bf16 mode;
  * the backward recomputes probabilities from the saved logsumexp
    (FLOPs are free at this arithmetic intensity; HBM traffic is
    not): one kernel produces dq gridded over query blocks, one
    produces dk/dv gridded over key blocks — no atomics, no
    cross-block races;
  * the forward rule NAMES what only the kernel can rebuild, its
    output and logsumexp rows (``FLASH_OUT``, ``FLASH_LSE``): the
    layers' checkpoint (``znicz.attention.checkpointed``) keeps the
    two and its recompute calls no kernel (ISSUE 32).

Head size 64 (OPT-125M…1.3B, GPT-2, BERT, T5; ISSUE 27) runs the
SAME three training kernels with the same block shapes: a
(1, block, 64) block spans the array's whole last dimension, which
Mosaic takes as it is.  What a 64-wide block costs: the operands
reach the call as ``bf16[B·H, S, 64]{T(8,128)(2,1)}``, each row
padded to a 128-lane tile in HBM and in VMEM, so the DMA moves and
VMEM holds what head 128 does (not twice the rows); a contraction
over 64 (q·kᵀ) and an output 64 wide (p·v) each fill half of the
128 × 128 MXU and take head 128's passes; the (block_q, block_k)
score-tile work does not depend on D.  Measured in ``opt-1.3b.train``
against ``opt-6.7b.train`` (B·H = 128, S = 2048 in both; one v5e, ms
a call, PERF.md §5): before the causal walk ``flash_fwd`` 2.433 at
head 64 / 2.421 at head 128, ``flash_dq`` 2.524 / 2.524,
``flash_dkv`` 3.342 / 3.279 — half the matmul work in the same time,
and a third of what XLA's materialised S × S scores took; with it
1.917 / 1.818 / 2.408 at head 128 and the four calls of a block 8.05
against 8.06 ms.  The tile work is MXU-bound (a ``dot_general`` on
f32 operands is ONE bf16 pass in Mosaic, so the operand dtype does
not show either), and 0.67–0.92 ms of a call is operand DMA and
epilogue that no tile accounts for (PERF.md §6, PR 30).  Only
:func:`supports` admits head 64; the ring and decode contracts stay
lane-native until a cell runs them.

Since ISSUE 13 the kernel is RESUMABLE and MULTI-CHIP-composable:

  * the public contract carries the running softmax statistics — the
    forward returns ``(out, lse)`` and the custom VJP accepts an lse
    cotangent (dL/ds gains a ``+ g_lse·p`` term, folded into the
    existing delta row for free: ``delta' = Σ dO·O − g_lse``), so a
    caller may hold partial results open across kernel invocations;
  * :func:`flash_chunk` runs one partial over a k/v CHUNK with
    GLOBAL causal offsets (the ring streams shards whose true
    positions the kernel must mask by — offsets arrive as traced
    scalars, (1, 1) i32 operands read inside the kernel, because a
    ring step's source rank is data-dependent under ``shard_map``);
  * :func:`merge_partials` folds two partials by lse —
    ``lse = logaddexp(lse₁, lse₂); out = Σᵢ exp(lseᵢ − lse)·outᵢ``
    — the exact streaming-softmax combine, every exponent ≤ 0 so the
    merge is unconditionally stable; :func:`flash_resume` is the
    carry-shaped wrapper (``(out, lse)`` IS the ``(acc, m, l)``
    triple in collapsed form: ``out = acc/l``, ``lse = m + log l``);
  * :func:`pallas_decode_attention` is the decode-shaped variant
    (S_q ∈ 1..``DECODE_MAX_Q``): a k/v-SPLIT grid over the gathered
    paged table — each program owns one key block, emits its partial
    ``(out, lse)``, and a cross-block lse merge combines them — so
    serving's one-token steps ride the kernel without a VMEM-whole
    sequence bound (forward-only; decode has no backward).

Like pallas_lrn.py, the module ships the kernel and its parity oracle
(ops/attention.blockwise_attention, which the tests pin).  Dispatch
(ops/attention._try_pallas, the ring body, export's decode gate)
selects the kernel by PLATFORM (``backends.tpu_available``) and by
the ``supports*`` geometry contracts below — off a TPU the XLA
formulation is what runs; on a TPU a kernel that does not lower is
an error that propagates, never a quiet switch to another path.
tests/test_tpu_compile.py compiles every kernel here for a described
v5e at the geometries the LM trains and serves at.

HBM-traffic budget at the bench geometry (B=8, S=1024, H=16, D=128):
q/k/v/o are 64 MB each in f32; the fwd reads q/k/v once and writes
o + lse ≈ 0.26 GB, the bwd reads them + do and writes dq/dk/dv ≈
0.45 GB — ~0.9 ms at 819 GB/s vs the 6.4 GB (7.8 ms) the XLA
formulation moves through its materialized f32 score/probability
tensors.  That 8× traffic cut is the whole thesis; BENCHNOTES r6/r9
carry the A/B protocol (``bench.py --lm --attn-stages=...``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import resilience

NEG_INF = -1e30

#: Default query block (the forward's and dq's grid step, the dk/dv
#: kernel's loop step) and key block (their loop step, its grid
#: step).  The pair sets how fine the causal walk is — a call at
#: S = 2048 visits 10 of its 16 (512, 512) tiles, 6 of 8 at (512,
#: 1024), 36 of 64 at (256, 256) — against what a loop step costs
#: (the trip count is traced, so steps are not overlapped): a key
#: block under 512 costs more than it skips, and a block of 1024
#: skips too little.  Measured on one v5e,
#: forward twice + dq + dk/dv, ms, bf16 inputs (PERF.md §6, PR 30):
#: (512, 512) 8.75 at (B·H, S, D) = (128, 2048, 128), 10.95 at (128,
#: 2048, 64), 22.69 at (256, 2048, 64) — the least of the sixteen
#: pairs of 128…1024 at all three, in each kernel; (512, 1024) reads
#: 9.71 / 11.92 / 24.49, (256, 256) 12.76 / 15.01 / 30.74.  One
#: shape for every head size.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512

#: ``jax.ad_checkpoint.checkpoint_name``s of what only the forward
#: kernel can rebuild: its output and its log-sum-exp rows, as the
#: (B·H, S, D) and (B·H, S) arrays the backward kernels read.  A
#: ``jax.checkpoint`` whose policy saves these names
#: (``znicz.attention.checkpointed``) keeps them from the forward
#: pass, and its recompute holds no ``flash_fwd`` (ISSUE 32); under
#: any other checkpoint, or none, a name is an identity.
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"

#: Geometry contract: lane-native head dim, tile-aligned sequence.
LANE = 128
#: The one head dim below a lane row that :func:`supports` admits.
HALF_LANE = LANE // 2

#: Upper bound of ONE kernel call's sequence: the kernel keeps a
#: (batch·head) slice's whole k/v in VMEM (S × D × 4 B each,
#: double-buffered) next to its f32 score tiles; the backward's
#: dk/dv kernel also keeps q and dO whole.  S=2048/D=128 forward and
#: backward compile inside the v5e's 16 MB scoped VMEM
#: (tests/test_tpu_compile.py); past it the tiles stop fitting.  A
#: longer sequence (a multiple of it) is walked in chunks of
#: ``MAX_SEQ`` rows and keys, one :func:`flash_chunk` call a visible
#: chunk pair, the partials merged by lse — the ring's composition
#: on one chip (:func:`_chunked`).
MAX_SEQ = 2048

#: Decode-kernel query bound: past this many query rows the chunk is
#: a prefill, which the full flash kernel (or the dense cached path)
#: serves better than a split-k/v decode launch.
DECODE_MAX_Q = 16
#: Decode key-block default: the split-k/v grid step over the
#: gathered paged table.
DEFAULT_DECODE_BLOCK_K = 512


def _pick_block(n, want):
    """Largest power-of-two divisor of ``n`` that is <= ``want``
    (n is a multiple of LANE by the ``supports`` contract)."""
    b = 1
    while b * 2 <= want and n % (b * 2) == 0:
        b *= 2
    return b


def supports(q_shape, k_shape, kv_len=None):
    """Whether the kernel's geometry contract holds: self-attention
    ((B, S, H, D) with equal q/k sequence), D lane-native (a multiple
    of 128 up to 512) or 64, S tile-aligned up to ``MAX_SEQ`` or a
    multiple of it.  ``kv_len`` (the blockwise padding contract) is
    supported as a static mask bound, within ``MAX_SEQ``."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    B, S, H, D = q_shape
    if k_shape[1] != S:
        return False
    if D != HALF_LANE and (D % LANE or D > 4 * LANE):
        return False
    if S % LANE or S < LANE:
        return False
    if S > MAX_SEQ and (S % MAX_SEQ or kv_len is not None):
        return False
    if kv_len is not None and not isinstance(kv_len, int):
        return False
    return True


def supports_ring(q_shape, k_shape, interpret=False):
    """The :func:`flash_chunk` geometry contract — one ring step's
    local queries against one streamed k/v shard.  Unlike
    :func:`supports` the q and k lengths may differ (a ring over an
    uneven composition could stream shards of another extent), but
    batch/heads/head-dim must agree.  ``interpret`` relaxes the
    lane/tile alignment: the interpret kernel is plain jax ops, so
    the tiny tier-1 geometries (D=4, S=8 shards) are parity-testable
    on CPU while compiled dispatch keeps the real-TPU tile contract.
    """
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    B, Sq, H, D = q_shape
    Bk, Sk, Hk, Dk = k_shape
    if (B, H, D) != (Bk, Hk, Dk):
        return False
    if Sq < 1 or Sk < 1:
        return False
    if interpret:
        return True
    if D % LANE or D > 4 * LANE:
        return False
    for S in (Sq, Sk):
        if S % LANE or S < LANE or S > MAX_SEQ:
            return False
    return True


def supports_decode(q_shape, k_shape, interpret=False):
    """The :func:`pallas_decode_attention` contract: a small query
    chunk (S_q ≤ ``DECODE_MAX_Q`` — decode steps, not prefills)
    against a long gathered key table.  The table has NO ``MAX_SEQ``
    bound — the split-k/v grid streams it block by block instead of
    holding it whole in VMEM.  ``interpret`` relaxes tile alignment
    exactly as in :func:`supports_ring`."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    B, Sq, H, D = q_shape
    Bk, L, Hk, Dk = k_shape
    if (B, H, D) != (Bk, Hk, Dk):
        return False
    if not 1 <= Sq <= DECODE_MAX_Q:
        return False
    if L < 1:
        return False
    if interpret:
        return True
    if D % LANE or D > 4 * LANE:
        return False
    if L % LANE:
        return False
    return True


# -- kernels -------------------------------------------------------------


def _mask_tile(grows0, gcols0, lcols0, bq, bk, causal, kv_len,
               transposed=False, window=None):
    """(bq, bk) boolean attend-mask for one score tile — (bk, bq)
    when ``transposed`` (the dk/dv kernel's key-major tile) — or
    None when nothing masks.  Causality — and with ``window`` how far
    back a row sees, ``row − col < window`` — is judged on GLOBAL
    positions (row/col origins ``grows0``/``gcols0`` — possibly
    traced scalars: the ring offsets are data-dependent), while the
    ``kv_len`` padding bound applies to the chunk's LOCAL columns
    (origin ``lcols0``) — it is the caller's own padding, wherever
    the chunk sits globally."""
    shape, qdim, kdim = ((bk, bq), 1, 0) if transposed else \
        ((bq, bk), 0, 1)
    mask = None
    if causal:
        rows = grows0 + jax.lax.broadcasted_iota(jnp.int32, shape, qdim)
        cols = gcols0 + jax.lax.broadcasted_iota(jnp.int32, shape, kdim)
        mask = rows >= cols
        if window is not None:
            mask = jnp.logical_and(mask, rows - cols < window)
    if kv_len is not None:
        cols = lcols0 + jax.lax.broadcasted_iota(jnp.int32, shape, kdim)
        kvm = cols < kv_len
        mask = kvm if mask is None else jnp.logical_and(mask, kvm)
    return mask


def _dot(a, b, od, trans_b=False):
    """MXU matmul: ``od`` operands (bf16 in production, f32 for the
    exact-parity tests), f32 accumulation."""
    dims = (((1,), (1,) if trans_b else (0,)), ((), ()))
    return jax.lax.dot_general(a.astype(od), b.astype(od), dims,
                               preferred_element_type=jnp.float32)


def _col_to_row(col):
    """(n, 1) → (1, n).  The per-query statistics (lse, delta) are
    kept as lane-dense ROWS — a column is padded to 128 lanes in
    VMEM, and with q, dO, lse and delta resident as columns the
    S=2048 backward asked for 17.7 MB of a 16 MB limit — while the
    kernels that walk query blocks want them as COLUMNS next to the
    (bq, bk) score tile.  The relayout is one aligned 2-d transpose
    of the lane-broadcast column."""
    n = col.shape[0]
    return jnp.broadcast_to(col, (n, LANE)).T[0:1, :]


def _row_to_col(row):
    """(1, n) → (n, 1): the inverse of :func:`_col_to_row`."""
    n = row.shape[1]
    return jnp.broadcast_to(row, (LANE, n)).T[:, 0:1]


def _clip(x, lo, hi):
    """``min(max(x, lo), hi)`` for Python ints (the tile count, made
    at trace time) and for traced scalars (the kernels) alike."""
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _key_stretches(causal, grows0, bq, gcols0, bk, nk, window=None):
    """The key blocks ONE query block walks, as ``(lo, hi, masked)``
    stretches of block indices, in ascending order.  The query block
    holds global rows ``grows0 … grows0 + bq − 1``; key block ``j``
    holds global columns ``gcols0 + j·bk … gcols0 + (j + 1)·bk − 1``.
    A causal call visits the blocks that hold a column ≤ the last
    row, ``j < cdiv(grows0 + bq − gcols0, bk)``: first those wholly
    below the diagonal (last column ≤ first row: no causal mask),
    then those the diagonal crosses.  The blocks past them are all
    zeros and are not visited.  The origins may be traced scalars
    (the ring's are data-dependent), so the bounds may be too.

    With a ``window`` (row ``i`` sees column ``j`` iff ``0 ≤ i − j <
    window``) the walk has a lower bound as it has an upper one: it
    starts at the first block that holds a column the FIRST row still
    sees, ``j ≥ (grows0 − window + 1 − gcols0) // bk``, and the blocks
    up to the first one that the LAST row sees whole, ``j ≥
    cdiv(grows0 + bq − window − gcols0, bk)``, are crossed by the
    window's edge and masked like the diagonal's."""
    if not causal:
        return ((0, nk, False),)
    hi = _clip((grows0 + bq - gcols0 + bk - 1) // bk, 0, nk)
    below = _clip((grows0 - gcols0 + 1) // bk, 0, hi)
    if window is None:
        return ((0, below, False), (below, hi, True))
    lo = _clip((grows0 - window + 1 - gcols0) // bk, 0, below)
    inside = _clip((grows0 + bq - window - gcols0 + bk - 1) // bk, lo,
                   below)
    return ((lo, inside, True), (inside, below, False),
            (below, hi, True))


def _query_stretches(causal, gcols0, bk, grows0, bq, nq, window=None):
    """The query blocks ONE key block walks (the dk/dv kernel's
    loop): the mirror of :func:`_key_stretches`.  Query block ``i``
    is visited if it holds a row ≥ the key block's first column,
    ``i ≥ (gcols0 − grows0) // bq``: first the blocks the diagonal
    crosses, then those wholly below it (first row ≥ last column).
    With a ``window`` the walk ends behind the last block that holds
    a row the LAST column still reaches, and the blocks past the last
    one the FIRST column reaches whole are masked."""
    if not causal:
        return ((0, nq, False),)
    lo = _clip((gcols0 - grows0) // bq, 0, nq)
    below = _clip((gcols0 + bk - 1 - grows0 + bq - 1) // bq, lo, nq)
    if window is None:
        return ((lo, below, True), (below, nq, False))
    hi = _clip((gcols0 + bk + window - 2 - grows0) // bq + 1, below, nq)
    inside = _clip((gcols0 + window - grows0) // bq, below, hi)
    return ((lo, below, True), (below, inside, False),
            (inside, hi, True))


def _walk(stretches, tile, carry):
    """Folds ``tile(index, carry, diagonal)`` over the stretches
    (``diagonal``: whether an edge of the mask crosses the tile)."""
    for lo, hi, diagonal in stretches:
        carry = jax.lax.fori_loop(
            lo, hi, functools.partial(tile, diagonal=diagonal), carry)
    return carry


def flash_tiles(q_len, k_len, block_q, block_k, q_offset=0,
                k_offset=0, causal=True, window=None):
    """``(visited, total)`` score tiles of ONE (batch·head) slice of
    a forward call: what the key loops of its ``q_len // block_q``
    programs visit, and what the grid holds (the dq kernel walks the
    same tiles, the dk/dv kernel the same set from the other side).
    Static offsets only — it is the count a trace records
    (``attention.flash.tiles_visited`` / ``.tiles_total``) and the
    tests pin; the kernels get their bounds from the same
    :func:`_key_stretches`."""
    nq, nk = q_len // block_q, k_len // block_k
    visited = sum(
        hi - lo for i in range(nq) for lo, hi, _ in _key_stretches(
            causal, q_offset + i * block_q, block_q, k_offset,
            block_k, nk, window=window))
    return visited, nq * nk


def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, *, scale, causal, kv_len, block_k,
                kv_seq_len, od, window=None):
    from jax.experimental import pallas as pl
    bq = q_ref.shape[1]
    D = q_ref.shape[2]
    i = pl.program_id(1)
    q = q_ref[0]
    grows0 = qoff_ref[0, 0] + i * bq
    koff = koff_ref[0, 0]

    def tile(j, carry, diagonal):
        acc, m, l = carry
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, kb, od, trans_b=True) * scale
        mask = _mask_tile(grows0, koff + j * block_k, j * block_k,
                          bq, block_k, diagonal, kv_len,
                          window=window)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        bm = s.max(axis=1, keepdims=True)
        new_m = jnp.maximum(m, bm)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        new_l = l * corr + p.sum(axis=1, keepdims=True)
        acc = acc * corr + _dot(p, vb, od)
        return acc, new_m, new_l

    # A chunk wholly after the queries is walked zero times and
    # leaves the carry as it starts: out 0 and lse ≈ -1e30 below.
    acc, m, l = _walk(
        _key_stretches(causal, grows0, bq, koff, block_k,
                       kv_seq_len // block_k, window=window),
        tile,
        (jnp.zeros((bq, D), jnp.float32),
         jnp.full((bq, 1), NEG_INF, jnp.float32),
         jnp.zeros((bq, 1), jnp.float32)))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # Fully-masked rows keep m = NEG_INF so lse ≈ -1e30 (finite, not
    # -inf); the bwd kernels do NOT rely on exp(s - lse) underflowing
    # for such rows — they re-mask p with jnp.where before use.
    # Finite lse is also what makes the cross-chunk merge total: a
    # chunk a row attends nothing in contributes weight exp(-1e30 -
    # lse_total) = 0, never NaN.
    lse_ref[0] = _col_to_row(m + jnp.log(l_safe))


def _dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, dq_ref, *, scale, causal, kv_len,
               block_k, kv_seq_len, od, window=None):
    from jax.experimental import pallas as pl
    bq = q_ref.shape[1]
    D = q_ref.shape[2]
    i = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = _row_to_col(lse_ref[0])
    delta = _row_to_col(delta_ref[0])
    grows0 = qoff_ref[0, 0] + i * bq
    koff = koff_ref[0, 0]

    def tile(j, dq, diagonal):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, kb, od, trans_b=True) * scale
        mask = _mask_tile(grows0, koff + j * block_k, j * block_k,
                          bq, block_k, diagonal, kv_len,
                          window=window)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = _dot(do, vb, od, trans_b=True)
        ds = p * (dp - delta) * scale
        return dq + _dot(ds, kb, od)

    dq_ref[0] = _walk(
        _key_stretches(causal, grows0, bq, koff, block_k,
                       kv_seq_len // block_k, window=window),
        tile, jnp.zeros((bq, D), jnp.float32)).astype(dq_ref.dtype)


def _dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, *, scale, causal,
                kv_len, block_q, q_seq_len, od, window=None):
    from jax.experimental import pallas as pl
    bk = k_ref.shape[1]
    D = k_ref.shape[2]
    j = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    qoff = qoff_ref[0, 0]
    gcols0 = koff_ref[0, 0] + j * bk
    lcols0 = j * bk

    def tile(i, carry, diagonal):
        # Key-major tiles (bk, bq): lse/delta arrive as (1, bq) rows
        # and broadcast down the key axis as they are, and dk/dv
        # accumulate without transposing a score-sized tile.
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :]
        dob = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i, 1), :]
        delta = delta_ref[0, pl.ds(i, 1), :]
        st = _dot(k, qb, od, trans_b=True) * scale
        mask = _mask_tile(qoff + i * block_q, gcols0, lcols0,
                          block_q, bk, diagonal, kv_len,
                          transposed=True, window=window)
        if mask is not None:
            st = jnp.where(mask, st, NEG_INF)
        pt = jnp.exp(st - lse)
        if mask is not None:
            pt = jnp.where(mask, pt, 0.0)
        dv = dv + _dot(pt, dob, od)
        dpt = _dot(v, dob, od, trans_b=True)
        dst = pt * (dpt - delta) * scale
        dk = dk + _dot(dst, qb, od)
        return dk, dv

    dk, dv = _walk(
        _query_stretches(causal, gcols0, bk, qoff, block_q,
                         q_seq_len // block_q, window=window),
        tile,
        (jnp.zeros((bk, D), jnp.float32),
         jnp.zeros((bk, D), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# -- pallas_call plumbing ------------------------------------------------


def _row_spec(block, D, which):
    """BlockSpec over (BH, S, D) arrays: ``which`` "blocked" walks
    grid dim 1 in ``block``-row steps, "whole" keeps the full
    sequence resident per (batch·head)."""
    from jax.experimental import pallas as pl
    if which == "blocked":
        return pl.BlockSpec((1, block, D), lambda b, i: (b, i, 0))
    return pl.BlockSpec((1, block, D), lambda b, i: (b, 0, 0))


def _stat_row_spec(block):
    """BlockSpec over the per-query statistics (lse/delta) as a
    lane-dense (BH, 1, S) row array, walked in ``block``-lane steps
    along grid dim 1 — what the kernels gridded over query blocks
    read and write."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((1, 1, block), lambda b, i: (b, 0, i))


def _stat_tile_spec(n_blocks, block):
    """BlockSpec over the same bytes seen as (BH, S/block, block) —
    one query block per sublane row, resident whole per (batch·head):
    the dk/dv kernel picks query block ``i`` with a sublane index."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((1, n_blocks, block), lambda b, i: (b, 0, 0))


def _off_spec():
    """BlockSpec for the (1, 1) i32 global-offset operands: every
    program reads the same scalar (the ring's shard origin is
    data-dependent, so it cannot be a static kernel parameter)."""
    from jax.experimental import pallas as pl
    return pl.BlockSpec((1, 1), lambda b, i: (0, 0))


def _off_operand(off):
    """Traced-or-static offset → the (1, 1) i32 kernel operand.
    Offsets cross the custom-VJP boundary as (1, 1) f32 — rank ≥ 1
    because shard_map's autodiff cannot carry a device-varying
    RANK-0 residual (the ring's offsets depend on axis_index), and
    f32 so the cotangent contract stays float (exact for any
    realistic sequence position)."""
    return jnp.asarray(off, jnp.int32).reshape(1, 1)


def _flash_fwd_flat(qf, kf, vf, qoff, koff, causal, kv_len, bq, bk,
                    od, interpret, window=None):
    """(BH, Sq, D) × (BH, Sk, D) forward: returns (out, lse)."""
    from jax.experimental import pallas as pl
    BH, Sq, D = qf.shape
    Sk = kf.shape[1]
    scale = 1.0 / (D ** 0.5)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             kv_len=kv_len, block_k=bk,
                             kv_seq_len=Sk, od=od, window=window)
    out, lse = pl.pallas_call(
        kern,
        grid=(BH, Sq // bq),
        in_specs=[_off_spec(), _off_spec(),
                  _row_spec(bq, D, "blocked"),
                  _row_spec(Sk, D, "whole"),
                  _row_spec(Sk, D, "whole")],
        out_specs=(_row_spec(bq, D, "blocked"),
                   _stat_row_spec(bq)),
        out_shape=(jax.ShapeDtypeStruct((BH, Sq, D), qf.dtype),
                   jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32)),
        interpret=interpret,
        name="flash_fwd",
    )(_off_operand(qoff), _off_operand(koff), qf, kf, vf)
    return out, lse.reshape(BH, Sq)


def _flash_bwd_flat(qf, kf, vf, of, dof, lse, dlse, qoff, koff,
                    causal, kv_len, bq, bk, od, interpret, window=None):
    from jax.experimental import pallas as pl
    BH, Sq, D = qf.shape
    Sk = kf.shape[1]
    scale = 1.0 / (D ** 0.5)
    # delta_i = Σ_d dO·O − g_lse: the lse cotangent rides the same
    # per-row correction term (dL/ds_j = p_j·(dp_j − delta + g_lse)),
    # so lifting lse into the public contract costs the kernels
    # NOTHING — tiny elementwise pass, left to XLA.
    delta = (dof.astype(jnp.float32) *
             of.astype(jnp.float32)).sum(axis=-1) - \
        dlse.astype(jnp.float32)
    offs = (_off_operand(qoff), _off_operand(koff))
    rows = (lse.reshape(BH, 1, Sq), delta.reshape(BH, 1, Sq))
    tiles = (lse.reshape(BH, Sq // bq, bq),
             delta.reshape(BH, Sq // bq, bq))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          kv_len=kv_len, block_k=bk, kv_seq_len=Sk,
                          od=od, window=window),
        grid=(BH, Sq // bq),
        in_specs=[_off_spec(), _off_spec(),
                  _row_spec(bq, D, "blocked"),
                  _row_spec(Sk, D, "whole"),
                  _row_spec(Sk, D, "whole"),
                  _row_spec(bq, D, "blocked"),
                  _stat_row_spec(bq),
                  _stat_row_spec(bq)],
        out_specs=_row_spec(bq, D, "blocked"),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), qf.dtype),
        interpret=interpret,
        name="flash_dq",
    )(*offs, qf, kf, vf, dof, *rows)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          kv_len=kv_len, block_q=bq, q_seq_len=Sq,
                          od=od, window=window),
        grid=(BH, Sk // bk),
        in_specs=[_off_spec(), _off_spec(),
                  _row_spec(Sq, D, "whole"),
                  _row_spec(bk, D, "blocked"),
                  _row_spec(bk, D, "blocked"),
                  _row_spec(Sq, D, "whole"),
                  _stat_tile_spec(Sq // bq, bq),
                  _stat_tile_spec(Sq // bq, bq)],
        out_specs=(_row_spec(bk, D, "blocked"),
                   _row_spec(bk, D, "blocked")),
        out_shape=(jax.ShapeDtypeStruct((BH, Sk, D), qf.dtype),
                   jax.ShapeDtypeStruct((BH, Sk, D), qf.dtype)),
        interpret=interpret,
        name="flash_dkv",
    )(*offs, qf, kf, vf, dof, *tiles)
    return dq, dk, dv


# -- differentiable (B, S, H, D) entry points ----------------------------


def _to_flat(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_flat(xf, B, H):
    BH, S, D = xf.shape
    return xf.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _lse_from_flat(lf, B, H):
    BH, S = lf.shape
    return lf.reshape(B, H, S).transpose(0, 2, 1)


def _lse_to_flat(l):
    B, S, H = l.shape
    return l.transpose(0, 2, 1).reshape(B * H, S)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9,
                                                    10, 11))
def _flash_lse(q, k, v, qoff, koff, causal, kv_len, bq, bk, od,
               interpret, window=None):
    """The lse-carrying flash core: (out, lse) with a backward that
    recomputes probabilities from the saved lse.  ``qoff``/``koff``
    are (1, 1) f32 arrays (global causal origins, possibly traced —
    see :func:`_off_operand` for the shape/dtype contract)."""
    out, lse = _flash_lse_fwd(q, k, v, qoff, koff, causal, kv_len,
                              bq, bk, od, interpret, window)[0]
    return out, lse


def _flash_lse_fwd(q, k, v, qoff, koff, causal, kv_len, bq, bk, od,
                   interpret, window=None):
    B, Sq, H, D = q.shape
    of, lsef = _flash_fwd_flat(_to_flat(q), _to_flat(k), _to_flat(v),
                               qoff, koff, causal, kv_len, bq, bk,
                               od, interpret, window)
    # Named in the layout the backward reads, and everything below
    # derives from the named values: a checkpoint that saves the two
    # names has no use left for the kernel in its recompute.  q, k
    # and v are not named — their projections are recomputed.
    of = checkpoint_name(of, FLASH_OUT)
    lsef = checkpoint_name(lsef, FLASH_LSE)
    out = _from_flat(of, B, H)
    lse = _lse_from_flat(lsef, B, H)
    return (out, lse), (q, k, v, of, lsef, qoff, koff)


def _flash_lse_bwd(causal, kv_len, bq, bk, od, interpret, window, res,
                   ct):
    q, k, v, of, lsef, qoff, koff = res
    do, dlse = ct
    B, Sq, H, D = q.shape
    dqf, dkf, dvf = _flash_bwd_flat(
        _to_flat(q), _to_flat(k), _to_flat(v), of, _to_flat(do),
        lsef, _lse_to_flat(dlse), qoff, koff, causal, kv_len, bq, bk,
        od, interpret, window)
    return (_from_flat(dqf, B, H), _from_flat(dkf, B, H),
            _from_flat(dvf, B, H), jnp.zeros((1, 1), jnp.float32),
            jnp.zeros((1, 1), jnp.float32))


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def check_window(causal, window):
    """``window`` as the kernels take it: None, or a positive static
    int beside ``causal`` (row ``i`` sees ``0 ≤ i − j < window``)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError("window=%r needs causal attention and at "
                         "least one column" % (window,))
    return int(window)  # lint-ok: VL101 static config int


def _chunked(q, k, v, causal, window, block_q, block_k, operand_dtype,
             interpret, chunk):
    """Attention over a sequence longer than one kernel call holds:
    queries and keys in chunks of ``chunk`` rows, one
    :func:`flash_chunk` a chunk pair in which the mask leaves anything
    to see (10 of 16 pairs for a causal call at 4 chunks, 7 with a
    window of one chunk), its tile walk bounded by the pair's GLOBAL
    origins, the pairs of one query chunk folded by lse
    (:func:`flash_resume`).  No S × S array anywhere; every partial
    carries the forward rule's two names, so a layer's checkpoint
    keeps them all and its recompute calls no kernel."""
    S = q.shape[1]
    outs = []
    for q0 in range(0, S, chunk):
        carry = None
        for k0 in range(0, S, chunk):
            if not flash_tiles(chunk, chunk, chunk, chunk, q0, k0,
                               causal, window)[0]:
                continue
            carry = flash_resume(
                carry, q[:, q0:q0 + chunk], k[:, k0:k0 + chunk],
                v[:, k0:k0 + chunk], causal=causal, q_offset=q0,
                k_offset=k0, window=window, block_q=block_q,
                block_k=block_k, operand_dtype=operand_dtype,
                interpret=interpret)
        outs.append(carry[0].astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def pallas_attention(q, k, v, causal=False, kv_len=None, block_q=None,
                     block_k=None, operand_dtype=None,
                     interpret=False, window=None, chunk=MAX_SEQ):
    """Flash attention over (B, S, H, D), differentiable (custom
    VJP).  Block shapes default to the geometry-tuned constants,
    shrunk to the largest power-of-two divisor of S — callers outside
    the ``supports`` contract must not reach here.

    ``operand_dtype``: matmul operand dtype — bf16 (default, the MXU
    contract) or f32 (the exact-parity test mode).  ``window``: a
    causal row sees the ``window`` columns up to its own (None: all
    of them).  ``chunk``: how many rows one kernel call holds (the
    tests walk small sequences in small chunks); past it the call is
    :func:`_chunked`."""
    B, S, H, D = q.shape
    if not supports(q.shape, k.shape, kv_len):
        raise ValueError(
            "geometry (%s, kv_len=%r) outside the pallas_attention "
            "contract — use ops.attention.blockwise_attention" %
            (q.shape, kv_len))
    window = check_window(causal, window)
    bq = _pick_block(min(S, chunk), block_q or DEFAULT_BLOCK_Q)
    bk = _pick_block(min(S, chunk), block_k or DEFAULT_BLOCK_K)
    od = jnp.dtype(operand_dtype or jnp.bfloat16).type
    if kv_len is not None:
        # Static by the supports() contract (isinstance(int) gate).
        kv_len = int(kv_len)  # lint-ok: VL101 static config int
    # Each TRACE counts what one (batch·head) slice of the forward
    # visits and what its grid holds, beside attention.kernel.pallas:
    # how far the causal schedule engages at this call's geometry.  A
    # call with a window counts into series labelled with it.
    visited, total = flash_tiles(S, S, bq, bk, causal=bool(causal),
                                 window=window)
    labels = None if window is None else {"window": str(window)}
    counter = resilience.stats.registry.counter
    counter("attention.flash.tiles_visited", labels).inc(visited)
    counter("attention.flash.tiles_total", labels).inc(total)
    if S > chunk:
        return _chunked(q, k, v, bool(causal), window, bq, bk, od,
                        bool(interpret), chunk)
    zero = jnp.zeros((1, 1), jnp.float32)
    out, _lse = _flash_lse(q, k, v, zero, zero, bool(causal),
                           kv_len, bq, bk, od, bool(interpret), window)
    return out


# -- the resumable (ring) contract ---------------------------------------


def flash_chunk(q, k, v, causal=False, q_offset=0, k_offset=0,
                kv_len=None, block_q=None, block_k=None,
                operand_dtype=None, interpret=False, window=None):
    """ONE flash partial: local queries (B, Sq, H, D) against one
    k/v chunk (B, Sk, H, D) whose global positions start at
    ``k_offset`` (queries at ``q_offset``) — the ring-attention step
    body.  Returns ``(out, lse)`` with ``out`` the chunk-normalized
    partial and ``lse`` (B, Sq, H) f32 its log-normalizer; fold
    partials with :func:`merge_partials`.  Offsets may be TRACED
    scalars (a ring step's source rank is data-dependent inside
    ``shard_map``); ``window`` is static and bounds the walk and the
    mask from below as ``causal`` does from above.  Differentiable: the backward recomputes
    probabilities from lse per chunk (dq/dkv kernels), and the lse
    output's own cotangent folds into the delta row — so autodiff
    through a chunk+merge composition is exact, no custom ring VJP
    needed."""
    if not supports_ring(q.shape, k.shape, interpret=interpret):
        raise ValueError(
            "geometry (%s × %s) outside the flash_chunk contract — "
            "use ops.attention's streaming formulations" %
            (q.shape, k.shape))
    Sq, Sk = q.shape[1], k.shape[1]
    bq = _pick_block(Sq, block_q or DEFAULT_BLOCK_Q)
    bk = _pick_block(Sk, block_k or DEFAULT_BLOCK_K)
    od = jnp.dtype(operand_dtype or jnp.bfloat16).type
    if kv_len is not None:
        # Static padding bound, never traced (supports_ring path).
        kv_len = int(kv_len)  # lint-ok: VL101 static config int
    qoff = jnp.asarray(q_offset, jnp.float32).reshape(1, 1)
    koff = jnp.asarray(k_offset, jnp.float32).reshape(1, 1)
    return _flash_lse(q, k, v, qoff, koff, bool(causal), kv_len, bq,
                      bk, od, bool(interpret),
                      check_window(causal, window))


def merge_partials(o1, lse1, o2, lse2):
    """Folds two flash partials by lse:
    ``lse = logaddexp(lse₁, lse₂)``;
    ``out = exp(lse₁ − lse)·o₁ + exp(lse₂ − lse)·o₂``.
    Every exponent is ≤ 0, so the merge is unconditionally stable,
    and a void partial (lse ≈ −1e30 from a fully-masked chunk)
    contributes weight exp(−1e30 − lse) = 0 — finite, never NaN.
    Associative and commutative: any merge tree over the ring steps
    produces the same softmax."""
    lse1 = lse1.astype(jnp.float32)
    lse2 = lse2.astype(jnp.float32)
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    out = (o1.astype(jnp.float32) * w1 +
           o2.astype(jnp.float32) * w2).astype(o1.dtype)
    return out, lse


def flash_resume(carry, q, k, v, **kwargs):
    """The carry-shaped resumable entry: folds one more k/v chunk
    into a running ``(out, lse)`` carry (None starts one).  The
    carry IS the streaming-softmax ``(acc, m, l)`` state in
    collapsed form — ``out = acc/l``, ``lse = m + log l`` — which is
    the only shape the cross-chunk combine needs.  The carried
    partial is HELD f32 whatever the chunk dtype (the merge's output
    dtype follows its first operand): a bf16 activation stream must
    round once when the caller finishes, not once per folded chunk —
    the single-accumulator discipline the lax streaming scan keeps.
    kwargs are :func:`flash_chunk`'s."""
    o_i, lse_i = flash_chunk(q, k, v, **kwargs)
    if carry is None:
        return o_i.astype(jnp.float32), lse_i
    return merge_partials(carry[0], carry[1], o_i, lse_i)


# -- the decode-shaped kernel --------------------------------------------


def _decode_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *,
                   scale, od):
    """One key block's flash partial for a tiny query chunk: the
    grid splits the KEY axis (each program owns one block of the
    gathered paged table), and the cross-block combine happens
    outside by lse merge — no carried state between programs, so the
    launch parallelizes over (B, H, key blocks) instead of
    serializing a fori_loop nobody amortizes at S_q = 1."""
    q = q_ref[0, 0]
    kb = k_ref[0, 0]
    vb = v_ref[0, 0]
    s = _dot(q, kb, od, trans_b=True) * scale
    mask = mask_ref[0] != 0
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = p.sum(axis=1, keepdims=True)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0, 0] = (_dot(p, vb, od) / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = m + jnp.log(l_safe)


def _decode_kernel_quant(q_ref, k_ref, v_ref, ks_ref, vs_ref,
                         mask_ref, o_ref, lse_ref, *, scale, od):
    """The quantized-pool twin of :func:`_decode_kernel`: the k/v
    block arrives as stored codes (int8/fp8) plus a per-position
    scale row, and the DEQUANT HAPPENS HERE in the gather — the
    memory traffic is the quantized bytes, never a materialized f32
    cache (the whole point of the quantized KV plane: decode is
    bandwidth-bound, bytes are throughput).  A position's scale is
    one number per key, so it factors out of both contractions:
    ``q·(c_k·s_k) = (q·c_k)·s_k`` scales the score COLUMN and
    ``p·(c_v·s_v) = (p·s_v)·c_v`` the probability column — the
    (1, bk) scale rows broadcast over the few query rows as they
    lie, and the codes reach the MXU exact (|int8| ≤ 127 is exact
    in bf16 too)."""
    q = q_ref[0, 0]
    kb = k_ref[0, 0].astype(jnp.float32)
    vb = v_ref[0, 0].astype(jnp.float32)
    s = _dot(q, kb, od, trans_b=True) * ks_ref[0, 0] * scale
    mask = mask_ref[0] != 0
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(mask, p, 0.0)
    l = p.sum(axis=1, keepdims=True)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0, 0] = (_dot(p * vs_ref[0, 0], vb, od) /
                      l_safe).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = m + jnp.log(l_safe)


def pallas_decode_attention(q, k, v, key_mask, block_k=None,
                            operand_dtype=None, interpret=False,
                            k_scale=None, v_scale=None):
    """Flash-decode over a gathered key table: q (B, Sq, H, D) with
    Sq ≤ ``DECODE_MAX_Q``, k/v (B, L, H, D), ``key_mask`` (B, Sq, L)
    True = attend (the serving paths' per-row valid-slot masks —
    causality, pad slots, and table trash all arrive through it).
    Grid (B, H, L/block_k): every program emits its block's partial
    (out, lse) and a cross-block lse merge combines them.  Forward
    only — decode never backpropagates.  Masked slots are exact
    zeros after the merge and real keys keep their relative order,
    the same exactness argument as the dense paged path.

    ``k_scale``/``v_scale`` (B, L, H) engage the quantized-pool
    variant: k/v are stored codes (int8/fp8) and each program
    dequantizes its own block inside the kernel — ``codes · scale``
    per position/head — so the HBM reads stay quantized-width."""
    if not supports_decode(q.shape, k.shape, interpret=interpret):
        raise ValueError(
            "geometry (%s × %s) outside the decode-kernel contract "
            "— serve through the dense cached path" %
            (q.shape, k.shape))
    from jax.experimental import pallas as pl
    B, Sq, H, D = q.shape
    L = k.shape[1]
    bk = _pick_block(L, block_k or DEFAULT_DECODE_BLOCK_K)
    nk = L // bk
    od = jnp.dtype(operand_dtype or jnp.bfloat16).type
    scale = 1.0 / (D ** 0.5)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    mask = key_mask.astype(jnp.int32)
    quantized = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, 1, Sq, D), lambda b, h, j: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h, j, 0)),
    ]
    operands = [qt, kt, vt]
    if quantized:
        # (B, L, H) → (B, H, 1, L): each program reads its block's
        # per-position scale as one lane-dense row next to the codes.
        in_specs += [
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, j: (b, h, 0, j)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, j: (b, h, 0, j)),
        ]
        operands += [k_scale.transpose(0, 2, 1)[:, :, None, :],
                     v_scale.transpose(0, 2, 1)[:, :, None, :]]
        kernel = functools.partial(_decode_kernel_quant,
                                   scale=scale, od=od)
    else:
        kernel = functools.partial(_decode_kernel, scale=scale,
                                   od=od)
    in_specs.append(
        pl.BlockSpec((1, Sq, bk), lambda b, h, j: (b, 0, j)))
    operands.append(mask)
    o_part, lse_part = pl.pallas_call(
        kernel,
        grid=(B, H, nk),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, 1, 1, Sq, D),
                         lambda b, h, j: (b, h, j, 0, 0)),
            # lse keeps its (Sq, 1) column: a block must span the
            # array's last two dimensions or tile them by (8, 128),
            # and Sq is a handful of rows.
            pl.BlockSpec((1, 1, 1, Sq, 1),
                         lambda b, h, j: (b, h, j, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, nk, Sq, D), jnp.float32),
            jax.ShapeDtypeStruct((B, H, nk, Sq, 1), jnp.float32),
        ),
        interpret=interpret,
        name="flash_decode",
    )(*operands)
    # Cross-block lse merge (the flash-decode combine): weights are
    # exp(lse_i − lse_total) ≤ 1, void blocks weigh 0.
    lse = jax.nn.logsumexp(lse_part, axis=2, keepdims=True)
    out = (o_part * jnp.exp(lse_part - lse)).sum(axis=2)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
