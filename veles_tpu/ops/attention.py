"""Attention ops: full, blockwise (flash-style), and ring
(sequence-parallel) formulations.

The reference framework predates attention entirely (2013-15, SURVEY
§5 "long-context: ABSENT in reference"), but long-context support is
a first-class obligation of the TPU build: sequences too long for one
chip's HBM shard along a mesh ``seq`` axis, and attention streams the
key/value shards around the ring over ICI (``lax.ppermute``) with an
online-softmax accumulator, so no device ever materializes the full
S×S score matrix or the full K/V.

Design notes (the "How to Scale Your Model" recipe):
  * all three formulations share one streaming-softmax block update —
    parity between them is structural, not coincidental;
  * the running max/normalizer (m, l) are ALWAYS float32 (bf16 loses
    the softmax tail); the materialized score/probability tensors and
    the output accumulator — the attention fast path's HBM traffic —
    drop to bf16 under ``root.common.engine.attention_dtype="bf16"``
    (per-block accumulation still happens in f32 and is rounded once
    per block), gated by parity tests with documented tolerances;
  * everything is ``lax.scan``/``ppermute`` — differentiable, so the
    backward pass is the same ring reversed, inserted by autodiff;
  * causal masking works on GLOBAL positions: each ring step offsets
    its key block by the sending device's shard start.

Attention fast path (BENCHNOTES round 6): three independently-gated
stages attack the LM bench's attention gap (7.8 ms fwd+bwd measured
vs ~1.5 ms of FLOP time at B=8/S=1024/H=16/D=128):

  * ``root.common.engine.fused_qkv`` — one (E, 3E) projection matmul
    per block instead of three (znicz/attention.py);
  * ``root.common.engine.attention_dtype`` — "f32" (default) or
    "bf16" score/accumulator intermediates (this module);
  * ``root.common.engine.attention_kernel`` — "auto" (default since
    the ISSUE 13 flip), "pallas", or "xla": route :func:`attention` /
    :func:`blockwise_attention` through the geometry-tuned Pallas
    flash kernel (ops/pallas_attention.py) when the platform
    supports it;
  * ``root.common.engine.sp_ring_kernel`` — "auto" (default),
    "pallas", or "xla": run each ring-attention step through the
    flash kernel on the ppermuted k/v shard with global causal
    offsets, merging partials by lse (ring-flash — the multi-chip
    composition of the kernel; docs/attention.md "Long context");
  * ``root.common.engine.decode_kernel`` — "off" (default: serving
    keeps its f32/xla pin), "pallas"/"auto"/"interpret": the
    flash-decode kernel behind export.py's cached/paged decode
    chain (token-identity gated).

Each knob has a ``--attn-*``/``--sp-*`` CLI flag (init_parser below)
and an A/B hook in ``bench.py --lm`` so the win is attributed per
stage.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .. import resilience
from ..config import root, get as config_get
from ..backends import tpu_available
from . import pallas_attention as PA

NEG_INF = -1e30

#: Valid sequence-parallel strategies (single source of truth for
#: sequence_parallel_attention and the unit-level validation).
SP_MODES = ("ring", "ulysses")

#: Valid attention-kernel dispatch modes.
KERNEL_MODES = ("xla", "pallas", "auto")

#: Default attention-kernel mode — "auto" since ISSUE 13 (the r6
#: roofline puts the flash kernel AT the bandwidth corner vs the XLA
#: formulation's ~7.4× traffic; off a TPU or outside the kernel's
#: geometry the XLA formulation is selected, so auto costs nothing
#: where it cannot win).
#: Serving surfaces pin kernel="xla" explicitly and never read this.
DEFAULT_KERNEL_MODE = "auto"

#: Default ring-kernel mode for sequence-parallel attention — the
#: ring-flash body (per-shard Pallas flash + lse merge) engages
#: wherever the platform/geometry supports it; elsewhere the lax
#: scan is selected.
DEFAULT_RING_KERNEL_MODE = "auto"


def init_parser(parser):
    """Attention fast-path flags, aggregated into the velescli parser
    (handed to ``root.common.engine`` by
    ``__main__.apply_subsystem_flags``)."""
    parser.add_argument(
        "--attn-fused-qkv", default=None, choices=("on", "off"),
        help="attention fast path: compute q/k/v with ONE (E, 3E) "
             "projection matmul per transformer block instead of "
             "three (E, E) matmuls (docs/attention.md)")
    parser.add_argument(
        "--attn-dtype", default=None, choices=("f32", "bf16"),
        help="attention fast path: dtype of the materialized score/"
             "probability tensors and output accumulator; bf16 "
             "halves the attention block's HBM traffic at a "
             "documented parity tolerance (serving stays f32)")
    parser.add_argument(
        "--attn-kernel", default=None, choices=KERNEL_MODES,
        help="attention fast path: 'pallas' routes attention through "
             "the geometry-tuned flash kernel "
             "(ops/pallas_attention.py) where the platform supports "
             "it, 'auto' (default since the r9 flip) selects it by "
             "platform and geometry, 'xla' keeps the fused XLA "
             "formulation")
    parser.add_argument(
        "--sp-ring-kernel", default=None, choices=KERNEL_MODES,
        help="sequence-parallel long-context path: 'pallas'/'auto' "
             "(default) run each ring step through the flash kernel "
             "on the ppermuted k/v shard with global causal offsets, "
             "merging partials by lse (ring-flash, "
             "docs/attention.md); 'xla' keeps the lax streaming scan")
    parser.add_argument(
        "--attn-decode-kernel", default=None,
        choices=("off", "pallas", "auto", "interpret"),
        help="serving decode kernel: 'pallas'/'auto' route the "
             "cached/paged one-token decode steps through the "
             "flash-decode kernel (k/v-split grid + lse merge) where "
             "supported; 'interpret' forces the interpret-mode "
             "kernel (tests/CI); 'off' (default — serving keeps its "
             "f32/xla pin until the token-identity gate flips it)")


def attention_compute_dtype(precision=None):
    """Resolves the score/accumulator dtype: the explicit
    ``precision`` argument wins, else ``root.common.engine.
    attention_dtype`` ("f32" default).  Unknown strings RAISE — a
    typo'd config override must not silently run the f32 baseline
    while the operator believes the bf16 stage is being measured."""
    if precision is None:
        precision = config_get(root.common.engine.attention_dtype,
                               "f32")
    if hasattr(precision, "dtype") or not isinstance(precision, str):
        return jnp.dtype(precision).type
    if precision == "bf16":
        return jnp.bfloat16
    if precision == "f32":
        return jnp.float32
    raise ValueError("unknown attention dtype %r — valid: 'f32', "
                     "'bf16' (or a jnp dtype)" % (precision,))


def _kernel_mode():
    mode = str(config_get(root.common.engine.attention_kernel,
                          DEFAULT_KERNEL_MODE))
    if mode not in KERNEL_MODES:
        raise ValueError("unknown attention kernel mode %r — valid: "
                         "%s" % (mode, list(KERNEL_MODES)))
    return mode


def _ring_kernel_mode():
    mode = str(config_get(root.common.engine.sp_ring_kernel,
                          DEFAULT_RING_KERNEL_MODE))
    if mode not in KERNEL_MODES:
        raise ValueError("unknown ring kernel mode %r — valid: %s" %
                         (mode, list(KERNEL_MODES)))
    return mode


def _selects_pallas(q_shape, k_shape, kv_len=None, mode=None,
                    v_shape=None, diffusion=None):
    """Whether attention at this geometry runs the Pallas flash
    kernel: the knob (or the explicit ``mode`` override) asks for
    it, the backend is a TPU and the geometry — values of their own
    width (``v_shape``, None: the keys') included — is inside the
    kernel's contract.  A SELECTION by what the process can observe, made
    before the kernel is touched — "pallas" and "auto" select
    identically, so a CPU test run with the flag on still exercises
    the reference path."""
    return (mode or _kernel_mode()) != "xla" and \
        PA.supports(q_shape, k_shape, kv_len, v_shape, diffusion) and \
        tpu_available()


def _try_pallas(q, k, v, causal, kv_len=None, mode=None,
                precision=None, window=None, diffusion=None):
    """Runs the Pallas flash kernel where :func:`_selects_pallas`
    says so; returns None (→ caller runs the jnp formulation)
    otherwise.  Once selected, a kernel that fails to lower raises —
    nothing here catches it.  The matmul operand dtype follows the
    ``attention_dtype`` knob (or the explicit ``precision``) exactly
    like every other formulation — f32 by default, bf16 under the
    bf16 stage: the dtype stage must stay an explicit opt-in, as the
    flip table documents.  Each TRACE of either choice counts into
    ``attention.kernel.pallas`` / ``attention.kernel.xla``, so a run
    can say which formulation its programs were built from."""
    if not _selects_pallas(q.shape, k.shape, kv_len, mode, v.shape,
                           diffusion):
        resilience.stats.incr("attention.kernel.xla")
        return None
    resilience.stats.incr("attention.kernel.pallas")
    # the mode rides along only where it is set: a causal or window
    # call is the call it was
    mode = {} if diffusion is None else {"diffusion": diffusion}
    return PA.pallas_attention(
        q, k, v, causal=causal, kv_len=kv_len, window=window,
        operand_dtype=attention_compute_dtype(precision), **mode)


def _block_update(acc, m, l, q, k, v, *, scale, mask=None):
    """One streaming-softmax update: fold the (q·kᵀ) scores of a
    key/value block into the running (acc, m, l) accumulator.

    Shapes: q (B, Sq, H, D); k/v (B, Sk, H, D); acc (B, Sq, H, D) in
    the caller-chosen compute dtype (``acc.dtype`` — f32 default,
    bf16 under the fast-path knob); m/l (B, Sq, H) ALWAYS f32.
    ``mask`` (Sq, Sk) True = attend.

    In bf16 mode the materialized tensors (scores, probabilities,
    the carried accumulator) are bf16 — the HBM traffic — while the
    running statistics and each block's accumulation happen in f32
    and are rounded ONCE per block, so the error is per-block
    rounding, not compounding summation drift.
    """
    dt = acc.dtype
    # preferred_element_type stays f32: the q·kᵀ dot is a D-term sum
    # whose ACCUMULATION must not round at bf16 (the materialized
    # tensor — the HBM traffic — is still dt after the cast).
    scores = (jnp.einsum("bqhd,bkhd->bqhk", q, k,
                         preferred_element_type=jnp.float32) *
              scale).astype(dt)
    if mask is not None:
        scores = jnp.where(mask[None, :, None, :], scores,
                           jnp.asarray(NEG_INF, dt))
    block_max = scores.max(axis=-1).astype(jnp.float32)
    new_m = jnp.maximum(m, block_max)
    correction = jnp.exp(m - new_m)
    p = jnp.exp(scores - new_m[..., None].astype(dt))
    if mask is not None:
        # exp(NEG_INF - m) underflows to 0 already; this guards the
        # fully-masked-row case where new_m itself is NEG_INF.
        p = jnp.where(mask[None, :, None, :], p, jnp.asarray(0.0, dt))
    new_l = l * correction + p.sum(axis=-1, dtype=jnp.float32)
    pv = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(dt),
                    preferred_element_type=jnp.float32)
    new_acc = (acc.astype(jnp.float32) * correction[..., None] +
               pv).astype(dt)
    return new_acc, new_m, new_l


def _finish(acc, l, dtype):
    return (acc.astype(jnp.float32) /
            jnp.maximum(l, 1e-30)[..., None]).astype(dtype)


def _causal_mask(sq, sk, q_offset, k_offset, window=None):
    """(sq, sk) True = attend: a row sees the columns up to its own
    global position, and with ``window`` only the last ``window`` of
    them (``0 ≤ row − col < window``)."""
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = k_offset + jnp.arange(sk)[None, :]
    if window is None:
        return qpos >= kpos
    return (qpos >= kpos) & (qpos - kpos < window)


def attention(q, k, v, causal=False, precision=None, kernel=None,
              window=None, diffusion=None):
    """Full O(S²)-memory attention (B, S, H, D) — the reference
    formulation the streaming variants are tested against.

    ``precision``: None → the ``attention_dtype`` knob; "f32"/"bf16"
    forces.  ``kernel``: None → the ``attention_kernel`` knob;
    "xla" forces the jnp formulation — what the serving surfaces pin
    so a training-process knob never changes deployed bits.  Under
    "pallas"/"auto" the call routes through the Pallas flash kernel
    when the platform supports the geometry (the kernel never
    materializes the S×S scores, so the precision knob is moot
    there beyond the matmul operand dtype).  ``window`` (a static
    int, beside ``causal``): row ``i`` sees key ``j`` iff ``0 ≤ i − j
    < window`` — sliding-window attention; the kernel's tile walk
    follows it.  The kernel takes the whole sequence in one call
    wherever its operands fit VMEM (``pallas_attention.call_rows``:
    every cell's, up to 8,192 rows); only a longer one is called a
    visible pair of k/v and query chunks at a time.

    Grouped-query attention: where k and v carry fewer heads than q
    (H a multiple of theirs) each key/value head is broadcast over
    its group of consecutive query heads first, so both formulations
    see equal head counts and the flash kernels are selected by the
    same shapes as for full multi-head attention; autodiff sums dk
    and dv over the group.  Values may have a head width of their own
    (latent attention: q and k 192 wide, v 128); the output has
    theirs.

    ``diffusion`` ``(L, B)`` (static, in place of ``causal``): block
    diffusion over two streams of ``L`` rows, noised then clean, in
    blocks of ``B`` positions (``pallas_attention.diffusion_visible``
    says which key a row sees); the kernels walk only the tiles that
    hold a visible pair, and this formulation builds the same mask
    from the same function."""
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        if group * k.shape[2] != q.shape[2] or v.shape[2] != k.shape[2]:
            raise ValueError("%d query heads over %d key and %d value "
                             "heads" % (q.shape[2], k.shape[2],
                                        v.shape[2]))
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    PA.check_window(causal, window)
    diffusion = PA.check_diffusion(diffusion, causal, window,
                                   seq=q.shape[1])
    out = _try_pallas(q, k, v, causal, mode=kernel,
                      precision=precision, window=window,
                      diffusion=diffusion)
    if out is not None:
        return out
    dt = attention_compute_dtype(precision)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    mask = _causal_mask(q.shape[1], k.shape[1], 0, 0, window) \
        if causal else None
    if diffusion is not None:
        rows = jnp.arange(q.shape[1])
        mask = PA.diffusion_visible(rows[:, None], rows[None, :],
                                    *diffusion)
    B, Sq, H = q.shape[:3]
    acc = jnp.zeros((B, Sq, H, v.shape[-1]), dt)
    m = jnp.full((B, Sq, H), NEG_INF, jnp.float32)
    l = jnp.zeros((B, Sq, H), jnp.float32)
    acc, m, l = _block_update(acc, m, l, q, k, v, scale=scale,
                              mask=mask)
    return _finish(acc, l, q.dtype)


def mesh_attention(q, k, v, mesh, causal=False, batch_axis=None,
                   head_axis=None, window=None, diffusion=None):
    """:func:`attention` inside a step that GSPMD partitions over
    ``mesh``.  The XLA formulation partitions by itself.  A Mosaic
    kernel does not ("Mosaic kernels cannot be automatically
    partitioned"): where the flash kernel is selected the call is
    wrapped in ``shard_map`` over the two dimensions attention is
    independent along — batch on ``batch_axis``, heads on
    ``head_axis`` — so every chip runs the kernel on its own
    (B/n, S, H/m, D) shard and no collective is needed inside.  An
    axis the mesh lacks, or that does not divide its dimension,
    leaves that dimension whole."""
    if not _selects_pallas(q.shape, k.shape, v_shape=v.shape,
                           diffusion=diffusion):
        return attention(q, k, v, causal=causal, window=window,
                         diffusion=diffusion)
    from jax.sharding import PartitionSpec as P

    def fits(axis, dim):
        return axis if axis in mesh.axis_names and \
            q.shape[dim] % mesh.shape[axis] == 0 else None

    spec = P(fits(batch_axis, 0), None, fits(head_axis, 2), None)
    fn = jax.shard_map(
        functools.partial(attention, causal=causal, window=window,
                          diffusion=diffusion),
        mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)


def blockwise_attention(q, k, v, block_size=128, causal=False,
                        kv_len=None, precision=None, kernel=None,
                        window=None):
    """Flash-style attention: scan over key/value blocks with the
    streaming accumulator — O(S·block) memory on one device.

    ``kv_len``: when set, keys at global positions >= kv_len are
    masked out — the padding contract for callers that padded k/v up
    to a block multiple (non-causal attention would otherwise attend
    the zero padding).  ``window``: as :func:`attention`'s.

    ``precision``/``kernel``: None → the ``attention_dtype`` /
    ``attention_kernel`` knobs (explicit values force, as in
    :func:`attention`).  Under "pallas"/"auto" the scan is replaced
    wholesale by the Pallas flash kernel when the platform supports
    the geometry."""
    PA.check_window(causal, window)
    out = _try_pallas(q, k, v, causal, kv_len=kv_len, mode=kernel,
                      precision=precision, window=window)
    if out is not None:
        return out
    dt = attention_compute_dtype(precision)
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    if S % block_size:
        raise ValueError("sequence %d not divisible by block %d" %
                         (S, block_size))
    nblocks = S // block_size
    scale = 1.0 / (D ** 0.5)
    kb = k.reshape(B, nblocks, block_size, H, D)
    vb = v.reshape(B, nblocks, block_size, H, Dv)

    def body(carry, xs):
        acc, m, l = carry
        kblk, vblk, idx = xs
        k_off = idx * block_size
        mask = _causal_mask(S, block_size, 0, k_off, window) \
            if causal else None
        if kv_len is not None:
            kvalid = jnp.broadcast_to(
                (k_off + jnp.arange(block_size))[None, :] < kv_len,
                (S, block_size))
            mask = kvalid if mask is None else \
                jnp.logical_and(mask, kvalid)
        acc, m, l = _block_update(acc, m, l, q, kblk, vblk,
                                  scale=scale, mask=mask)
        return (acc, m, l), None

    init = (jnp.zeros((B, S, H, Dv), dt),
            jnp.full((B, S, H), NEG_INF, jnp.float32),
            jnp.zeros((B, S, H), jnp.float32))
    (acc, m, l), _ = lax.scan(
        body, init,
        (kb.swapaxes(0, 1), vb.swapaxes(0, 1), jnp.arange(nblocks)))
    return _finish(acc, l, q.dtype)


def _try_ring_flash(q, k, mode, interpret):
    """Whether this ring call runs the Pallas flash body: the knob
    (or explicit ``kernel`` override) asks for it, the per-shard
    geometry fits, and the backend is a TPU (``interpret=True`` —
    the test/dryrun path — runs the interpret kernel anywhere).
    False selects the lax streaming scan; like ``_try_pallas`` the
    choice is made up front and a selected kernel's failure
    propagates."""
    if mode == "xla":
        return False
    if not PA.supports_ring(q.shape, k.shape, interpret=interpret):
        return False
    return interpret or tpu_available()


def _ring_flash(q, k, v, axis_name, causal, od, interpret):
    """The ring-flash body: every ring step invokes the Pallas flash
    kernel on the currently-held (ppermuted) k/v shard with GLOBAL
    causal offsets — the source rank's shard start, a traced scalar
    the kernel masks by — and the per-step partials merge by lse
    (``pallas_attention.merge_partials``).  The steps unroll in
    Python (the axis size is static inside shard_map), and the
    backward stays autodiff-derived: each chunk's custom VJP
    recomputes its probabilities from the saved lse, the merge and
    the reversed ppermutes differentiate as plain jax — recompute-
    from-lse per ring step, exactly the single-chip kernel's
    contract stretched across the ring."""
    n = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    q_offset = (rank * Sq).astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    carry = None
    kr, vr = k, v
    for step in range(n):
        # The k/v shard currently held arrived from `rank - step`.
        # flash_resume holds the carried partial f32 across every
        # merge (one rounding at the final cast, like the lax ring's
        # single f32 accumulator).
        src = (rank - step) % n
        carry = PA.flash_resume(
            carry, q, kr, vr, causal=causal, q_offset=q_offset,
            k_offset=(src * Sq).astype(jnp.float32),
            operand_dtype=od, interpret=interpret)
        if step != n - 1:
            kr = lax.ppermute(kr, axis_name, perm)
            vr = lax.ppermute(vr, axis_name, perm)
    out, _lse = carry
    return out.astype(q.dtype)


def ring_attention(q, k, v, axis_name, causal=False, kernel=None,
                   precision=None, interpret=None):
    """Sequence-parallel attention INSIDE ``shard_map``: each device
    holds its (B, S/N, H, D) shard; N ring steps ppermute the k/v
    shard to the next device while folding the arriving block into
    the local queries' accumulator.  Communication rides ICI and
    overlaps the einsums; peak memory per device is O(S/N) — the
    long-context enabler.

    ``kernel``: None → the ``sp_ring_kernel`` knob ("auto" default);
    "pallas"/"auto" run each step through the Pallas flash kernel on
    the held shard (the ring-flash body, :func:`_ring_flash`) where
    the platform/geometry supports it, "xla" forces the lax scan.
    ``precision`` follows the ``attention_dtype`` knob as everywhere
    (in the flash body it becomes the matmul operand dtype);
    ``interpret`` forces the interpret-mode kernel — the CPU parity/
    dryrun path.
    """
    mode = kernel if kernel is not None else _ring_kernel_mode()
    if mode not in KERNEL_MODES:
        raise ValueError("unknown ring kernel mode %r — valid: %s" %
                         (mode, list(KERNEL_MODES)))
    itp = bool(interpret)
    if _try_ring_flash(q, k, mode, itp):
        return _ring_flash(q, k, v, axis_name, causal,
                           attention_compute_dtype(precision), itp)
    n = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    dt = attention_compute_dtype(precision)
    scale = 1.0 / (D ** 0.5)
    q_offset = rank * Sq
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, step):
        acc, m, l, kr, vr = carry
        # The k/v block currently held arrived from `rank - step`.
        src = (rank - step) % n
        if causal:
            mask = _causal_mask(Sq, kr.shape[1], q_offset, src * Sq)
        else:
            mask = None
        acc, m, l = _block_update(acc, m, l, q, kr, vr, scale=scale,
                                  mask=mask)
        kr = lax.ppermute(kr, axis_name, perm)
        vr = lax.ppermute(vr, axis_name, perm)
        return (acc, m, l, kr, vr), None

    init = (jnp.zeros((B, Sq, H, D), dt),
            jnp.full((B, Sq, H), NEG_INF, jnp.float32),
            jnp.zeros((B, Sq, H), jnp.float32), k, v)
    (acc, m, l, _, _), _ = lax.scan(body, init, jnp.arange(n))
    return _finish(acc, l, q.dtype)


def ulysses_attention(q, k, v, axis_name, causal=False):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style),
    INSIDE ``shard_map``: each device holds a (B, S/N, H, D) sequence
    shard; one all-to-all re-shards to (B, S, H/N, D) — full sequence,
    a subset of heads — so plain full attention runs locally, then the
    reverse all-to-all restores sequence sharding.  Two collectives
    total per call (vs N ppermute steps for the ring); requires
    H % N == 0.  Complements the ring: Ulysses moves activations
    twice and computes dense attention, the ring streams k/v blocks —
    which wins depends on S, H, and the interconnect.
    """
    n = lax.psum(1, axis_name)
    B, Sq, H, D = q.shape
    if H % n:
        raise ValueError("ulysses needs heads (%d) divisible by the "
                         "sequence-axis size (%d)" % (H, n))

    def to_heads(x):
        # (B, S/N, H, D) → (B, S, H/N, D): head-chunk i goes to
        # device i, which receives every device's sequence shard.
        return lax.all_to_all(x, axis_name, split_axis=2,
                              concat_axis=1, tiled=True)

    def to_seq(x):
        # Exact inverse: sequence chunks scatter back, head chunks
        # reassemble in device order.
        return lax.all_to_all(x, axis_name, split_axis=1,
                              concat_axis=2, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    out = _gathered_attention(qh, kh, vh, causal)
    return to_seq(out)


#: Above this gathered length the local attention MUST stream
#: blockwise — a dense S×S score tensor is exactly the blow-up
#: sequence parallelism exists to avoid.
ULYSSES_DENSE_MAX = 1024


def _gathered_attention(q, k, v, causal):
    """Local attention over the Ulysses-gathered (full-S, head-shard)
    activations.  S <= ULYSSES_DENSE_MAX runs dense; anything longer
    streams blockwise at the largest dividing block size, PADDING up
    to a block multiple when nothing divides — never silently dense
    (the pre-round-5 behavior fell back to O(S²) scores for
    S = 1025..1535 and any non-multiple of 512)."""
    S = q.shape[1]
    if S <= ULYSSES_DENSE_MAX:
        return attention(q, k, v, causal=causal)
    for bs in (512, 384, 256, 128, 64):
        if S % bs == 0:
            return blockwise_attention(q, k, v, block_size=bs,
                                       causal=causal)
    bs = 512
    pad = (-S) % bs
    padded = [jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
              for x in (q, k, v)]
    # kv_len masks the padded keys (a causal mask alone would let
    # NON-causal attention read the zero padding); padded query rows
    # are garbage and sliced away.
    out = blockwise_attention(*padded, block_size=bs, causal=causal,
                              kv_len=S)
    return out[:, :S]


def sequence_parallel_attention(q, k, v, mesh, seq_axis,
                                causal=False, batch_axis=None,
                                mode="ring", head_axis=None,
                                kernel=None, interpret=None):
    """Wraps a sequence-parallel attention (``mode``: "ring" →
    :func:`ring_attention`, "ulysses" → :func:`ulysses_attention`) in
    ``shard_map`` over the mesh's sequence axis (activations
    (B, S, H, D) sharded on dim 1), usable from inside an outer jit:
    GSPMD reshards the operands to the in_specs, the collectives run
    over ICI, and the result comes back sequence-sharded.
    ``batch_axis`` keeps the batch dim data-parallel inside the
    shard_map (dp × sp composes: the collectives involve only
    ``seq_axis``); ``head_axis`` keeps the head dim TENSOR-parallel
    (dp × tp × sp composes: attention is per-head, so a Megatron
    head shard rotates only its own heads' k/v around the ring —
    no model-axis collective is ever needed inside, and the
    ring-flash body sees only the local heads' (B, S/N, H/ntp, D)
    shard).  ``kernel``/``interpret`` reach the ring body only
    (:func:`ring_attention`'s ring-flash dispatch); Ulysses keeps
    its knob-driven local attention."""
    from jax.sharding import PartitionSpec as P
    if batch_axis is not None and batch_axis not in mesh.axis_names:
        batch_axis = None
    if head_axis is not None and head_axis not in mesh.axis_names:
        head_axis = None
    spec = P(batch_axis, seq_axis, head_axis, None)
    modes = {"ring": ring_attention, "ulysses": ulysses_attention}
    assert set(modes) == set(SP_MODES)
    if mode not in modes:
        raise ValueError("unknown sequence-parallel mode %r — "
                         "valid: %s" % (mode, sorted(modes)))
    inner = modes[mode]
    inner_kw = {"axis_name": seq_axis, "causal": causal}
    if mode == "ring":
        inner_kw["kernel"] = kernel
        inner_kw["interpret"] = interpret
    # check_vma off: the ring's carried k/v blocks change their
    # varying-axis type across ppermute steps.
    fn = jax.shard_map(
        functools.partial(inner, **inner_kw),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
