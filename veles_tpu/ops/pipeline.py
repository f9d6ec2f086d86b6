"""Pipeline parallelism over a mesh ``stage`` axis: GPipe, 1F1B
(PipeDream-flush) and interleaved (Megatron) schedules.

Not in the 2013-15 reference (its only parallelism was master–slave
DP, SURVEY §2.3); completes the TPU build's scaling matrix
(dp/tp/sp/ep/pp).  The formulation is the standard collective-permute
pipeline: a stack of IDENTICALLY-SHAPED layer applications is laid
out one stage per device (stacked parameters shard on their leading
stage dimension), the batch splits into M microbatches, and each
device applies its stage to the microbatch it holds while
``lax.ppermute`` hands activations to the next stage.  Everything is
``lax.scan`` + ``ppermute`` inside ``shard_map``, so autodiff derives
the backward pipeline (reverse ring) automatically — for EVERY
schedule; :func:`sequential_stack` stays the exact-parity oracle.

Schedules (the ``schedule`` knob of :func:`pipeline`):

* ``gpipe`` — the classic fill-and-drain ramp: T = M + S − 1 scan
  steps, each device applying its whole local sub-stack per step.
  Bubble fraction (S − 1)/(M + S − 1); live activation residuals
  scale with M (every step's inputs are saved for the backward).
* ``1f1b`` — PipeDream-flush.  The forward ramp is timing-identical
  to GPipe's (T = M + S − 1 — as in the paper, the schedules differ
  in what is held live, not in forward step count), but each scan
  step REMATERIALIZES its stage application (``jax.checkpoint``), so
  the backward re-runs the stage forward per step and the live
  residuals drop from every layer's internals (attention scores, MLP
  hiddens — the dominant term) to one chunk-input activation per
  step.  NOTE the honest bound: the scan's carry chain is still
  O(M) activations — an SPMD scan whose backward autodiff derives
  cannot express the hand-scheduled O(S) in-flight interleave — so
  this is the remat memory class that makes large M affordable, not
  a strict ≤ S cap.  At a memory-constrained operating point GPipe
  flushes every ~S microbatches (bubble (S − 1)/(2S − 1) ≈ 43% at
  S=4) while 1F1B runs the full M unflushed (bubble
  (S − 1)/(M + S − 1) ≈ 27% at M=8) — the dispatch-count reduction
  measured in BENCHNOTES.
* ``interleaved`` — Megatron interleaved stages: each device hosts
  V = ``n_chunks`` non-contiguous layer chunks (global chunk j lives
  on device j mod S), microbatches circulate the ring V times in
  groups of S.  Per-step compute drops to 1/V of a stage, the table
  below packs groups back-to-back, and T = M·V + S − 1 chunk-steps
  (M ≥ S), so the bubble shrinks to (S − 1)/(M·V + S − 1) in
  chunk-step units — the Megatron 1/V bubble reduction, visible on
  CPU as both shorter weighted scan length and wall time.

Every schedule's step table comes from :func:`schedule_steps` — a
pure-python simulation the bubble-accounting tests assert on — and
:func:`bubble_fraction` derives the idle fraction from the table, so
the claimed formulas and the executed scan cannot drift apart.
"""

import functools

import numpy

import jax
import jax.numpy as jnp
from jax import lax

#: Valid pipeline schedules (single source of truth for the unit
#: knob, the CLI flag and the bench A/B hook).
SCHEDULES = ("gpipe", "1f1b", "interleaved")


def init_parser(parser):
    """Pipeline-schedule flags, aggregated into the velescli parser
    (handed to ``root.common.engine`` by
    ``__main__.apply_subsystem_flags``)."""
    parser.add_argument(
        "--pp-schedule", default=None, choices=SCHEDULES,
        help="pipeline-parallel schedule for stage-stacked "
             "transformer stacks: 'gpipe' (fill-and-drain, default), "
             "'1f1b' (PipeDream-flush: per-step rematerialization "
             "shrinks live residuals from per-layer internals to one "
             "activation per step, making large microbatch counts "
             "affordable), or 'interleaved' (Megatron V-chunk stages "
             "— bubble shrinks ~1/V; see --pp-chunks) "
             "(docs/pipeline.md)")
    parser.add_argument(
        "--pp-chunks", type=int, default=None, metavar="V",
        help="interleaved schedule: virtual chunks per pipeline "
             "stage (default: one chunk per local block; the block "
             "count must divide into stages x chunks)")


def _validate(x, n_microbatches, n_layers, n_stages):
    """Shared argument validation — actionable errors instead of
    silent reshape/astype surprises (ISSUE 12 satellite)."""
    if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        raise TypeError(
            "pipeline input dtype %s is not a float dtype — the "
            "pipelined stack carries a float activation stream "
            "(embed integer tokens before the stack instead of "
            "relying on a silent astype)" % jnp.asarray(x).dtype)
    B = x.shape[0]
    if n_microbatches < 1:
        raise ValueError(
            "n_microbatches must be >= 1, got %d" % n_microbatches)
    if n_microbatches > B:
        raise ValueError(
            "n_microbatches=%d exceeds the batch size %d — every "
            "microbatch needs at least one sample (lower "
            "n_microbatches or raise the minibatch size)"
            % (n_microbatches, B))
    if B % n_microbatches:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (B, n_microbatches))
    if n_layers % n_stages:
        raise ValueError(
            "%d stacked layers do not divide over %d pipeline "
            "stages" % (n_layers, n_stages))


def _pipeline_body(fn, params, x_mb, axis_name):
    """The per-device GPipe loop.  ``params``: this stage's layer
    parameters (stage dim already sliced away by shard_map);
    ``x_mb``: (M, mb, ...) microbatched input, replicated."""
    n_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    steps = M + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]
    mb_shape = x_mb.shape[1:]
    out_acc = jnp.zeros((M,) + mb_shape, jnp.float32)

    def body(carry, t):
        recv, acc = carry
        # Stage 0 injects microbatch t (zeros once the ramp ends);
        # later stages consume what arrived from stage-1.
        feed_idx = jnp.clip(t, 0, M - 1)
        fresh = jnp.where(t < M, x_mb[feed_idx],
                          jnp.zeros(mb_shape, x_mb.dtype))
        inp = jnp.where(stage == 0, fresh.astype(jnp.float32), recv)
        out = fn(params, inp)
        # The LAST stage finishes microbatch t−(S−1) at step t.
        mb_done = t - (n_stages - 1)
        is_last = stage == n_stages - 1
        valid = jnp.logical_and(is_last, mb_done >= 0)
        slot = jnp.clip(mb_done, 0, M - 1)
        acc = jnp.where(
            valid,
            acc.at[slot].set(out.astype(jnp.float32)),
            acc)
        # Hand the activation to the next stage (last stage sends
        # nothing anyone reads).
        recv = lax.ppermute(out, axis_name, perm)
        return (recv, acc), None

    init = (jnp.zeros(mb_shape, jnp.float32), out_acc)
    (_, acc), _ = lax.scan(body, init, jnp.arange(steps))
    # Only the last stage holds real outputs; psum replicates them
    # (every other stage contributes zeros).
    return lax.psum(acc, axis_name)


def gpipe(fn, stacked_params, x, mesh, stage_axis, n_microbatches):
    """Runs ``y = fn(p[S-1], …fn(p[1], fn(p[0], x))…)`` microbatch-
    pipelined over the mesh's ``stage_axis`` (GPipe schedule).

    Args:
      fn: (layer_params, activation (mb, ...)) → activation, same
        shape class in and out (stages must be homogeneous).
      stacked_params: pytree whose leaves carry a leading S dim.
      x: (B, ...) float input; B must divide into ``n_microbatches``.
      mesh / stage_axis: where the stages live.
      n_microbatches: M; the bubble fraction is (S−1)/(M+S−1).

    Returns y (B, ...) float32, replicated over the stage axis.
    """
    from jax.sharding import PartitionSpec as P
    B = x.shape[0]
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    n_stages = mesh.shape[stage_axis]
    _validate(x, n_microbatches, n_layers, n_stages)
    mb = B // n_microbatches
    x_mb = x.reshape((n_microbatches, mb) + x.shape[1:])

    def stage_fn(params, x_all):
        # shard_map leaves each device a (n_layers/n_stages, ...)
        # local sub-stack; a stage applies its local layers in
        # sequence (scan), so n_layers may be any multiple of the
        # stage count.
        return _pipeline_body(
            lambda p, h: sequential_stack(fn, p, h),
            params, x_all, stage_axis)

    pspec = jax.tree_util.tree_map(
        lambda p: P(stage_axis, *([None] * (p.ndim - 1))),
        stacked_params)
    out = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(pspec, P()), out_specs=P(), check_vma=False)(
            stacked_params, x_mb)
    return out.reshape((B,) + out.shape[2:])


def sequential_stack(fn, stacked_params, x):
    """The no-mesh reference path: the same stacked layers applied by
    a plain scan — every pipelined schedule and sequential must agree
    exactly (the parity oracle)."""
    def body(h, params):
        return fn(params, h), None
    y, _ = lax.scan(body, x.astype(jnp.float32), stacked_params)
    return y


# -- schedule tables -------------------------------------------------------

def schedule_steps(schedule, n_stages, n_microbatches, n_chunks=1):
    """The static schedule table — the single source of truth the
    scan loops consume and the bubble-accounting tests assert on.

    Returns a list of T steps; ``step[t]`` is a list of ``n_stages``
    entries, one per device: None (idle bubble slot) or a dict with

      * ``chunk``: local chunk index on that device (< n_chunks);
      * ``mb``: global microbatch id;
      * ``fresh``: the input is ``x_mb[mb]`` (pipeline entry);
      * ``final``: the output is the finished microbatch.

    GPipe and 1F1B are stage-granular (n_chunks must be 1) with
    T = M + S − 1: stage s is active exactly during steps
    [s, s + M) on microbatch t − s — the staggered ramp whose
    scan-reverse is the staggered backward.  Interleaved packs
    groups of min(S, M) microbatches back-to-back through V chunks
    per device (global chunk j on device j mod S): conflict-free by
    construction, one ring hop per chunk-step, T = M·V + S − 1 for
    M ≥ S (M + V·S − 1 for a single partial group).
    """
    S, M, V = n_stages, n_microbatches, n_chunks
    if schedule not in SCHEDULES:
        raise ValueError("unknown pipeline schedule %r — valid: %s"
                         % (schedule, list(SCHEDULES)))
    if schedule in ("gpipe", "1f1b"):
        if V != 1:
            raise ValueError(
                "schedule %r is stage-granular — n_chunks must be 1 "
                "(got %d); virtual chunks belong to 'interleaved'"
                % (schedule, V))
        steps = []
        for t in range(M + S - 1):
            row = []
            for s in range(S):
                m = t - s
                row.append(None if not 0 <= m < M else dict(
                    chunk=0, mb=m, fresh=(s == 0),
                    final=(s == S - 1)))
            steps.append(row)
        return steps
    # interleaved: groups of g microbatches, group k offset by k·V·S
    # chunk-steps; in-group microbatch m runs global chunk j at step
    # k·V·S + m + j.  Conflict-freedom (one op per device per step)
    # is asserted below, not assumed.
    g = min(S, M)
    if M % g:
        raise ValueError(
            "interleaved schedule needs n_microbatches (%d) "
            "divisible by the group size min(stages, microbatches) "
            "= %d — pad the microbatch count or use gpipe/1f1b"
            % (M, g))
    n_steps = (M // g - 1) * V * S + (g - 1) + (V * S - 1) + 1
    steps = [[None] * S for _ in range(n_steps)]
    for k in range(M // g):
        for m in range(g):
            for j in range(V * S):
                t = k * V * S + m + j
                d = j % S
                if steps[t][d] is not None:  # pragma: no cover
                    raise AssertionError(
                        "interleaved schedule conflict at step %d "
                        "device %d" % (t, d))
                steps[t][d] = dict(chunk=j // S, mb=k * g + m,
                                   fresh=(j == 0),
                                   final=(j == V * S - 1))
    return steps


def bubble_fraction(schedule, n_stages, n_microbatches, n_chunks=1):
    """Idle fraction of the fleet, derived FROM the schedule table
    (so formula and execution cannot drift): idle device-steps over
    total device-steps.  gpipe/1f1b: (S−1)/(M+S−1); interleaved:
    (S−1)/(M·V+S−1) in chunk-step units for M ≥ S."""
    table = schedule_steps(schedule, n_stages, n_microbatches,
                           n_chunks)
    total = len(table) * n_stages
    active = sum(1 for row in table for e in row if e is not None)
    return (total - active) / float(total)


def _table_arrays(table, n_stages):
    """Packs a schedule table into the (T, S) numpy arrays the scan
    consumes: chunk index, fresh flag, feed microbatch, final flag,
    output slot."""
    T = len(table)
    chunk = numpy.zeros((T, n_stages), numpy.int32)
    fresh = numpy.zeros((T, n_stages), numpy.float32)
    feed = numpy.zeros((T, n_stages), numpy.int32)
    final = numpy.zeros((T, n_stages), numpy.float32)
    slot = numpy.zeros((T, n_stages), numpy.int32)
    for t, row in enumerate(table):
        for d, e in enumerate(row):
            if e is None:
                continue
            chunk[t, d] = e["chunk"]
            if e["fresh"]:
                fresh[t, d] = 1.0
                feed[t, d] = e["mb"]
            if e["final"]:
                final[t, d] = 1.0
                slot[t, d] = e["mb"]
    return chunk, fresh, feed, final, slot


def _scheduled_body(fn, params, x_mb, tables, axis_name, n_chunks,
                    remat_step):
    """The per-device table-driven loop shared by 1F1B and
    interleaved: a closed ppermute ring, one chunk application per
    step, inputs selected fresh-vs-received and outputs accumulated
    per the schedule table.  ``params``: this device's local layer
    stack (stage dim sliced away, chunk-major order — see the
    reorder in :func:`pipeline`)."""
    n_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    mb_shape = x_mb.shape[1:]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    # Local stack (V·Lc, ...) → (V, Lc, ...): chunk a = local[a].
    local = jax.tree_util.tree_map(
        lambda p: p.reshape((n_chunks, p.shape[0] // n_chunks) +
                            p.shape[1:]), params)

    def apply_chunk(cparams, h):
        return sequential_stack(fn, cparams, h)
    if remat_step:
        # The 1F1B memory lever: the backward re-runs each chunk's
        # forward from its saved input instead of keeping every
        # layer's internals live — per-step residuals shrink to one
        # activation (the scan's O(M) carry chain remains; see the
        # module docstring for the honest bound).
        apply_chunk = jax.checkpoint(apply_chunk)

    def body(carry, xs):
        recv, acc = carry
        chunk_row, fresh_row, feed_row, final_row, slot_row = xs
        c = jnp.take(chunk_row, stage)
        is_fresh = jnp.take(fresh_row, stage)
        f_idx = jnp.take(feed_row, stage)
        is_final = jnp.take(final_row, stage)
        o_slot = jnp.take(slot_row, stage)
        fresh = x_mb[f_idx].astype(jnp.float32)
        inp = jnp.where(is_fresh > 0, fresh, recv)
        cparams = jax.tree_util.tree_map(
            lambda p: lax.dynamic_index_in_dim(p, c, 0,
                                               keepdims=False),
            local)
        out = apply_chunk(cparams, inp)
        acc = jnp.where(
            is_final > 0,
            acc.at[o_slot].set(out.astype(jnp.float32)),
            acc)
        recv = lax.ppermute(out, axis_name, perm)
        return (recv, acc), None

    init = (jnp.zeros(mb_shape, jnp.float32),
            jnp.zeros((M,) + mb_shape, jnp.float32))
    (_, acc), _ = lax.scan(body, init, tables)
    # Only final-chunk outputs landed in acc (on the last device);
    # psum replicates them (other stages contribute zeros).
    return lax.psum(acc, axis_name)


def pipeline(fn, stacked_params, x, mesh, stage_axis, n_microbatches,
             schedule="gpipe", n_chunks=None, remat_step=None):
    """Schedule-dispatching pipeline: ``schedule`` picks gpipe
    (exactly :func:`gpipe`), 1f1b, or interleaved; every schedule
    computes the same function as :func:`sequential_stack` (the
    parity oracle) over a mesh ``stage_axis``.

    Args beyond :func:`gpipe`:
      schedule: one of :data:`SCHEDULES`.
      n_chunks: interleaved only — virtual chunks per stage (default
        one chunk per local layer); layers must divide into
        stages × chunks.
      remat_step: per-step rematerialization; None → on for 1f1b
        (its defining memory lever), off otherwise.
    """
    if schedule not in SCHEDULES:
        raise ValueError("unknown pipeline schedule %r — valid: %s"
                         % (schedule, list(SCHEDULES)))
    if schedule in ("gpipe", "1f1b") and n_chunks not in (None, 1):
        # Refuse, don't silently ignore: --pp-chunks with a
        # stage-granular schedule means the operator expected
        # interleaving that would never happen.
        raise ValueError(
            "schedule %r is stage-granular — n_chunks must be 1 "
            "(got %r); virtual chunks belong to 'interleaved'"
            % (schedule, n_chunks))
    if schedule == "gpipe":
        return gpipe(fn, stacked_params, x, mesh, stage_axis,
                     n_microbatches)
    from jax.sharding import PartitionSpec as P
    B = x.shape[0]
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    n_stages = mesh.shape[stage_axis]
    _validate(x, n_microbatches, n_layers, n_stages)
    local_layers = n_layers // n_stages
    if schedule == "1f1b":
        V = 1
    else:
        V = local_layers if n_chunks is None else n_chunks
        if V < 1 or local_layers % V:
            raise ValueError(
                "interleaved schedule: %d layers per stage do not "
                "divide into %r chunks" % (local_layers, V))
    if remat_step is None:
        remat_step = schedule == "1f1b"
    table = schedule_steps(schedule, n_stages, n_microbatches,
                           n_chunks=V)
    arrays = tuple(jnp.asarray(a) for a in _table_arrays(table,
                                                         n_stages))
    mb = B // n_microbatches
    x_mb = x.reshape((n_microbatches, mb) + x.shape[1:])

    params = stacked_params
    if V > 1:
        # Interleaved layer placement: global chunk j lives on device
        # j mod S, so the stacked layers must be reordered CHUNK-
        # MAJOR PER DEVICE before shard_map's contiguous leading-dim
        # split (device d then holds chunks d, d+S, …, d+(V−1)S).
        # A gather is differentiable; the stage-axis sharding spec is
        # unchanged.
        lc = n_layers // (n_stages * V)
        order = numpy.zeros(n_layers, numpy.int32)
        pos = 0
        for d in range(n_stages):
            for a in range(V):
                j = a * n_stages + d
                for l in range(lc):
                    order[pos] = j * lc + l
                    pos += 1
        order = jnp.asarray(order)
        params = jax.tree_util.tree_map(
            lambda p: jnp.take(p, order, axis=0), stacked_params)

    def stage_fn(p, x_all, *tbl):
        return _scheduled_body(fn, p, x_all, tbl, stage_axis, V,
                               remat_step)

    pspec = jax.tree_util.tree_map(
        lambda p: P(stage_axis, *([None] * (p.ndim - 1))), params)
    out = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(pspec, P()) + (P(),) * len(arrays),
        out_specs=P(), check_vma=False)(params, x_mb, *arrays)
    return out.reshape((B,) + out.shape[2:])
