"""``python chip_smoke.py`` — the quickest proof that veles_tpu still
starts on the chip.

Drives the main path once, through the entry points a user calls, at
the full width of the LM the repo trains (``bench.py``'s LM geometry:
embed 2048, 16 heads x 128, seq 1024, vocab 16384, batch 8, 8 ticks a
dispatch, per-block remat; random weights from a seed, synthetic
corpus from a seed):

1. **train** — ``Launcher`` -> ``TinyLMWorkflow`` -> ``StepCompiler``,
   backend asked for as ``tpu`` by NAME, one epoch of three block
   dispatches; the first loss must be what this initialization gives,
   the training loss must not rise, the health sentinel must be
   clean, and the compiled block program must hold the flash kernel
   (``tpu_custom_call``);
2. **export** — ``export_workflow`` of those weights, then the trainer
   is freed;
3. **serve** — the ``ModelServer`` that ``python -m veles_tpu.serve``
   builds (paged decode, default kernel settings, ``--warmup``)
   answers ``POST /api/generate`` over loopback HTTP; greedy tokens
   must equal local ``ExportedModel.generate()``, the logits of one
   short forward must equal the host ``forward_numpy`` mirror (to an
   f32 tolerance with the device's matmuls at ``highest`` precision,
   to a bf16-sized bound at the precision the server runs), and
   ``/stats`` must show the breaker closed with no rebuild, replay,
   program error or warm-up failure.

``--chips 4`` runs the data-parallel path INSTEAD (and nothing else):
the same LM over ``make_mesh()`` of the four local chips with
``apply_dp_sharding``, compared with the same seed on one of those
chips in the same process.

Everything happens in ONE process: a chip belongs to one process at a
time, so a parent that touched JAX could not hand it to a child.  The
last line of stdout is one JSON object, ``{"ok": true, "device":
{"platform": "tpu", "kind": ..., "count": N}}``; the exit code is 0
only if the device is a TPU and every phase and check passed.
Earlier lines (one JSON object each) carry what is worth keeping:
compile and run seconds apart, step time around
``block_until_ready``, tokens, peak device bytes, which attention
path executed, the compile-cache directory and its hits and misses.
"""

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: The LM bench geometry (bench.py LM_*): ~640M parameters.  One epoch
#: is one validation block and two training blocks, all of ``ticks``
#: ticks, so the run compiles ONE block program.
#:
#: ``first_loss`` is what the repo's initialization gives at this
#: depth and width, NOT ln(vocab) = 9.70: nothing normalizes the
#: residual stream before the tied head, so the logits' spread — and
#: the loss — grows with depth (11.6 at 2 blocks, 15.6 at 6, 20.9 at
#: 12; CPU backend, XLA attention path, scratch run of PR 21).  The
#: chip has to reproduce that number, whatever one thinks of it.
LM_GEOMETRY = dict(vocab=16384, seq=1024, embed=2048, heads=16,
                   blocks=12, batch=8, ticks=8, train_blocks=2,
                   first_loss=20.9)
#: The sample's default rate (0.01, momentum 0.9) diverges from that
#: initialization within the first dispatch (gradient norm ~100 per
#: sequence: non-finite ticks on the chip and on the CPU alike); at
#: this rate the loss falls steadily.
LEARNING_RATE = 1e-4

#: How the smoke server is started (the flags are
#: ``python -m veles_tpu.serve``'s own) and what it is asked.  The
#: warm-up grid grows with log2 of both the batch and the table width
#: and every entry is a compile of the full-width model, so the batch
#: is small and the KV blocks large: 24 programs, not 59.
SERVE_FLAGS = ("--max-batch", "2", "--kv-block-size", "128")
PROMPT_LENGTHS = (5, 37, 200, 37)
MAX_NEW_TOKENS = 8
#: Sequence length of the forward compared with ``forward_numpy``.
PARITY_SEQ = 64

#: |first loss - geometry["first_loss"]| bound; the training loss
#: must then stay between ln(vocab) and the first loss, each widened
#: by the same bound.
LOSS_BOUND = 0.5
#: f32 tolerance, device logits vs the host numpy mirror, with the
#: device's matmuls at ``highest`` precision (different reduction
#: orders through ``blocks`` layers of width 2048).
LOGITS_RTOL = 2e-3
LOGITS_ATOL = 2e-3
#: At the DEFAULT matmul precision — what the server runs — a TPU
#: rounds f32 operands to bf16 (8 bits of mantissa): the bound is a
#: fraction of the largest logit, not an f32 tolerance.
LOGITS_DEFAULT_PRECISION_FRACTION = 0.05


def emit(**fields):
    """One JSON object per line on stdout, flushed — a killed run
    still shows how far it got."""
    print(json.dumps(fields, default=str), flush=True)


class SmokeFailure(Exception):
    """A check of this script did not hold."""


def check(condition, message):
    if not condition:
        raise SmokeFailure(message)


# -- compile accounting ----------------------------------------------------

class CompileCounters(object):
    """What the program's own ``compile.*`` counters
    (``veles_tpu.observability.startup``: JAX's backend compiles and
    persistent-cache hits and misses, by ``jax.monitoring``) gained
    since the last ``take()``, so each phase reports its own."""

    FIELDS = {"compile.seconds": "compile_s",
              "compile.programs": "programs_compiled",
              "compile.cache_hits": "cache_hits",
              "compile.cache_misses": "cache_misses"}

    def __init__(self):
        from veles_tpu.observability import startup
        startup.install()
        self._seen = self._read()

    def _read(self):
        from veles_tpu.observability import metrics
        totals = dict.fromkeys(self.FIELDS.values(), 0)
        for series in metrics.registry.metrics():
            field = self.FIELDS.get(series.name)
            # of the seconds, the backend compiles (or cached loads)
            if field and series.labels.get("stage", "backend") == \
                    "backend":
                totals[field] += series.value
        return totals

    def take(self):
        now, seen = self._read(), self._seen
        self._seen = now
        out = {k: v - seen[k] for k, v in now.items()}
        out["compile_s"] = round(out["compile_s"], 3)
        return out


def device_bytes():
    """(in use, peak) bytes of the first local device, or (None, None)
    where the backend keeps no such statistics (the CPU)."""
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_in_use"), stats.get("peak_bytes_in_use")


# -- phases ----------------------------------------------------------------

def barrier_phase():
    """Establishes that ``block_until_ready`` WAITS on this machine:
    a chain of matmuls whose FLOPs cannot finish faster than the
    chip's peak allows must keep ``block_until_ready`` at least that
    long, and a value fetched right after must arrive at once."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.observability import attribution
    n, chain = 8192, 48
    peak = attribution.device_peak_tflops()
    check(peak is not None, "no peak FLOP/s known for this device "
          "kind — add it to attribution.DEVICE_PEAK_TFLOPS")

    @jax.jit
    def work(x):
        def body(_, y):
            return jnp.dot(y, x, preferred_element_type=jnp.float32
                           ).astype(jnp.bfloat16) * 0.01
        return jax.lax.fori_loop(0, chain, body, x)

    x = jnp.full((n, n), 0.01, jnp.bfloat16)
    float(work(x)[0, 0])  # compile and warm both programs
    floor_s = 2.0 * n ** 3 * chain / (peak * 1e12)
    t0 = time.perf_counter()
    out = work(x)
    enqueue_s = time.perf_counter() - t0
    out.block_until_ready()
    ready_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    float(out[0, 0])
    fetch_s = time.perf_counter() - t1
    waits = ready_s >= floor_s and fetch_s < 0.5 * ready_s
    emit(phase="barrier", block_until_ready_waits=waits,
         enqueue_s=round(enqueue_s, 6), ready_s=round(ready_s, 6),
         fetch_after_ready_s=round(fetch_s, 6),
         peak_bound_s=round(floor_s, 6),
         note="ready_s >= peak_bound_s: the call cannot have "
              "returned before the chip finished")
    check(waits, "block_until_ready returned after %.4fs, before "
          "the %.4fs the chip needs at peak (or the fetch after it "
          "still waited %.4fs) — it is not a barrier here" %
          (ready_s, floor_s, fetch_s))


def build_lm(geometry, seed, backend):
    """``Launcher`` -> ``TinyLMWorkflow`` at ``geometry``, initialized
    on the backend asked for BY NAME (``root.common.engine.backend``,
    what ``-a`` sets)."""
    import numpy
    import veles_tpu.prng as prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import (FirstTokenLoader,
                                                TinyLMWorkflow)
    g = geometry
    n_valid = g["batch"] * g["ticks"]
    n_train = n_valid * g["train_blocks"]

    class SeededCorpus(FirstTokenLoader):
        """Uniform random tokens, next-token labels."""

        def load_data(self):
            rng = numpy.random.RandomState(seed)
            self.original_data.mem = rng.randint(
                0, g["vocab"], (n_valid + n_train, g["seq"])
            ).astype(numpy.int32)
            self.original_labels.mem = numpy.roll(
                self.original_data.mem, -1, axis=1)
            self.class_lengths = [0, n_valid, n_train]

    root.common.engine.backend = backend
    root.common.engine.remat = True
    prng.reset()
    prng.get(0).seed(seed)
    launcher = Launcher()
    wf = TinyLMWorkflow(
        launcher, vocab_size=g["vocab"], seq_len=g["seq"],
        embed_dim=g["embed"], n_heads=g["heads"],
        n_blocks=g["blocks"], minibatch_size=g["batch"],
        ticks_per_dispatch=g["ticks"], max_epochs=1,
        learning_rate=LEARNING_RATE, loader_cls=SeededCorpus,
        # Random tokens need not cover the vocabulary.
        loader_config={"validate_labels": False})
    launcher.initialize()
    return launcher, wf


def kernel_expected(geometry):
    """Whether the flash kernel must be in the train step — asked of
    the dispatch's own selection rule (knob, platform, geometry), so
    the check follows what the code decides, not a flag."""
    from veles_tpu.ops import attention
    g = geometry
    shape = (g["batch"], g["seq"], g["heads"], g["embed"] // g["heads"])
    return attention._selects_pallas(shape, shape)


def run_epoch(launcher, wf, geometry, meter, label):
    """Runs the workflow to its end (one epoch) and returns what the
    run showed; raises on a non-finite or implausible loss."""
    from veles_tpu import resilience
    from veles_tpu.loader.base import TRAIN, VALID
    from veles_tpu.observability import attribution
    g = geometry
    attribution.reset()
    resilience.stats.reset()
    t0 = time.perf_counter()
    launcher.run()
    wall = time.perf_counter() - t0
    decision = wf.decision
    perf = attribution.perf_summary() or {}
    compiled = meter.take()
    losses = {"valid": decision.epoch_loss[VALID],
              "train": decision.epoch_loss[TRAIN]}
    nonfinite = decision.epoch_nonfinite[VALID] + \
        decision.epoch_nonfinite[TRAIN]
    counters = resilience.stats.snapshot()
    traced = {"pallas": counters.get("attention.kernel.pallas", 0),
              "xla": counters.get("attention.kernel.xla", 0)}
    tokens = g["batch"] * g["seq"] * g["ticks"]
    report = dict(
        phase=label, wall_s=round(wall, 3),
        run_s=round(wall - compiled["compile_s"], 3),
        dispatches=perf.get("dispatches"), ticks=perf.get("ticks"),
        steady_dispatch_ms=perf.get("last_step_ms"),
        tokens_per_dispatch=tokens,
        first_loss=losses["valid"], train_loss=losses["train"],
        expected_first_loss=g["first_loss"],
        ln_vocab=round(math.log(g["vocab"]), 4),
        loss_bound=LOSS_BOUND, learning_rate=LEARNING_RATE,
        nonfinite_ticks=nonfinite,
        grad_norm=decision.epoch_grad_norm[TRAIN],
        attention_traces=traced,
        peak_device_bytes=device_bytes()[1], **compiled)
    emit(**report)
    check(perf.get("dispatches") == 1 + g["train_blocks"],
          "expected %d dispatches, ran %r" %
          (1 + g["train_blocks"], perf.get("dispatches")))
    check(nonfinite == 0, "health sentinel: %r non-finite ticks" %
          (nonfinite,))
    first, train = losses["valid"], losses["train"]
    check(math.isfinite(first) and math.isfinite(train),
          "losses are %r" % (losses,))
    check(abs(first - g["first_loss"]) <= LOSS_BOUND,
          "first loss %.4f is not within %.2f of the %.4f this "
          "initialization gives" % (first, LOSS_BOUND,
                                    g["first_loss"]))
    check(math.log(g["vocab"]) - LOSS_BOUND <= train <=
          first + LOSS_BOUND,
          "training loss %.4f left [ln(vocab) = %.4f, first loss = "
          "%.4f] by more than %.2f" %
          (train, math.log(g["vocab"]), first, LOSS_BOUND))
    check(math.isfinite(decision.epoch_grad_norm[TRAIN]),
          "gradient norm is not finite")
    return report


def train_phase(geometry, seed, backend, meter, artifact):
    """Phases 1 and 2: train, show what the compiled step holds,
    export.  The trainer lives and dies inside this function — the
    served copy needs its device memory."""
    from veles_tpu.export import export_workflow
    launcher, wf = build_lm(geometry, seed, backend)
    emit(phase="train.build", backend=launcher.device.backend_name,
         device=repr(launcher.device), geometry=geometry)
    report = run_epoch(launcher, wf, geometry, meter, "train")
    expect = kernel_expected(geometry)
    t0 = time.perf_counter()
    text = wf.compiler.lower_last_block().compile().as_text()
    kernels = text.count("tpu_custom_call")
    path = "pallas" if kernels else "xla"
    emit(phase="train.compiled_step", attention_path=path,
         tpu_custom_calls=kernels, flash_kernel_expected=expect,
         parameters=sum(v.size for v in
                        wf.compiler._param_vecs.values()),
         seconds=round(time.perf_counter() - t0, 3), **meter.take())
    check(bool(kernels) == expect,
          "the compiled train step holds %d tpu_custom_call(s) but "
          "platform and geometry select the %s path" %
          (kernels, "pallas" if expect else "xla"))
    traced = report["attention_traces"]
    check(bool(traced["pallas"]) == expect and
          bool(traced["xla"]) != expect,
          "attention dispatch traced %r, expected only the %s path"
          % (traced, "pallas" if expect else "xla"))
    t0 = time.perf_counter()
    export_workflow(wf, artifact)
    emit(phase="export", seconds=round(time.perf_counter() - t0, 3),
         artifact_bytes=os.path.getsize(artifact))
    launcher.stop()


def free_device():
    """Drops what the finished phase left on the device."""
    import jax
    gc.collect()
    jax.clear_caches()
    emit(phase="free", device_bytes_in_use=device_bytes()[0])


def http_json(port, path, payload=None):
    url = "http://127.0.0.1:%d%s" % (port, path)
    data = None if payload is None else json.dumps(payload).encode()
    request = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(request, timeout=300) as response:
        return json.loads(response.read())


def serve_phase(path, geometry, seed, meter):
    """Phase 3: serve the artifact and hold it to two references."""
    import jax
    import numpy
    from veles_tpu import serve
    rng = numpy.random.RandomState(seed + 1)
    prompts = [rng.randint(0, geometry["vocab"], (1, n)).tolist()
               for n in PROMPT_LENGTHS]
    t0 = time.perf_counter()
    server = serve.build_server(
        [path, "--host", "127.0.0.1", "--port", "0", "--warmup",
         "--deadline", "300"] + list(SERVE_FLAGS))
    server.start()
    try:
        emit(phase="serve.start",
             seconds=round(time.perf_counter() - t0, 3),
             flags=" ".join(SERVE_FLAGS), **meter.take())
        served, seconds = [], []
        for tokens in prompts:
            t1 = time.perf_counter()
            reply = http_json(server.port, "/api/generate",
                              {"tokens": tokens,
                               "max_new_tokens": MAX_NEW_TOKENS})
            seconds.append(round(time.perf_counter() - t1, 4))
            served.append(reply["tokens"])
        stats = http_json(server.port, "/stats")
    finally:
        server.stop()
    # The served model object, now idle: the references below call it
    # directly (its dense, unpaged programs), not through the engine.
    # Loading the 5 GB artifact a second time would prove nothing
    # more and costs a minute.
    local = server.model
    counters = stats["counters"]
    emit(phase="serve.requests", prompt_lengths=PROMPT_LENGTHS,
         new_tokens=MAX_NEW_TOKENS, request_s=seconds,
         device=stats["device"], counters=counters,
         gauges=stats.get("gauges"), kv_pool=stats.get("kv_pool"),
         compile_cache=stats.get("compile_cache"),
         peak_device_bytes=device_bytes()[1], **meter.take())

    # Reference 1: the dense (unpaged) generate of a local model.
    t0 = time.perf_counter()
    expected = [local.generate(numpy.asarray(tokens, numpy.int32),
                               MAX_NEW_TOKENS).tolist()
                for tokens in prompts]
    emit(phase="serve.local_generate",
         seconds=round(time.perf_counter() - t0, 3),
         tokens_equal=served == expected,
         generated=[row[0][-MAX_NEW_TOKENS:] for row in served],
         **meter.take())
    # Reference 2: the host numpy mirror, which shares no device.
    seq = min(PARITY_SEQ, geometry["seq"])
    x = rng.randint(0, geometry["vocab"], (1, seq))
    t0 = time.perf_counter()
    served_logits = numpy.asarray(local.forward(x), numpy.float32)
    with jax.default_matmul_precision("highest"):
        device_logits = numpy.asarray(local.forward(x),
                                      numpy.float32)
    host_logits = numpy.asarray(local.forward_numpy(x),
                                numpy.float32)
    scale = float(numpy.abs(host_logits).max())
    err = float(numpy.abs(device_logits - host_logits).max())
    served_err = float(numpy.abs(served_logits - host_logits).max())
    close = bool(numpy.allclose(device_logits, host_logits,
                                rtol=LOGITS_RTOL, atol=LOGITS_ATOL))
    emit(phase="serve.logits_vs_forward_numpy",
         seconds=round(time.perf_counter() - t0, 3),
         shape=list(device_logits.shape), host_abs_max=scale,
         max_abs_err_highest_precision=err, rtol=LOGITS_RTOL,
         atol=LOGITS_ATOL, close=close,
         max_abs_err_default_precision=served_err,
         default_precision_bound=(
             LOGITS_DEFAULT_PRECISION_FRACTION * scale),
         finite=bool(numpy.isfinite(served_logits).all()),
         **meter.take())

    check(stats["device"]["platform"] == jax.devices()[0].platform,
          "/stats names device %r" % (stats["device"],))
    check(served == expected, "served greedy tokens differ from "
          "local ExportedModel.generate()")
    check(device_logits.shape == (1, seq, geometry["vocab"]),
          "logits shape %r" % (device_logits.shape,))
    check(close, "device logits differ from forward_numpy by %g "
          "(rtol %g, atol %g)" % (err, LOGITS_RTOL, LOGITS_ATOL))
    check(served_err <= LOGITS_DEFAULT_PRECISION_FRACTION * scale,
          "at the default matmul precision the device logits differ "
          "from forward_numpy by %g, more than %g of the largest "
          "logit %g" % (served_err,
                        LOGITS_DEFAULT_PRECISION_FRACTION, scale))
    check(counters.get("warmup.compiles", 0) > 0,
          "--warmup compiled nothing")
    check((stats.get("gauges") or {}).get("breaker_state", 0) == 0,
          "circuit breaker is not closed")
    for name in ("warmup.failures", "errors.program",
                 "kv.pool.resets", "breaker.rebuilds",
                 "breaker.trips", "readopt.rows",
                 "readopt.exhausted"):
        check(not counters.get(name), "/stats counter %s = %r" %
              (name, counters.get(name)))


def dp_phase(geometry, seed, backend, meter):
    """``--chips 4``: the data-parallel path against one chip."""
    import jax
    from veles_tpu.parallel import apply_dp_sharding, make_mesh
    from __graft_entry__ import _verify_tolerances
    devices = jax.local_devices()
    reports = {}
    for label, mesh_devices in (("one_chip", devices[:1]),
                                ("four_chips", devices[:4])):
        launcher, wf = build_lm(geometry, seed, backend)
        mesh = make_mesh(mesh_devices)
        apply_dp_sharding(wf, mesh)
        reports[label] = run_epoch(launcher, wf, geometry, meter,
                                   "dp." + label)
        if label == "four_chips":
            check_dp_layout(wf, geometry)
        launcher.stop()
        del launcher, wf
        free_device()
    rtol, atol = _verify_tolerances()
    for name in ("first_loss", "train_loss"):
        one, four = reports["one_chip"][name], \
            reports["four_chips"][name]
        close = abs(one - four) <= atol + rtol * abs(one)
        emit(phase="dp.compare", metric=name, one_chip=one,
             four_chips=four, rtol=rtol, atol=atol, close=close)
        check(close, "%s: one chip %.6f, four chips %.6f" %
              (name, one, four))


def check_dp_layout(wf, geometry):
    """What never-multi-chip code gets wrong: parameters on every
    chip, every batch vector SPLIT (``apply_dp_sharding`` replicates,
    silently, any whose leading dimension does not divide), the split
    reaching the compiled block program, and an all-reduce in it."""
    from jax.sharding import PartitionSpec
    compiler = wf.compiler
    n = wf.mesh.devices.size
    for name, vec in compiler._collect("params").items():
        placed = vec.devmem.sharding
        check(len(placed.device_set) == n and
              placed.is_fully_replicated,
              "parameter %s is not replicated over %d chips: %r" %
              (name, n, placed))
    # Per-sample vectors (indices, mask); the per-tick sample class
    # is one number and is rightly on every chip.
    vectors = [v for v in compiler.batch_vectors
               if v.shape and v.shape[0] == geometry["batch"]]
    check(len(vectors) >= 2, "found %d per-sample batch vectors" %
          len(vectors))
    for vec in vectors:
        check(vec.sharding.spec == PartitionSpec("data"),
              "batch vector of shape %r is not split over the data "
              "axis: %r" % (vec.shape, vec.sharding))
    compiled = compiler.lower_last_block().compile()
    blocks = compiled.input_shardings[0][2]
    split = {}
    for vec in vectors:
        shape = (geometry["ticks"],) + tuple(vec.shape)
        shard = blocks[str(id(vec))].shard_shape(shape)
        split[str(shape)] = list(shard)
        check(shard[1] * n == shape[1] and shard[0] == shape[0],
              "the compiled step takes a %r shard of the %r block" %
              (shard, shape))
    text = compiled.as_text()
    emit(phase="dp.layout", chips=n, parameters_replicated=True,
         block_shard_shapes=split,
         all_reduces=text.count("all-reduce"),
         tpu_custom_calls=text.count("tpu_custom_call"))
    check("all-reduce" in text,
          "the compiled data-parallel step holds no all-reduce")


# -- entry -----------------------------------------------------------------

def run(chips, geometry=LM_GEOMETRY, seed=20260926, backend="tpu",
        scratch=None):
    """Runs the phases; raises on the first failure.  ``geometry``,
    ``backend`` and ``scratch`` exist for the CPU rehearsal at a toy
    size (tests/test_chip_smoke.py) — the command line fixes them."""
    from veles_tpu.backends import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    meter = CompileCounters()
    emit(phase="start", chips=chips, seed=seed,
         compile_cache_dir=cache_dir,
         compile_cache_entries=len(os.listdir(cache_dir))
         if os.path.isdir(cache_dir) else 0)
    if backend == "tpu":
        barrier_phase()
    if chips == 4:
        dp_phase(geometry, seed, backend, meter)
        return
    own = scratch is None
    scratch = scratch or tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        artifact = os.path.join(scratch, "lm.veles.tgz")
        train_phase(geometry, seed, backend, meter, artifact)
        free_device()
        serve_phase(artifact, geometry, seed, meter)
    finally:
        if own:
            shutil.rmtree(scratch, ignore_errors=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split(
        "\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): train -> export -> serve on one chip; "
             "4: the data-parallel path over four chips, compared "
             "with one of them, and no other phase")
    args = parser.parse_args(argv)
    device = None
    t0 = time.perf_counter()
    try:
        from veles_tpu.backends import device_entry
        device = device_entry()
        check(device["platform"] == "tpu",
              "JAX runs on %r, not on a TPU" % (device,))
        check(device["count"] >= args.chips,
              "--chips %d needs %d devices, JAX sees %d" %
              (args.chips, args.chips, device["count"]))
        run(args.chips)
    except BaseException as e:
        traceback.print_exc()
        emit(ok=False, device=device,
             error="%s: %s" % (type(e).__name__, e),
             seconds=round(time.perf_counter() - t0, 1))
        if not isinstance(e, Exception):
            raise
        return 1
    emit(phase="done", seconds=round(time.perf_counter() - t0, 1))
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
