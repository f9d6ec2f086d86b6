"""Driver ``train_block``: build the trainer, drive its first dispatch
from the seed (which compiles and is compared with the plain
reference), warm until two dispatches take the same time, then time
dispatches for the window.

It knows nothing of a model family: the family's module builds the
program (``build_trainer``), does the yardstick's arithmetic and follows
the reference (``reference_train``).

What a run hands to the metric readers (its *record*):

    end_to_end      train_rate (items/s), setup_s
    spans           [{"name": "dispatch", "t0", "t1", "items"}, ...]
                    on the host's clock, seconds since the window began
    counters        attention traces, tpu_custom_call count
    window          {"seconds", "items", "dispatches"}
    yardstick       required FLOPs an item, kernel costs, peaks
    trace           the reduced device trace (``--trace 1``)
"""

import gc
import os
import time

from benchmark import checks as C
from benchmark import trace as T


def first_dispatch(trainer):
    """Drives the first dispatch from the seed through the window's own
    call and reads what is compared: the dispatch's mean loss, and per
    leaf the norm of the momentum state and of the parameters' change.
    Returns (readings, seconds of the dispatch)."""
    t0 = time.perf_counter()
    trainer.dispatch()
    trainer.wait()
    seconds = time.perf_counter() - t0
    loss_sum, n_ticks = trainer.loss_sum_and_ticks()
    program = trainer.state_norms()
    program["loss_sum"] = loss_sum
    program["loss"] = loss_sum / max(n_ticks, 1.0)
    return program, seconds


def build(ctx):
    family = ctx.family
    sz = family.sizes(ctx.config, ctx.rehearse)
    traffic = ctx.traffic()
    rows = traffic["batch"] * traffic["ticks"] * \
        traffic.get("dispatches_per_epoch", 4)
    backend = "cpu" if ctx.rehearse else "tpu"
    return sz, traffic, family.build_trainer(sz, traffic, ctx.seed, rows,
                                            backend, ctx.chips)


def control(ctx, seeds, control_seeds, variants, control_only=False):
    """What ``benchmark/control.py`` prints for a train cell.  One
    compiled program, re-seeded: for every seed the program's first
    dispatch against the reference (the LOWER readings); for the first
    ``control_seeds`` of them the reference in the program's place, in
    the precision below the configuration's and with each fault planted
    (the UPPER readings), each with the verdict ``train_checks`` gives
    at the cell's limits.  A state left unchanged reads 1 by the
    measure and needs no run."""
    family, emit = ctx.family, ctx.emit
    ctx.seed = seeds[0]
    sz = family.sizes(ctx.config, ctx.rehearse)
    traffic = ctx.traffic()
    programs = {}
    if not control_only:
        sz, traffic, trainer = build(ctx)
        for seed in seeds:
            if seed != seeds[0]:
                trainer.reseed(seed)
            programs[seed], seconds = first_dispatch(trainer)
            emit(phase="program", seed=seed, seconds=seconds,
                 loss=programs[seed]["loss"])
        trainer.close()
        del trainer
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        ref = family.reference_train(seed, sz, traffic, traffic["ticks"])
        rows = {}
        if not control_only:
            rows["program"] = programs[seed]
        if i < control_seeds:
            for name in variants:
                operand, fault = (None, name) if name in family.FAULTS \
                    else (name, None)
                rows[name] = family.reference_train(
                    seed, sz, traffic, traffic["ticks"],
                    operand=operand, fault=fault)
        rows = {k: C.train_checks(v, ref, ctx.limits())
                for k, v in rows.items()}
        # a state left unchanged inside the dispatch: every tick reads
        # the first tick's loss (no run needed)
        unchanged = abs(ref["tick_losses"][0] - ref["loss"]) / ref["loss"]
        emit(phase="readings", seed=seed, unchanged_loss_gap=unchanged,
             seconds=time.perf_counter() - t0, reference_loss=ref["loss"],
             correct={k: all(c["ok"] for c in v) for k, v in rows.items()},
             **{k: {c["name"]: [c["value"], c.get("leaf"), c["ok"]] +
                    ([c["worst"], c["worst_leaf"]] if "worst" in c
                     else []) for c in v} for k, v in rows.items()})


def run(ctx):
    import jax
    family, emit = ctx.family, ctx.emit
    emit(phase="setup.imports", seconds=ctx.since_start())
    t0 = time.perf_counter()
    sz, traffic, trainer = build(ctx)
    ticks, batch, seq = traffic["ticks"], traffic["batch"], traffic["seq"]
    built_s = time.perf_counter() - t0
    emit(phase="setup.build", seconds=built_s, sizes=sz,
         parameters=family.parameter_count(sz, seq),
         items_per_dispatch=trainer.items_per_dispatch,
         **ctx.meter.take())

    # First dispatch from the seed: compiles, and is what the reference
    # follows.  The same object goes on into the window.
    t0 = time.perf_counter()
    program, first_s = first_dispatch(trainer)
    loss_sum = program["loss_sum"]
    emit(phase="setup.first_dispatch", seconds=first_s,
         reading_state_s=time.perf_counter() - t0 - first_s,
         loss=program["loss"], ticks=ticks, **ctx.meter.take())

    # Warm dispatches until one takes what the one before it took (1%),
    # two at the least and five at the most: after a cold compile of
    # the block step one dispatch has been seen to run a quarter late
    # (PERF.md, PR 25), and it belongs to set-up, not to the window.
    warm = []
    while len(warm) < 2 or (len(warm) < 5 and
                            abs(warm[-1] - warm[-2]) > 0.01 * warm[-2]):
        t0 = time.perf_counter()
        trainer.dispatch()
        trainer.wait()
        warm.append(time.perf_counter() - t0)
    prev_sum = trainer.loss_sum_and_ticks()[0]
    emit(phase="setup.warm_dispatches", seconds=warm,
         loss=(prev_sum - loss_sum) / (ticks * len(warm)),
         **ctx.meter.take())

    # -- the window ---------------------------------------------------------
    stretch = T.TracedStretch(
        os.path.join(ctx.scratch, "trace", ctx.args.workload),
        traffic.get("trace_seconds", 10.0), ctx.trace and not ctx.rehearse)
    spans = []
    # What set-up left on the heap (traced programs, the workflow) is
    # put out of the collector's sight for the window: a full
    # collection between two dispatches walks all of it, and dispatches
    # have been seen 0.17 s (warm) and 2.3 s (after a cold compile)
    # late with the device's own time unchanged (PERF.md, PR 25).
    gc.collect()
    gc.freeze()
    setup_s = ctx.since_start()
    begin = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            trainer.dispatch()
            trainer.wait()
        t1 = time.perf_counter()
        spans.append({"name": "dispatch", "t0": t0 - begin,
                      "t1": t1 - begin,
                      "items": trainer.items_per_dispatch})
        stretch.tick(t1 - begin)
        if t1 - begin >= ctx.seconds:
            break
    window_s = spans[-1]["t1"]
    gc.unfreeze()
    trace = stretch.finish(window_s)
    in_window = ctx.meter.take()
    sums = trainer.loss_sum_and_ticks()
    nonfinite = trainer.nonfinite_ticks()
    items = sum(s["items"] for s in spans)
    emit(phase="window", seconds=window_s, dispatches=len(spans),
         items=items,
         dispatch_s=[s["t1"] - s["t0"] for s in spans][:32],
         mean_loss=(sums[0] - prev_sum) / (len(spans) * ticks),
         nonfinite_ticks=nonfinite, compiled_in_window=in_window)

    counters = {"attention": trainer.attention_traces()}
    if ctx.trace:
        counters["tpu_custom_calls"] = trainer.compiled_custom_calls()
    peak = ctx.memory_peak_bytes()
    trainer.close()
    del trainer

    # -- correct ------------------------------------------------------------
    t0 = time.perf_counter()
    reference = family.reference_train(ctx.seed, sz, traffic, ticks)
    emit(phase="reference", seconds=time.perf_counter() - t0,
         loss=reference["loss"], tick_losses=reference["tick_losses"],
         **ctx.meter.take())
    limits = ctx.limits()
    checks = C.train_checks(program, reference, limits)
    checks.append(C.check("compiled_in_window",
                          in_window["programs_compiled"], 0))
    checks.append(C.check("nonfinite_ticks", nonfinite, 0))

    record = {
        "correct": all(c["ok"] for c in checks), "checks": checks,
        "attempted": len(spans), "failed": 0,
        "end_to_end": {"train_rate": items / window_s,
                       "setup_s": setup_s},
        "spans": spans, "counters": counters,
        "window": {"seconds": window_s, "items": items,
                   "dispatches": len(spans)},
        "memory_peak_bytes": peak, "chips": ctx.chips,
        "peaks": ctx.peaks,
        "yardstick": {
            "flops_per_item": family.train_flops_per_item(sz, seq),
            "flash": family.flash_call_cost(sz, batch, seq),
            "flash_calls_per_dispatch": ticks * sz["blocks"]},
        "trace": trace,
    }
    if trace:
        trace["dispatches"] = sum(1 for s in spans
                                  if s["t1"] <= trace["until"])
        emit(phase="trace", **T.earlier_line(trace))
    return record
