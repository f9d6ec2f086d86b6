"""The program's account of its own start-up and of a late dispatch
(ISSUE 37; docs/observability.md, "Start-up"): one compile record a
program from JAX's own events (``observability.startup``), the set-up
spans, the dispatch record's compile fields and clock, the
late-dispatch warning, and the EWMA that a compiling dispatch stays
out of.  A fake ``jax.monitoring`` feed and the records' injectable
clock (``startup._timer``); one real tiny workflow.  CPU only; nothing here asserts a time.
"""

import logging
import re

import jax.monitoring as monitoring
import pytest

from veles_tpu.config import root
from veles_tpu.launcher import Launcher
from veles_tpu.observability import (attribution, metrics, programs,
                                     startup, tracing)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def _reset():
    tracing.reset()
    attribution.reset()
    programs.reset()
    # the process's running totals, which no reset of the program's
    # clears: every test here counts from zero
    metrics.registry.remove_prefix("compile.")
    root.common.observability.peak_tflops = None


@pytest.fixture(autouse=True)
def _clean():
    _reset()
    startup.install()
    yield
    _reset()


@pytest.fixture
def clock(monkeypatch):
    """The clock of the compile records, the set-up spans and the
    dispatch records, moved by hand."""
    now = [100.0]
    monkeypatch.setattr(startup, "_timer", lambda: now[0])
    return now


def _stage(clock, event, name, seconds, cache=None, retrieval=None,
           inside=None):
    """What JAX reports of one stage: its start as a scalar, the
    persistent cache's events, then its duration."""
    monitoring.record_scalar(event, 0.0, fun_name=name)
    if inside is not None:
        inside()
    clock[0] += seconds
    if cache is not None:
        monitoring.record_event(cache)
    if retrieval is not None:
        monitoring.record_event_duration_secs(RETRIEVAL, retrieval)
    monitoring.record_event_duration_secs(event, seconds, fun_name=name)


def _compile(clock, name="block_step", cache=HIT, retrieval=0.5):
    _stage(clock, TRACE, name, 4.0)
    _stage(clock, LOWER, "jit(%s)" % name, 2.0)
    _stage(clock, BACKEND, "jit(%s)" % name, 1.0, cache, retrieval)


def _counter(name, **labels):
    series = metrics.registry.peek(name, labels or None)
    return series.value if series is not None else 0


# -- one compile record a program --------------------------------------------

def test_three_stages_land_in_one_record_with_the_fun_name(clock):
    _compile(clock)
    (record,) = startup.compiles()
    assert record == {
        "program": "block_step", "step": True, "t0": 100.0,
        "t1": 107.0, "trace_s": 4.0, "traces": 1, "lower_s": 2.0,
        "lowers": 1, "compile_s": 1.0, "cache": "hit",
        "retrieval_s": 0.5, "inside": None}
    assert startup.compiled() == (1, 107.0)


@pytest.mark.parametrize("cache,retrieval,found", [
    (HIT, 0.25, ("hit", 0.25)), (MISS, None, ("miss", 0.0)),
    (None, None, (None, 0.0))])
def test_hit_and_miss_are_told_apart(clock, cache, retrieval, found):
    _compile(clock, cache=cache, retrieval=retrieval)
    # the next program's compile does not inherit this one's answer
    _compile(clock, name="norms", cache=None, retrieval=None)
    first, second = startup.compiles()
    assert (first["cache"], first["retrieval_s"]) == found
    assert (second["cache"], second["retrieval_s"]) == (None, 0.0)
    assert _counter("compile.cache_hits", kind="step") == \
        (found[0] == "hit")
    assert _counter("compile.cache_misses", kind="step") == \
        (found[0] == "miss")


def test_a_second_lowering_adds_and_counts(clock):
    _stage(clock, TRACE, "block_step", 4.0)
    _stage(clock, LOWER, "jit(block_step)", 2.0)
    _stage(clock, TRACE, "block_step", 0.001)
    _stage(clock, LOWER, "jit(block_step)", 3.0)
    (open_record,) = startup.compiles()
    assert open_record["cache"] is None and \
        open_record["compile_s"] == 0.0
    _stage(clock, BACKEND, "jit(block_step)", 1.0, MISS)
    (record,) = startup.compiles()
    assert (record["traces"], record["lowers"]) == (2, 2)
    assert record["trace_s"] == pytest.approx(4.001)
    assert record["lower_s"] == 5.0 and record["cache"] == "miss"
    # compiled: the record is closed, the next stage opens another
    _stage(clock, LOWER, "jit(block_step)", 2.5)
    assert [r["lowers"] for r in startup.compiles()] == [2, 1]


@pytest.mark.parametrize("name", sorted(startup.STEP_PROGRAMS) +
                         ["norms", "_threefry_seed"])
def test_a_program_is_one_record_under_any_of_its_names(clock, name):
    _stage(clock, TRACE, name, 1.0)
    _stage(clock, LOWER, "jit(%s)" % name, 1.0)
    _stage(clock, BACKEND, "jit_%s" % name, 1.0)
    (record,) = startup.compiles()
    assert record["program"] == name
    assert record["step"] == (name in startup.STEP_PROGRAMS)
    kind = "step" if record["step"] else "other"
    assert _counter("compile.programs", kind=kind) == 1
    for stage in ("trace", "lower", "backend"):
        assert _counter("compile.seconds", kind=kind,
                        stage=stage) == 1.0


def test_inside_names_the_open_span(clock):
    with tracing.annotated("step"):
        with tracing.annotated("step.lower"):
            _stage(clock, TRACE, "block_step", 4.0)
            assert startup.compiles()[0]["inside"] == "step.lower"
        with tracing.annotated("step.enqueue"):
            _stage(clock, BACKEND, "jit(block_step)", 1.0, HIT, 0.5)
        _stage(clock, BACKEND, "jit(norms)", 1.0)
    _stage(clock, BACKEND, "jit(sample)", 1.0)
    assert [(r["program"], r["inside"]) for r in startup.compiles()] \
        == [("block_step", "step.enqueue"), ("norms", "step"),
            ("sample", None)]
    assert tracing.inside() is None


def test_a_function_traced_inside_another_stage_is_part_of_it(clock):
    def inner():
        _stage(clock, TRACE, "softmax", 0.5)
        _stage(clock, TRACE, "_where", 0.25)

    _stage(clock, TRACE, "block_step", 4.0, inside=inner)
    _stage(clock, LOWER, "jit(block_step)", 2.0, inside=inner)
    (record,) = startup.compiles()
    assert record["program"] == "block_step"
    assert (record["trace_s"], record["lower_s"]) == (4.0, 2.0)
    assert (record["traces"], record["lowers"]) == (1, 1)
    assert (record["t0"], record["t1"]) == (100.0, 107.5)


def test_a_program_compiled_inside_a_trace_takes_its_seconds_off(
        clock):
    def eager():
        _stage(clock, TRACE, "iota", 0.125)
        _stage(clock, LOWER, "jit(iota)", 0.25)
        _stage(clock, BACKEND, "jit(iota)", 0.5)

    # JAX's duration of the outer trace holds the inner program's
    _stage(clock, TRACE, "block_step", 4.0 + 0.875, inside=eager)
    iota, step = startup.compiles()
    assert (iota["program"], iota["lower_s"], iota["compile_s"]) == \
        ("iota", 0.25, 0.5)
    assert iota["trace_s"] == 0.0      # inside a stage: that stage's
    assert step["trace_s"] == pytest.approx(4.0 + 0.125)
    assert sum(r["trace_s"] + r["lower_s"] + r["compile_s"]
               for r in (iota, step)) == pytest.approx(4.875)


def test_compile_records_are_bounded(clock):
    for i in range(startup.KEPT + 3):
        _stage(clock, TRACE, "f%d" % i, 0.5)
    records = startup.compiles()
    assert len(records) == startup.KEPT == 512
    assert records[0]["program"] == "f3"
    assert "f0" not in startup._open and "f3" in startup._open


def test_listeners_are_installed_once(clock):
    startup.install()
    startup.install()
    _compile(clock)
    assert len(startup.compiles()) == 1
    assert _counter("compile.programs", kind="step") == 1
    assert _counter("compile.seconds", kind="step",
                    stage="trace") == 4.0


def test_stages_are_ring_spans_while_the_ring_is_on(clock):
    _compile(clock, name="norms")          # ring off: no span
    assert tracing.spans() == []
    tracing.enable()
    with tracing.annotated("step.enqueue"):
        _compile(clock, cache=MISS, retrieval=None)
    by_name = {s["name"]: s for s in tracing.spans()}
    parent = by_name["step.enqueue"]["id"]
    for name, cache in (("compile.trace", None),
                        ("compile.lower", None),
                        ("compile.backend", "miss")):
        span = by_name[name]
        assert span["parent"] == parent, name
        assert span["attrs"] == {"program": "block_step",
                                 "cache": cache}


# -- the dispatch record: compile fields and a clock -------------------------

def _dispatch(clock, seconds, program="block_step", compiles=None,
              lowers=None, gap=0.0):
    clock[0] += gap
    with attribution.dispatch(program=program, ticks=8) as step:
        if lowers:
            with step.lower():
                lowers()
        with step.enqueue():
            if compiles:
                compiles()
        clock[0] += seconds
        step.wait(None)


def test_the_record_splits_what_compiling_took_of_the_dispatch(clock):
    def lowers():
        _stage(clock, TRACE, "block_step", 4.0)
        _stage(clock, LOWER, "jit(block_step)", 2.0)
        clock[0] += 0.5                 # XLA's cost analysis

    def compiles():
        _stage(clock, BACKEND, "jit(block_step)", 1.0, HIT, 0.75)
        _stage(clock, TRACE, "sum", 0.25)

    _dispatch(clock, 3.0, compiles=compiles, lowers=lowers)
    _dispatch(clock, 3.0, gap=0.125)
    first, second = attribution.recent()
    assert first["lower_s"] >= 0.0       # the span's own clock
    assert (first["compile_s"], first["compiled"]) == (1.25, 1)
    assert (first["t0"], first["t1"], first["gap_s"]) == \
        (100.0, 110.75, None)
    assert first["t1"] - first["t0"] - first["compile_s"] - 6.5 == 3.0
    assert (second["compile_s"], second["compiled"],
            second["lower_s"], second["build_s"]) == (0.0, 0, 0.0, 0.0)
    assert (second["t0"], second["t1"], second["gap_s"]) == \
        (110.875, 113.875, 0.125)


def test_a_compiling_dispatch_stays_out_of_the_ewma(clock):
    """The first dispatch's seconds hold the compile: folded into the
    EWMA they would make ``device.step_ms`` and ``device.mfu`` read
    wrong for twenty dispatches."""
    root.common.observability.peak_tflops = 1.0
    _dispatch(clock, 3.0, compiles=lambda: _stage(
        clock, BACKEND, "jit(block_step)", 97.0))
    summary = attribution.perf_summary()
    assert summary["dispatches"] == 1 and summary["step_ms"] is None
    assert summary["last_step_ms"] == 100000.0
    assert metrics.registry.peek("device.step_ms") is None
    _dispatch(clock, 3.0)
    _dispatch(clock, 5.0)
    summary = attribution.perf_summary()
    assert summary["dispatches"] == 3
    assert summary["step_ms"] == 3000.0 + 0.25 * 2000.0
    assert metrics.registry.peek("device.step_ms").value == 3500.0
    assert [r["compiled"] for r in attribution.recent()] == [1, 0, 0]


# -- a late dispatch, with its record ----------------------------------------

@pytest.mark.parametrize("factor,late", [(1.3, 1), (1.1, 0)])
def test_the_late_dispatch_warning_fires_once(clock, caplog, factor,
                                              late):
    caplog.set_level(logging.WARNING, logger="attribution")
    for _ in range(6):
        _dispatch(clock, 4.0)
    assert _counter("device.late_dispatches") == 0
    _dispatch(clock, 4.0 * factor)
    for _ in range(3):
        _dispatch(clock, 4.0)
    assert _counter("device.late_dispatches") == late
    assert len([r for r in caplog.records
                if "late" in r.getMessage()]) == late


def test_the_warning_carries_the_split_and_the_compile_context(
        clock, caplog):
    """PR 36's case: six dispatches at their pace after a cold
    compile, the seventh late; the record says whose the time was."""
    caplog.set_level(logging.WARNING, logger="attribution")
    _stage(clock, BACKEND, "jit(block_step)", 30.0, MISS)
    for _ in range(6):
        _dispatch(clock, 4.0)
    _dispatch(clock, 6.5, gap=0.75)
    (warning,) = [r.getMessage() for r in caplog.records]
    # (the children's seconds are on the spans' own clock)
    assert re.match(
        r"dispatch 7 of block_step late: 6\.5 s against 4; serve 0 "
        r"upload 0 enqueue \S+ wait \S+ gc 0 \(0 full\) gap 0\.75; 1 "
        r"programs compiled, the last ended 24\.75 s before$", warning)
    late = attribution.recent()[-1]
    assert (late["gap_s"], late["t1"] - late["t0"]) == (0.75, 6.5)


def test_every_late_dispatch_counts_and_warnings_keep_a_distance(
        clock, caplog):
    caplog.set_level(logging.WARNING, logger="attribution")
    for _ in range(5):
        _dispatch(clock, 1.0)
    _dispatch(clock, 2.0)
    _dispatch(clock, 2.0)      # 2 s after the warning: counted only
    for _ in range(8):
        _dispatch(clock, 1.0)
    _dispatch(clock, 2.0)      # 12 s after it: warned again
    assert _counter("device.late_dispatches") == 3
    assert len([r for r in caplog.records
                if "late" in r.getMessage()]) == 2


def test_a_pause_between_dispatches_makes_none_late(clock):
    """An epoch's end, a snapshot, an evaluation: the time between
    two dispatches is ``gap_s`` and nobody's lateness."""
    for _ in range(6):
        _dispatch(clock, 1.0)
    _dispatch(clock, 1.0, gap=30.0)
    _dispatch(clock, 1.2, gap=5.0)
    assert _counter("device.late_dispatches") == 0
    assert [r["gap_s"] for r in attribution.recent()[-2:]] == \
        [30.0, 5.0]
    _dispatch(clock, 1.3)
    assert _counter("device.late_dispatches") == 1


def test_the_pace_follows_a_program_that_changed_it(clock):
    """The median is kept between dispatches and taken again when one
    passes it: a program that slows down for good is late until the
    median has moved, and not after."""
    for _ in range(4):
        _dispatch(clock, 1.0)
    for _ in range(12):
        _dispatch(clock, 2.0)
    # late while the 1 s dispatches hold the median: four against 1,
    # a fifth against 1.5, and then 2 s is the pace
    assert _counter("device.late_dispatches") == 5
    assert attribution._paces["block_step"][1] == 2.0


def test_a_compiling_dispatch_is_neither_late_nor_a_period(clock,
                                                           caplog):
    caplog.set_level(logging.WARNING, logger="attribution")
    for _ in range(5):
        _dispatch(clock, 1.0)
    _dispatch(clock, 1.0, compiles=lambda: _stage(
        clock, BACKEND, "jit(block_step)", 50.0))
    _dispatch(clock, 1.0)
    assert _counter("device.late_dispatches") == 0
    assert list(attribution._paces["block_step"][0]) == [1.0] * 6
    # programs keep their own pace
    _dispatch(clock, 9.0, program="train_step")
    _dispatch(clock, 9.0, program="train_step")
    assert _counter("device.late_dispatches") == 0


def test_reset_clears_the_new_state(clock):
    _compile(clock)
    with startup.span("launcher.initialize"):
        clock[0] += 2.0
    for _ in range(5):
        _dispatch(clock, 1.0)
    _dispatch(clock, 2.0)
    assert startup.compiles() and startup.spans()
    assert startup.charged() != (0.0, 0, 0.0)
    assert _counter("device.late_dispatches") == 1
    attribution.reset()
    assert startup.compiles() == [] and startup.spans() == []
    assert startup.compiled() == (0, None)
    assert startup.charged() == (0.0, 0, 0.0)
    assert attribution._paces == {} and \
        attribution._state["late_warned"] is None
    assert _counter("device.late_dispatches") == 0
    # the process's totals stay, as the listeners do
    assert _counter("compile.programs", kind="step") == 1
    _dispatch(clock, 1.0)
    assert attribution.recent()[0]["gap_s"] is None


# -- a real tiny workflow ------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_run():
    """A two-block tiny LM, three block dispatches with the ring on
    and a peak known (so the step is lowered for its FLOP estimate
    inside ``step.lower``)."""
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    import veles_tpu.prng as prng
    _reset()
    root.common.observability.peak_tflops = 1.0
    prng.reset()
    prng.get(0).seed(7)
    tracing.enable()
    launcher = Launcher()
    wf = TinyLMWorkflow(
        launcher, vocab_size=32, seq_len=8, embed_dim=16, n_heads=2,
        n_blocks=2, minibatch_size=4, max_epochs=1 << 30,
        ticks_per_dispatch=4, loader_config={"validate_labels": False})
    launcher.initialize()
    for _ in range(3):
        wf.loader.run()
    out = {"recent": attribution.recent(), "spans": startup.spans(),
           "compiles": startup.compiles(), "ring": tracing.spans(),
           "perf": attribution.perf_summary()}
    launcher.stop()
    _reset()
    return out


def test_a_workflow_leaves_its_setup_spans(tiny_run):
    spans = {s["name"]: s for s in tiny_run["spans"]}
    assert set(spans) == {"launcher.initialize", "step.build"}
    for span in spans.values():
        assert span["t0"] < span["t1"]
        assert span["seconds"] == span["t1"] - span["t0"]
    first = tiny_run["recent"][0]
    # the step is built inside its first dispatch
    assert first["t0"] < spans["step.build"]["t0"] and \
        spans["step.build"]["t1"] < first["t1"]
    assert first["build_s"] == spans["step.build"]["seconds"]
    assert spans["launcher.initialize"]["t1"] < first["t0"]
    ring = [s["name"] for s in tiny_run["ring"]]
    for name in ("launcher.initialize", "step.build", "step.lower"):
        assert ring.count(name) == 1, name


def test_a_workflows_first_dispatch_holds_the_compile(tiny_run):
    first, second, third = tiny_run["recent"]
    assert first["compile_s"] > 0 and first["compiled"] >= 1
    assert first["lower_s"] > 0
    assert first["gap_s"] is None
    for record in (second, third):
        assert record["compile_s"] == 0 and record["compiled"] == 0
        assert record["lower_s"] == 0 and record["build_s"] == 0
        assert record["gap_s"] is not None and record["gap_s"] >= 0
    for before, record in zip(tiny_run["recent"],
                              tiny_run["recent"][1:]):
        assert before["t0"] < before["t1"] <= record["t0"]
        assert record["gap_s"] == record["t0"] - before["t1"]
    # the compiling dispatch is not in the live gauge
    assert tiny_run["perf"]["step_ms"] < first["device_s"] * 1e3


def test_a_workflows_block_step_is_one_record(tiny_run):
    steps = [r for r in tiny_run["compiles"] if r["step"]]
    assert [r["program"] for r in steps] == ["block_step"]
    (record,) = steps
    first = tiny_run["recent"][0]
    assert first["t0"] < record["t0"] < record["t1"] < first["t1"]
    assert record["trace_s"] > 0 and record["lower_s"] > 0 and \
        record["compile_s"] > 0
    # lowered ONCE: the dispatch reuses the module the estimate lowered
    assert record["lowers"] == 1
    assert record["inside"] == "step.enqueue"
    assert record["trace_s"] + record["lower_s"] < first["lower_s"]
    ring = {s["name"]: s for s in tiny_run["ring"]
            if s["name"].startswith("compile.") and s["dur"] > 1e3
            and s["attrs"]["program"] == "block_step"}
    by_id = {s["id"]: s["name"] for s in tiny_run["ring"]}
    assert {n: by_id[s["parent"]] for n, s in ring.items()} == {
        "compile.trace": "step.lower", "compile.lower": "step.lower",
        "compile.backend": "step.enqueue"}


def test_chip_smoke_takes_the_programs_counters(clock):
    import chip_smoke
    meter = chip_smoke.CompileCounters()
    _compile(clock)
    _compile(clock, name="norms", cache=MISS, retrieval=None)
    assert meter.take() == {"compile_s": 2.0, "programs_compiled": 2,
                            "cache_hits": 1, "cache_misses": 1}
    assert meter.take() == {"compile_s": 0.0, "programs_compiled": 0,
                            "cache_hits": 0, "cache_misses": 0}


def test_chip_smoke_takes_each_phases_share_across_a_reset(clock):
    """``run_epoch`` resets the attribution before every run (twice in
    ``--chips 4``): each phase still reads what the feed sent in it."""
    import chip_smoke
    meter = chip_smoke.CompileCounters()
    _compile(clock)                          # the build
    attribution.reset()
    _compile(clock, name="norms", cache=MISS, retrieval=None)
    assert meter.take() == {"compile_s": 2.0, "programs_compiled": 2,
                            "cache_hits": 1, "cache_misses": 1}
    attribution.reset()
    _stage(clock, BACKEND, "jit(block_step)", 3.0, MISS)
    assert meter.take() == {"compile_s": 3.0, "programs_compiled": 1,
                            "cache_hits": 0, "cache_misses": 1}
    attribution.reset()
    assert meter.take() == {"compile_s": 0.0, "programs_compiled": 0,
                            "cache_hits": 0, "cache_misses": 0}
