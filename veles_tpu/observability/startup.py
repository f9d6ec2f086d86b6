"""The program's own account of its start-up: one record a compiled
program, and the set-up spans.

From process start to the first steady dispatch most of the time goes
into work that happens once: units initialise, the step's closures are
built, JAX traces the step, lowers it, and compiles it or loads it
from the persistent cache.  ``jax.monitoring`` reports every trace,
lowering and backend compile WITH the function's name, and the
persistent cache's hits and misses between them; :func:`install`
registers ONE set of listeners (at first use, idempotent) and keeps,
for each program compiled in the process, one record::

    {"program": "block_step", "step": True,
     "t0": 12.31, "t1": 45.80,           # on :data:`_timer`'s clock
     "trace_s": 21.4, "traces": 1,       # Python tracing to a jaxpr
     "lower_s": 6.9, "lowers": 1,        # jaxpr to an MLIR module
     "compile_s": 5.1,                   # backend compile, or the
                                         # load of a cached executable
     "cache": "hit", "retrieval_s": 4.2,
     "inside": "step.enqueue"}

``program`` is JAX's ``fun_name`` without the ``jit(...)`` or ``jit_``
the lowering and the compile put around it.  A record opens with a program's first
stage and closes with its backend compile; what is traced or lowered
twice before that is added and counted (``traces``, ``lowers``).
``cache`` is ``"hit"``, ``"miss"`` (compiled and written) or None
(the cache was not asked, or the program was too small to keep);
``retrieval_s`` is the part of ``compile_s`` spent reading and loading
the cached executable.  ``step`` says whether it is one of
``StepCompiler``'s programs (:data:`STEP_PROGRAMS`); ``inside`` names
the ``veles.*`` span open on the thread at the record's latest stage
(``tracing.inside()``).  A stage's seconds are its own: a program
compiled while another is being traced (an eager operation on a
constant) gets its record, and its seconds come off the trace around
it; a function traced INSIDE another stage (a ``jit`` within the
traced function, a lowering rule that traces) is part of that stage.

While the ring is on (``tracing.enable()`` / ``--trace-out``) each
stage is also a ring span — ``compile.trace``, ``compile.lower``,
``compile.backend`` with ``program=`` and ``cache=`` — child of the
span that was open, so the Chrome trace of a start shows them.
Counters in the process registry: ``compile.programs``,
``compile.cache_hits``, ``compile.cache_misses`` labelled
``kind="step"|"other"``, and ``compile.seconds`` labelled ``kind=``
and ``stage="trace"|"lower"|"backend"``.

:func:`span` is the set-up span: a ``tracing.annotated`` span whose
``{"name", "t0", "t1", "seconds"}`` is kept (``launcher.initialize``,
``step.build``); :func:`spans` reads them back, :func:`compiles` the
compile records.  Both are bounded.  ``attribution.reset()`` clears
them; the listeners and the process's ``compile.*`` counters stay.
"""

import collections
import threading
import time

from . import metrics, tracing

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SECONDS_FIELD = {"trace": "trace_s", "lower": "lower_s",
                  "backend": "compile_s"}

#: ``StepCompiler``'s programs, by the names of its step functions.
STEP_PROGRAMS = frozenset((
    "block_step", "train_step", "infer_step", "block_step_hyper",
    "train_step_hyper"))

#: How many compile records :func:`compiles` keeps (a benchmark run
#: compiles some 60 programs before its window and as many after).
KEPT = 512
#: How many set-up spans :func:`spans` keeps.
KEPT_SPANS = 64

_lock = threading.Lock()
_records = collections.deque(maxlen=KEPT)
_open = {}                # program -> its record, until it compiles
_spans = collections.deque(maxlen=KEPT_SPANS)
_compiled = [0, None]     # programs compiled, when the last one ended
_local = threading.local()
_installed = False
#: The one clock that compile records, set-up spans and
#: ``attribution``'s dispatch records lie on (injectable for tests).
_timer = time.perf_counter


def _thread():
    state = getattr(_local, "state", None)
    if state is None:
        state = _local.state = {
            "stages": [],          # open stages, outermost first
            "cache": None, "retrieval_s": 0.0,
            # running totals; a dispatch takes what was added while
            # it was open: seconds of stages outside ``step.lower``,
            # programs compiled, seconds of set-up spans
            "charged": [0.0, 0, 0.0]}
    return state


def install():
    """Registers the listeners with ``jax.monitoring``, once."""
    global _installed
    if _installed:
        return
    with _lock:
        if _installed:
            return
        import jax.monitoring as monitoring
        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def reset():
    """Forgets every record and span (test isolation).  The
    ``compile.*`` counters are the process's running totals and stay:
    ``chip_smoke.py`` takes each phase's share as a difference across
    such a reset."""
    with _lock:
        _records.clear()
        _open.clear()
        _spans.clear()
        _compiled[:] = [0, None]
    _local.state = None


def compiles():
    """The last :data:`KEPT` compile records, oldest first (copies)."""
    with _lock:
        return [dict(r) for r in _records]


def spans():
    """The last :data:`KEPT_SPANS` set-up spans, oldest first."""
    with _lock:
        return [dict(s) for s in _spans]


def compiled():
    """``(programs compiled in this process, when the last compile
    ended)`` — the second None before the first."""
    with _lock:
        return tuple(_compiled)


def charged():
    """This thread's running totals ``(seconds of compile stages
    outside step.lower, programs compiled, seconds of set-up spans)``:
    a dispatch takes them when it opens and when it closes, and the
    difference is what fell inside it (of the set-up spans only
    ``step.build`` ever does)."""
    return tuple(_thread()["charged"])


class _SetupSpan(object):
    __slots__ = ("name", "_annotated", "_t0")

    def __init__(self, name, attrs):
        self.name = name
        self._t0 = _timer()
        self._annotated = tracing.annotated(name, **attrs)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._annotated.__exit__(*exc)
        t1 = _timer()
        _thread()["charged"][2] += t1 - self._t0
        with _lock:
            _spans.append({"name": self.name, "t0": self._t0,
                           "t1": t1, "seconds": t1 - self._t0})
        return False


def span(name, **attrs):
    """A set-up span: ``tracing.annotated(name)`` (a ``veles.*``
    annotation on the profiler's clock, a ring span while the ring is
    on) whose begin, end and seconds :func:`spans` keeps.  Opens when
    called; use as ``with startup.span("launcher.initialize"):``."""
    install()
    return _SetupSpan(name, attrs)


# -- the listeners -----------------------------------------------------------

def _program(fun_name):
    """``block_step`` for the trace's ``block_step`` and for the
    lowering's and the compile's ``jit(block_step)`` (the module's
    name, ``jit_block_step`` once XLA has it)."""
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name[4:] if name.startswith("jit_") else name


def _on_start(event, value, fun_name=None, **_kw):
    """A stage begins (JAX records its start as a scalar)."""
    stage = _STAGES.get(event)
    if stage is None:
        return
    state = _thread()
    stages = state["stages"]
    # a function traced inside another stage (a jit inside the traced
    # function, a lowering rule that traces) is part of that stage:
    # no ring span, no record
    ring = None
    if not (stage == "trace" and stages):
        ring = tracing.span("compile." + stage,
                            program=_program(fun_name))
    if stage == "backend":
        state["cache"], state["retrieval_s"] = None, 0.0
    # [stage, began, ring span, seconds of recorded stages inside it]
    stages.append([stage, _timer(), ring, 0.0])


def _on_event(event, **_kw):
    found = _CACHE_EVENTS.get(event)
    if found is not None:
        _thread()["cache"] = found


def _on_duration(event, seconds, fun_name=None, **_kw):
    if event == _RETRIEVAL_EVENT:
        _thread()["retrieval_s"] = seconds
        return
    stage = _STAGES.get(event)
    if stage is None:
        return
    state = _thread()
    stages = state["stages"]
    t1 = _timer()
    if stages and stages[-1][0] == stage:
        _stage, t0, ring, inner = stages.pop()
    else:
        # the listeners came in the middle of this stage
        t0, ring, inner = t1 - seconds, tracing._NULL, 0.0
    if ring is None:
        if stages:
            stages[-1][3] += inner
        return
    if stages:
        stages[-1][3] += seconds
    program = _program(fun_name)
    cache, retrieval_s = (state["cache"], state["retrieval_s"]) \
        if stage == "backend" else (None, 0.0)
    ring.set(program=program, cache=cache)
    ring.finish()
    own = max(seconds - inner, 0.0)
    where = tracing.inside()
    if where != "step.lower":
        state["charged"][0] += own
    if stage == "backend":
        state["charged"][1] += 1
    _fold(stage, program, t0, t1, own, cache, retrieval_s, where)


def _fold(stage, program, t0, t1, seconds, cache, retrieval_s, where):
    kind = "step" if program in STEP_PROGRAMS else "other"
    with _lock:
        record = _open.get(program)
        if record is None:
            if len(_records) == KEPT:
                oldest = _records[0]
                if _open.get(oldest["program"]) is oldest:
                    del _open[oldest["program"]]
            record = _open[program] = {
                "program": program, "step": kind == "step",
                "t0": t0, "t1": t1, "trace_s": 0.0, "traces": 0,
                "lower_s": 0.0, "lowers": 0, "compile_s": 0.0,
                "cache": None, "retrieval_s": 0.0, "inside": where}
            _records.append(record)
        record["t1"], record["inside"] = t1, where
        record[_SECONDS_FIELD[stage]] += seconds
        if stage == "backend":
            record["cache"], record["retrieval_s"] = cache, retrieval_s
            del _open[program]
            _compiled[0] += 1
            _compiled[1] = t1
        else:
            record[stage + "s"] += 1
    reg = metrics.registry
    reg.counter("compile.seconds",
                labels={"kind": kind, "stage": stage}).inc(seconds)
    if stage == "backend":
        labels = {"kind": kind}
        reg.counter("compile.programs", labels=labels).inc()
        if cache is not None:
            reg.counter("compile.cache_%s" % (
                "hits" if cache == "hit" else "misses"),
                labels=labels).inc()
