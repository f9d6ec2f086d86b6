"""Device-time and MFU attribution for the fused step.

``StepCompiler`` dispatches are asynchronous — ``time.perf_counter``
around the call measures Python dispatch, not the chip.  This module
closes the gap: every dispatch runs inside a :class:`Dispatch`
(``with attribution.dispatch(...) as step``), which opens the ``step``
span and its children (``loader.serve_block``, ``step.upload``,
``step.enqueue``, ``step.wait``) on the profiler's clock, waits for a
small output leaf (``block_until_ready``: waiting, not transferring —
all outputs of one XLA computation complete together) and keeps ONE
record per dispatch: ordinal, program, ticks, the host's split
(``serve_s``, ``upload_s``, ``enqueue_s``, ``wait_s``, ``gc_s``,
``gc_full``), what compiling took of it (``lower_s``, ``compile_s``,
``compiled``, ``build_s``: ``observability.startup`` keeps the record
a program) and a clock (``t0``, ``t1``, ``gap_s``).  The last 64
records are :func:`recent`; the newest one's split rides
:func:`perf_summary` (launcher heartbeat ``perf`` section, web_status
row).  A dispatch that took more than :data:`LATE_FACTOR` × the median
of its program's recent ones counts into ``device.late_dispatches``
and is logged WITH its record (:func:`_judge`).  Combined with a FLOP
count per compiled step from XLA's HLO cost analysis (the ``Lowered``
is made once per program and kept for ``observability.programs``),
the record yields the live ``device.mfu`` gauge: XLA's count —
recomputation included — over host-timed dispatches, NOT the
benchmark's ``train_mfu_pct``.

Also owns the ``--xprof DIR`` capture window: a ``jax.profiler``
trace opened at the first fused dispatch and closed after N of them,
then reduced and printed by ``observability.profile``.

Knobs (``root.common.observability``):

* ``attribution`` (default True) — the per-dispatch sync costs one
  host round-trip per *block* of ticks; flip off for maximally
  async dispatch chains (spans still open; no record is kept);
* ``peak_tflops`` — the MFU denominator; defaults from the device
  kind table below (v5e bf16 = 197), None on unknown hardware
  (device time still publishes; the MFU gauge just stays silent).

Everything here is wall-clock accounting around an unchanged
computation: bits on device are identical with attribution on, off,
or absent.
"""

import collections
import gc
import itertools
import logging
import statistics
import threading

from . import metrics, startup, tracing
from ..config import root, get as config_get

#: device_kind substring → peak dense bf16 TFLOP/s (the MFU
#: denominator).  Substring match: jax reports kinds like
#: "TPU v5 lite" / "TPU v5e".
DEVICE_PEAK_TFLOPS = (
    ("v5 lite", 197.0),
    ("v5e", 197.0),
    ("v5p", 459.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)

#: EWMA smoothing for the live gauges (per dispatch).
EWMA_ALPHA = 0.25

#: A dispatch is late when it took (its ``t1 - t0``) more than this
#: many times the median of what its program's last
#: :data:`LATE_RECORDS` dispatches took, once :data:`LATE_KNOWN` of
#: them are known.  The time BETWEEN two dispatches (``gap_s``: an
#: epoch's end, a snapshot, an evaluation) is reported beside it and
#: makes none late.
LATE_FACTOR = 1.25
LATE_RECORDS = 15
LATE_KNOWN = 3
#: Seconds between two late-dispatch warnings (the counter counts
#: every late dispatch): on a shared host a step of milliseconds
#: passes the factor by jitter alone, some 4 to 6 times in a hundred.
LATE_WARN_EVERY_S = 10.0

_lock = threading.Lock()
_state = {
    "last_ms": None,       # the newest dispatch, unsmoothed
    "device_ms": None,     # EWMA ms per dispatch
    "mfu": None,           # EWMA model-flop utilization
    "dispatches": 0,
    "ticks": 0,
    "device_s_total": 0.0,
    "late_warned": None,   # when the last late-dispatch warning went
}
#: How many dispatch records :func:`recent` keeps.
RECENT = 64
_recent = collections.deque(maxlen=RECENT)
_local = threading.local()
#: The open outermost dispatch the gc hook charges collections to,
#: and when the collection under way began.
_gc = {"target": None, "t0": None}
_xprof = {"dir": None, "steps": 0, "done": 0, "started": False}
#: Optimizer observability (StepCompiler.compile publishes once per
#: compile): the configured kind(s), total slot bytes, and the ZeRO
#: shard fraction each dp rank persistently stores (1.0 = replicated).
_optimizer = {"kind": None, "state_bytes": None, "shard_frac": None}
_ordinals = itertools.count(1)
#: program -> [what its recent dispatches took, their median once
#: LATE_KNOWN are known, dispatches since that median was taken].
_paces = {}
#: configured-peak-value -> resolved FLOP/s (the device probe and
#: config walk are constant per process; never pay them per
#: dispatch).
_peak_cache = {}


def _config(name, default):
    return config_get(getattr(root.common.observability, name),
                      default)


def enabled():
    """Device-time attribution on?  (Default True — one host sync
    per dispatched BLOCK of ticks.)"""
    return bool(_config("attribution", True))


def reset():
    """Clears accumulated attribution state AND this module's
    ``device.*`` series in the process registry (test isolation) —
    attribution owns its gauges; the resilience shim's reset only
    touches counters created through it."""
    global _ordinals
    _ordinals = itertools.count(1)
    with _lock:
        _state.update(last_ms=None, device_ms=None, mfu=None,
                      dispatches=0, ticks=0, device_s_total=0.0,
                      late_warned=None)
        _recent.clear()
        _paces.clear()
        _optimizer.update(kind=None, state_bytes=None,
                          shard_frac=None)
    _xprof.update(dir=None, steps=0, done=0, started=False)
    _local.dispatch = _local.closed = None
    _gc["target"] = _gc["t0"] = None
    _peak_cache.clear()
    startup.reset()
    metrics.registry.remove_prefix("device.")
    metrics.registry.remove_prefix("optimizer.")
    metrics.registry.remove_prefix("moe.")


def device_peak_tflops():
    """The live device's peak dense bf16 TFLOP/s from
    :data:`DEVICE_PEAK_TFLOPS`, or None for a device the table does
    not know (a CPU, a new chip) — callers that report a utilization
    must treat None as "cannot say", never substitute a default."""
    import jax
    kind = jax.devices()[0].device_kind.lower()
    for sub, tflops in DEVICE_PEAK_TFLOPS:
        if sub in kind:
            return tflops
    return None


def peak_flops():
    """The MFU denominator in FLOP/s, or None when unknown.
    Memoized per configured value — this sits on the per-dispatch
    path and neither the config nor the device set changes mid-run."""
    configured = _config("peak_tflops", None)
    if configured in _peak_cache:
        return _peak_cache[configured]
    if configured:
        peak = float(configured) * 1e12
    else:
        peak = device_peak_tflops()
        if peak is not None:
            peak *= 1e12
    _peak_cache[configured] = peak
    return peak


# -- xprof capture window --------------------------------------------------

def configure_xprof(directory, steps=4):
    """Arms the capture window: a ``jax.profiler`` trace spanning the
    next ``steps`` fused dispatches (opened lazily at the first
    one)."""
    _xprof.update(dir=directory, steps=int(steps), done=0,
                  started=False)


def _xprof_step_begin():
    if _xprof["dir"] is None or _xprof["started"] \
            or _xprof["done"] >= _xprof["steps"]:
        return
    try:
        import jax
        from . import profile
        jax.profiler.start_trace(
            _xprof["dir"], profiler_options=profile.profile_options())
        _xprof["started"] = True
    except Exception:
        # The operator explicitly asked for a capture (--xprof):
        # a disarm must be LOUD, not a mystery empty directory.
        logging.getLogger("attribution").exception(
            "xprof capture could not start — disarming")
        _xprof["dir"] = None  # unusable; disarm rather than retrying


def _xprof_step_end(leaf):
    if not _xprof["started"]:
        return
    _xprof["done"] += 1
    if _xprof["done"] < _xprof["steps"]:
        return
    _device_sync(leaf)
    directory = _xprof["dir"]
    _xprof["started"] = False
    _xprof["dir"] = None
    try:
        import jax
        jax.profiler.stop_trace()
    except Exception as e:
        logging.getLogger("attribution").debug(
            "xprof stop_trace failed: %s", e)
        return
    try:
        from . import profile
        print(profile.report(profile.reduce_dir(directory)),
              flush=True)
    except Exception:
        # The trace is on disk either way; the reduction is a
        # convenience and must not take the training run down.
        logging.getLogger("attribution").exception(
            "could not reduce the xprof trace under %s", directory)


def _device_sync(leaf):
    """Waits for ``leaf`` — and with it every output of the dispatch
    that produced it — to be computed.  ``block_until_ready`` waits
    without transferring (chip_smoke.py prints the evidence that it
    does wait on this machine).  A failed computation raises here;
    that is the step failing, so it propagates."""
    if leaf is not None:
        leaf.block_until_ready()


# -- per-dispatch hooks (called by StepCompiler and the loader) ------------

def _on_gc(phase, info):
    """``gc.callbacks`` hook: seconds and generation of every
    collection that falls inside an open dispatch (a collection
    stops every Python thread, whichever thread set it off)."""
    target = _gc["target"]
    if target is None:
        return
    if phase == "start":
        _gc["t0"] = startup._timer()
    elif _gc["t0"] is not None:
        target.gc_s += startup._timer() - _gc["t0"]
        target.gc_full += info.get("generation") == 2
        _gc["t0"] = None


def _seconds(span):
    return span.seconds if span is not None else 0.0


class Dispatch(object):
    """The host's side of ONE dispatch of a step program: the
    ``step`` span, its children, and the record :func:`recent`
    keeps.  Re-entrant on a thread: the loader opens it around
    ``serve_block`` and ``StepCompiler.execute_block`` joins the
    same one."""

    __slots__ = ("ordinal", "program", "ticks", "flops", "gc_s",
                 "gc_full", "_span", "_serve", "_upload", "_lower",
                 "_enqueue", "_wait", "_depth", "_t0", "_leaf",
                 "_timed", "_opened", "_charged")

    def __init__(self):
        self.ordinal = next(_ordinals)
        self.program = None
        self.ticks = 1
        self.flops = None
        self.gc_s = 0.0
        self.gc_full = 0
        self._span = self._t0 = self._leaf = None
        self._serve = self._upload = self._enqueue = self._wait = None
        self._lower = self._opened = self._charged = None
        self._depth = 0
        self._timed = False

    def __enter__(self):
        self._depth += 1
        if self._depth == 1:
            if _on_gc not in gc.callbacks:
                gc.callbacks.append(_on_gc)
            startup.install()
            # Before the span: an annotation opened ahead of the
            # profiler session is not in its trace.
            _xprof_step_begin()
            self._charged = startup.charged()
            self._opened = startup._timer()
            self._span = tracing.annotated(
                "step", ordinal=self.ordinal, ticks=self.ticks)
            _local.dispatch = _gc["target"] = self
        return self

    # The children: each opens once a dispatch, and its seconds go
    # into the dispatch's record.

    def serve(self):
        """``loader.serve_block``: host index serving and stacking."""
        self._serve = tracing.annotated("loader.serve_block")
        return self._serve

    def upload(self):
        """``step.upload``: the host→device put of the dispatch's
        inputs."""
        self._upload = tracing.annotated("step.upload")
        return self._upload

    def lower(self):
        """``step.lower``: once a compiled program, its lowering
        from its arguments' shapes (trace and MLIR module) and XLA's
        cost analysis of it, for the FLOP estimate."""
        self._lower = tracing.annotated("step.lower")
        return self._lower

    def enqueue(self):
        """``step.enqueue``: the jitted call until it returns — the
        first of a program holds its backend compile or the load of
        its cached executable (the record's ``compile_s``)."""
        self._t0 = startup._timer()
        self._enqueue = tracing.annotated("step.enqueue")
        return self._enqueue

    def wait(self, leaf):
        """``step.wait``: ``block_until_ready`` on one output leaf,
        where attribution is on (else the leaf is only kept for the
        xprof window's last dispatch, and no record is made)."""
        self._leaf = leaf
        self._timed = enabled()
        if self._timed:
            self._wait = tracing.annotated("step.wait")
            with self._wait:
                _device_sync(leaf)

    def __exit__(self, exc_type, exc, tb):
        self._depth -= 1
        if self._depth:
            return False
        _local.dispatch = None
        if _gc["target"] is self:
            _gc["target"] = None
        self._span.set(program=self.program, ticks=self.ticks,
                       gc_s=self.gc_s, gc_full=self.gc_full)
        self._span.__exit__(exc_type, exc, tb)
        closed = startup._timer()
        before, _local.closed = getattr(_local, "closed", None), closed
        if exc_type is None:
            if self._timed and self._t0 is not None:
                device_s = closed - self._t0
                charged = startup.charged()
                record = {
                    "ordinal": self.ordinal,
                    "program": self.program,
                    "ticks": self.ticks,
                    "device_s": device_s,
                    "serve_s": _seconds(self._serve),
                    "upload_s": _seconds(self._upload),
                    "enqueue_s": _seconds(self._enqueue),
                    "wait_s": _seconds(self._wait),
                    "gc_s": self.gc_s,
                    "gc_full": int(self.gc_full),
                    "lower_s": _seconds(self._lower),
                    "compile_s": charged[0] - self._charged[0],
                    "compiled": charged[1] - self._charged[1],
                    "build_s": charged[2] - self._charged[2],
                    "t0": self._opened, "t1": closed,
                    "gap_s": None if before is None
                    else self._opened - before}
                _judge(record)
                record_step(device_s, flops=self.flops,
                            ticks=self.ticks, record=record)
            # After the record: closing the window reduces the trace
            # (and may compile for the scope table), which is none of
            # this dispatch's time.
            _xprof_step_end(self._leaf)
        self._leaf = None
        return False


def dispatch(program=None, ticks=None):
    """The :class:`Dispatch` this thread is inside of, or a new one;
    enter it with ``with``.  Arguments that are given are set on
    it."""
    step = getattr(_local, "dispatch", None)
    if step is None:
        step = Dispatch()
    if program is not None:
        step.program = program
    if ticks is not None:
        step.ticks = int(ticks)
    return step


def recent():
    """The last :data:`RECENT` dispatch records, oldest first: dicts
    of ``ordinal``, ``program``, ``ticks``, ``device_s`` (enqueue to
    ready), the host's split ``serve_s``, ``upload_s``,
    ``enqueue_s``, ``wait_s``, ``gc_s`` (seconds of garbage
    collections inside the dispatch) and ``gc_full`` (how many of
    them were full ones); what compiling took of the dispatch —
    ``lower_s`` (the ``step.lower`` span), ``compile_s`` (seconds of
    JAX's traces, lowerings and backend compiles or cached loads that
    fell inside it and outside ``step.lower``), ``compiled`` (the
    programs whose compile ended inside it), ``build_s`` (the
    ``step.build`` span, where ``StepCompiler.compile()`` ran inside
    it) — and its clock: ``t0``, ``t1`` (the ``step`` span's opening
    and closing on ``time.perf_counter``, the clock of
    ``startup.compiles()``) and ``gap_s`` (``t0`` minus the closing
    of this thread's dispatch before it; None for the first)."""
    with _lock:
        return list(_recent)


def _judge(record):
    """Whether this dispatch is late: what it took against
    :data:`LATE_FACTOR` × the median of what its program's recent
    ones took.  A late one counts into ``device.late_dispatches`` and
    is logged with its whole split and the compile context (at most
    once in :data:`LATE_WARN_EVERY_S` seconds).  A dispatch that
    compiled is neither judged nor remembered.  The median is taken
    again when a dispatch passes it, and every :data:`LATE_RECORDS`
    dispatches."""
    if record["compiled"]:
        return
    took = record["t1"] - record["t0"]
    with _lock:
        pace = _paces.get(record["program"])
        if pace is None:
            pace = _paces[record["program"]] = [
                collections.deque(maxlen=LATE_RECORDS), None, 0]
        usual = pace[1]
        if usual is not None and took > LATE_FACTOR * usual:
            # against the newest median: the pace may have changed
            usual = statistics.median(pace[0])
        late = usual is not None and took > LATE_FACTOR * usual
        pace[0].append(took)
        pace[2] += 1
        if (late or pace[1] is None or pace[2] >= LATE_RECORDS) \
                and len(pace[0]) >= LATE_KNOWN:
            pace[1], pace[2] = statistics.median(pace[0]), 0
    if not late:
        return
    metrics.registry.counter("device.late_dispatches").inc()
    with _lock:
        warned = _state["late_warned"]
        if warned is not None and \
                record["t1"] - warned < LATE_WARN_EVERY_S:
            return
        _state["late_warned"] = record["t1"]
    programs, ended = startup.compiled()
    logging.getLogger("attribution").warning(
        "dispatch %d of %s late: %.4g s against %.4g; serve %.4g "
        "upload %.4g enqueue %.4g wait %.4g gc %.4g (%d full) gap "
        "%.4g; %d programs compiled%s", record["ordinal"],
        record["program"], took, usual, record["serve_s"],
        record["upload_s"], record["enqueue_s"], record["wait_s"],
        record["gc_s"], record["gc_full"], record["gap_s"] or 0.0,
        programs, "" if ended is None else
        ", the last ended %.4g s before" % (record["t0"] - ended))


def record_step(device_seconds, flops=None, ticks=1, record=None):
    """Folds one measured dispatch into the attribution state, the
    metrics registry and, given the dispatch's ``record``, the deque
    behind :func:`recent` — callable on its own so tests can drive
    the MFU plumbing with a fake device timer."""
    device_seconds = max(float(device_seconds), 1e-9)
    mfu = None
    peak = peak_flops() if flops else None
    if flops and peak:
        mfu = float(flops) / device_seconds / peak
    # A dispatch that compiled holds the compile in its seconds (30
    # to 140 s on the chip): it keeps its record and is not folded
    # into the EWMA, which would read wrong for twenty dispatches.
    steady = not (record and record.get("compiled"))
    with _lock:
        ms = device_seconds * 1e3
        _state["last_ms"] = ms
        if steady:
            prev = _state["device_ms"]
            _state["device_ms"] = ms if prev is None else \
                prev + EWMA_ALPHA * (ms - prev)
        if steady and mfu is not None:
            prev = _state["mfu"]
            _state["mfu"] = mfu if prev is None else \
                prev + EWMA_ALPHA * (mfu - prev)
        _state["dispatches"] += 1
        _state["ticks"] += int(ticks)
        _state["device_s_total"] += device_seconds
        if record is not None:
            _recent.append(record)
        snap = dict(_state)
    reg = metrics.registry
    reg.counter("device.dispatches").inc()
    reg.counter("device.ticks").inc(int(ticks))
    if snap["device_ms"] is not None:
        reg.gauge("device.step_ms").set(round(snap["device_ms"], 3))
    if snap["mfu"] is not None:
        reg.gauge("device.mfu").set(round(snap["mfu"], 4))
    return snap


def note_optimizer(kind, state_bytes, shard_frac=1.0):
    """Publishes the optimizer observability gauges (called by
    ``StepCompiler.compile`` once per compile): ``optimizer.
    state_bytes`` and ``optimizer.shard_frac`` in the process metrics
    registry, labeled with the optimizer kind, plus the heartbeat
    ``perf`` section fields (→ web_status perf row, /metrics)."""
    with _lock:
        _optimizer.update(kind=str(kind),
                          state_bytes=int(state_bytes),
                          shard_frac=float(shard_frac))
    reg = metrics.registry
    labels = {"kind": str(kind)}
    reg.gauge("optimizer.state_bytes",
              labels=labels).set(int(state_bytes))
    reg.gauge("optimizer.shard_frac",
              labels=labels).set(round(float(shard_frac), 6))


def note_moe_share(assignments_made, assignments_landed,
                   max_load_frac):
    """Publishes what DecisionGD read from the layers that hold a
    share of a dropless expert layer: ``moe.assignments_made`` and
    ``moe.assignments_landed`` (means a tick, summed over those
    layers; landed / made is the share of the routing that fell on
    the experts held here) and ``moe.max_load_frac`` (docs/moe.md)."""
    reg = metrics.registry
    reg.gauge("moe.assignments_made").set(float(assignments_made))
    reg.gauge("moe.assignments_landed").set(float(assignments_landed))
    reg.gauge("moe.max_load_frac").set(round(float(max_load_frac), 6))


def optimizer_summary():
    """The last published optimizer stats, or None before the first
    compiled step."""
    with _lock:
        if _optimizer["kind"] is None:
            return None
        return dict(_optimizer)


def lowered_flops(lowered):
    """Per-dispatch FLOP count from XLA's HLO cost analysis of a
    lowered step (``Lowered.cost_analysis()`` — no compile), or None
    when the backend/version can't say.  XLA's count of the program
    as written: a rematerialized block's second forward is in it."""
    try:
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception as e:
        logging.getLogger("attribution").debug(
            "HLO cost analysis unavailable: %s", e)
        return None


def perf_summary():
    """The heartbeat ``perf`` section: live device-time and MFU for
    this process's fused step, or None before the first measured
    dispatch."""
    with _lock:
        if not _state["dispatches"]:
            return None
        out = {
            "dispatches": _state["dispatches"],
            "ticks": _state["ticks"],
            "step_ms": round(_state["device_ms"], 3)
            if _state["device_ms"] is not None else None,
            "last_step_ms": round(_state["last_ms"], 3),
            "device_s_total": round(_state["device_s_total"], 3),
        }
        if _state["mfu"] is not None:
            out["mfu"] = round(_state["mfu"], 4)
        if _recent:
            # Where the host's time around the newest dispatch went:
            # what an operator looks at when a dispatch runs late.
            last = _recent[-1]
            for field in ("serve_s", "upload_s", "enqueue_s",
                          "wait_s", "gc_s"):
                out["last_" + field[:-2] + "_ms"] = round(
                    last[field] * 1e3, 3)
            out["last_gc_full"] = last["gc_full"]
        if _optimizer["kind"] is not None:
            out["optimizer"] = _optimizer["kind"]
            out["optimizer_state_bytes"] = _optimizer["state_bytes"]
            out["optimizer_shard_frac"] = _optimizer["shard_frac"]
    return out
