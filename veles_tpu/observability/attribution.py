"""Device-time and MFU attribution for the fused step.

``StepCompiler`` dispatches are asynchronous — ``time.perf_counter``
around the call measures Python dispatch, not the chip.  This module
closes the gap: after each dispatch the compiler hands a small output
leaf to :func:`end_step`, which ``block_until_ready``s it (waiting,
not transferring — all outputs of one XLA computation complete
together) and records the true wall→ready delta.  Combined with a
``cost_analysis()``-derived FLOP estimate per compiled step (one
extra trace per geometry, no extra compile — ``Lowered
.cost_analysis()`` runs XLA's HLO cost model), that yields a **live
MFU gauge** published into the process metrics registry, the
launcher heartbeat's ``perf`` section, and the web_status dashboard.

Also owns the ``--xprof DIR`` capture window: a ``jax.profiler``
trace opened at the first fused dispatch and closed after N of them
— the "give me a profile of exactly the steady-state step" operator
workflow, without bracketing the whole run like ``--profile`` does.

Knobs (``root.common.observability``):

* ``attribution`` (default True) — the per-dispatch sync costs one
  host round-trip per *block* of ticks; flip off for maximally
  async dispatch chains;
* ``peak_tflops`` — the MFU denominator; defaults from the device
  kind table below (v5e bf16 = 197), None on unknown hardware
  (device time still publishes; the MFU gauge just stays silent).

Everything here is wall-clock accounting around an unchanged
computation: bits on device are identical with attribution on, off,
or absent.
"""

import logging
import threading
import time

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

_lock = threading.Lock()
_state = {
    "last_ms": None,       # the newest dispatch, unsmoothed
    "device_ms": None,     # EWMA ms per dispatch
    "mfu": None,           # EWMA model-flop utilization
    "flops": None,         # last per-dispatch FLOP estimate
    "dispatches": 0,
    "ticks": 0,
    "device_s_total": 0.0,
}
_xprof = {"dir": None, "steps": 0, "done": 0, "started": False}
#: Optimizer observability (StepCompiler.compile publishes once per
#: compile): the configured kind(s), total slot bytes, and the ZeRO
#: shard fraction each dp rank persistently stores (1.0 = replicated).
_optimizer = {"kind": None, "state_bytes": None, "shard_frac": None}
#: MoE router observability (DecisionGD publishes per class-epoch
#: from the blocks' moe_acc accumulators): mean load-balance aux per
#: tick and the worst expert-load share (1/E = balanced, 1.0 =
#: collapsed).
_moe = {"aux_loss": None, "max_load_frac": None, "n_experts": None}
_timer = time.perf_counter  # injectable for tests
#: configured-peak-value -> resolved FLOP/s (the device probe and
#: config walk are constant per process; never pay them per
#: dispatch).
_peak_cache = {}


def _config(name, default):
    from ..config import root, get as config_get
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
    with _lock:
        _state.update(last_ms=None, device_ms=None, mfu=None,
                      flops=None,
                      dispatches=0, ticks=0, device_s_total=0.0)
        _optimizer.update(kind=None, state_bytes=None,
                          shard_frac=None)
        _moe.update(aux_loss=None, max_load_frac=None,
                    n_experts=None)
    _xprof.update(dir=None, steps=0, done=0, started=False)
    _peak_cache.clear()
    from . import metrics
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
        jax.profiler.start_trace(_xprof["dir"])
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
    try:
        import jax
        jax.profiler.stop_trace()
    except Exception as e:
        logging.getLogger("attribution").debug(
            "xprof stop_trace failed: %s", e)
    _xprof["started"] = False
    _xprof["dir"] = None


def _device_sync(leaf):
    """Waits for ``leaf`` — and with it every output of the dispatch
    that produced it — to be computed.  ``block_until_ready`` waits
    without transferring (chip_smoke.py prints the evidence that it
    does wait on this machine).  A failed computation raises here;
    that is the step failing, so it propagates."""
    if leaf is not None:
        leaf.block_until_ready()


# -- per-dispatch hooks (called by StepCompiler) ---------------------------

class _StepTimer(object):
    __slots__ = ("t0", "ticks", "flops")

    def __init__(self, ticks, flops):
        self.t0 = _timer()
        self.ticks = ticks
        self.flops = flops


def begin_step(ticks=1, flops=None):
    """Called right before a fused dispatch.  Returns a timer token
    for :func:`end_step`, or None when nothing here is active."""
    _xprof_step_begin()
    if not enabled():
        return None
    return _StepTimer(ticks, flops)


def end_step(timer, leaf=None):
    """Called right after the dispatch returns.  Syncs on ``leaf``
    (when given) so the delta covers device execution, then folds the
    measurement into the live gauges."""
    _xprof_step_end(leaf)
    if timer is None:
        return None
    _device_sync(leaf)
    return record_step(_timer() - timer.t0, flops=timer.flops,
                       ticks=timer.ticks)


def record_step(device_seconds, flops=None, ticks=1):
    """Folds one measured dispatch into the attribution state and the
    metrics registry — separated from :func:`end_step` so tests can
    drive the MFU plumbing with a fake device timer."""
    from . import metrics
    device_seconds = max(float(device_seconds), 1e-9)
    mfu = None
    peak = peak_flops() if flops else None
    if flops and peak:
        mfu = float(flops) / device_seconds / peak
    with _lock:
        ms = device_seconds * 1e3
        _state["last_ms"] = ms
        prev = _state["device_ms"]
        _state["device_ms"] = ms if prev is None else \
            prev + EWMA_ALPHA * (ms - prev)
        if mfu is not None:
            prev = _state["mfu"]
            _state["mfu"] = mfu if prev is None else \
                prev + EWMA_ALPHA * (mfu - prev)
        if flops:
            _state["flops"] = float(flops)
        _state["dispatches"] += 1
        _state["ticks"] += int(ticks)
        _state["device_s_total"] += device_seconds
        snap = dict(_state)
    reg = metrics.registry
    reg.counter("device.dispatches").inc()
    reg.counter("device.ticks").inc(int(ticks))
    reg.gauge("device.step_ms").set(round(snap["device_ms"], 3))
    if snap["mfu"] is not None:
        reg.gauge("device.mfu").set(round(snap["mfu"], 4))
    if snap["flops"] is not None:
        reg.gauge("device.flops_per_dispatch").set(snap["flops"])
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
    from . import metrics
    reg = metrics.registry
    labels = {"kind": str(kind)}
    reg.gauge("optimizer.state_bytes",
              labels=labels).set(int(state_bytes))
    reg.gauge("optimizer.shard_frac",
              labels=labels).set(round(float(shard_frac), 6))


def note_moe(aux_loss, max_load_frac, n_experts, expert_shares=None):
    """Publishes the MoE router gauges (called by DecisionGD at
    epoch boundaries from the blocks' ``moe_acc`` rows):
    ``moe.aux_loss`` (mean load-balance aux per tick) and
    ``moe.expert_load`` (per-expert share, labeled by block and
    expert index) in the process metrics registry, plus the heartbeat
    ``perf`` section fields (→ web_status perf row, /metrics) — the
    live router-collapse signal."""
    with _lock:
        _moe.update(aux_loss=float(aux_loss),
                    max_load_frac=float(max_load_frac),
                    n_experts=int(n_experts))
    from . import metrics
    reg = metrics.registry
    reg.gauge("moe.aux_loss").set(round(float(aux_loss), 6))
    reg.gauge("moe.max_load_frac").set(
        round(float(max_load_frac), 6))
    for (block, idx), share in (expert_shares or {}).items():
        reg.gauge("moe.expert_load",
                  labels={"block": str(block),
                          "expert": str(idx)}).set(
            round(float(share), 6))


def moe_summary():
    """The last published MoE router stats, or None when no MoE
    epoch has completed."""
    with _lock:
        if _moe["aux_loss"] is None:
            return None
        return dict(_moe)


def optimizer_summary():
    """The last published optimizer stats, or None before the first
    compiled step."""
    with _lock:
        if _optimizer["kind"] is None:
            return None
        return dict(_optimizer)


def estimate_flops(jitted, *args):
    """Per-dispatch FLOP count from XLA's HLO cost analysis of the
    jitted step (``Lowered.cost_analysis()`` — a re-trace, NOT a
    recompile), or None when the backend/version can't say."""
    try:
        cost = jitted.lower(*args).cost_analysis()
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
        if _state["flops"] is not None:
            out["flops_per_dispatch"] = _state["flops"]
        if _optimizer["kind"] is not None:
            out["optimizer"] = _optimizer["kind"]
            out["optimizer_state_bytes"] = _optimizer["state_bytes"]
            out["optimizer_shard_frac"] = _optimizer["shard_frac"]
        if _moe["aux_loss"] is not None:
            out["moe_aux_loss"] = round(_moe["aux_loss"], 6)
            out["moe_max_load_frac"] = round(_moe["max_load_frac"],
                                             6)
    return out
