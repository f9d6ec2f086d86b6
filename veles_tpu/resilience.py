"""Unified resilience layer: retry/backoff policies, deadlines,
deterministic fault injection, and resilience-event accounting.

The reference treated worker death as a first-class event
(``--slave-death-probability`` chaos flag client.py:302-307, hang
detection with mean+3σ timeouts server.py:619-635, blacklist +
requeue server.py:315-338), but scattered the mechanics ad-hoc across
the server, client, and snapshotter with no way to *prove* they
compose.  This module centralizes them:

* :class:`RetryPolicy` — exponential backoff with seeded jitter (the
  jitter stream rides :mod:`veles_tpu.prng`, so a resumed run replays
  the same backoff schedule);
* :class:`Deadline` — a wall-clock budget shared across retries;
* :class:`FaultInjector` — a seeded, *schedulable* chaos engine with
  named injection points.  A chaos plan like
  ``net.drop@job:7,worker.kill@job:12,seed:42`` reproduces the exact
  same failure sequence every run: faults trigger on logical event
  counters (jobs served, frames sent), never on wall time;
* :data:`stats` — a thread-safe counter registry.  Every retry, drop,
  blacklist, crash, and resume increments a named counter which the
  launcher heartbeats ship to ``web_status`` — operators see
  degradation, not just survive it.

Injection points (where the control plane consults the injector):

========================  ================================================
point                     consulted by
========================  ================================================
``net.send``              :class:`network_common.Channel` before a frame
``net.recv``              :class:`network_common.Channel` before a read
``net.connect``           :class:`client.Client` before dialing
``worker.job``            :class:`client.Client` before executing a job
``fleet.join``            :class:`server.Server` while admitting a joiner
``snapshot.write``        :class:`snapshotter.SnapshotterToFile` per write
``master.crash``          :class:`server.Server` after serving/applying
========================  ================================================

Chaos-plan grammar (comma-separated entries)::

    seed:<int>              seed for probabilistic rules
    <fault>@<counter>:<n>   one-shot: fire when counter == n
    <fault>@<n>             one-shot at the n-th check of the fault's
                            own injection point
    <fault>%<p>             fire with probability p per check (seeded)

Faults: ``net.drop`` (send dies), ``net.recv_drop`` (read dies),
``net.connect_fail`` (dial refused), ``worker.kill`` (worker process
death), ``worker.hang`` (worker stalls — exercises the watchdog),
``worker.preempt`` (planned preemption — the worker drains and says
bye), ``fleet.join`` (a joiner's admission dies mid-handshake),
``snapshot.fail`` (checkpoint write error), ``master.crash``
(coordinator process death).

A plan is interpreted **per process**: every participant installs the
same plan, each rule fires off that process's own logical counters
(a worker ticks ``job`` per job received, the master per job served),
so the failure sequence is reproducible regardless of thread or
network timing.
"""

import threading
import time


# -- errors ----------------------------------------------------------------

class ResilienceError(Exception):
    """Base for resilience-layer errors."""


class HandshakeRejected(ResilienceError):
    """The coordinator is ALIVE and explicitly refused this worker
    (checksum/version mismatch, protocol violation).  Permanent —
    retrying the full reconnect schedule against a live server that
    keeps saying no wastes minutes and buries the real diagnostic."""


class ProtocolError(ResilienceError):
    """The peer spoke a desynchronized wire dialect (delta against a
    missing/mismatched base version, unknown trainable).  Session-
    fatal but recoverable: the worker reconnects with a fresh id and
    the master rebases it with a full weights ship."""


class InjectedFault(ResilienceError):
    """Base for injector-raised faults; carries the rule that fired."""

    def __init__(self, fault, counter=None, count=None):
        super(InjectedFault, self).__init__(
            "injected fault %s (%s=%s)" % (fault, counter, count))
        self.fault = fault
        self.counter = counter
        self.count = count


class InjectedNetworkFault(InjectedFault, ConnectionError):
    """A dropped frame/connection.  Subclasses ConnectionError so the
    existing dead-peer handling paths catch it unchanged — injected
    faults exercise the REAL recovery code, not a parallel one."""


class WorkerKilled(InjectedFault):
    """Simulated worker process death (subsumes the reference's
    ``--slave-death-probability``, client.py:438-442)."""


class WorkerHang(InjectedFault):
    """Simulated worker stall — the job never completes, driving the
    coordinator's adaptive-timeout watchdog (server.py:619-635)."""

    def __init__(self, fault, counter=None, count=None,
                 seconds=3600.0):
        super(WorkerHang, self).__init__(fault, counter, count)
        self.seconds = seconds


class WorkerPreempted(InjectedFault):
    """Simulated spot/maintenance preemption notice.  Unlike
    :class:`WorkerKilled` this is a PLANNED departure: the client
    catches it, finishes the in-flight job, ships the update, sends
    the ``bye`` frame, and leaves cleanly — the master records a
    retirement (``server.goodbye``), not a drop.  Past the
    ``--preempt-grace`` budget the drain degrades to an abrupt drop
    (today's requeue path), which is what a real preemptor does when
    the grace window closes."""


class MasterCrash(InjectedFault):
    """Simulated coordinator process death: every socket dies
    abruptly, no cleanup — recovery must come from the atomic
    snapshot (crash-resume)."""


class SnapshotWriteFault(InjectedFault, OSError):
    """A failed checkpoint write (disk full, NFS hiccup)."""


class InjectedStepNaN(InjectedFault):
    """A poisoned training tick: the consulting step executor (see
    ``AcceleratedWorkflow.execute_step``) catches this and feeds NaN
    into the minibatch, so the NaN flows through the REAL fused step
    — loss, gradients, and the on-device health sentinel all see it
    exactly the way a bad record would produce it."""


class InjectedSnapshotCorruption(InjectedFault):
    """Bit-rot on a just-written snapshot: the snapshotter catches
    this and flips one byte of the blob AFTER the manifest was
    computed, so checksum verification must reject it on resume."""


class InjectedDeviceFault(InjectedFault):
    """A device failure during a serving decode call (XLA abort,
    preemption, a lost chip): the serving engine's supervisor
    catches it on the device thread, rebuilds the KV pool, and
    re-adopts surviving streams from their request-side token
    prefixes — the exact recovery path a real device fault drives."""


class InjectedReloadCorruption(InjectedFault):
    """Bit-rot on a serving artifact about to be hot-deployed: the
    reload verifier catches this and flips one byte of the blob it
    just read, so the sha256 manifest gate must reject the artifact
    and the old weights must keep serving."""


# -- stats -----------------------------------------------------------------

class ResilienceStats(object):
    """Thread-safe named event counters — the PR-1 API every call
    site and test uses (``incr``/``get``/``snapshot``/``reset``),
    now a thin shim over a typed
    :class:`~veles_tpu.observability.metrics.MetricsRegistry`: each
    name is a Counter series, so everything incremented here is also
    scrapeable as Prometheus text at ``GET /metrics`` without
    touching a single increment site.  Surfaced through launcher
    heartbeats and ``Workflow.print_stats``."""

    def __init__(self, registry=None):
        if registry is None:
            from .observability.metrics import MetricsRegistry
            registry = MetricsRegistry()
        self.registry = registry

    def incr(self, name, n=1):
        self.registry.counter(name).inc(n)

    def get(self, name):
        counter = self.registry.peek(name)
        return counter.value if counter is not None else 0

    def snapshot(self):
        """name → value over the counters (the historical flat-dict
        shape; gauges/histograms sharing the registry stay out)."""
        return self.registry.counters_snapshot()

    def reset(self):
        # Counters only: gauges/histograms sharing the registry
        # (device attribution, serving latency windows) belong to
        # their own subsystems — a counter reset must not wipe them.
        self.registry.reset(kind="counter")


def _global_registry():
    from .observability.metrics import registry
    return registry


#: The process-wide resilience event registry, shimmed onto the
#: process metrics registry (observability.metrics.registry).
stats = ResilienceStats(registry=_global_registry())

#: prng registry key for the resilience jitter stream — distinct from
#: the model/loader generators (0, 1, …) so retry jitter never
#: perturbs training randomness.
PRNG_KEY = 201


# -- deadline --------------------------------------------------------------

class Deadline(object):
    """A wall-clock budget.  ``Deadline(None)`` never expires."""

    def __init__(self, seconds=None):
        self.seconds = seconds
        self._start = time.monotonic()

    @property
    def expired(self):
        return self.seconds is not None and self.remaining() <= 0.0

    def remaining(self):
        if self.seconds is None:
            return float("inf")
        return self.seconds - (time.monotonic() - self._start)

    def clamp(self, delay):
        """Bounds a sleep to the remaining budget (never negative)."""
        return max(0.0, min(delay, self.remaining()))


def _process_phase():
    """A stable pseudo-random value in [0, 1) per PROCESS (machine id
    + pid) — constant within a process (replayable backoff), distinct
    across fleet members (desynchronized reconnect storms)."""
    if _phase[0] is None:
        import os
        import uuid
        _phase[0] = ((uuid.getnode() * 1000003 + os.getpid())
                     % 997) / 997.0
    return _phase[0]


_phase = [None]


# -- retry policy ----------------------------------------------------------

class RetryPolicy(object):
    """Exponential backoff with seeded jitter.

    ``delay(attempt)`` = min(base·factor^attempt, max) scaled by a
    uniform draw in [1-jitter, 1+jitter] from the :mod:`prng`
    resilience stream — deterministic given seed and draw order, so a
    replayed chaos run reproduces its backoff schedule too — and by a
    stable per-process phase (machine id + pid): the prng stream is
    seeded identically in every worker process, so without the phase
    a coordinator crash would have the whole fleet redial in
    lock-step (the thundering herd jitter exists to prevent).
    """

    def __init__(self, max_attempts=5, base_delay=0.2, factor=2.0,
                 max_delay=30.0, jitter=0.25, deadline=None,
                 rng=None):
        self.max_attempts = int(max_attempts)
        self.base_delay = float(base_delay)
        self.factor = float(factor)
        self.max_delay = float(max_delay)
        self.jitter = float(jitter)
        self.deadline = deadline
        #: Private jitter source (``random.Random``-like).  None uses
        #: the shared seeded resilience stream.  Policies whose draw
        #: RATE is wall-clock-dependent (the client's no-job idle
        #: poll) MUST bring their own rng — their draws would shift
        #: the shared stream's order and break chaos-replay
        #: determinism for every other consumer.
        self.rng = rng

    def delay(self, attempt):
        # factor**attempt overflows float range for a large enough
        # attempt (an hour-long no-job streak reaches ~1750) — once
        # past max_delay the exact power is irrelevant anyway.
        try:
            grown = self.base_delay * self.factor ** attempt
        except OverflowError:
            grown = self.max_delay
        d = min(grown, self.max_delay)
        if self.jitter:
            if self.rng is not None:
                rng = self.rng
            else:
                from . import prng
                rng = prng.get(PRNG_KEY)
            d *= 1.0 + rng.uniform(-self.jitter, self.jitter)
            d *= 1.0 + self.jitter * (_process_phase() - 0.5)
        if self.deadline is not None:
            d = self.deadline.clamp(d)
        return max(0.0, d)

    def delays(self):
        """Yields the backoff before each retry (``max_attempts``
        values)."""
        for attempt in range(self.max_attempts):
            yield self.delay(attempt)

    def call(self, fn, retry_on=(OSError,), on_retry=None,
             sleep=time.sleep, stat=None):
        """Calls ``fn()`` with retries.  ``on_retry(attempt, exc)``
        observes each failure; ``stat`` names a counter incremented
        per retry.  The last exception propagates when attempts (or
        the deadline) are exhausted."""
        attempt = 0
        while True:
            try:
                return fn()
            except retry_on as e:
                expired = (self.deadline is not None and
                           self.deadline.expired)
                if attempt >= self.max_attempts or expired:
                    raise
                if stat:
                    stats.incr(stat)
                if on_retry is not None:
                    on_retry(attempt, e)
                sleep(self.delay(attempt))
                attempt += 1


# -- fault injection -------------------------------------------------------

#: fault name -> (injection point, exception class)
FAULTS = {
    "net.drop": ("net.send", InjectedNetworkFault),
    "net.recv_drop": ("net.recv", InjectedNetworkFault),
    "net.connect_fail": ("net.connect", InjectedNetworkFault),
    "worker.kill": ("worker.job", WorkerKilled),
    "worker.hang": ("worker.job", WorkerHang),
    "worker.preempt": ("worker.job", WorkerPreempted),
    "fleet.join": ("fleet.join", InjectedNetworkFault),
    "snapshot.fail": ("snapshot.write", SnapshotWriteFault),
    "snapshot.corrupt": ("snapshot.corrupt", InjectedSnapshotCorruption),
    "step.nan": ("step.nan", InjectedStepNaN),
    "master.crash": ("master.crash", MasterCrash),
    "serve.device_fault": ("serve.device_fault", InjectedDeviceFault),
    "serve.reload_corrupt": ("serve.reload_corrupt",
                             InjectedReloadCorruption),
}

#: The valid injection-point names (for validation/docs).
POINTS = tuple(sorted({p for p, _ in FAULTS.values()}))


class _Rule(object):
    """One parsed chaos-plan entry."""

    __slots__ = ("fault", "point", "exc", "counter", "at",
                 "probability", "fired")

    def __init__(self, fault, counter=None, at=None,
                 probability=None):
        if fault not in FAULTS:
            raise ValueError(
                "unknown fault %r (known: %s)" %
                (fault, ", ".join(sorted(FAULTS))))
        self.fault = fault
        self.point, self.exc = FAULTS[fault]
        self.counter = counter or self.point
        self.at = at
        self.probability = probability
        self.fired = False

    def __repr__(self):
        if self.probability is not None:
            return "%s%%%g" % (self.fault, self.probability)
        return "%s@%s:%d" % (self.fault, self.counter, self.at)


class FaultInjector(object):
    """A seeded, schedulable fault injector.

    Code under test calls :meth:`tick` to advance logical counters
    (``job`` per job, …) and :meth:`check` at injection points; a
    rule whose condition holds raises its fault exception.  Each
    ``check(point)`` also auto-ticks a counter named after the point,
    so ``net.drop@net.send:30`` needs no explicit ticking.

    Every fired rule is appended to :attr:`fired` as
    ``(fault, counter, count)`` — two runs with the same plan, seed,
    and logical event sequence produce identical logs, which is the
    determinism contract chaos tests assert.
    """

    def __init__(self, plan="", seed=0):
        self.plan = plan or ""
        self.seed = seed
        self._rules = []
        self._by_point = {}
        self.counters = {}
        self.fired = []
        self._lock = threading.Lock()
        for entry in (e.strip() for e in self.plan.split(",")):
            if not entry:
                continue
            if entry.startswith("seed:"):
                self.seed = int(entry[5:])
                continue
            self._rules.append(self._parse_rule(entry))
        for rule in self._rules:
            self._by_point.setdefault(rule.point, []).append(rule)
        import numpy
        self._rng = numpy.random.RandomState(self.seed & 0xFFFFFFFF)

    @staticmethod
    def _parse_rule(entry):
        if "%" in entry:
            fault, _, p = entry.partition("%")
            return _Rule(fault, probability=float(p))
        if "@" in entry:
            fault, _, cond = entry.partition("@")
            if ":" in cond:
                counter, _, n = cond.rpartition(":")
                return _Rule(fault, counter=counter, at=int(n))
            return _Rule(fault, at=int(cond))
        raise ValueError(
            "bad chaos entry %r — expected fault@counter:N, fault@N, "
            "fault%%p, or seed:N" % entry)

    @property
    def active(self):
        return bool(self._rules)

    def add_rule(self, entry):
        """Appends one parsed entry (used to fold legacy flags like
        ``--slave-death-probability`` into the injector)."""
        rule = self._parse_rule(entry)
        self._rules.append(rule)
        self._by_point.setdefault(rule.point, []).append(rule)
        return rule

    def tick(self, counter, n=1):
        """Advances a named logical counter (``job``, ``update``, …)."""
        if not self._rules:
            return
        with self._lock:
            self.counters[counter] = self.counters.get(counter, 0) + n

    def check(self, point, **ctx):
        """Consults the injector at a named point; raises the first
        triggering rule's fault.  No-op (and allocation-free) without
        rules."""
        if not self._rules:
            return
        with self._lock:
            count = self.counters.get(point, 0) + 1
            self.counters[point] = count
            rules = self._by_point.get(point)
            if not rules:
                return
            for rule in rules:
                if self._triggers(rule):
                    self.fired.append(
                        (rule.fault, rule.counter,
                         self.counters.get(rule.counter, 0)))
                    stats.incr("chaos." + rule.fault)
                    raise rule.exc(
                        rule.fault, rule.counter,
                        self.counters.get(rule.counter, 0))

    def _triggers(self, rule):
        if rule.probability is not None:
            return float(self._rng.random_sample()) < rule.probability
        if rule.fired:
            return False
        if self.counters.get(rule.counter, 0) >= rule.at:
            rule.fired = True
            return True
        return False

    def __repr__(self):
        return "FaultInjector(%r, seed=%d)" % (self.plan, self.seed)


#: Null injector — always installed by default; ``check`` is a cheap
#: early return.
_default = FaultInjector()
_install_lock = threading.Lock()


def get_injector():
    """The process-wide injector (a no-op unless a plan was
    installed via ``--chaos`` / :func:`install`)."""
    return _default


def effective(injector):
    """The injector a component should consult: its explicit one, or
    the process-wide default (one fallback rule, defined once)."""
    return injector if injector is not None else _default


def install(plan_or_injector, seed=0):
    """Installs the process-wide injector (from a plan string or an
    instance) and returns it."""
    global _default
    with _install_lock:
        if isinstance(plan_or_injector, FaultInjector):
            _default = plan_or_injector
        else:
            _default = FaultInjector(plan_or_injector or "",
                                     seed=seed)
        return _default


def reset():
    """Restores the null injector and clears stats (test isolation)."""
    global _default
    with _install_lock:
        _default = FaultInjector()
    stats.reset()


# -- crash-resume helpers --------------------------------------------------

def iter_snapshots(directory, prefix=None):
    """Yields snapshot paths named by ``*_current.lnk`` pointers in
    ``directory``, newest pointer first, then — per pointer family —
    the family's OLDER generations (newest first).  ``prefix``
    narrows the search to one snapshot family.  A dangling pointer
    (operators delete files; a corrupt write leaves a rejected blob)
    falls through to the family's surviving generations rather than
    crashing the resume — the caller verifies each candidate and
    walks on."""
    import glob
    import os
    if not directory or not os.path.isdir(directory):
        return
    pattern = ("%s_current.lnk" % prefix) if prefix \
        else "*_current.lnk"
    links = glob.glob(os.path.join(directory, pattern))

    def _mtime(path):
        try:
            return os.path.getmtime(path)
        except OSError:
            return 0.0  # pruned between glob and sort: sorts last

    links.sort(key=_mtime, reverse=True)
    from .snapshotter import SnapshotterToFile, iter_generations
    for link in links:
        seen = set()
        try:
            target = SnapshotterToFile.resolve(link)
        except FileNotFoundError:
            target = None  # dangling/empty: the walk takes over
        if target is not None and os.path.isfile(target):
            seen.add(os.path.abspath(target))
            yield target
        # Generation walk: older snapshots of the same family (kept
        # by the retention policy) back a resume up past a corrupt,
        # deleted, or unloadable newest snapshot.
        family = os.path.basename(link)[:-len("_current.lnk")]
        for path in iter_generations(os.path.dirname(link), family):
            if os.path.abspath(path) in seen:
                continue
            seen.add(os.path.abspath(path))
            yield path


def latest_snapshot(directory, prefix=None):
    """The newest resumable snapshot path, or None."""
    for path in iter_snapshots(directory, prefix):
        return path
    return None
