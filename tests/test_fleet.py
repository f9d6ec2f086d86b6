"""Elastic fleet: membership change as a normal event (ISSUE 16).

FleetScheduler semantics (numbered membership epochs, rank healing,
affinity placement, least-loaded respawn), preemption-as-drain over
real sockets (``worker.preempt`` retires with a clean goodbye, never
a drop), clean-bye parole, admission chaos at the membership seam
(``fleet.join``), and THE elastic acceptance gate: a fleet that walks
grow→shrink→grow mid-training under serialized dispatch finishes with
final trainables BIT-IDENTICAL to a fixed-fleet run — drains requeue
nothing, late joiners full-ship + rebase, the step is never lost.
The fast walk runs in-process; the full 8→5→8 socket soak is marked
slow.
"""

import threading
import time

import numpy
import pytest

import veles_tpu.prng as prng
import veles_tpu.resilience as resilience
from veles_tpu.client import Client
from veles_tpu.fleet import FleetScheduler, live_fleet_summary
from veles_tpu.launcher import Launcher
from veles_tpu.observability import metrics
from veles_tpu.resilience import FaultInjector
from veles_tpu.server import Server, SlaveDescription

from test_resilience import LedgerWorkflow, _start_client

DELTA_PROTO = {"tensor": True, "delta": True, "codec": "none",
               "dtype": "fp32", "ticks": 1}


@pytest.fixture(autouse=True)
def _clean_stats():
    resilience.reset()


# -- FleetScheduler: membership epochs ------------------------------------


def test_membership_epoch_numbers_every_event():
    fleet = FleetScheduler()
    assert fleet.join("w1", mid="m1") == 1
    assert fleet.join("w2", mid="m2") == 2
    assert fleet.size == 2
    assert fleet.leave("w1", clean=True) == 3   # drain
    assert fleet.leave("w2") == 4               # drop
    # An sid that never joined (admission died before registration)
    # must not mint an epoch — no membership residue.
    assert fleet.leave("ghost") == 4
    snap = fleet.snapshot()
    assert snap["epoch"] == 4 and snap["size"] == 0
    assert snap["joins"] == 2 and snap["leaves"] == 2
    assert snap["drains"] == 1
    assert snap["last_event"] == (4, "drop", "w2")
    assert resilience.stats.get("fleet.join") == 2
    assert resilience.stats.get("fleet.leave") == 2
    assert resilience.stats.get("fleet.drain") == 1
    assert metrics.registry.peek("membership.epoch").value == 4
    assert metrics.registry.peek("fleet.size").value == 0


def test_live_fleet_summary_feeds_heartbeat():
    fleet = FleetScheduler()
    fleet.join("w1")
    fleet.join("w2")
    summary = live_fleet_summary()
    assert summary is not None
    assert summary["epoch"] >= 2 and summary["joins"] >= 2
    # The launcher heartbeat ships it as the "fleet" section.
    master = LedgerWorkflow(Launcher())
    payload = master.launcher.status_payload("mid0")
    assert payload.get("fleet", {}).get("epoch") >= 2


# -- FleetScheduler: placement policy --------------------------------------


def test_lowest_free_rank_heals_holes_first():
    assert FleetScheduler.lowest_free_rank(4, ()) == 0
    assert FleetScheduler.lowest_free_rank(4, (0, 2, 3)) == 1
    assert FleetScheduler.lowest_free_rank(2, (0, 1)) is None


def test_pick_affine_prefers_locality_then_fresh_then_steals():
    mems = [{"id": "a", "aff": "w1", "age": 5.0},
            {"id": "b", "aff": "w1", "age": 3.0},
            {"id": "c", "aff": None, "age": 0.0},
            {"id": "d", "aff": "w2", "age": 1.0}]

    def aff(m):
        return m["aff"]

    def age(m):
        return m["age"]

    # Affine candidates win, least-recently-served first.
    assert FleetScheduler.pick_affine(mems, "w1", aff, age)["id"] == "b"
    # A stranger takes a fresh candidate before stealing.
    assert FleetScheduler.pick_affine(mems, "w3", aff, age)["id"] == "c"
    busy = [m for m in mems if m["aff"] is not None]
    # No affine, no fresh: steal the stalest.
    assert FleetScheduler.pick_affine(busy, "w3", aff, age)["id"] == "d"
    assert FleetScheduler.pick_affine([], "w1", aff, age) is None


def test_least_loaded_stable_ties():
    load = {"n1": 2, "n2": 1, "n3": 1}
    assert FleetScheduler.least_loaded(
        ("n1", "n2", "n3"), load.__getitem__) == "n2"
    assert FleetScheduler.least_loaded((), len) is None


# -- preemption is a drain, not a crash (real sockets) ---------------------


def _sessions_that_worked(server):
    """How many of the sessions the fleet admitted did a job — after
    checking that every join the ledger minted IS an admitted session.
    At an orderly finish a worker whose socket closes before its bye
    redials, and now and then gets in before the port shuts: one more
    join, handed nothing.  So a test counts the sessions that worked,
    not the joins."""
    sessions = server.all_slaves
    assert server.fleet.snapshot()["joins"] == len(sessions)
    return sum(1 for desc in sessions.values() if desc.jobs_done)


def test_preempt_retires_clean_goodbye_not_drop():
    """Deterministic ``worker.preempt`` chaos: the noticed worker
    finishes its in-flight job, ships the update, says bye, and the
    run completes on the survivor — ``server.goodbye``, never
    ``server.drop``, zero requeues, and the fleet ledger records the
    drain."""
    master = LedgerWorkflow(Launcher(), total_jobs=8)
    server = Server(":0", master)
    addr = "127.0.0.1:%d" % server.port
    injector = FaultInjector("worker.preempt@job:2")
    preempted, t1, _ = _start_client(addr, injector=injector)
    # The survivor dials only once the noticed worker is gone: two
    # workers racing for eight jobs can leave the noticed one fewer
    # than the two its notice waits for, and then nothing fires.
    t1.join(timeout=10)
    assert not t1.is_alive(), "preempted worker failed to exit"
    _await_retires(1)  # ...and its handler on the server has too
    _survivor, t2, _ = _start_client(addr)
    server.wait(timeout=30)
    assert not server.is_running
    t2.join(timeout=10)
    assert injector.fired == [("worker.preempt", "job", 2)]
    assert len(master.done) == 8
    assert all(v == 1 for v in master.done.values())
    assert not master.requeue_log  # zero lost ticks
    assert preempted._draining
    assert resilience.stats.get("client.preempt") == 1
    assert resilience.stats.get("client.drain") == 1
    assert resilience.stats.get("server.goodbye") >= 1
    assert resilience.stats.get("server.drop") == 0
    assert resilience.stats.get("server.requeue") == 0
    assert _sessions_that_worked(server) == 2
    assert server.fleet.snapshot()["drains"] >= 1


def test_fleet_join_fault_rides_dead_peer_path():
    """``fleet.join`` chaos kills an admission at the membership
    seam: the worker sees a dead peer and redials; exactly ONE
    membership epoch is ever minted for it — a failed admission
    leaves no residue."""
    master = LedgerWorkflow(Launcher(), total_jobs=4)
    injector = FaultInjector("fleet.join@1")
    server = Server(":0", master, injector=injector)
    addr = "127.0.0.1:%d" % server.port
    _client, thread, _ = _start_client(addr)
    server.wait(timeout=30)
    thread.join(timeout=10)
    assert len(master.done) == 4
    assert all(v == 1 for v in master.done.values())
    assert injector.fired == [("fleet.join", "fleet.join", 1)]
    # the admission that died minted no join and left no session
    assert _sessions_that_worked(server) == 1


def test_clean_bye_during_probation_grants_parole():
    """An orderly departure must not keep the machine's cooldown
    armed: a probation session that drains with NOTHING outstanding
    clears the blacklist entry (parole), while a dirty drop — or a
    'goodbye' with work still in flight — keeps it."""
    master = LedgerWorkflow(Launcher())
    server = Server(":0", master)
    try:
        # Clean bye, nothing outstanding: parole.
        desc = SlaveDescription("s1", "mach1", 1.0, ("127.0.0.1", 1))
        desc.probation = True
        with server._lock:
            server._slaves["s1"] = desc
            server._blacklist["mach1"] = time.time()
        server.fleet.join("s1", "mach1")
        # The counter is the process's: a session of the test before
        # this one may say its bye late, so count this drop's own.
        byes = resilience.stats.get("server.goodbye")
        server._drop(desc, clean=True)
        assert not desc.probation
        assert "mach1" not in server._blacklist
        assert resilience.stats.get("server.parole") == 1
        assert resilience.stats.get("server.goodbye") == byes + 1
        assert server.fleet.snapshot()["drains"] == 1

        # Dirty drop: cooldown stays armed.
        desc2 = SlaveDescription("s2", "mach2", 1.0, ("127.0.0.1", 2))
        desc2.probation = True
        with server._lock:
            server._slaves["s2"] = desc2
            server._blacklist["mach2"] = time.time()
        server.fleet.join("s2", "mach2")
        server._drop(desc2, clean=False)
        assert "mach2" in server._blacklist

        # 'Goodbye' with outstanding work is NOT clean: requeue, no
        # parole.
        desc3 = SlaveDescription("s3", "mach3", 1.0, ("127.0.0.1", 3))
        desc3.probation = True
        with server._lock:
            server._slaves["s3"] = desc3
            server._blacklist["mach3"] = time.time()
            server._outstanding["s3"] = 1
        server.fleet.join("s3", "mach3")
        server._drop(desc3, clean=True)
        assert "mach3" in server._blacklist
        assert resilience.stats.get("server.requeue") == 1
        assert resilience.stats.get("server.parole") == 1
    finally:
        server.stop()


def test_max_inflight_serializes_dispatch():
    """``max_inflight=1``: with three eager workers at most ONE job
    is ever outstanding — the dispatch discipline the bit-parity
    soak rides."""

    # Instrument the INSTANCE, not a subclass — the handshake vets
    # the workflow checksum by class, and the workers run the plain
    # LedgerWorkflow.
    master = LedgerWorkflow(Launcher(), total_jobs=12)
    seen = {"max": 0}
    orig = master.generate_data_for_slave

    def probed(slave=None):
        job = orig(slave)
        n = sum(len(v) for v in master.outstanding.values())
        seen["max"] = max(seen["max"], n)
        return job

    master.generate_data_for_slave = probed
    server = Server(":0", master, max_inflight=1)
    addr = "127.0.0.1:%d" % server.port
    threads = [_start_client(addr)[1] for _ in range(3)]
    server.wait(timeout=30)
    for t in threads:
        t.join(timeout=10)
    assert len(master.done) == 12
    assert all(v == 1 for v in master.done.values())
    assert seen["max"] == 1, \
        "max_inflight=1 let %d jobs fly concurrently" % seen["max"]


# -- the elastic walk: bit-parity vs a fixed fleet -------------------------


def _mnist(seed, **kwargs):
    from veles_tpu.znicz.samples.mnist import MnistWorkflow
    kwargs.setdefault("max_epochs", 2)
    kwargs.setdefault("learning_rate", 0.1)
    # Momentum-free: optimizer slots are WORKER-LOCAL by default
    # (delayed-SGD semantics, docs/distributed.md), so a worker's
    # output depends only on (synced weights, minibatch) — exactly
    # the property the placement-independence parity gate needs.
    kwargs.setdefault("gradient_moment", 0.0)
    prng.reset()
    prng.get(0).seed(seed)
    launcher = Launcher()
    wf = MnistWorkflow(launcher, **kwargs)
    launcher.initialize()
    return wf


def _final_trainables(master):
    out = {}
    for unit in master.units:
        trainables = getattr(unit, "trainables", None)
        if not trainables:
            continue
        for attr, vec in trainables.items():
            vec.map_read()
            out["%s/%s" % (unit.name, attr)] = numpy.array(vec.mem)
    return out


def _drive_serialized(master, schedule, proto, max_cycles=6000):
    """One job in flight GLOBALLY (the ``Server(max_inflight=1)``
    dispatch discipline, in-process): serve → run → fold, one worker
    at a time.  ``schedule(k)`` names the worker for the k-th job and
    may grow or shrink the fleet as a side effect.  Returns the first
    job each session was served (full-ship inspection)."""
    first_jobs = {}
    registered = set()
    k = 0
    for _ in range(max_cycles):
        if master.should_stop_serving():
            return first_jobs
        sid, wf = schedule(k)
        if sid not in registered:
            master.note_slave_protocol(sid, proto)
            wf.note_net_proto(proto)
            registered.add(sid)
        job = master.generate_data_for_slave(sid)
        if job is None:
            continue
        first_jobs.setdefault(sid, job)
        replies = []
        wf.do_job(job, None, replies.append)
        master.apply_data_from_slave(replies[0], sid)
        k += 1
    raise AssertionError("driver did not converge in %d cycles"
                         % max_cycles)


def _full_ship_pieces(job):
    """The weight-sync pieces of a job: True per piece that is a full
    ship ("F"), False per delta ("D")."""
    return [("F" in p) for p in job.values()
            if isinstance(p, dict) and ("F" in p or "D" in p)]


def test_elastic_walk_matches_fixed_fleet_bit_for_bit():
    """THE elastic acceptance gate, in-process: the fleet walks
    3→1→3 mid-training — two clean drains, then two late joiners
    that FULL-SHIP + rebase — under serialized dispatch, and the
    final trainables are bit-identical to a fixed single-worker run.
    Drains requeue nothing (tick order preserved); joiners rebase
    onto the current weights (growth changes placement, never the
    trajectory)."""
    proto = dict(DELTA_PROTO)

    # Fixed-fleet reference: one worker takes every job.  The master
    # is always built LAST so the process prng state at run start is
    # identical across runs regardless of fleet size.
    ref_worker = _mnist(4242)
    ref_master = _mnist(4242)
    _drive_serialized(ref_master, lambda k: ("w1", ref_worker), proto)
    assert ref_master.decision.epoch_number == 2
    ref = _final_trainables(ref_master)

    workers = {"w1": _mnist(4242), "w2": _mnist(4242),
               "w3": _mnist(4242)}
    late = {"w4": _mnist(4242), "w5": _mnist(4242)}
    master = _mnist(4242)
    fleet = FleetScheduler()
    for sid in sorted(workers):
        fleet.join(sid)

    def schedule(k):
        # The 2-epoch run serves ~38 jobs: shrink and grow land
        # mid-epoch on both sides of the walk.
        if k == 12:   # two workers drain: clean leave, no requeue
            for sid in ("w2", "w3"):
                workers.pop(sid)
                fleet.leave(sid, clean=True)
        if k == 20:   # two late joiners full-ship + rebase
            for sid in sorted(late):
                workers[sid] = late[sid]
                fleet.join(sid)
        live = sorted(workers)
        sid = live[k % len(live)]
        return sid, workers[sid]

    first_jobs = _drive_serialized(master, schedule, proto)
    assert master.decision.epoch_number == 2
    # 3 joins + 2 drains + 2 joins = epoch 7, all drains clean.
    snap = fleet.snapshot()
    assert snap["epoch"] == 7 and snap["drains"] == 2
    # The late joiner's first job was a FULL ship (rebase), not a
    # delta against a base it never had.
    pieces = _full_ship_pieces(first_jobs["w4"])
    assert pieces and all(pieces)

    elastic = _final_trainables(master)
    assert set(elastic) == set(ref) and ref
    for key in ref:
        assert ref[key].dtype == elastic[key].dtype
        assert numpy.array_equal(ref[key], elastic[key]), \
            "trainable %s diverged between elastic and fixed" % key


# -- the full 8→5→8 socket soak (slow) -------------------------------------


def _start_mnist_worker(addr, wf):
    client = Client(addr, wf, reconnect_attempts=300,
                    reconnect_delay=0.05)
    thread = threading.Thread(target=client.run, daemon=True)
    thread.start()
    return client, thread


def _await_retires(n, deadline=30.0):
    """Settle until ``n`` sessions have fully retired (goodbye+drop).
    ``_drop`` runs in each server handler thread's ``finally`` — it can
    lag the client thread's exit, so counters are racy until then."""
    limit = time.time() + deadline
    while time.time() < limit:
        done = (resilience.stats.get("server.goodbye") +
                resilience.stats.get("server.drop"))
        if done >= n:
            return
        time.sleep(0.01)


@pytest.mark.slow
def test_elastic_soak_8_5_8_socket_bit_parity():
    """The headline chaos soak over REAL sockets: an 8-worker MNIST
    fleet walks 8→5→8 mid-training — three workers preempt-drain,
    three late joiners dial in and full-ship — under serialized
    dispatch (``max_inflight=1``), and the final weights are
    bit-identical to a fixed-fleet single-worker run of the same
    seed.  Zero lost ticks: every leave is a goodbye, nothing
    requeues, and the membership epoch numbers the whole walk."""
    from veles_tpu.znicz.samples.mnist import MnistWorkflow

    def build(seed):
        prng.reset()
        prng.get(0).seed(seed)
        launcher = Launcher()
        # Momentum-free for the same reason as the fast walk: slots
        # are worker-local, so parity must not depend on placement.
        wf = MnistWorkflow(launcher, max_epochs=2, learning_rate=0.1,
                           gradient_moment=0.0)
        launcher.initialize()
        return wf

    # Fixed-fleet reference run over sockets.
    ref_worker = build(777)
    ref_master = build(777)
    ref_server = Server(":0", ref_master, max_inflight=1)
    client, thread = _start_mnist_worker(
        "127.0.0.1:%d" % ref_server.port, ref_worker)
    ref_server.wait(timeout=900)
    assert not ref_server.is_running
    client.stop()
    thread.join(timeout=30)
    ref = _final_trainables(ref_master)
    # Let the reference session's server-side retire land BEFORE the
    # stats reset — a straggler goodbye after reset would pollute the
    # elastic run's counters.
    _await_retires(1)

    # Elastic run: every worker workflow is built UP FRONT (workflow
    # construction resets the process prng; mid-run builds would
    # perturb the master's stream vs the reference), the master last.
    resilience.reset()
    worker_wfs = [build(777) for _ in range(11)]
    master = build(777)
    # Instrument the INSTANCE (a subclass would change the workflow
    # checksum the handshake vets) to watch walk progress.
    applied = {"n": 0}
    orig_apply = master.apply_data_from_slave

    def counting_apply(data, slave=None):
        out = orig_apply(data, slave)
        applied["n"] += 1
        return out

    master.apply_data_from_slave = counting_apply
    server = Server(":0", master, max_inflight=1)
    addr = "127.0.0.1:%d" % server.port

    def wait_applied(threshold, deadline=600.0):
        limit = time.time() + deadline
        while applied["n"] < threshold and time.time() < limit:
            time.sleep(0.01)
        assert applied["n"] >= threshold, \
            "stalled at %d applied updates" % applied["n"]

    # A 2-epoch MNIST run serves 38 jobs total, so the walk points sit
    # inside that budget: shrink at 12 applied updates, grow at 20.
    fleet8 = [_start_mnist_worker(addr, wf) for wf in worker_wfs[:8]]
    wait_applied(12)
    for c, _t in fleet8[:3]:        # 8 → 5: preemption drains
        c.drain()
    for _c, t in fleet8[:3]:
        t.join(timeout=120)
        assert not t.is_alive(), "drained worker failed to exit"
    wait_applied(20)
    joiners = [_start_mnist_worker(addr, wf)
               for wf in worker_wfs[8:]]    # 5 → 8: late join
    server.wait(timeout=900)
    assert not server.is_running
    for c, t in fleet8[3:] + joiners:
        c.stop()
        t.join(timeout=30)
    _await_retires(11)

    # Zero lost ticks: drains and the final retirement are all clean.
    assert resilience.stats.get("server.drop") == 0
    assert resilience.stats.get("server.requeue") == 0
    assert resilience.stats.get("server.goodbye") == 11
    assert resilience.stats.get("client.drain") == 3
    snap = server.fleet.snapshot()
    assert snap["joins"] == 11 and snap["leaves"] == 11
    assert snap["drains"] == 11 and snap["epoch"] == 22
    summary = live_fleet_summary()
    assert summary is not None and summary["epoch"] >= 22
    assert metrics.registry.peek("membership.epoch").value >= 22

    assert master.decision.epoch_number == 2
    elastic = _final_trainables(master)
    assert set(elastic) == set(ref) and ref
    for key in ref:
        assert numpy.array_equal(ref[key], elastic[key]), \
            "trainable %s diverged across the 8->5->8 walk" % key


def test_wire_mesh_rebuild_exactly_one_rebuild_per_epoch():
    """Satellite regression (serving fabric PR): wire_mesh_rebuild
    auto-subscribes rebuild_mesh to FleetScheduler epoch changes —
    every join/leave epoch bump triggers EXACTLY one rebuild call
    stamped with that epoch, duplicates and stale bumps are deduped,
    and a rebuild that raises never detaches the subscription."""
    from veles_tpu.fleet import wire_mesh_rebuild

    sched = FleetScheduler()
    calls = []

    def recorder(workflow, epoch=None):
        calls.append((workflow, epoch))

    sentinel = object()
    cb = wire_mesh_rebuild(sched, sentinel, rebuild=recorder)
    assert cb is not None

    sched.join("a")                    # epoch 1
    sched.join("b")                    # epoch 2
    sched.leave("a", clean=True)       # epoch 3 (drain)
    sched.leave("b", clean=False)      # epoch 4 (drop)
    assert calls == [(sentinel, 1), (sentinel, 2),
                     (sentinel, 3), (sentinel, 4)]

    # A stale/duplicate notification is deduped, not re-applied.
    cb(2, "join", "late")
    assert len(calls) == 4

    # A raising rebuild is logged, not fatal, and the subscription
    # survives for the next epoch.
    def flaky(workflow, epoch=None):
        calls.append((workflow, epoch))
        if epoch == 5:
            raise RuntimeError("mesh re-form failed")

    sched2 = FleetScheduler()
    wire_mesh_rebuild(sched2, sentinel, rebuild=flaky)
    # Pre-bump epochs so the first fire lands on 5.
    for sid in ("w0", "w1", "w2", "w3"):
        sched2.join(sid)
    del calls[:]
    sched2.join("w4")                  # epoch 5: raises inside
    sched2.leave("w4", clean=True)     # epoch 6: still subscribed
    assert [e for _, e in calls] == [5, 6]

    # Default rebuild target is the real rebuild_mesh.
    from veles_tpu.parallel.mesh import rebuild_mesh
    import inspect
    default = inspect.signature(wire_mesh_rebuild).parameters
    assert default["rebuild"].default is None  # resolved lazily
    assert callable(rebuild_mesh)
