"""Distributed-engine polish tests: async-slave pipelining, worker
respawn, periodic power re-measurement, multi-process
``mode="distributed"`` bring-up, and the precision tiers
(reference capabilities: client.py:293-341 --async-slave,
server.py:637-655 respawn, client.py:308-313 power, launcher
multi-host mode, config.py:244-247 precision levels)."""

import os
import socket
import subprocess
import sys
import threading
import time

import pytest

import veles_tpu.prng as prng
from veles_tpu.client import Client
from veles_tpu.config import root
from veles_tpu.launcher import Launcher
from veles_tpu.server import Server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mnist_pair(seed, **kwargs):
    from veles_tpu.znicz.samples.mnist import MnistWorkflow
    kwargs.setdefault("max_epochs", 5)
    kwargs.setdefault("learning_rate", 0.1)
    kwargs.setdefault("gradient_moment", 0.5)
    prng.reset()
    prng.get(0).seed(seed)
    launcher = Launcher()
    wf = MnistWorkflow(launcher, **kwargs)
    launcher.initialize()
    return launcher, wf


def test_async_slave_pipelining_converges():
    """Pipelined workers must preserve training correctness (job N+1
    requested before update N lands).  Pipelining doubles gradient
    staleness (2 workers × 2 in-flight ≈ 4 stale steps), so the test
    uses a staleness-safe lr (large steps genuinely diverge under
    async SGD — physics, not protocol) with momentum off."""
    kw = dict(gradient_moment=0.0, max_epochs=8, learning_rate=0.03)
    _, master = _mnist_pair(77, **kw)
    server = Server(":0", master)
    addr = "127.0.0.1:%d" % server.port
    threads = []
    clients = []
    for _ in range(2):
        _, slave = _mnist_pair(77, **kw)
        client = Client(addr, slave, async_mode=True)
        clients.append(client)
        t = threading.Thread(target=client.run, daemon=True)
        t.start()
        threads.append(t)
    server.wait(timeout=300)
    for t in threads:
        t.join(timeout=10)
    assert not server.is_running
    assert bool(master.decision.complete)
    assert master.decision.epoch_number == 8
    assert master.decision.min_validation_err < 0.25
    assert sum(c.jobs_done for c in clients) > 0


def test_respawn_hook_relaunches_dropped_worker():
    """A worker that dies mid-job is respawned via the hook and the
    run completes with correct accounting
    (reference: server.py:637-655)."""
    from tests.test_network import (InstrumentedWorkflow,
                                    _handshook_channel)

    master = InstrumentedWorkflow(Launcher())
    master.job_limit = 4
    respawned = []

    def respawn(desc):
        slave = InstrumentedWorkflow(Launcher())
        client = Client("127.0.0.1:%d" % server.port, slave)
        respawned.append((desc.mid, client))
        threading.Thread(target=client.run, daemon=True).start()

    server = Server(":0", master, respawn=respawn)
    # First worker: raw protocol, takes one job and dies.
    chan, _ = _handshook_channel(server, master)
    chan.send({"cmd": "job_request"})
    job = chan.recv()
    assert job["cmd"] == "job"
    chan.close()  # crash
    server.wait(timeout=60)
    assert not server.is_running
    assert len(respawned) == 1
    # The respawned worker finished every remaining job (the dead
    # worker's in-flight one is requeued by real loaders, which this
    # instrumented workflow does not model).
    assert master.applied_from_slave == master.job_limit - 1
    assert master.dropped  # the dead worker was dropped


def test_respawn_gives_up_after_max(monkeypatch):
    """Exponential-backoff respawn stops at max_respawns."""
    from tests.test_network import InstrumentedWorkflow

    master = InstrumentedWorkflow(Launcher())
    master.job_limit = 1000000
    calls = []
    server = Server(":0", master,
                    respawn=lambda desc: calls.append(desc.mid),
                    max_respawns=2)
    try:
        class FakeDesc:
            mid = "m"
            id = "m/1"
        for _ in range(5):
            server._maybe_respawn(FakeDesc())
        deadline = time.time() + 10
        while len(calls) < 2 and time.time() < deadline:
            time.sleep(0.05)
        time.sleep(1.0)
        assert len(calls) == 2
    finally:
        server.stop()


def test_periodic_power_remeasure(monkeypatch):
    """Workers re-measure and report power; the master's worker table
    updates (reference: client.py:308-313, server power handler)."""
    from tests.test_network import InstrumentedWorkflow

    import itertools
    powers = itertools.chain([2.0], itertools.count(8.0))
    monkeypatch.setattr("veles_tpu.client.measure_computing_power",
                        lambda *a, **k: next(powers))
    master = InstrumentedWorkflow(Launcher())
    master.job_limit = 4
    server = Server(":0", master)
    slave = InstrumentedWorkflow(Launcher())
    # reconnect_attempts bounds the run: the final power report can
    # race the server's post-completion close, and a client dialing a
    # stopped server would otherwise sit out the full crash-resume
    # backoff schedule (minutes) synchronously.
    client = Client("127.0.0.1:%d" % server.port, slave,
                    measure_power=True, power_interval=0.0,
                    reconnect_attempts=1)
    seen = []
    orig_apply = server._apply_update

    def spy(desc, data):
        seen.append(desc.power)
        return orig_apply(desc, data)

    server._apply_update = spy
    client.run()
    server.stop()
    assert client.power > 2.0  # re-measured after handshake's 2.0
    assert any(p > 2.0 for p in seen)


_DIST_SCRIPT = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, %(repo)r)
pid, port = int(sys.argv[1]), sys.argv[2]
from veles_tpu.launcher import Launcher
from veles_tpu.workflow import Workflow
from veles_tpu.units import TrivialUnit
import jax
launcher = Launcher(mode="distributed",
                    coordinator_address="127.0.0.1:" + port,
                    num_processes=2, process_id=pid)
wf = Workflow(launcher)
u = TrivialUnit(wf)
u.link_from(wf.start_point)
wf.end_point.link_from(u)
launcher.initialize()
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2, jax.device_count()
launcher.run()
print("DISTOK", pid, jax.process_count(), flush=True)
"""


def test_distributed_mode_two_process_loopback():
    """mode="distributed" forms a real 2-process jax.distributed
    group over CPU loopback (SURVEY §4 tier (c))."""
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = str(sock.getsockname()[1])
    sock.close()
    script = _DIST_SCRIPT % {"repo": REPO}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, str(i), port],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=REPO) for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("distributed bring-up timed out")
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, err[-2000:]
        assert "DISTOK" in out


def test_precision_level_1_compensated_accumulation():
    """Level 1: f32 streams + Kahan epoch sums — training still
    converges and the carry state is live."""
    root.common.engine.precision_level = 1
    try:
        from veles_tpu.znicz.samples.mnist import MnistWorkflow
        prng.reset()
        prng.get(0).seed(1234)
        launcher = Launcher()
        wf = MnistWorkflow(launcher, max_epochs=3, learning_rate=0.1)
        launcher.initialize()
        launcher.run()
        assert wf.gather_results()["min_validation_err"] < 0.15
        assert "epoch_acc_c" in wf.evaluator.tstate
    finally:
        root.common.engine.precision_level = 0


def test_precision_level_2_highest_matmul():
    """Level 2: HIGHEST-precision MXU passes compile and train."""
    root.common.engine.precision_level = 2
    try:
        from veles_tpu.znicz.samples.mnist import MnistWorkflow
        prng.reset()
        prng.get(0).seed(1234)
        launcher = Launcher()
        wf = MnistWorkflow(launcher, max_epochs=2, learning_rate=0.1)
        launcher.initialize()
        launcher.run()
        assert wf.gather_results()["min_validation_err"] < 0.2
    finally:
        root.common.engine.precision_level = 0


def test_coordinated_snapshot_defers_until_drained(tmp_path):
    """Coordinated distributed snapshotting (reference:
    snapshotter.py:181-195,227-234 — the master waits for all
    workers' acks): a snapshot requested while jobs are in flight is
    DEFERRED until the queue drains, and the resulting checkpoint
    resumes training correctly."""
    import pickle
    from veles_tpu.snapshotter import SnapshotterToFile
    from veles_tpu.znicz.samples.mnist import MnistWorkflow

    def build(seed=77):
        prng.reset()
        prng.get(0).seed(seed)
        launcher = Launcher()
        wf = MnistWorkflow(launcher, max_epochs=3, learning_rate=0.1,
                           gradient_moment=0.5)
        launcher.initialize()
        return launcher, wf

    _, master = build()
    snap = SnapshotterToFile(master, directory=str(tmp_path),
                             prefix="coord", time_interval=0.0,
                             compression="")
    snap.initialize()

    # Master serves a job -> one outstanding worker job.
    job = master.generate_data_for_slave("w1")
    assert master.total_inflight_jobs() == 1

    # Snapshot request mid-job: deferred, nothing written.
    snap.run()
    assert snap._deferred
    assert snap.destination is None

    # The worker answers; applying its update drains the queue and
    # fires the deferred export.
    _, worker = build()
    replies = []
    worker.do_job(job, None, replies.append)
    master.apply_data_from_slave(replies[0], "w1")
    assert master.total_inflight_jobs() == 0
    assert not snap._deferred
    assert snap.destination and os.path.isfile(snap.destination)

    # The checkpoint is consistent: it resumes and finishes training.
    with open(snap.destination, "rb") as fin:
        resumed = pickle.load(fin)
    l2 = Launcher()
    l2.add_ref(resumed)
    l2.initialize()
    l2._finished.clear()
    resumed.run()
    assert resumed.decision.epoch_number == 3
    assert resumed.gather_results()["min_validation_err"] < 0.2


def test_drop_slave_fires_deferred_snapshot(tmp_path):
    """A dropped worker requeues its jobs — that also counts as
    draining, so a deferred snapshot must not hang forever."""
    from veles_tpu.snapshotter import SnapshotterToFile
    from veles_tpu.znicz.samples.mnist import MnistWorkflow
    prng.reset()
    prng.get(0).seed(5)
    launcher = Launcher()
    master = MnistWorkflow(launcher, max_epochs=2)
    launcher.initialize()
    snap = SnapshotterToFile(master, directory=str(tmp_path),
                             prefix="dropcoord", time_interval=0.0,
                             compression="")
    snap.initialize()
    master.generate_data_for_slave("w9")
    snap.run()
    assert snap._deferred
    master.drop_slave("w9")
    assert not snap._deferred
    assert snap.destination and os.path.isfile(snap.destination)
