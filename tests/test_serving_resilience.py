"""Serving resilience (ISSUE 8): supervised decode recovery, hot
weight reload, and graceful drain.

The contracts under test, per docs/serving.md "Operations":

* a chaos-injected ``serve.device_fault`` mid-decode kills ZERO live
  requests: the pool is rebuilt and every stream resumes
  TOKEN-IDENTICALLY to an uninjected run (greedy AND sampled rows —
  the replay restores the exact PRNG fold position);
* the circuit breaker answers 503 + Retry-After while rebuilding and
  trips to permanent-fail past the rebuild budget;
* a same-geometry hot reload under concurrent load drops zero
  requests, bumps ``weight_version``, reuses the compiled programs
  (zero new compile-cache misses), and old/new outputs each match
  their own artifact; different geometry falls back to
  drain-and-swap;
* a corrupt artifact (``serve.reload_corrupt``) is rejected by the
  sha256 manifest gate and the old weights keep serving;
* ``stop(drain=True)`` finishes live rows, rejects new work with
  503 + Retry-After, and queued-but-unstarted requests at any stop
  get :class:`ServiceUnavailable` instead of a bare error;
* the worker goodbye frame and blacklist parole keep ``server.drop``
  a pure error signal (satellites).

Chaos runs are GATED: every request is queued before the device
thread starts, so the ``serve.device_fault`` check count (one per
coalesced prefill, one per decode step) is schedule-independent and
the fault lands at the exact same token boundary every run.
"""

import hashlib
import json
import os
import threading
import time

import numpy
import pytest

import veles_tpu.resilience as resilience
from veles_tpu.error import Bug
from veles_tpu.export import ExportedModel
from veles_tpu.launcher import Launcher
from veles_tpu.resilience import FaultInjector, InjectedDeviceFault
from veles_tpu.server import Server
from veles_tpu.serving import (ArtifactRejected, ArtifactWatcher,
                               ServiceUnavailable, ServingEngine,
                               read_verified, resolve_artifact)
from veles_tpu.serving.reload import ARTIFACT_SUFFIX

from test_resilience import LedgerWorkflow, _start_client
from test_serving import PagedFakeModel, _random_lm_artifact

# -- helpers ---------------------------------------------------------------

#: The fixed request mix every chaos/parity run uses: mixed prompt
#: lengths, budgets, and sampling temperatures (greedy + two seeded
#: sampled rows, so PRNG-stream identity is part of the contract).
REQUESTS = (
    ([1, 2, 3], 6, 0.0, 0),
    ([5, 4, 3, 2], 6, 0.8, 7),
    ([2, 2], 5, 0.9, 11),
)


def _gated_run(model, plan=None, requests=REQUESTS, **ekw):
    """Queues every request into a NOT-yet-started engine, then
    starts the device thread: adoption happens in one coalesced
    prefill and the chaos-point check sequence is deterministic.
    Returns (engine, results, errors) after all requests settle."""
    ekw.setdefault("max_batch", 4)
    ekw.setdefault("default_deadline", 120.0)
    ekw.setdefault("kv_blocks", 64)
    ekw.setdefault("kv_block_size", 4)
    injector = FaultInjector(plan) if plan else None
    engine = ServingEngine(model, injector=injector, **ekw)
    results = [None] * len(requests)
    errors = [None] * len(requests)

    def submit(i, prompt, max_new, temp, seed):
        try:
            results[i] = engine.submit_generate(
                [prompt], max_new, temperature=temp, seed=seed)
        except Exception as e:  # noqa: BLE001 — recorded for asserts
            errors[i] = e

    threads = [threading.Thread(target=submit, args=(i,) + req,
                                daemon=True)
               for i, req in enumerate(requests)]
    for t in threads:
        t.start()
    deadline = time.time() + 10
    while engine.queue_depth_now() < len(requests) and \
            time.time() < deadline:
        time.sleep(0.005)
    assert engine.queue_depth_now() == len(requests)
    engine.start()
    for t in threads:
        t.join(timeout=120)
    engine.stop()
    return engine, results, errors


@pytest.fixture(scope="module")
def lm_paths(tmp_path_factory):
    """Three artifacts: v1, v2 (same geometry, different weights),
    v3 (different geometry — bigger vocab)."""
    d = tmp_path_factory.mktemp("resilience_lm")
    return (_random_lm_artifact(d / "v1.veles.tgz", seed=42),
            _random_lm_artifact(d / "v2.veles.tgz", seed=43),
            _random_lm_artifact(d / "v3.veles.tgz", seed=44,
                                vocab=17))


@pytest.fixture(scope="module")
def lm_v1(lm_paths):
    return ExportedModel(lm_paths[0])


def _write_artifact_manifest(path):
    """The sha256 sidecar the snapshotter writes next to a deploy
    artifact (snapshotter.MANIFEST_SUFFIX format)."""
    digest = hashlib.sha256(open(path, "rb").read()).hexdigest()
    manifest = {"format": 1, "kind": "serving-artifact",
                "sha256": digest, "size": os.path.getsize(path),
                "created": time.time()}
    with open(str(path) + ".manifest.json", "w") as fout:
        json.dump(manifest, fout)
    return manifest


# -- supervised decode recovery (acceptance) -------------------------------

def test_device_fault_mid_decode_resumes_token_identically(lm_paths):
    """THE acceptance gate: a device fault at the 3rd decode step
    wrecks the pool; the supervisor rebuilds it and re-adopts every
    live stream from its request-side tokens — final outputs are
    bit-identical to an uninjected run, zero requests die."""
    model = ExportedModel(lm_paths[0])
    _, base_results, base_errors = _gated_run(model)
    assert all(e is None for e in base_errors)
    # check #1 = the coalesced prefill, #2.. = decode steps: @4 is
    # the 3rd decode step, mid-stream for every request.
    engine, results, errors = _gated_run(
        model, plan="serve.device_fault@4")
    assert all(e is None for e in errors), errors
    assert engine.injector.fired == [
        ("serve.device_fault", "serve.device_fault", 4)]
    assert engine.stats.get("kv.pool.resets") == 1
    assert engine.stats.get("breaker.rebuilds") == 1
    assert engine.stats.get("readopt.rows") == len(REQUESTS)
    for got, want in zip(results, base_results):
        assert numpy.array_equal(got, want)


def test_device_fault_during_prefill_requeues_and_recovers(lm_paths):
    """A fault on the FIRST check (the coalesced prefill itself):
    the adopting requests go back to the wait queue and ride the
    normal adoption path against the rebuilt pool — same outputs,
    zero failures."""
    model = ExportedModel(lm_paths[0])
    _, base_results, _ = _gated_run(model)
    engine, results, errors = _gated_run(
        model, plan="serve.device_fault@1")
    assert all(e is None for e in errors), errors
    assert engine.stats.get("kv.pool.resets") == 1
    for got, want in zip(results, base_results):
        assert numpy.array_equal(got, want)


def test_breaker_trips_after_rebuild_budget():
    """Two faults inside a breaker_limit=1 window: the first rebuild
    is supervised, the second trips the breaker — the live request
    fails with the device error and NEW submissions get 503."""
    model = PagedFakeModel()
    engine, results, errors = _gated_run(
        model, plan="serve.device_fault@2,serve.device_fault@3",
        requests=(([3, 1], 4, 0.0, 0),), breaker_limit=1)
    assert results[0] is None
    assert isinstance(errors[0], InjectedDeviceFault)
    assert engine.stats.get("breaker.trips") == 1
    assert engine._breaker == "tripped"
    with pytest.raises(ServiceUnavailable) as ei:
        engine._admission_gate_locked()
    assert ei.value.status == 503


@pytest.mark.parametrize("where, call", [
    ("paged_step", 2), ("paged_extend", 1)],
    ids=["decode step", "prefill"])
def test_program_error_fails_loudly_without_recovery(where, call):
    """A lowering/shape/type error out of a paged device call is the
    PROGRAM being wrong, not the device: the requests of that call
    fail with the error itself, ``errors.program`` counts it, and
    nothing is rebuilt, replayed or tripped — a replay could only
    raise the same error again."""
    model = PagedFakeModel()
    real = getattr(model, where)
    calls = []

    def broken(*args, **kwargs):
        calls.append(1)
        if len(calls) == call:
            raise ValueError("block shape refused by the lowering")
        return real(*args, **kwargs)

    setattr(model, where, broken)
    engine, results, errors = _gated_run(
        model, requests=(([3, 1], 4, 0.0, 0), ([2], 3, 0.0, 0)))
    assert results == [None, None]
    assert all(isinstance(e, ValueError) for e in errors), errors
    assert engine.stats.get("errors.program") == 1
    for name in ("kv.pool.resets", "breaker.rebuilds",
                 "breaker.trips", "readopt.rows"):
        assert not engine.stats.get(name), name
    assert engine._breaker == "closed"
    assert engine.kv_pool.occupancy()["blocks_used"] == 0


def test_breaker_rebuilding_answers_503_with_retry_after():
    engine = ServingEngine(PagedFakeModel(), kv_blocks=32)
    engine._breaker = "rebuilding"
    with pytest.raises(ServiceUnavailable) as ei:
        engine.submit_generate([[1, 2]], 4)
    assert ei.value.status == 503
    assert ei.value.retry_after is not None


# -- hot weight reload (acceptance) ----------------------------------------

def test_inplace_reload_under_load_zero_drops_and_parity(lm_paths):
    """Same-geometry reload under concurrent load: zero dropped
    requests, weight_version bumps everywhere, outputs before/after
    match their own artifact, and the compile cache takes ZERO new
    misses (the executables survive the swap)."""
    p1, p2, _ = lm_paths
    model = ExportedModel(p1)
    old_model, new_model = ExportedModel(p1), ExportedModel(p2)
    engine = ServingEngine(model, max_batch=4, kv_blocks=64,
                           kv_block_size=4,
                           default_deadline=120.0).start()
    try:
        prompt = [3, 1, 4, 1]
        want_old = old_model.generate([prompt], 6)
        want_new = new_model.generate([prompt], 6)
        assert not numpy.array_equal(want_old, want_new)
        # Wave A: the old weights serve.
        got = engine.submit_generate([prompt], 6)
        assert numpy.array_equal(got, want_old)
        # Second sequential request: takes the prefix-HIT path
        # (fully-cached prompt → COW copy + 1-token re-feed),
        # compiling pcopy and the short-chunk extend NOW.  Without
        # this, whether those keys exist before wave B depends on
        # how the concurrent wave interleaves with the reload's
        # prefix flush — the zero-new-misses assert below was flaky.
        got = engine.submit_generate([prompt], 6)
        assert numpy.array_equal(got, want_old)
        assert engine.weight_version == 1
        # Concurrent load straddling the swap: every request must
        # COMPLETE (token content may be either generation).
        inflight_err = []

        def pound():
            try:
                engine.submit_generate([prompt], 6)
            except Exception as e:  # noqa: BLE001
                inflight_err.append(e)

        threads = [threading.Thread(target=pound, daemon=True)
                   for _ in range(4)]
        for t in threads:
            t.start()
        version = engine.reload(p2, timeout=60.0)
        for t in threads:
            t.join(timeout=60)
        assert not inflight_err
        assert version == 2 and engine.weight_version == 2
        snap = engine.stats.snapshot()
        assert snap["gauges"]["weight_version"] == 2
        assert snap["counters"]["reload.inplace"] == 1
        # Wave B: the new weights serve — through the SAME programs
        # (this request's geometry compiled in wave A, so the swap
        # surviving the compile cache means ZERO new misses here).
        misses_before = model.compile_cache.stats()["misses"]
        got = engine.submit_generate([prompt], 6)
        assert numpy.array_equal(got, want_new)
        assert model.compile_cache.stats()["misses"] == misses_before
    finally:
        engine.stop()


def test_different_geometry_falls_back_to_drain_and_swap(lm_paths):
    p1, _, p3 = lm_paths
    engine = ServingEngine(ExportedModel(p1), max_batch=4,
                           kv_blocks=64, kv_block_size=4).start()
    try:
        prompt = [3, 1, 4]
        engine.submit_generate([prompt], 4)
        version = engine.reload(p3, timeout=120.0)
        assert version == 2
        assert engine.stats.get("reload.swap") == 1
        # The engine now serves the NEW model (vocab 17 geometry).
        want = ExportedModel(p3).generate([prompt], 4)
        assert numpy.array_equal(engine.submit_generate([prompt], 4),
                                 want)
    finally:
        engine.stop()


def test_swap_weights_rejects_geometry_mismatch(lm_paths):
    p1, _, p3 = lm_paths
    model = ExportedModel(p1)
    with pytest.raises(Bug):
        model.swap_weights(ExportedModel(p3).weights)
    assert model.weight_version == 1


def test_corrupt_artifact_rejected_old_weights_keep_serving(
        lm_paths, tmp_path):
    """serve.reload_corrupt flips one byte of the candidate blob:
    the manifest gate rejects it and the engine keeps serving the
    old weights at the old version."""
    p1, p2, _ = lm_paths
    _write_artifact_manifest(p2)
    engine = ServingEngine(ExportedModel(p1), max_batch=4,
                           kv_blocks=64, kv_block_size=4).start()
    try:
        prompt = [2, 7, 1]
        want_old = ExportedModel(p1).generate([prompt], 4)
        inj = FaultInjector("serve.reload_corrupt@1")
        with pytest.raises(ArtifactRejected):
            read_verified(p2, injector=inj)
        assert resilience.stats.get("serve.reload_rejected") == 1
        # Nothing reached the engine: same version, same outputs.
        assert engine.weight_version == 1
        assert numpy.array_equal(
            engine.submit_generate([prompt], 4), want_old)
        # The SAME artifact verifies clean without the fault — and
        # a clean verified blob hot-swaps fine.
        assert engine.reload(read_verified(p2, injector=inj)) == 2
    finally:
        engine.stop()


def test_read_verified_requires_manifest_for_watchers(lm_paths):
    p1 = lm_paths[0]  # v1 has no sidecar manifest
    with pytest.raises(ArtifactRejected):
        read_verified(p1, require_manifest=True)
    assert read_verified(p1, require_manifest=False) is not None


def test_watcher_follows_current_lnk(tmp_path, lm_paths):
    """The train→serve loop: the watcher resolves the snapshotter's
    _current.lnk to the snapshot blob and deploys its .veles.tgz
    sibling; a moved pointer dispatches exactly once."""
    p1, p2, _ = lm_paths
    blob1, blob2 = tmp_path / "m_1.pickle", tmp_path / "m_2.pickle"
    link = tmp_path / "m_current.lnk"
    for blob, src in ((blob1, p1), (blob2, p2)):
        blob.write_bytes(b"snapshot")
        art = str(blob) + ARTIFACT_SUFFIX
        with open(src, "rb") as fin:
            open(art, "wb").write(fin.read())
        _write_artifact_manifest(art)
    link.write_text(str(blob1))
    assert resolve_artifact(str(link)) == str(blob1) + ARTIFACT_SUFFIX
    seen = []
    fail_next = [True]

    def on_change(path):
        if fail_next[0]:
            fail_next[0] = False
            raise ServiceUnavailable("engine busy")  # transient
        seen.append(path)

    watcher = ArtifactWatcher(str(link), on_change, poll=999)
    assert not watcher.check_once()  # startup target is "current"
    link.write_text(str(blob2))
    # First dispatch fails TRANSIENTLY → the generation is retried
    # on the next poll, not skipped forever.
    assert not watcher.check_once()
    assert watcher.check_once()
    assert not watcher.check_once()  # dispatched exactly once
    assert seen == [str(blob2) + ARTIFACT_SUFFIX]
    # The deploy gate accepts the manifested sibling.
    assert read_verified(seen[0], require_manifest=True) is not None


def test_snapshotter_exports_verified_artifact(tmp_path, monkeypatch):
    """--snapshot-artifact: each snapshot writes a manifested
    .veles.tgz sibling BEFORE the pointer moves; generations prune
    it; the resume walk never mistakes it for a snapshot."""
    from veles_tpu.snapshotter import (SnapshotterToFile,
                                       iter_generations)
    import veles_tpu.export as export_mod

    def fake_export(workflow, path):
        with open(path, "wb") as fout:
            fout.write(b"artifact-bytes-%d" % len(str(path)))
        return path

    monkeypatch.setattr(export_mod, "export_workflow", fake_export)
    wf = LedgerWorkflow(Launcher())
    snap = SnapshotterToFile(wf, directory=str(tmp_path),
                             prefix="dep", time_interval=0.0,
                             compression="", keep=1, artifact=True)
    snap.initialize()
    for suffix in ("a", "b"):
        snap.suffix = suffix
        snap.export()
    blob = snap.destination
    art = blob + ARTIFACT_SUFFIX
    assert os.path.isfile(art)
    # Verifiable: the sidecar manifest matches the artifact bytes.
    assert read_verified(art, require_manifest=True) is not None
    # The pointer's sibling is resolvable — the watch contract.
    link = os.path.join(str(tmp_path), "dep_current.lnk")
    assert resolve_artifact(link) == art
    # Resume-walk hygiene: generations never include artifacts.
    gens = iter_generations(str(tmp_path), "dep")
    assert gens == [blob]
    # keep=1 pruned generation "a" AND its artifact + manifest.
    stems = os.listdir(str(tmp_path))
    assert not any("dep_a" in name for name in stems), stems
    assert resilience.stats.get("snapshot.artifact") == 2


def test_admin_reload_requires_token_and_reloads(lm_paths):
    from test_serving import _get, _post
    from veles_tpu.restful import ModelServer
    p1, p2, _ = lm_paths
    _write_artifact_manifest(p2)
    server = ModelServer(p1, port=0, token="sekret", max_batch=4,
                         kv_blocks=64, kv_block_size=4)
    server.start()
    try:
        port = server.port
        status, body, _ = _post(port, "/admin/reload", {})
        assert status == 403
        status, body, _ = _post(port, "/admin/reload",
                                {"artifact": str(p2)},
                                headers={"X-Status-Token": "wrong"})
        assert status == 403
        status, body = _get(port, "/stats")
        assert body["weight_version"] == 1
        status, body, _ = _post(port, "/admin/reload",
                                {"artifact": str(p2)},
                                headers={"X-Status-Token": "sekret"})
        assert status == 200 and body["weight_version"] == 2
        status, body = _get(port, "/stats")
        assert body["weight_version"] == 2
        assert body["gauges"]["weight_version"] == 2
        # /metrics carries the gauge too.
        import urllib.request
        text = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % port,
            timeout=10).read().decode()
        assert "veles_serving_weight_version 2.0" in text
    finally:
        server.stop()


def test_tokenless_server_refuses_admin_reload(lm_paths):
    from test_serving import _post
    from veles_tpu.restful import ModelServer
    server = ModelServer(lm_paths[0], port=0, max_batch=4,
                         kv_blocks=64, kv_block_size=4)
    server.start()
    try:
        status, body, _ = _post(server.port, "/admin/reload",
                                {"artifact": lm_paths[1]})
        assert status == 403
    finally:
        server.stop()


def test_serving_summary_carries_weight_version_and_breaker():
    """The launcher-heartbeat serving summary (the web_status
    serving row's payload) shows the served weight generation, and
    leads with a degraded breaker state when there is one."""
    from veles_tpu.serving.metrics import live_serving_summary
    engine = ServingEngine(PagedFakeModel(), kv_blocks=32).start()
    try:
        summary = live_serving_summary()
        assert summary["weight_version"] == 1
        assert "breaker" not in summary
        engine.weight_version = 7
        engine._breaker = "rebuilding"
        summary = live_serving_summary()
        assert summary["weight_version"] == 7
        assert summary["breaker"] == "rebuilding"
    finally:
        engine.stop()


# -- graceful drain --------------------------------------------------------

def test_drain_finishes_live_rows_and_rejects_new_work():
    model = PagedFakeModel(step_delay=0.01)
    engine = ServingEngine(model, max_batch=4, kv_blocks=64,
                           kv_block_size=4,
                           default_deadline=60.0).start()
    results, errors = [], []

    def run_one():
        try:
            results.append(engine.submit_generate([[3, 1]], 20))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=run_one, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.time() + 10
    while len(engine._rows) < 2 and time.time() < deadline:
        time.sleep(0.005)
    assert len(engine._rows) == 2
    stopper = threading.Thread(
        target=lambda: engine.stop(drain=True, timeout=30.0),
        daemon=True)
    stopper.start()
    # New work during the drain: 503 + Retry-After, never queued.
    rejected = None
    drain_deadline = time.time() + 10
    while rejected is None and time.time() < drain_deadline:
        try:
            engine.submit_generate([[5]], 4)
            time.sleep(0.002)
        except ServiceUnavailable as e:
            rejected = e
    assert rejected is not None and rejected.status == 503
    assert rejected.retry_after is not None
    stopper.join(timeout=60)
    for t in threads:
        t.join(timeout=60)
    # The LIVE rows finished with real results — zero casualties.
    assert not errors, errors
    assert len(results) == 2
    assert engine.stats.get("drained.requests") == 2


def test_queued_at_stop_get_503_with_retry_after():
    """Satellite: requests a stop() catches still queued become
    ServiceUnavailable (503 + Retry-After), not a bare error — the
    client retries the restarted replica."""
    engine = ServingEngine(PagedFakeModel(), max_batch=4,
                           kv_blocks=64)  # never started
    captured = []

    def submit():
        try:
            engine.submit_generate([[1, 2]], 4)
        except Exception as e:  # noqa: BLE001
            captured.append(e)

    t = threading.Thread(target=submit, daemon=True)
    t.start()
    deadline = time.time() + 5
    while engine.queue_depth_now() < 1 and time.time() < deadline:
        time.sleep(0.005)
    engine.stop()
    t.join(timeout=10)
    assert len(captured) == 1
    err = captured[0]
    assert isinstance(err, ServiceUnavailable)
    assert err.status == 503 and err.retry_after is not None


# -- satellites: worker goodbye + blacklist parole -------------------------

def test_clean_worker_exit_sends_goodbye_not_drop():
    master = LedgerWorkflow(Launcher(), total_jobs=50)
    server = Server(":0", master)
    client, thread, _slave = _start_client(
        "127.0.0.1:%d" % server.port)
    deadline = time.time() + 10
    while not master.done and time.time() < deadline:
        time.sleep(0.01)
    assert master.done  # at least one job applied
    client.stop()
    thread.join(timeout=10)
    deadline = time.time() + 5
    while resilience.stats.get("server.goodbye") < 1 and \
            time.time() < deadline:
        time.sleep(0.01)
    server.stop()
    assert resilience.stats.get("server.goodbye") == 1
    assert resilience.stats.get("server.drop") == 0
    assert resilience.stats.get("server.requeue") == 0


def test_completed_run_retires_workers_cleanly():
    """The master's own bye (training finished) is also a clean
    retirement — completions no longer read as drops."""
    master = LedgerWorkflow(Launcher(), total_jobs=3)
    server = Server(":0", master)
    _client, thread, _slave = _start_client(
        "127.0.0.1:%d" % server.port)
    server.wait(timeout=20)
    thread.join(timeout=10)
    assert master.done == {1: 1, 2: 1, 3: 1}
    # The goodbye lands when the SERVER's connection handler unwinds
    # past its finally — strictly after the client thread exits, so
    # a raced read here was the pre-ISSUE-13 flake.  Poll like the
    # sibling goodbye test; drop is asserted AFTER the handler has
    # provably retired the worker, when a mis-classified retirement
    # would actually be visible.
    deadline = time.time() + 5
    while resilience.stats.get("server.goodbye") < 1 and \
            time.time() < deadline:
        time.sleep(0.01)
    assert resilience.stats.get("server.goodbye") >= 1
    assert resilience.stats.get("server.drop") == 0


def test_blacklist_parole_readmits_on_probation():
    """A blacklisted machine rejoins after the cooldown ON PROBATION
    and earns parole by completing one clean job — the run finishes
    and server.parole records the re-admission."""
    master = LedgerWorkflow(Launcher(), total_jobs=3)
    server = Server(":0", master, job_timeout=0.3,
                    watchdog_interval=0.05, blacklist_cooldown=0.2)
    addr = "127.0.0.1:%d" % server.port
    hang = FaultInjector("worker.hang@job:1")
    client_a, thread_a, _ = _start_client(addr, injector=hang,
                                          attempts=0)
    deadline = time.time() + 10
    while resilience.stats.get("server.blacklist") < 1 and \
            time.time() < deadline:
        time.sleep(0.02)
    assert resilience.stats.get("server.blacklist") == 1
    # The replacement worker shares the machine id → probation.
    _client_b, thread_b, _ = _start_client(addr)
    server.wait(timeout=20)
    assert not server.is_running
    client_a.stop()
    thread_a.join(timeout=5)
    thread_b.join(timeout=5)
    assert master.done == {1: 1, 2: 1, 3: 1}
    assert resilience.stats.get("server.parole") == 1
    assert not server._blacklist  # parole erased the entry
