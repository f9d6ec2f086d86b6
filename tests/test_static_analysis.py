"""veles-lint (ISSUE 11): seeded fixture violations per rule ID, the
repo-wide zero-findings gate, suppressions/baselines, and the runtime
enforcers (lock-order recorder + strict_step) over real loopbacks.
"""

import os
import textwrap
import threading
import time

import numpy
import pytest

from veles_tpu import analysis
from veles_tpu.analysis import core, runtime
from veles_tpu.distributable import SniffedLock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(tmp_path, source, name="snippet.py"):
    path = tmp_path / name
    path.write_text(textwrap.dedent(source))
    return core.run(paths=[str(path)], root=str(tmp_path))


def _rules(findings):
    return {f.rule for f in findings}


# -- seeded fixture violations (one per rule ID) ---------------------------

def test_vl101_host_sync_in_jit_reachable_code(tmp_path):
    findings = _lint(tmp_path, """
        import jax
        import numpy

        def helper(x):
            return numpy.asarray(x).sum() + x.mean().item()

        def build():
            def run(x):
                return helper(x) + float(x)
            return jax.jit(run)
        """)
    hits = [f for f in findings if f.rule == "VL101"]
    # .item(), numpy.asarray (via the call-graph walk into helper),
    # and float() must ALL be caught.
    assert len(hits) == 3, findings
    assert any("asarray" in f.message for f in hits)
    assert any("item" in f.message for f in hits)
    assert any("float" in f.message for f in hits)


def test_vl102_retrace_nondeterminism(tmp_path):
    findings = _lint(tmp_path, """
        import random
        import time
        import jax

        def make():
            def step(x):
                return x * time.time() + random.random()
            return jax.jit(step)
        """)
    hits = [f for f in findings if f.rule == "VL102"]
    assert len(hits) == 2, findings


def test_vl101_traced_method_convention(tmp_path):
    """tforward methods are entries WITHOUT any jax.jit in sight —
    the StepCompiler convention the walk encodes."""
    findings = _lint(tmp_path, """
        class MyUnit(object):
            def tforward(self, read, write, params, ctx, state=None):
                return params["w"].item()
        """)
    assert _rules(findings) == {"VL101"}


def test_vl101_shard_map_closures_are_entries(tmp_path):
    """shard_map-wrapped functions (the pipeline schedule closures,
    ISSUE 12) are traced entry points — hazards inside them and in
    their nested scan bodies are caught, from BOTH forms the
    installed JAX offers (``jax.shard_map`` and ``from jax import
    shard_map``)."""
    findings = _lint(tmp_path, """
        import numpy
        import jax

        def pipelined(params, x, mesh):
            def stage_fn(p, h):
                def body(carry, t):
                    return carry + numpy.asarray(t), None
                return body(p, h)[0].item()
            return jax.shard_map(stage_fn, mesh=mesh)(params, x)
        """)
    hits = [f for f in findings if f.rule == "VL101"]
    assert hits and _rules(findings) == {"VL101"}, findings
    assert any("asarray" in f.message for f in hits)
    assert any("item" in f.message for f in hits)
    assert all("stage_fn" in f.message for f in hits)
    findings = _lint(tmp_path, """
        import time
        from jax import shard_map

        def run(params, x, mesh):
            def stage_fn(p, h):
                return p * time.time()
            return shard_map(stage_fn, mesh=mesh)(params, x)
        """, name="jaxform.py")
    assert _rules(findings) == {"VL102"}, findings


def test_vl102_partial_and_dict_dispatch_entries(tmp_path):
    """The ring-step registration shape (ISSUE 13): the traced body
    reaches shard_map through ``functools.partial`` over a
    DICT-dispatched alias (ops/attention.sequence_parallel_attention
    hands ``partial(modes[mode], ...)`` to shard_map) — entry
    discovery must unwrap both, so hazards inside the ring body and
    its per-step helper are caught."""
    findings = _lint(tmp_path, """
        import functools
        import time
        from jax import shard_map

        def _step_helper(x):
            return x * time.time()

        def ring_body(q, k, axis_name=None):
            return _step_helper(q) + k

        def ulysses_body(q, k, axis_name=None):
            return q + k

        def dispatch(q, k, mesh, mode):
            modes = {"ring": ring_body, "ulysses": ulysses_body}
            inner = modes[mode]
            fn = shard_map(functools.partial(inner, axis_name="s"),
                           mesh=mesh)
            return fn(q, k)
        """)
    hits = [f for f in findings if f.rule == "VL102"]
    assert hits and _rules(findings) == {"VL102"}, findings
    assert any("time" in f.message for f in hits)
    # ...reached THROUGH the dispatch table into the nested helper
    # (the message names the entry the walk came from).
    assert all("ring_body" in f.message for f in hits), hits


def test_vl101_host_code_not_flagged(tmp_path):
    """The builder around a jitted closure is host code — its numpy
    calls are legitimate and must NOT be flagged."""
    findings = _lint(tmp_path, """
        import jax
        import numpy

        def dispatch(x):
            x = numpy.ascontiguousarray(x)
            def run(v):
                return v * 2
            return numpy.asarray(jax.jit(run)(x))
        """)
    assert not findings, findings


def test_vl201_guarded_field_written_outside_lock(tmp_path):
    findings = _lint(tmp_path, """
        import threading

        class Box(object):
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []  # guarded-by: _lock
                self.n = 0  # guarded-by: _lock

            def ok(self):
                with self._lock:
                    self.items.append(1)
                    self.n += 1

            def ok_helper_locked(self):
                self.items.append(2)

            def bad(self):
                self.items.append(3)
        """)
    hits = [f for f in findings if f.rule == "VL201"]
    assert len(hits) == 1, findings
    assert "bad()" in hits[0].message


def test_vl202_lock_order_cycle(tmp_path):
    findings = _lint(tmp_path, """
        import threading

        class AB(object):
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._b:
                    with self._a:
                        pass
        """)
    hits = [f for f in findings if f.rule == "VL202"]
    assert len(hits) == 1, findings
    assert "AB._a" in hits[0].message and "AB._b" in hits[0].message


def test_vl301_dynamic_registry_name(tmp_path):
    findings = _lint(tmp_path, """
        from somewhere import stats

        def pick():
            return "a.b"

        def good(stat):
            stats.incr("net.retry")
            stats.incr("chaos.%s" % stat)
            stats.incr(stat)  # param pass-through: callers checked

        def bad():
            n = pick()
            stats.incr(n)
        """)
    hits = [f for f in findings if f.rule == "VL301"]
    assert len(hits) == 1, findings


def test_vl302_silent_broad_except(tmp_path):
    findings = _lint(tmp_path, """
        import logging

        def risky():
            pass

        def silent():
            try:
                risky()
            except Exception:
                pass

        def logged():
            try:
                risky()
            except Exception:
                logging.getLogger("x").exception("boom")

        def used():
            try:
                risky()
            except Exception as e:
                result = {"error": e}
                return result
        """)
    hits = [f for f in findings if f.rule == "VL302"]
    assert len(hits) == 1, findings


def test_inline_suppression_and_baseline(tmp_path):
    source = """
        def risky():
            pass

        def one():
            try:
                risky()
            except Exception:  # lint-ok: VL302 demo fixture
                pass

        def two():
            try:
                risky()
            except Exception:
                pass
        """
    findings = _lint(tmp_path, source)
    assert len(findings) == 1  # the suppressed handler is gone
    # Baseline round-trip: recorded findings stop reporting, and the
    # format is the greppable path:line: RULE-ID message form.
    base = tmp_path / "baseline.txt"
    core.write_baseline(str(base), findings)
    line = base.read_text().strip().splitlines()[-1]
    assert ": VL302 " in line and line.split(":")[1].isdigit()
    keys = core.load_baseline(str(base))
    assert not core.apply_baseline(findings, keys)


def test_rule_catalog_and_cli(tmp_path, capsys):
    """Every rule ID has a catalog entry; the CLI lists them and
    exits nonzero on findings."""
    assert set(core.RULES) == {"VL101", "VL102", "VL201", "VL202",
                               "VL301", "VL302"}
    from veles_tpu.analysis.__main__ import main
    assert main(["--list-rules"]) == 0
    bad = tmp_path / "bad.py"
    bad.write_text("try:\n    pass\nexcept Exception:\n    pass\n")
    assert main([str(bad), "--quiet"]) == 1
    out = capsys.readouterr().out
    assert "VL302" in out


# -- the tier-1 gate -------------------------------------------------------

def test_repo_wide_zero_findings():
    """`python -m veles_tpu.analysis` over veles_tpu/, bench.py,
    __graft_entry__.py and chip_smoke.py reports ZERO unsuppressed
    findings — every
    future hazard, unguarded write, silent except, or unregistered
    name fails tier-1 by construction."""
    findings = analysis.run(root=REPO)
    assert not findings, "\n" + "\n".join(
        core.format_finding(f) for f in findings)


# -- runtime: lock-order recorder ------------------------------------------

def test_lock_order_recorder_detects_inversion():
    a = SniffedLock(name="A")
    b = SniffedLock(name="B")
    rec = runtime.enable_lock_order()
    try:
        with a:
            with b:
                pass

        def inverted():
            with b:
                with a:
                    pass

        t = threading.Thread(target=inverted)
        t.start()
        t.join()
        with pytest.raises(runtime.LockOrderViolation,
                           match="A#.* -> B#.*|B#.* -> A#.*"):
            rec.assert_acyclic()
    finally:
        runtime.disable_lock_order()


def test_lock_order_recorder_consistent_order_passes():
    a = SniffedLock(name="A")
    b = SniffedLock(name="B")
    with runtime.lock_order_recording() as rec:
        for _ in range(3):
            with a:
                with b:
                    pass
        assert rec.edge_count() == 1
    # lock_order_recording already asserted acyclic at exit.


def test_lock_order_instances_do_not_merge():
    """Two INSTANCES sharing a name, locked in opposite orders by
    disjoint threads, are distinct nodes — no false cycle from name
    collision alone; the real inversion across the same two
    instances IS caught (covered above)."""
    a1 = SniffedLock(name="Unit.data_lock")
    a2 = SniffedLock(name="Unit.data_lock")
    rec = runtime.enable_lock_order()
    try:
        with a1:
            with a2:
                pass
        rec.assert_acyclic()
    finally:
        runtime.disable_lock_order()


def test_lock_order_cycle_free_master_worker_loopback():
    """Acceptance: the recorder runs cycle-free over a real
    master+worker loopback (Server + Client over sockets, one MNIST
    epoch) and actually observed nested acquisitions."""
    from veles_tpu.client import Client
    from veles_tpu.server import Server
    from test_dataplane import _mnist_pair
    rec = runtime.enable_lock_order()
    try:
        master = _mnist_pair(31, max_epochs=1)
        server = Server(":0", master)
        slave = _mnist_pair(31, max_epochs=1)
        client = Client("127.0.0.1:%d" % server.port, slave)
        t = threading.Thread(target=client.run, daemon=True)
        t.start()
        server.wait(timeout=120)
        t.join(timeout=10)
        assert not server.is_running
        assert rec.edge_count() > 0
        rec.assert_acyclic()
    finally:
        runtime.disable_lock_order()


# -- runtime: strict_step --------------------------------------------------

def test_strict_step_compile_sentinel_fires():
    with pytest.raises(runtime.StrictStepViolation,
                       match="budget 0.*sentinel-test"):
        with runtime.strict_step():
            runtime.note_compile("sentinel-test")
    # Within budget: no violation.
    with runtime.strict_step(allowed_compiles=1):
        runtime.note_compile("sentinel-test-2")


def test_strict_step_transfer_guard_trips_on_implicit_upload():
    import jax
    f = jax.jit(lambda x: x * 2)
    host = numpy.ones(4, numpy.float32)
    dev = jax.device_put(host)
    f(dev)  # warm
    with runtime.strict_step():
        f(dev)  # device-resident args: clean
    with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
        with runtime.strict_step():
            f(host)  # implicit numpy upload at dispatch


def test_strict_step_steady_state_fused_step():
    """Acceptance: after warmup, the fused training step runs under
    strict_step with zero implicit transfers and zero compiles —
    hardening the host_sync_count pins into enforcement."""
    import jax
    import veles_tpu.prng as prng
    from test_optimizers import _mnist
    _, wf = _mnist(3, serve=True)
    c = wf.compiler
    c.execute(key=jax.random.PRNGKey(0), training=True)  # warm
    prng.get().jax_key()  # materialize the device key chain
    with runtime.strict_step():
        for _ in range(3):
            c.execute(training=True)
    # The sentinel really is armed on this path: a forced re-trace
    # inside the region raises.
    c.invalidate()
    with pytest.raises(runtime.StrictStepViolation):
        with runtime.strict_step():
            c.execute(training=True)


def test_strict_step_paged_decode_loop_and_serving_soak():
    """Acceptance: the paged serving decode loop is strict-clean
    after warmup (zero transfers, zero compile misses), and a short
    concurrent soak under the lock-order recorder is cycle-free."""
    from test_serving import _random_lm_artifact
    from veles_tpu.export import ExportedModel
    from veles_tpu.serving import ServingEngine
    model = ExportedModel(_random_lm_artifact(
        os.path.join(str(pytest.importorskip("tempfile").
                         mkdtemp()), "rand.veles.tgz")))
    engine = ServingEngine(model, max_batch=4, kv_blocks=64,
                           kv_block_size=4,
                           default_deadline=60.0).start()
    rec = runtime.enable_lock_order()
    try:
        rng = numpy.random.RandomState(0)
        prompt = rng.randint(0, 13, (1, 6)).astype(numpy.int32)
        warm = engine.submit_generate(prompt, 5)
        # Identical-bucket traffic after warmup: the whole
        # prefill+decode loop must neither compile nor transfer
        # implicitly.
        with runtime.strict_step():
            again = engine.submit_generate(prompt, 5)
        numpy.testing.assert_array_equal(warm, again)

        # Mini soak: concurrent mixed-length streams.
        errors = []

        def stream(idx):
            srng = numpy.random.RandomState(idx)
            try:
                for _ in range(2):
                    p = srng.randint(0, 13, (1, 2 + 2 * (idx % 3))) \
                        .astype(numpy.int32)
                    engine.submit_generate(p, 3, seed=idx)
            except Exception as e:  # surfaced below, not swallowed
                errors.append(e)

        threads = [threading.Thread(target=stream, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        # The engine's locks are DESIGNED not to nest (cond released
        # before pool calls) — the gate here is cycle-freedom, and
        # any nesting a future edit introduces gets order-checked.
        rec.assert_acyclic()
    finally:
        runtime.disable_lock_order()
        engine.stop()


# -- docs / tooling plumbing -----------------------------------------------

def test_lint_script_entry_matches_module_cli():
    """scripts/lint.py is a console-entry wrapper over the same main
    (generate_docs.py parity)."""
    from veles_tpu.analysis.__main__ import main as module_main
    from veles_tpu.scripts import lint
    assert lint.main is module_main


def test_analysis_doc_exists_and_is_linked():
    doc = os.path.join(REPO, "docs", "analysis.md")
    assert os.path.isfile(doc)
    with open(doc) as fin:
        text = fin.read()
    for rule in core.RULES:
        assert rule in text, "rule %s missing from docs" % rule
    assert "guarded-by" in text and "strict_step" in text
    with open(os.path.join(REPO, "docs", "index.md")) as fin:
        assert "analysis.md" in fin.read()
