"""Optimizer family + ZeRO state sharding gates (ISSUE 9): registry
semantics, fused-step parity vs numpy oracles for every update rule,
ZeRO-1/2 sharded step == unsharded step, slot-shard wire sync
(bit-identical master mirror, ÷dp wire bytes + bookkeeping),
snapshot/rollback slot matrix, GA tunability of Adam betas, optimizer
observability, and the steady-state device-residency invariant."""

import pickle

import numpy
import pytest

import veles_tpu.prng as prng
import veles_tpu.resilience as resilience
from veles_tpu.config import root, Tune
from veles_tpu.error import Bug
from veles_tpu.launcher import Launcher
from veles_tpu.znicz import optimizers
from veles_tpu.znicz.nn_units import GradientDescentBase
from veles_tpu.znicz.samples.mnist import MnistWorkflow

ALL_OPTS = ("sgd", "adam", "adamw", "lion")

#: Loopback wire dialect (test_dataplane's DELTA_PROTO) + slot sync.
DELTA = {"tensor": True, "delta": True, "codec": "none",
         "codec_level": 1, "codec_threshold": 1 << 16,
         "dtype": "fp32", "ticks": 1}


@pytest.fixture(autouse=True)
def _clean_engine_config():
    yield
    root.common.engine.optimizer = "sgd"
    root.common.net.zero = 0


def _mnist(seed, optimizer="sgd", serve=False, **kwargs):
    """Tiny MNIST workflow under the named optimizer; returns
    (launcher, wf).  The config default is restored after initialize
    (units constructed non-explicitly keep the kind they were built
    with — production leaves the config set for the process)."""
    kwargs.setdefault("max_epochs", 3)
    kwargs.setdefault("learning_rate", 0.1)
    kwargs.setdefault("gradient_moment", 0.5)
    kwargs.setdefault("layers", (24, 10))
    prng.reset()
    prng.get(0).seed(seed)
    launcher = Launcher()
    root.common.engine.optimizer = optimizer
    try:
        wf = MnistWorkflow(launcher, **kwargs)
        launcher.initialize()
    finally:
        root.common.engine.optimizer = "sgd"
    if serve:
        wf.compiler.compile()
        wf.loader.serve_next_minibatch()
    return launcher, wf


# -- registry ---------------------------------------------------------------

def test_registry_and_slot_naming():
    assert set(optimizers.OPTIMIZERS) >= set(ALL_OPTS)
    with pytest.raises(ValueError, match="unknown optimizer"):
        optimizers.get("adagrad")
    with pytest.raises(ValueError, match="unknown optimizer"):
        _mnist(1, optimizer="adagrad")
    assert optimizers.param_of_slot("velocity_weights") == "weights"
    assert optimizers.param_of_slot("adam_m_bias") == "bias"
    assert optimizers.param_of_slot("adam_t_weights") == "weights"
    assert optimizers.param_of_slot("lion_m_weights") == "weights"
    assert optimizers.param_of_slot("epoch_acc") is None


# -- numpy-oracle parity for every update rule ------------------------------

EXPECTED_SLOTS = {
    "sgd": ("velocity_bias", "velocity_weights"),
    "adam": ("adam_m_bias", "adam_m_weights", "adam_t_bias",
             "adam_t_weights", "adam_v_bias", "adam_v_weights"),
    "adamw": ("adam_m_bias", "adam_m_weights", "adam_t_bias",
              "adam_t_weights", "adam_v_bias", "adam_v_weights"),
    "lion": ("lion_m_bias", "lion_m_weights"),
}


def _oracle_update(name, hyper, attr, p, g, slots):
    """Per-rule numpy reference (float32 throughout, like the step)."""
    f32 = numpy.float32
    lr, decay = f32(hyper["learning_rate"]), f32(
        hyper["weights_decay"])
    if name == "sgd":
        moment = f32(hyper["gradient_moment"])
        geff = g + decay * p if hyper["weights_decay"] else g
        key = "velocity_" + attr
        if hyper["gradient_moment"] and key in slots:
            v = moment * slots[key] - lr * geff
            return p + v, {key: v}
        return p - lr * geff, {}
    if name in ("adam", "adamw"):
        b1, b2 = f32(hyper["beta1"]), f32(hyper["beta2"])
        eps = f32(hyper["eps"])
        t = slots["adam_t_" + attr] + f32(1.0)
        geff = g + decay * p \
            if (name == "adam" and hyper["weights_decay"]) else g
        m = b1 * slots["adam_m_" + attr] + (f32(1) - b1) * geff
        v = b2 * slots["adam_v_" + attr] + \
            (f32(1) - b2) * geff * geff
        mhat = m / (f32(1) - b1 ** t)
        vhat = v / (f32(1) - b2 ** t)
        step = lr * mhat / (numpy.sqrt(vhat) + eps)
        if name == "adamw":
            step = step + lr * decay * p
        return p - step, {"adam_m_" + attr: m, "adam_v_" + attr: v,
                          "adam_t_" + attr: t}
    assert name == "lion"
    b1, b2 = f32(hyper["beta1"]), f32(hyper["beta2"])
    m0 = slots["lion_m_" + attr]
    u = numpy.sign(b1 * m0 + (f32(1) - b1) * g)
    step = lr * u + lr * decay * p
    return p - step, {"lion_m_" + attr: b2 * m0 + (f32(1) - b2) * g}


@pytest.mark.parametrize("name", ALL_OPTS)
def test_fused_step_matches_numpy_oracle(name, f32_precision):
    """THE rule-parity gate: three fused steps, each checked against
    a numpy oracle applied to the gradients of the same
    params/states/batch/key through the same forward.

    Those gradients come from a trace of their own (``jax.grad``, op
    by op), not from inside the fused step, so the activation stream
    is pinned to f32 here: at the default bf16 activations the two
    compilations round one intermediate differently and 12 of
    fc0/weights' 18,816 gradient elements differ by one bf16 ulp
    (0.45%, up to 1.5e-5) — fifteen times the 1e-6 this test allows
    a RULE.  With f32 activations every parameter and slot of every
    rule agrees at rtol 1e-5: the rules do not depart."""
    import jax
    _, wf = _mnist(31, optimizer=name, serve=True,
                   weights_decay=0.0005)
    c = wf.compiler
    for gd in wf.gds:
        assert gd.optimizer == name
        assert tuple(sorted(gd.tstate)) == EXPECTED_SLOTS[name]
    run_forward = c._core_[0]
    for step in range(3):
        key = jax.random.PRNGKey(step)
        params_dev = {n: v.devmem for n, v in c._param_vecs.items()}
        states_dev = {n: v.devmem for n, v in c._state_vecs.items()}
        batch = {str(id(v)): v.devmem for v in c.batch_vectors}
        consts = {str(id(v)): v.devmem for v in c.const_vectors}
        grads = jax.grad(
            lambda p: run_forward(p, states_dev, batch, consts, key,
                                  True)[0])(params_dev)
        p0 = {n: numpy.array(jax.device_get(a))
              for n, a in params_dev.items()}
        s0 = {n: numpy.array(jax.device_get(a))
              for n, a in states_dev.items()}
        g0 = {n: numpy.array(jax.device_get(a))
              for n, a in grads.items()}
        c.execute(key=key, training=True)
        for gd in wf.gds:
            slots0 = {s: s0["%s/%s" % (gd.name, s)]
                      for s in gd.tstate}
            for attr in gd.target.trainables:
                pkey = "%s/%s" % (gd.target.name, attr)
                exp_p, exp_slots = _oracle_update(
                    name, gd._hyper_dict(attr), attr, p0[pkey],
                    g0[pkey], slots0)
                got_p = numpy.array(jax.device_get(
                    c._param_vecs[pkey].devmem))
                numpy.testing.assert_allclose(
                    got_p, exp_p, rtol=1e-5, atol=1e-6,
                    err_msg="%s step %d param %s" %
                            (name, step, pkey))
                for sname, exp in exp_slots.items():
                    got = numpy.array(jax.device_get(
                        c._state_vecs["%s/%s" %
                                      (gd.name, sname)].devmem))
                    numpy.testing.assert_allclose(
                        got, exp, rtol=1e-5, atol=1e-6,
                        err_msg="%s step %d slot %s" %
                                (name, step, sname))


def test_adam_trains_mnist_to_convergence():
    """End-to-end: a full (tiny) training run under Adam converges —
    the fused loop, decision, guardian and snapshot plumbing all
    carry the new slot family."""
    launcher, wf = _mnist(7, optimizer="adam", learning_rate=0.002,
                          max_epochs=3)
    launcher.run()
    assert wf.decision.epoch_number == 3
    assert wf.decision.min_validation_err < 0.5


# -- ZeRO-1/2 mesh sharding -------------------------------------------------

def _host_params(wf):
    out = {}
    for n, vec in wf.compiler._param_vecs.items():
        vec.map_read()
        out[n] = numpy.array(vec.mem)
    return out


def _two_steps(wf):
    import jax
    wf.compiler.execute(key=jax.random.PRNGKey(0), training=True)
    m = wf.compiler.execute(key=jax.random.PRNGKey(1), training=True)
    return {k: float(jax.device_get(v)) for k, v in m.items()}


@pytest.mark.parametrize("level,tp", [(1, False), (2, True)])
def test_zero_sharded_step_matches_unsharded(level, tp):
    """ZeRO acceptance gate: the sharded step reproduces the
    unsharded one (two steps — metrics and params; step-1 metrics
    predate any update, so step 2 is what proves the sharded update
    path), while each dp rank persistently stores 1/dp of the
    optimizer slots."""
    import jax
    from veles_tpu.parallel import (make_mesh, apply_dp_sharding,
                                    apply_dp_tp_sharding,
                                    apply_zero_sharding)
    devices = jax.devices()
    assert len(devices) >= 8

    def build():
        _, wf = _mnist(55, optimizer="adam", layers=(32, 16),
                       minibatch_size=64, max_epochs=5)
        for gd in wf.gds:
            gd.eps = 1e-3  # bounds √v̂ sensitivity near g≈0
        wf.compiler.invalidate()
        wf.compiler.compile()
        wf.loader.serve_next_minibatch()
        return wf

    ref_wf = build()
    apply_dp_sharding(ref_wf, make_mesh(devices[:1], {"data": 1}))
    ref = _two_steps(ref_wf)
    ref_params = _host_params(ref_wf)

    wf = build()
    if tp:
        dp = 2
        apply_dp_tp_sharding(
            wf, make_mesh(devices[:8], {"data": 2, "model": 4}))
    else:
        dp = 8
        apply_dp_sharding(wf, make_mesh(devices[:8], {"data": 8}))
    apply_zero_sharding(wf, wf.mesh, level=level)
    assert wf._zero_ == (level, dp, "data")
    if level >= 2:
        assert wf._zero_grad_shardings_  # grads reduce-scatter
    got = _two_steps(wf)
    for key in sorted(set(ref) & set(got)):
        assert abs(got[key] - ref[key]) <= \
            2e-4 + 2e-4 * abs(ref[key]), (key, got[key], ref[key])
    for key, ref_arr in ref_params.items():
        numpy.testing.assert_allclose(
            _host_params(wf)[key], ref_arr, rtol=1e-3, atol=1e-4,
            err_msg="zero%d param %s" % (level, key))
    # The memory claim on live buffers: slot dim 0 sharded over data,
    # each rank holding 1/dp rows; scalar step counters replicated.
    gd = wf.gds[-1]
    mvec = gd.tstate["adam_m_weights"]
    spec = mvec.devmem.sharding.spec
    assert spec and spec[0] == "data", spec
    rows = mvec.devmem.addressable_shards[0].data.shape[0]
    assert rows == mvec.shape[0] // dp
    tvec = gd.tstate["adam_t_weights"]
    assert tvec.devmem.is_fully_replicated


def test_zero_noop_keeps_shard_frac_honest():
    """When no slot geometry divides the data axis, ZeRO degrades to
    replicated — and the shard_frac gauge must say 1.0, not 1/dp."""
    import jax
    from veles_tpu.observability import attribution
    from veles_tpu.parallel import (make_mesh, apply_dp_sharding,
                                    apply_zero_sharding)
    devices = jax.devices()
    # dp=6: no slot leading dim (784/13/10) divides it — nothing
    # shards.
    _, wf = _mnist(66, optimizer="adam", max_epochs=1,
                   layers=(13, 10))
    apply_dp_sharding(wf, make_mesh(devices[:6], {"data": 6}))
    apply_zero_sharding(wf, wf.mesh, level=1)
    assert wf._zero_ == (1, 1, "data")
    attribution.reset()
    wf.compiler.compile()
    assert attribution.optimizer_summary()["shard_frac"] == 1.0
    attribution.reset()


# -- slot-shard wire sync (ZeRO over the delta data plane) ------------------

def _drive(master, workers, protos, max_cycles=2000):
    """test_dataplane's fixed round-robin loopback schedule, with a
    per-worker proto (slot-sync sessions carry per-worker ranks)."""
    for sid, wf in workers.items():
        master.note_slave_protocol(sid, protos[sid])
        wf.note_net_proto(protos[sid])
    for _ in range(max_cycles):
        if master.should_stop_serving():
            return
        jobs = {}
        for sid in workers:
            if master.should_stop_serving():
                break
            job = master.generate_data_for_slave(sid)
            if job is not None:
                jobs[sid] = job
        if not jobs:
            return
        for sid, job in jobs.items():
            replies = []
            workers[sid].do_job(job, None, replies.append)
            master.apply_data_from_slave(replies[0], sid)
    raise AssertionError("driver did not converge")


def _slot_state(wf):
    out = {}
    for unit in wf.units:
        if not isinstance(unit, GradientDescentBase):
            continue
        for attr, vec in unit.tstate.items():
            vec.map_read()
            out["%s/%s" % (unit.name, attr)] = numpy.array(vec.mem)
    return out


def test_slot_sync_master_mirrors_trainer_bit_identical():
    """The shard-fold gate: with one worker syncing the full state
    (--net-zero 1), the master's canonical optimizer slots are
    BIT-IDENTICAL to the trainer's — the XOR reconstruction is exact,
    so a master snapshot carries the same optimizer state a
    single-node run would have (weights keep training to completion
    through the same session)."""
    proto = dict(DELTA, zero=1, zero_rank=0)
    _, master = _mnist(1234, optimizer="adam")
    _, worker = _mnist(1234, optimizer="adam")
    _drive(master, {"w1": worker}, {"w1": proto})
    assert master.decision.epoch_number == 3
    ms, ws = _slot_state(master), _slot_state(worker)
    assert set(ms) == set(ws) and ms
    moved = 0
    for key in ms:
        assert ms[key].dtype == ws[key].dtype
        numpy.testing.assert_array_equal(
            ms[key], ws[key],
            err_msg="slot %s diverged master vs trainer" % key)
        moved += int(numpy.any(ms[key] != 0))
    assert moved  # the state actually evolved — not a zeros==zeros pass


def test_slot_sync_shards_split_across_workers():
    """--net-zero 2 with two workers: each owns half of every slot
    tensor; the master's canonical state is the union, each half
    bit-identical to its owner's."""
    protos = {"w0": dict(DELTA, zero=2, zero_rank=0),
              "w1": dict(DELTA, zero=2, zero_rank=1)}
    _, master = _mnist(77, optimizer="adam")
    _, w0 = _mnist(77, optimizer="adam")
    _, w1 = _mnist(77, optimizer="adam")
    _drive(master, {"w0": w0, "w1": w1}, protos)
    ms = _slot_state(master)
    states = {"w0": _slot_state(w0), "w1": _slot_state(w1)}
    assert ms
    for key, marr in ms.items():
        flat = marr.reshape(-1)
        n = flat.size
        lo_owner = states["w0"][key].reshape(-1)
        hi_owner = states["w1"][key].reshape(-1)
        numpy.testing.assert_array_equal(flat[:n // 2],
                                         lo_owner[:n // 2],
                                         err_msg="%s lo" % key)
        numpy.testing.assert_array_equal(flat[n // 2:],
                                         hi_owner[n // 2:],
                                         err_msg="%s hi" % key)


def test_slot_wire_bytes_and_bookkeeping_divide_by_dp():
    """BENCHNOTES gate (PR 4 style): vs the replicated baseline
    (--net-zero 1, every worker syncs the FULL state), two-way
    sharding halves the per-minibatch slot wire bytes and the
    master's per-worker synced-base memory."""
    def run(dp):
        resilience.reset()
        protos = {"w%d" % i: dict(DELTA, zero=dp,
                                  zero_rank=i % dp)
                  for i in range(2)}
        _, master = _mnist(42, optimizer="adam", max_epochs=2)
        workers = {}
        for sid in protos:
            _, workers[sid] = _mnist(42, optimizer="adam",
                                     max_epochs=2)
        _drive(master, workers, protos)
        wire = resilience.stats.get("net.slot_bytes")
        book = sum(
            arr.nbytes
            for unit in master.units
            if isinstance(unit, GradientDescentBase)
            for _v, arrays in unit._slot_synced_.values()
            for arr in arrays.values())
        jobs = master.decision.epoch_number  # same schedule both runs
        return wire, book, jobs

    full_wire, full_book, _ = run(1)
    shard_wire, shard_book, _ = run(2)
    assert full_wire > 0 and shard_wire > 0
    # Bookkeeping is exactly ÷dp: 2 workers × full state vs 2 × half.
    assert shard_book * 2 == full_book
    # Wire bytes: each piece carries half the elements; steady-state
    # asymmetries (replicated mode re-ships dense master→worker
    # deltas after the other worker's fold) make the replicated
    # baseline strictly MORE than 2× — require ≥ 1.8× to be robust.
    assert full_wire >= 1.8 * shard_wire, (full_wire, shard_wire)


def test_slot_sync_absent_without_negotiation():
    """Default sessions (no zero capability negotiated) ship NO slot
    pieces — worker optimizer state stays local, wire unchanged."""
    _, master = _mnist(5, optimizer="adam", max_epochs=2)
    master.note_slave_protocol("w1", dict(DELTA))
    job = master.generate_data_for_slave("w1")
    for unit in master.units:
        if isinstance(unit, GradientDescentBase):
            assert unit.name not in job
    assert resilience.stats.get("net.slot_bytes") == 0


def test_zero_negotiation_matrix():
    from veles_tpu.server import negotiate_protocol
    from veles_tpu.client import WORKER_CAPS
    cfg = dict(mode="delta", codec="none", codec_level=1,
               codec_threshold=1, dtype="fp32", job_ticks=1,
               require=False, trace=False, zero=4)
    proto, err = negotiate_protocol(
        {"proto": dict(WORKER_CAPS)}, cfg)
    assert err is None and proto["zero"] == 4
    # Old worker without the slots capability: no slot sync, session
    # still serves (protocol bump by capability, not frame break).
    caps = dict(WORKER_CAPS)
    caps.pop("slots")
    proto, err = negotiate_protocol({"proto": caps}, cfg)
    assert err is None and "zero" not in proto
    proto, err = negotiate_protocol(
        {"proto": dict(WORKER_CAPS)}, dict(cfg, zero=0))
    assert "zero" not in proto
    proto, err = negotiate_protocol(
        {"proto": dict(WORKER_CAPS)}, dict(cfg, mode="legacy"))
    assert proto == {}


# -- snapshot/rollback matrix ----------------------------------------------

@pytest.mark.parametrize("name", ("adam", "lion"))
def test_snapshot_roundtrip_preserves_slots(name):
    """The snapshot matrix's new rows: every slot kind rides the
    pickle bit-for-bit (m/v moments, scalar step counters, lion
    momentum) and the restored unit keeps its optimizer."""
    _, wf = _mnist(91, optimizer=name, serve=True)
    import jax
    for i in range(2):
        wf.compiler.execute(key=jax.random.PRNGKey(i), training=True)
    wf2 = pickle.loads(pickle.dumps(wf))
    before, after = _slot_state(wf), _slot_state(wf2)
    assert set(before) == set(after) and before
    for key in before:
        numpy.testing.assert_array_equal(before[key], after[key])
    for gd in wf2.gds:
        assert gd.optimizer == name


def test_rollback_restores_all_slot_kinds():
    """Guardian rollback must restore EVERY slot kind, not just
    velocity_* — restore_vectors walks tstate generically."""
    from veles_tpu.guardian import restore_vectors
    import jax
    _, wf = _mnist(21, optimizer="adam", serve=True)
    wf.compiler.execute(key=jax.random.PRNGKey(0), training=True)
    snapshot = pickle.loads(pickle.dumps(wf))
    good = _slot_state(snapshot)
    for gd in wf.gds:  # poison the live state
        for vec in gd.tstate.values():
            vec.map_write()
            vec.mem[...] = -7.0
    restored = restore_vectors(wf, snapshot)
    assert restored > 0
    live = _slot_state(wf)
    assert set(live) == set(good)
    for key in good:
        numpy.testing.assert_array_equal(live[key], good[key])
    # adam_t_* (scalar counters) were restored too, not skipped.
    assert any("adam_t_" in key for key in good)


def test_rollback_across_optimizer_kinds_is_loud_not_corrupting():
    """A rollback source trained under a different optimizer restores
    weights but leaves the live slot family alone — and says so."""
    from veles_tpu.guardian import restore_vectors
    _, wf = _mnist(23, optimizer="adam", max_epochs=1)
    _, src = _mnist(23, optimizer="sgd", max_epochs=1)
    live_before = _slot_state(wf)
    restored = restore_vectors(wf, src)
    assert restored > 0  # weights still restore
    live_after = _slot_state(wf)
    assert set(live_after) == set(live_before)
    for key in live_before:
        numpy.testing.assert_array_equal(live_after[key],
                                         live_before[key])


def test_momentum_snapshot_into_adam_run_errors():
    """Regression (ISSUE 9 satellite): resuming a momentum-SGD
    snapshot under --optimizer adam must fail with an actionable
    slot-mismatch error, not silently reinitialize the slots."""
    _, wf = _mnist(9, optimizer="sgd", max_epochs=1)
    assert any("velocity_" in s for gd in wf.gds for s in gd.tstate)
    wf2 = pickle.loads(pickle.dumps(wf))
    launcher2 = Launcher()
    launcher2.add_ref(wf2)
    root.common.engine.optimizer = "adam"
    try:
        with pytest.raises(optimizers.SlotMismatchError,
                           match="different optimizer"):
            launcher2.initialize(snapshot=True)
    finally:
        root.common.engine.optimizer = "sgd"


def test_explicit_optimizer_kwarg_pins_against_override():
    """A unit constructed with optimizer= keeps it even when the
    config override names another rule."""
    prng.reset()
    prng.get(0).seed(3)
    launcher = Launcher()
    wf = MnistWorkflow(launcher, layers=(24, 10), max_epochs=1)
    for gd in wf.gds:
        gd.optimizer = "lion"
        gd._optimizer_explicit = True
    root.common.engine.optimizer = "adam"
    try:
        launcher.initialize()
    finally:
        root.common.engine.optimizer = "sgd"
    for gd in wf.gds:
        assert gd.optimizer == "lion"
        assert all(s.startswith("lion_m_") for s in gd.tstate)


# -- GA tunability ----------------------------------------------------------

def test_vmap_population_tunes_adam_betas():
    """vmap_eval satellite: optimizer hypers from the registry (Adam
    beta1) become traced population inputs alongside the classic
    learning rate; tuning a hyper NO unit's optimizer consumes is an
    actionable Bug."""
    import os
    from veles_tpu.__main__ import import_workflow_module
    from veles_tpu.genetics import collect_tunes
    from veles_tpu.genetics.vmap_eval import (PopulationEvaluator,
                                              hyper_names)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))
    mnist = os.path.join(repo, "veles_tpu", "znicz", "samples",
                         "mnist.py")
    root.mnist.reset()
    root.mnist.max_epochs = 1
    root.mnist.learning_rate = Tune(0.005, 0.0001, 0.1)
    root.mnist.beta1 = Tune(0.9, 0.5, 0.999)
    tunes = [(p, t) for p, t in collect_tunes(root)
             if p.startswith("mnist.")]
    names = hyper_names(tunes)
    assert set(names) == {"learning_rate", "beta1"}
    module = import_workflow_module(mnist)
    root.common.engine.optimizer = "adam"
    try:
        prng.reset()
        evaluator = PopulationEvaluator(module, tunes, seed=11)
        gene = {"learning_rate": 0.005, "beta1": 0.9}
        gene_lo = {"learning_rate": 0.005, "beta1": 0.55}
        fits = evaluator.evaluate(
            [[gene[n] for n in names], [gene_lo[n] for n in names]],
            epochs=1)
        assert fits.shape == (2,)
        assert numpy.isfinite(fits).all()
        # Tuning a hyper adam does not consume → actionable Bug.
        evaluator.names = ("gradient_moment",)
        with pytest.raises(Bug, match="consumes"):
            evaluator._check_tuned_hypers()
    finally:
        root.common.engine.optimizer = "sgd"
        root.mnist.reset()


# -- observability + device residency ---------------------------------------

def test_optimizer_gauges_and_perf_summary():
    from veles_tpu.observability import attribution, metrics
    attribution.reset()
    _, wf = _mnist(5, optimizer="lion", max_epochs=1)
    wf.compiler.compile()
    summary = attribution.optimizer_summary()
    assert summary["kind"] == "lion"
    expected = sum(vec.nbytes for gd in wf.gds
                   for vec in gd.tstate.values())
    assert summary["state_bytes"] == expected > 0
    assert summary["shard_frac"] == 1.0
    gauge = metrics.registry.gauge("optimizer.state_bytes",
                                   labels={"kind": "lion"})
    assert gauge.value == expected
    # Rides the heartbeat perf section (→ web_status perf row).
    attribution.record_step(0.01, flops=None, ticks=1)
    perf = attribution.perf_summary()
    assert perf["optimizer"] == "lion"
    assert perf["optimizer_state_bytes"] == expected
    assert perf["optimizer_shard_frac"] == 1.0
    attribution.reset()
    assert attribution.optimizer_summary() is None


def test_slots_stay_on_device_during_steady_state():
    """memory.py satellite: optimizer slots never leave the device
    while stepping — host syncs happen only at snapshot/rollback/
    wire boundaries."""
    import jax
    _, wf = _mnist(3, optimizer="adam", serve=True)
    c = wf.compiler
    c.execute(key=jax.random.PRNGKey(0), training=True)
    slot_vecs = [vec for gd in wf.gds
                 for vec in gd.tstate.values()]
    assert slot_vecs
    before = [vec.host_sync_count for vec in slot_vecs]
    for i in range(3):
        c.execute(key=jax.random.PRNGKey(i + 1), training=True)
    assert [vec.host_sync_count for vec in slot_vecs] == before
    pickle.dumps(wf)  # a snapshot boundary maps device → host
    assert any(vec.host_sync_count > b
               for vec, b in zip(slot_vecs, before))


# -- CLI / bench / docs plumbing -------------------------------------------

def test_cli_flags_registered():
    from veles_tpu.cmdline import init_argparser
    parser = init_argparser(prog="veles_tpu")
    args = parser.parse_args(
        ["wf.py", "--optimizer", "adam", "--zero", "2",
         "--net-zero", "4"])
    assert args.optimizer == "adam"
    assert args.zero == 2
    assert args.net_zero == 4
    import bench
    assert "--optimizer" in bench.BENCH_FLAGS


def test_bench_optimizer_fields():
    import bench
    _, wf = _mnist(8, optimizer="adamw", serve=True)
    fields = bench.optimizer_fields(wf, "adamw")
    assert fields["optimizer"] == "adamw"
    assert fields["optimizer_state_bytes"] > 0
    assert fields["update_device_ms"] > 0
    assert fields["slot_wire_bytes"] is None  # single-node bench


def test_snapshot_manifest_records_optimizer(tmp_path):
    from veles_tpu.snapshotter import SnapshotterToFile, read_manifest
    _, wf = _mnist(13, optimizer="adam", max_epochs=1)
    snap = SnapshotterToFile(wf, directory=str(tmp_path),
                             prefix="opt", time_interval=0.0)
    snap.export()
    manifest = read_manifest(snap.destination)
    assert manifest["optimizer"] == "adam"
