"""Rematerialization (jax.checkpoint) for transformer stacks.

Ring attention gives O(S/N) *attention* memory, but without remat the
backward pass still stores every block's residual stream — the real
long-context limiter.  ``root.common.engine.remat`` (or the per-unit
``remat`` kwarg) wraps each block application in ``jax.checkpoint``:
XLA's buffer assignment then shows the activation-memory drop, and
the math is bit-for-bit the same step (checkpointing only re-runs the
forward inside the backward).
"""

import contextlib

import numpy
import pytest

import veles_tpu.prng as prng
from veles_tpu.launcher import Launcher


@contextlib.contextmanager
def _remat_config(value):
    from veles_tpu.config import root
    prev = getattr(root.common.engine, "remat", None)
    root.common.engine.remat = value
    try:
        yield
    finally:
        root.common.engine.remat = False if prev is None else prev


def _build_tinylm(**kwargs):
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    prng.reset()
    prng.get(0).seed(42)
    launcher = Launcher()
    kwargs.setdefault("max_epochs", 1)
    wf = TinyLMWorkflow(launcher, **kwargs)
    launcher.initialize()
    return launcher, wf


_DEEP = dict(n_blocks=6, embed_dim=64, n_heads=4, seq_len=128,
             minibatch_size=16,
             loader_config={"n_train": 64, "n_valid": 16})


def _prepared_compiler(remat, **kwargs):
    with _remat_config(remat):
        _, wf = _build_tinylm(**kwargs)
        c = wf.compiler
        c.compile()
        wf.loader.serve_next_minibatch()
    return c


def _step_args(c):
    params = {n: v.devmem for n, v in c._param_vecs.items()}
    states = {n: v.devmem for n, v in c._state_vecs.items()}
    batch = {str(id(v)): v.devmem for v in c.batch_vectors}
    consts = {str(id(v)): v.devmem for v in c.const_vectors}
    return params, states, batch, consts


def _train_step_temp_bytes(remat, **kwargs):
    """XLA buffer-assignment temp bytes of the fused train step.
    NB the remat config must cover the LOWER call — tracing is lazy,
    and remat_enabled() is consulted when tforward actually traces."""
    import jax
    c = _prepared_compiler(remat, **kwargs)
    params, states, batch, consts = _step_args(c)
    with _remat_config(remat):
        lowered = jax.jit(c._train_fn).lower(
            params, states, batch, consts, jax.random.PRNGKey(0))
    return lowered.compile().memory_analysis().temp_size_in_bytes


def _saved_residual_bytes(remat, **kwargs):
    """Bytes of forward residuals autodiff will STORE for the
    backward — the quantity jax.checkpoint controls directly (and
    backend-independently; XLA-CPU's buffer assignment does not
    reschedule unrolled chains the way the TPU compiler does, so
    temp_size alone understates remat there)."""
    import jax
    import numpy as np
    try:
        from jax.ad_checkpoint import saved_residuals
    except ImportError:
        from jax._src.ad_checkpoint import saved_residuals
    c = _prepared_compiler(remat, **kwargs)
    params, states, batch, consts = _step_args(c)
    run_forward = c._core_[0]

    def loss(p):
        l, _, _, _ = run_forward(p, states, batch, consts,
                                 jax.random.PRNGKey(0), True)
        return l

    with _remat_config(remat):
        res = saved_residuals(loss, params)
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a, _ in res
               if hasattr(a, "shape") and hasattr(a, "dtype"))


def test_remat_shrinks_stored_residuals():
    """A 6-block stack must store an order of magnitude fewer
    backward residuals with per-block checkpointing (the whole
    point: trade ~1/3 extra FLOPs for O(blocks·S²)→O(blocks·S)
    stored bytes).  Measured: ~199 MB → ~4.5 MB on this geometry."""
    base = _saved_residual_bytes(False, **_DEEP)
    remat = _saved_residual_bytes(True, **_DEEP)
    assert remat < 0.1 * base, \
        "remat residuals %d not < 0.1 × base %d" % (remat, base)


def test_remat_shrinks_pipelined_stack_memory():
    kwargs = dict(_DEEP)
    kwargs.update(pipelined=True, n_microbatches=2)
    base = _train_step_temp_bytes(False, **kwargs)
    remat = _train_step_temp_bytes(True, **kwargs)
    assert remat < 0.85 * base, \
        "remat temp %d not < 0.85 × base temp %d" % (remat, base)


def _one_step_params(remat, **kwargs):
    import jax
    with _remat_config(remat):
        _, wf = _build_tinylm(**kwargs)
        wf.loader.serve_next_minibatch()
        wf.begin_tick()
        wf.compiler.execute(key=jax.random.PRNGKey(0), training=True)
        return {n: numpy.asarray(jax.device_get(v.devmem))
                for n, v in wf.compiler._param_vecs.items()}


@pytest.mark.parametrize("family", ["dense", "moe", "pipelined"])
def test_remat_step_matches_plain(family, f32_precision):
    """Checkpointing must not change the math — the recompute is the
    same computation, so any difference is only XLA re-fusing around
    the checkpoint boundary (float-noise level).  (The MoE case also
    proves the expert layer's counts survive the checkpoint
    boundary: side outputs ride the return value, not ctx closure
    mutation.)"""
    kwargs = {"n_blocks": 2, "seq_len": 32, "minibatch_size": 32}
    if family == "moe":
        from veles_tpu.znicz.attention import layer_spec
        kwargs["layers"] = [
            layer_spec(ffn="experts", n_experts=4, top_k=2),
            layer_spec(norm="rms", ffn="experts", n_experts=4,
                       top_k=2, held=(1, 2), bias=False,
                       rope_theta=1e4)]
    elif family == "pipelined":
        kwargs.update(pipelined=True, n_microbatches=2)
    ref = _one_step_params(False, **kwargs)
    got = _one_step_params(True, **kwargs)
    for name in ref:
        numpy.testing.assert_allclose(
            ref[name], got[name], rtol=1e-5, atol=1e-7,
            err_msg="param %s diverged under remat" % name)


def test_remat_training_reaches_gate():
    """End-to-end: the attention-recall gate holds with remat on."""
    with _remat_config(True):
        launcher, wf = _build_tinylm(max_epochs=8)
        launcher.run()
        assert wf.decision.min_validation_err < 0.05


def test_unit_kwarg_overrides_config():
    """remat=False on the unit beats an enabled config (and vice
    versa): the kwarg is the per-unit escape hatch."""
    from veles_tpu.znicz.attention import remat_enabled
    with _remat_config(True):
        assert remat_enabled(None) is True
        assert remat_enabled(False) is False
    with _remat_config(False):
        assert remat_enabled(None) is False
        assert remat_enabled(True) is True
