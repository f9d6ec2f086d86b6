"""Rematerialization (jax.checkpoint) for transformer stacks.

Ring attention gives O(S/N) *attention* memory, but without remat the
backward pass still stores every block's residual stream — the real
long-context limiter.  ``root.common.engine.remat`` (or the per-unit
``remat`` kwarg) wraps each block application in ``jax.checkpoint``:
XLA's buffer assignment then shows the activation-memory drop, and
the math is bit-for-bit the same step (checkpointing only re-runs the
forward inside the backward).
"""

import collections
import contextlib
import functools
import re

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


@pytest.fixture
def flash_interpret(monkeypatch):
    """Attention dispatch selects the flash kernels, as on a TPU, and
    they run in interpret mode: the custom VJP, its residuals and the
    two names are the chip's."""
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import pallas_attention as PA
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    monkeypatch.setattr(PA, "pallas_attention", functools.partial(
        PA.pallas_attention, interpret=True))


#: A geometry inside ``pallas_attention.supports``: 2 heads of 64
#: over 128 positions.
_FLASH = dict(embed_dim=128, n_heads=2, seq_len=128, minibatch_size=4,
              loader_config={"n_train": 64, "n_valid": 16})


@pytest.mark.parametrize("family", ["dense", "moe", "pipelined",
                                    "dense-flash", "pipelined-flash"])
def test_remat_step_matches_plain(family, f32_precision, request):
    """Checkpointing must not change the math — the recompute is the
    same computation, so any difference is only XLA re-fusing around
    the checkpoint boundary (float-noise level).  (The MoE case also
    proves the expert layer's counts survive the checkpoint
    boundary: side outputs ride the return value, not ctx closure
    mutation.)  The ``-flash`` families take the kernel path, where
    the checkpoint keeps the forward kernel's output."""
    kwargs = {"n_blocks": 2, "seq_len": 32, "minibatch_size": 32}
    if family.endswith("-flash"):
        request.getfixturevalue("flash_interpret")
        kwargs.update(_FLASH)
        family = family[:-len("-flash")]
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


# -- what the layers' checkpoint keeps (ISSUE 32) ------------------------


def _layer_loss(wrap, interpret, n_layers=2, **spec):
    """``(loss, params, x)``: ``n_layers`` layers of ``spec`` (OPT's
    by default; 2 heads of 64, 128 positions, bfloat16 operands), each
    under ``wrap``; attention through the flash kernels in interpret
    mode, or XLA's."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import pallas_attention as PA
    from veles_tpu.znicz import attention as Z
    B, S, H, D = 2, 128, 2, 64
    spec = Z.layer_spec(n_heads=H, ffn_dim=64, **spec)
    key = jax.random.PRNGKey(0)
    params = {
        name: 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape)
        for i, (name, shape) in enumerate(
            Z.layer_param_shapes(spec, H * D).items())}
    x = jax.random.normal(key, (B, S, H * D))
    attend = functools.partial(PA.pallas_attention, causal=True,
                               interpret=True) if interpret else None

    def layer(p, h):
        return Z.layer_apply(spec, p, h, jnp.bfloat16,
                             attend=attend)[0]

    layer = wrap(layer)

    def loss(p, h):
        for _ in range(n_layers):
            h = layer(p, h)
        return (h * h).sum()

    return loss, params, x


def _wrap(name):
    """What a case wraps each layer in, by the case's name."""
    import jax
    from veles_tpu.znicz.attention import checkpointed
    return {"none": lambda fn: fn, "bare": jax.checkpoint,
            "checkpointed": checkpointed}[name]


def _residuals(loss, *args):
    """What autodiff stores for the backward pass, arguments left
    out: a list of (shape, dtype name, where it came from)."""
    from jax._src.ad_checkpoint import saved_residuals
    return [(tuple(a.shape), str(a.dtype), why)
            for a, why in saved_residuals(loss, *args)
            if not why.startswith("from the argument")]


def _kernel_calls(loss, *args):
    import jax
    text = str(jax.make_jaxpr(jax.grad(loss))(*args))
    return {kernel: len(re.findall(r"\bname=%s\b" % kernel, text))
            for kernel in ("flash_fwd", "flash_dq", "flash_dkv")}


def test_checkpoint_keeps_what_the_flash_forward_produced():
    """Beside what a bare ``jax.checkpoint`` stores (each layer's
    input), a layer keeps exactly the kernel's output — one
    compute-dtype activation in the backward's (B·H, S, D) layout —
    and its float32 log-sum-exp rows: no q, k, v, no projection, no
    MLP activation."""
    from veles_tpu.ops import pallas_attention as PA
    ours = _residuals(*_layer_loss(_wrap("checkpointed"), True))
    kept = collections.Counter(r[:2] for r in ours)
    bare = collections.Counter(
        r[:2] for r in _residuals(*_layer_loss(_wrap("bare"), True)))
    assert kept - bare == {((4, 128, 64), "bfloat16"): 2,
                           ((4, 128), "float32"): 2}
    assert not bare - kept
    named = [why for _, _, why in ours if "named" in why]
    assert len(named) == 2 and all(PA.FLASH_LSE in w for w in named)


@pytest.mark.parametrize("wrap,forwards", [
    ("none", 2), ("bare", 4), ("checkpointed", 2)])
def test_recompute_holds_no_flash_forward(wrap, forwards):
    """Two attention layers: the gradient holds one ``flash_dq`` and
    one ``flash_dkv`` a layer whatever wraps it, and ONE
    ``flash_fwd`` a layer under the layers' checkpoint — as without
    any; a checkpoint with no policy runs the kernel again in every
    recompute."""
    assert _kernel_calls(*_layer_loss(_wrap(wrap), True)) == {
        "flash_fwd": forwards, "flash_dq": 2, "flash_dkv": 2}


@pytest.mark.parametrize("wrap,forwards", [("bare", 4),
                                           ("checkpointed", 2)])
def test_ring_keeps_every_chunks_partial(wrap, forwards):
    """The ring reaches the same custom VJP through ``flash_chunk``:
    over two sequence shards a checkpointed layer keeps each of its
    two chunks' output and rows (the names are given inside
    ``shard_map``), and the recompute runs no forward kernel."""
    import jax
    from veles_tpu.ops import attention as A
    from veles_tpu.parallel import make_mesh
    mesh = make_mesh(axes={"seq": 2})
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (2, 32, 3, 5)) for i in range(3))

    @_wrap(wrap)
    def attend(q, k, v):
        return A.sequence_parallel_attention(
            q, k, v, mesh, "seq", causal=True, kernel="pallas",
            interpret=True)

    def loss(q, k, v):
        return (attend(q, k, v) ** 2).sum()

    assert _kernel_calls(loss, q, k, v) == {
        "flash_fwd": forwards, "flash_dq": 2, "flash_dkv": 2}


@pytest.mark.parametrize("spec", [
    {}, dict(norm="rms", bias=False, ffn="gated-mlp", kv_heads=1,
             qk_norm=True, rope_theta=1e4),
    dict(norm="rms", bias=False, operator="shortconv",
         ffn="gated-mlp")],
    ids=["opt", "gqa + gated-mlp", "shortconv + gated-mlp"])
def test_checkpoint_over_xla_attention_is_the_bare_one(spec):
    """Where no value came out of the flash kernel or of an expert
    layer the policy finds nothing to save — XLA's attention, a
    short convolution, a dense MLP name nothing: the stored residuals
    are the bare ``jax.checkpoint``'s one for one, the gradient's
    program lowers to the same text, and the gradients are the same
    bits."""
    import jax
    ours, params, x = _layer_loss(_wrap("checkpointed"), False, **spec)
    bare, _, _ = _layer_loss(_wrap("bare"), False, **spec)
    assert _residuals(ours, params, x) == _residuals(bare, params, x)
    assert _kernel_calls(ours, params, x)["flash_fwd"] == 0
    ours, bare = (jax.jit(jax.grad(f)) for f in (ours, bare))
    assert ours.lower(params, x).as_text() == \
        bare.lower(params, x).as_text()
    for name, grad in ours(params, x).items():
        numpy.testing.assert_array_equal(grad, bare(params, x)[name])


def test_kept_output_gives_the_second_calls_bits():
    """A kept value is the value the recompute's kernel call would
    have produced: gradients under the layers' checkpoint equal a
    bare ``jax.checkpoint``'s bit for bit.  (Here, where the recompute
    reproduces the forward pass; on the chip the parent's did not, to
    the last bit — PERF.md §6, PR 32.)"""
    import jax
    ours, params, x = _layer_loss(_wrap("checkpointed"), True)
    bare, _, _ = _layer_loss(_wrap("bare"), True)
    ours = jax.jit(jax.grad(ours))(params, x)
    bare = jax.jit(jax.grad(bare))(params, x)
    for name in ours:
        numpy.testing.assert_array_equal(ours[name], bare[name])


# -- what the layers' checkpoint keeps of an expert layer (ISSUE 34) -----

#: The expert layer the cases below build: 256 tokens of 32, top 2 of
#: 8 experts 16 wide, experts 2 and 3 held; with row tiles of 128 the
#: common path is compiled for 256 of the 512 assignments, 2 chunks.
_EXPERTS = dict(T=256, D=32, E=8, top_k=2, held=(2, 2), F=16,
                row_tile=128, chunk=256)


def _expert_layer_loss(wrap, monkeypatch, routing="common"):
    """``(loss, params, x, landed)``: one short-convolution + experts
    layer under ``wrap``.  ``routing``: ``common`` (no selection bias:
    about a quarter of the assignments land, one chunk) or ``walk``
    (the bias sends every token to the two held experts: all 512
    land, and ``lax.cond`` takes the walk over both chunks)."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import moe as M
    from veles_tpu.znicz import attention as Z
    e = _EXPERTS
    monkeypatch.setattr(M, "ROW_TILE", e["row_tile"])
    assert M.dropless_rows(e["T"], e["top_k"], e["E"],
                           e["held"][1]) == (e["chunk"], 2)
    spec = Z.layer_spec(norm="rms", operator="shortconv", bias=False,
                        ffn="experts", ffn_dim=e["F"],
                        n_experts=e["E"], top_k=e["top_k"],
                        held=e["held"])
    key = jax.random.PRNGKey(0)
    params = {
        name: 0.2 * jax.random.normal(jax.random.fold_in(key, i), shape)
        for i, (name, shape) in enumerate(
            Z.layer_param_shapes(spec, e["D"]).items())}
    x = jax.random.normal(key, (2, e["T"] // 2, e["D"]))
    bias = jnp.zeros((e["E"],))
    if routing == "walk":
        first, count = e["held"]
        bias = bias.at[first:first + count].add(10.0)

    def apply(p, h):
        return Z.layer_apply(spec, p, h, jnp.bfloat16,
                             buffers={"expert_bias": bias})

    landed = float(apply(params, x)[1]["landed"])
    layer = wrap(lambda p, h: apply(p, h)[0])

    def loss(p, h):
        return (layer(p, h) ** 2).sum()

    return loss, params, x, landed


def _count(jaxpr, counts=None):
    """Primitive name → equations of ``jaxpr``, sub-jaxprs included."""
    from jax._src import core
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for sub in core.jaxprs_in_params(eqn.params):
            _count(sub, counts)
    return counts


@pytest.mark.parametrize("wrap,products,routing_ops,gathers", [
    ("bare", 3, 1, 2), ("checkpointed", 1, 0, 1)])
def test_recompute_of_an_expert_layer_holds_one_grouped_product(
        monkeypatch, wrap, products, routing_ops, gathers):
    """The ``remat2`` equation of a checkpointed expert layer's
    gradient (recompute, then backward).  Under the layers' checkpoint
    its recompute — the first ``cond``, whose common branch is the
    second — holds ONE grouped product (``ys``: ``lax.ragged_dot``
    stands in for ``gmm`` here), the gather of the chunk's weights
    alone, and no sort, no ``top_k``, no router product (the float32
    product of ``(T, D)`` by ``(D, E)``) and no gather of the chosen
    scores; under a bare ``jax.checkpoint`` all three products, both
    gathers and the whole routing.  The walk's
    branch names nothing and is the same under both: its inner
    checkpoint rebuilds a chunk from the layer's input."""
    import jax
    e = _EXPERTS
    loss, params, x, _ = _expert_layer_loss(_wrap(wrap), monkeypatch)
    remat, = [eqn for eqn in
              jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr.eqns
              if eqn.primitive.name == "remat2"]
    body = remat.params["jaxpr"]
    counts = _count(body)
    router = [eqn for eqn in body.eqns
              if eqn.primitive.name == "dot_general" and
              [v.aval.shape for v in eqn.invars] ==
              [(e["T"], e["D"]), (e["D"], e["E"])]]
    chosen = [eqn for eqn in body.eqns if eqn.primitive.name in
              ("jit", "pjit") and eqn.params["name"] == "take_along_axis"
              and "gather" in _count(eqn.params["jaxpr"].jaxpr)]
    assert (counts["sort"], counts["top_k"], len(router),
            len(chosen)) == (routing_ops,) * 4
    recompute, backward = [eqn for eqn in body.eqns
                           if eqn.primitive.name == "cond"]
    walk, common = (_count(branch.jaxpr)
                    for branch in recompute.params["branches"])
    assert common["ragged_dot_general"] == products
    assert common["gather"] == gathers
    assert walk["ragged_dot_general"] == 0 and walk["scan"] == 1
    walk, common = (_count(branch.jaxpr)
                    for branch in backward.params["branches"])
    # dlhs and drhs of each product; the walk's inner checkpoint
    # runs the three forward products once more
    assert (common["ragged_dot_general"],
            walk["ragged_dot_general"]) == (6, 9)


@pytest.mark.parametrize("routing", ["common", "walk"])
def test_kept_expert_values_give_the_recomputes_bits(monkeypatch,
                                                     routing):
    """A kept value is the value the recompute would have produced:
    evaluated operation by operation, an expert layer's gradients
    under the layers' checkpoint equal a bare ``jax.checkpoint``'s bit
    for bit — on a routing that takes the common path, whose names
    are kept, and on one that lands more than ``chunk``, where the
    branch taken names nothing and the other hands zeros.  (Compiled
    as ONE program the router's gradient is 1e-7 off on the CPU: XLA
    fuses the derivative of the scores with their rebuilding where
    there is one.)"""
    import jax
    grads = {}
    for wrap in ("bare", "checkpointed"):
        loss, params, x, landed = _expert_layer_loss(
            _wrap(wrap), monkeypatch, routing)
        assert (landed > _EXPERTS["chunk"]) == (routing == "walk")
        with jax.disable_jit():
            grads[wrap] = jax.grad(loss, argnums=(0, 1))(params, x)
    for a, b in zip(*(jax.tree_util.tree_leaves(grads[wrap])
                      for wrap in ("bare", "checkpointed"))):
        assert float(abs(a).max()) > 0
        numpy.testing.assert_array_equal(a, b)


def test_checkpoint_keeps_what_the_expert_layer_names(monkeypatch):
    """Beside what a bare ``jax.checkpoint`` stores, a checkpointed
    expert layer keeps the router's scores (T, E) float32, the choice
    (T, k) int32 and the scores it chose (T, k) float32, the order
    (T · k) and the sizes (count) as int32, the gathered rows
    (chunk, D) in the compute type and the two products before the
    gate (chunk, F) float32 — and nothing else (a value
    handed on through a jitted helper is listed once more: shapes, not
    counts).  The selection bias is no longer stored: only the
    choice's rebuilding read it."""
    e = _EXPERTS
    kept, bare = (
        {r[:2] for r in _residuals(*_expert_layer_loss(
            _wrap(wrap), monkeypatch)[:3])}
        for wrap in ("checkpointed", "bare"))
    assert bare - kept == {((e["E"],), "float32")}
    assert kept - bare == {
        ((e["T"], e["E"]), "float32"),
        ((e["T"], e["top_k"]), "int32"),
        ((e["T"], e["top_k"]), "float32"),
        ((e["T"] * e["top_k"],), "int32"),
        ((e["held"][1],), "int32"),
        ((e["chunk"], e["D"]), "bfloat16"),
        ((e["chunk"], e["F"]), "float32")}


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["LMLayer", "stack"])
def test_both_checkpoint_sites_use_the_one_helper(pipelined,
                                                  monkeypatch,
                                                  flash_interpret,
                                                  f32_precision):
    """``LMLayer.tforward`` and the pipelined stack's block function
    go through ``checkpointed`` — so the one-tick step on the kernel
    path, with a bare ``jax.checkpoint`` swapped in under them (the
    forward kernel then runs twice a block), lands on the same
    parameters, to the re-fusing of the interpreted kernel around the
    checkpoint's boundary."""
    import jax
    from veles_tpu.znicz import attention as Z
    kwargs = dict(_FLASH, n_blocks=2)
    if pipelined:
        kwargs.update(pipelined=True, n_microbatches=2)
    wrapped = []

    def recording(fn):
        wrapped.append(fn)
        return jax.checkpoint(fn)

    ours = _one_step_params(True, **kwargs)
    monkeypatch.setattr(Z, "checkpointed", recording)
    bare = _one_step_params(True, **kwargs)
    assert wrapped
    for name in ours:
        numpy.testing.assert_allclose(
            ours[name], bare[name], rtol=1e-5, atol=1e-7,
            err_msg="param %s differs from the bare checkpoint's"
            % name)
