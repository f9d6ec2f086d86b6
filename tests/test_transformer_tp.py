"""Megatron-style tensor parallelism for the transformer family.

The reference's only engine was master–slave data parallelism
(reference: veles/server.py:659, veles/client.py:405); SURVEY §2.3
sets tensor parallelism as the TPU build's natural-XLA obligation.
These tests pin the column/row weight layout per parameter family
(attention qkv/o, MLP up/down, pipelined stacks, LM head,
embedding), chosen by the unit's parameter NAMES; verify ONE fused
training step under dp×tp is numerically the same step as
fully-replicated dp, and exercise the composed 3-axis dp×tp×sp
layout end-to-end.
"""

import numpy
import pytest

import veles_tpu.prng as prng
from veles_tpu.launcher import Launcher
from veles_tpu.parallel import (make_mesh, apply_dp_sharding,
                                apply_dp_tp_sharding,
                                apply_dp_tp_sp_sharding)


def _build_tinylm(**kwargs):
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    prng.reset()
    prng.get(0).seed(42)
    launcher = Launcher()
    kwargs.setdefault("max_epochs", 8)
    wf = TinyLMWorkflow(launcher, **kwargs)
    launcher.initialize()
    return launcher, wf


def _one_step_params(shard_fn=None, **lm_kwargs):
    """Builds a TinyLM, applies ``shard_fn``, runs ONE fused training
    step with a fixed key, returns host copies of every parameter."""
    import jax
    lm_kwargs.setdefault("max_epochs", 1)
    _, wf = _build_tinylm(**lm_kwargs)
    if shard_fn is not None:
        shard_fn(wf)
    wf.loader.serve_next_minibatch()
    wf.begin_tick()
    wf.compiler.execute(key=jax.random.PRNGKey(0), training=True)
    return {n: numpy.asarray(jax.device_get(v.devmem))
            for n, v in wf.compiler._param_vecs.items()}


def _block_unit(wf):
    return [u for u in wf.forwards
            if type(u).__name__.endswith("TransformerBlock")][0]


def test_dense_block_param_shardings():
    """The canonical Megatron layout on a dense block: qkv/up column,
    o/down row, qkv biases sharded, residual-side params replicated,
    momentum slots mirroring their parameter (BY NAME — wq/wk/wv all
    share a shape)."""
    import jax
    from jax.sharding import PartitionSpec as P
    _, wf = _build_tinylm(max_epochs=1)
    mesh = make_mesh(jax.devices(), {"data": 2, "model": 4})
    apply_dp_tp_sharding(wf, mesh)
    blk = _block_unit(wf)
    spec_of = lambda v: v.devmem.sharding.spec  # noqa: E731
    assert spec_of(blk.params["wq"]) == P(None, "model")
    assert spec_of(blk.params["wk"]) == P(None, "model")
    assert spec_of(blk.params["wv"]) == P(None, "model")
    assert spec_of(blk.params["wo"]) == P("model", None)
    assert spec_of(blk.params["w1"]) == P(None, "model")
    assert spec_of(blk.params["w2"]) == P("model", None)
    assert spec_of(blk.params["bq"]) == P("model")
    assert spec_of(blk.params["b1"]) == P("model")
    assert spec_of(blk.params["bo"]) == P()
    assert spec_of(blk.params["ln1_g"]) == P()
    # Embedding: embed dim sharded, vocab gather stays local.
    assert spec_of(wf.embedding.weights) == P(None, "model")
    assert spec_of(wf.embedding.pos) == P(None, "model")
    # Momentum mirrors its parameter by NAME.
    gd = [g for g in wf.gds if g.target is blk][0]
    assert spec_of(gd.tstate["velocity_wq"]) == P(None, "model")
    assert spec_of(gd.tstate["velocity_wo"]) == P("model", None)
    assert spec_of(gd.tstate["velocity_b2"]) == P()


def test_indivisible_heads_stay_replicated():
    """3 heads over a 4-wide model axis: the block must stay fully
    replicated (correct, merely not tensor-parallel) — same contract
    as All2All widths."""
    import jax
    from jax.sharding import PartitionSpec as P
    _, wf = _build_tinylm(max_epochs=1, embed_dim=24, n_heads=3)
    mesh = make_mesh(jax.devices(), {"data": 2, "model": 4})
    apply_dp_tp_sharding(wf, mesh)
    blk = _block_unit(wf)
    assert blk.params["wq"].devmem.sharding.spec == P()


@pytest.mark.parametrize("family", ["dense", "pipelined"])
def test_tp_step_parity_vs_replicated(family, f32_precision):
    """ONE fused training step under dp×tp(2×4) == the same step
    fully replicated, per sharded parameter family — the annotation
    must never change the math, only the layout."""
    import jax
    kwargs = {}
    if family == "pipelined":
        kwargs = {"pipelined": True, "n_blocks": 2,
                  "n_microbatches": 2}
    devices = jax.devices()

    def dp(wf):
        apply_dp_sharding(wf, make_mesh(devices, {"data": 8}))

    def tp(wf):
        apply_dp_tp_sharding(
            wf, make_mesh(devices, {"data": 2, "model": 4}))

    ref = _one_step_params(dp, **kwargs)
    got = _one_step_params(tp, **kwargs)
    assert set(ref) == set(got)
    for name in ref:
        numpy.testing.assert_allclose(
            ref[name], got[name], rtol=2e-4, atol=2e-5,
            err_msg="param %s diverged under tp" % name)


@pytest.mark.parametrize("kind", ["opt-spec", "shortconv", "experts"])
def test_tp_plan_is_by_name(kind, f32_precision):
    """The plan is chosen by the layer's parameter NAMES, not by its
    class: a spec-built layer with the OPT block's leaves gets
    TransformerBlock's specs leaf for leaf; a layer holding a name
    the plan does not know (the short convolution's ``w_in`` /
    ``w_conv``, an expert layer's ``router`` / ``w3``) stays
    replicated whole — and either way ONE dp×tp step is the
    replicated step."""
    import jax
    from jax.sharding import PartitionSpec as P
    from veles_tpu.znicz.attention import layer_spec
    spec = {"opt-spec": layer_spec(n_heads=4),
            "shortconv": layer_spec(norm="rms", operator="shortconv",
                                    ffn="gated-mlp", bias=False),
            "experts": layer_spec(norm="rms", ffn="experts",
                                  n_experts=4, top_k=2, bias=False,
                                  rope_theta=1e4)}[kind]
    devices = jax.devices()
    axes = {"data": 2, "model": 4}
    spec_of = lambda v: v.devmem.sharding.spec  # noqa: E731
    _, wf = _build_tinylm(max_epochs=1, layers=[spec])
    apply_dp_tp_sharding(wf, make_mesh(devices, axes))
    layer = wf.forwards[1]
    if kind == "opt-spec":
        _, opt = _build_tinylm(max_epochs=1)
        apply_dp_tp_sharding(opt, make_mesh(devices, axes))
        blk = _block_unit(opt)
        assert set(layer.params) == set(blk.params)
        for name, vec in blk.params.items():
            assert spec_of(layer.params[name]) == spec_of(vec), name
        assert spec_of(layer.params["wq"]) == P(None, "model")
        assert layer.head_axis == blk.head_axis == "model"
    else:
        assert all(spec_of(v) == P() for v in layer.params.values())
        assert layer.head_axis is None

    def dp(wf):
        apply_dp_sharding(wf, make_mesh(devices, {"data": 8}))

    def tp(wf):
        apply_dp_tp_sharding(wf, make_mesh(devices, axes))

    ref = _one_step_params(dp, layers=[spec])
    got = _one_step_params(tp, layers=[spec])
    assert set(ref) == set(got)
    for name in ref:
        numpy.testing.assert_allclose(
            ref[name], got[name], rtol=2e-4, atol=2e-5,
            err_msg="param %s diverged under tp" % name)


def test_pipelined_stack_tp_shardings():
    """Stage-stacked parameters: leading stage dim untouched (the
    stage axis's business), trailing dims carry the column/row
    pairing."""
    import jax
    from jax.sharding import PartitionSpec as P
    _, wf = _build_tinylm(max_epochs=1, pipelined=True, n_blocks=2,
                          n_microbatches=2)
    mesh = make_mesh(jax.devices(), {"data": 2, "model": 4})
    apply_dp_tp_sharding(wf, mesh)
    stack = wf.forwards[1]
    spec_of = lambda v: v.devmem.sharding.spec  # noqa: E731
    assert spec_of(stack.params["wq"]) == P(None, None, "model")
    assert spec_of(stack.params["wo"]) == P(None, "model", None)
    assert spec_of(stack.params["w1"]) == P(None, None, "model")
    assert spec_of(stack.params["w2"]) == P(None, "model", None)
    assert spec_of(stack.params["bq"]) == P(None, "model")
    assert spec_of(stack.params["ln1_g"]) == P(None)


def test_untied_lmhead_vocab_sharding():
    """A free (untied) LM head vocab-shards its projection — the
    declarative StandardWorkflow path builds one."""
    import jax
    from jax.sharding import PartitionSpec as P
    from veles_tpu.znicz.standard_workflow import StandardWorkflow
    from veles_tpu.znicz.samples.tinylm import FirstTokenLoader
    prng.reset()
    prng.get(0).seed(3)
    launcher = Launcher()
    wf = StandardWorkflow(
        launcher,
        layers=[
            {"type": "embedding",
             "->": {"vocab_size": 16, "embed_dim": 32}},
            {"type": "transformer_block", "->": {"n_heads": 4},
             "<-": {"learning_rate": 0.01, "gradient_moment": 0.9}},
            {"type": "lm_head", "->": {"vocab_size": 16},
             "<-": {"learning_rate": 0.01}},
        ],
        loader_cls=FirstTokenLoader,
        loader_config={"minibatch_size": 64},
        loss_function="lm",
        decision_config={"max_epochs": 2})
    launcher.initialize()
    mesh = make_mesh(jax.devices(), {"data": 2, "model": 4})
    apply_dp_tp_sharding(wf, mesh)
    head = wf.forwards[-1]
    assert head.weights.devmem.sharding.spec == P(None, "model")
    launcher._finished.clear()
    wf.run()
    assert numpy.isfinite(
        wf.gather_results()["min_validation_err"])


def test_tinylm_trains_under_dp_tp():
    """End-to-end: the attention-recall gate holds under the Megatron
    layout (2×4)."""
    import jax
    launcher, wf = _build_tinylm()
    mesh = make_mesh(jax.devices(), {"data": 2, "model": 4})
    apply_dp_tp_sharding(wf, mesh)
    launcher._finished.clear()
    wf.run()
    assert wf.decision.min_validation_err < 0.05


@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
def test_three_axis_dp_tp_sp(sp_mode):
    """The COMPOSED 3-axis layout (data 2 × model 2 × seq 2): weights
    Megatron-sharded, attention sequence-parallel with the head dim
    kept on the model axis inside the shard_map, trained to the
    recall gate."""
    import jax
    from jax.sharding import PartitionSpec as P
    launcher, wf = _build_tinylm(seq_axis="seq", sp_mode=sp_mode)
    mesh = make_mesh(jax.devices(),
                     {"data": 2, "model": 2, "seq": 2})
    apply_dp_tp_sp_sharding(wf, mesh)
    assert wf._parallel_style_ == ("dp_tp_sp", "data", "model", "seq")
    blk = _block_unit(wf)
    assert blk.head_axis == "model"
    assert blk.params["wq"].devmem.sharding.spec == P(None, "model")
    assert blk.params["wo"].devmem.sharding.spec == P("model", None)
    launcher._finished.clear()
    wf.run()
    assert wf.decision.min_validation_err < 0.05


def _rebuild_case(style):
    """(lm kwargs, mesh axes, applier) per parallelism style."""
    from veles_tpu.parallel import (apply_dp_pp_sharding,
                                    apply_dp_sp_sharding)
    return {
        "dp_sp": ({"seq_axis": "seq"}, {"data": 2, "seq": 4},
                  apply_dp_sp_sharding),
        "dp_pp": ({"pipelined": True, "n_blocks": 4,
                   "n_microbatches": 2},
                  {"data": 2, "stage": 4}, apply_dp_pp_sharding),
    }[style]


@pytest.mark.parametrize("style", ["dp_sp", "dp_pp"])
def test_rebuild_preserves_style(style):
    """8→4 chip loss must RE-FORM the sp/pp layout over the
    survivors (pre-round-5 both silently degraded to plain DP;
    only dp_tp was preserved), and training must continue."""
    import jax
    kwargs, axes, applier = _rebuild_case(style)
    launcher, wf = _build_tinylm(max_epochs=2, **kwargs)
    applier(wf, make_mesh(jax.devices(), axes))
    launcher._finished.clear()
    wf.run()
    from veles_tpu.parallel import rebuild_mesh
    rebuild_mesh(wf, jax.devices()[:4])
    assert wf._parallel_style_[0] == style, wf._parallel_style_
    nondata = [a for a in wf.mesh.axis_names if a != "data"][0]
    assert wf.mesh.shape == {"data": 2, nondata: 2}
    wf.decision.max_epochs = 4
    wf.decision.complete <<= False
    wf._finished_.clear()
    wf.run()
    assert wf.gather_results()["epochs"] == 4
    some_param = next(iter(wf.compiler._param_vecs.values()))
    assert len(some_param.devmem.sharding.device_set) == 4


def test_rebuild_preserves_three_axis_style():
    """dp×tp×sp 2×2×2 → 4 survivors: model and seq sizes preserved
    exactly, the data axis absorbs the loss (1×2×2)."""
    import jax
    launcher, wf = _build_tinylm(max_epochs=2, seq_axis="seq")
    apply_dp_tp_sp_sharding(
        wf, make_mesh(jax.devices(),
                      {"data": 2, "model": 2, "seq": 2}))
    launcher._finished.clear()
    wf.run()
    from veles_tpu.parallel import rebuild_mesh
    rebuild_mesh(wf, jax.devices()[:4])
    assert wf._parallel_style_[0] == "dp_tp_sp"
    assert wf.mesh.shape == {"data": 1, "model": 2, "seq": 2}
    wf.decision.max_epochs = 4
    wf.decision.complete <<= False
    wf._finished_.clear()
    wf.run()
    assert wf.gather_results()["epochs"] == 4


def test_rebuild_partial_fit_8_to_6_keeps_two_axis_style():
    """VERDICT item 9: 8→6 on dp×tp×sp 2×2×2 — 6 is not divisible by
    model·seq (4), but a 2-axis style must survive the shrink: the
    ladder keeps tp and drops sp → dp_tp 3×2, never the pure-DP
    cliff."""
    import jax
    launcher, wf = _build_tinylm(max_epochs=2, seq_axis="seq")
    apply_dp_tp_sp_sharding(
        wf, make_mesh(jax.devices(),
                      {"data": 2, "model": 2, "seq": 2}))
    launcher._finished.clear()
    wf.run()
    from veles_tpu.parallel import rebuild_mesh
    rebuild_mesh(wf, jax.devices()[:6])
    assert wf._parallel_style_[0] == "dp_tp", wf._parallel_style_
    assert wf.mesh.shape == {"data": 3, "model": 2}
    wf.decision.max_epochs = 4
    wf.decision.complete <<= False
    wf._finished_.clear()
    wf.run()
    assert wf.gather_results()["epochs"] == 4
    some_param = next(iter(wf.compiler._param_vecs.values()))
    assert len(some_param.devmem.sharding.device_set) == 6


def test_rebuild_growth_widens_data_axis_and_stamps_epoch():
    """Membership GROWTH (ISSUE 16): 4→8 devices re-forms dp×sp with
    the seq axis at its exact old size and the data axis doubled; the
    explicit membership epoch stamps the workflow and the grow
    counter ticks."""
    import jax
    import veles_tpu.resilience as resilience
    from veles_tpu.parallel import apply_dp_sp_sharding, rebuild_mesh
    launcher, wf = _build_tinylm(max_epochs=2, seq_axis="seq")
    apply_dp_sp_sharding(wf, make_mesh(jax.devices()[:4],
                                       {"data": 2, "seq": 2}))
    launcher._finished.clear()
    wf.run()
    before = resilience.stats.snapshot().get("membership.grow", 0)
    rebuild_mesh(wf, jax.devices(), epoch=17)
    assert wf._parallel_style_[0] == "dp_sp"
    assert wf.mesh.shape == {"data": 4, "seq": 2}
    assert wf._membership_epoch_ == 17
    assert resilience.stats.snapshot().get(
        "membership.grow", 0) == before + 1
    wf.decision.max_epochs = 4
    wf.decision.complete <<= False
    wf._finished_.clear()
    wf.run()
    assert wf.gather_results()["epochs"] == 4
    some_param = next(iter(wf.compiler._param_vecs.values()))
    assert len(some_param.devmem.sharding.device_set) == 8


def test_rebuild_falls_back_to_dp_when_indivisible():
    """3 survivors cannot hold any 2-axis style — plain DP with a
    warning, never a crash."""
    import jax
    launcher, wf = _build_tinylm(max_epochs=2, seq_axis="seq")
    from veles_tpu.parallel import apply_dp_sp_sharding, rebuild_mesh
    apply_dp_sp_sharding(wf, make_mesh(jax.devices(),
                                       {"data": 2, "seq": 4}))
    launcher._finished.clear()
    wf.run()
    rebuild_mesh(wf, jax.devices()[:3])
    assert wf._parallel_style_[0] == "dp"
    assert wf.mesh.shape == {"data": 3}


def test_uninitialized_unit_degrades_to_replicated():
    """ADVICE regression: a transformer unit with no linked input
    (or an input whose shape is still None) must degrade to a
    replicated plan (None), not dereference ``unit.input.shape``."""
    from veles_tpu.memory import Vector
    from veles_tpu.parallel.mesh import _transformer_tp_plan
    from veles_tpu.znicz.attention import TransformerBlock
    _, wf = _build_tinylm(max_epochs=1)
    blk = TransformerBlock(wf, n_heads=2, name="orphan")
    assert getattr(blk, "input", None) is None or \
        blk.input.shape is None
    assert _transformer_tp_plan(blk, 4, "model") is None
    blk.input = Vector()  # allocated but shapeless
    assert blk.input.shape is None
    assert _transformer_tp_plan(blk, 4, "model") is None


def test_fused_qkv_tp_shardings():
    """The fused (E, 3E) weight column-shards its 3E dim on the
    model axis (head-major layout → a contiguous column shard is
    whole heads' q/k/v), bqkv follows, and the momentum slot mirrors
    by name."""
    import jax
    from jax.sharding import PartitionSpec as P
    _, wf = _build_tinylm(max_epochs=1, fused_qkv=True)
    mesh = make_mesh(jax.devices(), {"data": 2, "model": 4})
    apply_dp_tp_sharding(wf, mesh)
    blk = _block_unit(wf)
    assert "wqkv" in blk.params and "wq" not in blk.params
    spec_of = lambda v: v.devmem.sharding.spec  # noqa: E731
    assert spec_of(blk.params["wqkv"]) == P(None, "model")
    assert spec_of(blk.params["bqkv"]) == P("model")
    assert spec_of(blk.params["wo"]) == P("model", None)
    gd = [g for g in wf.gds if g.target is blk][0]
    assert spec_of(gd.tstate["velocity_wqkv"]) == P(None, "model")
    assert spec_of(gd.tstate["velocity_bqkv"]) == P("model")


def test_fused_qkv_tp_step_matches_unfused_dp(f32_precision):
    """The fused-QKV TP composition gate: one seeded dp×tp(2×4) step
    with the fused projection == the unfused fully-data-parallel
    step — same loss trajectory, same updated weights (wqkv split
    back per projection)."""
    import jax
    from veles_tpu.znicz.attention import split_qkv_arrays
    devices = jax.devices()

    def dp(wf):
        apply_dp_sharding(wf, make_mesh(devices, {"data": 8}))

    def tp(wf):
        apply_dp_tp_sharding(
            wf, make_mesh(devices, {"data": 2, "model": 4}))

    ref = _one_step_params(dp)

    # The fused workflow must start from the SAME weights: fuse the
    # reference init into wqkv before the step (seeded construction
    # draws different tensors for a (E, 3E) fused weight).
    from tests.test_attention_fastpath import _graft_fused_weights
    _, fused_wf = _build_tinylm(max_epochs=1, fused_qkv=True)
    _, src_wf = _build_tinylm(max_epochs=1)
    blk_dst = _block_unit(fused_wf)
    _graft_fused_weights(src_wf, fused_wf)
    tp(fused_wf)
    fused_wf.loader.serve_next_minibatch()
    fused_wf.begin_tick()
    fused_wf.compiler.execute(key=jax.random.PRNGKey(0),
                              training=True)
    got = {n: numpy.asarray(jax.device_get(v.devmem))
           for n, v in fused_wf.compiler._param_vecs.items()}
    n_heads = blk_dst.n_heads
    for name, want in ref.items():
        if name.endswith(("wq", "wk", "wv", "bq", "bk", "bv")):
            fused_name = name[:-2] + (
                "wqkv" if name[-2] == "w" else "bqkv")
            parts = dict(zip(
                ("q", "k", "v"),
                split_qkv_arrays(got[fused_name], n_heads)))
            have = parts[name[-1]]
        else:
            have = got[name]
        numpy.testing.assert_allclose(
            want, have, rtol=2e-4, atol=2e-5,
            err_msg="param %s diverged under fused dp×tp" % name)


def test_three_axis_step_parity_vs_replicated(f32_precision):
    """One fused step under dp×tp×sp(2×2×2) == the replicated step —
    the ring collectives and head sharding must not change the
    math."""
    import jax
    devices = jax.devices()

    def dp(wf):
        apply_dp_sharding(wf, make_mesh(devices, {"data": 8}))

    def tpsp(wf):
        apply_dp_tp_sp_sharding(
            wf, make_mesh(devices,
                          {"data": 2, "model": 2, "seq": 2}))

    ref = _one_step_params(dp, seq_axis="seq")
    got = _one_step_params(tpsp, seq_axis="seq")
    for name in ref:
        numpy.testing.assert_allclose(
            ref[name], got[name], rtol=2e-4, atol=2e-5,
            err_msg="param %s diverged under tp×sp" % name)
