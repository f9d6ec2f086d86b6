"""Long-context stack tests: attention ops (full / blockwise / ring),
the transformer unit family, and dp × sequence-parallel training.
(The reference has no attention — SURVEY §5 long-context 'ABSENT';
this is the TPU build's first-class extension.)"""

import numpy
import pytest

import veles_tpu.prng as prng
from veles_tpu.launcher import Launcher
from veles_tpu.parallel import make_mesh, apply_dp_sp_sharding


def _qkv(B=2, S=64, H=4, D=16, seed=0):
    rng = numpy.random.RandomState(seed)
    return [rng.normal(0, 1, (B, S, H, D)).astype(numpy.float32)
            for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_full(causal):
    from veles_tpu.ops.attention import attention, \
        blockwise_attention
    q, k, v = _qkv()
    full = attention(q, k, v, causal=causal)
    blk = blockwise_attention(q, k, v, block_size=16, causal=causal)
    numpy.testing.assert_allclose(full, blk, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full(causal):
    """Ring attention over an 8-device seq mesh == full attention."""
    from veles_tpu.ops.attention import attention, \
        sequence_parallel_attention
    q, k, v = _qkv()
    mesh = make_mesh(axes={"seq": 8})
    full = attention(q, k, v, causal=causal)
    ring = sequence_parallel_attention(q, k, v, mesh, "seq",
                                       causal=causal)
    numpy.testing.assert_allclose(full, numpy.asarray(ring),
                                  rtol=2e-5, atol=2e-5)


def test_ring_gradients_match_full():
    """Autodiff through the ppermute ring == full-attention grads —
    the property that makes ring attention trainable, not just
    servable."""
    import jax
    from veles_tpu.ops.attention import attention, \
        sequence_parallel_attention
    q, k, v = _qkv()
    mesh = make_mesh(axes={"seq": 8})

    def loss_full(q, k, v):
        return (attention(q, k, v, causal=True) ** 2).sum()

    def loss_ring(q, k, v):
        return (sequence_parallel_attention(
            q, k, v, mesh, "seq", causal=True) ** 2).sum()

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_full, g_ring):
        numpy.testing.assert_allclose(numpy.asarray(a),
                                      numpy.asarray(b),
                                      rtol=5e-4, atol=5e-5)


def test_fully_masked_rows_are_finite():
    """A row whose every key is masked (ring step where the query
    block is strictly BEFORE the key block) must produce exact zeros,
    not NaN.  Driven through _block_update with an explicit key
    offset — attention() itself always builds its mask with both
    offsets 0, so slicing k can never fully mask a row."""
    import jax.numpy as jnp
    from veles_tpu.ops.attention import (NEG_INF, _block_update,
                                         _causal_mask, _finish)
    q, k, v = _qkv(S=8)
    S = 8
    # Query positions 0..7, key positions S..2S-1: every (q, k) pair
    # violates causality, so the mask is all-False.
    mask = _causal_mask(S, S, 0, S)
    assert not bool(numpy.asarray(mask).any())
    acc = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3], jnp.float32)
    acc, m, l = _block_update(acc, m, l, q, k, v,
                              scale=1.0 / q.shape[-1] ** 0.5,
                              mask=mask)
    out = numpy.asarray(_finish(acc, l, q.dtype))
    assert numpy.isfinite(out).all()
    numpy.testing.assert_array_equal(out, numpy.zeros_like(out))


def _expert_layers():
    """A two-layer spec-built body whose FFNs are dropless expert
    layers (top 2 of 4, all held)."""
    from veles_tpu.znicz.attention import layer_spec
    return [layer_spec(ffn="experts", n_experts=4, top_k=2)
            for _ in range(2)]


def _train_tinylm(**kwargs):
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    kwargs.setdefault("max_epochs", 8)
    prng.reset()
    prng.get(0).seed(3)
    launcher = Launcher()
    wf = TinyLMWorkflow(launcher, **kwargs)
    launcher.initialize()
    return launcher, wf


def test_tinylm_learns_first_token_recall():
    """The causal transformer must learn a task impossible without
    attention (label = first token of the sequence; chance = 1/16)."""
    launcher, wf = _train_tinylm()
    launcher.run()
    assert wf.decision.min_validation_err < 0.05
    # and the task really needs attention: epoch-0 error ~ chance
    assert wf.decision.epoch_number <= 8


def test_tinylm_sequence_parallel_training():
    """dp(2) × sp(4): the same model trains to the same gate with
    ring attention over the mesh's seq axis."""
    launcher, wf = _train_tinylm(seq_axis="seq")
    mesh = make_mesh(axes={"data": 2, "seq": 4})
    apply_dp_sp_sharding(wf, mesh)
    assert wf._parallel_style_[0] == "dp_sp"
    launcher.run()
    assert wf.decision.min_validation_err < 0.05


@pytest.mark.parametrize("variant,kwargs,param,lead", [
    ("dense", {}, "wq", None),
    ("fused", {"fused_qkv": True}, "wqkv", None),
    ("moe", {"layers": "experts"}, "w1", 4),
    ("pipelined", {"pipelined": True, "n_blocks": 4}, "w1", 4),
])
def test_lm_snapshot_roundtrip(variant, kwargs, param, lead):
    """Every transformer variant pickles/resumes like every other
    workflow (params — incl. expert/stage-stacked — ride Vectors;
    the ring/pipeline is rebuilt from config; an expert layer's
    selection bias and accumulator ride too)."""
    import pickle
    if kwargs.get("layers") == "experts":
        kwargs = {"layers": _expert_layers()}
    launcher, wf = _train_tinylm(max_epochs=2, **kwargs)
    if variant == "moe":
        bias = wf.forwards[1].expert_bias
        bias.map_write()
        bias.mem[:] = [0.5, -0.5, 0.25, 0.0]
    launcher.run()
    wf2 = pickle.loads(pickle.dumps(wf))
    if variant == "moe":
        for name in ("expert_bias", "moe_acc"):
            a, b = (getattr(w.forwards[1], name) for w in (wf, wf2))
            a.map_read()
            b.map_read()
            numpy.testing.assert_array_equal(numpy.array(a.mem),
                                             numpy.array(b.mem))
        assert list(wf2.forwards[1].expert_bias.mem[:2]) == [0.5, -0.5]
        assert wf2.forwards[1].spec == wf.forwards[1].spec
    a = wf.forwards[1].params[param]
    a.map_read()
    b = wf2.forwards[1].params[param]
    b.map_read()
    numpy.testing.assert_array_equal(numpy.array(a.mem),
                                     numpy.array(b.mem))
    if lead is not None:
        assert b.shape[0] == lead  # expert/stage stacking survived


# -- pipeline parallelism -----------------------------------------------


def _stack_params(n_stages, E=16, H=2, seed=0):
    from veles_tpu.znicz.attention import TransformerBlock
    rng = numpy.random.RandomState(seed)
    hidden = E * 4
    shapes = {
        "ln1_g": (E,), "ln1_b": (E,), "wq": (E, E), "wk": (E, E),
        "wv": (E, E), "wo": (E, E), "bq": (E,), "bk": (E,),
        "bv": (E,), "bo": (E,), "ln2_g": (E,), "ln2_b": (E,),
        "w1": (E, hidden), "b1": (hidden,), "w2": (hidden, E),
        "b2": (E,),
    }
    params = {}
    for name in TransformerBlock.PARAM_NAMES:
        shape = (n_stages,) + shapes[name]
        if name.endswith("_g"):
            params[name] = numpy.ones(shape, numpy.float32)
        elif name.startswith("w"):
            params[name] = rng.normal(0, 0.1, shape) \
                .astype(numpy.float32)
        else:
            params[name] = numpy.zeros(shape, numpy.float32)
    return params


def test_gpipe_matches_sequential():
    """The collective-permute pipeline over a 4-stage mesh computes
    EXACTLY the sequential composition of the same stacked layers."""
    import jax.numpy as jnp
    from veles_tpu.ops.pipeline import gpipe, sequential_stack
    from veles_tpu.znicz.attention import transformer_block_apply
    params = _stack_params(4)
    x = numpy.random.RandomState(1).normal(
        0, 1, (8, 12, 16)).astype(numpy.float32)

    def fn(p, h):
        return transformer_block_apply(p, h, n_heads=2, causal=True,
                                       cdt=jnp.float32)

    seq = sequential_stack(fn, params, jnp.asarray(x))
    mesh = make_mesh(axes={"stage": 4})
    pipe = gpipe(fn, params, jnp.asarray(x), mesh, "stage",
                 n_microbatches=4)
    numpy.testing.assert_allclose(numpy.asarray(pipe),
                                  numpy.asarray(seq),
                                  rtol=2e-5, atol=2e-5)


def test_gpipe_gradients_match_sequential():
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops.pipeline import gpipe, sequential_stack
    from veles_tpu.znicz.attention import transformer_block_apply
    params = _stack_params(4, seed=2)
    x = numpy.random.RandomState(3).normal(
        0, 1, (4, 8, 16)).astype(numpy.float32)

    def fn(p, h):
        return transformer_block_apply(p, h, n_heads=2, causal=True,
                                       cdt=jnp.float32)

    mesh = make_mesh(axes={"stage": 4})
    g_seq = jax.grad(lambda p: (sequential_stack(
        fn, p, jnp.asarray(x)) ** 2).sum())(params)
    g_pipe = jax.grad(lambda p: (gpipe(
        fn, p, jnp.asarray(x), mesh, "stage", 2) ** 2).sum())(params)
    for name in params:
        numpy.testing.assert_allclose(
            numpy.asarray(g_pipe[name]), numpy.asarray(g_seq[name]),
            rtol=1e-3, atol=1e-4, err_msg=name)


def test_tinylm_pipeline_parallel_training():
    """dp(2) × pp(4): a 4-block pipelined stack trains to the gate."""
    from veles_tpu.parallel import apply_dp_pp_sharding
    launcher, wf = _train_tinylm(n_blocks=4, pipelined=True,
                                 stage_axis="stage",
                                 learning_rate=0.02, max_epochs=10)
    mesh = make_mesh(axes={"data": 2, "stage": 4})
    apply_dp_pp_sharding(wf, mesh)
    assert wf._parallel_style_[0] == "dp_pp"
    stack = wf.forwards[1]
    assert stack.params["wq"].sharding.spec[0] == "stage"
    launcher.run()
    assert wf.decision.min_validation_err < 0.1


def test_gpipe_multiple_blocks_per_stage():
    """n_layers = 2 × stages: each device applies its local sub-stack
    sequentially; result still equals the full sequential stack."""
    import jax.numpy as jnp
    from veles_tpu.ops.pipeline import gpipe, sequential_stack
    from veles_tpu.znicz.attention import transformer_block_apply
    params = _stack_params(8, seed=4)
    x = numpy.random.RandomState(5).normal(
        0, 1, (4, 8, 16)).astype(numpy.float32)

    def fn(p, h):
        return transformer_block_apply(p, h, n_heads=2, causal=True,
                                       cdt=jnp.float32)

    seq = sequential_stack(fn, params, jnp.asarray(x))
    mesh = make_mesh(axes={"stage": 4})
    pipe = gpipe(fn, params, jnp.asarray(x), mesh, "stage",
                 n_microbatches=2)
    numpy.testing.assert_allclose(numpy.asarray(pipe),
                                  numpy.asarray(seq),
                                  rtol=2e-5, atol=2e-5)


def test_pipelined_stack_falls_back_when_indivisible():
    """A 3-block stack on a 4-stage mesh stays sequential (the
    apply_dp_pp_sharding contract) instead of crashing in shard_map."""
    from veles_tpu.parallel import apply_dp_pp_sharding
    launcher, wf = _train_tinylm(n_blocks=3, pipelined=True,
                                 stage_axis="stage",
                                 learning_rate=0.02, max_epochs=2)
    mesh = make_mesh(axes={"data": 2, "stage": 4})
    apply_dp_pp_sharding(wf, mesh)  # warns, leaves replicated
    launcher.run()  # must not raise
    assert wf.decision.epoch_number == 2


def test_tinylm_rejects_pipelined_moe():
    """The pipelined stack holds OPT blocks only: a spec-built body
    (here expert layers) does not go with it."""
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    with pytest.raises(ValueError, match="pipelined"):
        TinyLMWorkflow(Launcher(), pipelined=True,
                       layers=_expert_layers())


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    """All-to-all (Ulysses) sequence parallelism == full attention —
    the second sp strategy (two collectives vs the ring's N steps)."""
    from veles_tpu.ops.attention import attention, \
        sequence_parallel_attention
    q, k, v = _qkv(H=8)
    mesh = make_mesh(axes={"seq": 8})
    full = attention(q, k, v, causal=causal)
    uly = sequence_parallel_attention(q, k, v, mesh, "seq",
                                      causal=causal, mode="ulysses")
    numpy.testing.assert_allclose(full, numpy.asarray(uly),
                                  rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S", [1536, 1200])
def test_ulysses_long_gathered_sequences(causal, S):
    """The gathered local attention must handle ANY long S — 1536
    (streams at a dividing block size) and 1200 (divides by nothing
    in the block ladder: pads to a block multiple with masked keys).
    Pre-round-5 both fell back to dense O(S²) scores (the shape
    cliff: `S > 1024 and S % 512 == 0` was the only streamed case)."""
    from veles_tpu.ops.attention import attention, \
        sequence_parallel_attention
    q, k, v = _qkv(B=1, S=S, H=8, D=8)
    mesh = make_mesh(axes={"seq": 8})
    full = attention(q, k, v, causal=causal)
    uly = sequence_parallel_attention(q, k, v, mesh, "seq",
                                      causal=causal, mode="ulysses")
    numpy.testing.assert_allclose(full, numpy.asarray(uly),
                                  rtol=2e-5, atol=3e-5)


def test_gathered_attention_never_dense_past_threshold(monkeypatch):
    """Above ULYSSES_DENSE_MAX the dense path must not run at all —
    guard the streaming guarantee itself, not just numerics."""
    import jax.numpy as jnp
    from veles_tpu.ops import attention as A

    def boom(*a, **kw):
        raise AssertionError("dense attention called for long S")

    monkeypatch.setattr(A, "attention", boom)
    for S in (1088, 1200, 1536):
        q = jnp.zeros((1, S, 2, 4))
        out = A._gathered_attention(q, q, q, causal=True)
        assert out.shape == q.shape
    # ...and at/below the threshold dense is still the choice.
    q = jnp.zeros((1, A.ULYSSES_DENSE_MAX, 2, 4))
    with pytest.raises(AssertionError, match="dense"):
        A._gathered_attention(q, q, q, causal=True)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_kv_len_masks_padding(causal):
    """kv_len must make padded keys invisible: padded blockwise ==
    dense over the unpadded operands (the non-causal case is the
    dangerous one — zero-padding is attendable without the mask)."""
    from veles_tpu.ops.attention import attention, blockwise_attention
    q, k, v = _qkv(B=1, S=48, H=2, D=8)
    pad = 16
    qp, kp, vp = [numpy.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                  for x in (q, k, v)]
    ref = attention(q, k, v, causal=causal)
    got = blockwise_attention(qp, kp, vp, block_size=16,
                              causal=causal, kv_len=48)
    numpy.testing.assert_allclose(ref, numpy.asarray(got)[:, :48],
                                  rtol=2e-5, atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    import jax.numpy as jnp
    from veles_tpu.ops.attention import sequence_parallel_attention
    q, k, v = _qkv(H=4)  # 4 heads over 8 devices
    mesh = make_mesh(axes={"seq": 8})
    with pytest.raises(ValueError, match="divisible"):
        sequence_parallel_attention(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), mesh, "seq",
                                    mode="ulysses")


def test_tinylm_ulysses_training():
    """dp(2) × sp(4) with the Ulysses strategy trains to the gate."""
    launcher, wf = _train_tinylm(seq_axis="seq", sp_mode="ulysses")
    mesh = make_mesh(axes={"data": 2, "seq": 4})
    apply_dp_sp_sharding(wf, mesh)
    launcher.run()
    assert wf.decision.min_validation_err < 0.05


def test_standard_workflow_builds_transformer_lm():
    """The declarative builder assembles a transformer LM from layer
    configs alone (registry types + loss_function='lm') and trains
    it to the recall gate."""
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
             "<-": {"learning_rate": 0.01, "gradient_moment": 0.9}},
        ],
        loader_cls=FirstTokenLoader,
        loader_config={"minibatch_size": 64},
        loss_function="lm",
        decision_config={"max_epochs": 8})
    launcher.initialize()
    launcher.run()
    assert wf.decision.min_validation_err < 0.05


def test_ring_long_sequence_smoke():
    """S=1024 over 8 devices: each shard holds 128 positions; the
    ring must produce finite, parity-correct output at a length where
    full attention's score matrix is 8x the per-device shard's."""
    from veles_tpu.ops.attention import attention, \
        sequence_parallel_attention
    q, k, v = _qkv(B=1, S=1024, H=2, D=8)
    mesh = make_mesh(axes={"seq": 8})
    ring = numpy.asarray(sequence_parallel_attention(
        q, k, v, mesh, "seq", causal=True))
    assert numpy.isfinite(ring).all()
    full = numpy.asarray(attention(q, k, v, causal=True))
    numpy.testing.assert_allclose(ring, full, rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("variant", ["moe", "pipelined"])
def test_lm_variant_snapshot_roundtrip(variant):
    """MoE and pipelined LM variants pickle/resume like every other
    workflow (expert/stage-stacked params ride Vectors)."""
    import pickle
    kwargs = {"layers": _expert_layers()} if variant == "moe" else \
        {"pipelined": True, "n_blocks": 4}
    launcher, wf = _train_tinylm(max_epochs=2, **kwargs)
    launcher.run()
    wf2 = pickle.loads(pickle.dumps(wf))
    if variant == "moe":
        layer = wf2.forwards[1]
        assert layer.has_experts and layer.expert_bias.shape == (4,)
        # made, landed, ticks + the load of each of the 4 held
        assert layer.moe_acc.shape == (3, 7)
    name = "w1"
    a = wf.forwards[1].params[name]
    a.map_read()
    b = wf2.forwards[1].params[name]
    b.map_read()
    numpy.testing.assert_array_equal(numpy.array(a.mem),
                                     numpy.array(b.mem))
    assert b.shape[0] == 4  # expert/stage stacking survived


def test_vmapped_ga_composes_with_transformer(tmp_path,
                                               monkeypatch):
    """The vmapped genetics path trains a whole LM population in one
    compiled program (EvaluatorLM's epoch accumulators feed fitness
    exactly like the conv/FC evaluators)."""
    import json
    import os
    from veles_tpu.__main__ import Main
    import veles_tpu.genetics.optimizer as optimizer_mod
    from veles_tpu.genetics.vmap_eval import PopulationEvaluator
    engaged = []

    class Recording(PopulationEvaluator):
        def evaluate(self, genes, epochs=None):
            engaged.append(len(genes))
            return super(Recording, self).evaluate(genes, epochs)

    # _make_vmap_evaluator silently falls back on Bug — the test must
    # fail if the vmapped path stops engaging for transformer models.
    monkeypatch.setattr(optimizer_mod, "PopulationEvaluator",
                        Recording, raising=False)
    import veles_tpu.genetics.vmap_eval as vmap_mod
    monkeypatch.setattr(vmap_mod, "PopulationEvaluator", Recording)
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = tmp_path / "ga.json"
    prng.reset()
    rc = Main([os.path.join(REPO, "veles_tpu", "znicz", "samples",
                            "tinylm.py"),
               "root.tinylm.max_epochs=4",
               "root.tinylm.learning_rate=Tune(0.001, 0.0005, 0.1)",
               "--optimize", "4:2",
               "--result-file", str(result),
               "--random-seed", "11", "-v", "warning"]).run()
    assert rc == 0
    data = json.loads(result.read_text())
    assert data["generations"] == 2
    assert engaged and sum(engaged) >= 4  # vmapped path really ran
    # GA must find an lr that learns recall within 4 epochs.
    assert data["best_fitness"] > 0.8


def test_lm_elastic_rebuild_on_chip_loss():
    """Chip loss mid-LM-training: rebuild_mesh re-places the
    transformer's params over the survivors, requeues in-flight work,
    and training continues to the recall gate (the dp elastic story
    extends to the attention family unchanged)."""
    import jax
    from veles_tpu.parallel import (apply_dp_sharding, make_mesh,
                                    rebuild_mesh)
    launcher, wf = _train_tinylm(max_epochs=3, minibatch_size=64)
    mesh = make_mesh(jax.devices(), {"data": 8})
    apply_dp_sharding(wf, mesh)
    launcher._finished.clear()
    wf.run()
    mid_err = wf.decision.min_validation_err

    survivors = jax.devices()[:4]
    rebuild_mesh(wf, survivors)
    wf.decision.max_epochs = 8
    wf.decision.complete <<= False
    wf._finished_.clear()
    wf.run()
    assert wf.decision.min_validation_err <= mid_err + 1e-9
    assert wf.decision.min_validation_err < 0.05
    some_param = wf.forwards[1].params["wq"]
    assert len(some_param.devmem.sharding.device_set) == 4


def test_gpipe_single_stage_degenerates_to_plain_apply():
    """A 1-stage 'pipeline' must equal direct application (the ramp
    logic has no off-by-one at the degenerate boundary)."""
    import jax.numpy as jnp
    from veles_tpu.ops.pipeline import gpipe, sequential_stack
    from veles_tpu.znicz.attention import transformer_block_apply
    params = _stack_params(1, seed=9)
    x = numpy.random.RandomState(9).normal(
        0, 1, (4, 8, 16)).astype(numpy.float32)

    def fn(p, h):
        return transformer_block_apply(p, h, n_heads=2, causal=True,
                                       cdt=jnp.float32)

    mesh = make_mesh(axes={"stage": 1})
    pipe = gpipe(fn, params, jnp.asarray(x), mesh, "stage",
                 n_microbatches=4)
    seq = sequential_stack(fn, params, jnp.asarray(x))
    numpy.testing.assert_allclose(numpy.asarray(pipe),
                                  numpy.asarray(seq),
                                  rtol=2e-5, atol=2e-5)


def test_gpipe_rejects_bad_geometry():
    import jax.numpy as jnp
    from veles_tpu.ops.pipeline import gpipe
    params = _stack_params(3)
    x = jnp.zeros((4, 8, 16), jnp.float32)
    mesh = make_mesh(axes={"stage": 4})
    with pytest.raises(ValueError, match="stages"):
        gpipe(lambda p, h: h, params, x, mesh, "stage", 2)
    params4 = _stack_params(4)
    with pytest.raises(ValueError, match="microbatches"):
        gpipe(lambda p, h: h, params4, jnp.zeros((5, 8, 16)),
              mesh, "stage", 2)
