"""The spec-driven LM layer (``znicz.attention.layer_apply`` /
``LMLayer``), its ops (``ops/shortconv.py``, ``ops/rotary.py``,
grouped-query ``ops.attention.attention``, ``ops.moe.moe_dropless``)
and the ``samples/lfm2.py`` workflow, at a small size on the CPU,
seeded random weights, against the benchmark's plain reference
(``benchmark/models/lfm2_moe.py``, which imports nothing of the
program)."""

import functools

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.models import lfm2_moe as REF
from veles_tpu.ops import attention as A
from veles_tpu.ops import moe as M
from veles_tpu.ops.rotary import rms_norm, rotary
from veles_tpu.ops.shortconv import causal_depthwise_conv
from veles_tpu.znicz import attention as Z
from veles_tpu.znicz.samples.lfm2 import lfm2_layers

HIGHEST = jax.lax.Precision.HIGHEST


def small_sizes(layer_types=("conv", "full_attention", "conv",
                             "full_attention"), dense_layers=2,
                held=2, experts=8, top_k=2):
    """A family ``sz`` at test size: both operator kinds under both
    FFN kinds."""
    types = tuple(layer_types)
    return {"hidden": 64, "heads": 4, "kv_heads": 2, "dense_ffn": 160,
            "expert_ffn": 48, "experts": experts, "held": held,
            "top_k": top_k, "vocab": 128, "dense_layers": dense_layers,
            "layer_types": types, "conv_kernel": 3, "rope_theta": 1e6,
            "norm_eps": 1e-5, "norm_topk": True, "scaling": 1.0,
            "bias_std": 0.002, "blocks": types.count("full_attention")}


def specs_of(sz):
    return lfm2_layers(
        sz["layer_types"], n_heads=sz["heads"], kv_heads=sz["kv_heads"],
        intermediate_size=sz["dense_ffn"],
        moe_intermediate_size=sz["expert_ffn"], n_experts=sz["experts"],
        top_k=sz["top_k"], num_dense_layers=sz["dense_layers"],
        held=(0, sz["held"]))


def reference_layer(sz, i, p, bias, x):
    """The reference's layer ``i`` over a batch, a sequence at a time;
    (output, assignments landed)."""
    dot = REF._dot(None)
    out = [REF._layer(p, bias, x[b], sz, i, dot, 1, None)
           for b in range(x.shape[0])]
    return jnp.stack([o[0] for o in out]), sum(o[1] for o in out)


# layer index -> (operator, ffn) under small_sizes()
@pytest.mark.parametrize("layer,kinds", [
    (0, "shortconv + gated-mlp"), (1, "attention + gated-mlp"),
    (2, "shortconv + experts"), (3, "attention + experts")])
def test_layer_matches_reference_forward_and_gradient(layer, kinds):
    with jax.default_matmul_precision("highest"):
        sz = small_sizes()
        spec = specs_of(sz)[layer]
        assert kinds == "%s + %s" % (spec["operator"], spec["ffn"])
        tree = REF.init_params(11, sz)
        p = REF._block_leaves(tree, layer)
        bias = p.pop("expert_bias", None)
        assert set(p) == set(Z.layer_param_shapes(spec, sz["hidden"]))
        x = jax.random.normal(jax.random.PRNGKey(layer), (2, 32, 64))
        weight = jax.random.normal(jax.random.PRNGKey(7), x.shape)

        def program(p, x):
            y, stats = Z.layer_apply(spec, p, x, jnp.float32,
                                     buffers={"expert_bias": bias})
            return (y * weight).sum(), (y, stats)

        def reference(p, x):
            y, landed = reference_layer(sz, layer, p, bias, x)
            return (y * weight).sum(), (y, landed)

        (_, (y, stats)), grads = jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True)(p, x)
        (_, (want, landed)), want_grads = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True)(p, x)
        numpy.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
        for got, ref in zip(jax.tree_util.tree_leaves(grads),
                            jax.tree_util.tree_leaves(want_grads)):
            numpy.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)
        if spec["ffn"] == "experts":
            assert float(stats["landed"]) == float(landed) > 0
            assert float(stats["made"]) == 2 * 32 * sz["top_k"]
        else:
            assert stats is None


def test_short_convolution_is_causal_and_depthwise():
    rng = numpy.random.RandomState(3)
    x = rng.randn(2, 9, 5).astype(numpy.float32)
    w = rng.randn(5, 3).astype(numpy.float32)
    want = numpy.zeros_like(x)
    for t in range(9):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += w[:, j] * x[:, t - 2 + j]
    numpy.testing.assert_allclose(causal_depthwise_conv(x, w), want,
                                  rtol=1e-6, atol=1e-6)


def test_rotary_and_rms_norm_match_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 3, 8))
    gain = jax.random.normal(jax.random.PRNGKey(1), (8,))
    numpy.testing.assert_allclose(
        rotary(x, 1e6)[0], REF._rope(x[0], 1e6), rtol=1e-6, atol=1e-6)
    numpy.testing.assert_allclose(
        rms_norm(x, gain, 1e-5), REF._rms_norm(x, gain, 1e-5),
        rtol=1e-6, atol=1e-6)
    # a rotation: norms are kept, position 0 is left alone
    numpy.testing.assert_allclose(
        jnp.linalg.norm(rotary(x, 1e4), axis=-1),
        jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    numpy.testing.assert_allclose(rotary(x, 1e4)[:, 0], x[:, 0])


def test_grouped_query_attention_repeats_kv_heads():
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (2, 16, 8, 4))
    k = jax.random.normal(keys[1], (2, 16, 2, 4))
    v = jax.random.normal(keys[2], (2, 16, 2, 4))
    got = A.attention(q, k, v, causal=True)
    wide = A.attention(q, jnp.repeat(k, 4, axis=2),
                       jnp.repeat(v, 4, axis=2), causal=True)
    numpy.testing.assert_array_equal(got, wide)
    dot = functools.partial(jnp.matmul, precision=HIGHEST)
    want = jnp.stack([REF._attention(
        q[b], jnp.repeat(k[b], 4, axis=1), jnp.repeat(v[b], 4, axis=1),
        dot, 1) for b in range(2)])
    numpy.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # autodiff sums dk over the group
    dk = jax.grad(lambda k: A.attention(q, k, v, causal=True).sum())(k)
    dk_wide = jax.grad(lambda kw: A.attention(
        q, kw, jnp.repeat(v, 4, axis=2), causal=True).sum())(
            jnp.repeat(k, 4, axis=2))
    numpy.testing.assert_allclose(
        dk, dk_wide.reshape(2, 16, 2, 4, 4).sum(axis=3), rtol=1e-5,
        atol=1e-5)
    with pytest.raises(ValueError):
        A.attention(q, k[:, :, :1].repeat(3, axis=2), v, causal=True)


# -- the expert layer --------------------------------------------------------

def expert_layer(T=1024, E=8, seed=0):
    """(sz, params of an UNCUT layer of E experts, bias, tokens)."""
    sz = small_sizes(layer_types=("conv",), dense_layers=0, held=E,
                     experts=E)
    p = REF._block_leaves(REF.init_params(seed, sz), 0)
    bias = p.pop("expert_bias")
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, 64))
    return sz, p, bias, x


def share(p, bias, x, sz, first, count):
    return M.moe_dropless(
        x, p["router"], bias, p["w1"][first:first + count],
        p["w3"][first:first + count], p["w2"][first:first + count],
        top_k=sz["top_k"], held=(first, count), cdt=jnp.float32)


def reference_share(p, bias, x, sz, first, count):
    held = {k: p[k][first:first + count] for k in ("w1", "w3", "w2")}
    held["router"] = p["router"]
    return REF.expert_ffn(held, bias, x, sz, REF._dot(None),
                          first=first, held=count)


def test_the_shares_add_up_to_the_uncut_layer():
    with jax.default_matmul_precision("highest"):
        sz, p, bias, x = expert_layer()
        whole, made = REF.expert_ffn(p, bias, x, sz, REF._dot(None))
        assert float(made) == 1024 * sz["top_k"]    # uncut: all land
        parts = [share(p, bias, x, sz, first, 2) for first in
                 (0, 2, 4, 6)]
        total = sum(y for y, _ in parts)
        numpy.testing.assert_allclose(total, whole, rtol=2e-5,
                                      atol=2e-5)
        assert sum(float(s["landed"]) for _, s in parts) == float(made)
        # one share alone is not the layer
        assert float(jnp.abs(parts[0][0] - whole).max()) > 0.1
        # and each share is the reference's share
        for first, (y, stats) in zip((0, 2, 4, 6), parts):
            want, landed = reference_share(p, bias, x, sz, first, 2)
            numpy.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
            assert float(stats["landed"]) == float(landed)


@pytest.mark.parametrize("routing,landed_share", [
    ("even", None), ("all to held", 1.0), ("all to absent", 0.0),
    ("all to one expert", None)])
def test_no_routing_drops_an_assignment(routing, landed_share):
    """The common path is compiled for 1.25 × the even share; a
    routing that lands more takes the walk over every chunk, and all
    of them give the reference's result with its exact count."""
    with jax.default_matmul_precision("highest"):
        sz, p, bias, x = expert_layer()
        first, count = 2, 2
        chunk, n_chunks = M.dropless_rows(1024, sz["top_k"], 8, count)
        assert (chunk, n_chunks) == (1024, 2)
        bias = {"even": bias,
                "all to held": bias.at[2:4].add(10.0),
                "all to absent": bias.at[2:4].add(-10.0),
                "all to one expert": bias.at[3].add(10.0)}[routing]
        y, stats = share(p, bias, x, sz, first, count)
        want, landed = reference_share(p, bias, x, sz, first, count)
        numpy.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
        assert float(stats["landed"]) == float(landed)
        assert float(stats["load"].sum()) == float(landed)
        if landed_share is not None:
            assert float(landed) == landed_share * 1024 * sz["top_k"]
        if routing == "all to one expert":
            assert float(stats["load"][1]) == 1024 > chunk / 2
        if routing == "even":
            assert float(landed) <= chunk     # the common path
        elif routing != "all to absent":
            assert float(landed) > chunk      # the walk
        # gradients through either path
        weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)
        got = jax.grad(lambda x, p: (share(p, bias, x, sz, first,
                                           count)[0] * weight).sum(),
                       argnums=(0, 1))(x, p)
        ref = jax.grad(lambda x, p: (reference_share(
            p, bias, x, sz, first, count)[0] * weight).sum(),
            argnums=(0, 1))(x, p)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            numpy.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)


def test_choice_follows_score_plus_bias_and_weights_the_score():
    sz, p, bias, x = expert_layer(T=64)
    bias = jnp.zeros_like(bias).at[5].set(10.0)
    idx, weights = M.sigmoid_route(x, p["router"], bias, 2)
    scores = jax.nn.sigmoid(jnp.dot(x, p["router"], precision=HIGHEST))
    assert bool((idx == 5).any(axis=-1).all())     # the bias chooses
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    numpy.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)                                 # the score weighs
    # no gradient reaches the bias
    g = jax.grad(lambda b: M.sigmoid_route(
        x, p["router"], b, 2)[1].sum())(bias)
    assert float(jnp.abs(g).max()) == 0.0
    # without the bias expert 5 is chosen where its score says so
    idx0, _ = M.sigmoid_route(x, p["router"], jnp.zeros_like(bias), 2)
    assert not bool((idx0 == 5).any(axis=-1).all())


def test_grouped_dot_kernel_matches_ragged_dot():
    """The megablox kernel the TPU path selects, run by the Pallas
    interpreter, forward and both gradients, with an empty group and
    rows past the groups' end."""
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    lhs = jax.random.normal(keys[0], (256, 128)).astype(jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (3, 128, 256)).astype(jnp.bfloat16)
    sizes = jnp.array([100, 0, 77], jnp.int32)
    valid = (jnp.arange(256) < 177)[:, None]

    def run(interpret):
        def f(lhs, rhs):
            out = M.grouped_dot(lhs, rhs, sizes, interpret=interpret)
            return jnp.where(valid, out, 0)
        out, vjp = jax.vjp(f, lhs, rhs)
        return (out,) + vjp(jnp.ones_like(out))

    for got, want in zip(run(True), run(False)):
        numpy.testing.assert_allclose(
            got.astype(jnp.float32)[:177] if got.shape[0] == 256
            else got.astype(jnp.float32),
            want.astype(jnp.float32)[:177] if want.shape[0] == 256
            else want.astype(jnp.float32), rtol=2e-2, atol=2e-1)


def test_layer_spec_refuses_what_it_does_not_know():
    with pytest.raises(ValueError):
        Z.layer_spec(norm="batch")
    with pytest.raises(ValueError):
        Z.layer_spec(n_heads=6, kv_heads=4)
    with pytest.raises(ValueError):
        Z.layer_spec(ffn="experts", n_experts=8, top_k=2, held=(7, 2))
    opt = Z.layer_spec(n_heads=8)
    assert (opt["norm"], opt["operator"], opt["ffn"], opt["kv_heads"],
            opt["bias"]) == ("layer", "attention", "relu-mlp", 8, True)


# -- the workflow ------------------------------------------------------------

TRAFFIC = {"batch": 2, "seq": 32, "ticks": 2, "remat": True,
           "learning_rate": 1e-4, "momentum": 0.9}


@pytest.fixture
def trainer():
    sz = small_sizes(layer_types=("conv", "full_attention", "conv",
                                  "conv", "full_attention"),
                     dense_layers=1)
    t = REF.build_trainer(sz, TRAFFIC, 4242, 16, "cpu")
    yield sz, t
    if t.launcher is not None:
        t.launcher.stop()


def test_first_dispatch_through_the_step_compiler(trainer, capsys):
    """Loss, momentum norms, parameter change and the landed count of
    the sample workflow's first dispatch (``Launcher`` → workflow →
    ``StepCompiler``, per-layer remat, block mode) against the
    reference's first ticks."""
    from benchmark import checks
    from benchmark.drivers.train_block import first_dispatch
    sz, t = trainer
    names = [u.name for u in t.wf.forwards]
    assert names == ["embedding"] + ["block%d" % i for i in range(5)] + \
        ["final_norm", "head"]
    assert not t.wf.embedding.pos          # no learned positions
    program, _seconds = first_dispatch(t)
    counted = t.assignments()
    reference = REF.reference_train(4242, sz, TRAFFIC, 2)
    limits = {"loss_gap": 1e-3, "velocity_gap": 0.1, "change_gap": 0.1,
              "direction_gap": 0.12}
    compared = checks.train_checks(program, reference, limits)
    assert all(c["ok"] for c in compared), compared
    # bfloat16 operands upstream of the router flip a near-tie or two
    assert counted["assignments_landed"] == pytest.approx(
        sum(reference["landed"]), rel=0.01)
    assert counted["assignments_made"] == 2 * 2 * 32 * 2 * 4
    assert counted["ticks"] == 2
    assert 0.5 <= counted["max_load_frac"] <= 1.0
    capsys.readouterr()


def test_decision_publishes_the_share_counters():
    """A whole run through the launcher: at the end of an epoch
    ``DecisionGD`` folds the layers' accumulators into the gauges and
    empties them."""
    from veles_tpu.launcher import Launcher
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.observability import attribution, metrics
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    import veles_tpu.prng as prng
    attribution.reset()
    prng.reset()
    prng.get(0).seed(5)
    sz = small_sizes()
    launcher = Launcher()
    wf = TinyLMWorkflow(
        launcher, vocab_size=16, seq_len=32, embed_dim=64,
        layers=specs_of(sz), minibatch_size=64, max_epochs=1)
    launcher.initialize()
    launcher.run()
    made = metrics.registry.peek("moe.assignments_made").value
    landed = metrics.registry.peek("moe.assignments_landed").value
    frac = metrics.registry.peek("moe.max_load_frac").value
    # two expert layers, 64 sequences of 32 tokens, top 2, a tick
    assert made == 2 * 64 * 32 * 2
    assert 0 < landed < made and 0.5 <= frac <= 1.0
    assert wf.decision.epoch_moe[TRAIN]["assignments_landed"] == landed
    layer = [u for u in wf.forwards if getattr(u, "has_experts", 0)][0]
    assert float(layer.read_moe_share(TRAIN).sum()) == 0.0
    launcher.stop()
    attribution.reset()


def test_export_refuses_the_new_kinds_by_name(trainer, tmp_path):
    from veles_tpu.error import Bug
    from veles_tpu.export import export_workflow
    _sz, t = trainer
    with pytest.raises(Bug, match="lm_layer units train but are not "
                                  "served yet"):
        export_workflow(t.wf, str(tmp_path / "model.veles.tgz"))


# -- one unit: TransformerBlock is LMLayer at the OPT spec; placement --------

def _bare_unit(cls, mesh=None, shape=(2, 16, 32), **kwargs):
    """A decoder-layer unit on a workflow of its own (whose ``mesh``
    is the one given), initialised over an input of ``shape``."""
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.memory import Vector
    import veles_tpu.prng as prng
    prng.reset()
    prng.get(0).seed(11)
    wf = DummyWorkflow()
    if mesh is not None:
        wf.mesh = mesh
    unit = cls(wf, **kwargs)
    unit.input = Vector(numpy.zeros(shape, numpy.float32))
    unit.initialize()
    return unit


def _unit_fn(unit):
    """``(params, x) -> y``: the unit's ``tforward`` as a pure
    function of its trainables and its input."""
    def fn(params, x):
        out = []
        unit.tforward(lambda vec: x, lambda vec, val: out.append(val),
                      params, None)
        return out[0]
    return fn


def _unit_params(unit):
    return {n: jnp.asarray(v.mem) for n, v in unit.trainables.items()}


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("remat", [False, True])
def test_transformer_block_is_lm_layer_at_the_opt_spec(remat, fused):
    """``TransformerBlock(n_heads=…)`` and ``LMLayer(spec=layer_spec(
    n_heads=…))`` are one unit: the same leaves from the same seed,
    and forward + gradient lower to the same text, checkpointed or
    not, with three projections or the fused one."""
    kwargs = {"remat": remat, "fused_qkv": fused}
    block = _bare_unit(Z.TransformerBlock, n_heads=4, **kwargs)
    layer = _bare_unit(Z.LMLayer, spec=Z.layer_spec(n_heads=4), **kwargs)
    assert type(block).tforward is Z.LMLayer.tforward
    assert type(block).initialize is Z.LMLayer.initialize
    assert block.fused_qkv is layer.fused_qkv is fused
    assert ("wqkv" in block.params) is fused
    if not fused:
        assert tuple(block.params) == Z.TransformerBlock.PARAM_NAMES
    pb, pl = _unit_params(block), _unit_params(layer)
    assert set(pb) == set(pl)
    for name in pb:
        numpy.testing.assert_array_equal(pb[name], pl[name])
    x = jnp.zeros((2, 16, 32), jnp.float32)

    def text(unit, params):
        fn = _unit_fn(unit)

        def both(p, x):
            return fn(p, x), jax.grad(lambda p: fn(p, x).sum())(p)
        return jax.jit(both).lower(params, x).as_text()

    assert text(block, pb) == text(layer, pl)


GROUPED = dict(norm="rms", n_heads=4, kv_heads=2, qk_norm=True,
               rope_theta=1e4, bias=False, ffn="gated-mlp", ffn_dim=96)


@pytest.mark.parametrize("kind", ["opt", "grouped-rotary-gated"])
def test_spec_built_body_under_a_data_mesh_is_the_replicated_step(
        kind, f32_precision):
    """One fused step of a spec-built body under ``apply_dp_sharding``
    on the 8-device CPU mesh (``LMLayer._attend`` goes through
    ``mesh_attention`` there) equals the step with no mesh."""
    from veles_tpu.launcher import Launcher
    from veles_tpu.parallel import apply_dp_sharding, make_mesh
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    import veles_tpu.prng as prng
    spec = Z.layer_spec(n_heads=4) if kind == "opt" \
        else Z.layer_spec(**GROUPED)

    def one_step(shard):
        prng.reset()
        prng.get(0).seed(42)
        launcher = Launcher()
        wf = TinyLMWorkflow(launcher, layers=[spec, spec],
                            minibatch_size=32, max_epochs=1)
        launcher.initialize()
        if shard:
            apply_dp_sharding(wf, make_mesh(jax.devices(), {"data": 8}))
            assert wf.forwards[1].workflow.mesh is not None
        wf.loader.serve_next_minibatch()
        wf.begin_tick()
        wf.compiler.execute(key=jax.random.PRNGKey(0), training=True)
        out = {n: numpy.asarray(jax.device_get(v.devmem))
               for n, v in wf.compiler._param_vecs.items()}
        launcher.stop()
        return out

    ref, got = one_step(False), one_step(True)
    assert set(ref) == set(got)
    for name in ref:
        numpy.testing.assert_allclose(
            ref[name], got[name], rtol=2e-4, atol=2e-5,
            err_msg="param %s diverged under the data mesh" % name)


@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
def test_rotary_layer_sequence_parallel_matches_one_device(
        sp_mode, f32_precision):
    """An ``LMLayer`` of RMS norms, rotary positions (each token's
    own, whichever sequence shard holds it) and a gated MLP with
    ``seq_axis`` on a dp × sp mesh: forward and gradients are the
    single-device layer's."""
    from veles_tpu.parallel import make_mesh
    spec = Z.layer_spec(**dict(GROUPED, kv_heads=4))
    mesh = make_mesh(jax.devices(), {"data": 2, "seq": 4})
    kwargs = {"spec": spec, "shape": (4, 32, 32)}
    one = _bare_unit(Z.LMLayer, **kwargs)
    sp = _bare_unit(Z.LMLayer, mesh=mesh, seq_axis="seq",
                    sp_mode=sp_mode, sp_kernel="xla", **kwargs)
    params = _unit_params(one)
    x = jnp.asarray(numpy.random.RandomState(3).normal(
        0, 1, (4, 32, 32)).astype(numpy.float32))

    def run(unit):
        fn = _unit_fn(unit)
        return jax.jit(lambda p, x: (
            fn(p, x), jax.grad(lambda p: (fn(p, x) ** 2).sum())(p)))(
                params, x)

    (y1, g1), (y2, g2) = run(one), run(sp)
    numpy.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    for name in g1:
        numpy.testing.assert_allclose(
            g1[name], g2[name], rtol=2e-3, atol=2e-3, err_msg=name)
    # ...and the positions matter: without them the output differs
    flat = _bare_unit(Z.LMLayer, spec=dict(spec, rope_theta=None),
                      shape=(4, 32, 32))
    assert float(jnp.abs(_unit_fn(flat)(params, x) - y1).max()) > 1e-2


def test_grouped_heads_refuse_a_sequence_axis():
    """The ring does not broadcast key/value groups: said at
    construction, not inside a shard_map three layers down."""
    from veles_tpu.dummy import DummyWorkflow
    with pytest.raises(ValueError, match="key/value heads"):
        Z.LMLayer(DummyWorkflow(), spec=Z.layer_spec(**GROUPED),
                  seq_axis="seq")
    # the same spec without the axis is fine, and is never fused
    layer = Z.LMLayer(DummyWorkflow(), spec=Z.layer_spec(**GROUPED),
                      fused_qkv=True)
    assert not layer.fused_qkv and "wk" in layer.params


@pytest.mark.parametrize("cls,what", [
    ("TransformerBlock", {"n_heads": 4}),
    ("LMLayer", {"spec": {"n_heads": 4, "norm": "rms"}})])
def test_unknown_sp_mode_is_refused_at_construction(cls, what):
    """One unit takes the placement arguments, so one place checks
    them: a sequence-parallel mode the ops do not know is a
    ``ValueError`` from either name (a spec-built layer used to take
    no ``sp_mode`` at all and ignore the word)."""
    from veles_tpu.dummy import DummyWorkflow
    with pytest.raises(ValueError, match="sp_mode"):
        getattr(Z, cls)(DummyWorkflow(), seq_axis="seq",
                        sp_mode="spiral", **what)


# -- the accumulator's rows --------------------------------------------------

def _expert_workflow():
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    import veles_tpu.prng as prng
    prng.reset()
    prng.get(0).seed(3)
    launcher = Launcher()
    wf = TinyLMWorkflow(
        launcher, max_epochs=1, seq_len=16, minibatch_size=16,
        embed_dim=16,
        layers=[Z.layer_spec(n_heads=2, ffn="experts", n_experts=4,
                             top_k=2)],
        loader_config={"n_train": 64, "n_valid": 16})
    launcher.initialize()
    return launcher, wf


def test_moe_acc_buckets_ticks_by_minibatch_class():
    """One epoch of 64 train / 16 validation samples at minibatch 16,
    tick by tick: the validation row holds 1 tick and the train row
    4, each with its own assignments; the test row stays empty."""
    from veles_tpu.loader.base import TEST, TRAIN, VALID
    launcher, wf = _expert_workflow()
    layer = wf.forwards[1]
    while True:
        wf.loader.serve_next_minibatch()
        wf.begin_tick()
        wf.compiler.execute(
            key=jax.random.PRNGKey(0),
            training=wf.loader.minibatch_class == TRAIN)
        if wf.loader.epoch_ended:
            break
    a_tick = 16 * 16 * 2               # tokens x top 2, all 4 held
    for cls, ticks in ((TEST, 0), (VALID, 1), (TRAIN, 4)):
        row = layer.read_moe_share(cls)
        assert list(row[:3]) == [ticks * a_tick, ticks * a_tick, ticks]
        assert float(row[3:].sum()) == ticks * a_tick
    launcher.stop()


def test_moe_acc_leaves_padded_ticks_out():
    """A block whose last tick is padding (an all-zero mask) adds
    three ticks' counts to the train row, not four."""
    from veles_tpu.loader.base import TRAIN, VALID
    launcher, wf = _expert_workflow()
    layer, loader = wf.forwards[1], wf.loader
    block = loader.serve_block(4)
    assert loader.minibatch_class == VALID   # served first: 1 tick
    block = loader.serve_block(4)
    assert loader.minibatch_class == TRAIN
    mask = block[str(id(loader.minibatch_mask))]
    assert mask.shape[0] == 4
    mask[-1] = 0.0
    wf.compiler.execute_block(block, True, key=jax.random.PRNGKey(0))
    row = layer.read_moe_share(TRAIN)
    assert list(row[:3]) == [3 * 16 * 16 * 2, 3 * 16 * 16 * 2, 3]
    assert float(layer.read_moe_share(VALID).sum()) == 0.0
    launcher.stop()
