"""Qwen3-Next (``qwen3_next``): the spec keys it brought (``operator=
"gated_delta"`` and its ``linear_*``, ``rope_fraction``, ``score``,
``shared_gate``) and ``samples/qwen3_next.py`` — at a small size on the
CPU, seeded random weights, several chunks a sequence, against the
benchmark's plain reference (``benchmark/models/qwen3_next.py``, which
imports nothing of the program and runs the rule a row at a time).
The rule itself: ``tests/test_gated_delta.py``."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.models import qwen3_next as REF
from veles_tpu.ops import moe as M
from veles_tpu.ops.rotary import rotary
from veles_tpu.znicz import attention as Z
from veles_tpu.znicz.samples.qwen3_next import qwen3_next_layers

LINEAR, FULL = REF.LINEAR, REF.FULL


def small_sizes(layers=4, held=2, experts=8, top_k=2, chunk=16):
    types = tuple(FULL if (i + 1) % 4 == 0 else LINEAR
                  for i in range(layers))
    return {"hidden": 64, "heads": 4, "kv_heads": 2, "head_dim": 32,
            "rope_dim": 8, "rope_fraction": 0.25, "rope_theta": 1e7,
            "key_heads": 2, "value_heads": 4, "key_dim": 16,
            "value_dim": 16, "conv_kernel": 4, "chunk": chunk,
            "expert_ffn": 48, "shared_ffn": 40, "experts": experts,
            "held": held, "top_k": top_k, "norm_topk": True,
            "vocab": 128, "interval": 4, "layer_types": types,
            "norm_eps": 1e-6, "a_range": (1e-3, 16.0),
            "dt_range": (1e-3, 1e-1), "blocks": types.count(FULL)}


def specs_of(sz):
    return qwen3_next_layers(
        len(sz["layer_types"]), n_heads=sz["heads"],
        kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        linear_key_heads=sz["key_heads"],
        linear_value_heads=sz["value_heads"],
        linear_key_dim=sz["key_dim"], linear_value_dim=sz["value_dim"],
        moe_intermediate_size=sz["expert_ffn"], n_experts=sz["experts"],
        top_k=sz["top_k"],
        shared_expert_intermediate_size=sz["shared_ffn"],
        full_attention_interval=sz["interval"],
        linear_conv_kernel=sz["conv_kernel"],
        partial_rotary_factor=sz["rope_fraction"],
        rope_theta=sz["rope_theta"], held=(0, sz["held"]),
        norm_eps=sz["norm_eps"], linear_chunk=sz["chunk"])


# -- the whole layer of each kind against the reference ----------------------

@pytest.mark.parametrize("layer,kind", [(0, LINEAR), (3, FULL)])
def test_layer_matches_reference_forward_and_gradient(layer, kind):
    with jax.default_matmul_precision("highest"):
        sz = small_sizes()
        spec = specs_of(sz)[layer]
        assert sz["layer_types"][layer] == kind
        assert (spec["operator"] == "gated_delta") == (kind == LINEAR)
        p = REF._block_leaves(REF.init_params(11, sz), layer)
        assert list(Z.layer_param_shapes(spec, sz["hidden"])) == [
            n.split(".")[1] for n in REF.leaf_shapes(sz)
            if n.startswith("block%d." % layer)]
        assert {n: tuple(v.shape) for n, v in p.items()} == \
            Z.layer_param_shapes(spec, sz["hidden"])
        x = jax.random.normal(jax.random.PRNGKey(layer), (2, 48, 64))
        weight = jax.random.normal(jax.random.PRNGKey(7), x.shape)
        bias = jnp.zeros(sz["experts"])

        def program(p, x):
            y, stats = Z.layer_apply(spec, p, x, jnp.float32,
                                     buffers={"expert_bias": bias})
            return (y * weight).sum(), (y, stats)

        def reference(p, x):
            out = [REF._layer(p, x[b], sz, layer, REF._dot(None), 2, None)
                   for b in range(x.shape[0])]
            y = jnp.stack([o[0] for o in out])
            return (y * weight).sum(), (y, sum(o[1] for o in out))

        (_, (y, stats)), grads = jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True)(p, x)
        (_, (want, landed)), want_grads = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True)(p, x)
        numpy.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
        for name in p:
            numpy.testing.assert_allclose(
                grads[0][name], want_grads[0][name], rtol=3e-4,
                atol=3e-4, err_msg=name)
        numpy.testing.assert_allclose(grads[1], want_grads[1], rtol=3e-4,
                                      atol=3e-4)
        assert float(stats["landed"]) == float(landed) > 0


def test_the_faults_change_a_linear_layer_only():
    with jax.default_matmul_precision("highest"):
        sz = small_sizes()
        tree = REF.init_params(5, sz)
        x = jax.random.normal(jax.random.PRNGKey(1), (48, 64))
        for layer, moved in ((0, True), (3, False)):
            p = REF._block_leaves(tree, layer)
            ys = {fault: REF._layer(p, x, sz, layer, REF._dot(None), 2,
                                    fault)[0]
                  for fault in (None, "state_dropped", "decay_ignored")}
            for fault in ("state_dropped", "decay_ignored"):
                assert (float(jnp.abs(ys[fault] - ys[None]).max())
                        > 1e-3) == moved, (layer, fault)
        assert float(jnp.abs(ys["state_dropped"] -
                             ys["decay_ignored"]).max()) == 0.0


# -- each new spec key alone --------------------------------------------------

def test_partial_rotary_against_a_rotation_by_hand():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 32))
    out = rotary(x, 1e4, 0.25)
    # the other 24 of a head are untouched, and row 0 is not rotated
    numpy.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    numpy.testing.assert_allclose(out[:, 0], x[:, 0], atol=1e-7)
    # pairs (i, i + 4) of the first 8 turn by t * theta^(-2 i / 8)
    t = numpy.arange(12)[:, None]
    angle = t * 1e4 ** (-numpy.arange(0, 8, 2) / 8.0)[None, :]
    cos, sin = numpy.cos(angle)[None, :, None], numpy.sin(angle)[None, :,
                                                                 None]
    a, b = numpy.asarray(x[..., :4]), numpy.asarray(x[..., 4:8])
    numpy.testing.assert_allclose(out[..., :4], a * cos - b * sin,
                                  rtol=1e-5, atol=1e-6)
    numpy.testing.assert_allclose(out[..., 4:8], b * cos + a * sin,
                                  rtol=1e-5, atol=1e-6)
    # the whole head: a fraction of one, or none given
    numpy.testing.assert_array_equal(rotary(x, 1e4, 1.0), rotary(x, 1e4))
    numpy.testing.assert_allclose(
        REF._rope_first(x[0], 1e4, 8), out[0], rtol=1e-5, atol=1e-6)


def test_softmax_routing_choice_and_weights():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    gate = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    bias = 10.0 * jax.random.normal(jax.random.PRNGKey(2), (12,))
    idx, w = M.softmax_route(x, gate, bias, 3, eps=0.0)
    p = jax.nn.softmax(jnp.dot(x, gate, precision="highest"), axis=-1)
    want_w, want_idx = jax.lax.top_k(p, 3)
    numpy.testing.assert_array_equal(idx, want_idx)       # no bias read
    numpy.testing.assert_allclose(
        w, want_w / want_w.sum(-1, keepdims=True), rtol=1e-6)
    numpy.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    _, raw = M.softmax_route(x, gate, bias, 3, norm_topk=False)
    numpy.testing.assert_allclose(raw, want_w, rtol=1e-6)
    ref_idx, ref_w = REF.route(x, gate, {"top_k": 3, "norm_topk": True})
    numpy.testing.assert_array_equal(idx, ref_idx)
    numpy.testing.assert_allclose(w, ref_w, rtol=1e-6)
    # the sigmoid router does read it
    sig_idx, _ = M.sigmoid_route(x, gate, bias, 3)
    assert not numpy.array_equal(sig_idx, M.sigmoid_route(
        x, gate, 0 * bias, 3)[0])
    # the derivative is softmax's own
    weight = jax.random.normal(jax.random.PRNGKey(3), p.shape)
    numpy.testing.assert_allclose(
        jax.grad(lambda z: (M._probabilities(z) * weight).sum())(x @ gate),
        jax.grad(lambda z: (jax.nn.softmax(z) * weight).sum())(x @ gate),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_the_checkpoint_keeps_the_scores_of_either_router(score):
    """Under the layers' checkpoint the recompute holds no router
    product and no ``top_k`` whatever the score function: the named
    scores, choice and chosen scores are kept (PR 34's rule)."""
    spec = Z.layer_spec(norm="rms", bias=False, ffn="experts",
                        n_experts=8, top_k=2, held=(0, 4), ffn_dim=24,
                        score=score)
    shapes = Z.layer_param_shapes(spec, 32)
    keys = jax.random.split(jax.random.PRNGKey(0), len(shapes))
    p = {n: jnp.ones(s) if n.endswith("_g") else
         jax.random.normal(k, s) / 6.0
         for k, (n, s) in zip(keys, shapes.items())}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 32))
    bias = jnp.zeros(8)

    def loss(wrap):
        def apply(p, x):
            return Z.layer_apply(spec, p, x, jnp.float32,
                                 buffers={"expert_bias": bias})[0]
        return lambda p: wrap(apply)(p, x).sum()

    text = str(jax.make_jaxpr(jax.grad(loss(Z.checkpointed)))(p))
    bare = str(jax.make_jaxpr(jax.grad(loss(jax.checkpoint)))(p))
    assert text.count("top_k") == 1 and bare.count("top_k") == 2
    assert text.count("argsort") + text.count(" sort") < \
        bare.count("argsort") + bare.count(" sort")
    numpy.testing.assert_allclose(
        jax.grad(loss(Z.checkpointed))(p)["router"],
        jax.grad(loss(lambda f: f))(p)["router"], rtol=1e-5, atol=1e-6)


def test_layer_spec_refuses_what_does_not_go_together():
    linear = dict(operator="gated_delta", linear_key_heads=2,
                  linear_value_heads=4, linear_key_dim=8,
                  linear_value_dim=8)
    for bad in (dict(linear, window=8), dict(linear, attn_gate=True),
                dict(linear, head_dim=16),
                dict(linear, rope_theta=1e4, rope_fraction=0.5),
                dict(linear, linear_value_heads=3),
                dict(linear, linear_key_dim=None),
                dict(linear, linear_chunk=0),
                {"linear_key_heads": 2}, {"operator": "shortconv",
                                          "linear_value_dim": 8},
                {"rope_fraction": 0.25},                 # no rope_theta
                {"rope_theta": 1e4, "rope_fraction": 0.0},
                {"rope_theta": 1e4, "rope_fraction": 1.5},
                {"ffn": "experts", "n_experts": 4, "score": "tanh"},
                {"ffn": "experts", "n_experts": 4, "shared_gate": True},
                {"shared_gate": True}):
        with pytest.raises(ValueError):
            Z.layer_spec(**bad)
    opt = Z.layer_spec(n_heads=8)
    assert (opt["rope_fraction"], opt["score"], opt["shared_gate"],
            opt["linear_key_heads"], opt["linear_chunk"]) == \
        (None, "sigmoid", False, None, 64)
    # under softmax the selection bias is not read; the buffer stays
    spec = specs_of(small_sizes())[0]
    assert spec["score"] == "softmax" and spec["shared_gate"] and \
        spec["route_eps"] == 0.0 and spec["slack"] == (2, 1)


def test_older_specs_keep_their_keys_and_leaves_in_order():
    """Seeded trajectories draw in the leaves' order; snapshots carry
    the spec's."""
    assert tuple(Z.layer_spec())[:23] == (
        "norm", "operator", "ffn", "n_heads", "kv_heads", "qk_norm",
        "rope_theta", "bias", "ffn_dim", "conv_kernel", "n_experts",
        "top_k", "held", "norm_topk", "routed_scaling", "norm_eps",
        "head_dim", "window", "attn_gate", "post_norm",
        "shared_ffn_dim", "route_eps", "slack")
    assert tuple(Z.layer_param_shapes(Z.layer_spec(), 16)) == \
        Z.TransformerBlock.PARAM_NAMES
    conv = Z.layer_spec(norm="rms", operator="shortconv", bias=False,
                        ffn="gated-mlp")
    assert tuple(Z.layer_param_shapes(conv, 16)) == (
        "ln1_g", "w_in", "w_conv", "w_out", "ln2_g", "w1", "w3", "w2")
    trinity = Z.layer_spec(
        norm="rms", kv_heads=2, qk_norm=True, bias=False, head_dim=8,
        attn_gate=True, post_norm=True, ffn="experts", n_experts=4,
        top_k=2, shared_ffn_dim=8)
    assert tuple(Z.layer_param_shapes(trinity, 16)) == (
        "ln1_g", "wq", "wk", "wv", "wo", "wg", "q_norm_g", "k_norm_g",
        "ln1_post_g", "ln2_g", "router", "w1", "w3", "w2", "ws1", "ws3",
        "ws2", "ln2_post_g")
    sz = small_sizes()
    assert tuple(Z.layer_param_shapes(specs_of(sz)[0], 64)) == \
        REF.LINEAR_LEAVES + REF.EXPERT_LEAVES
    assert tuple(Z.layer_param_shapes(specs_of(sz)[3], 64)) == \
        REF.FULL_LEAVES + REF.EXPERT_LEAVES


# -- the shares add up --------------------------------------------------------

def test_the_sixteen_shares_and_the_gated_shared_expert_once_make_the_layer():
    """The routed parts of all sixteen shares, plus what every chip
    computes alike — the shared expert behind its gate — counted ONCE,
    are the uncut reference layer's FFN."""
    with jax.default_matmul_precision("highest"):
        sz = small_sizes(layers=1, held=32, experts=32, top_k=5)
        p = REF._block_leaves(REF.init_params(3, sz), 0)
        x = jax.random.normal(jax.random.PRNGKey(8), (512, 64))
        dot = REF._dot(None)
        whole, made = REF.expert_ffn(p, x, sz, dot)
        assert float(made) == 512 * 5            # uncut: all land

        def share(first):
            return M.moe_dropless(
                x, p["router"], jnp.zeros(32), p["w1"][first:first + 2],
                p["w3"][first:first + 2], p["w2"][first:first + 2],
                top_k=5, held=(first, 2), cdt=jnp.float32, eps=0.0,
                score="softmax")

        parts = [share(first) for first in range(0, 32, 2)]
        assert len(parts) == 16
        shared = REF.shared_ffn(p, x, dot)
        numpy.testing.assert_allclose(
            sum(y for y, _ in parts) + shared, whole, rtol=3e-5,
            atol=3e-5)
        assert sum(float(s["landed"]) for _, s in parts) == float(made)
        # sixteen shares each with its own shared expert count it 16 times
        assert float(jnp.abs(sum(y + shared for y, _ in parts) -
                             whole).max()) > 0.1
        # and the program's layer at one share holds it once, gated
        spec = dict(specs_of(sz)[0], held=(0, 2))
        held = {k: (v[:2] if k in ("w1", "w3", "w2") else v)
                for k, v in p.items()}
        y, stats = Z.layer_apply(spec, held, x[None], jnp.float32,
                                 buffers={"expert_bias": jnp.zeros(32)})
        want, landed = REF._layer(held, x, dict(sz, held=2), 0, dot, 2,
                                  None)
        numpy.testing.assert_allclose(y[0], want, rtol=3e-5, atol=3e-5)
        assert float(stats["landed"]) == float(landed) > 0
        ungated = Z.layer_apply(
            dict(spec, shared_gate=False),
            {k: v for k, v in held.items() if k != "wsg"}, x[None],
            jnp.float32, buffers={"expert_bias": jnp.zeros(32)})[0]
        assert float(jnp.abs(ungated[0] - want).max()) > 1e-3


# -- the workflow ---------------------------------------------------------------

TRAFFIC = {"batch": 1, "seq": 64, "ticks": 2, "remat": True,
           "learning_rate": 1e-4, "momentum": 0.9}


@pytest.fixture
def trainer():
    """The program in float32 (``precision_level`` 2), as
    ``test_trinity.py``'s."""
    from veles_tpu.config import root
    sz = small_sizes()
    was = root.common.engine.precision_level
    root.common.engine.precision_level = 2
    t = REF.build_trainer(sz, TRAFFIC, 4242, 8, "cpu")
    yield sz, t
    root.common.engine.precision_level = was
    if t.launcher is not None:
        t.launcher.stop()


def test_first_dispatch_through_the_step_compiler(trainer, capsys):
    """A body of linear, linear, linear, full through ``Launcher`` →
    workflow → ``StepCompiler`` (per-layer remat, block mode, four
    chunks a sequence): loss, momentum norms, parameter change and the
    landed count of the first dispatch against the reference's first
    ticks; the reference with its state dropped, and with the decay
    ignored, is told apart."""
    from benchmark import checks
    from benchmark.drivers.train_block import first_dispatch
    sz, t = trainer
    names = [u.name for u in t.wf.forwards]
    assert names == ["embedding"] + ["block%d" % i for i in range(4)] + \
        ["final_norm", "head"]
    assert not t.wf.embedding.pos and t.wf.embedding.scale == 1.0
    assert t.wf.head.tie_to is None and \
        t.wf.head.weights.shape == (64, 128)
    assert t.wf.forwards[-2].eps == 1e-6
    assert [u.spec["operator"] for u in t.wf.forwards[1:5]] == \
        ["gated_delta"] * 3 + ["attention"]
    program, _seconds = first_dispatch(t)
    assert set(program["velocity"]) == set(REF.leaf_shapes(sz))
    counted = t.assignments()
    reference = REF.reference_train(4242, sz, TRAFFIC, 2)
    limits = {"loss_gap": 1e-5, "velocity_gap": 1e-4, "change_gap": 1e-4,
              "direction_gap": 1e-4}
    compared = checks.train_checks(program, reference, limits)
    assert all(c["ok"] for c in compared), compared
    assert counted["assignments_landed"] == sum(reference["landed"])
    assert counted["assignments_made"] == 2 * 64 * 2 * 4
    assert counted["ticks"] == 2
    need = t.attention_traces()["gated_delta"]
    assert need["units"] == ["block0", "block1", "block2"] and \
        need["calls_per_dispatch"] == 6
    for fault in ("state_dropped", "decay_ignored"):
        planted = REF.reference_train(4242, sz, TRAFFIC, 2, fault=fault)
        told = checks.train_checks(planted, reference, limits)
        assert not all(c["ok"] for c in told), (fault, told)
    capsys.readouterr()


def test_export_refuses_the_new_kinds_by_name(trainer, tmp_path):
    from veles_tpu.error import Bug
    from veles_tpu.export import export_workflow
    _sz, t = trainer
    with pytest.raises(Bug, match="lm_layer units train"):
        export_workflow(t.wf, str(tmp_path / "m.veles.tgz"))


def test_a_gated_delta_layer_refuses_a_sequence_axis():
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    with pytest.raises(ValueError, match="carried along the sequence"):
        TinyLMWorkflow(Launcher(), seq_axis="seq",
                       layers=specs_of(small_sizes())[:1])


def test_rows_that_are_no_multiple_of_the_chunk_are_refused():
    spec = specs_of(small_sizes(chunk=64))[0]
    shapes = Z.layer_param_shapes(spec, 64)
    p = {n: jnp.ones(s) for n, s in shapes.items()}
    with pytest.raises(ValueError, match="48 rows in chunks of 64"):
        Z.layer_apply(spec, p, jnp.ones((1, 48, 64)), jnp.float32,
                      buffers={"expert_bias": jnp.zeros(8)})


def test_the_linear_layers_alone_learn_first_token_recall():
    """Every label is the sequence's first token: a layer whose state
    carried nothing along the sequence stays at chance (15 / 16).  One
    Gated DeltaNet layer, no attention layer beside it."""
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    import veles_tpu.prng as prng
    prng.reset()
    prng.get(0).seed(1234)
    launcher = Launcher()
    layers = qwen3_next_layers(
        1, n_heads=4, kv_heads=2, head_dim=16, linear_key_heads=2,
        linear_value_heads=4, linear_key_dim=16, linear_value_dim=16,
        moe_intermediate_size=32, n_experts=8, top_k=2,
        shared_expert_intermediate_size=32, rope_theta=1e4,
        linear_chunk=16)
    assert [s["operator"] for s in layers] == ["gated_delta"]
    wf = TinyLMWorkflow(launcher, vocab_size=16, seq_len=32, embed_dim=32,
                        tied_head=False, layers=layers, minibatch_size=64,
                        learning_rate=0.03, max_epochs=30)
    launcher.initialize()
    launcher.run()
    assert wf.decision.min_validation_err < 0.2
    launcher.stop()
