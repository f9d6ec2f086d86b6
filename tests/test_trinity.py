"""Trinity (``afmoe``): the spec keys it brought (``head_dim``,
``window``, ``attn_gate``, ``post_norm``, ``shared_ffn_dim``,
``route_eps``), the embedding's scale and the free head, and
``samples/trinity.py`` — at a small size on the CPU, seeded random
weights, S > window so that the window cuts, against the benchmark's
plain reference (``benchmark/models/afmoe.py``, which imports nothing
of the program).  The window in the attention formulations and the
kernels: ``tests/test_window_attention.py``."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.models import afmoe as REF
from veles_tpu.ops import attention as A
from veles_tpu.ops import moe as M
from veles_tpu.znicz import attention as Z
from veles_tpu.znicz.samples.trinity import trinity_layers

SLIDING, FULL = REF.SLIDING, REF.FULL
#: dense + one whole period, as the benchmark's cut
BODY = (SLIDING, SLIDING, SLIDING, SLIDING, FULL)


def small_sizes(layer_types=BODY, dense_layers=1, held=2, experts=8,
                top_k=2, window=20):
    types = tuple(layer_types)
    return {"hidden": 64, "heads": 4, "kv_heads": 2, "head_dim": 32,
            "dense_ffn": 160, "expert_ffn": 48, "experts": experts,
            "held": held, "top_k": top_k, "vocab": 128,
            "dense_layers": dense_layers, "layer_types": types,
            "window": window, "rope_theta": 1e4, "norm_eps": 1e-5,
            "route_norm": True, "scaling": 2.826, "embed_scale": 8.0,
            "bias_std": 0.002, "blocks": len(types)}


def specs_of(sz):
    return trinity_layers(
        sz["layer_types"], n_heads=sz["heads"], kv_heads=sz["kv_heads"],
        head_dim=sz["head_dim"], intermediate_size=sz["dense_ffn"],
        moe_intermediate_size=sz["expert_ffn"], n_experts=sz["experts"],
        top_k=sz["top_k"], sliding_window=sz["window"],
        num_dense_layers=sz["dense_layers"], held=(0, sz["held"]),
        rope_theta=sz["rope_theta"], route_scale=sz["scaling"])


def masked_attention(q, k, v, window=None):
    """The oracle: (B, S, H, D), an explicit (S, S) mask."""
    S, D = q.shape[1], q.shape[-1]
    row, col = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = col <= row
    if window is not None:
        mask = mask & (row - col < window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / \
        D ** 0.5
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def qkv(shape, seed=0):
    return tuple(jax.random.normal(k, shape, jnp.float32)
                 for k in jax.random.split(jax.random.PRNGKey(seed), 3))


# -- the whole layer of each kind against the reference ----------------------

@pytest.mark.parametrize("layer,kinds", [
    (0, "sliding + gated-mlp"), (1, "sliding + experts"),
    (4, "full + experts")])
def test_layer_matches_reference_forward_and_gradient(layer, kinds):
    with jax.default_matmul_precision("highest"):
        sz = small_sizes()
        spec = specs_of(sz)[layer]
        assert kinds == "%s + %s" % (
            "sliding" if spec["window"] else "full", spec["ffn"])
        assert (spec["rope_theta"] is None) == (spec["window"] is None)
        tree = REF.init_params(11, sz)
        p = REF._block_leaves(tree, layer)
        bias = p.pop("expert_bias", None)
        assert list(Z.layer_param_shapes(spec, sz["hidden"])) == [
            n.split(".")[1] for n in REF.leaf_shapes(sz)
            if n.startswith("block%d." % layer)]
        x = jax.random.normal(jax.random.PRNGKey(layer), (2, 48, 64))
        weight = jax.random.normal(jax.random.PRNGKey(7), x.shape)

        def program(p, x):
            y, stats = Z.layer_apply(spec, p, x, jnp.float32,
                                     buffers={"expert_bias": bias})
            return (y * weight).sum(), (y, stats)

        def reference(p, x):
            out = [REF._layer(p, bias, x[b], sz, layer, REF._dot(None),
                              2, None) for b in range(x.shape[0])]
            y = jnp.stack([o[0] for o in out])
            return (y * weight).sum(), (y, sum(o[1] for o in out))

        (_, (y, stats)), grads = jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True)(p, x)
        (_, (want, landed)), want_grads = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True)(p, x)
        numpy.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
        for got, ref in zip(jax.tree_util.tree_leaves(grads),
                            jax.tree_util.tree_leaves(want_grads)):
            numpy.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)
        if spec["ffn"] == "experts":
            assert float(stats["landed"]) == float(landed) > 0
        else:
            assert stats is None


def test_window_ignored_changes_a_sliding_layer_only():
    with jax.default_matmul_precision("highest"):
        sz = small_sizes()
        tree = REF.init_params(5, sz)
        x = jax.random.normal(jax.random.PRNGKey(1), (48, 64))
        for layer, moved in ((1, True), (4, False)):
            p = REF._block_leaves(tree, layer)
            bias = p.pop("expert_bias")
            ys = [REF._layer(p, bias, x, sz, layer, REF._dot(None), 2,
                             fault)[0] for fault in (None,
                                                     "window_ignored")]
            assert (float(jnp.abs(ys[0] - ys[1]).max()) > 1e-3) == moved


# -- each new spec key alone --------------------------------------------------

def manual_layer(spec, p, x, bias=None):
    """A spec-built RMS layer without biases, written out: the oracle
    for one key at a time (one sequence (S, E))."""
    eps, H, KV = spec["norm_eps"], spec["n_heads"], spec["kv_heads"]
    S = x.shape[0]
    dot = REF._dot(None)

    def post(name, y):
        return REF._rms_norm(y, p[name], eps) if spec["post_norm"] else y

    u = REF._rms_norm(x, p["ln1_g"], eps)
    q = dot(u, p["wq"]).reshape(S, H, -1)
    k = dot(u, p["wk"]).reshape(S, KV, -1)
    v = dot(u, p["wv"]).reshape(S, KV, -1)
    k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
    a = masked_attention(q[None], k[None], v[None],
                         spec["window"])[0].reshape(S, -1)
    if spec["attn_gate"]:
        a = a * jax.nn.sigmoid(dot(u, p["wg"]))
    x = x + post("ln1_post_g", dot(a, p["wo"]))
    u = REF._rms_norm(x, p["ln2_g"], eps)
    if spec["ffn"] == "experts":
        sz = {"top_k": spec["top_k"], "route_norm": spec["norm_topk"],
              "scaling": spec["routed_scaling"],
              "experts": spec["n_experts"], "held": spec["held"][1]}
        f, _ = REF.routed_ffn(p, bias, u, sz, dot)
        if spec["shared_ffn_dim"]:
            f = f + REF._gated(u, p["ws1"], p["ws3"], p["ws2"], dot)
    else:
        f = REF._gated(u, p["w1"], p["w3"], p["w2"], dot)
    return x + post("ln2_post_g", f)


@pytest.mark.parametrize("key,more", [
    ("head_dim", {"head_dim": 32}),
    ("window", {"window": 11}),
    ("attn_gate", {"attn_gate": True}),
    ("post_norm", {"post_norm": True}),
    ("shared_ffn_dim", {"ffn": "experts", "n_experts": 4, "top_k": 2,
                        "ffn_dim": 24, "shared_ffn_dim": 40,
                        "routed_scaling": 2.5, "route_eps": 1e-20}),
])
def test_each_new_spec_key_alone(key, more):
    with jax.default_matmul_precision("highest"):
        spec = Z.layer_spec(**dict(
            dict(norm="rms", bias=False, n_heads=4, kv_heads=2,
                 ffn="gated-mlp", ffn_dim=96), **more))
        base = Z.layer_spec(**{k: v for k, v in spec.items()
                               if k != key})
        shapes = Z.layer_param_shapes(spec, 64)
        new = set(shapes) - set(Z.layer_param_shapes(base, 64))
        assert new == {"head_dim": set(), "window": set(),
                       "attn_gate": {"wg"},
                       "post_norm": {"ln1_post_g", "ln2_post_g"},
                       "shared_ffn_dim": {"ws1", "ws3", "ws2"}}[key]
        keys = jax.random.split(jax.random.PRNGKey(3), len(shapes))
        p = {n: (jnp.ones(s) if n.endswith("_g") else
                 jax.random.normal(k, s) / 8.0)
             for k, (n, s) in zip(keys, shapes.items())}
        bias = jnp.zeros(4)
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))

        def program(p):
            return Z.layer_apply(spec, p, x, jnp.float32,
                                 buffers={"expert_bias": bias})[0]

        def manual(p):
            return jnp.stack([manual_layer(spec, p, x[b], bias)
                              for b in range(2)])

        numpy.testing.assert_allclose(program(p), manual(p), rtol=2e-5,
                                      atol=2e-5)
        weight = jax.random.normal(jax.random.PRNGKey(5), x.shape)
        got = jax.grad(lambda p: (program(p) * weight).sum())(p)
        want = jax.grad(lambda p: (manual(p) * weight).sum())(p)
        for name in shapes:
            numpy.testing.assert_allclose(got[name], want[name],
                                          rtol=3e-4, atol=3e-4)


def test_route_eps_and_scaling_reach_the_weights():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    gate = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    _, unit = M.sigmoid_route(x, gate, jnp.zeros(6), 2, eps=1e-20)
    _, lfm2 = M.sigmoid_route(x, gate, jnp.zeros(6), 2)
    _, scaled = M.sigmoid_route(x, gate, jnp.zeros(6), 2, scaling=2.826,
                                eps=1e-20)
    numpy.testing.assert_allclose(unit.sum(-1), 1.0, rtol=1e-6)
    assert float(jnp.abs(lfm2.sum(-1) - 1.0).max()) > 1e-7
    numpy.testing.assert_allclose(scaled, 2.826 * unit, rtol=1e-6)


def test_layer_spec_refuses_what_the_new_keys_do_not_go_with():
    for bad in ({"operator": "shortconv", "window": 8},
                {"operator": "shortconv", "attn_gate": True},
                {"operator": "shortconv", "head_dim": 16},
                {"window": 0}, {"shared_ffn_dim": 32},
                {"ffn": "gated-mlp", "shared_ffn_dim": 32}):
        with pytest.raises(ValueError):
            Z.layer_spec(**bad)
    with pytest.raises(ValueError):
        trinity_layers([SLIDING, "conv"], 4, 2, 16, 64, 32, 8, 2, 8)
    with pytest.raises(ValueError):
        A.attention(*qkv((1, 8, 2, 4)), causal=False, window=4)
    opt = Z.layer_spec(n_heads=8)
    assert (opt["head_dim"], opt["window"], opt["attn_gate"],
            opt["post_norm"], opt["shared_ffn_dim"], opt["route_eps"],
            opt["slack"]) == (None, None, False, False, None, 1e-6,
                              M.DROPLESS_SLACK)
    assert specs_of(small_sizes())[1]["slack"] == (2, 1)


def test_older_specs_keep_their_parameter_order():
    """Seeded trajectories draw in this order."""
    assert tuple(Z.layer_param_shapes(Z.layer_spec(), 16)) == \
        Z.TransformerBlock.PARAM_NAMES
    lfm2 = Z.layer_spec(norm="rms", kv_heads=2, qk_norm=True,
                        rope_theta=1e6, bias=False, ffn="experts",
                        n_experts=4, top_k=2)
    assert tuple(Z.layer_param_shapes(lfm2, 16)) == (
        "ln1_g", "wq", "wk", "wv", "wo", "q_norm_g", "k_norm_g",
        "ln2_g", "router", "w1", "w3", "w2")


# -- the shares add up --------------------------------------------------------

def test_the_eight_shares_and_the_shared_expert_once_make_the_layer():
    """The routed parts of all the shares, plus what every chip
    computes alike — the shared expert — counted ONCE, are the uncut
    reference layer's FFN."""
    with jax.default_matmul_precision("highest"):
        sz = small_sizes(layer_types=(SLIDING,), dense_layers=0,
                         held=16, experts=16, top_k=4)
        p = REF._block_leaves(REF.init_params(3, sz), 0)
        bias = p.pop("expert_bias")
        x = jax.random.normal(jax.random.PRNGKey(8), (512, 64))
        dot = REF._dot(None)
        whole, made = REF.expert_ffn(p, bias, x, sz, dot)
        assert float(made) == 512 * 4            # uncut: all land
        spec = dict(specs_of(sz)[0], held=(0, 2))

        def share(first):
            return M.moe_dropless(
                x, p["router"], bias, p["w1"][first:first + 2],
                p["w3"][first:first + 2], p["w2"][first:first + 2],
                top_k=4, held=(first, 2), scaling=sz["scaling"],
                cdt=jnp.float32, eps=spec["route_eps"])

        parts = [share(first) for first in range(0, 16, 2)]
        shared = REF._gated(x, p["ws1"], p["ws3"], p["ws2"], dot)
        numpy.testing.assert_allclose(
            sum(y for y, _ in parts) + shared, whole, rtol=3e-5,
            atol=3e-5)
        assert sum(float(s["landed"]) for _, s in parts) == float(made)
        # eight shares each with its own shared expert count it 8 times
        assert float(jnp.abs(sum(y + shared for y, _ in parts) -
                             whole).max()) > 0.1
        # and the program's layer at one share holds it once
        held = {k: (v[:2] if k in ("w1", "w3", "w2") else v)
                for k, v in p.items()}
        y, stats = Z.layer_apply(spec, held, x[None], jnp.float32,
                                 buffers={"expert_bias": bias})
        want, landed = REF._layer(held, bias, x, dict(sz, held=2), 0,
                                  dot, 2, None)
        numpy.testing.assert_allclose(y[0], want, rtol=3e-5, atol=3e-5)
        assert float(stats["landed"]) == float(landed) > 0


# -- the workflow ---------------------------------------------------------------

TRAFFIC = {"batch": 1, "seq": 64, "ticks": 2, "remat": True,
           "learning_rate": 1e-4, "momentum": 0.9}


@pytest.fixture
def trainer():
    """The program in float32 (``precision_level`` 2): at 64 wide
    bfloat16 operands alone put a fifth between the program's and the
    reference's gradients, and nothing could be told from it."""
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
    """A body of dense, sliding, sliding, sliding, full through
    ``Launcher`` → workflow → ``StepCompiler`` (per-layer remat, block
    mode): loss, momentum norms, parameter change and the landed count
    of the first dispatch against the reference's first ticks; the
    reference with the window ignored is told apart."""
    from benchmark import checks
    from benchmark.drivers.train_block import first_dispatch
    sz, t = trainer
    names = [u.name for u in t.wf.forwards]
    assert names == ["embedding"] + ["block%d" % i for i in range(5)] + \
        ["final_norm", "head"]
    assert not t.wf.embedding.pos and t.wf.embedding.scale == 8.0
    assert t.wf.head.tie_to is None and \
        t.wf.head.weights.shape == (64, 128)
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
    ignored = REF.reference_train(4242, sz, TRAFFIC, 2,
                                  fault="window_ignored")
    told = checks.train_checks(ignored, reference, limits)
    assert not all(c["ok"] for c in told), told
    capsys.readouterr()


def test_export_refuses_the_new_kinds_by_name(trainer, tmp_path):
    from veles_tpu.error import Bug
    from veles_tpu.export import export_workflow
    _sz, t = trainer
    with pytest.raises(Bug, match="scaled by 8.0|lm_layer units train"):
        export_workflow(t.wf, str(tmp_path / "m.veles.tgz"))


def test_embedding_scale_is_refused_by_export_on_its_own():
    from veles_tpu import export
    from veles_tpu.error import Bug

    class Scaled(object):
        MAPPING = "embedding"
        name, scale = "embedding", 45.25

    with pytest.raises(Bug, match="scaled by 45.25"):
        export._unit_entry(Scaled())


def test_window_refuses_a_sequence_axis():
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    with pytest.raises(ValueError, match="takes no window"):
        TinyLMWorkflow(Launcher(), seq_axis="seq",
                       layers=[Z.layer_spec(window=8)])
