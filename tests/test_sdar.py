"""SDAR (``sdar``): block diffusion on the normal path — the mask
(``ops.pallas_attention.diffusion_visible``), the flash kernels' walk
over it and their results interpreted against XLA's masked attention,
rotary positions of two streams, the noising step and the weighted
loss (``znicz.attention``), and ``samples/sdar.py`` through the step
compiler — at a small size on the CPU, seeded random weights, against
the benchmark's plain reference (``benchmark/models/sdar.py``, which
imports nothing of the program)."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.models import sdar as REF
from veles_tpu.ops import attention as A
from veles_tpu.ops import moe as M
from veles_tpu.ops import pallas_attention as PA
from veles_tpu.ops.rotary import rotary
from veles_tpu.znicz import attention as Z


def small_sizes(layers=2, held=2, experts=8, top_k=2):
    return {"hidden": 32, "heads": 4, "kv_heads": 2, "head_dim": 8,
            "expert_ffn": 16, "experts": experts, "held": held,
            "top_k": top_k, "norm_topk": True, "vocab": 64,
            "layers": layers, "rope_theta": 1e4, "norm_eps": 1e-6,
            "block": 4, "eps": 1e-3, "blocks": layers,
            "published_layers": layers}


def by_hand(L, B):
    """The mask of the model's table, a pair at a time."""
    out = numpy.zeros((2 * L, 2 * L), bool)
    for r in range(2 * L):
        for c in range(2 * L):
            r_clean, c_clean = r >= L, c >= L
            rb, cb = (r % L) // B, (c % L) // B
            if r_clean:
                out[r, c] = c_clean and cb <= rb
            elif c_clean:
                out[r, c] = cb < rb
            else:
                out[r, c] = cb == rb
    return out


# -- the mask and the walk ----------------------------------------------------

@pytest.mark.parametrize("L,B", [(24, 4), (32, 16), (48, 3)])
def test_the_mask_is_the_table_by_hand(L, B):
    rows = numpy.arange(2 * L)
    got = numpy.asarray(PA.diffusion_visible(rows[:, None], rows[None, :],
                                             L, B))
    want = by_hand(L, B)
    numpy.testing.assert_array_equal(got, want)
    numpy.testing.assert_array_equal(
        numpy.asarray(REF.visible(rows[:, None], rows[None, :], L, B)),
        want)
    # L² + L·B visible pairs; every row sees at least itself's block
    assert want.sum() == L * L + L * B == REF.visible_pairs(
        {"block": B}, L)
    assert want.any(axis=1).all()
    leak = numpy.asarray(REF.visible(rows[:, None], rows[None, :], L, B,
                                     leak=True))
    assert (leak & ~want).sum() == L * B and not (want & ~leak).any()


def test_flash_tiles_of_the_three_walks():
    """80 of 256 tiles over two streams of 4,096 in blocks of 4 (36
    clean → clean, 36 noised → clean, 8 on the noised diagonal); the
    causal and window walks at 8,192 as they were."""
    assert PA.flash_tiles(8192, 8192, 512, 512,
                          diffusion=(4096, 4)) == (80, 256)
    assert PA.flash_tiles(8192, 8192, 512, 512, causal=True) == (136, 256)
    assert PA.flash_tiles(8192, 8192, 512, 512, causal=True,
                          window=2048) == (70, 256)
    assert PA.flash_tiles(2048, 2048, 512, 512, causal=True) == (10, 16)
    # a tile that straddles the two streams
    assert PA.flash_tiles(384, 384, 128, 128,
                          diffusion=(192, 4)) == (7, 9)


def test_the_walk_visits_exactly_the_tiles_that_see_something():
    """Every query block's key walk and every key block's query walk,
    streams that end inside a tile and blocks across tiles: each tile
    that holds a visible pair visited once, no other, and a tile left
    unmasked only where every pair of it is visible."""
    for L, B, b in ((24, 4, 8), (20, 4, 8), (40, 8, 16), (36, 2, 8)):
        V = by_hand(L, B)
        n = 2 * L // b
        for i in range(n):
            walk = [(j, masked) for lo, hi, masked in PA._bd_key_stretches(
                i * b, b, 0, b, n, (L, B)) for j in range(lo, hi)]
            tiles = [V[i * b:(i + 1) * b, j * b:(j + 1) * b]
                     for j in range(n)]
            assert [j for j, _ in walk] == \
                [j for j in range(n) if tiles[j].any()]
            assert all(masked or tiles[j].all() for j, masked in walk)
            walk = [(r, masked) for lo, hi, masked in
                    PA._bd_query_stretches(i * b, b, 0, b, n, (L, B))
                    for r in range(lo, hi)]
            tiles = [V[r * b:(r + 1) * b, i * b:(i + 1) * b]
                     for r in range(n)]
            assert [r for r, _ in walk] == \
                [r for r in range(n) if tiles[r].any()]
            assert all(masked or tiles[r].all() for r, masked in walk)


@pytest.mark.parametrize("L,B,heads,chunk", [(192, 4, 2, None),
                                              (192, 16, 1, 128)])
def test_kernels_interpreted_against_xla_masked_attention(L, B, heads,
                                                          chunk):
    """Forward and all three gradients of ONE interpreted call over
    2L = 384 rows (tiles of 128: a tile holds the end of the noised
    stream and the start of the clean one) — or of the chunked walk, a
    call a visible pair of 128-row chunks at their global origins —
    against XLA's attention under the same mask."""
    S = 2 * L
    keys = jax.random.split(jax.random.PRNGKey(L + B), 4)
    q, k, v, do = (jax.random.normal(kk, (1, S, heads, 64))
                   for kk in keys)

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(do)

    got = run(lambda q, k, v: PA.pallas_attention(
        q, k, v, diffusion=(L, B), operand_dtype=jnp.float32,
        interpret=True, chunk=chunk))
    want = run(lambda q, k, v: A.attention(
        q, k, v, diffusion=(L, B), precision="f32", kernel="xla"))
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        numpy.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                      err_msg=name)
    causal = A.attention(q, k, v, causal=True, precision="f32",
                         kernel="xla")
    assert float(jnp.abs(causal - got[0]).max()) > 0.1


def test_the_mode_refuses_what_does_not_go_with_it():
    q = jnp.zeros((1, 16, 1, 8))
    for kwargs in ({"causal": True}, {"window": 4}):
        with pytest.raises(ValueError):
            A.attention(q, q, q, diffusion=(8, 4), **kwargs)
    with pytest.raises(ValueError):
        A.attention(q, q, q, diffusion=(6, 4))      # 2L != S
    with pytest.raises(ValueError):
        A.attention(q, q, q, diffusion=(8, 3))      # B does not divide L
    assert not PA.supports((1, 256, 1, 128), (1, 256, 1, 128),
                           diffusion=(128, 3))      # not a power of two
    assert PA.supports((1, 256, 1, 128), (1, 256, 1, 128),
                       diffusion=(128, 4))


# -- positions, noising, the loss ---------------------------------------------

def test_rotary_takes_the_two_streams_positions():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 16))
    numpy.testing.assert_array_equal(
        rotary(x, 1e4, positions=jnp.arange(24)), rotary(x, 1e4))
    both = rotary(x, 1e4, positions=jnp.tile(jnp.arange(12), 2))
    numpy.testing.assert_allclose(both[:, :12], rotary(x[:, :12], 1e4),
                                  rtol=1e-6, atol=1e-6)
    numpy.testing.assert_allclose(both[:, 12:], rotary(x[:, 12:], 1e4),
                                  rtol=1e-6, atol=1e-6)
    numpy.testing.assert_allclose(
        both[0], REF._rope_at(x[0], 1e4, jnp.tile(jnp.arange(12), 2)),
        rtol=1e-5, atol=1e-6)


def test_noising_is_the_keys_and_keeps_a_rate_a_block():
    sz = small_sizes()
    words = numpy.array([3900000021 >> 31, 3900000021 & 0x7FFFFFFF],
                        numpy.uint32)
    key = Z.diffusion_key(jnp.asarray(words), 5)
    t, m = Z.block_noise(key, 3, 4096, 4, 1e-3)
    t2, m2 = Z.block_noise(key, 3, 4096, 4, 1e-3)
    assert (numpy.asarray(t) == numpy.asarray(t2)).all() and \
        (numpy.asarray(m) == numpy.asarray(m2)).all()
    want_t, want_m = REF.noise(3900000021, 5, 3, 4096, dict(sz))
    numpy.testing.assert_array_equal(t, want_t)
    numpy.testing.assert_array_equal(m, want_m)
    t = numpy.asarray(t).reshape(3, 1024, 4)
    assert (t == t[..., :1]).all() and 1e-3 <= t.min() and t.max() < 1
    assert len(numpy.unique(t[..., 0])) > 0.99 * 3 * 1024   # a block's own
    # a block is masked as often as its rate says
    m = numpy.asarray(m).reshape(3, 1024, 4)
    low, high = t[..., 0] < 0.2, t[..., 0] > 0.8
    assert m[low].mean() < 0.25 < 0.75 < m[high].mean()
    other = Z.block_noise(Z.diffusion_key(jnp.asarray(words), 6), 3, 4096,
                          4, 1e-3)[1]
    assert (numpy.asarray(other) != numpy.asarray(m2)).mean() > 0.3


def test_the_weighted_loss_against_a_cross_entropy_by_hand():
    rng = numpy.random.RandomState(3)
    logits = rng.standard_normal((2, 8, 11)).astype(numpy.float32)
    labels = rng.randint(0, 11, (2, 8))
    t = rng.uniform(0.1, 1.0, (2, 8)).astype(numpy.float32)
    masked = rng.uniform(size=(2, 8)) < 0.5
    mask = numpy.array([1.0, 1.0], numpy.float32)
    total = errors = 0.0
    for b in range(2):
        for i in range(8):
            if masked[b, i]:
                z = logits[b, i] - logits[b, i].max()
                total -= (z[labels[b, i]] - numpy.log(numpy.exp(z).sum())
                          ) / t[b, i]
                errors += logits[b, i].argmax() != labels[b, i]
    logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.asarray(labels)[..., None],
                               axis=-1)[..., 0]
    loss, n_err, counted = Z.diffusion_loss(
        nll, jnp.argmax(logits, -1), jnp.asarray(labels),
        jnp.asarray(masked / t), mask[:, None] * jnp.ones((1, 8)))
    numpy.testing.assert_allclose(loss, total / 16, rtol=1e-5)
    assert float(n_err) == errors and float(counted.sum()) == masked.sum()


@pytest.mark.parametrize("noising", [False, True])
def test_the_noising_embedding_keeps_the_stream_float32(noising):
    """Every masked position starts from the ONE MASK row, so the
    noising embedding hands the layers its rows in float32, the
    residual stream's type — the table's rows exactly, MASK where the
    step masked — where the plain embedding writes the compute type."""
    from types import SimpleNamespace
    table = jax.random.normal(jax.random.PRNGKey(5), (64, 32))
    tokens = (jnp.arange(16, dtype=jnp.int32) * 7 % 63)[None]
    unit = SimpleNamespace(
        input="ids", output="out", noise_weights="w", scale=1.0,
        compute_dtype=jnp.bfloat16,
        diffusion={"block": 4, "eps": 1e-3, "mask_id": 63}
        if noising else None)
    written = {}
    state = {"noise_seed": jnp.asarray([0, 7], jnp.uint32),
             "noise_tick": jnp.zeros(1, jnp.int32)}
    Z.Embedding.tforward(unit, lambda name: tokens, written.__setitem__,
                         {"weights": table}, None, state)
    out = written["out"]
    if not noising:
        assert out.dtype == jnp.bfloat16 and out.shape == (1, 16, 32)
        return
    masked = numpy.asarray(written["w"])[0] > 0
    assert 0 < masked.sum() < 16
    ids = numpy.concatenate([numpy.where(masked, 63, tokens[0]),
                             tokens[0]])
    assert out.dtype == jnp.float32 and out.shape == (1, 32, 32)
    numpy.testing.assert_array_equal(numpy.asarray(out[0]),
                                     numpy.asarray(table)[ids])


# -- the layer and the shares -------------------------------------------------

def _layer_inputs(sz, seed=11):
    p = REF._block_leaves(REF.init_params(seed, sz), 0)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 32, sz["hidden"]))
    return p, x


def test_layer_matches_reference_forward_and_gradient():
    """One expert layer over the two streams of 16 ids: the block mask,
    the two streams' positions and the softmax-routed share against the
    reference layer."""
    with jax.default_matmul_precision("highest"):
        sz = small_sizes()
        spec = REF.program_layers(sz)[0]
        p, x = _layer_inputs(sz)
        assert {n: tuple(v.shape) for n, v in p.items()} == \
            Z.layer_param_shapes(spec, sz["hidden"])
        assert tuple(Z.layer_param_shapes(spec, 32)) == REF.LAYER_LEAVES
        weight = jax.random.normal(jax.random.PRNGKey(7), x.shape)

        def program(p, x):
            y, _ = Z.layer_apply(
                spec, p, x, jnp.float32,
                buffers={"expert_bias": jnp.zeros(sz["experts"])},
                attend=lambda q, k, v: A.attention(q, k, v,
                                                   diffusion=(16, 4)),
                positions=jnp.tile(jnp.arange(16), 2))
            return (y * weight).sum(), y

        def reference(p, x):
            y = REF._layer(p, x[0], sz, REF._dot(None), False)[0][None]
            return (y * weight).sum(), y

        (_, y), grads = jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True)(p, x)
        (_, want), want_grads = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True)(p, x)
        numpy.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
        for name in p:
            numpy.testing.assert_allclose(
                grads[0][name], want_grads[0][name], rtol=3e-4,
                atol=3e-4, err_msg=name)
        numpy.testing.assert_allclose(grads[1], want_grads[1], rtol=3e-4,
                                      atol=3e-4)
        leak = REF._layer(p, x[0], sz, REF._dot(None), True)[0]
        assert float(jnp.abs(leak - want[0]).max()) > 1e-3


def test_the_shares_of_held_experts_make_the_uncut_layer():
    """The routed parts of all four shares of an uncut layer of 8
    experts, plus what every chip computes alike — attention — counted
    ONCE, are the reference layer with every expert held."""
    with jax.default_matmul_precision("highest"):
        sz = small_sizes(held=8, experts=8, top_k=4)
        p, x = _layer_inputs(sz, 3)
        dot = REF._dot(None)
        whole, made = REF._layer(p, x[0], sz, dot, False)
        assert float(made) == 32 * 4                 # uncut: all land
        a = _attended(p, x[0], sz, dot)
        u = REF._rms_norm(a, p["ln2_g"], 1e-6)

        def share(first):
            return M.moe_dropless(
                u, p["router"], jnp.zeros(8), p["w1"][first:first + 2],
                p["w3"][first:first + 2], p["w2"][first:first + 2],
                top_k=4, held=(first, 2), cdt=jnp.float32, eps=0.0,
                score="softmax")

        parts = [share(first) for first in range(0, 8, 2)]
        numpy.testing.assert_allclose(a + sum(y for y, _ in parts), whole,
                                      rtol=3e-5, atol=3e-5)
        assert sum(float(s["landed"]) for _, s in parts) == float(made)
        assert float(jnp.abs(a + sum(y for y, _ in parts[:3]) -
                             whole).max()) > 1e-3


def _attended(p, h, sz, dot):
    """The layer's residual stream after its attention (the experts'
    last products zeroed): the part every chip of the eight computes
    alike."""
    return REF._layer(dict(p, w2=jnp.zeros_like(p["w2"])), h, sz, dot,
                      False)[0]


# -- the workflow -------------------------------------------------------------

TRAFFIC = {"batch": 1, "seq": 16, "ticks": 2, "remat": True,
           "learning_rate": 1e-4, "momentum": 0.9}


@pytest.fixture
def trainer():
    """The program in float32 (``precision_level`` 2), as
    ``test_joyai_llm_flash.py``'s."""
    from veles_tpu.config import root
    sz = small_sizes()
    was = root.common.engine.precision_level
    root.common.engine.precision_level = 2
    t = REF.build_trainer(sz, TRAFFIC, 4141, 4, "cpu")
    yield sz, t
    root.common.engine.precision_level = was
    if t.launcher is not None:
        t.launcher.stop()


def test_first_dispatch_through_the_step_compiler(trainer, capsys):
    """Two expert layers over the two streams through ``Launcher`` →
    workflow → ``StepCompiler`` (per-layer remat, block mode), the
    noising inside the step: the loss, every leaf's momentum norm and
    sample, the parameters' change, the landed count and the masked
    share of the first dispatch against the reference's first ticks;
    the leak of a block's clean keys to its noised rows is told apart,
    and the masked share is published as ``diffusion.masked_frac``."""
    from benchmark import checks
    from benchmark.drivers.train_block import first_dispatch
    from veles_tpu.observability.metrics import registry
    sz, t = trainer
    wf = t.wf
    assert [u.name for u in wf.forwards] == ["embedding", "block0",
                                             "block1", "final_norm",
                                             "head"]
    assert wf.embedding.output.shape == (1, 32, 32)
    assert wf.head.output.shape == (1, 16, 64)
    assert wf.evaluator.labels is wf.loader.minibatch_data
    assert wf.evaluator.weights is wf.embedding.noise_weights
    assert all(u.diffusion == (16, 4) for u in wf.forwards[1:3])
    program, _seconds = first_dispatch(t)
    assert set(program["velocity"]) == set(REF.leaf_shapes(sz))
    counted = t.assignments()
    masked = registry.peek("diffusion.masked_frac").value
    reference = REF.reference_train(4141, sz, TRAFFIC, 2)
    assert masked == reference["masked_frac"]
    limits = {"loss_gap": 1e-5, "velocity_gap": 1e-4, "change_gap": 1e-4,
              "direction_gap": 1e-4}
    compared = checks.train_checks(program, reference, limits)
    assert all(c["ok"] for c in compared), compared
    assert counted["assignments_landed"] == sum(reference["landed"])
    assert counted["assignments_made"] == 2 * 32 * 2 * 2
    for fault in ("block_leak", "half_batch"):
        planted = REF.reference_train(4141, sz, TRAFFIC, 2, fault=fault)
        told = checks.train_checks(planted, reference, limits)
        assert not all(c["ok"] for c in told), (fault, told)
    capsys.readouterr()


def test_export_refuses_the_family_by_name(trainer, tmp_path):
    from veles_tpu.error import Bug
    from veles_tpu.export import export_workflow
    _sz, t = trainer
    with pytest.raises(Bug, match="block diffusion"):
        export_workflow(t.wf, str(tmp_path / "m.veles.tgz"))


def test_block_diffusion_needs_spec_layers_and_a_free_head():
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    layers = REF.program_layers(small_sizes())
    diffusion = {"block": 4, "eps": 1e-3, "mask_id": 15}
    for kwargs in ({}, {"layers": layers}):          # a tied head
        with pytest.raises(ValueError, match="tied_head=False"):
            TinyLMWorkflow(Launcher(), diffusion=diffusion, **kwargs)
    with pytest.raises(ValueError, match="block diffusion"):
        Z.LMLayer(Launcher(), spec=dict(layers[0], window=4),
                  diffusion=(16, 4))
