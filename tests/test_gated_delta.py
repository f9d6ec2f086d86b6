"""The gated delta rule (``ops/linear_attention.py``): the chunked form
the program runs against the recurrence that defines it — written here
a row at a time, and the benchmark's own (``benchmark/models/
qwen3_next.py`` ``delta_recurrence``) — forward and the gradients of
all five operands, at one, two and five chunks, chunks of 64 and
smaller, strong and weak decay, like and unlike keys; the Pallas
kernels (``ops/pallas_gated_delta.py``, interpreted) against the same
recurrence, against XLA's form under the layers' checkpoint, and the
choice between the two paths; the two faults a chunked program can
have told apart; and, where ``transformers`` and ``torch`` import, the
family's own modelling code at a tiny config on the same weights."""

import functools

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.models import qwen3_next as REF
from veles_tpu import resilience
from veles_tpu.ops import linear_attention as L
from veles_tpu.ops import moe as M
from veles_tpu.ops import pallas_gated_delta as PG
from veles_tpu.znicz import attention as Z

B, HK, HV, DK, DV = 2, 2, 4, 16, 8


def recurrence(q, k, v, g, beta):
    """The definition, over (B, S, H, …): decay, read, write, read."""
    q, k = (jnp.repeat(x, v.shape[2] // x.shape[2], axis=2)
            for x in (q, k))

    def row(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, kt)
        delta = (vt - read) * bt[..., None]
        state = state + kt[..., :, None] * delta[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state = jnp.zeros(v.shape[:1] + v.shape[2:3] + (q.shape[-1],
                                                    v.shape[-1]))
    return jnp.moveaxis(jax.lax.scan(row, state, xs)[1], 0, 1)


def operands(S, decay, like=0.0, seed=1, HK=HK):
    """q, k normalised as the layer hands them over; ``decay`` scales
    g (8: a row forgets nearly all; 0.01: hundreds of rows are
    remembered); ``like`` gives every key a common part."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, HK, DK))
    k = jax.random.normal(ks[1], (B, S, HK, DK)) + 3.0 * like
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / DK ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, HV, DV))
    g = -jnp.exp(jax.random.normal(ks[3], (B, S, HV))) * decay
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, HV)))
    return q, k, v, g, beta


@pytest.mark.parametrize("decay", [8.0, 0.01], ids=["strong", "weak"])
@pytest.mark.parametrize("S,chunk", [(64, 64), (128, 64), (320, 64),
                                     (80, 16), (40, 8)])
def test_chunked_rule_is_the_recurrence(S, chunk, decay):
    with jax.default_matmul_precision("highest"):
        args = operands(S, decay, like=float(S == 128))
        weight = jax.random.normal(jax.random.PRNGKey(9), (B, S, HV, DV))
        want = recurrence(*args)
        got = L.gated_delta_rule(*args, chunk=chunk)
        assert got.shape == (B, S, HV, DV) and got.dtype == jnp.float32
        numpy.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        grads = jax.grad(lambda *a: (L.gated_delta_rule(
            *a, chunk=chunk) * weight).sum(), argnums=(0, 1, 2, 3, 4))(
                *args)
        wants = jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                         argnums=(0, 1, 2, 3, 4))(*args)
        for name, a, b in zip("q k v g beta".split(), grads, wants):
            scale = float(jnp.abs(b).max())
            numpy.testing.assert_allclose(
                a, b, rtol=2e-4, atol=2e-4 * scale, err_msg=name)


def _gradients(rule, args, weight):
    return jax.grad(lambda *a: (rule(*a) * weight).sum(),
                    argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("decay", [8.0, 0.01], ids=["strong", "weak"])
@pytest.mark.parametrize("ratio", [1, 2])
@pytest.mark.parametrize("S", [64, 128, 320])
def test_the_kernels_are_the_recurrence(S, ratio, decay):
    """The three kernels, interpreted (one, two and five chunks: a
    block of one, of two, of five; one and two value heads a key
    head): ``o`` and the gradients of all five operands against the
    recurrence a row at a time, at the chunked form's tolerances."""
    with jax.default_matmul_precision("highest"):
        args = operands(S, decay, like=float(S == 128), HK=HV // ratio)
        weight = jax.random.normal(jax.random.PRNGKey(9), (B, S, HV, DV))
        rule = functools.partial(PG.gated_delta, interpret=True)
        got = rule(*args)
        assert got.shape == (B, S, HV, DV) and got.dtype == jnp.float32
        numpy.testing.assert_allclose(got, recurrence(*args), rtol=2e-5,
                                      atol=2e-5)
        for name, a, b in zip("q k v g beta".split(),
                              _gradients(rule, args, weight),
                              _gradients(recurrence, args, weight)):
            assert a.dtype == b.dtype and a.shape == b.shape, name
            numpy.testing.assert_allclose(
                a, b, rtol=2e-4, atol=2e-4 * float(jnp.abs(b).max()),
                err_msg=name)


def _steer_to_the_kernels(monkeypatch):
    """What a TPU would select, interpreted: the choice is the
    program's (``L._selects_pallas``), the steering the test's."""
    monkeypatch.setattr(L, "tpu_available", lambda: True)
    monkeypatch.setattr(PG, "LANE", 8)
    monkeypatch.setattr(PG, "gated_delta", functools.partial(
        PG.gated_delta, interpret=True))


def test_both_paths_agree_under_the_layers_checkpoint(monkeypatch):
    """A ``gated_delta`` layer under ``Z.checkpointed``, bfloat16
    operands: value and every parameter's gradient through the kernels
    against XLA's form — the kept names change what is saved, not
    what is computed — and each trace counted by the path it took."""
    spec = Z.layer_spec(
        norm="rms", bias=False, norm_eps=1e-6, operator="gated_delta",
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=16,
        linear_value_dim=8, conv_kernel=4, n_heads=2, ffn="gated-mlp",
        ffn_dim=16)
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 32))
    params = {name: 0.3 * jax.random.normal(next(keys), shape)
              for name, shape in Z.layer_param_shapes(spec, 32).items()}
    x = jax.random.normal(next(keys), (2, 128, 32))

    def loss(params, x):
        y = Z.checkpointed(lambda p, h: Z.layer_apply(
            spec, p, h, jnp.bfloat16)[0])(params, x)
        return (y * y).mean()

    def run():
        names = ("linear_attention.kernel.pallas",
                 "linear_attention.kernel.xla")
        before = [resilience.stats.get(name) for name in names]
        out = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)
        return out, {name: resilience.stats.get(name) - was
                     for name, was in zip(names, before)}

    (want, (wp, wx)), counted = run()
    assert counted == {"linear_attention.kernel.pallas": 0,
                       "linear_attention.kernel.xla": 1}
    _steer_to_the_kernels(monkeypatch)
    (got, (gp, gx)), counted = run()
    assert counted == {"linear_attention.kernel.pallas": 1,
                       "linear_attention.kernel.xla": 0}
    numpy.testing.assert_allclose(got, want, rtol=2e-3)

    def close(a, b, name):
        assert float(jnp.linalg.norm(a - b)) <= \
            0.02 * float(jnp.linalg.norm(b)) + 1e-6, name

    close(gx, wx, "x")
    for name in wp:
        close(gp[name], wp[name], name)


@pytest.mark.parametrize("case,selected", [
    ("the cell's", True), ("a CPU", False), ("Dk 64", False),
    ("Dv 64", False), ("a chunk of 32", False),
    ("rows that 64 does not divide", False)])
def test_the_path_is_chosen_by_platform_and_shape(monkeypatch, case,
                                                  selected):
    q, v, chunk = (1, 8192, 16, 128), (1, 8192, 32, 128), 64
    monkeypatch.setattr(L, "tpu_available", lambda: case != "a CPU")
    if case == "Dk 64":
        q = q[:3] + (64,)
    if case == "Dv 64":
        v = v[:3] + (64,)
    if case == "a chunk of 32":
        chunk = 32
    if case == "rows that 64 does not divide":
        q, v = (1, 8160) + q[2:], (1, 8160) + v[2:]
    assert L._selects_pallas(q, v, chunk) is selected


def test_the_benchmarks_recurrence_is_the_same_function():
    """``delta_recurrence`` (one sequence, blocks of rows) against the
    recurrence above, and its two faults against the chunked rule: a
    program that dropped the state between chunks, or ignored the
    decay, is neither."""
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = operands(128, 0.01)
        q, k = (jnp.repeat(x, HV // HK, axis=2) for x in (q, k))
        want = recurrence(q, k, v, g, beta)
        for b in range(B):
            got = REF.delta_recurrence(q[b], k[b], v[b], g[b], beta[b],
                                       block=32)
            numpy.testing.assert_allclose(got, want[b], rtol=2e-5,
                                          atol=2e-5)
        rule = L.gated_delta_rule(q, k, v, g, beta, chunk=32)[0]
        dropped = REF.delta_recurrence(q[0], k[0], v[0], g[0], beta[0],
                                       block=32, dropped=True)
        ignored = REF.delta_recurrence(q[0], k[0], v[0], 0.0 * g[0],
                                       beta[0], block=32)
        # the first block starts from nought either way
        numpy.testing.assert_allclose(dropped[:32], rule[:32], rtol=2e-5,
                                      atol=2e-5)
        size = float(jnp.abs(rule[32:]).mean())
        for fault in (dropped, ignored):
            assert float(jnp.abs(fault - rule)[32:].mean()) > 0.05 * size
        assert float(jnp.abs(dropped - ignored).mean()) > 0.05 * size
        # under a decay that forgets within a row the state carries
        # nothing, and dropping it shows nowhere
        q, k, v, g, beta = (x[0] for x in operands(128, 400.0))
        q, k = (jnp.repeat(x, HV // HK, axis=1) for x in (q, k))
        numpy.testing.assert_allclose(
            REF.delta_recurrence(q, k, v, g, beta, 32, dropped=True),
            REF.delta_recurrence(q, k, v, g, beta, 32), atol=1e-6)


def test_a_decay_that_kills_the_state_overflows_nowhere():
    q, k, v, g, beta = operands(128, 1.0)
    g = g.at[:, ::3].set(-400.0)
    out, grads = jax.value_and_grad(
        lambda *a: L.gated_delta_rule(*a).sum(), argnums=(0, 1, 2, 3, 4))(
            q, k, v, g, beta)
    assert bool(jnp.isfinite(out)) and all(
        bool(jnp.isfinite(x).all()) for x in grads)


def test_unit_lower_inverse_and_its_rule():
    with jax.default_matmul_precision("highest"):
        for n in (1, 7, 16, 24, 64):
            a = jnp.tril(jax.random.normal(jax.random.PRNGKey(n),
                                           (3, n, n)) * 0.3, -1)
            want = jnp.linalg.inv(jnp.eye(n) - a)
            numpy.testing.assert_allclose(L.unit_lower_inverse(a), want,
                                          rtol=1e-4, atol=1e-5)
            weight = jax.random.normal(jax.random.PRNGKey(2), a.shape)
            got = jax.grad(lambda a: (L.unit_lower_inverse(a) *
                                      weight).sum())(a)
            ref = jax.grad(lambda a: (jnp.linalg.inv(jnp.eye(n) - a) *
                                      weight).sum())(a)
            numpy.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)
        # sixty-four like keys at a step of one: the powers of A reach
        # 1e8 where the entries of the inverse stay under one
        a = -jnp.tril(jnp.full((64, 64), 0.9), -1)
        numpy.testing.assert_allclose(
            L.unit_lower_inverse(a), jnp.linalg.inv(jnp.eye(64) - a),
            atol=1e-4)


def test_rows_that_are_no_multiple_of_the_chunk_are_refused_by_name():
    q, k, v, g, beta = operands(48, 1.0)
    with pytest.raises(ValueError, match="48 rows in chunks of 64"):
        L.gated_delta_rule(q, k, v, g, beta)
    with pytest.raises(ValueError, match="4 value heads over 3"):
        L.gated_delta_rule(q[:, :, :1].repeat(3, 2), k[:, :, :1].repeat(
            3, 2), v, g, beta, chunk=16)


# -- the family's own modelling code ---------------------------------------------

def _tiny_config():
    transformers = pytest.importorskip("transformers")
    pytest.importorskip("torch")
    return transformers.Qwen3NextConfig(
        hidden_size=32, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=8, linear_conv_kernel_dim=4,
        moe_intermediate_size=24, shared_expert_intermediate_size=24,
        num_experts=8, num_experts_per_tok=3, norm_topk_prob=True,
        vocab_size=64, intermediate_size=48, rms_norm_eps=1e-6)


def _arr(t):
    return jnp.asarray(t.detach().numpy())


def test_transformers_recurrence_and_chunked_form_agree_with_ours():
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = operands(128, 0.05)
        # theirs take unnormalised q and k at the value heads' count
        ks = jax.random.split(jax.random.PRNGKey(3), 2)
        raw_q = jax.random.normal(ks[0], (B, 128, HV, DK))
        raw_k = jax.random.normal(ks[1], (B, 128, HV, DK))
        theirs = [fn(*(torch.tensor(numpy.asarray(x)) for x in
                       (raw_q, raw_k, v)),
                     g=torch.tensor(numpy.asarray(g)),
                     beta=torch.tensor(numpy.asarray(beta)),
                     use_qk_l2norm_in_kernel=True, **more)[0]
                  for fn, more in (
                      (modeling.torch_recurrent_gated_delta_rule,
                       {"initial_state": None,
                        "output_final_state": False}),
                      (modeling.torch_chunk_gated_delta_rule, {}))]

        def l2(x):
            return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) +
                                     1e-6)

        ours = L.gated_delta_rule(l2(raw_q) * DK ** -0.5, l2(raw_k), v,
                                  g, beta)
        for their in theirs:
            numpy.testing.assert_allclose(ours, _arr(their), rtol=2e-4,
                                          atol=2e-5)


def test_a_gated_delta_layer_is_transformers_gated_deltanet():
    """One whole ``Qwen3NextGatedDeltaNet`` on the program's weights:
    the checkpoint interleaves [q | k | v | z] and [b | a] a key head,
    the program lays them out flat — the same function."""
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    config = _tiny_config()
    with torch.no_grad():
        theirs = modeling.Qwen3NextGatedDeltaNet(config, 0).float()
        theirs.A_log.uniform_(-3.0, 1.0)
        theirs.dt_bias.uniform_(-4.0, 0.0)
        theirs.norm.weight.uniform_(0.5, 1.5)
    hk, hv, dk, dv = 2, 4, 8, 8
    r = hv // hk
    w = _arr(theirs.in_proj_qkvz.weight).T.reshape(32, hk, -1)
    parts = jnp.split(w, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    w_qkvz = jnp.concatenate([x.reshape(32, -1) for x in parts], axis=-1)
    ba = _arr(theirs.in_proj_ba.weight).T.reshape(32, hk, 2 * r)
    w_ba = jnp.concatenate([ba[..., :r].reshape(32, -1),
                            ba[..., r:].reshape(32, -1)], axis=-1)
    spec = Z.layer_spec(
        norm="rms", bias=False, norm_eps=1e-6, operator="gated_delta",
        linear_key_heads=hk, linear_value_heads=hv, linear_key_dim=dk,
        linear_value_dim=dv, conv_kernel=4, linear_chunk=16)
    params = {"w_qkvz": w_qkvz, "w_ba": w_ba,
              "w_conv": _arr(theirs.conv1d.weight)[:, 0],
              "a_log": _arr(theirs.A_log), "dt_bias": _arr(theirs.dt_bias),
              "gdn_norm_g": _arr(theirs.norm.weight),
              "w_out": _arr(theirs.out_proj.weight).T}
    assert {n: v.shape for n, v in params.items()} == {
        n: s for n, s in Z.layer_param_shapes(spec, 32).items()
        if n in params}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 32))
    with jax.default_matmul_precision("highest"):
        ours = Z._gated_delta_operator(
            spec, params, x, jnp.float32, lambda a, w: jnp.dot(a, w))
    with torch.no_grad():
        want = theirs(torch.tensor(numpy.asarray(x)))
    numpy.testing.assert_allclose(ours, _arr(want), rtol=2e-4, atol=2e-5)


def test_an_expert_layer_is_transformers_sparse_moe_block():
    """``Qwen3NextSparseMoeBlock`` (softmax over all the experts, top
    k, normalised; the shared expert behind its sigmoid gate) against
    ``moe_dropless(score="softmax")`` holding every expert, and the
    gated shared expert as ``layer_apply`` adds it."""
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip(
        "transformers.models.qwen3_next.modeling_qwen3_next")
    config = _tiny_config()
    theirs = modeling.Qwen3NextSparseMoeBlock(config).float()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    with torch.no_grad():
        want = _arr(theirs(torch.tensor(numpy.asarray(x)))[0])
    stack = lambda name: jnp.stack([                      # noqa: E731
        _arr(getattr(e, name).weight).T for e in theirs.experts])
    shared = theirs.shared_expert
    with jax.default_matmul_precision("highest"):
        flat = x.reshape(-1, 32)
        routed, stats = M.moe_dropless(
            flat, _arr(theirs.gate.weight).T, jnp.zeros(8),
            stack("gate_proj"), stack("up_proj"), stack("down_proj"),
            top_k=3, held=(0, 8), cdt=jnp.float32, eps=0.0,
            score="softmax")
        gate = jax.nn.sigmoid(flat @ _arr(
            theirs.shared_expert_gate.weight).T)
        both = routed + gate * ((
            jax.nn.silu(flat @ _arr(shared.gate_proj.weight).T) *
            (flat @ _arr(shared.up_proj.weight).T)) @
            _arr(shared.down_proj.weight).T)
    assert float(stats["landed"]) == 80 * 3
    numpy.testing.assert_allclose(both.reshape(x.shape), want, rtol=2e-4,
                                  atol=2e-5)
