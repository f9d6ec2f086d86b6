"""The attention fast path's parity gates (docs/attention.md).

Three independently-flagged stages attack the LM bench's attention
gap (BENCHNOTES r6); each is allowed to change the SPEED of the hot
path, never its math beyond a documented tolerance:

  * fused QKV — one (E, 3E) head-major projection per block: the
    seeded training step must match the unfused step (loss, grads —
    proven through the momentum update), snapshots must round-trip,
    and every serving surface (numpy mirror, jitted chain, native
    C++ runtime, KV-cache decode) must agree on the fused artifact;
  * bf16 score/probability intermediates — parity within the
    tolerance documented here (outputs ~1e-2 absolute at unit scale,
    grads <2e-2 relative), while m/l statistics stay f32 so
    fully-masked rows and the softmax tail survive;
  * the Pallas flash kernel — interpret-mode parity (f32 operands)
    against ``blockwise_attention``, the same oracle pallas_lrn
    pins, plus the silent-fallback dispatch contract off-TPU.

Geometries stay tiny (S<=64 dense, S=256 only for the kernel's
lane-width contract) — tier-1 budget discipline.
"""

import functools

import numpy
import pytest

import veles_tpu.prng as prng
from veles_tpu.launcher import Launcher


@pytest.fixture
def engine_knobs():
    """Restores the attention fast-path knobs to their defaults (the
    tests flip them; a leak would silently change every later test's
    math).  Kernel-mode defaults are "auto" since the r9 flip —
    restoring "xla" here would leak the OLD default forward."""
    from veles_tpu.config import root
    from veles_tpu.ops.attention import (DEFAULT_KERNEL_MODE,
                                         DEFAULT_RING_KERNEL_MODE)
    yield root.common.engine
    root.common.engine.fused_qkv = False
    root.common.engine.attention_dtype = "f32"
    root.common.engine.attention_kernel = DEFAULT_KERNEL_MODE
    root.common.engine.sp_ring_kernel = DEFAULT_RING_KERNEL_MODE
    root.common.engine.decode_kernel = "off"


def _rand(shape, seed=0):
    import jax.numpy as jnp
    return jnp.asarray(
        numpy.random.RandomState(seed).randn(*shape).astype("f"))


# -- fused QKV: layout + unit-level parity ------------------------------


def test_fuse_split_roundtrip():
    """fuse_qkv_arrays/split_qkv_arrays are exact inverses for
    weights, biases, and stage-stacked (L, E, O) params alike."""
    from veles_tpu.znicz.attention import (fuse_qkv_arrays,
                                           split_qkv_arrays)
    rng = numpy.random.RandomState(0)
    for shape in ((8, 8), (8,), (3, 8, 8)):
        wq, wk, wv = (rng.randn(*shape).astype("f") for _ in range(3))
        fused = fuse_qkv_arrays(wq, wk, wv, n_heads=2)
        assert fused.shape == shape[:-1] + (3 * shape[-1],)
        gq, gk, gv = split_qkv_arrays(fused, n_heads=2)
        numpy.testing.assert_array_equal(gq, wq)
        numpy.testing.assert_array_equal(gk, wk)
        numpy.testing.assert_array_equal(gv, wv)


def test_fused_layout_is_head_major():
    """The (E, 3E) column layout is [q_h | k_h | v_h] per head — the
    property that makes a Megatron column shard whole heads' q/k/v
    and the (B, S, H, 3, D) reshape correct."""
    from veles_tpu.znicz.attention import fuse_qkv_arrays
    E, H = 4, 2
    D = E // H
    wq = numpy.full((E, E), 1.0, "f")
    wk = numpy.full((E, E), 2.0, "f")
    wv = numpy.full((E, E), 3.0, "f")
    fused = fuse_qkv_arrays(wq, wk, wv, H)
    per_head = fused.reshape(E, H, 3, D)
    assert (per_head[:, :, 0, :] == 1.0).all()
    assert (per_head[:, :, 1, :] == 2.0).all()
    assert (per_head[:, :, 2, :] == 3.0).all()


def test_fused_layout_rewrites_the_leaf_names():
    """One source of the leaf names, in the order initialization
    draws them: the fused layout puts wqkv / bqkv where wq / bq
    stood and leaves the rest where it was."""
    from veles_tpu.znicz.attention import (TransformerBlock,
                                           _block_param_shapes)
    assert tuple(_block_param_shapes(8, 32)) == \
        TransformerBlock.PARAM_NAMES
    assert tuple(_block_param_shapes(8, 32, fused_qkv=True)) == (
        "ln1_g", "ln1_b", "wqkv", "wo", "bqkv", "bo",
        "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")


def test_fused_block_apply_matches_unfused():
    """Unit-level gate: transformer_block_apply with the fused
    (E, 3E) weight == the three-matmul block on the same numbers."""
    import jax.numpy as jnp
    from veles_tpu.znicz.attention import (fuse_qkv_arrays,
                                           transformer_block_apply)
    rng = numpy.random.RandomState(3)
    E, H, hidden = 16, 4, 32
    shapes = {
        "ln1_g": (E,), "ln1_b": (E,),
        "wq": (E, E), "wk": (E, E), "wv": (E, E), "wo": (E, E),
        "bq": (E,), "bk": (E,), "bv": (E,), "bo": (E,),
        "ln2_g": (E,), "ln2_b": (E,),
        "w1": (E, hidden), "b1": (hidden,),
        "w2": (hidden, E), "b2": (E,),
    }
    params = {n: jnp.asarray(0.1 * rng.randn(*s).astype("f"))
              for n, s in shapes.items()}
    fused = dict(params)
    for n in ("wq", "wk", "wv", "bq", "bk", "bv"):
        del fused[n]
    fused["wqkv"] = jnp.asarray(fuse_qkv_arrays(
        params["wq"], params["wk"], params["wv"], H))
    fused["bqkv"] = jnp.asarray(fuse_qkv_arrays(
        params["bq"], params["bk"], params["bv"], H))
    x = _rand((2, 8, E), seed=4)
    a = transformer_block_apply(params, x, H, True, jnp.float32)
    b = transformer_block_apply(fused, x, H, True, jnp.float32)
    numpy.testing.assert_allclose(numpy.asarray(a), numpy.asarray(b),
                                  rtol=1e-5, atol=1e-5)


# -- fused QKV: the seeded training-step gate ---------------------------


def _build_tinylm(**kwargs):
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    prng.reset()
    prng.get(0).seed(42)
    launcher = Launcher()
    kwargs.setdefault("max_epochs", 1)
    wf = TinyLMWorkflow(launcher, **kwargs)
    launcher.initialize()
    return launcher, wf


def _graft_fused_weights(src_wf, dst_wf):
    """Copies every trainable of the unfused ``src_wf`` into the
    fused ``dst_wf``, fusing wq/wk/wv (and biases) into wqkv/bqkv —
    the surgery that makes the two seeded steps comparable."""
    from veles_tpu.znicz.attention import fuse_qkv_arrays
    for src, dst in zip(src_wf.forwards, dst_wf.forwards):
        st = getattr(src, "trainables", {})
        for name, vec in getattr(dst, "trainables", {}).items():
            if name in ("wqkv", "bqkv"):
                parts = [st[n] for n in
                         (("wq", "wk", "wv") if name == "wqkv"
                          else ("bq", "bk", "bv"))]
                for p in parts:
                    p.map_read()
                value = fuse_qkv_arrays(
                    *[numpy.asarray(p.mem) for p in parts],
                    n_heads=dst.n_heads)
            else:
                st[name].map_read()
                value = numpy.asarray(st[name].mem)
            vec.map_write()
            vec.mem[...] = value


def _one_step(wf, key_seed=0):
    import jax
    wf.loader.serve_next_minibatch()
    wf.begin_tick()
    metrics = wf.compiler.execute(key=jax.random.PRNGKey(key_seed),
                                  training=True)
    host = {k: float(jax.device_get(v)) for k, v in metrics.items()}
    params = {n: numpy.asarray(jax.device_get(v.devmem))
              for n, v in wf.compiler._param_vecs.items()}
    return host, params


def test_fused_seeded_step_matches_unfused(f32_precision,
                                           engine_knobs):
    """THE fused-QKV parity gate: one seeded training step with the
    fused projection == the unfused step — loss, grad_norm, and
    every updated parameter (the momentum update exposes the grads;
    wqkv is split back for the comparison)."""
    from veles_tpu.znicz.attention import split_qkv_arrays
    _, ref_wf = _build_tinylm()
    _, fused_wf = _build_tinylm(fused_qkv=True)
    blk = fused_wf.forwards[1]
    assert "wqkv" in blk.params and "wq" not in blk.params
    _graft_fused_weights(ref_wf, fused_wf)
    ref_metrics, ref_params = _one_step(ref_wf)
    got_metrics, got_params = _one_step(fused_wf)
    assert abs(ref_metrics["loss"] - got_metrics["loss"]) < 1e-5, \
        (ref_metrics, got_metrics)
    assert abs(ref_metrics["grad_norm"] - got_metrics["grad_norm"]) \
        < 1e-4, (ref_metrics, got_metrics)
    for name, ref in ref_params.items():
        if any(name.endswith(s) for s in ("wq", "wk", "wv",
                                          "bq", "bk", "bv")):
            continue  # compared via the fused split below
        assert name in got_params, (name, sorted(got_params))
        numpy.testing.assert_allclose(
            ref, got_params[name], rtol=2e-5, atol=2e-6,
            err_msg="param %s diverged under fused qkv" % name)
    fused_names = [n for n in got_params if n.endswith("wqkv")]
    assert fused_names
    for name in fused_names:
        prefix = name[:-len("wqkv")]
        for fused_n, parts in (("wqkv", ("wq", "wk", "wv")),
                               ("bqkv", ("bq", "bk", "bv"))):
            split = split_qkv_arrays(got_params[prefix + fused_n],
                                     blk.n_heads)
            for part, arr in zip(parts, split):
                numpy.testing.assert_allclose(
                    ref_params[prefix + part], arr, rtol=2e-5,
                    atol=2e-6,
                    err_msg="updated %s diverged through the fused "
                            "projection" % part)


def test_fused_knob_from_engine_config(engine_knobs):
    """root.common.engine.fused_qkv flips the layout when the unit
    kwarg is absent — the --attn-fused-qkv CLI path."""
    engine_knobs.fused_qkv = True
    _, wf = _build_tinylm()
    assert "wqkv" in wf.forwards[1].params
    engine_knobs.fused_qkv = False
    _, wf = _build_tinylm()
    assert "wq" in wf.forwards[1].params


# -- bf16 intermediates -------------------------------------------------


def test_bf16_intermediates_within_tolerance():
    """The documented bf16-mode tolerance: outputs within 3e-2
    absolute at unit scale (the score/probability tensors round to
    bf16 once per block), gradients within 2e-2 relative."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import attention as A
    q, k, v = (_rand((2, 64, 4, 16), seed=s) for s in (1, 2, 3))
    for causal in (False, True):
        f = A.attention(q, k, v, causal=causal, precision="f32")
        b = A.attention(q, k, v, causal=causal, precision="bf16")
        assert b.dtype == f.dtype  # output dtype follows the input
        numpy.testing.assert_allclose(
            numpy.asarray(f), numpy.asarray(b), atol=3e-2)
        blk = A.blockwise_attention(q, k, v, block_size=16,
                                    causal=causal, precision="bf16")
        numpy.testing.assert_allclose(
            numpy.asarray(f), numpy.asarray(blk), atol=3e-2)

    def grads(precision):
        def loss(q, k, v):
            return (A.blockwise_attention(
                q, k, v, block_size=16, causal=True,
                precision=precision) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for gf, gb in zip(grads("f32"), grads("bf16")):
        scale = float(jnp.abs(gf).max())
        assert float(jnp.abs(gf - gb).max()) <= 2e-2 * scale


def test_bf16_fully_masked_rows_stay_finite():
    """The f32 m/l statistics keep the fully-masked-row guard intact
    in bf16 mode (kv_len=0 keys for some rows would otherwise
    produce NaN through exp(NEG_INF - NEG_INF))."""
    from veles_tpu.ops import attention as A
    q, k, v = (_rand((1, 16, 2, 8), seed=s) for s in (4, 5, 6))
    out = A.blockwise_attention(q, k, v, block_size=8, causal=False,
                                kv_len=4, precision="bf16")
    assert numpy.isfinite(numpy.asarray(out)).all()


def test_attention_dtype_knob_resolution(engine_knobs):
    import jax.numpy as jnp
    from veles_tpu.ops.attention import attention_compute_dtype
    assert attention_compute_dtype() == jnp.float32
    engine_knobs.attention_dtype = "bf16"
    assert attention_compute_dtype() == jnp.bfloat16
    assert attention_compute_dtype("f32") == jnp.float32  # arg wins
    engine_knobs.attention_dtype = "f32"
    assert attention_compute_dtype("bf16") == jnp.bfloat16


# -- the Pallas kernel --------------------------------------------------

PALLAS_GEOM = (2, 256, 2, 128)  # B, S, H, D — lane-native head dim


def _pallas_ref_pair(causal, kv_len=None, seed=0):
    import jax.numpy as jnp
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import pallas_attention as PA
    q, k, v = (_rand(PALLAS_GEOM, seed=seed + i) for i in range(3))
    out = PA.pallas_attention(q, k, v, causal=causal, kv_len=kv_len,
                              operand_dtype=jnp.float32,
                              interpret=True)
    ref = A.blockwise_attention(q, k, v, block_size=128,
                                causal=causal, kv_len=kv_len)
    return out, ref, (q, k, v)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_pallas_forward_matches_blockwise(causal):
    """Kernel parity oracle (interpret mode, f32 operands): the
    geometry-tuned flash kernel == blockwise_attention to float
    noise."""
    out, ref, _ = _pallas_ref_pair(causal)
    numpy.testing.assert_allclose(
        numpy.asarray(out), numpy.asarray(ref), rtol=2e-5,
        atol=2e-5)


@pytest.mark.slow
def test_pallas_kv_len_masks_padding():
    out, ref, _ = _pallas_ref_pair(False, kv_len=200, seed=7)
    numpy.testing.assert_allclose(
        numpy.asarray(out), numpy.asarray(ref), rtol=2e-5,
        atol=2e-5)
    assert numpy.isfinite(numpy.asarray(out)).all()


@pytest.mark.slow
def test_pallas_gradients_match_blockwise():
    """The custom-VJP backward (recompute-from-lse, dq + dk/dv
    kernels) == autodiff through the reference scan."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import pallas_attention as PA
    q, k, v = (_rand(PALLAS_GEOM, seed=10 + i) for i in range(3))

    def loss_pallas(q, k, v):
        return (PA.pallas_attention(
            q, k, v, causal=True, operand_dtype=jnp.float32,
            interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (A.blockwise_attention(
            q, k, v, block_size=128, causal=True) ** 2).sum()

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, ("dq", "dk", "dv")):
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) <= 2e-5 * scale + 1e-6, \
            "pallas %s diverged from the reference" % name


@pytest.mark.parametrize("shape,block,kv_len", [
    ((1, 128, 1, 128), None, None),
    ((1, 256, 2, 64), 128, None),
    ((1, 256, 2, 64), 128, 200),
], ids=["lane-tile", "head64", "head64+kv_len"])
def test_pallas_minimal_geometry_parity_tier1(shape, block, kv_len):
    """Tier-1 kernel gate: forward and backward match the blockwise
    reference in interpret mode at the contract's smallest geometry
    (B=1, H=1, S=D=128 — one lane tile) and at head size 64 (half a
    lane row: OPT-1.3B, GPT-2, BERT), there over two query and two
    key blocks so the online softmax and both backward kernels walk
    more than one tile, causal and with the ``kv_len`` bound."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import pallas_attention as PA
    q, k, v = (_rand(shape, seed=50 + i) for i in range(3))

    def run(fn):
        def loss(q, k, v):
            return (fn(q, k, v) ** 2).sum()
        out = fn(q, k, v)
        return out, jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    out_p, g_p = run(lambda q, k, v: PA.pallas_attention(
        q, k, v, causal=True, kv_len=kv_len, block_q=block,
        block_k=block, operand_dtype=jnp.float32, interpret=True))
    out_r, g_r = run(lambda q, k, v: A.blockwise_attention(
        q, k, v, block_size=128, causal=True, kv_len=kv_len))
    numpy.testing.assert_allclose(
        numpy.asarray(out_p), numpy.asarray(out_r), rtol=2e-5,
        atol=2e-5)
    for a, b in zip(g_p, g_r):
        numpy.testing.assert_allclose(
            numpy.asarray(a), numpy.asarray(b), rtol=2e-4,
            atol=2e-5)


def _all_tiles(causal, *_origins_and_blocks, window=None):
    """The schedule before the causal walk: every block of the range,
    the causal mask built on each."""
    return ((0, _origins_and_blocks[-1], causal),)


@pytest.mark.parametrize("kv_len", [None, "last tile",
                                    "earlier tile"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
@pytest.mark.parametrize("S", [1024, 2048])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 512),
                                    (512, 1024)],
                         ids=lambda b: "%dx%d" % b)
def test_causal_tile_schedule_is_bit_equal_to_all_tiles(
        monkeypatch, blocks, S, causal, kv_len):
    """The kernels walk only the tiles a causal call can see and mask
    only those the diagonal crosses; what they skip contributed
    exact zeros, so ``out``, ``lse``, ``dq``, ``dk`` and ``dv`` are
    the SAME BITS as the all-tiles schedule gives (the bounds forced
    to the full range, the causal mask on every tile).  Interpret
    mode, f32 operands, one head of 64; ``kv_len`` keeps its own
    mask, in the last key tile and in an earlier one."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import pallas_attention as PA
    bq, bk = blocks
    if kv_len is not None:
        kv_len = S - 37 if kv_len == "last tile" else S // 2 - 37
    q, k, v, do = (_rand((1, S, 1, 64), seed=70 + i)
                   for i in range(4))
    dlse = _rand((1, S, 1), seed=75)

    def run():
        (out, lse), vjp = jax.vjp(
            lambda q, k, v: PA.flash_chunk(
                q, k, v, causal=causal, kv_len=kv_len, block_q=bq,
                block_k=bk, operand_dtype=jnp.float32,
                interpret=True), q, k, v)
        return (out, lse) + vjp((do, dlse))

    got = run()
    monkeypatch.setattr(PA, "_key_stretches", _all_tiles)
    monkeypatch.setattr(PA, "_query_stretches", _all_tiles)
    want = run()
    for a, b, name in zip(got, want, ("out", "lse", "dq", "dk",
                                      "dv")):
        a, b = numpy.asarray(a), numpy.asarray(b)
        assert numpy.isfinite(a).all(), name
        assert numpy.array_equal(a, b), \
            "%s: %d elements differ from the all-tiles schedule" % (
                name, (a != b).sum())


@pytest.mark.parametrize(
    "S,bq,bk,qoff,koff,causal,visited,total", [
        (2048, 512, 1024, 0, 0, True, 6, 8),
        (2048, 512, 512, 0, 0, True, 10, 16),
        (2048, 256, 256, 0, 0, True, 36, 64),
        (2048, 256, 512, 0, 0, True, 20, 32),
        (2048, 128, 128, 0, 0, True, 136, 256),
        (2048, 1024, 1024, 0, 0, True, 3, 4),
        (1024, 512, 1024, 0, 0, True, 2, 2),
        (1024, 512, 512, 0, 0, True, 3, 4),
        (2048, 512, 512, 0, 0, False, 16, 16),
        # a ring step's chunk: wholly before the queries, across the
        # diagonal one block up, wholly after them
        (512, 128, 128, 512, 0, True, 16, 16),
        (512, 128, 128, 0, 0, True, 10, 16),
        (512, 128, 128, 64, 0, True, 13, 16),
        (512, 128, 128, 0, 64, True, 10, 16),
        (512, 128, 128, 0, 512, True, 0, 16),
        (512, 128, 128, 0, 511, True, 1, 16),
    ])
def test_flash_tiles_table(S, bq, bk, qoff, koff, causal, visited,
                           total):
    """The pure tile count, pinned: what one (batch·head) slice's
    forward visits and what its grid holds."""
    from veles_tpu.ops.pallas_attention import flash_tiles
    assert flash_tiles(S, S, bq, bk, qoff, koff, causal) == \
        (visited, total)


def test_flash_tiles_are_exactly_the_visible_ones():
    """Against the definition, for every query and key block of
    unequal extents and origins either side of the diagonal: a tile
    is visited iff it holds a column ≤ one of its rows, masked iff
    it also holds a column > one of its rows; the dk/dv kernel's
    walk visits the same tiles from the other side."""
    from veles_tpu.ops import pallas_attention as PA
    bq, bk, nq, nk = 32, 64, 6, 5
    for qoff in (0, 17, 64, 200, 400):
        for koff in (0, 31, 64, 192, 330, 1000):
            seen_q = set()
            for i in range(nq):
                r0 = qoff + i * bq
                walked = {}
                for lo, hi, diagonal in PA._key_stretches(
                        True, r0, bq, koff, bk, nk):
                    walked.update((j, diagonal)
                                  for j in range(lo, hi))
                for j in range(nk):
                    c0 = koff + j * bk
                    visible = c0 <= r0 + bq - 1
                    crossed = visible and c0 + bk - 1 > r0
                    assert (j in walked) == visible, (qoff, koff, i, j)
                    if visible:
                        assert walked[j] == crossed
                        seen_q.add((i, j))
            seen_k = set()
            for j in range(nk):
                for lo, hi, diagonal in PA._query_stretches(
                        True, koff + j * bk, bk, qoff, bq, nq):
                    for i in range(lo, hi):
                        assert diagonal == (
                            koff + (j + 1) * bk - 1 > qoff + i * bq)
                        seen_k.add((i, j))
            assert seen_k == seen_q
            assert PA.flash_tiles(nq * bq, nk * bk, bq, bk, qoff,
                                  koff) == (len(seen_q), nq * nk)


def test_flash_tile_counters_after_a_trace():
    """Each trace of ``pallas_attention`` counts one (batch·head)
    slice's forward schedule into ``attention.flash.tiles_visited``
    / ``.tiles_total`` — what the tile-count function says for the
    call's geometry and the block shape it chose."""
    import jax
    import jax.numpy as jnp
    from veles_tpu import resilience
    from veles_tpu.ops import pallas_attention as PA

    def counts():
        return tuple(resilience.stats.get("attention.flash.tiles_" + n)
                     for n in ("visited", "total"))

    x = jax.ShapeDtypeStruct((4, 2048, 32, 64), jnp.bfloat16)
    for causal, kwargs in ((True, {}), (False, {}),
                           (True, {"block_q": 512, "block_k": 512})):
        was = counts()
        jax.eval_shape(lambda q, k, v: PA.pallas_attention(
            q, k, v, causal=causal, interpret=True, **kwargs),
            x, x, x)
        want = PA.flash_tiles(
            2048, 2048, kwargs.get("block_q", PA.DEFAULT_BLOCK_Q),
            kwargs.get("block_k", PA.DEFAULT_BLOCK_K), causal=causal)
        assert tuple(b - a for a, b in zip(was, counts())) == want
        assert want[0] < want[1] if causal else want[0] == want[1]
    assert want == (10, 16)


def test_pallas_supports_contract():
    from veles_tpu.ops.pallas_attention import (
        supports, supports_decode, supports_ring)
    good = (2, 256, 2, 128)
    assert supports(good, good)
    # Head size: 64, or a multiple of 128 up to 512 — nothing else.
    half = (2, 256, 2, 64)
    assert supports(half, half)
    assert supports(half, half, kv_len=200)
    assert supports((2, 256, 2, 512), (2, 256, 2, 512))
    for D in (32, 96, 192, 640):
        assert not supports((2, 256, 2, D), (2, 256, 2, D)), D
    assert not supports((2, 100, 2, 64), (2, 100, 2, 64))  # S%128
    assert not supports(half, (2, 512, 2, 64))  # cross-attention
    # The ring and decode contracts stay lane-native: no cell runs
    # them at head 64 yet.
    assert not supports_ring(half, half)
    assert not supports_decode((2, 1, 2, 64), half)
    assert not supports((2, 100, 2, 128), (2, 100, 2, 128))  # S%128
    assert not supports(good, (2, 512, 2, 128))  # cross-attention
    assert not supports((2, 256, 128), (2, 256, 128))  # rank
    assert supports(good, good, kv_len=200)
    assert not supports(good, good, kv_len=object())


def test_kernel_not_selected_off_tpu():
    """Dispatch selects by PLATFORM: on this CPU backend the flash
    kernel is never selected, whatever the knob and however well the
    geometry fits — the XLA formulation is what runs."""
    from veles_tpu.ops import attention as A
    from veles_tpu.backends import tpu_available
    assert tpu_available() is False
    assert A._selects_pallas(PALLAS_GEOM, PALLAS_GEOM,
                             mode="pallas") is False


def test_platform_check_has_one_home():
    """Every kernel dispatch asks ``backends.tpu_available``: each
    module that selects a kernel holds that one function under the
    name the tests steer (``monkeypatch.setattr(A, "tpu_available",
    …)``), and none keeps a copy of its own."""
    from veles_tpu import backends
    from veles_tpu.ops import attention as A, moe as M, pallas_lrn as L
    for module in (A, M, L):
        assert module.tpu_available is backends.tpu_available


@pytest.mark.parametrize("head,selected", [(64, True), (128, True),
                                           (32, False), (96, False)])
def test_selects_pallas_by_head_size_on_tpu(monkeypatch, head,
                                            selected):
    """On a TPU (the platform is stubbed here) the dispatch's one
    decision follows ``supports()``: head 64 takes the kernel through
    ``attention`` like head 128, other sub-lane heads the XLA
    formulation — and "xla" pins the formulation whatever the head."""
    from veles_tpu.ops import attention as A
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    shape = (4, 2048, 32, head)
    assert A._selects_pallas(shape, shape) is selected
    assert A._selects_pallas(shape, shape, mode="xla") is False


def test_kernel_knob_dispatch(engine_knobs, monkeypatch):
    """attention_kernel="pallas" routes blockwise_attention through
    the kernel on a TPU (the platform is stubbed and the kernel runs
    in interpret mode here), selects the XLA formulation when the
    geometry is out of contract, and never engages under "xla"."""
    import jax.numpy as jnp
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import pallas_attention as PA
    q, k, v = (_rand(PALLAS_GEOM, seed=20 + i) for i in range(3))
    ref = A.blockwise_attention(q, k, v, block_size=128, causal=True)

    calls = []
    real = PA.pallas_attention

    def fake_kernel(q, k, v, causal=False, kv_len=None,
                    operand_dtype=None, window=None):
        calls.append(q.shape)
        return real(q, k, v, causal=causal, kv_len=kv_len,
                    window=window, operand_dtype=jnp.float32,
                    interpret=True)

    monkeypatch.setattr(PA, "pallas_attention", fake_kernel)
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    engine_knobs.attention_kernel = "pallas"
    out = A.blockwise_attention(q, k, v, block_size=128, causal=True)
    assert len(calls) == 1
    numpy.testing.assert_allclose(
        numpy.asarray(out), numpy.asarray(ref), rtol=2e-5,
        atol=2e-5)
    # Geometry outside the contract: the XLA path, no kernel call.
    q2, k2, v2 = (_rand((2, 32, 2, 16), seed=30 + i)
                  for i in range(3))
    A.blockwise_attention(q2, k2, v2, block_size=16, causal=True)
    assert len(calls) == 1
    # Under a mesh the selected kernel runs inside shard_map (GSPMD
    # cannot partition a Mosaic call): each of the two data shards
    # sees ITS batch row, and the result is the reference's.
    from veles_tpu.parallel import make_mesh
    mesh = make_mesh(axes={"data": 2})
    out = A.mesh_attention(q, k, v, mesh, causal=True,
                           batch_axis="data", head_axis="model")
    assert calls[1:] == [(1,) + PALLAS_GEOM[1:]]
    numpy.testing.assert_allclose(
        numpy.asarray(out), numpy.asarray(ref), rtol=2e-5,
        atol=2e-5)
    # "xla" never touches the kernel even on a TPU.
    engine_knobs.attention_kernel = "xla"
    A.blockwise_attention(q, k, v, block_size=128, causal=True)
    A.mesh_attention(q, k, v, mesh, causal=True, batch_axis="data")
    assert len(calls) == 2


def test_kernel_knob_rejects_unknown_mode(engine_knobs):
    from veles_tpu.ops import attention as A
    engine_knobs.attention_kernel = "cuda"
    q = _rand((1, 16, 2, 8), seed=40)
    with pytest.raises(ValueError, match="kernel mode"):
        A.attention(q, q, q, causal=True)


# -- fused artifact: every serving surface ------------------------------


@pytest.fixture(scope="module")
def fused_artifacts(tmp_path_factory):
    """An unfused and a fused TinyLM artifact carrying THE SAME
    weights (the fused workflow gets the unfused one's params fused
    in before export) — what makes decode comparisons exact."""
    from veles_tpu.export import export_workflow
    tmp = tmp_path_factory.mktemp("fastpath")
    prng.reset()
    prng.get(0).seed(3)
    launcher = Launcher()
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    ref_wf = TinyLMWorkflow(launcher, n_blocks=2, max_epochs=8)
    launcher.initialize()
    launcher.run()
    assert ref_wf.decision.min_validation_err < 0.05
    _, fused_wf = _build_tinylm(n_blocks=2, fused_qkv=True)
    _graft_fused_weights(ref_wf, fused_wf)
    ref_path = str(tmp / "ref.veles.tgz")
    fused_path = str(tmp / "fused.veles.tgz")
    export_workflow(ref_wf, ref_path)
    export_workflow(fused_wf, fused_path)
    return ref_path, fused_path


def test_fused_export_all_paths_agree(fused_artifacts):
    """The fused artifact carries wqkv/bqkv and every runtime —
    numpy mirror, jitted jax chain, native C++ — agrees with the
    unfused artifact's forward on the same weights."""
    from veles_tpu.export import ExportedModel
    from veles_tpu.native import NativeModel
    ref_path, fused_path = fused_artifacts
    ref = ExportedModel(ref_path)
    fused = ExportedModel(fused_path)
    blocks = [u for u in fused.units
              if u["type"] == "transformer_block"]
    assert blocks and all("wqkv" in b["params"] and
                          "wq" not in b["params"] for b in blocks)
    x = numpy.random.RandomState(0).randint(
        0, 16, (4, 32)).astype(numpy.float32)
    want = ref.forward_numpy(x)
    a = fused.forward_numpy(x)
    b = numpy.asarray(fused.forward(x))
    numpy.testing.assert_allclose(a, want, rtol=1e-4, atol=1e-4)
    numpy.testing.assert_allclose(b, want, rtol=2e-3, atol=2e-3)
    nat = NativeModel(fused_path)
    c = nat.forward(x)
    numpy.testing.assert_allclose(c, want.reshape(4, -1), rtol=1e-4,
                                  atol=1e-4)


def test_fused_kv_cache_greedy_decode_unchanged(fused_artifacts):
    """The KV-cache gate: greedy decode from the fused artifact is
    TOKEN-IDENTICAL to the unfused artifact's, through both the
    bucketed serving path (generate) and the exact-length program
    (return_logits)."""
    from veles_tpu.export import ExportedModel
    ref_path, fused_path = fused_artifacts
    ref = ExportedModel(ref_path)
    fused = ExportedModel(fused_path)
    prompt = numpy.array([[7, 3, 1, 4, 1, 5, 9, 2],
                          [2, 6, 5, 3, 5, 8, 9, 7]], numpy.int32)
    want = ref.generate(prompt, max_new_tokens=6)
    got = fused.generate(prompt, max_new_tokens=6)
    numpy.testing.assert_array_equal(want, got)
    got_exact, _ = fused.generate(prompt, 6, return_logits=True)
    numpy.testing.assert_array_equal(want, got_exact)
    # The recall task still solves through the fused decode.
    assert (got[:, 8:] == prompt[:, :1]).all()


def test_serving_ignores_fastpath_knobs(engine_knobs,
                                        fused_artifacts):
    """The serving surfaces pin f32/XLA attention: flipping the
    attention_dtype/attention_kernel knobs in the process must not
    change a single deployed bit (forward OR greedy decode)."""
    from veles_tpu.export import ExportedModel
    ref_path, _ = fused_artifacts
    x = numpy.random.RandomState(2).randint(
        0, 16, (2, 32)).astype(numpy.float32)
    prompt = numpy.array([[7, 3, 1, 4, 1, 5, 9, 2]], numpy.int32)
    base_fwd = numpy.asarray(ExportedModel(ref_path).forward(x))
    base_gen = ExportedModel(ref_path).generate(prompt, 4)
    engine_knobs.attention_dtype = "bf16"
    engine_knobs.attention_kernel = "auto"
    model = ExportedModel(ref_path)  # fresh jit under the knobs
    numpy.testing.assert_array_equal(
        numpy.asarray(model.forward(x)), base_fwd)
    numpy.testing.assert_array_equal(
        model.generate(prompt, 4), base_gen)


def test_fused_snapshot_roundtrip():
    """A fused workflow pickles/resumes with its layout intact —
    the construction-frozen fused_qkv flag and the wqkv Vector both
    survive."""
    import pickle
    launcher, wf = _build_tinylm(max_epochs=2, fused_qkv=True)
    launcher.run()
    wf2 = pickle.loads(pickle.dumps(wf))
    assert wf2.forwards[1].fused_qkv
    a = wf.forwards[1].params["wqkv"]
    a.map_read()
    b = wf2.forwards[1].params["wqkv"]
    b.map_read()
    numpy.testing.assert_array_equal(numpy.array(a.mem),
                                     numpy.array(b.mem))
