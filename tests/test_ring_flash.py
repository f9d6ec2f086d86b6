"""Ring-flash attention + flash-decode gates (ISSUE 13).

The Pallas flash kernel's public contract now carries the running
softmax statistics — ``flash_chunk`` returns ``(out, lse)`` partials
with GLOBAL causal offsets and ``merge_partials`` folds them by lse —
so the ring sequence-parallel path runs the kernel per ppermuted
shard instead of the lax ``_block_update`` scan, and serving's
one-token decode steps ride a k/v-split decode variant.  Everything
here runs the INTERPRET kernel (the math, not the TPU lowering) at
tiny tier-1 geometry, pinned against the same oracles every other
attention formulation shares: ``attention`` / ``blockwise_attention``
/ the lax ``ring_attention``; compiled-lowering coverage rides the
on-chip probes exactly like pallas_lrn.

Includes the stage-flip parity gates: kernel-mode defaults are
"auto" since r9 (docs/attention.md "Defaults after the r9 flip"),
and the default dispatch must be a no-op where the platform cannot
win (this CPU box) — covered bit-for-bit below.
"""

import numpy
import pytest

from veles_tpu.parallel import make_mesh


def _rand(shape, seed=0):
    import jax.numpy as jnp
    return jnp.asarray(
        numpy.random.RandomState(seed).randn(*shape).astype("f"))


def _qkv(B=2, S=32, H=3, D=5, seed=0):
    return tuple(_rand((B, S, H, D), seed=seed + i) for i in range(3))


# -- flash_chunk: the resumable contract --------------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_flash_chunk_matches_blockwise(causal):
    """One chunk covering the whole sequence == the blockwise oracle,
    and the returned lse is the true per-row logsumexp."""
    import jax.numpy as jnp
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import pallas_attention as PA
    q, k, v = _qkv(S=16, seed=3)
    out, lse = PA.flash_chunk(q, k, v, causal=causal,
                              operand_dtype=jnp.float32,
                              interpret=True)
    ref = A.blockwise_attention(q, k, v, block_size=8, causal=causal)
    numpy.testing.assert_allclose(numpy.asarray(out),
                                  numpy.asarray(ref), rtol=2e-5,
                                  atol=2e-5)
    # lse oracle: logsumexp of the (masked, scaled) score rows.
    import jax
    scores = jnp.einsum("bqhd,bkhd->bqhk", q, k) / (q.shape[-1] **
                                                    0.5)
    if causal:
        S = q.shape[1]
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        scores = jnp.where(mask[None, :, None, :], scores, -1e30)
    want = jax.nn.logsumexp(scores, axis=-1)
    numpy.testing.assert_allclose(numpy.asarray(lse),
                                  numpy.asarray(want), rtol=2e-5,
                                  atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_merge_reconstructs_full(causal):
    """Two chunks with global k offsets, merged by lse == full
    attention — fwd AND bwd (the dlse cotangent path through the
    custom VJP is what the gradient exercises)."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import pallas_attention as PA
    q, k, v = _qkv(S=24, seed=7)

    def chunked(q, k, v):
        carry = None
        for j, off in ((0, 0), (1, 12)):
            carry = PA.flash_resume(
                carry, q, k[:, off:off + 12], v[:, off:off + 12],
                causal=causal, q_offset=0, k_offset=off,
                operand_dtype=jnp.float32, interpret=True)
        return carry[0]

    full = A.attention(q, k, v, causal=causal, kernel="xla")
    numpy.testing.assert_allclose(numpy.asarray(chunked(q, k, v)),
                                  numpy.asarray(full), rtol=2e-5,
                                  atol=2e-5)
    gc = jax.grad(lambda *o: (chunked(*o) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(
        lambda *o: (A.attention(*o, causal=causal, kernel="xla")
                    ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gc, gf, ("dq", "dk", "dv")):
        numpy.testing.assert_allclose(
            numpy.asarray(a), numpy.asarray(b), rtol=2e-4,
            atol=2e-5, err_msg="chunked %s diverged" % name)


def test_merge_partials_handles_void_chunk():
    """A fully-masked chunk (lse ≈ −1e30) merges as exact weight
    zero — finite everywhere, the ring's early-step contract for
    strictly-future shards."""
    import jax.numpy as jnp
    from veles_tpu.ops import pallas_attention as PA
    o = _rand((1, 4, 2, 3), seed=1)
    lse = jnp.zeros((1, 4, 2))
    void_o = jnp.zeros_like(o)
    void_lse = jnp.full((1, 4, 2), PA.NEG_INF)
    out, new_lse = PA.merge_partials(o, lse, void_o, void_lse)
    assert numpy.isfinite(numpy.asarray(out)).all()
    numpy.testing.assert_allclose(numpy.asarray(out),
                                  numpy.asarray(o), rtol=1e-6)
    numpy.testing.assert_allclose(numpy.asarray(new_lse),
                                  numpy.asarray(lse), atol=1e-6)


# -- the causal tile schedule under traced offsets -----------------------

#: Queries at global rows 28..43 against three 24-key chunks: columns
#: 0..23 lie wholly before them, 24..47 cross the diagonal, 48..71
#: lie wholly after.  Sq (16) ≠ Sk (24); 8 x 8 tiles, so a chunk is
#: 2 x 3 of them.
_Q_OFF, _SQ, _SK = 28, 16, 24
_CHUNKS = {"before": 0, "across": 24, "after": 48}


def _chunk_operands(seed=11):
    q = _rand((2, _SQ, 2, 4), seed=seed)
    k, v = (_rand((2, 3 * _SK, 2, 4), seed=seed + i) for i in (1, 2))
    return q, k, v


def _dense_partial(q, k, v, q_off, k_off):
    """One chunk's partial by the definition: (out, lse) over the
    columns a row may see; a row that sees none reads out 0."""
    import jax
    import jax.numpy as jnp
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k) / (q.shape[-1] ** 0.5)
    rows = q_off + jnp.arange(q.shape[1])[:, None]
    cols = k_off + jnp.arange(k.shape[1])[None, :]
    mask = (rows >= cols)[None, :, None, :]
    s = jnp.where(mask, s, -1e30)
    p = jnp.where(mask, jax.nn.softmax(s, axis=-1), 0.0)
    return (jnp.einsum("bqhk,bkhd->bqhd", p, v),
            jax.nn.logsumexp(s, axis=-1))


def _jit_chunk():
    """A fresh jitted chunk (a new trace each time: the schedule is
    read when the kernels are traced) whose offsets are TRACED."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import pallas_attention as PA

    def chunk(q, k, v, q_off, k_off):
        return PA.flash_chunk(q, k, v, causal=True, q_offset=q_off,
                              k_offset=k_off, block_q=8, block_k=8,
                              operand_dtype=jnp.float32,
                              interpret=True)

    def with_grads(q, k, v, do, dlse, q_off, k_off):
        part, vjp = jax.vjp(
            lambda q, k, v: chunk(q, k, v, q_off, k_off), q, k, v)
        return part + vjp((do, dlse))

    return jax.jit(with_grads)


@pytest.mark.parametrize("where", list(_CHUNKS))
def test_flash_chunk_walks_by_traced_offsets(monkeypatch, where):
    """The kernels read their loop bounds from the TRACED origins: a
    chunk wholly before the queries is walked whole and unmasked, one
    across the diagonal up to it, one wholly after not at all — and
    that last one still writes ``out`` 0, a finite ``lse`` ≈ −1e30
    and zero gradients.  Each equals the dense partial, and is the
    same bits as the all-tiles schedule."""
    import jax.numpy as jnp
    from veles_tpu.ops import pallas_attention as PA
    q, k, v = _chunk_operands()
    k_off = _CHUNKS[where]
    kc, vc = (x[:, k_off:k_off + _SK] for x in (k, v))
    do, dlse = _rand(q.shape, seed=21), _rand(q.shape[:3], seed=22)
    offs = (jnp.float32(_Q_OFF), jnp.float32(k_off))
    got = _jit_chunk()(q, kc, vc, do, dlse, *offs)
    assert PA.flash_tiles(_SQ, _SK, 8, 8, _Q_OFF, k_off) == {
        "before": (6, 6), "across": (5, 6), "after": (0, 6)}[where]
    out, lse = _dense_partial(q, kc, vc, _Q_OFF, k_off)
    numpy.testing.assert_allclose(numpy.asarray(got[0]),
                                  numpy.asarray(out), rtol=2e-5,
                                  atol=2e-5)
    numpy.testing.assert_allclose(numpy.asarray(got[1]),
                                  numpy.asarray(lse), rtol=2e-5,
                                  atol=2e-5)
    assert all(numpy.isfinite(numpy.asarray(x)).all() for x in got)
    if where == "after":
        assert float(jnp.abs(got[0]).max()) == 0.0
        assert float(got[1].max()) < -1e29
        for grad in got[2:]:
            assert float(jnp.abs(grad).max()) == 0.0

    def all_tiles(causal, *origins_and_blocks, window=None):
        return ((0, origins_and_blocks[-1], causal),)

    monkeypatch.setattr(PA, "_key_stretches", all_tiles)
    monkeypatch.setattr(PA, "_query_stretches", all_tiles)
    for a, b, name in zip(got, _jit_chunk()(q, kc, vc, do, dlse, *offs),
                          ("out", "lse", "dq", "dk", "dv")):
        assert numpy.array_equal(numpy.asarray(a), numpy.asarray(b)), \
            name


def test_merge_over_walked_chunks_equals_whole_attention():
    """``merge_partials`` over the three chunks — one of them void —
    is attention over all 72 keys, forward and gradients, with every
    offset traced."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import pallas_attention as PA
    q, k, v = _chunk_operands(seed=31)
    w = _rand(q.shape, seed=41)

    @jax.jit
    def merged(q, k, v, q_off, k_offs):
        carry = None
        for c in range(3):
            carry = PA.flash_resume(
                carry, q, k[:, c * _SK:(c + 1) * _SK],
                v[:, c * _SK:(c + 1) * _SK], causal=True,
                q_offset=q_off, k_offset=k_offs[c], block_q=8,
                block_k=8, operand_dtype=jnp.float32, interpret=True)
        return carry[0]

    def whole(q, k, v):
        return _dense_partial(q, k, v, _Q_OFF, 0)[0]

    offs = (jnp.float32(_Q_OFF),
            jnp.asarray(sorted(_CHUNKS.values()), jnp.float32))
    numpy.testing.assert_allclose(
        numpy.asarray(merged(q, k, v, *offs)),
        numpy.asarray(whole(q, k, v)), rtol=2e-5, atol=2e-5)
    gm = jax.grad(lambda *o: (merged(*o, *offs) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gw = jax.grad(lambda *o: (whole(*o) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gm, gw, ("dq", "dk", "dv")):
        numpy.testing.assert_allclose(
            numpy.asarray(a), numpy.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg="merged %s diverged" % name)
    # the keys no query may see get no gradient at all
    assert float(jnp.abs(gm[1][:, 2 * _SK:]).max()) == 0.0
    assert float(jnp.abs(gm[2][:, 2 * _SK:]).max()) == 0.0


# -- ring-flash through shard_map ---------------------------------------


@pytest.mark.parametrize("shards,causal", [(2, True), (4, True)])
def test_ring_flash_matches_oracles(shards, causal):
    """Ring-flash (interpret kernel per ppermuted shard, lse merge)
    == the lax ring == full attention — FORWARD AND BACKWARD in one
    trace (jax.value_and_grad, so the fwd+bwd parity costs one
    compile, tier-1 budget discipline) — at tiny tier-1 geometry
    over 2- and 4-shard rings, with the causal masks judged on
    GLOBAL positions (non-causal parity rides the chunk/merge tests
    above — shard count is immaterial without a mask).  The backward
    is autodiff-derived: per-chunk custom-VJP recompute-from-lse +
    differentiable merge + reversed ppermutes — what makes
    ring-flash trainable, not just servable."""
    import jax
    from veles_tpu.ops import attention as A
    q, k, v = _qkv(S=32, seed=11)
    mesh = make_mesh(axes={"seq": shards})
    # Gradients only on the 2-shard ring: the backward's cost is
    # compile-dominated (every unrolled step traces a fwd+dq+dkv
    # kernel triple) and two steps already cover the merge/ppermute
    # transpose; the 4-shard case gates the forward composition.
    with_grads = shards == 2

    def run(fn, grads):
        if not grads:
            return fn(q, k, v), None
        def loss(q, k, v):
            out = fn(q, k, v)
            return (out ** 2).sum(), out
        (_, out), g = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return out, g

    out_full, g_full = run(lambda q, k, v: A.attention(
        q, k, v, causal=causal, kernel="xla"), with_grads)
    out_ring, _ = run(lambda q, k, v: A.sequence_parallel_attention(
        q, k, v, mesh, "seq", causal=causal, kernel="xla"), False)
    out_flash, g_flash = run(
        lambda q, k, v: A.sequence_parallel_attention(
            q, k, v, mesh, "seq", causal=causal, kernel="pallas",
            interpret=True), with_grads)
    numpy.testing.assert_allclose(numpy.asarray(out_flash),
                                  numpy.asarray(out_full),
                                  rtol=2e-5, atol=2e-5)
    numpy.testing.assert_allclose(numpy.asarray(out_flash),
                                  numpy.asarray(out_ring),
                                  rtol=2e-5, atol=2e-5)
    if with_grads:
        for a, b, name in zip(g_flash, g_full, ("dq", "dk", "dv")):
            numpy.testing.assert_allclose(
                numpy.asarray(a), numpy.asarray(b), rtol=5e-4,
                atol=5e-5, err_msg="ring-flash %s diverged" % name)


def test_ring_flash_head_sharded_composition():
    """tp×sp: with the head dim sharded too (the 3-axis layout's
    attention spec), each rank rotates only its own heads' k/v
    through the kernel — parity must hold through the composed
    shard_map."""
    from veles_tpu.ops import attention as A
    q, k, v = _qkv(B=2, S=16, H=4, D=6, seed=17)
    mesh = make_mesh(axes={"model": 2, "seq": 4})
    full = A.attention(q, k, v, causal=True, kernel="xla")
    flash = A.sequence_parallel_attention(
        q, k, v, mesh, "seq", causal=True, head_axis="model",
        kernel="pallas", interpret=True)
    numpy.testing.assert_allclose(numpy.asarray(flash),
                                  numpy.asarray(full), rtol=2e-5,
                                  atol=2e-5)


@pytest.mark.slow
def test_ring_flash_s2048_kernel_geometry():
    """The real kernel-contract geometry (D=128, per-shard S=512 —
    lane-native tiles) at S=2048 over a 4-shard ring, interpret
    mode: the long-context regime the ring-flash exists for."""
    from veles_tpu.ops import attention as A
    q, k, v = _qkv(B=1, S=2048, H=2, D=128, seed=19)
    mesh = make_mesh(axes={"seq": 4})
    full = A.attention(q, k, v, causal=True, kernel="xla")
    flash = A.sequence_parallel_attention(
        q, k, v, mesh, "seq", causal=True, kernel="pallas",
        interpret=True)
    numpy.testing.assert_allclose(numpy.asarray(flash),
                                  numpy.asarray(full), rtol=5e-5,
                                  atol=5e-5)


# -- contracts -----------------------------------------------------------


def test_supports_ring_contract():
    from veles_tpu.ops.pallas_attention import supports_ring
    good = (2, 256, 2, 128)
    assert supports_ring(good, good)
    # Ring shards may differ in length...
    assert supports_ring((2, 256, 2, 128), (2, 512, 2, 128))
    # ...but batch/heads/head-dim must agree.
    assert not supports_ring((2, 256, 2, 128), (1, 256, 2, 128))
    assert not supports_ring((2, 256, 2, 128), (2, 256, 4, 128))
    assert not supports_ring((2, 256, 2, 128), (2, 256, 2, 256))
    # Compiled mode keeps the lane/tile contract...
    assert not supports_ring((2, 256, 2, 64), (2, 256, 2, 64))
    assert not supports_ring((2, 100, 2, 128), (2, 100, 2, 128))
    assert not supports_ring((2, 4096, 2, 128), (2, 4096, 2, 128))
    # ...which interpret mode relaxes (tiny tier-1 geometry).
    assert supports_ring((2, 8, 2, 4), (2, 8, 2, 4), interpret=True)
    assert not supports_ring((2, 8, 2), (2, 8, 2), interpret=True)


def test_supports_decode_contract():
    from veles_tpu.ops.pallas_attention import (DECODE_MAX_Q,
                                                supports_decode)
    q1 = (4, 1, 2, 128)
    table = (4, 1024, 2, 128)
    assert supports_decode(q1, table)
    assert supports_decode((4, DECODE_MAX_Q, 2, 128), table)
    # Reject paths: prefill-sized chunks, geometry mismatches,
    # unaligned tables (compiled), rank errors.
    assert not supports_decode((4, DECODE_MAX_Q + 1, 2, 128), table)
    assert not supports_decode((2, 1, 2, 128), table)
    assert not supports_decode((4, 1, 4, 128), table)
    assert not supports_decode((4, 1, 2, 64), table)
    assert not supports_decode(q1, (4, 1000, 2, 128))
    assert not supports_decode((4, 1, 2), (4, 1024, 2))
    # No sequence bound: the split-k/v grid streams long tables.
    assert supports_decode(q1, (4, 16384, 2, 128))
    # Interpret mode relaxes alignment, not the S_q bound.
    assert supports_decode((1, 1, 1, 4), (1, 10, 1, 4),
                           interpret=True)
    assert not supports_decode((1, DECODE_MAX_Q + 1, 1, 4),
                               (1, 10, 1, 4), interpret=True)


def test_flash_chunk_rejects_out_of_contract():
    import jax.numpy as jnp
    from veles_tpu.ops import pallas_attention as PA
    q = _rand((1, 8, 2, 4), seed=23)
    with pytest.raises(ValueError, match="flash_chunk contract"):
        PA.flash_chunk(q, q, q)  # tiny geometry needs interpret
    with pytest.raises(ValueError, match="decode-kernel contract"):
        PA.pallas_decode_attention(
            q, q, q, jnp.ones((1, 8, 8), bool))  # S_q too large


# -- the decode kernel ---------------------------------------------------


@pytest.mark.parametrize("sq", [1, 4])
def test_decode_kernel_matches_dense(sq):
    """Flash-decode (k/v-split grid + cross-block lse merge) == the
    dense masked softmax over a gathered table, under RAGGED per-row
    key masks (different true lengths — the serving batch shape)."""
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import pallas_attention as PA
    B, H, D, L = 3, 2, 4, 40
    q = _rand((B, sq, H, D), seed=31)
    k = _rand((B, L, H, D), seed=32)
    v = _rand((B, L, H, D), seed=33)
    lens = numpy.array([7, 23, 40])
    mask = jnp.asarray(
        numpy.arange(L)[None, None, :] < lens[:, None, None])
    mask = jnp.broadcast_to(mask, (B, sq, L))
    out = PA.pallas_decode_attention(q, k, v, mask, block_k=8,
                                     operand_dtype=jnp.float32,
                                     interpret=True)
    scores = jnp.einsum("bqhd,bkhd->bqhk", q, k) / (D ** 0.5)
    scores = jnp.where(mask[:, :, None, :], scores, -1e30)
    ref = jnp.einsum("bqhk,bkhd->bqhd",
                     jax.nn.softmax(scores, axis=-1), v)
    numpy.testing.assert_allclose(numpy.asarray(out),
                                  numpy.asarray(ref), rtol=2e-5,
                                  atol=2e-5)


@pytest.fixture
def decode_knob():
    """Restores the decode-kernel gate (default off — the serving
    pin) after a test flips it."""
    from veles_tpu.config import root
    yield root.common.engine
    root.common.engine.decode_kernel = "off"


@pytest.fixture(scope="module")
def lm_artifact(tmp_path_factory):
    """The handcrafted causal-LM artifact the token-identity gate
    decodes (random weights — identity is about the decode MATH,
    not model quality; 2 blocks / E=64 keeps the six jitted decode
    programs inside the tier-1 budget)."""
    import io
    import tarfile
    from veles_tpu.json_encoders import dumps_json
    rng = numpy.random.RandomState(77)
    V, E, H, P, HID, BLOCKS = 64, 64, 2, 128, 128, 2

    def g(*shape):
        return (rng.standard_normal(shape) * 0.5).astype(
            numpy.float32)

    weights = {"emb__weights": g(V, E), "emb__pos": g(P, E)}
    units = [{"name": "emb", "type": "embedding",
              "config": {"vocab_size": V, "embed_dim": E},
              "params": {"weights": "emb__weights",
                         "pos": "emb__pos"}}]
    for b in range(BLOCKS):
        name = "blk%d" % b
        params = {}
        for pname, shape in [
                ("ln1_g", (E,)), ("ln1_b", (E,)),
                ("wq", (E, E)), ("bq", (E,)), ("wk", (E, E)),
                ("bk", (E,)), ("wv", (E, E)), ("bv", (E,)),
                ("wo", (E, E)), ("bo", (E,)),
                ("ln2_g", (E,)), ("ln2_b", (E,)),
                ("w1", (E, HID)), ("b1", (HID,)),
                ("w2", (HID, E)), ("b2", (E,))]:
            key = "%s__%s" % (name, pname)
            weights[key] = numpy.ones(shape, numpy.float32) \
                if pname.endswith("_g") else g(*shape)
            params[pname] = key
        units.append({"name": name, "type": "transformer_block",
                      "config": {"n_heads": H, "causal": 1},
                      "params": params})
    weights["head__weights"] = g(E, V)
    units.append({"name": "head", "type": "lm_head",
                  "config": {"output_sample_shape": [V]},
                  "params": {"weights": "head__weights"}})
    manifest = {"format": "veles-tpu-model", "version": 1,
                "workflow": "RingFlashGate", "checksum": "t",
                "created": "1970-01-01T00:00:00Z",
                "input": {"sample_shape": [8], "dtype": "int32"},
                "output": {"sample_shape": [V]},
                "units": units}
    npz = io.BytesIO()
    numpy.savez(npz, **weights)
    path = str(tmp_path_factory.mktemp("ringflash") /
               "lm.veles.tgz")
    with tarfile.open(path, "w:gz") as tar:
        for name, blob in (("manifest.json",
                            dumps_json(manifest).encode()),
                           ("weights.npz", npz.getvalue())):
            info = tarfile.TarInfo(name)
            info.size = len(blob)
            tar.addfile(info, io.BytesIO(blob))
    return path


def test_decode_kernel_token_identity(decode_knob, lm_artifact):
    """THE decode-kernel gate: with the flag on (interpret — the CPU
    kernel), greedy AND sampled decode are TOKEN-IDENTICAL to the
    pinned f32/xla path, through the bucketed serving program and
    the paged extend/step chain.  Until this holds on a platform,
    the flag stays off there and serving keeps its pin."""
    from veles_tpu.export import ExportedModel
    prompt = numpy.random.RandomState(5).randint(
        0, 60, (2, 12)).astype(numpy.int32)

    def all_paths(model):
        greedy = model.generate(prompt, 6)
        sampled = model.generate(prompt, 6, temperature=0.8, seed=9)
        pool = model.make_kv_pool(16, block_size=8)
        tables = numpy.array([[0, 1, 2, 15], [3, 4, 5, 15]],
                             numpy.int32)
        toks = numpy.zeros((2, 16), numpy.int32)
        toks[:, :12] = prompt
        outs = [model.paged_extend(
            pool, tables, toks, numpy.zeros(2, numpy.int32),
            numpy.full(2, 12, numpy.int32),
            numpy.full(2, 0.7, numpy.float32),
            numpy.arange(2).astype(numpy.uint32))]
        pos = numpy.full(2, 12, numpy.int32)
        for j in range(2):
            outs.append(model.paged_step(
                pool, tables, pos, outs[-1],
                numpy.full(2, j + 1, numpy.int32),
                numpy.full(2, 0.7, numpy.float32),
                numpy.arange(2).astype(numpy.uint32)))
            pos = pos + 1
        return greedy, sampled, numpy.stack(outs)

    decode_knob.decode_kernel = "off"
    base = all_paths(ExportedModel(lm_artifact))
    decode_knob.decode_kernel = "interpret"
    got = all_paths(ExportedModel(lm_artifact))
    for b, g, name in zip(base, got, ("greedy", "sampled", "paged")):
        numpy.testing.assert_array_equal(
            b, g, err_msg="%s decode diverged under the kernel" %
            name)


def test_decode_mode_rides_compile_cache_key(decode_knob,
                                             lm_artifact):
    """Flipping the decode-kernel knob must never serve a stale
    program: the mode string is part of every decode compile-cache
    key."""
    from veles_tpu.export import ExportedModel
    model = ExportedModel(lm_artifact)
    prompt = numpy.array([[1, 2, 3]], numpy.int32)
    decode_knob.decode_kernel = "off"
    model.generate(prompt, 1)
    keys_off = {k for k in model.compile_cache._entries
                if k[0] == "genb"}
    decode_knob.decode_kernel = "interpret"
    model.generate(prompt, 1)
    keys_on = {k for k in model.compile_cache._entries
               if k[0] == "genb"}
    assert keys_off and keys_on > keys_off
    assert any("interpret" in k for k in keys_on - keys_off)


def test_decode_kernel_unknown_mode_raises(decode_knob):
    from veles_tpu.error import Bug
    from veles_tpu.export import ExportedModel
    decode_knob.decode_kernel = "cuda"
    with pytest.raises(Bug, match="decode kernel mode"):
        ExportedModel._decode_kernel_mode()


# -- the r9 default flips ------------------------------------------------


def test_kernel_mode_defaults_flipped():
    """The r9 flip, pinned: attention_kernel and sp_ring_kernel
    default to "auto" (the winning stages — dispatch engages where
    the platform supports it, degrades silently where it cannot);
    the decode kernel stays OFF (serving keeps its pin until the
    identity gate passes on the target platform)."""
    from veles_tpu.config import root, get as config_get
    from veles_tpu.ops import attention as A
    assert config_get(root.common.engine.attention_kernel, None) \
        in (None, "auto")
    assert A._kernel_mode() == "auto"
    assert A._ring_kernel_mode() == "auto"
    assert A.DEFAULT_KERNEL_MODE == "auto"
    assert A.DEFAULT_RING_KERNEL_MODE == "auto"
    from veles_tpu.export import ExportedModel
    assert ExportedModel._decode_kernel_mode() == "off"
    assert ExportedModel._decode_attend() is None


def test_default_dispatch_is_noop_off_platform():
    """Flip-safety on this CPU box: the "auto" defaults must produce
    BIT-IDENTICAL results to forced-"xla" — the platform is not a
    TPU, so the XLA formulations are selected (parity is exact
    equality here, not a tolerance)."""
    from veles_tpu.ops import attention as A
    q, k, v = _qkv(S=16, seed=41)
    mesh = make_mesh(axes={"seq": 4})
    default = A.attention(q, k, v, causal=True)
    pinned = A.attention(q, k, v, causal=True, kernel="xla")
    numpy.testing.assert_array_equal(numpy.asarray(default),
                                     numpy.asarray(pinned))
    dring = A.sequence_parallel_attention(q, k, v, mesh, "seq",
                                          causal=True)
    pring = A.sequence_parallel_attention(q, k, v, mesh, "seq",
                                          causal=True, kernel="xla")
    numpy.testing.assert_array_equal(numpy.asarray(dring),
                                     numpy.asarray(pring))


def test_ring_kernel_knob_rejects_unknown_mode():
    from veles_tpu.config import root
    from veles_tpu.ops import attention as A
    q, k, v = _qkv(S=16, seed=43)
    mesh = make_mesh(axes={"seq": 4})
    prev = getattr(root.common.engine, "sp_ring_kernel", None)
    root.common.engine.sp_ring_kernel = "cuda"
    try:
        with pytest.raises(ValueError, match="ring kernel"):
            A.sequence_parallel_attention(q, k, v, mesh, "seq",
                                          causal=True)
    finally:
        root.common.engine.sp_ring_kernel = \
            prev if prev is not None else A.DEFAULT_RING_KERNEL_MODE
