"""The flash kernels with a value head of its own (latent attention:
keys 192 wide, values 128): forward and all three gradients against
``ops.attention.blockwise_attention`` in interpret mode, causal, within
one call and chunked past it, and the contract that admits the widths.
How many rows one call takes (``call_rows``), the compiler parameters
a long call states, one call against the chunked walk, and the count
of calls by path.  The kernels at the cells' real size compile in
``tests/test_tpu_compile.py``."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops import attention as A
from veles_tpu.ops import pallas_attention as PA


def _operands(S, H, D_qk, D_v, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k = (jax.random.normal(key, (1, S, H, D_qk), jnp.float32)
            for key in keys[:2])
    v = jax.random.normal(keys[2], (1, S, H, D_v), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("D_qk,D_v", [(192, 128), (64, 128), (128, 64)])
@pytest.mark.parametrize("chunk", [None, 128],
                         ids=["one call", "chunked"])
def test_kernels_take_a_value_head_of_its_own(D_qk, D_v, chunk):
    """Two heads of 256 rows, blocks of 128: the forward walks two key
    blocks a query block, the dq and dk/dv kernels two tiles each way;
    at ``chunk`` 128 the call is walked as three visible chunk pairs
    merged by lse.  ``out`` and ``dv`` are ``D_v`` wide, ``dq`` and
    ``dk`` ``D_qk``; the softmax scale is ``1 / sqrt(D_qk)``."""
    q, k, v = _operands(256, 2, D_qk, D_v, D_qk + D_v)
    weight = jax.random.normal(jax.random.PRNGKey(3), (1, 256, 2, D_v))

    def run(fn):
        def loss(q, k, v):
            return (fn(q, k, v) * weight).sum()
        return fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    out, grads = run(lambda q, k, v: PA.pallas_attention(
        q, k, v, causal=True, block_q=128, block_k=128,
        operand_dtype=jnp.float32, interpret=True, chunk=chunk))
    want, want_grads = run(lambda q, k, v: A.blockwise_attention(
        q, k, v, block_size=128, causal=True, precision="f32"))
    assert out.shape == (1, 256, 2, D_v)
    numpy.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for got, ref, name, width in zip(grads, want_grads, "qkv",
                                     (D_qk, D_qk, D_v)):
        assert got.shape[-1] == width, name
        numpy.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5,
                                      err_msg="d" + name)


def test_blockwise_and_full_attention_agree_at_two_widths():
    """The XLA formulations (the CPU's path and the kernels' oracle)
    give a value head of its own too, and agree."""
    q, k, v = _operands(64, 3, 24, 16, 1)
    full = A.attention(q, k, v, causal=True, precision="f32")
    blocked = A.blockwise_attention(q, k, v, block_size=16, causal=True,
                                    precision="f32")
    assert full.shape == blocked.shape == (1, 64, 3, 16)
    numpy.testing.assert_allclose(full, blocked, rtol=1e-5, atol=1e-6)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / numpy.sqrt(24.0)
    scores = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), scores,
                       -jnp.inf)
    by_hand = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores), v)
    numpy.testing.assert_allclose(full, by_hand, rtol=1e-5, atol=1e-6)


def test_the_contract_admits_the_latent_widths():
    keys = (2, 2048, 32, 192)
    values = (2, 2048, 32, 128)
    assert PA.supports(keys, keys, v_shape=values)
    assert PA.supports(keys, keys)                 # values as wide
    assert PA.supports((2, 8192, 32, 192), (2, 8192, 32, 192),
                       v_shape=(2, 8192, 32, 128))  # chunked past it
    for bad in ((2, 2048, 32, 96), (2, 2048, 32, 0), (2, 2048, 16, 128),
                (2, 1024, 32, 128), (2, 2048, 128)):
        assert not PA.supports(keys, keys, v_shape=bad), bad
    assert not PA.supports(keys, (2, 2048, 32, 128))  # q and k differ
    # the ring's contract stays lane-native and of one width
    assert not PA.supports_ring(keys, keys)


def test_selection_follows_the_value_head(monkeypatch):
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    keys = (1, 8192, 32, 192)
    assert A._selects_pallas(keys, keys, v_shape=(1, 8192, 32, 128))
    assert not A._selects_pallas(keys, keys, v_shape=(1, 8192, 32, 96))
    assert not A._selects_pallas(keys, keys, mode="xla",
                                 v_shape=(1, 8192, 32, 128))


# -- one call a sequence, and the chunked walk -----------------------------


@pytest.mark.parametrize("D_qk,D_v,window", [
    (128, 128, None), (128, 128, 200), (192, 128, None)],
    ids=["causal", "window", "keys wider than values"])
def test_one_call_matches_the_chunked_walk_and_the_oracle(D_qk, D_v,
                                                          window):
    """A sequence four chunks long (512 rows, chunks of 128, blocks of
    64): the one call that ``call_rows`` picks and the chunked walk of
    ten (nine under the window) visible pairs merged by lse give the
    oracle's output and gradients of q, k and v."""
    q, k, v = _operands(512, 2, D_qk, D_v, D_qk + (window or 0))
    weight = jax.random.normal(jax.random.PRNGKey(5), (1, 512, 2, D_v))
    assert PA.call_rows(512, D_qk, D_v, 4) == 512

    def run(fn):
        def loss(q, k, v):
            return (fn(q, k, v) * weight).sum()
        return (fn(q, k, v),) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def kernel(chunk):
        return run(lambda q, k, v: PA.pallas_attention(
            q, k, v, causal=True, window=window, block_q=64, block_k=64,
            operand_dtype=jnp.float32, interpret=True, chunk=chunk))

    whole, chunked = kernel(None), kernel(128)
    want = run(lambda q, k, v: A.blockwise_attention(
        q, k, v, block_size=128, causal=True, window=window,
        precision="f32"))
    for got, walked, ref, name in zip(whole, chunked, want,
                                      ("out", "dq", "dk", "dv")):
        numpy.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5,
                                      err_msg=name)
        numpy.testing.assert_allclose(got, walked, rtol=2e-4, atol=2e-5,
                                      err_msg=name)


#: (S, D_qk, D_v, bytes an operand element, rows a call): the three 8k
#: cells' calls (``joyai-flash``, ``qwen3-next``, ``trinity-mini``) in
#: bfloat16 and in float32, calls of 2,048 rows and fewer, and
#: sequences too long for ``VMEM_BUDGET`` — the longest chunk that fits
#: and divides them, a power of two or not.
CALL_ROWS = [(8192, 192, 128, 2, 8192), (8192, 256, 256, 2, 8192),
             (8192, 128, 128, 2, 8192), (8192, 192, 128, 4, 8192),
             (2048, 64, 64, 2, 2048), (2048, 512, 512, 4, 2048),
             (1024, 128, 128, 4, 1024), (65536, 256, 256, 2, 16384),
             (49152, 256, 256, 2, 24576), (131072, 128, 128, 4, 16384)]


@pytest.mark.parametrize("S,D,Dv,itemsize,rows", CALL_ROWS)
def test_call_rows_follows_what_the_call_observes(S, D, Dv, itemsize,
                                                  rows):
    assert PA.call_rows(S, D, Dv, itemsize) == rows
    assert S % rows == 0 and rows % PA.LANE == 0

    def fits(n):
        return PA.call_bytes(n, D, Dv, itemsize, min(n, 512),
                             min(n, 512)) <= PA.VMEM_BUDGET
    assert fits(rows)
    assert not any(fits(n) for n in range(rows + PA.LANE, S + 1, PA.LANE)
                   if S % n == 0)


def test_call_bytes_pads_rows_to_lanes_and_counts_the_resident_pair():
    """At 8,192 rows in bfloat16 keys of 192 take 256 lanes: k (4 MiB)
    and v (2 MiB) double-buffered are 12 MiB of the call's reckoning,
    as keys of 256 and values of 128 would be."""
    joyai = PA.call_bytes(8192, 192, 128, 2, 512, 512)
    assert joyai == PA.call_bytes(8192, 256, 128, 2, 512, 512)
    assert joyai - PA.call_bytes(4096, 192, 128, 2, 512, 512) == \
        2 * 4096 * (256 + 128) * 2 + 2 * 2 * 8 * 512 * 4
    assert 12 << 20 < joyai < PA.VMEM_BUDGET


def _kernel_calls(fn, *structs):
    """(name, VMEM limit or None) of every ``pallas_call`` in ``fn``'s
    jaxpr, nested ones included — what the program asks of Mosaic."""
    from jax._src import core
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                params = eqn.params["compiler_params"].get("mosaic_tpu")
                found.append((eqn.params["name"], params and
                              params.vmem_limit_bytes))
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*structs).jaxpr)
    return found


@pytest.mark.parametrize("shape,D_v,chunk,calls", [
    ((4, 2048, 32, 128), 128, None, 1), ((8, 2048, 32, 64), 64, None, 1),
    ((1, 8192, 32, 192), 128, None, 1), ((1, 8192, 16, 256), 256, None, 1),
    ((1, 8192, 32, 192), 128, 2048, 10)],
    ids=["opt-6.7b", "lfm2", "joyai-flash 8k", "qwen3-next 8k",
         "joyai-flash 8k, chunked"])
def test_a_call_states_its_vmem_past_the_scoped_length(shape, D_v, chunk,
                                                       calls):
    """Forward and gradients traced for the chip (no interpret): a call
    of at most ``SCOPED_SEQ`` rows — the 2,048-row cells', and each
    pair of the chunked walk — states no compiler parameters, as it
    never did; a whole 8,192-row call states a scoped VMEM limit of
    twice what ``call_bytes`` reckons, under the core's 128 MiB.  One
    ``flash_fwd``, ``flash_dq`` and ``flash_dkv`` a call."""
    B, S, H, D = shape
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    values = jax.ShapeDtypeStruct((B, S, H, D_v), jnp.bfloat16)
    found = _kernel_calls(jax.grad(
        lambda q, k, v: PA.pallas_attention(
            q, k, v, causal=True, chunk=chunk).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), x, x, values)
    assert sorted(name for name, _ in found) == sorted(
        ["flash_fwd", "flash_dq", "flash_dkv"] * calls)
    rows = min(S, chunk or S)
    if rows <= PA.SCOPED_SEQ:
        assert {limit for _, limit in found} == {None}
    else:
        need = 2 * PA.call_bytes(S, D, D_v, 2, 512, 512)
        assert {limit for _, limit in found} == {-(-need >> 20) << 20}
        assert need <= 128 << 20


@pytest.mark.parametrize("chunk,window,path,calls", [
    (None, None, "whole", 1), (128, None, "chunked", 10),
    (128, 200, "chunked", 9)])
def test_flash_calls_count_by_path(chunk, window, path, calls):
    """Each trace counts its kernel calls into ``attention.flash.calls``
    labelled by path: one for a whole call, one a visible chunk pair
    for the chunked walk (ten of sixteen causal pairs at four chunks;
    nine under a window of 200 rows, which reaches two chunks back)."""
    from veles_tpu import resilience
    registry = resilience.stats.registry

    def read():
        return {p: getattr(registry.peek("attention.flash.calls",
                                         {"path": p}), "value", 0)
                for p in ("whole", "chunked")}

    before = read()
    q, k, v = _operands(512, 1, 128, 128, 7)
    PA.pallas_attention(q, k, v, causal=True, window=window, block_q=128,
                        block_k=128, interpret=True, chunk=chunk)
    after = read()
    assert {p: after[p] - before[p] for p in after} == {
        p: calls if p == path else 0 for p in after}
