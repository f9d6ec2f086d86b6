"""The flash kernels with a value head of its own (latent attention:
keys 192 wide, values 128): forward and all three gradients against
``ops.attention.blockwise_attention`` in interpret mode, causal, within
one call and chunked past it, and the contract that admits the widths.
How many rows one call takes (``call_rows``), the compiler parameters
a long call states, one call against the chunked walk, and the count
of calls by path.  The kernels at the cells' real size compile in
``tests/test_tpu_compile.py``."""

import functools

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


def _mib(n):
    """``n`` bytes in whole MiB, rounded up, as a stated limit is."""
    return -(-n >> 20) << 20


@pytest.mark.parametrize("shape,D_v,chunk,calls,fused", [
    ((4, 2048, 32, 128), 128, None, 1, True),
    ((8, 2048, 32, 64), 64, None, 1, True),
    ((1, 8192, 32, 192), 128, None, 1, True),
    ((1, 8192, 16, 256), 256, None, 1, True),
    ((1, 8192, 32, 192), 128, 2048, 10, True),
    ((1, 8192, 8, 512), 512, None, 1, False)],
    ids=["opt-6.7b", "lfm2", "joyai-flash 8k", "qwen3-next 8k",
         "joyai-flash 8k, chunked", "head 512 8k, split"])
def test_a_call_states_its_vmem_past_the_scoped_length(shape, D_v, chunk,
                                                       calls, fused):
    """Forward and gradients traced for the chip (no interpret): a
    forward call of at most ``SCOPED_SEQ`` rows — the 2,048-row cells',
    and each pair of the chunked walk — states no compiler parameters,
    as it never did; a whole 8,192-row call states a scoped VMEM limit
    of twice what ``call_bytes`` reckons, under the core's 128 MiB.
    The backward is ONE ``flash_dkv`` a call wherever ``fused_bytes``
    fits ``VMEM_BUDGET`` — every cell's call — stating twice what that
    reckons at every length; past the budget (heads of 512 at 8,192
    rows: 79 MiB) it is ``flash_dq`` and ``flash_dkv``, which state
    what the forward does."""
    B, S, H, D = shape
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    values = jax.ShapeDtypeStruct((B, S, H, D_v), jnp.bfloat16)
    found = _kernel_calls(jax.grad(
        lambda q, k, v: PA.pallas_attention(
            q, k, v, causal=True, chunk=chunk).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)), x, x, values)
    rows = min(S, chunk or S)
    assert PA.fuses(rows, rows, D, D_v, 2, 512, 512) == fused
    backward = ["flash_dkv"] if fused else ["flash_dq", "flash_dkv"]
    assert sorted(name for name, _ in found) == sorted(
        (["flash_fwd"] + backward) * calls)
    stated = None if rows <= PA.SCOPED_SEQ else \
        _mib(2 * PA.call_bytes(rows, D, D_v, 2, 512, 512))
    want = {"flash_fwd": stated, "flash_dq": stated, "flash_dkv": stated}
    if fused:
        want["flash_dkv"] = _mib(2 * PA.fused_bytes(rows, D, D_v, 2, 512,
                                                    512))
    assert all(limit == want[name] for name, limit in found), found
    assert all(limit is None or limit <= 128 << 20 for _, limit in found)


#: The fused backward's VMEM at each cell's call, bfloat16 (``fuses``
#: true at all), and where it passes the budget while the forward's
#: call still fits: heads of 512 at 8,192 rows in bfloat16, heads of 256
#: at 8,192 rows in float32.
@pytest.mark.parametrize("S,D,Dv,itemsize,fused", [
    (8192, 192, 128, 2, True), (8192, 256, 256, 2, True),
    (8192, 128, 128, 2, True), (2048, 128, 128, 2, True),
    (2048, 64, 64, 2, True), (2048, 128, 128, 4, True),
    (8192, 512, 512, 2, False), (8192, 256, 256, 4, False)])
def test_the_backward_fuses_where_its_vmem_fits(S, D, Dv, itemsize,
                                                fused):
    """``fused_bytes`` is the dk/dv kernel's reckoning and dq over the
    call's rows at whole lane tiles — a float32 accumulator and the
    resident output, double-buffered: +16 MiB at joyai-flash's 8,192
    rows of 192 (256 lanes) in bfloat16, 38.9 MiB in all."""
    extra = PA.fused_bytes(S, D, Dv, itemsize, 512, 512) - \
        PA.call_bytes(S, D, Dv, itemsize, 512, 512)
    assert extra == S * PA._lanes(D) * (4 + 2 * itemsize)
    assert PA.fuses(S, S, D, Dv, itemsize, 512, 512) == fused
    assert PA.call_rows(S, D, Dv, itemsize) == S
    if (S, D, Dv, itemsize) == (8192, 192, 128, 2):
        assert extra == 16 << 20
        assert 38 << 20 < PA.fused_bytes(S, D, Dv, 2, 512, 512) < 39 << 20


# -- the fused backward against the two kernels and the oracle -------------


def _dense(q, k, v, mask):
    """The oracle: softmax attention over a (Sq, Sk) boolean ``mask``
    at float32 ``highest``, and its log-sum-exp rows (B, Sq, H)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / \
        numpy.sqrt(q.shape[-1])
    s = jnp.where(mask, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v,
                     precision="highest")
    return out, lse.transpose(0, 2, 1)


def _visible(mode, Sq, Sk):
    """Each mode's mask by hand, from the rows' and columns' meaning."""
    i = numpy.arange(Sq)[:, None]
    j = numpy.arange(Sk)[None, :]
    if mode == "causal":
        return i >= j
    if mode == "window":
        return (i >= j) & (i - j < 200)
    if mode == "kv_len":
        return numpy.broadcast_to(j < 200, (Sq, Sk))
    if mode == "ring":
        return i + 128 >= j
    L, B = Sq // 2, 4
    row_clean, col_clean = i >= L, j >= L
    row_block, col_block = (i % L) // B, (j % L) // B
    return numpy.where(
        row_clean, col_clean & (col_block <= row_block),
        numpy.where(col_clean, col_block < row_block,
                    col_block == row_block))


def _attend(mode, q, k, v):
    """``(out, lse or None)`` of the kernels in ``mode``: 128-row blocks,
    float32 operands, interpreted; the whole sequence one call."""
    common = dict(block_q=128, block_k=128, operand_dtype=jnp.float32,
                  interpret=True)
    if mode == "ring":
        return PA.flash_chunk(q, k, v, causal=True, q_offset=128,
                              k_offset=0, **common)
    kwargs = {"causal": dict(causal=True), "kv_len": dict(kv_len=200),
              "window": dict(causal=True, window=200),
              "block diffusion": dict(diffusion=(q.shape[1] // 2, 4))}
    return PA.pallas_attention(q, k, v, chunk=q.shape[1], **common,
                               **kwargs[mode]), None


@pytest.mark.parametrize("mode,S,D_qk,D_v", [
    ("causal", 256, 64, 64), ("causal", 256, 128, 128),
    ("causal", 256, 192, 128), ("causal", 256, 256, 256),
    ("window", 512, 128, 128), ("block diffusion", 256, 128, 128),
    ("kv_len", 256, 192, 128), ("ring", 256, 128, 128)])
def test_fused_backward_matches_the_two_kernels_and_the_oracle(
        monkeypatch, mode, S, D_qk, D_v):
    """dq, dk and dv of the ONE backward call — the dk/dv kernel's walk
    over the query blocks that see each key block, dq summed in a
    float32 accumulator over the call's rows — against the two kernels
    (``VMEM_BUDGET`` 0 leaves the fused call no room) to float32
    rounding, and against the dense oracle: causal at heads of 64,
    128, 192 / 128 and 256; a window of 200 rows over 512 (the
    window's edge, the tiles it sees whole and the diagonal); block
    diffusion over two streams of 128 in blocks of 4; ``kv_len`` 200
    of 256, no causal mask; a ring partial at query offset 128,
    traced through ``flash_chunk``, its lse's cotangent in the loss.
    Each backward traced counts once into
    ``attention.flash.bwd.fused`` or ``.split``."""
    from veles_tpu import resilience
    registry = resilience.stats.registry
    q, k, v = _operands(S, 2, D_qk, D_v, D_qk + D_v + S)
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    weight = jax.random.normal(keys[0], (1, S, 2, D_v))
    lse_weight = jax.random.normal(keys[1], (1, S, 2))

    def grads(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v)
            total = (out * weight).sum()
            return total if lse is None else total + (lse * lse_weight).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def counts():
        return [getattr(registry.peek("attention.flash.bwd." + path),
                        "value", 0) for path in ("fused", "split")]

    before = counts()
    fused = grads(functools.partial(_attend, mode))
    middle = counts()
    monkeypatch.setattr(PA, "VMEM_BUDGET", 0)
    split = grads(functools.partial(_attend, mode))
    after = counts()
    assert [b - a for a, b in zip(before, middle)] == [1, 0]
    assert [b - a for a, b in zip(middle, after)] == [0, 1]
    mask = _visible(mode, S, S)
    want = grads(lambda q, k, v: _dense(q, k, v, mask)
                 if mode == "ring" else (_dense(q, k, v, mask)[0], None))
    for got, two, ref, name in zip(fused, split, want, "qkv"):
        numpy.testing.assert_allclose(got, two, rtol=1e-5, atol=1e-6,
                                      err_msg="d" + name)
        numpy.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5,
                                      err_msg="d" + name)


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
