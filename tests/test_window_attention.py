"""The window beside ``causal`` (row ``i`` sees key ``j`` iff ``0 ≤ i −
j < window``) in every attention formulation: the XLA formulations
against an explicit mask, the flash kernels (interpreted) with their
tile walk bounded from below, one call and the chunked walk, the tile
counts by hand and the labelled counters."""

import itertools

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchmark.models import afmoe as REF
from veles_tpu import resilience
from veles_tpu.ops import attention as A
from veles_tpu.ops import pallas_attention as PA


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


@pytest.mark.parametrize("window", [1, 37, 64, 200, 256])
def test_xla_formulations_take_the_window(window):
    q, k, v = qkv((2, 256, 2, 16))
    want = masked_attention(q, k, v, window)
    got = A.attention(q, k, v, causal=True, window=window, kernel="xla")
    numpy.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    got = A.blockwise_attention(q, k, v, block_size=64, causal=True,
                                window=window, kernel="xla")
    numpy.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# (S, chunk, window, block_q, block_k): windows that are no multiple of
# a tile, narrower than one, as wide as a chunk; unequal blocks; one
# call and chunked calls
KERNEL_CASES = [(512, 512, 200, 128, 128), (512, 128, 200, 64, 64),
                (512, 128, 130, 128, 128), (256, 256, 1, 128, 128),
                (512, 128, 128, 64, 64), (512, 128, 100, 64, 32),
                (512, 256, 300, 64, 128), (512, 256, None, 128, 128),
                (384, 128, 511, 128, 64)]


@pytest.mark.parametrize("S,chunk,window,bq,bk", KERNEL_CASES)
def test_window_kernel_interpreted_against_the_masked_oracle(
        S, chunk, window, bq, bk):
    """Forward and the three gradients of the interpreted kernels —
    one call, or a visible pair of chunks at a time with the partials
    merged — against the explicit mask."""
    q, k, v = qkv((1, S, 2, 64), seed=S + (window or 0))

    def kernel(q, k, v):
        return PA.pallas_attention(
            q, k, v, causal=True, window=window, block_q=bq,
            block_k=bk, operand_dtype=jnp.float32, interpret=True,
            chunk=chunk)

    want = masked_attention(q, k, v, window)
    numpy.testing.assert_allclose(kernel(q, k, v), want, rtol=2e-5,
                                  atol=2e-5)
    weight = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    got = jax.grad(lambda *a: (kernel(*a) * weight).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: (masked_attention(*a, window) *
                               weight).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, ref):
        numpy.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


def visible(rows0, bq, cols0, bk, window):
    """(any, all) entries of one tile a causal row may see."""
    row = rows0 + numpy.arange(bq)[:, None]
    col = cols0 + numpy.arange(bk)[None, :]
    mask = col <= row
    if window is not None:
        mask &= row - col < window
    return mask.any(), mask.all()


@pytest.mark.parametrize("bq,bk,window,qoff,koff", [
    c for c in itertools.product((8, 16), (8, 16),
                                 (None, 1, 7, 8, 20, 33, 64),
                                 (0, 32, 40), (0, 32))])
def test_stretches_hold_every_tile_with_something_to_see(
        bq, bk, window, qoff, koff):
    """Both walks against the definition: a tile with any visible
    entry is in a stretch, a tile in an unmasked stretch is visible
    whole, and ``flash_tiles`` counts the forward's walk."""
    nq, nk = 64 // bq, 64 // bk
    walked = 0
    for i in range(nq):
        rows0 = qoff + i * bq
        kinds = {}
        for lo, hi, masked in PA._key_stretches(
                True, rows0, bq, koff, bk, nk, window):
            assert 0 <= lo <= max(lo, hi) <= nk
            for j in range(lo, hi):
                assert j not in kinds
                kinds[j] = masked
        walked += len(kinds)
        for j in range(nk):
            some, whole = visible(rows0, bq, koff + j * bk, bk, window)
            assert not some or j in kinds
            assert kinds.get(j, True) or whole
    assert PA.flash_tiles(64, 64, bq, bk, qoff, koff, True,
                          window) == (walked, nq * nk)
    for j in range(nk):
        cols0 = koff + j * bk
        kinds = {i: masked for lo, hi, masked in PA._query_stretches(
            True, cols0, bk, qoff, bq, nq, window)
            for i in range(lo, hi)}
        for i in range(nq):
            some, whole = visible(qoff + i * bq, bq, cols0, bk, window)
            assert not some or i in kinds
            assert kinds.get(i, True) or whole


@pytest.mark.parametrize("S,window,blocks,visited", [
    (8192, 2048, 512, 70), (8192, None, 512, 136),
    (2048, 2048, 512, 10), (4096, 2048, 512, 30),
    (8192, 2048, 256, 252), (8192, 1000, 512, 45),
    (8192, 8192, 512, 136), (8192, 1, 512, 16)])
def test_flash_tiles_with_a_window_by_hand(S, window, blocks, visited):
    """70 of 256 at 8,192 / 2,048 / 512: query block i sees key blocks
    i − 4 … i (five, fewer for the first four: 1 + 2 + 3 + 4 + 12 ×
    5); a causal call 16 × 17 / 2."""
    n = S // blocks
    assert PA.flash_tiles(S, S, blocks, blocks, causal=True,
                          window=window) == (visited, n * n)
    chunk = PA.SCOPED_SEQ
    if S == 4 * chunk:
        # the chunked walk visits the same tiles, pair by pair
        pairs = [PA.flash_tiles(chunk, chunk, blocks, blocks, q0, k0,
                                True, window)[0]
                 for q0 in range(0, S, chunk)
                 for k0 in range(0, S, chunk)]
        assert sum(pairs) == visited
        seen = sum(1 for p in pairs if p)
        assert seen == {2048: 7, None: 10, 8192: 10}.get(window, seen)


def test_supports_admits_whole_chunks_past_max_seq():
    def fits(S, kv_len=None):
        return PA.supports((1, S, 32, 128), (1, S, 32, 128), kv_len)
    assert fits(2048) and fits(4096) and fits(8192) and fits(2048, 2000)
    assert not fits(3072) and not fits(8192 + 128)
    assert not fits(4096, 4000)


def test_windowed_calls_count_their_tiles_under_a_label():
    registry = resilience.stats.registry
    label = {"window": "200"}

    def read():
        return [(registry.peek("attention.flash.tiles_" + what, lab) or
                 0) and registry.peek("attention.flash.tiles_" + what,
                                      lab).value
                for lab in (None, label) for what in ("visited",
                                                      "total")]

    before = read()
    q, k, v = qkv((1, 512, 1, 128))
    PA.pallas_attention(q, k, v, causal=True, window=200, block_q=128,
                        block_k=128, interpret=True, chunk=256)
    after = read()
    assert after[:2] == before[:2]          # the plain series: untouched
    assert (after[2] - before[2], after[3] - before[3]) == \
        PA.flash_tiles(512, 512, 128, 128, causal=True, window=200)
    assert REF.window_tiles(200) == {"visited": after[2],
                                     "total": after[3]}
    assert REF.window_tiles(12345) is None
