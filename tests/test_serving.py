"""Production serving subsystem (veles_tpu/serving/): shape-bucketed
compile cache, continuous request batching, and admission control.

The contracts under test, per docs/serving.md:

* bucket rounding is the compile-DoS fix — 50 distinct prompt lengths
  must reach O(log span) compile keys, not 50;
* coalesced batches pad stragglers but NEVER corrupt them — the
  bucketed decode path is bit-identical to per-request greedy decode
  (proved on a real artifact, not a mock);
* admission control answers 429 + Retry-After under a flooded queue
  while /health stays responsive, and expired deadlines cancel work
  unserved;
* /stats exposes queue depth, batch occupancy, compile-cache
  hits/misses, and latency percentiles;
* batching buys ≥ 2× throughput over the serial handler;
* paged decode (KVBlockPool + decode-step continuous batching) is
  TOKEN-IDENTICAL to the dense bucketed path on a real artifact,
  shares prompt prefixes with copy-on-write, sheds 429 on pool
  exhaustion, carries pool geometry in its compile keys, ignores the
  attention fast-path knobs, and sustains strictly higher aggregate
  tok/s than whole-request batching on mixed-length streams.

Everything runs on CPU with fake models except the parity/prefix/knob
tests, which load a small randomly-weighted LM artifact (no training —
weights are handcrafted, so the tests cost compiles, not epochs).
"""

import io
import json
import tarfile
import threading
import time
import urllib.error
import urllib.request

import numpy
import pytest

from veles_tpu.error import Bug
from veles_tpu.export import ExportedModel, KVBlockPool
from veles_tpu.resilience import Deadline
from veles_tpu.serving import (BucketPolicy, CompileCache,
                               DeadlineExceeded, PoolExhausted,
                               QueueFull, RateLimited, RateLimiter,
                               ServingEngine, ServingStats,
                               TokenBucket, next_pow2)


# -- helpers ---------------------------------------------------------------


class FakeModel(object):
    """Duck-typed serving model: deterministic per-row outputs so a
    straggler corrupted by batching is caught, call recording so
    coalescing/bucketing is observable, optional per-call delay to
    make queueing real."""

    manifest = {
        "workflow": "Fake",
        "units": [],
        "input": {"sample_shape": [4], "dtype": "float32"},
        "output": {"sample_shape": [3]},
    }
    max_position = 64

    def __init__(self, delay=0.0):
        self.delay = delay
        self.forward_shapes = []
        self.gen_shapes = []
        self._lock = threading.Lock()

    def forward(self, x):
        x = numpy.asarray(x, dtype=numpy.float32)
        with self._lock:
            self.forward_shapes.append(tuple(x.shape))
        if self.delay:
            time.sleep(self.delay)
        # Per-row fingerprint: output depends only on the row.
        return x.sum(axis=1)[:, None] + numpy.arange(3)[None, :]

    #: Per-decoded-token device cost (whole-request batching pays it
    #: for the full DECODE BUCKET per batch — the padded-decode waste
    #: continuous batching eliminates).
    per_token_delay = 0.0

    def generate_bucketed(self, prompts, lengths, max_new,
                          temperatures, seeds):
        prompts = numpy.asarray(prompts)
        lengths = numpy.asarray(lengths)
        with self._lock:
            self.gen_shapes.append(
                (tuple(prompts.shape), int(max_new)))
        if self.delay:
            time.sleep(self.delay)
        if self.per_token_delay:
            time.sleep(self.per_token_delay * int(max_new))
        out = numpy.zeros((prompts.shape[0], int(max_new)),
                          numpy.int32)
        for i in range(prompts.shape[0]):
            last = int(prompts[i, int(lengths[i]) - 1])
            out[i] = (last + 1 + numpy.arange(int(max_new))) % 97
        return out


class PagedFakeModel(object):
    """Duck-typed PAGED serving model: the block-pool bookkeeping is
    the real :class:`KVBlockPool` (device storage replaced by a
    no-op), decode produces the same per-row fingerprint as
    :class:`FakeModel` — token t = (last_prompt_token + 1 + t) % 97,
    via tok+1 per step — and injectable per-call delays model device
    economics: ``step_delay`` per decode step, ``prefill_delay`` per
    extend call.  That makes scheduler properties (joins, immediate
    retirement, aggregate tok/s) observable without XLA compiles."""

    max_position = 64

    def __init__(self, step_delay=0.0, prefill_delay=0.0):
        self.step_delay = step_delay
        self.prefill_delay = prefill_delay
        self.extend_shapes = []  # (B, T, Sc)
        self.step_shapes = []    # (B, T)
        self.verify_shapes = []  # (B, K+1)
        self._lock = threading.Lock()

    def make_kv_pool(self, n_blocks, block_size=16, kv_dtype="f32"):
        return KVBlockPool(n_blocks, block_size,
                           copy_fn=lambda storage, s, d: storage,
                           kv_dtype=kv_dtype)

    def paged_extend(self, pool, tables, tokens, prior, chunk_lens,
                     temps, seeds):
        tables = numpy.asarray(tables)
        tokens = numpy.asarray(tokens)
        clens = numpy.asarray(chunk_lens)
        with self._lock:
            self.extend_shapes.append(
                tables.shape + (tokens.shape[1],))
        if self.prefill_delay:
            time.sleep(self.prefill_delay)
        out = numpy.zeros(tokens.shape[0], numpy.int32)
        for i in range(tokens.shape[0]):
            out[i] = (int(tokens[i, max(int(clens[i]) - 1, 0)])
                      + 1) % 97
        return out

    def paged_step(self, pool, tables, pos, tok, gen_idx, temps,
                   seeds):
        with self._lock:
            self.step_shapes.append(numpy.asarray(tables).shape)
        if self.step_delay:
            time.sleep(self.step_delay)
        return (numpy.asarray(tok) + 1) % 97

    def paged_verify(self, pool, tables, pos, toks, draft_lens,
                     gen_idx, temps, seeds):
        """Speculative verify with the same per-row fingerprint:
        the target's token at column j is (fed token at j) + 1 —
        so a drafter proposing the +1 chain is fully accepted and
        any other proposal is rejected at its first wrong token."""
        toks = numpy.asarray(toks)
        with self._lock:
            self.verify_shapes.append(toks.shape)
        if self.step_delay:
            time.sleep(self.step_delay)
        return (toks + 1) % 97


def _expected_forward(x):
    x = numpy.asarray(x, dtype=numpy.float32)
    return x.sum(axis=1)[:, None] + numpy.arange(3)[None, :]


def _expected_generated(prompt_row, max_new):
    return (int(prompt_row[-1]) + 1 + numpy.arange(max_new)) % 97


def _post(port, path, payload, headers=None, timeout=30):
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        "http://127.0.0.1:%d%s" % (port, path),
        data=json.dumps(payload).encode(), headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), resp.headers
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), e.headers


def _get(port, path, timeout=30):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path),
            timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _write_artifact(path, units, weights, sample_shape=(8,)):
    from veles_tpu.json_encoders import dumps_json
    manifest = {"format": "veles-tpu-model", "version": 1,
                "workflow": "Handcrafted", "checksum": "x",
                "created": "1970-01-01T00:00:00Z",
                "input": {"sample_shape": list(sample_shape),
                          "dtype": "int32"},
                "output": {"sample_shape": [1]}, "units": units}
    npz = io.BytesIO()
    numpy.savez(npz, **weights)
    blobs = {"manifest.json": dumps_json(manifest).encode(),
             "weights.npz": npz.getvalue()}
    with tarfile.open(path, "w:gz") as tar:
        for name, blob in blobs.items():
            info = tarfile.TarInfo(name)
            info.size = len(blob)
            tar.addfile(info, io.BytesIO(blob))
    return str(path)


def _random_lm_artifact(path, vocab=13, embed=8, heads=2, pos=32,
                        hidden=16, seed=42):
    """A small causal LM with random (untrained) weights — generate()
    parity needs real attention math, not a trained model."""
    rng = numpy.random.RandomState(seed)

    def g(*shape):
        return (rng.standard_normal(shape) * 1.5).astype(numpy.float32)

    weights = {"emb__weights": g(vocab, embed), "emb__pos": g(pos, embed)}
    units = [{"name": "emb", "type": "embedding",
              "config": {"vocab_size": vocab, "embed_dim": embed},
              "params": {"weights": "emb__weights",
                         "pos": "emb__pos"}}]
    bp = {}
    for n, shape in [("ln1_g", (embed,)), ("ln1_b", (embed,)),
                     ("wq", (embed, embed)), ("bq", (embed,)),
                     ("wk", (embed, embed)), ("bk", (embed,)),
                     ("wv", (embed, embed)), ("bv", (embed,)),
                     ("wo", (embed, embed)), ("bo", (embed,)),
                     ("ln2_g", (embed,)), ("ln2_b", (embed,)),
                     ("w1", (embed, hidden)), ("b1", (hidden,)),
                     ("w2", (hidden, embed)), ("b2", (embed,))]:
        key = "blk__%s" % n
        weights[key] = numpy.ones(shape, numpy.float32) \
            if n.startswith("ln") and n.endswith("_g") else g(*shape)
        bp[n] = key
    units.append({"name": "blk", "type": "transformer_block",
                  "config": {"n_heads": heads, "causal": 1},
                  "params": bp})
    weights["head__weights"] = g(embed, vocab)
    units.append({"name": "head", "type": "lm_head",
                  "config": {"output_sample_shape": [vocab]},
                  "params": {"weights": "head__weights"}})
    return _write_artifact(path, units, weights)


# -- bucket policy ---------------------------------------------------------


def test_bucket_rounding_table():
    assert [next_pow2(n) for n in (1, 2, 3, 4, 5, 17, 64, 100)] == \
        [1, 2, 4, 4, 8, 32, 64, 128]
    policy = BucketPolicy(max_batch=8, prompt_floor=16,
                          prompt_cap=64, new_floor=16)
    assert [policy.batch_bucket(n) for n in (1, 2, 3, 7, 8)] == \
        [1, 2, 4, 8, 8]
    assert [policy.prompt_bucket(s) for s in (1, 9, 16, 17, 40, 60)] \
        == [16, 16, 16, 32, 64, 64]
    # The cap never rounds BELOW the true length.
    assert policy.prompt_bucket(63) == 63 or \
        policy.prompt_bucket(63) == 64
    assert policy.new_bucket(5) == 16
    assert policy.batch_buckets() == [1, 2, 4, 8]
    assert policy.prompt_buckets(50) == [16, 32, 64]


def test_fifty_prompt_lengths_bound_compiles():
    """The acceptance gate: 50 distinct prompt lengths reach at most
    ceil(log2 span) compile keys."""
    policy = BucketPolicy(max_batch=8, prompt_floor=16,
                          prompt_cap=64)
    buckets = {policy.prompt_bucket(s) for s in range(1, 51)}
    assert len(buckets) <= numpy.ceil(numpy.log2(50))
    assert buckets == {16, 32, 64}


def test_compile_cache_lru_and_counters():
    evicted = []
    cache = CompileCache(capacity=2,
                         on_evict=lambda k, v: evicted.append(k))
    built = []

    def builder(key):
        def build():
            built.append(key)
            return "exe-%s" % (key,)
        return build

    assert cache.get_or_build("a", builder("a")) == "exe-a"
    assert cache.get_or_build("b", builder("b")) == "exe-b"
    assert cache.get_or_build("a", builder("a")) == "exe-a"  # hit
    assert built == ["a", "b"]
    # "b" is now least-recently-used; "c" evicts it.
    cache.get_or_build("c", builder("c"))
    assert evicted == ["b"]
    assert "a" in cache and "c" in cache and "b" not in cache
    stats = cache.stats()
    assert stats == {"hits": 1, "misses": 3, "evictions": 1,
                     "entries": 2, "capacity": 2}


# -- admission -------------------------------------------------------------


def test_token_bucket_refills_on_fake_clock():
    now = [0.0]
    bucket = TokenBucket(rate=2.0, burst=2.0, clock=lambda: now[0])
    assert bucket.try_acquire() and bucket.try_acquire()
    assert not bucket.try_acquire()
    assert bucket.retry_after() == pytest.approx(0.5)
    now[0] += 0.5  # one token refilled
    assert bucket.try_acquire()
    assert not bucket.try_acquire()


def test_rate_limiter_is_per_client():
    now = [0.0]
    limiter = RateLimiter(rate=1.0, burst=1.0, clock=lambda: now[0])
    limiter.admit("10.0.0.1")
    limiter.admit("10.0.0.2")  # separate bucket
    with pytest.raises(RateLimited) as e:
        limiter.admit("10.0.0.1")
    assert e.value.status == 429
    assert e.value.retry_after > 0
    now[0] += 1.0
    limiter.admit("10.0.0.1")  # refilled


# -- engine: coalescing + masking ------------------------------------------


def test_engine_coalesces_classify_and_pads_to_buckets():
    model = FakeModel(delay=0.05)
    engine = ServingEngine(model, max_batch=8,
                           queue_depth=64).start()
    try:
        rng = numpy.random.RandomState(0)
        inputs = [rng.rand(n, 4).astype(numpy.float32)
                  for n in (1, 2, 1, 3, 1)]
        results = [None] * len(inputs)

        def worker(i):
            results[i] = engine.submit_classify(inputs[i])

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(inputs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # Masked stragglers: every request got ITS OWN rows back.
        for x, y in zip(inputs, results):
            numpy.testing.assert_allclose(y, _expected_forward(x),
                                          rtol=1e-6)
        # Coalescing happened (5 requests, fewer device calls) and
        # every device batch was a power-of-two bucket.
        assert len(model.forward_shapes) < len(inputs)
        assert all(shape[0] == next_pow2(shape[0])
                   for shape in model.forward_shapes)
    finally:
        engine.stop()


def test_engine_coalesces_generate_with_per_request_geometry():
    model = FakeModel(delay=0.15)
    engine = ServingEngine(model, max_batch=8,
                           queue_depth=64).start()
    try:
        # A blocker occupies the device so the two generate requests
        # queue together and must coalesce into ONE bucketed batch.
        blocker = threading.Thread(
            target=engine.submit_classify,
            args=(numpy.zeros((1, 4), numpy.float32),))
        blocker.start()
        time.sleep(0.01)
        p_a = numpy.array([[5, 7, 9]], numpy.int32)
        p_b = numpy.array([[11, 13, 17, 19, 23]], numpy.int32)
        out = {}

        def gen(name, tokens, max_new):
            out[name] = engine.submit_generate(tokens, max_new)

        ta = threading.Thread(target=gen, args=("a", p_a, 3))
        tb = threading.Thread(target=gen, args=("b", p_b, 4))
        ta.start()
        tb.start()
        ta.join()
        tb.join()
        blocker.join()
        # Same (prompt, decode) buckets -> one coalesced device call
        # with both rows, padded to the bucket width.
        assert len(model.gen_shapes) == 1
        (shape, m), = model.gen_shapes
        assert shape == (2, 16) and m == 16  # floors: prompt 16, new 16
        # ...and each request got its own geometry back: its own
        # prompt, its own max_new, tokens derived from ITS last token.
        assert out["a"].shape == (1, 6)
        assert out["b"].shape == (1, 9)
        numpy.testing.assert_array_equal(
            out["a"][0, 3:], _expected_generated(p_a[0], 3))
        numpy.testing.assert_array_equal(
            out["b"][0, 5:], _expected_generated(p_b[0], 4))
    finally:
        engine.stop()


def test_fifty_lengths_through_engine_reach_three_buckets():
    model = FakeModel()
    engine = ServingEngine(model, max_batch=8,
                           queue_depth=64).start()
    try:
        for length in range(1, 51):
            prompt = numpy.arange(length, dtype=numpy.int32)[None]
            engine.submit_generate(prompt, 4)
        widths = {shape[1] for shape, _ in model.gen_shapes}
        assert widths <= {16, 32, 64}
        assert len(widths) <= numpy.ceil(numpy.log2(50))
    finally:
        engine.stop()


def test_engine_rejects_overlong_prompt_eagerly():
    engine = ServingEngine(FakeModel(), max_batch=8)
    # Never started: eager validation happens on the submit path.
    with pytest.raises(Bug, match="positional"):
        engine.submit_generate(
            numpy.zeros((1, 60), numpy.int32), 10)
    # A non-positive decode budget must be rejected HERE — downstream
    # only sees the bucket (>= the floor), so it would otherwise
    # slice garbage into a 200 response.
    for bad in (0, -5):
        with pytest.raises(Bug, match="max_new"):
            engine.submit_generate(
                numpy.zeros((1, 4), numpy.int32), bad)
    # Past the policy's decode cap, bucketing degrades to one key
    # per distinct value — so the cap is a hard request limit.
    capped = ServingEngine(
        FakeModel(),
        policy=BucketPolicy(max_batch=8, new_cap=16))
    with pytest.raises(Bug, match="serving cap"):
        capped.submit_generate(numpy.zeros((1, 4), numpy.int32), 17)


def test_hostile_seed_cannot_poison_a_coalesced_batch():
    """An arbitrary-precision client seed folds into the 32-bit PRNG
    key width at submission — it must never reach the device thread,
    where an int64 overflow would 500 every batched neighbor."""
    model = FakeModel()
    engine = ServingEngine(model, max_batch=8).start()
    try:
        prompt = numpy.array([[3, 1, 4]], numpy.int32)
        full = engine.submit_generate(prompt, 2, seed=2 ** 80 + 7)
        numpy.testing.assert_array_equal(
            full[0, 3:], _expected_generated(prompt[0], 2))
    finally:
        engine.stop()


def test_non_ascii_token_authenticates_over_the_wire():
    """An operator CAN use a non-ASCII token: the server recovers the
    client's wire bytes (latin-1, the inverse of http.server's header
    decode) and matches the token's UTF-8 encoding — what curl-style
    clients send."""
    from veles_tpu.restful import ModelServer
    server = ModelServer(FakeModel(), host="127.0.0.1", port=0,
                         token="café").start()
    try:
        payload = {"tokens": [[1, 2, 3]], "max_new_tokens": 2}
        # urllib encodes str headers as latin-1; smuggle the UTF-8
        # wire bytes a curl client would send.
        wire = "café".encode("utf-8").decode("latin-1")
        status, _, _ = _post(server.port, "/api/generate", payload,
                             headers={"X-Status-Token": wire})
        assert status == 200
        status, _, _ = _post(server.port, "/api/generate", payload,
                             headers={"X-Status-Token": "wrong"})
        assert status == 403
    finally:
        server.stop()


def test_engine_splits_oversized_requests():
    """The pre-engine handler accepted any batch size; the engine
    preserves that by chunking wide requests — only DEVICE batches
    are bounded."""
    model = FakeModel()
    engine = ServingEngine(model, max_batch=8).start()
    try:
        x = numpy.random.RandomState(1).rand(20, 4) \
            .astype(numpy.float32)
        y = engine.submit_classify(x)
        numpy.testing.assert_allclose(y, _expected_forward(x),
                                      rtol=1e-6)
        assert all(s[0] <= 8 for s in model.forward_shapes)
        prompts = numpy.tile(numpy.array([[3, 1, 4]], numpy.int32),
                             (10, 1))
        full = engine.submit_generate(prompts, 2)
        assert full.shape == (10, 5)
        for i in range(10):
            numpy.testing.assert_array_equal(
                full[i, 3:], _expected_generated(prompts[i], 2))
        assert all(s[0][0] <= 8 for s in model.gen_shapes)
    finally:
        engine.stop()


# -- admission through the engine and the HTTP surface ---------------------


def test_queue_full_raises_429_shaped_error():
    model = FakeModel(delay=0.2)
    engine = ServingEngine(model, max_batch=1,
                           queue_depth=1).start()
    try:
        t = threading.Thread(
            target=engine.submit_classify,
            args=(numpy.zeros((1, 4), numpy.float32),))
        t.start()
        time.sleep(0.05)  # device busy; next request queues
        t2 = threading.Thread(
            target=lambda: engine.submit_classify(
                numpy.zeros((1, 4), numpy.float32)))
        t2.start()
        time.sleep(0.05)  # queue now at depth
        with pytest.raises(QueueFull) as e:
            engine.submit_classify(numpy.zeros((1, 4),
                                               numpy.float32))
        assert e.value.status == 429
        assert e.value.retry_after is not None
        assert engine.stats.get("rejected.queue_full") == 1
        t.join()
        t2.join()
    finally:
        engine.stop()


def test_deadline_cancels_queued_work_unserved():
    model = FakeModel(delay=0.3)
    engine = ServingEngine(model, max_batch=1,
                           queue_depth=8).start()
    try:
        blocker = threading.Thread(
            target=engine.submit_classify,
            args=(numpy.zeros((1, 4), numpy.float32),))
        blocker.start()
        time.sleep(0.05)
        marker = numpy.full((1, 4), 7.0, numpy.float32)
        with pytest.raises(DeadlineExceeded) as e:
            engine.submit_classify(marker, deadline=Deadline(0.01))
        assert e.value.status == 504
        blocker.join()
        time.sleep(0.05)
        # The cancelled request's rows never reached the device.
        assert all(shape[0] == 1 for shape in model.forward_shapes)
        assert len(model.forward_shapes) == 1
        assert engine.stats.get("cancelled.deadline") == 1
    finally:
        engine.stop()


@pytest.fixture
def flooded_server():
    from veles_tpu.restful import ModelServer
    model = FakeModel(delay=0.08)
    server = ModelServer(model, host="127.0.0.1", port=0,
                         max_batch=1, queue_depth=2).start()
    yield model, server
    server.stop()


def test_backpressure_429_while_health_stays_live(flooded_server):
    _, server = flooded_server
    statuses, retry_afters = [], []
    lock = threading.Lock()

    def flood():
        status, body, headers = _post(
            server.port, "/api", {"input": [[1.0, 2.0, 3.0, 4.0]]})
        with lock:
            statuses.append(status)
            if status == 429:
                retry_afters.append(headers.get("Retry-After"))

    threads = [threading.Thread(target=flood) for _ in range(12)]
    for t in threads:
        t.start()
    # While the flood drains, /health answers immediately — it never
    # touches the device thread.
    t0 = time.monotonic()
    status, body = _get(server.port, "/health")
    health_latency = time.monotonic() - t0
    assert status == 200 and body["status"] == "ok"
    assert "queue_depth" in body
    assert health_latency < 2.0
    for t in threads:
        t.join()
    assert 200 in statuses
    assert 429 in statuses
    # Every 429 carried a Retry-After hint.
    assert retry_afters and all(r is not None for r in retry_afters)


def test_http_deadline_maps_to_504(flooded_server):
    model, server = flooded_server
    blocker = threading.Thread(
        target=_post, args=(server.port, "/api",
                            {"input": [[0.0] * 4]}))
    blocker.start()
    time.sleep(0.03)
    status, body, _ = _post(server.port, "/api",
                            {"input": [[1.0] * 4],
                             "deadline": 0.001})
    blocker.join()
    assert status == 504
    assert "deadline" in body["error"]


# -- /stats + token gate ---------------------------------------------------


def test_stats_endpoint_counters():
    from veles_tpu.restful import ModelServer
    server = ModelServer(FakeModel(), host="127.0.0.1", port=0,
                         max_batch=4).start()
    try:
        for _ in range(3):
            status, _, _ = _post(server.port, "/api",
                                 {"input": [[1.0] * 4]})
            assert status == 200
        status, _, _ = _post(server.port, "/api/generate",
                             {"tokens": [[1, 2, 3]],
                              "max_new_tokens": 4})
        assert status == 200
        status, stats = _get(server.port, "/stats")
        assert status == 200
        assert stats["queue_depth"] == 0
        assert stats["max_batch"] == 4
        assert stats["counters"]["requests.classify"] == 3
        assert stats["counters"]["requests.generate"] == 1
        assert stats["counters"]["batches.classify"] >= 1
        assert stats["batch_occupancy"]  # non-empty histogram
        lat = stats["latency"]["request.classify"]
        assert lat["count"] == 3
        assert lat["p50_ms"] is not None
        assert lat["p99_ms"] >= lat["p50_ms"]
    finally:
        server.stop()


def test_generate_gated_behind_status_token():
    from veles_tpu.restful import ModelServer
    server = ModelServer(FakeModel(), host="127.0.0.1", port=0,
                         token="s3cret").start()
    try:
        payload = {"tokens": [[1, 2, 3]], "max_new_tokens": 2}
        status, body, _ = _post(server.port, "/api/generate", payload)
        assert status == 403
        status, _, _ = _post(server.port, "/api/generate", payload,
                             headers={"X-Status-Token": "wrong"})
        assert status == 403
        # Non-ASCII header bytes must 403, not crash the handler
        # (compare_digest rejects non-ASCII str operands).
        status, _, _ = _post(server.port, "/api/generate", payload,
                             headers={"X-Status-Token": "café"})
        assert status == 403
        # Oversized Content-Length is refused before the body is
        # buffered (unauthenticated memory-DoS guard).
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=10)
        conn.request("POST", "/api/generate", body=b"x",
                     headers={"Content-Type": "application/json",
                              "Content-Length": str(1 << 31)})
        assert conn.getresponse().status == 400
        conn.close()
        status, body, _ = _post(server.port, "/api/generate", payload,
                                headers={"X-Status-Token": "s3cret"})
        assert status == 200
        assert len(body["generated"][0]) == 2
        # The classify endpoint is not token-gated (parity with the
        # reference's open /api), only the compile-heavy surface is.
        status, _, _ = _post(server.port, "/api",
                             {"input": [[0.0] * 4]})
        assert status == 200
    finally:
        server.stop()


def test_rate_limit_answers_429():
    from veles_tpu.restful import ModelServer
    server = ModelServer(FakeModel(), host="127.0.0.1", port=0,
                         rate_limit=2.0).start()
    try:
        statuses = [
            _post(server.port, "/api", {"input": [[0.0] * 4]})[0]
            for _ in range(6)]
        assert statuses.count(200) >= 1
        assert 429 in statuses
    finally:
        server.stop()


# -- throughput ------------------------------------------------------------


def test_batched_throughput_at_least_2x_serial():
    """The acceptance demo: the same per-call device cost, 16
    requests — the serial handler pays it 16 times, the engine
    coalesces.  Wall-clock ratio must be >= 2 (it is ~5 in
    practice); the call-count assertion pins WHY."""
    delay = 0.03
    serial_model = FakeModel(delay=delay)
    t0 = time.monotonic()
    for _ in range(16):
        serial_model.forward(numpy.zeros((1, 4), numpy.float32))
    serial_time = time.monotonic() - t0

    batched_model = FakeModel(delay=delay)
    engine = ServingEngine(batched_model, max_batch=16,
                           queue_depth=64).start()
    try:
        threads = [threading.Thread(
            target=engine.submit_classify,
            args=(numpy.zeros((1, 4), numpy.float32),))
            for _ in range(16)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        batched_time = time.monotonic() - t0
    finally:
        engine.stop()
    assert len(batched_model.forward_shapes) <= 8
    assert serial_time / batched_time >= 2.0, \
        "batched %.3fs vs serial %.3fs" % (batched_time, serial_time)


# -- bucketed decode parity (real artifact) --------------------------------


@pytest.fixture(scope="module")
def random_lm(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("serving") / "rand.veles.tgz")
    model = ExportedModel(_random_lm_artifact(path))
    model._test_artifact_path = path  # for fresh-load tests
    return model


def test_bucketed_generate_matches_unbucketed_greedy(random_lm):
    """Coalesced rows of DIFFERENT true lengths in one padded bucket
    decode bit-identically to per-request generate() — the masking
    proof, on real attention."""
    model = random_lm
    rng = numpy.random.RandomState(7)
    # A straggler (2), a middle length, and a full-width row (8 =
    # the bucket) in ONE padded batch.  Three lengths, not more:
    # each distinct length costs an unbucketed generate() compile
    # and the tier-1 budget is tight.
    lengths = [2, 5, 8]
    prompts = numpy.zeros((3, 8), numpy.int32)
    refs = []
    for i, length in enumerate(lengths):
        p = rng.randint(0, 13, (1, length)).astype(numpy.int32)
        prompts[i, :length] = p[0]
        refs.append(model.generate(p, 6)[0, length:])
    gen = model.generate_bucketed(prompts, lengths, 6)
    for i in range(3):
        numpy.testing.assert_array_equal(gen[i], refs[i])


def test_bucketed_generate_deterministic_sampling(random_lm):
    model = random_lm
    # Same (B, S0b, max_new) bucket triple as the parity test — a
    # compile-cache HIT, so this test costs no extra XLA compile.
    prompts = numpy.zeros((3, 8), numpy.int32)
    prompts[0, :3] = [1, 2, 3]
    prompts[1, :4] = [4, 5, 6, 7]
    prompts[2, :2] = [8, 9]
    lens = [3, 4, 2]
    a = model.generate_bucketed(prompts, lens, 6,
                                temperatures=1.3, seeds=[11, 12, 13])
    b = model.generate_bucketed(prompts, lens, 6,
                                temperatures=1.3, seeds=[11, 12, 13])
    numpy.testing.assert_array_equal(a, b)
    # Compile-cache accounting saw these calls (hit on the repeat).
    stats = model.compile_cache.stats()
    assert stats["hits"] >= 1
    assert stats["misses"] >= 1


def test_bucketed_generate_validates_geometry(random_lm):
    model = random_lm
    prompts = numpy.zeros((1, 8), numpy.int32)
    with pytest.raises(Bug, match="lengths"):
        model.generate_bucketed(prompts, [9], 4)
    # A prompt bucket beyond the positional table (32 here) is
    # refused eagerly; an over-bucket DECODE budget is not — the
    # engine validates each request's true need, and over-bucket
    # steps are discardable junk by construction.
    with pytest.raises(Bug, match="positional"):
        model.generate_bucketed(numpy.zeros((1, 40), numpy.int32),
                                [40], 4)


# -- satellite regressions -------------------------------------------------


def test_moe_artifact_generate_has_precise_refusal(tmp_path):
    units = [
        {"name": "emb", "type": "embedding",
         "config": {"vocab_size": 4, "embed_dim": 4},
         "params": {"weights": "e__w", "pos": "e__p"}},
        {"name": "moe", "type": "moe_transformer_block",
         "config": {"n_heads": 1, "n_experts": 2,
                    "capacity_factor": 1.0, "causal": 1},
         "params": {}},
        {"name": "head", "type": "lm_head",
         "config": {"output_sample_shape": [4]},
         "params": {"weights": "h__w"}},
    ]
    weights = {"e__w": numpy.zeros((4, 4), numpy.float32),
               "e__p": numpy.zeros((8, 4), numpy.float32),
               "h__w": numpy.zeros((4, 4), numpy.float32)}
    path = _write_artifact(tmp_path / "moe.veles.tgz", units, weights)
    # An artifact of the removed capacity-routed block is refused at
    # LOAD, by the kind's name, not as an unknown unit three calls in.
    with pytest.raises(Bug, match="moe_transformer_block.*removed"):
        ExportedModel(path)


def test_tp_plan_degrades_on_uninitialized_unit():
    """Pre-initialize sharding (input not linked yet) returns None —
    replicated — instead of raising (ADVICE low, mesh.py:129)."""
    import veles_tpu.prng as prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.parallel.mesh import _transformer_tp_plan
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    prng.reset()
    prng.get(0).seed(1)
    wf = TinyLMWorkflow(Launcher(), n_blocks=1, max_epochs=1)
    block = [u for u in wf.forwards
             if type(u).__name__.endswith("TransformerBlock")][0]
    assert block.input is None or block.input.shape is None
    assert _transformer_tp_plan(block, 2, "model") is None


# -- warmup ----------------------------------------------------------------


def test_warmup_precompiles_the_bucket_grid():
    model = FakeModel()
    engine = ServingEngine(model, max_batch=4)
    compiles = engine.warmup(longest_prompt=20, max_new=4)
    assert compiles > 0
    assert engine.stats.get("warmup.compiles") == compiles
    # Classify warmed each batch bucket; generate warmed the
    # (batch × prompt) grid at the decode-bucket floor.
    assert {s[0] for s in model.forward_shapes} == {1, 2, 4}
    widths = {shape[1] for shape, _ in model.gen_shapes}
    assert widths == {16, 32}


def test_warmup_defaults_cover_the_handler_default_budget():
    """A no-field /api/generate defaults to max_new_tokens=32; the
    default warmup must cover that decode bucket, not just the
    floor."""
    model = FakeModel()
    engine = ServingEngine(model, max_batch=2)
    engine.warmup()
    budgets = {m for _, m in model.gen_shapes}
    assert budgets == {16, 32}


def test_compile_cache_capacity_grows_to_hold_warmup_grid():
    """A cache smaller than the warmup grid would evict its own
    earliest compiles while warming — the engine grows it first."""

    class CachedFake(FakeModel):
        def __init__(self):
            super(CachedFake, self).__init__()
            self.compile_cache = CompileCache(capacity=2)

    model = CachedFake()
    engine = ServingEngine(model, max_batch=4)
    engine.warmup(longest_prompt=20)
    grid = len(engine.policy.grid()) + \
        len(engine.policy.grid(20, ServingEngine.DEFAULT_MAX_NEW))
    assert model.compile_cache.capacity >= grid


def test_fwd_sentinels_evict_as_a_group(random_lm):
    """All forward shapes hide behind ONE jit callable — evicting one
    fwd sentinel must drop them all, or the survivors would report
    cache HITs while forward() silently recompiles."""
    model = random_lm
    cache = model.compile_cache
    model.forward_bucketed(numpy.zeros((1, 8), numpy.float32), 2)
    model.forward_bucketed(numpy.zeros((1, 8), numpy.float32), 4)
    fwd_keys = [k for k in list(cache._entries)
                if k and k[0] == "fwd"]
    assert len(fwd_keys) == 2
    cache.on_evict(fwd_keys[0], True)  # what capacity pressure does
    assert not any(k and k[0] == "fwd"
                   for k in list(cache._entries))
    assert model._jit_forward is None


# -- paged KV block pool (host-side accounting) ----------------------------


def test_kv_block_pool_accounting():
    copies = []
    pool = KVBlockPool(8, 4, storage="S",
                       copy_fn=lambda s, a, b: copies.append(
                           (a, b)) or s)
    assert pool.usable == 7  # block 0 is trash
    assert pool.blocks_for(1) == 1 and pool.blocks_for(9) == 3
    ids = pool.alloc(3)
    assert len(ids) == 3 and KVBlockPool.TRASH not in ids
    assert pool.free_count() == 4 and pool.used_count() == 3
    pool.retain(ids[:1])
    pool.release(ids)      # ids[0] still held by the extra ref
    assert pool.free_count() == 6
    pool.release(ids[:1])
    assert pool.free_count() == 7
    # Trash ids are ignored by retain/release (table padding).
    pool.release([KVBlockPool.TRASH])
    assert pool.free_count() == 7
    # Over-ask fails cleanly — the caller sheds.
    assert pool.alloc(8) is None
    # COW copies through the model-supplied device copy.
    a = pool.alloc(1)[0]
    b = pool.cow_copy(a)
    assert b != a and copies == [(a, b)]
    assert pool.occupancy()["cow_copies"] == 1


def test_kv_block_pool_prefix_cache_and_eviction():
    pool = KVBlockPool(9, 4)
    tokens = numpy.arange(10, dtype=numpy.int32)  # 2 full blocks
    ids = pool.alloc(3)
    pool.register_prefix(tokens, ids)
    # Full-block granularity: prefixes of 1 and 2 blocks match, the
    # partial tail does not ride the cache.
    k, got = pool.lookup_prefix(tokens)
    assert k == 2 and got == ids[:2]
    pool.release(got)
    k, got = pool.lookup_prefix(tokens[:7])  # 1 full block + tail
    assert k == 1 and got == ids[:1]
    pool.release(got)
    k, got = pool.lookup_prefix(
        numpy.arange(100, 110, dtype=numpy.int32))
    assert k == 0 and got == []
    occ = pool.occupancy()
    assert occ["prefix_hits"] == 2 and occ["prefix_misses"] == 1
    assert occ["prefix_entries"] == 2
    # The cache holds refs: releasing the row's own refs keeps the
    # blocks resident...
    pool.release(ids)
    assert pool.occupancy()["blocks_used"] == 2
    # ...until allocation pressure evicts entries LRU-first — cached
    # prompts are an optimization, never a reason to refuse traffic.
    big = pool.alloc(8)
    assert big is not None
    assert pool.occupancy()["prefix_entries"] == 0


# -- paged decode through the engine (real artifact) -----------------------


def _paged_engine(model, **kw):
    defaults = dict(max_batch=4, kv_blocks=32, kv_block_size=4)
    defaults.update(kw)
    return ServingEngine(model, **defaults)


def test_paged_engine_greedy_matches_dense_bucketed(random_lm):
    """THE acceptance gate: greedy decode through the paged path —
    block tables, gather/scatter, continuous batching — is
    TOKEN-IDENTICAL to the proven dense ``generate_bucketed``
    program, on real attention, across coalesced rows of different
    lengths."""
    model = random_lm
    rng = numpy.random.RandomState(7)
    lengths = [2, 5, 8]
    prompts = numpy.zeros((3, 8), numpy.int32)
    rows = []
    for i, length in enumerate(lengths):
        p = rng.randint(0, 13, (1, length)).astype(numpy.int32)
        prompts[i, :length] = p[0]
        rows.append(p)
    ref = model.generate_bucketed(prompts, lengths, 6)
    engine = _paged_engine(model).start()
    try:
        assert engine.paged and engine.kv_pool is not None
        out = {}

        def gen(i):
            out[i] = engine.submit_generate(rows[i], 6)

        threads = [threading.Thread(target=gen, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, length in enumerate(lengths):
            numpy.testing.assert_array_equal(
                out[i][0, length:], ref[i])
        # The decode ran through the paged surface, not the dense
        # program: step batches executed and tokens were counted.
        assert engine.stats.get("batches.decode") >= 1
        assert engine.stats.get("tokens.generated") >= 18
    finally:
        engine.stop()


def test_paged_prefix_reuse_and_cow(random_lm):
    """A re-sent prompt adopts its cached blocks (prefilled ONCE) —
    and because the whole prompt is cached, the first decode write
    lands inside the last shared block, forcing a copy-on-write —
    with output still token-identical to the dense path."""
    model = random_lm
    rng = numpy.random.RandomState(21)
    prompt = rng.randint(0, 13, (1, 8)).astype(numpy.int32)
    ref = model.generate_bucketed(prompt.copy(), [8], 6)
    engine = _paged_engine(model).start()
    try:
        first = engine.submit_generate(prompt, 6)
        numpy.testing.assert_array_equal(first[0, 8:], ref[0])
        occ0 = engine.kv_pool.occupancy()
        assert occ0["prefix_entries"] >= 1
        second = engine.submit_generate(prompt, 6)
        numpy.testing.assert_array_equal(second[0, 8:], ref[0])
        occ1 = engine.kv_pool.occupancy()
        assert occ1["prefix_hits"] >= occ0["prefix_hits"] + 1
        assert occ1["cow_copies"] >= occ0["cow_copies"] + 1
    finally:
        engine.stop()


def test_paged_pool_geometry_is_a_compile_key(random_lm):
    """Flipping the pool's block size must reach a DIFFERENT
    executable — a stale program compiled for another geometry would
    scatter k/v into the wrong slots."""
    model = random_lm
    tokens = numpy.array([[3, 1, 4, 1]], numpy.int32)
    outs = []
    for bs in (4, 8):
        pool = model.make_kv_pool(9, bs)
        tables = numpy.zeros((1, 2), numpy.int32)
        ids = pool.alloc(2)
        tables[0, :2] = ids
        tok0 = model.paged_extend(
            pool, tables, tokens,
            numpy.zeros(1, numpy.int32),
            numpy.full(1, 4, numpy.int32),
            numpy.zeros(1, numpy.float32),
            numpy.zeros(1, numpy.uint32))
        outs.append(int(tok0[0]))
    pext_keys = {k for k in list(model.compile_cache._entries)
                 if k and k[0] == "pext" and k[4] == 9}
    assert len(pext_keys) == 2  # one per block size
    assert {k[5] for k in pext_keys} == {4, 8}
    # Same content, different layout — same first token.
    assert outs[0] == outs[1]


def test_paged_decode_ignores_fastpath_knobs(random_lm):
    """PR-5 contract extended to the paged path: the paged programs
    pin f32/XLA attention arithmetic, so flipping the attention
    fast-path knobs in the process must not change a single decoded
    token."""
    from veles_tpu.config import root
    model = random_lm
    prompt = numpy.array([[7, 3, 1, 4, 1]], numpy.int32)
    ref = model.generate_bucketed(
        numpy.pad(prompt, ((0, 0), (0, 3))), [5], 4)
    was = (root.common.engine.attention_dtype,
           root.common.engine.attention_kernel)
    root.common.engine.attention_dtype = "bf16"
    root.common.engine.attention_kernel = "auto"
    try:
        # A FRESH model: its paged programs trace under the flipped
        # knobs — deployed bits must still be identical.
        flipped = ExportedModel(model._test_artifact_path)
        engine = _paged_engine(flipped).start()
        try:
            out = engine.submit_generate(prompt, 4)
            numpy.testing.assert_array_equal(out[0, 5:], ref[0])
        finally:
            engine.stop()
    finally:
        # As found: "xla" left behind made a later file of the same
        # xdist worker (test_tpu_compile) miss the kernel.
        root.common.engine.attention_dtype, \
            root.common.engine.attention_kernel = was


# -- paged decode scheduling (fake model, no compiles) ---------------------


def test_paged_continuous_batching_beats_whole_request():
    """The tier-1 loopback acceptance gate: on mixed decode budgets,
    whole-request batching pays the padded decode bucket per group
    and serializes incompatible groups, while decode-step continuous
    batching runs exactly the needed steps with every stream riding
    one batch — strictly higher aggregate tok/s, same per-token
    device cost."""
    delay = 0.01
    needs = [3, 5, 9, 17, 20, 31]
    prompts = [numpy.array([[5, 7, 9, 11]], numpy.int32)
               for _ in needs]

    def drive(engine):
        outs = [None] * len(needs)

        def gen(i):
            outs[i] = engine.submit_generate(prompts[i], needs[i])

        threads = [threading.Thread(target=gen, args=(i,))
                   for i in range(len(needs))]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        for i, n in enumerate(needs):
            numpy.testing.assert_array_equal(
                outs[i][0, 4:], _expected_generated(prompts[i][0], n))
        return sum(needs) / wall

    dense_model = FakeModel()
    dense_model.per_token_delay = delay
    dense = ServingEngine(
        dense_model, max_batch=8,
        policy=BucketPolicy(max_batch=8, new_floor=4)).start()
    try:
        dense_tps = drive(dense)
    finally:
        dense.stop()

    paged = ServingEngine(
        PagedFakeModel(step_delay=delay), max_batch=8,
        kv_blocks=64, kv_block_size=8,
        policy=BucketPolicy(max_batch=8, new_floor=4)).start()
    try:
        paged_tps = drive(paged)
    finally:
        paged.stop()
    # Dense pays bucketed decode steps per (serialized) group:
    # buckets 4+8+16+32 = 60 device steps for 85 real tokens; paged
    # pays ~32 steps total with every row coalesced.  Strictly
    # higher, with margin for scheduler jitter.
    assert paged_tps > dense_tps * 1.15, \
        "paged %.1f tok/s vs dense %.1f tok/s" % (paged_tps,
                                                  dense_tps)


def test_paged_rows_join_and_retire_mid_flight():
    """Iteration-level scheduling: a short request submitted while a
    long one is mid-decode joins the RUNNING batch (no whole-request
    boundary) and retires ahead of it, freeing its blocks
    immediately."""
    model = PagedFakeModel(step_delay=0.02)
    engine = ServingEngine(model, max_batch=4, kv_blocks=33,
                           kv_block_size=8).start()
    try:
        done = {}

        def long_req():
            out = engine.submit_generate(
                numpy.array([[9, 9, 9]], numpy.int32), 40)
            done["long"] = time.monotonic()
            done["long_out"] = out

        t_long = threading.Thread(target=long_req)
        t_long.start()
        time.sleep(0.2)  # the long request is decoding by now
        short_out = engine.submit_generate(
            numpy.array([[5, 7]], numpy.int32), 3)
        done["short"] = time.monotonic()
        t_long.join()
        assert done["short"] < done["long"]
        numpy.testing.assert_array_equal(
            short_out[0, 2:],
            _expected_generated(numpy.array([5, 7]), 3))
        numpy.testing.assert_array_equal(
            done["long_out"][0, 3:],
            _expected_generated(numpy.array([9, 9, 9]), 40))
        # 40 tokens = 1 from prefill + 39 decode steps; the short
        # request rode those same steps rather than its own batch.
        assert engine.stats.get("batches.decode") >= 39
    finally:
        engine.stop()


def test_paged_pool_exhaustion_sheds_429():
    """Admission control under paged decode sheds on the BLOCK POOL,
    not the queue: a request whose worst-case block need does not
    fit on top of existing commitments is refused 429 with a
    Retry-After derived from the running batch's retirement
    horizon."""
    model = PagedFakeModel(step_delay=0.03)
    engine = ServingEngine(model, max_batch=4, kv_blocks=9,
                           kv_block_size=8).start()
    try:
        blocker = threading.Thread(
            target=engine.submit_generate,
            args=(numpy.array([[1] * 8], numpy.int32), 40))
        blocker.start()
        time.sleep(0.15)  # 6 of 8 usable blocks committed
        with pytest.raises(PoolExhausted) as e:
            engine.submit_generate(
                numpy.array([[2] * 8], numpy.int32), 40)
        assert e.value.status == 429
        assert e.value.retry_after is not None
        assert engine.stats.get("rejected.pool_exhausted") == 1
        blocker.join()
        # A request that can NEVER fit is a client/config error, not
        # a retry-later.
        with pytest.raises(Bug, match="KV blocks"):
            engine.submit_generate(
                numpy.tile(numpy.array([[3] * 8], numpy.int32),
                           (2, 1)), 40)
    finally:
        engine.stop()


def test_paged_queue_depth_still_backstops():
    """The pool is the primary shed point, but --queue-depth stays
    live on the paged path as the payload-memory backstop: tiny
    requests on a big pool must not park unbounded handler
    threads."""
    model = PagedFakeModel(step_delay=0.05)
    engine = ServingEngine(model, max_batch=1, queue_depth=1,
                           kv_blocks=65, kv_block_size=8).start()
    try:
        prompt = numpy.array([[1, 2]], numpy.int32)
        first = threading.Thread(
            target=engine.submit_generate, args=(prompt, 20))
        first.start()
        time.sleep(0.15)  # adopted into the decode batch by now
        second = threading.Thread(
            target=lambda: engine.submit_generate(prompt, 20))
        second.start()
        time.sleep(0.15)  # waiting for adoption: queue at depth
        with pytest.raises(QueueFull) as e:
            engine.submit_generate(prompt, 20)
        assert e.value.status == 429
        assert engine.stats.get("rejected.queue_full") == 1
        first.join()
        second.join()
    finally:
        engine.stop()


def test_paged_deadline_cancels_mid_decode():
    """A deadline expiring MID-DECODE retires the request's rows and
    frees their blocks — a hung client cannot squat on the pool."""
    model = PagedFakeModel(step_delay=0.05)
    engine = ServingEngine(model, max_batch=4, kv_blocks=17,
                           kv_block_size=8).start()
    try:
        with pytest.raises(DeadlineExceeded):
            engine.submit_generate(
                numpy.array([[1, 2, 3]], numpy.int32), 60,
                deadline=Deadline(0.3))
        deadline_wait = time.monotonic()
        while engine.kv_pool.occupancy()["blocks_used"] and \
                time.monotonic() - deadline_wait < 5.0:
            time.sleep(0.02)
        occ = engine.kv_pool.occupancy()
        assert occ["blocks_used"] == 0  # blocks freed on cancel
    finally:
        engine.stop()


def test_serve_load_tiny_paged():
    """Tier-1 micro-soak (the 64-stream bench.py --serve soak is
    marked slow): 4 concurrent streams of 8-token decodes through
    the paged engine, with the operator metrics the soak reports —
    tok/s, TTFT/ITL windows, pool gauges — all live."""
    model = PagedFakeModel(step_delay=0.002)
    engine = ServingEngine(model, max_batch=4, kv_blocks=17,
                           kv_block_size=8).start()
    try:
        def stream(idx):
            for _ in range(2):
                p = numpy.array([[idx + 1, idx + 2]], numpy.int32)
                out = engine.submit_generate(p, 8)
                numpy.testing.assert_array_equal(
                    out[0, 2:], _expected_generated(p[0], 8))

        threads = [threading.Thread(target=stream, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = engine.stats.snapshot()
        assert snap["decode_tok_per_sec"] > 0
        assert snap["counters"]["tokens.generated"] == 64
        assert snap["latency"]["ttft.generate"]["count"] == 8
        assert snap["latency"]["itl.decode"]["p50_ms"] is not None
        assert snap["gauges"]["kv_blocks_total"] == 16
        assert snap["gauges"]["kv_blocks_used"] == 0  # all retired
    finally:
        engine.stop()


@pytest.mark.slow
def test_serve_soak_64_streams():
    """The ≥64-stream soak (slow tier): mixed prompt/decode
    geometry, a pool deliberately too small for the worst case, ~3
    seconds of sustained load — every completed request is
    token-correct, shedding is graceful 429 (no other errors), and
    the live stats carry the soak's numbers."""
    model = PagedFakeModel(step_delay=0.001)
    engine = ServingEngine(model, max_batch=32, kv_blocks=129,
                           kv_block_size=8,
                           default_deadline=60.0).start()
    stop_at = time.monotonic() + 3.0
    totals = {"tokens": 0, "requests": 0, "shed": 0, "errors": 0}
    lock = threading.Lock()

    def stream(idx):
        rng = numpy.random.RandomState(idx)
        while time.monotonic() < stop_at:
            s = int(rng.choice([2, 5, 8, 13]))
            m = int(rng.choice([4, 8, 16, 32]))
            p = rng.randint(0, 90, (1, s)).astype(numpy.int32)
            try:
                out = engine.submit_generate(p, m)
                numpy.testing.assert_array_equal(
                    out[0, s:], _expected_generated(p[0], m))
                with lock:
                    totals["tokens"] += m
                    totals["requests"] += 1
            except PoolExhausted:
                with lock:
                    totals["shed"] += 1
                time.sleep(0.01)
            except Exception:
                with lock:
                    totals["errors"] += 1

    threads = [threading.Thread(target=stream, args=(i,))
               for i in range(64)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    engine.stop()
    assert totals["errors"] == 0
    assert totals["requests"] >= 64
    assert totals["shed"] >= 1  # the pool IS the limiter
    assert totals["tokens"] > 0


# -- satellite: per-kind drain estimates -----------------------------------


def test_drain_estimate_is_per_kind():
    """A multi-second generate batch must not poison the Retry-After
    quoted to a cheap classify flood: the estimate mixes per-kind
    EWMAs by the queue's actual composition."""
    from veles_tpu.serving.engine import _Request
    engine = ServingEngine(FakeModel(), max_batch=4)
    engine._batch_ewma = {"classify": 0.02, "generate": 8.0}
    for _ in range(8):
        engine._pending.append(_Request("classify", ("c",), 1, None))
    # 8 classify = 2 batches x 0.02s -> floors at 1s, NOT 2x8s.
    assert engine._drain_estimate_locked() == 1.0
    for _ in range(4):
        engine._pending.append(_Request("generate", ("g",), 1, None))
    # ...but queued generate work IS quoted at generate cost.
    est = engine._drain_estimate_locked()
    assert 8.0 <= est <= 9.0


# -- satellite: end-to-end deadlines across chunks -------------------------


def test_chunked_request_deadline_fails_fast():
    """An oversized request splits into sequential chunks that all
    share the ORIGINAL deadline — a nearly-expired budget fails fast
    with zero device work instead of half-generating."""
    model = FakeModel()
    engine = ServingEngine(model, max_batch=2).start()
    try:
        deadline = Deadline(1e-9)
        time.sleep(0.01)
        prompts = numpy.tile(numpy.array([[3, 1, 4]], numpy.int32),
                             (6, 1))
        with pytest.raises(DeadlineExceeded):
            engine.submit_generate(prompts, 2, deadline=deadline)
        time.sleep(0.05)
        assert model.gen_shapes == []  # no device call at all
        assert engine.stats.get("cancelled.deadline") >= 1
        # Same contract on the classify split path.
        with pytest.raises(DeadlineExceeded):
            engine.submit_classify(
                numpy.zeros((6, 4), numpy.float32),
                deadline=Deadline(1e-9))
        assert model.forward_shapes == []
    finally:
        engine.stop()


# -- satellite: stats gauges + token rate ----------------------------------


def test_stats_gauges_and_token_rate():
    stats = ServingStats()
    stats.set_gauge("kv_blocks_used", 12)
    stats.note_tokens(30)
    stats.observe_latency("ttft.generate", 0.25)
    stats.observe_latency("itl.decode", 0.005)
    snap = stats.snapshot()
    assert snap["gauges"]["kv_blocks_used"] == 12
    assert snap["decode_tok_per_sec"] > 0
    assert snap["latency"]["ttft.generate"]["p50_ms"] == 250.0
    assert snap["latency"]["itl.decode"]["count"] == 1


def test_stats_endpoint_reports_kv_pool():
    """/stats carries the pool occupancy section when the engine
    serves paged."""
    from veles_tpu.restful import ModelServer
    server = ModelServer(PagedFakeModel(), host="127.0.0.1", port=0,
                         max_batch=2, kv_blocks=9,
                         kv_block_size=8).start()
    try:
        status, _, _ = _post(server.port, "/api/generate",
                             {"tokens": [[1, 2, 3]],
                              "max_new_tokens": 4})
        assert status == 200
        status, stats = _get(server.port, "/stats")
        assert status == 200
        assert stats["kv_pool"]["blocks_total"] == 8
        assert stats["kv_pool"]["block_size"] == 8
        assert "decode_tok_per_sec" in stats
    finally:
        server.stop()
