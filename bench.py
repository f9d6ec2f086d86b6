"""Driver benchmark — prints ONE JSON line
{"metric", "value", "unit", "vs_baseline"}.

Headline: **AlexNet training throughput** (BASELINE.json north star:
"znicz ImageNet AlexNet end-to-end training ≥ single-A100 throughput").
The reference publishes no numbers of its own (BASELINE.md:
``published == {}``), so ``vs_baseline`` is computed against
A100_ALEXNET_IMG_PER_SEC — a public-ballpark single-A100 AlexNet
*training* throughput (~10k images/s; AlexNet is input/bandwidth-bound
on modern accelerators, fp16/bf16, batch 256).  vs_baseline > 1.0
means faster than a single A100.

The dataset is the synthetic uint8 fallback (227×227×3) resident in
HBM — the bench measures the compute path (gather + mean-disp
normalize + convs + FCs + backward + updates, all ONE fused XLA
computation per block of ticks), not JPEG decode.

``python bench.py --mlp`` runs the secondary MNIST784-MLP bench.

``python bench.py --lm`` runs the transformer-LM bench (no reference
counterpart — the reference predates attention): a ~640M-param causal
LM sized to exercise the chip (12 pre-LN blocks, embed 2048, head dim
128, seq 1024, vocab 16384, per-block remat) trained end-to-end
through the same fused block step; reports tokens/s and MFU against
the analytic 6·P + attention FLOP count.  ``--lm-toy`` keeps the
round-4 GPT-small-ish geometry (8 blocks / embed 512 / seq 512) for
cross-round continuity.  ``--attn-stages=fused,bf16,pallas`` (or
``all``/``none``) toggles the attention fast-path stages for the
per-stage A/B attribution protocol (docs/attention.md,
BENCHNOTES r6); the chosen set rides the JSON line.

``python bench.py --serve`` runs the serving load benchmark
(BENCH_r06): an in-process ``ServingEngine`` over a randomly-weighted
LM artifact, driven by ``--serve-streams`` (default 64) concurrent
client threads with mixed prompt lengths and decode budgets for
``--serve-seconds`` per mode.  Reports sustained generated tok/s,
p50/p99 time-to-first-token and inter-token latency, KV block-pool
occupancy, and 429 sheds past pool exhaustion — first through the
paged decode-step continuous-batching path, then the same workload
through whole-request batching (``vs_baseline`` = paged/dense tok/s).

``python bench.py --serve --spec`` runs the speculative-decoding A/B
(BENCH_r10) instead: the same engine over a REPETITIVE-text workload
(small-scale weights — greedy continuations collapse into cycles,
the prompt-lookup-favorable regime), measured three ways — spec off,
n-gram drafting at fixed K, n-gram with per-row adaptive K — and
reports tok/s per mode, accept rate, tokens/step, draft/verify
latencies, and rewound blocks; ``vs_baseline`` is adaptive-spec over
plain paged decode on the same workload.

``python bench.py --serve --replicas N [--fabric-disagg]`` runs the
serving-fabric soak (BENCH_r12): the same load through N paged
replicas behind the prefix-affinity ``ReplicaRouter`` — aggregate
tok/s vs a 1-replica fleet, per-replica occupancy, the
cross-replica prefix hit-rate, and with ``--fabric-disagg`` the
disaggregated-vs-colocated TTFT/ITL p50/p99 A/B (prefill worker
ships KV blocks, decode replicas adopt).

``python bench.py --elastic`` runs the elastic-fleet control-plane
bench (docs/distributed.md "Elastic operations"): a real loopback
socket fleet walks 4→2→4 workers mid-run — two workers drain on a
preemption notice, two late joiners full-ship in — while a trivial
job ledger streams through.  Reports sustained jobs/s across the
walk, late-join latency (dial → first job applied), and the
membership ledger (epochs, joins, drains; zero drops is the pass
condition).  ``--elastic-jobs=N`` sizes the ledger (default 400).

``python bench.py --streamed-jpeg`` decodes REAL JPEG files (a
synthetic directory tree written once) through the streamed loader's
host worker pool — decode + double-buffered upload + fused dispatch
overlap; reports decode throughput and pipeline_efficiency vs the
measured bandwidth/decode ceilings.

``python bench.py --streamed`` runs AlexNet from a NON-resident
dataset: the streamed loader (loader/stream.py) reads a disk-backed
npy memmap, a host worker pool stages each block, and uploads
double-buffer against the fused dispatch.  The JSON line additionally
reports the measured host→device upload bandwidth and the
bandwidth-imposed throughput ceiling (227×227×3 uint8 = 154 KB/image
⇒ ceiling ≈ bandwidth/154KB img/s): where the upload path is slow it,
not the pipeline design, bounds streamed throughput.
``pipeline_efficiency`` = achieved/ceiling is the design's figure of
merit: ≥0.9 means decode+upload+dispatch fully overlap.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

A100_ALEXNET_IMG_PER_SEC = 10000.0
A100_MLP_IMG_PER_SEC = 1.5e6

#: Every flag bench.py recognizes (argv is parsed ad-hoc, not via
#: argparse) — the docs-consistency test cross-checks documentation
#: references against this, so a flag mentioned in docs/*.md must
#: exist here or in a real parser.
BENCH_FLAGS = ("--mlp", "--lm", "--lm-toy", "--serve", "--streamed",
               "--streamed-jpeg", "--attn-stages", "--attn-ladder",
               "--serve-streams", "--serve-seconds", "--spec",
               "--trace-out", "--optimizer", "--pp-schedule",
               "--population", "--population-members",
               "--population-epochs",
               "--population-ticks", "--elastic", "--elastic-jobs",
               "--replicas", "--fabric-disagg", "--kv-dtype",
               "--net-dtype")

# Tuned on v5e (round 2): batch 512 × 32-tick blocks; larger batches
# or blocks gain <3% more.  The perf levers that got here: banded-
# matmul LRN (~2× over shifted adds), bf16 activation stream, and
# unpadded partial blocks (validation used to burn a full block).
ALEXNET_BATCH = 512
ALEXNET_TICKS_PER_DISPATCH = 32
ALEXNET_N_TRAIN = 16384
ALEXNET_N_VALID = 512

#: Analytic AlexNet training cost for the network THIS bench runs —
#: the UNGROUPED variant (no 2-way filter groups; grouping was a
#: 2-GPU memory workaround, not a capability).  Forward MACs at
#: 227px/1000 classes:
#:   conv1 55·55·96·11·11·3   = 105.4 M
#:   conv2 27·27·256·5·5·96   = 447.9 M   (grouped would be half)
#:   conv3 13·13·384·3·3·256  = 149.5 M
#:   conv4 13·13·384·3·3·384  = 224.3 M   (grouped would be half)
#:   conv5 13·13·256·3·3·384  = 149.5 M   (grouped would be half)
#:   fc6 9216·4096 + fc7 4096·4096 + fc8 4096·1000 = 58.6 M
#:   total ≈ 1.135 GMAC fwd → ×2 FLOP/MAC ×3 (fwd+dgrad+wgrad)
#: ≈ 6.81 GF/img trained.  (Round 3 reported MFU with the GROUPED
#: constant 4.33 — a 1.57× undercount for this net; see
#: BENCHNOTES.md.)  Used only for TFLOP/s / MFU diagnostics.
ALEXNET_TRAIN_GFLOP_PER_IMG = 6.81


def device_peak_tflops():
    """The MFU denominator: the peak of the device this process runs
    on, from the ``device_kind`` table kept with the live gauges
    (observability.attribution.DEVICE_PEAK_TFLOPS).  A device the
    table does not know is an ERROR in the modes that report a
    utilization — dividing by some other chip's peak would print a
    number that means nothing."""
    from veles_tpu.backends import device_entry
    from veles_tpu.observability import attribution
    peak = attribution.device_peak_tflops()
    if peak is None:
        raise SystemExit(
            "bench: no peak FLOP/s known for device %r — this mode "
            "reports a utilization and runs only on a device listed "
            "in observability.attribution.DEVICE_PEAK_TFLOPS" %
            (device_entry(),))
    return peak

# LM bench geometry — sized to EXERCISE the v5e, not to demo the
# code path (round 4 ran a toy E=512/B=16 net whose 26% MFU was
# bounded by the tiny contraction dims; VERDICT r4 item 3).  ~640M
# params (E=2048, 12 pre-LN blocks, head dim 128 — the MXU-native
# tile width, measured ~15% faster than D=80 —, hidden 4·E, seq
# 1024, vocab 16384), trained with per-block remat
# (root.common.engine.remat): without remat the stored attention
# probabilities alone (L·B·S²·H f32) would exceed HBM.  B=8 measured
# FASTER than B=16 (37.7% vs 14.7% MFU — the bigger batch pushes the
# attention transients into HBM pressure).  ``--lm-toy`` keeps the
# round-4 geometry for continuity.  Tuning table: BENCHNOTES.md
# "A serious LM bench geometry".
LM_VOCAB = 16384
LM_SEQ = 1024
LM_EMBED = 2048
LM_HEADS = 16
LM_BLOCKS = 12
LM_BATCH = 8
LM_TICKS_PER_DISPATCH = 8
LM_N_TRAIN = 512
LM_N_VALID = 64

LM_TOY_VOCAB = 8192
LM_TOY_SEQ = 512
LM_TOY_EMBED = 512
LM_TOY_HEADS = 8
LM_TOY_BLOCKS = 8
LM_TOY_BATCH = 16
LM_TOY_N_TRAIN = 2048
LM_TOY_N_VALID = 128


def lm_train_flop_per_token(embed, blocks, seq, vocab):
    """Analytic train cost per token: 6 FLOP/param over the
    12·E²-per-block weights (fwd+bwd+update matmuls) + the tied
    embedding/head projection, plus the attention score/value
    matmuls 12·S·E per layer."""
    return (6.0 * (12 * embed * embed * blocks + vocab * embed) +
            12.0 * seq * embed * blocks)

MLP_BATCH = 100
MLP_TICKS_PER_DISPATCH = 120
MLP_N_TRAIN = 60000
MLP_N_VALID = 10000

# Streamed mode: small enough that an epoch's upload (~355 MB) takes
# seconds even on a slow host link, big enough to amortize warmup.
STREAM_BATCH = 256
STREAM_TICKS_PER_DISPATCH = 8
STREAM_N_TRAIN = 2048
STREAM_N_VALID = 256
STREAM_BYTES_PER_IMG = 227 * 227 * 3  # uint8

# Streamed-JPEG mode: REAL image files decoded by the host worker
# pool (PIL) inside the streamed double-buffer — the reference
# pipeline's daily reality (veles/loader/fullbatch_image.py:56).
# The staged samples are float32 (the host normalizer's output), so
# the upload ceiling is 4× lower than the uint8 streamed mode; the
# figure of merit is still pipeline_efficiency vs the measured
# ceilings (bandwidth AND decode).
JPEG_SIZE = 227
JPEG_CLASSES = 8
JPEG_TRAIN_PER_CLASS = 96
JPEG_VALID_PER_CLASS = 16
JPEG_BATCH = 64
JPEG_TICKS_PER_DISPATCH = 4
JPEG_BYTES_PER_IMG = JPEG_SIZE * JPEG_SIZE * 3 * 4  # float32


# Serving bench geometry: a compact but real causal LM (random
# weights — the bench measures the SERVING substrate: paged
# gather/scatter decode, continuous batching, admission — not model
# quality), sized so prefill+decode exercise real attention math
# while the bucket grid stays small enough to warm up quickly.
SERVE_VOCAB = 512
SERVE_EMBED = 128
SERVE_HEADS = 4
SERVE_POS = 1024
SERVE_HIDDEN = 256
SERVE_BLOCKS = 4
SERVE_STREAMS = 64
SERVE_SECONDS = 15.0
SERVE_MAX_BATCH = 32
SERVE_KV_BLOCK = 16
SERVE_PROMPT_CHOICES = (8, 24, 48, 96, 160)
SERVE_NEW_CHOICES = (8, 16, 24, 40, 64)
#: Fraction of streams that open with a common "system prompt" so
#: the prefix cache has something to share.
SERVE_SHARED_PREFIX = 32

#: ``--serve --spec`` A/B: the REPETITIVE-text workload — near-zero
#: attention/positional weights make the next token a deterministic
#: function of the current one, so greedy continuations cycle (the
#: deterministic-continuation limit of extractive/copy/summary
#: traffic, the prompt-lookup-favorable regime) — with long decode
#: budgets so drafting has a stream to ride.
SERVE_SPEC_ATTN_SCALE = 0.002
SERVE_SPEC_K = 8
SERVE_SPEC_PROMPT_CHOICES = (8, 16, 24)
SERVE_SPEC_NEW_CHOICES = (48, 64, 96)


def build_serve_artifact(path, scale=0.5, attn_scale=1.0):
    """Writes a randomly-weighted causal-LM artifact (embedding →
    blocks → lm_head) without training — serving economics do not
    depend on the weights.  ``attn_scale`` < 1 shapes the TEXT the
    model emits: shrinking the attention/positional weights makes
    the next token a (near-)deterministic function of the current
    one, so greedy continuations fall into cycles — guaranteed
    REPETITIVE text, the n-gram-drafter-favorable regime the --spec
    A/B measures (the deterministic-continuation limit of
    extractive/copy/summary traffic).  The attention math still
    runs at full cost either way."""
    import io
    import tarfile
    import numpy
    from veles_tpu.json_encoders import dumps_json
    rng = numpy.random.RandomState(1234)
    attn_names = ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo")

    def g(*shape, extra=1.0):
        return (rng.standard_normal(shape) * scale * extra).astype(
            numpy.float32)

    weights = {"emb__weights": g(SERVE_VOCAB, SERVE_EMBED),
               "emb__pos": g(SERVE_POS, SERVE_EMBED,
                             extra=attn_scale)}
    units = [{"name": "emb", "type": "embedding",
              "config": {"vocab_size": SERVE_VOCAB,
                         "embed_dim": SERVE_EMBED},
              "params": {"weights": "emb__weights",
                         "pos": "emb__pos"}}]
    E, H = SERVE_EMBED, SERVE_HIDDEN
    for b in range(SERVE_BLOCKS):
        name = "blk%d" % b
        params = {}
        for pname, shape in [
                ("ln1_g", (E,)), ("ln1_b", (E,)),
                ("wq", (E, E)), ("bq", (E,)), ("wk", (E, E)),
                ("bk", (E,)), ("wv", (E, E)), ("bv", (E,)),
                ("wo", (E, E)), ("bo", (E,)),
                ("ln2_g", (E,)), ("ln2_b", (E,)),
                ("w1", (E, H)), ("b1", (H,)),
                ("w2", (H, E)), ("b2", (E,))]:
            key = "%s__%s" % (name, pname)
            weights[key] = numpy.ones(shape, numpy.float32) \
                if pname.endswith("_g") else \
                g(*shape, extra=attn_scale
                  if pname in attn_names else 1.0)
            params[pname] = key
        units.append({"name": name, "type": "transformer_block",
                      "config": {"n_heads": SERVE_HEADS,
                                 "causal": 1},
                      "params": params})
    weights["head__weights"] = g(SERVE_EMBED, SERVE_VOCAB)
    units.append({"name": "head", "type": "lm_head",
                  "config": {"output_sample_shape": [SERVE_VOCAB]},
                  "params": {"weights": "head__weights"}})
    manifest = {"format": "veles-tpu-model", "version": 1,
                "workflow": "ServeBench", "checksum": "bench",
                "created": "1970-01-01T00:00:00Z",
                "input": {"sample_shape": [8], "dtype": "int32"},
                "output": {"sample_shape": [SERVE_VOCAB]},
                "units": units}
    npz = io.BytesIO()
    numpy.savez(npz, **weights)
    blobs = {"manifest.json": dumps_json(manifest).encode(),
             "weights.npz": npz.getvalue()}
    with tarfile.open(path, "w:gz") as tar:
        for name, blob in blobs.items():
            info = tarfile.TarInfo(name)
            info.size = len(blob)
            tar.addfile(info, io.BytesIO(blob))
    return path


def run_serve_load(engine, streams, seconds, seed=0,
                   prompt_choices=SERVE_PROMPT_CHOICES,
                   new_choices=SERVE_NEW_CHOICES):
    """Drives ``streams`` concurrent client threads against the
    engine in-process for ``seconds``; returns aggregate client-side
    numbers (the engine's ServingStats carries the server-side
    TTFT/ITL/pool views)."""
    import threading
    import numpy
    from veles_tpu.serving import AdmissionError
    stop_at = time.monotonic() + seconds
    lock = threading.Lock()
    totals = {"tokens": 0, "requests": 0, "shed": 0, "timeouts": 0,
              "errors": 0, "pool_peak": 0}
    error_samples = []
    shared_prefix = numpy.random.RandomState(99).randint(
        0, SERVE_VOCAB, 64).astype(numpy.int32)

    def stream(idx):
        rng = numpy.random.RandomState(seed * 1000 + idx)
        while time.monotonic() < stop_at:
            s = int(rng.choice(prompt_choices))
            m = int(rng.choice(new_choices))
            prompt = rng.randint(0, SERVE_VOCAB, (1, s)) \
                .astype(numpy.int32)
            if idx < SERVE_SHARED_PREFIX and s >= 48:
                # A common system prompt: the prefix-cache's food.
                prompt[0, :32] = shared_prefix[:32]
            try:
                out = engine.submit_generate(prompt, m,
                                             seed=idx)
                with lock:
                    totals["tokens"] += int(out.shape[1] - s)
                    totals["requests"] += 1
            except AdmissionError as e:
                # Only genuine 429 backpressure counts as a shed —
                # deadline cancellations (504) and engine shutdown
                # (503) are failures, not graceful load management.
                key = "shed" if e.status == 429 else "timeouts"
                with lock:
                    totals[key] += 1
                time.sleep(0.05)
            except Exception as e:
                # Counted AND sampled: an all-errors soak must name
                # its failure mode in the report, not just count it.
                with lock:
                    totals["errors"] += 1
                    if len(error_samples) < 3:
                        error_samples.append(repr(e))

    def sample_pool():
        # ONE sampler thread, so the occupancy readout does not
        # contend with the device thread's pool lock once per
        # completed request across every stream.
        pool = engine.kv_pool
        while pool is not None and time.monotonic() < stop_at:
            used = pool.occupancy()["blocks_used"]
            with lock:
                if used > totals["pool_peak"]:
                    totals["pool_peak"] = used
            time.sleep(0.05)

    threads = [threading.Thread(target=stream, args=(i,),
                                daemon=True)
               for i in range(streams)]
    sampler = threading.Thread(target=sample_pool, daemon=True)
    t0 = time.monotonic()
    sampler.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    totals["wall"] = time.monotonic() - t0
    sampler.join(timeout=1.0)
    if error_samples:
        print("serve-load errors (%d): %s" %
              (totals["errors"], "; ".join(error_samples)))
    return totals


def serve_bench(argv):
    import tempfile
    from veles_tpu.export import ExportedModel
    from veles_tpu.serving import ServingEngine
    streams = SERVE_STREAMS
    seconds = SERVE_SECONDS
    spec_ab = "--spec" in argv
    replicas = 1
    disagg = "--fabric-disagg" in argv
    kv_dtype = None
    for i, arg in enumerate(argv):
        if arg.startswith("--serve-streams="):
            streams = int(arg.split("=", 1)[1])
        elif arg.startswith("--serve-seconds="):
            seconds = float(arg.split("=", 1)[1])
        elif arg.startswith("--replicas="):
            replicas = int(arg.split("=", 1)[1])
        elif arg == "--replicas" and i + 1 < len(argv):
            replicas = int(argv[i + 1])
        elif arg.startswith("--kv-dtype="):
            kv_dtype = arg.split("=", 1)[1]
        elif arg == "--kv-dtype" and i + 1 < len(argv):
            kv_dtype = argv[i + 1]
    if replicas > 1 or disagg:
        return serve_fabric_bench(streams, seconds, replicas,
                                  disagg)
    path = os.path.join(tempfile.gettempdir(),
                        "veles_serve_bench.veles.tgz")
    build_serve_artifact(
        path, scale=0.5,
        attn_scale=SERVE_SPEC_ATTN_SCALE if spec_ab else 1.0)

    prompts = SERVE_SPEC_PROMPT_CHOICES if spec_ab else \
        SERVE_PROMPT_CHOICES
    news = SERVE_SPEC_NEW_CHOICES if spec_ab else SERVE_NEW_CHOICES

    def one_mode(paged, kv_blocks=None, spec=False,
                 spec_adaptive=True, kv_dtype=None):
        from veles_tpu.serving import BucketPolicy
        model = ExportedModel(path, compile_capacity=256)
        engine = ServingEngine(
            model, max_batch=SERVE_MAX_BATCH, queue_depth=streams,
            default_deadline=max(30.0, seconds),
            # batch_floor trims the warmup grid: under sustained
            # ≥64-stream load, device batches below 8 rows are a
            # transient, not a regime worth its own executable.
            policy=BucketPolicy(max_batch=SERVE_MAX_BATCH,
                                batch_floor=8,
                                prompt_cap=SERVE_POS),
            paged=paged, kv_blocks=kv_blocks,
            kv_block_size=SERVE_KV_BLOCK, kv_dtype=kv_dtype,
            spec=spec, spec_max_k=SERVE_SPEC_K,
            spec_adaptive=spec_adaptive)
        engine.start()
        try:
            engine.warmup(longest_prompt=max(prompts),
                          max_new=max(news))
            totals = run_serve_load(engine, streams, seconds,
                                    prompt_choices=prompts,
                                    new_choices=news)
            snap = engine.stats.snapshot()
            pool = engine.kv_pool
            occ = pool.occupancy() if pool is not None else {}
        finally:
            engine.stop()
        return totals, snap, occ

    if spec_ab:
        return serve_spec_ab(one_mode, streams, seconds)
    if kv_dtype and kv_dtype != "f32":
        return serve_kv_quant_ab(one_mode, streams, seconds,
                                 kv_dtype, path)

    # The paged pool is deliberately sized BELOW the worst case
    # (max_batch full-length rows) so the soak drives it past
    # exhaustion and exercises graceful 429 shedding.
    per_row = -(-(max(SERVE_PROMPT_CHOICES) +
                  max(SERVE_NEW_CHOICES)) // SERVE_KV_BLOCK)
    kv_blocks = SERVE_MAX_BATCH * per_row * 3 // 4 + 1
    paged_totals, paged_snap, occ = one_mode(True, kv_blocks)
    dense_totals, _, _ = one_mode(False)
    paged_tps = paged_totals["tokens"] / paged_totals["wall"]
    dense_tps = dense_totals["tokens"] / \
        max(dense_totals["wall"], 1e-9)

    def pct(key, p):
        lat = paged_snap["latency"].get(key) or {}
        return lat.get("p%d_ms" % p)

    print(json.dumps({
        "metric": "serve_paged_decode_tok_per_sec",
        "value": round(paged_tps, 1),
        "unit": "tokens/sec",
        # vs_baseline here is paged vs whole-request batching on the
        # SAME workload — >1.0 means decode-step continuous batching
        # sustains more aggregate throughput.
        "vs_baseline": round(paged_tps / max(dense_tps, 1e-9), 4),
        "vs_baseline_meaning": "paged_vs_whole_request_tok_per_sec",
        "streams": streams,
        "seconds": seconds,
        "requests": paged_totals["requests"],
        "shed_429": paged_totals["shed"],
        "timeouts": paged_totals["timeouts"],
        "errors": paged_totals["errors"],
        "ttft_p50_ms": pct("ttft.generate", 50),
        "ttft_p99_ms": pct("ttft.generate", 99),
        "itl_p50_ms": pct("itl.decode", 50),
        "itl_p99_ms": pct("itl.decode", 99),
        "kv_blocks": kv_blocks,
        "kv_pool_peak_blocks": paged_totals["pool_peak"],
        "kv_prefix_hits": occ.get("prefix_hits"),
        "kv_cow_copies": occ.get("cow_copies"),
        "dense_tok_per_sec": round(dense_tps, 1),
    }))


def serve_kv_quant_ab(one_mode, streams, seconds, kv_dtype, path):
    """``--serve --kv-dtype={bf16,int8,fp8}`` (BENCH_r13): the
    quantized-KV capacity A/B.  Both sides get the SAME HBM byte
    budget — the headline soak's deliberately undersized f32 pool,
    in bytes — converted to each storage dtype's block count, then
    run the same mixed-geometry soak.  The figure of merit is
    capacity: streams held before the first PoolExhausted shed.
    Admission reserves each stream's worst-case blocks at the door,
    so capacity is exactly usable-blocks // worst-case-rows — int8
    fits ~4x the blocks (minus the per-(block, head) f32 scale
    sidecar) in the budget, and the soak's shed counts show the
    extra headroom live.  Token-level quality is NOT this bench's
    claim: the greedy-parity and perplexity gates live in tier-1
    (tests/test_quant.py)."""
    from veles_tpu.export import ExportedModel, check_kv_dtype
    kv_dtype = check_kv_dtype(kv_dtype)
    model = ExportedModel(path)
    per_row = -(-(max(SERVE_PROMPT_CHOICES) +
                  max(SERVE_NEW_CHOICES)) // SERVE_KV_BLOCK)
    block_bytes = {
        dt: model.make_kv_pool(2, SERVE_KV_BLOCK,
                               kv_dtype=dt).block_bytes
        for dt in ("f32", kv_dtype)}
    budget = (SERVE_MAX_BATCH * per_row * 3 // 4 + 1) * \
        block_bytes["f32"]
    sides = {}
    for dt in ("f32", kv_dtype):
        n = max(int(budget // block_bytes[dt]), per_row + 2)
        totals, _snap, occ = one_mode(True, n, kv_dtype=dt)
        offered = totals["requests"] + totals["shed"]
        sides[dt] = {
            "kv_blocks": n,
            "block_bytes": block_bytes[dt],
            "pool_bytes": occ.get("bytes_total"),
            "capacity_streams": (n - 1) // per_row,
            "tok_per_sec": round(
                totals["tokens"] / max(totals["wall"], 1e-9), 1),
            "requests": totals["requests"],
            "shed_429": totals["shed"],
            "shed_rate": round(
                totals["shed"] / max(offered, 1), 4),
            "pool_peak_blocks": totals["pool_peak"],
        }
    print(json.dumps({
        "metric": "serve_kv_quant_capacity_streams",
        "value": sides[kv_dtype]["capacity_streams"],
        "unit": "streams",
        "vs_baseline": round(
            sides[kv_dtype]["capacity_streams"] /
            max(sides["f32"]["capacity_streams"], 1), 4),
        "vs_baseline_meaning":
            "streams_before_first_shed_vs_f32_at_fixed_byte_budget",
        "kv_dtype": kv_dtype,
        "streams": streams,
        "seconds": seconds,
        "budget_bytes": budget,
        "worst_case_blocks_per_stream": per_row,
        "f32": sides["f32"],
        kv_dtype: sides[kv_dtype],
    }))


def serve_fabric_bench(streams, seconds, replicas, disagg):
    """``--serve --replicas N [--fabric-disagg]`` (BENCH_r12): the
    serving-fabric soak — N paged engine replicas behind the
    prefix-affinity ``ReplicaRouter``, the same mixed-geometry
    ≥64-stream load as the plain serve soak.  Reports aggregate
    tok/s and its ratio to a 1-replica fleet (near-linear on real
    accelerators; CPU loopback shares one host, see BENCHNOTES),
    per-replica occupancy, the cross-replica prefix hit-rate the
    affinity routing exists to protect, and — with
    ``--fabric-disagg`` — the disaggregated-vs-colocated TTFT/ITL
    A/B (prefill worker fills KV blocks, decode replicas adopt them
    over the wire, so decode-side TTFT shrinks)."""
    import tempfile
    import numpy
    from veles_tpu.export import ExportedModel
    from veles_tpu.serving import (BucketPolicy, PrefillWorker,
                                   ReplicaRouter, ServingEngine)
    path = os.path.join(tempfile.gettempdir(),
                        "veles_serve_bench.veles.tgz")
    build_serve_artifact(path, scale=0.5)

    # Sized to FIT (unlike the single-engine soak, which starves the
    # pool on purpose): the fabric soak measures routing and
    # adoption, and a shed request routes nowhere.
    per_row = -(-(max(SERVE_PROMPT_CHOICES) +
                  max(SERVE_NEW_CHOICES)) // SERVE_KV_BLOCK)
    kv_blocks = SERVE_MAX_BATCH * per_row + 1

    def build_engine(model):
        return ServingEngine(
            model, max_batch=SERVE_MAX_BATCH, queue_depth=streams,
            default_deadline=max(30.0, seconds),
            policy=BucketPolicy(max_batch=SERVE_MAX_BATCH,
                                batch_floor=8,
                                prompt_cap=SERVE_POS),
            paged=True, kv_blocks=kv_blocks,
            kv_block_size=SERVE_KV_BLOCK).start()

    class FabricClient(object):
        """run_serve_load's engine surface over the router (the
        pool sampler reads ``kv_pool`` — per-replica pools are in
        ``router.occupancy()`` instead)."""
        kv_pool = None

        def __init__(self, router):
            self._router = router

        def submit_generate(self, tokens, max_new, seed=0):
            return self._router.submit_generate(tokens, max_new,
                                                seed=seed)

    def merged_pct(engines, key, p):
        # Raw samples pooled ACROSS replicas, then one percentile —
        # percentiles of per-replica percentiles are not percentiles.
        samples = []
        for e in engines:
            samples.extend(e.stats.latency_samples(key))
        if not samples:
            return None
        return round(
            float(numpy.percentile(samples, p)) * 1000.0, 3)

    def one_fleet(n, with_disagg):
        model = ExportedModel(path, compile_capacity=256)
        engines = [build_engine(model) for _ in range(n)]
        prefill = PrefillWorker(build_engine(model)) \
            if with_disagg else None
        router = ReplicaRouter(prefill=prefill)
        for i, engine in enumerate(engines):
            router.add_replica("r%d" % i, engine)
        # Replicas share the model object, hence ONE compile cache:
        # warming the first engine warms the fleet.
        engines[0].warmup(
            longest_prompt=max(SERVE_PROMPT_CHOICES),
            max_new=max(SERVE_NEW_CHOICES))
        try:
            totals = run_serve_load(FabricClient(router), streams,
                                    seconds)
            occ = router.occupancy()
            lat = {"ttft_p50_ms": merged_pct(engines,
                                             "ttft.generate", 50),
                   "ttft_p99_ms": merged_pct(engines,
                                             "ttft.generate", 99),
                   "itl_p50_ms": merged_pct(engines,
                                            "itl.decode", 50),
                   "itl_p99_ms": merged_pct(engines,
                                            "itl.decode", 99)}
        finally:
            router.stop(drain=False)
        return totals, occ, lat

    single_totals, _, _ = one_fleet(1, False)
    single_tps = single_totals["tokens"] / \
        max(single_totals["wall"], 1e-9)
    fleet_totals, fleet_occ, fleet_lat = one_fleet(replicas, False)
    fleet_tps = fleet_totals["tokens"] / \
        max(fleet_totals["wall"], 1e-9)
    out = {
        "metric": "serve_fabric_tok_per_sec",
        "value": round(fleet_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(fleet_tps / max(single_tps, 1e-9), 4),
        "vs_baseline_meaning":
            "fabric_%d_replicas_vs_single_replica_tok_per_sec"
            % replicas,
        "replicas": replicas,
        "streams": streams,
        "seconds": seconds,
        "requests": fleet_totals["requests"],
        "shed_429": fleet_totals["shed"],
        "timeouts": fleet_totals["timeouts"],
        "errors": fleet_totals["errors"],
        "routed": fleet_occ["routed"],
        "reroutes": fleet_occ["reroutes"],
        "prefix_hit_rate": fleet_occ.get("prefix_hit_rate"),
        "per_replica": fleet_occ["per_replica"],
        "single_tok_per_sec": round(single_tps, 1),
    }
    out.update(fleet_lat)
    if disagg:
        d_totals, d_occ, d_lat = one_fleet(replicas, True)
        d_tps = d_totals["tokens"] / max(d_totals["wall"], 1e-9)
        speedup = None
        if d_lat["ttft_p99_ms"] and fleet_lat["ttft_p99_ms"]:
            speedup = round(fleet_lat["ttft_p99_ms"] /
                            d_lat["ttft_p99_ms"], 4)
        out["disagg"] = {
            "tok_per_sec": round(d_tps, 1),
            "adopted_blocks": d_occ["adopted_blocks"],
            "prefix_hit_rate": d_occ.get("prefix_hit_rate"),
            "ttft_p50_ms": d_lat["ttft_p50_ms"],
            "ttft_p99_ms": d_lat["ttft_p99_ms"],
            "itl_p50_ms": d_lat["itl_p50_ms"],
            "itl_p99_ms": d_lat["itl_p99_ms"],
            "colocated_ttft_p99_ms": fleet_lat["ttft_p99_ms"],
            "ttft_p99_speedup": speedup,
        }
    print(json.dumps(out))


def serve_spec_ab(one_mode, streams, seconds):
    """``--serve --spec``: the speculative-decoding A/B on a
    repetitive-text workload (BENCH_r10) — spec off / n-gram at
    fixed K / n-gram with adaptive K, same artifact, same mixed
    geometry, pool sized to the worst case so the ratio measures
    DECODE, not shedding."""
    per_row = -(-(max(SERVE_SPEC_PROMPT_CHOICES) +
                  max(SERVE_SPEC_NEW_CHOICES)) // SERVE_KV_BLOCK)
    # Worst-case reservations for every concurrent STREAM (queued
    # requests hold commits too): the A/B measures decode, never
    # shedding.
    kv_blocks = streams * per_row + 1 + 16
    off_t, off_s, _ = one_mode(True, kv_blocks)
    fix_t, fix_s, _ = one_mode(True, kv_blocks, spec=True,
                               spec_adaptive=False)
    ada_t, ada_s, occ = one_mode(True, kv_blocks, spec=True,
                                 spec_adaptive=True)
    off_tps = off_t["tokens"] / max(off_t["wall"], 1e-9)
    fix_tps = fix_t["tokens"] / max(fix_t["wall"], 1e-9)
    ada_tps = ada_t["tokens"] / max(ada_t["wall"], 1e-9)

    def pct(snap, key, p):
        lat = snap["latency"].get(key) or {}
        return lat.get("p%d_ms" % p)

    def gauges(snap):
        g = snap.get("gauges", {})
        return {"accept_rate": g.get("spec.accept_rate"),
                "tokens_per_step": g.get("spec.tokens_per_step"),
                "mean_accepted_len": g.get("spec.mean_accepted_len"),
                "draft_ms": g.get("spec.draft_ms"),
                "verify_ms": g.get("spec.verify_ms")}

    print(json.dumps({
        "metric": "serve_spec_decode_tok_per_sec",
        "value": round(ada_tps, 1),
        "unit": "tokens/sec",
        # vs_baseline = adaptive-K speculative vs plain paged decode
        # on the SAME repetitive workload — the acceptance gate is
        # strictly > 1.0.
        "vs_baseline": round(ada_tps / max(off_tps, 1e-9), 4),
        "vs_baseline_meaning": "spec_adaptive_vs_plain_tok_per_sec",
        "streams": streams,
        "seconds": seconds,
        "spec_max_k": SERVE_SPEC_K,
        "attn_scale": SERVE_SPEC_ATTN_SCALE,
        "plain_tok_per_sec": round(off_tps, 1),
        "ngram_fixed_tok_per_sec": round(fix_tps, 1),
        "ngram_adaptive_tok_per_sec": round(ada_tps, 1),
        "ngram_fixed_vs_plain": round(
            fix_tps / max(off_tps, 1e-9), 4),
        "spec_fixed": gauges(fix_s),
        "spec_adaptive": gauges(ada_s),
        "itl_p50_ms_plain": pct(off_s, "itl.decode", 50),
        "itl_p50_ms_spec": pct(ada_s, "itl.decode", 50),
        "itl_p99_ms_plain": pct(off_s, "itl.decode", 99),
        "itl_p99_ms_spec": pct(ada_s, "itl.decode", 99),
        "requests": {"plain": off_t["requests"],
                     "fixed": fix_t["requests"],
                     "adaptive": ada_t["requests"]},
        "errors": off_t["errors"] + fix_t["errors"] +
        ada_t["errors"],
        "kv_blocks": kv_blocks,
        "kv_pool_peak_blocks": ada_t["pool_peak"],
        "spec_rewound_blocks":
            ada_s["counters"].get("spec.rewound_blocks", 0),
        "kv_prefix_hits": occ.get("prefix_hits"),
    }))


def build_alexnet():
    import veles_tpu.prng as prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.imagenet import AlexNetWorkflow
    prng.reset()
    prng.get(0).seed(42)
    launcher = Launcher()
    wf = AlexNetWorkflow(
        launcher, minibatch_size=ALEXNET_BATCH,
        ticks_per_dispatch=ALEXNET_TICKS_PER_DISPATCH, max_epochs=1000,
        loader_config={"sim_train": ALEXNET_N_TRAIN,
                       "sim_valid": ALEXNET_N_VALID,
                       "sim_image_size": 227, "sim_classes": 1000,
                       # Synthetic labels can't cover 1000 classes;
                       # the analysis warning is dataset QA noise in
                       # a perf record (VERDICT r4 weak item 7).
                       "validate_labels": False})
    launcher.initialize()
    return launcher, wf


def build_mlp():
    import numpy
    import veles_tpu.prng as prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.znicz.samples.mnist import MnistWorkflow

    class SyntheticMnist(FullBatchLoader):
        def load_data(self):
            rng = numpy.random.RandomState(0)
            n = MLP_N_TRAIN + MLP_N_VALID
            self.original_data.mem = rng.rand(
                n, 784).astype(numpy.float32)
            self.original_labels.mem = rng.randint(
                0, 10, size=n).astype(numpy.int32)
            self.class_lengths = [0, MLP_N_VALID, MLP_N_TRAIN]

    prng.reset()
    prng.get(0).seed(42)
    launcher = Launcher()
    wf = MnistWorkflow(launcher, layers=(100, 10),
                       minibatch_size=MLP_BATCH,
                       ticks_per_dispatch=MLP_TICKS_PER_DISPATCH,
                       max_epochs=1000, loader_cls=SyntheticMnist)
    launcher.initialize()
    return launcher, wf


#: The attention fast-path stages ``--attn-stages`` can toggle
#: (docs/attention.md; each maps to one root.common.engine knob).
#: "ring" engages the ring-flash body inside sequence-parallel
#: attention (multi-chip runs), "decode" the serving flash-decode
#: kernel (meaningful under ``--serve``); both ride the JSON line
#: either way so the record says what was measured.
ATTN_STAGES = ("fused", "bf16", "pallas", "ring", "decode")


def parse_attn_stages(argv):
    """``--attn-stages=fused,bf16,pallas,ring,decode`` → the stage
    set for the LM bench A/B protocol (BENCHNOTES r6/r9): "none" (or
    absent) is the r5 baseline — every knob explicitly OFF, which
    matters now that auto-kernel defaults are on — and "all" turns
    every stage on."""
    stages = None
    for arg in argv:
        if arg.startswith("--attn-stages="):
            stages = arg.split("=", 1)[1]
    if stages is None or stages == "none":
        return ()
    if stages == "all":
        return ATTN_STAGES
    out = []
    for s in stages.split(","):
        s = s.strip()
        if not s:
            continue
        if s not in ATTN_STAGES:
            raise SystemExit(
                "unknown attention stage %r — valid: %s, 'all', "
                "'none'" % (s, ", ".join(ATTN_STAGES)))
        out.append(s)
    return tuple(out)


def apply_attn_stages(stages):
    """Sets the engine knobs for the chosen stages (the same knobs
    the --attn-*/--sp-* CLI flags set for a real run; the fused_qkv
    knob is read at unit CONSTRUCTION, so this must run before
    build_lm).  Every knob is set BOTH ways: with auto-dispatch the
    default since the r9 flip, the "none" baseline must force the
    kernels off, not merely not-ask for them."""
    from veles_tpu.config import root
    root.common.engine.fused_qkv = "fused" in stages
    root.common.engine.attention_dtype = \
        "bf16" if "bf16" in stages else "f32"
    root.common.engine.attention_kernel = \
        "auto" if "pallas" in stages else "xla"
    root.common.engine.sp_ring_kernel = \
        "auto" if "ring" in stages else "xla"
    root.common.engine.decode_kernel = \
        "auto" if "decode" in stages else "off"


def build_lm(vocab=LM_VOCAB, seq=LM_SEQ, embed=LM_EMBED,
             heads=LM_HEADS, blocks=LM_BLOCKS, batch=LM_BATCH,
             n_train=LM_N_TRAIN, n_valid=LM_N_VALID, remat=True):
    import numpy
    import veles_tpu.prng as prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import (FirstTokenLoader,
                                                TinyLMWorkflow)

    class SyntheticCorpus(FirstTokenLoader):
        def load_data(self):
            rng = numpy.random.RandomState(0)
            n = n_train + n_valid
            self.original_data.mem = rng.randint(
                0, vocab, (n, seq)).astype(numpy.int32)
            self.original_labels.mem = numpy.roll(
                self.original_data.mem, -1, axis=1)
            self.class_lengths = [0, n_valid, n_train]

    root.common.engine.remat = remat
    prng.reset()
    prng.get(0).seed(42)
    launcher = Launcher()
    wf = TinyLMWorkflow(
        launcher, vocab_size=vocab, seq_len=seq,
        embed_dim=embed, n_heads=heads, n_blocks=blocks,
        minibatch_size=batch,
        ticks_per_dispatch=LM_TICKS_PER_DISPATCH,
        max_epochs=1000, loader_cls=SyntheticCorpus,
        # Random tokens need not cover the vocab (small corpora
        # would trip the unseen-validation-label check).
        loader_config={"validate_labels": False})
    launcher.initialize()
    return launcher, wf


#: --attn-ladder geometry: a compact LM (D = 64 so the CPU box can
#: afford the full per-stage rebuild × measure matrix) plus the
#: long-S dense-vs-ring-flash attention ladder.  Chip-scale numbers
#: ride the --lm protocol when hardware is attached; this mode's job
#: is the per-stage ORDERING and the scaling SHAPE.
LADDER_VOCAB = 256
LADDER_SEQ = 256
LADDER_EMBED = 128
LADDER_HEADS = 2
LADDER_BLOCKS = 2
LADDER_BATCH = 8
LADDER_N_TRAIN = 64
LADDER_N_VALID = 16
#: Long-S ladder: weak-scaling shard size (per-device S under dp×sp
#: stays fixed while devices grow with S — the regime the ring
#: exists for), and the sequence points.
LADDER_SHARD = 512
LADDER_SEQS = (512, 1024, 2048, 4096)


def attn_ladder_bench(argv):
    """``--attn-ladder`` (BENCH_r09): two ladders in one JSON line.

    1. The ``--attn-stages`` A/B at a compact LM geometry: for each
       stage set the workflow is REBUILT under the stage knobs (the
       fused layout freezes at construction) and the fused-step
       training wall is measured — same protocol as
       ``--lm --attn-stages=...``, sized so a CPU box can run the
       whole matrix.  On a box without the TPU toolchain the pallas
       stage degrades to its fallback by design (the dispatch
       contract) — the row records it honestly.

    2. The long-S ladder: dense single-device attention fwd+bwd wall
       at each S, against the dp×sp ring-flash PER-DEVICE time at
       the same S under weak scaling (shard size fixed at
       ``LADDER_SHARD``, device count N = S/shard): per-device work
       is N flash chunks of (shard × shard), so per-device time
       ≈ N · t_chunk — LINEAR in S where the dense formulation grows
       quadratically.  t_chunk is measured (interpret-mode kernel on
       CPU — the math, not the lowering), each ring step's kernel
       wall at the fixed shard geometry; the dense row is measured
       outright.
    """
    import numpy
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import pallas_attention as PA

    stage_rows = {}
    for stages in ((), ("fused",), ("bf16",), ("fused", "bf16"),
                   ("fused", "bf16", "pallas")):
        apply_attn_stages(stages)
        _, wf = build_lm(
            vocab=LADDER_VOCAB, seq=LADDER_SEQ, embed=LADDER_EMBED,
            heads=LADDER_HEADS, blocks=LADDER_BLOCKS,
            batch=LADDER_BATCH, n_train=LADDER_N_TRAIN,
            n_valid=LADDER_N_VALID, remat=False)
        ips = measure(wf, epochs=2)
        stage_rows[",".join(stages) or "none"] = {
            "tokens_per_sec": round(ips * LADDER_SEQ, 1),
        }
    apply_attn_stages(())

    def timed(fn, *args, repeats=3):
        def sync(tree):
            leaves = jax.tree_util.tree_leaves(tree)
            numpy.array(jax.device_get(leaves[0].ravel()[0]))

        sync(fn(*args))  # compile
        t0 = time.time()
        for _ in range(repeats):
            out = fn(*args)
        sync(out)
        return (time.time() - t0) / repeats * 1e3

    B, H, D = 1, 2, 64
    shard = LADDER_SHARD

    def make(S, seed):
        rng = numpy.random.RandomState(seed)
        return [jnp.asarray(rng.normal(0, 1, (B, S, H, D))
                            .astype(numpy.float32))
                for _ in range(3)]

    # One ring step's kernel wall at the fixed shard geometry
    # (fwd+bwd through the chunk's custom VJP — what every device
    # runs N times per step under dp×sp).
    qc, kc, vc = make(shard, 7)
    t_chunk = timed(jax.jit(jax.grad(lambda q, k, v: (
        PA.flash_chunk(q, k, v, causal=True,
                       operand_dtype=jnp.float32,
                       interpret=True)[0] ** 2).sum(),
        argnums=(0, 1, 2))), qc, kc, vc)

    ladder = []
    for S in LADDER_SEQS:
        q, k, v = make(S, S)
        dense_ms = timed(jax.jit(jax.grad(lambda q, k, v: (
            A.attention(q, k, v, causal=True, kernel="xla")
            ** 2).sum(), argnums=(0, 1, 2))), q, k, v)
        n_dev = max(1, S // shard)
        ladder.append({
            "seq": S,
            "dense_1dev_fwd_bwd_ms": round(dense_ms, 3),
            "ring_flash_devices": n_dev,
            "ring_flash_per_device_ms": round(n_dev * t_chunk, 3),
        })
    print(json.dumps({
        "metric": "attn_ladder",
        "unit": "ms_and_tokens_per_sec",
        # vs_baseline: best stage set over the r5-style baseline.
        "value": round(max(r["tokens_per_sec"]
                           for r in stage_rows.values()), 1),
        "vs_baseline": round(
            max(r["tokens_per_sec"] for r in stage_rows.values()) /
            stage_rows["none"]["tokens_per_sec"], 4),
        "vs_baseline_meaning": "best_stage_set_over_none",
        "stages": stage_rows,
        "ring_flash_chunk_ms": round(t_chunk, 3),
        "ring_flash_shard": shard,
        "long_s_ladder": ladder,
    }))


def build_alexnet_streamed():
    import veles_tpu.prng as prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.imagenet import (
        AlexNetWorkflow, StreamedImagenetLoader)
    prng.reset()
    prng.get(0).seed(42)
    launcher = Launcher()
    wf = AlexNetWorkflow(
        launcher, minibatch_size=STREAM_BATCH,
        ticks_per_dispatch=STREAM_TICKS_PER_DISPATCH, max_epochs=1000,
        loader_cls=StreamedImagenetLoader,
        loader_config={"sim_train": STREAM_N_TRAIN,
                       "sim_valid": STREAM_N_VALID,
                       "sim_image_size": 227, "sim_classes": 1000,
                       "validate_labels": False})
    launcher.initialize()
    return launcher, wf


def make_jpeg_tree(base):
    """Writes the synthetic JPEG directory tree ONCE (class
    subdirectories of per-class-tinted photos-ish noise) and returns
    (train_dirs, valid_dirs).  Per-class deterministic RNG, and a
    stale tree (any generation parameter changed since it was
    written) is cleared before regeneration — the loader scans
    directories, so leftovers would silently change the dataset."""
    import shutil
    import numpy
    from PIL import Image
    # Full generation config rides a marker file: a tree written
    # under ANY different config (not just a different file count)
    # must not be silently reused.
    config = {"classes": JPEG_CLASSES,
              "train_per": JPEG_TRAIN_PER_CLASS,
              "valid_per": JPEG_VALID_PER_CLASS,
              "src_size": 256, "sigma": 40, "quality": 85,
              "version": 1}
    marker = os.path.join(base, "generation.json")
    try:
        with open(marker) as fin:
            stale = json.load(fin) != config
    except (OSError, ValueError):
        stale = os.path.isdir(base)
    if stale:
        shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base, exist_ok=True)
    made = []
    for si, (split, per) in enumerate((
            ("train", JPEG_TRAIN_PER_CLASS),
            ("valid", JPEG_VALID_PER_CLASS))):
        dirs = []
        for cls in range(JPEG_CLASSES):
            d = os.path.join(base, split, "class%02d" % cls)
            dirs.append(d)
            if os.path.isdir(d):
                if len(os.listdir(d)) == per:
                    continue
                shutil.rmtree(d)
            os.makedirs(d, exist_ok=True)
            rng = numpy.random.RandomState(1000 * si + cls)
            tint = rng.randint(0, 255, 3)
            src = config["src_size"]
            for i in range(per):
                arr = numpy.clip(
                    rng.normal(tint, config["sigma"],
                               (src, src, 3)), 0,
                    255).astype(numpy.uint8)
                Image.fromarray(arr).save(
                    os.path.join(d, "%04d.jpg" % i),
                    quality=config["quality"])
        made.append(dirs)
    # Marker LAST: an interrupted generation must never leave a
    # marker vouching for a partial tree (the next run will rebuild).
    with open(marker, "w") as fout:
        json.dump(config, fout)
    return made[0], made[1]


def build_jpeg_streamed(train_dirs, valid_dirs):
    """A compact conv net over the streamed JPEG directory (the model
    is deliberately small — this bench is IO-bound by design; the measurement is the PIPELINE, decode +
    upload + dispatch overlap)."""
    import veles_tpu.prng as prng
    from veles_tpu.launcher import Launcher
    from veles_tpu.loader.image import StreamedFileImageLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow
    prng.reset()
    prng.get(0).seed(42)
    launcher = Launcher()
    gd = {"learning_rate": 0.01, "gradient_moment": 0.9}
    wf = StandardWorkflow(
        launcher,
        layers=[
            {"type": "conv_str",
             "->": {"n_kernels": 32, "kx": 7, "ky": 7,
                    "sliding": (4, 4)}, "<-": dict(gd)},
            {"type": "max_pooling", "->": {"kx": 3, "ky": 3,
                                           "sliding": (2, 2)}},
            {"type": "conv_str",
             "->": {"n_kernels": 64, "kx": 3, "ky": 3,
                    "padding": 1}, "<-": dict(gd)},
            {"type": "max_pooling", "->": {"kx": 3, "ky": 3,
                                           "sliding": (2, 2)}},
            {"type": "softmax",
             "->": {"output_sample_shape": (JPEG_CLASSES,)},
             "<-": dict(gd)},
        ],
        loader_cls=StreamedFileImageLoader,
        loader_config={
            "minibatch_size": JPEG_BATCH,
            "train_paths": train_dirs,
            "validation_paths": valid_dirs,
            "size": (JPEG_SIZE, JPEG_SIZE),
            "normalization_type": "linear"},
        loss_function="softmax",
        decision_config={"max_epochs": 1000},
        ticks_per_dispatch=JPEG_TICKS_PER_DISPATCH)
    launcher.initialize()
    return launcher, wf


def measure_decode_throughput(loader, n=256):
    """Raw host decode+normalize rate of the worker pool (no device
    involvement): images/sec over one staged block of n samples."""
    import numpy
    idxs = numpy.tile(
        numpy.arange(sum(loader.class_lengths[:2]),
                     sum(loader.class_lengths[:2]) + min(
                         n, loader.class_lengths[2]),
                     dtype=numpy.int32), (1, 1))
    masks = numpy.ones_like(idxs, dtype=numpy.float32)
    loader._fill_block(idxs, masks)  # warm the pool
    t0 = time.time()
    loader._fill_block(idxs, masks)
    dt = time.time() - t0
    return idxs.shape[1] / dt


def measure_upload_bandwidth(repeats=3, shape=None, dtype=None):
    """Host→device throughput of a representative streamed block
    chunk.  The payload must MATCH the mode's real staged blocks
    (shape AND dtype): per-transfer roundtrip overhead amortizes with
    payload size, so probing with a smaller/other-dtype buffer than
    the run stages biases the ceiling and can push the efficiency
    ratio past 1.0."""
    import jax
    import jax.numpy as jnp
    import numpy
    if shape is None:
        shape = (STREAM_BATCH, 227, 227, 3)
    if dtype is None:
        dtype = numpy.uint8
    if dtype == numpy.uint8:
        x = numpy.random.randint(0, 255, size=shape,
                                 dtype=numpy.uint8)
    else:
        x = numpy.random.rand(*shape).astype(dtype)

    def sync(a):
        numpy.array(jax.device_get(jnp.sum(a[(0,) * a.ndim])))

    sync(jax.device_put(x))  # warmup
    t0 = time.time()
    for _ in range(repeats):
        sync(jax.device_put(x))
    dt = time.time() - t0
    return repeats * x.nbytes / dt


def measure(wf, epochs):
    import jax
    loader, compiler = wf.loader, wf.compiler
    compiler.compile()

    def sync():
        """Waits for the last dispatch: its state outputs all come
        from one computation, so any of them will do."""
        jax.block_until_ready(
            next(iter(compiler._state_vecs.values())).devmem)

    def run_epoch():
        start_epoch = loader.epoch_number
        while loader.epoch_number == start_epoch:
            loader.run()

    # Warmup epoch compiles the train+validation block programs.
    run_epoch()
    sync()
    t0 = time.time()
    for _ in range(epochs):
        run_epoch()
    sync()
    dt = time.time() - t0
    return epochs * loader.total_samples / dt


def trace_one_step(wf, path):
    """Enables span tracing, drives fused dispatches until one
    ``step`` span lands, exports the Chrome trace to ``path`` and
    returns the milliseconds of that step, serving to ready
    (--trace-out; docs/observability.md)."""
    from veles_tpu.observability import tracing
    tracing.enable()
    tracing.clear()
    loader = wf.loader

    def dispatch_spans():
        return [s for s in tracing.spans()
                if s["name"] == "step"]

    for _ in range(4 * max(getattr(wf, "ticks_per_dispatch", 1), 1)):
        loader.run()
        if dispatch_spans():
            break
    tracing.export_chrome_trace(path)
    spans = dispatch_spans()
    tracing.reset()
    if not spans:
        return None
    return round(spans[-1]["dur"] / 1000.0, 3)


#: Pipeline-schedule A/B geometry (``--pp-schedule``; docs/
#: pipeline.md, BENCHNOTES): 4 stages × 8 microbatches of 8 layers —
#: the ≥4-stage case ISSUE 12 asks the bubble measurement for.
PP_STAGES = 4
PP_MICRO = 8
PP_LAYERS = 8
PP_WIDTH = 256
PP_MB_ROWS = 8


def pipeline_bench(argv):
    """``--pp-schedule[=gpipe,1f1b,interleaved]`` — the pipeline
    schedule A/B micro-bench (BENCHNOTES; docs/pipeline.md): one
    jitted fwd+bwd through ops.pipeline per schedule at
    PP_STAGES×PP_MICRO, reporting table-derived scan steps and
    bubble fractions plus measured wall ms, and the 1F1B
    matched-memory headline — GPipe at 1F1B's S-microbatch
    activation budget must flush every S microbatches (two M=S
    ramps here) while 1F1B runs the full M in one; ``value`` is the
    measured flushed-GPipe/1F1B wall ratio."""
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.ops.pipeline import (SCHEDULES, bubble_fraction,
                                        pipeline, schedule_steps)
    from veles_tpu.parallel import make_mesh
    spec = next((a.split("=", 1)[1] for a in argv
                 if a.startswith("--pp-schedule=")), "")
    names = tuple(s for s in spec.split(",") if s) or SCHEDULES
    for s in names:
        if s not in SCHEDULES:
            raise SystemExit("unknown pipeline schedule %r — valid: "
                             "%s" % (s, ", ".join(SCHEDULES)))
    S, M, L, F = PP_STAGES, PP_MICRO, PP_LAYERS, PP_WIDTH
    V = max(1, L // S)
    rng = numpy.random.RandomState(0)
    params = {
        "w": rng.normal(0, 0.2, (L, F, F)).astype(numpy.float32),
        "b": rng.normal(0, 0.1, (L, F)).astype(numpy.float32)}
    x = rng.normal(0, 1, (M * PP_MB_ROWS, F)).astype(numpy.float32)

    def fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    mesh = make_mesh(axes={"stage": S})

    def timed_grad(xs, micro, schedule, repeats=5):
        f = jax.jit(jax.grad(lambda p: (pipeline(
            fn, p, jnp.asarray(xs), mesh, "stage", micro,
            schedule=schedule) ** 2).sum()))

        def sync(g):
            numpy.array(jax.device_get(g["b"].ravel()[0]))

        sync(f(params))  # compile
        t0 = time.time()
        for _ in range(repeats):
            out = f(params)
        sync(out)
        return (time.time() - t0) / repeats * 1e3

    schedules = {}
    for name in names:
        chunks = V if name == "interleaved" else 1
        steps = len(schedule_steps(name, S, M, n_chunks=chunks))
        schedules[name] = {
            "scan_steps": steps,
            # Interleaved steps cost 1/V of a stage step — the
            # comparable unit across schedules.
            "weighted_steps": round(steps / float(chunks), 2),
            "bubble_frac": round(bubble_fraction(
                name, S, M, n_chunks=chunks), 4),
            "chunks": chunks,
            "fwd_bwd_wall_ms": round(timed_grad(x, M, name), 3),
        }
    out = {
        "metric": "pipeline_schedule_ab",
        "unit": "x_vs_memory_matched_gpipe",
        "stages": S, "microbatches": M, "layers": L,
        "schedules": schedules,
    }
    if "1f1b" in schedules:
        # Matched activation memory: GPipe flushes every S
        # microbatches (M/S ramps of M=S), 1F1B runs M unflushed.
        # Each flush covers its SLICE of the batch at the SAME
        # microbatch size (S·rows of the M-run's per-microbatch
        # rows), so total compute — and per-step activation memory —
        # match the 1F1B run; only the schedule differs.
        flushes = M // S
        flushed_steps = flushes * (S + S - 1)
        flushed_ms = timed_grad(x[:S * PP_MB_ROWS], S,
                                "gpipe") * flushes
        out["gpipe_flushed_scan_steps"] = flushed_steps
        out["gpipe_flushed_bubble_frac"] = round(
            bubble_fraction("gpipe", S, S), 4)
        out["gpipe_flushed_wall_ms"] = round(flushed_ms, 3)
        out["value"] = round(
            flushed_ms / schedules["1f1b"]["fwd_bwd_wall_ms"], 4)
        out["vs_baseline"] = out["value"]
        out["vs_baseline_meaning"] = \
            "memory_matched_gpipe_over_1f1b_wall"
    print(json.dumps(out))


def parse_optimizer(argv):
    """``--optimizer=adam`` → sets the engine default so every GD
    unit of the benched workflow uses the named rule (sgd default);
    returns the name for the JSON line."""
    name = "sgd"
    for arg in argv:
        if arg.startswith("--optimizer="):
            name = arg.split("=", 1)[1]
    from veles_tpu.znicz import optimizers
    optimizers.get(name)  # actionable error on unknown names
    from veles_tpu.config import root
    root.common.engine.optimizer = name
    return name


def measure_update_ms(wf, repeats=10):
    """Device milliseconds of ONE optimizer update phase: the step
    compiler's apply_updates closure jitted alone over the model's
    real params/slots (zero grads — the update rule's cost does not
    depend on gradient values).  This is the ``optimizer_state_
    bytes`` sibling number: what the chosen rule costs per dispatch,
    isolated from forward/backward."""
    import jax
    import jax.numpy as jnp
    import numpy
    c = wf.compiler
    if not c._compiled:
        c.compile()
    apply_updates = c._core_[1]
    params = {n: v.devmem for n, v in c._param_vecs.items()}
    states = {n: v.devmem for n, v in c._state_vecs.items()}
    grads = {n: jnp.zeros_like(v) for n, v in params.items()}
    fn = jax.jit(
        lambda p, s, g: apply_updates(p, g, dict(s), None))

    def sync(res):
        new_p, _new_s = res
        numpy.array(jax.device_get(
            next(iter(new_p.values())).ravel()[0]))

    sync(fn(params, states, grads))  # warm/compile
    t0 = time.time()
    for _ in range(repeats):
        out = fn(params, states, grads)
    sync(out)
    return round((time.time() - t0) / repeats * 1e3, 3)


def optimizer_fields(wf, name):
    """Optimizer columns for the bench JSON line: kind, total slot
    bytes, isolated update-phase device ms, and (distributed runs
    only) the slot-shard wire bytes counter — None on single-node
    benches, where no slot traffic exists."""
    from veles_tpu import resilience
    from veles_tpu.znicz.nn_units import GradientDescentBase
    state_bytes = sum(
        vec.nbytes for u in wf.units
        if isinstance(u, GradientDescentBase)
        for vec in u.tstate.values())
    slot_wire = resilience.stats.get("net.slot_bytes")
    return {
        "optimizer": name,
        "optimizer_state_bytes": int(state_bytes),
        "update_device_ms": measure_update_ms(wf),
        "slot_wire_bytes": int(slot_wire) if slot_wire else None,
    }


def parse_net_dtype(argv):
    for i, arg in enumerate(argv):
        if arg.startswith("--net-dtype="):
            return arg.split("=", 1)[1]
        if arg == "--net-dtype" and i + 1 < len(argv):
            return argv[i + 1]
    return None


def net_dtype_fields(wf, net_dtype):
    """``--lm --net-dtype=DT`` A/B columns (BENCH_r13): the
    worker→master delta wire bytes per minibatch at every codec
    rung up to DT.  One real update message (``{"U": ..., "bv":
    ...}``) is built from the LM's actual trainable arrays — a
    delta has exactly a weight's shape — encoded through
    ``encode_delta`` and framed by the PR-4 zero-copy tensor wire,
    so the figure is wire truth (codes + scales + pickled
    skeleton), not an nbytes estimate.  The acceptance bar is int8
    ≤ ~half the bf16 bytes; the convergence and quality gates for
    the lossy rungs live in tier-1 (tests/test_quant.py)."""
    if not net_dtype:
        return {}
    import numpy
    from veles_tpu.network_common import (DELTA_DTYPES,
                                          encode_delta,
                                          encode_tensor_parts)
    if net_dtype not in DELTA_DTYPES:
        raise SystemExit("--net-dtype %s: valid rungs are %s" %
                         (net_dtype, ", ".join(DELTA_DTYPES)))
    deltas = {}
    for i, u in enumerate(wf.units):
        get = getattr(u, "_trainable_arrays", None)
        if get is None or not getattr(u, "trainables", None):
            continue
        for attr, arr in get().items():
            a = numpy.ascontiguousarray(arr, dtype=numpy.float32)
            deltas["%d.%s" % (i, attr)] = a
    ladder = [n for n in DELTA_DTYPES
              if n in ("fp32", "bf16") or n == net_dtype]
    out = {"net_dtype": net_dtype}
    for rung in ladder:
        msg = {"U": {}, "bv": 0}
        for name, a in deltas.items():
            payload = encode_delta(a, rung, seed=1)
            msg["U"][name] = a if payload is None else payload
        parts = encode_tensor_parts(msg)
        out["delta_bytes_per_minibatch_%s" % rung] = \
            sum(len(p) for p in parts)
    base = out.get("delta_bytes_per_minibatch_bf16")
    mine = out.get("delta_bytes_per_minibatch_%s" % net_dtype)
    if base and mine and net_dtype not in ("fp32", "bf16"):
        out["delta_bytes_vs_bf16"] = round(mine / base, 4)
    return out


def parse_population(argv):
    """``--population[=N]`` / ``--population-members=N`` /
    ``--population-epochs=E`` / ``--population-ticks=K`` knobs for
    the population bench (defaults 4 members, 3 epochs, 8-tick
    jobs).  The member count follows the product CLI's
    ``--population N`` / ``--population=N`` spellings too."""
    members, epochs, ticks = 4, 3, 8
    for i, arg in enumerate(argv):
        if arg == "--population":
            if i + 1 < len(argv) and argv[i + 1].isdigit():
                members = int(argv[i + 1])
        elif arg.startswith("--population="):
            members = int(arg.split("=", 1)[1])
        elif arg.startswith("--population-members="):
            members = int(arg.split("=", 1)[1])
        elif arg.startswith("--population-epochs="):
            epochs = int(arg.split("=", 1)[1])
        elif arg.startswith("--population-ticks="):
            ticks = int(arg.split("=", 1)[1])
    return members, epochs, ticks


def population_bench(argv):
    """``--population``: PBT population over the in-process loopback
    fleet contract (docs/population.md) — N member lineages with a
    tuned learning rate trained to completion through the REAL
    member-job/delta-fold cycle, every job serialized through the
    tensor-frame encoder so the JSON line carries true wire costs.
    Reports members·ticks/s (the population engine's figure of
    merit: lineage minibatches trained per second across the whole
    population), exploit latency, and the exploit-as-delta wire
    ratio (exploit job bytes vs a full weight ship)."""
    import numpy
    import veles_tpu.prng as prng
    from veles_tpu.config import Tune, root
    from veles_tpu.launcher import Launcher
    from veles_tpu.network_common import encode_message
    from veles_tpu.population import (PopulationMaster,
                                      PopulationWorker)
    from veles_tpu.population.engine import loopback_proto
    from veles_tpu.__main__ import import_workflow_module

    members, epochs, ticks = parse_population(argv)
    module = import_workflow_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "veles_tpu", "znicz", "samples", "mnist.py"))
    root.mnist.max_epochs = epochs
    root.mnist.learning_rate = Tune(0.1, 0.001, 0.5)
    prng.reset()
    master = PopulationMaster(
        Launcher(), module, mode="pbt", size=members, seed=42,
        pbt_interval=1, pbt_quantile=0.34)
    worker = PopulationWorker(Launcher(), module, seed=42)
    proto = loopback_proto(ticks)
    master.note_slave_protocol("local", proto)
    worker.note_net_proto(proto)

    sizes = {"first": [], "exploit": [], "steady": []}
    exploit_ms = []
    seen = set()
    prev_exploits = 0
    t0 = time.time()
    while not master.should_stop_serving():
        job = master.generate_data_for_slave("local")
        if job is None:
            break
        _flags, parts = encode_message(
            {"cmd": "job", "data": job}, codec=None, tensor=True)
        tag = ("exploit" if "exploit" in job else
               "first" if job["m"] not in seen else "steady")
        seen.add(job["m"])
        sizes[tag].append(sum(len(p) for p in parts))
        replies = []
        worker.do_job(job, None, replies.append)
        master.apply_data_from_slave(replies[0], "local")
        if master.exploits > prev_exploits:
            prev_exploits = master.exploits
            exploit_ms.append(master.last_exploit_ms)
    wall = time.time() - t0

    summary = master.population_summary()
    total_ticks = sum(m.ticks_done for m in master.members)
    full = max(sizes["first"]) if sizes["first"] else None
    exploit_bytes = (round(float(numpy.mean(sizes["exploit"])))
                     if sizes["exploit"] else None)
    print(json.dumps({
        "metric": "population_members_ticks_per_sec",
        "value": round(total_ticks / wall, 1),
        "unit": "members*ticks/sec",
        "members": members,
        "scheduling": "pbt",
        "epochs": epochs,
        "job_ticks": ticks,
        "jobs": summary["jobs"],
        "ticks": total_ticks,
        "wall_s": round(wall, 2),
        "exploits": master.exploits,
        "exploit_ms_mean": (round(float(numpy.mean(exploit_ms)), 2)
                            if exploit_ms else None),
        "exploit_job_bytes": exploit_bytes,
        "full_ship_bytes": full,
        "steady_job_bytes": (round(float(numpy.median(
            sizes["steady"]))) if sizes["steady"] else None),
        "exploit_delta_ratio": (round(full / exploit_bytes, 1)
                                if full and exploit_bytes else None),
        "best_fitness": summary.get("best_fitness"),
        "mean_fitness": summary.get("mean_fitness"),
    }))


def elastic_bench(argv):
    """``--elastic``: membership-walk bench over the REAL socket
    fleet (docs/distributed.md "Elastic operations").  A trivial job
    ledger streams through a loopback coordinator while the fleet
    walks 4→2→4: at 25% done two workers receive a preemption notice
    and drain (finish in-flight work, goodbye, thread exits), at 50%
    two fresh workers dial in and are full-shipped.  Reports jobs/s
    sustained across the whole walk, late-join latency, and the
    membership ledger — clean goodbyes only, zero drops."""
    import threading

    from veles_tpu import resilience
    from veles_tpu.client import Client
    from veles_tpu.launcher import Launcher
    from veles_tpu.observability import metrics
    from veles_tpu.server import Server
    from veles_tpu.units import TrivialUnit
    from veles_tpu.workflow import Workflow

    total = 400
    for arg in argv:
        if arg.startswith("--elastic-jobs="):
            total = int(arg.split("=", 1)[1])

    class _Ledger(Workflow):
        """Echo-job ledger: the bench measures the control plane
        (dispatch + fold + membership), not device math."""

        def __init__(self, launcher, total_jobs=0, **kwargs):
            super(_Ledger, self).__init__(launcher, **kwargs)
            self.body = TrivialUnit(self)
            self.body.link_from(self.start_point)
            self.end_point.link_from(self.body)
            self.total_jobs = total_jobs
            self.next_job = 1
            self.done = {}
            self.outstanding = {}
            self.requeued = []
            self.jobs_run = 0

        def generate_data_for_slave(self, slave=None):
            if self.requeued:
                n = self.requeued.pop(0)
            elif self.next_job <= self.total_jobs:
                n = self.next_job
                self.next_job += 1
            else:
                return None
            self.outstanding.setdefault(slave, []).append(n)
            return {"n": n}

        def apply_data_from_slave(self, data, slave=None):
            n = data["echo"]
            lst = self.outstanding.get(slave, [])
            if n in lst:
                lst.remove(n)
                self.done[n] = self.done.get(n, 0) + 1

        def drop_slave(self, slave=None):
            self.requeued.extend(self.outstanding.pop(slave, []))

        def should_stop_serving(self):
            return (len(self.done) >= self.total_jobs and
                    not self.requeued and
                    not any(self.outstanding.values()))

        def do_job(self, data, update, callback):
            self.jobs_run += 1
            callback({"echo": data["n"]})

    def start_worker():
        slave = _Ledger(Launcher())
        client = Client(addr, slave, reconnect_attempts=100,
                        reconnect_delay=0.02)
        thread = threading.Thread(target=client.run, daemon=True)
        t_dial = time.time()
        thread.start()
        return {"client": client, "thread": thread, "slave": slave,
                "dialed": t_dial}

    def wait_done(threshold, deadline=60.0):
        limit = time.time() + deadline
        while len(master.done) < threshold and time.time() < limit:
            time.sleep(0.002)

    master = _Ledger(Launcher(), total_jobs=total)
    server = Server(":0", master)
    addr = "127.0.0.1:%d" % server.port
    t0 = time.time()
    workers = [start_worker() for _ in range(4)]

    def watch_first_job(w):
        # Stamp dial → first job applied on the worker; runs beside
        # the join so the stamp is not smeared by the rest of the run.
        while not w["slave"].jobs_run and not w["client"]._stop:
            time.sleep(0.0005)
        w["first_job"] = time.time()

    wait_done(total // 4)
    for w in workers[2:]:
        w["client"].drain()
    wait_done(total // 2)
    joiners = [start_worker() for _ in range(2)]
    watchers = [threading.Thread(target=watch_first_job, args=(w,),
                                 daemon=True) for w in joiners]
    for t in watchers:
        t.start()
    server.wait(timeout=120)
    wall = time.time() - t0

    server.stop()
    for w in workers + joiners:
        w["thread"].join(timeout=5)
    for t in watchers:
        t.join(timeout=5)
    join_ms = [(w["first_job"] - w["dialed"]) * 1e3
               for w in joiners if "first_job" in w]

    snap = server.fleet.snapshot()
    print(json.dumps({
        "metric": "elastic_jobs_per_sec",
        "value": round(total / wall, 1),
        "unit": "jobs/sec",
        "jobs": total,
        "wall_s": round(wall, 3),
        "walk": "4->2->4",
        "exactly_once": all(v == 1 for v in master.done.values()),
        "membership_epoch": snap["epoch"],
        "joins": snap["joins"],
        "drains": snap["drains"],
        "goodbyes": resilience.stats.get("server.goodbye"),
        "drops": resilience.stats.get("server.drop"),
        "requeues": resilience.stats.get("server.requeue"),
        "join_latency_ms": (round(max(join_ms), 1)
                            if join_ms else None),
        "epoch_gauge": getattr(
            metrics.registry.peek("membership.epoch"), "value", None),
    }))


def attribution_fields():
    """Live device-time/MFU gauge readings for the bench JSON line
    (the BENCH_r06 per-stage attribution record)."""
    from veles_tpu.observability import attribution
    perf = attribution.perf_summary() or {}
    dispatches = perf.get("dispatches") or 0
    mean_ms = None
    if dispatches:
        mean_ms = round(perf["device_s_total"] / dispatches * 1e3, 3)
    return {
        "step_device_ms": perf.get("step_ms"),
        "step_device_ms_mean": mean_ms,
        "device_dispatches": dispatches,
        "mfu_live": perf.get("mfu"),
    }


def main():
    from veles_tpu.backends import (device_entry,
                                    enable_compilation_cache)
    enable_compilation_cache()
    if any(a.startswith("--pp-schedule") for a in sys.argv):
        # The pipeline schedule A/B micro-bench is its own mode
        # (the LM headline bench is dense/non-pipelined).
        pipeline_bench(sys.argv)
        return
    if any(a.startswith("--population") for a in sys.argv):
        population_bench(sys.argv)
        return
    if any(a.startswith("--elastic") for a in sys.argv):
        elastic_bench(sys.argv)
        return
    if "--serve" in sys.argv:
        serve_bench(sys.argv)
        return
    if "--attn-ladder" in sys.argv:
        attn_ladder_bench(sys.argv)
        return
    if "--streamed-jpeg" in sys.argv:
        base = os.environ.get(
            "VELES_JPEG_DIR",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".bench_jpeg"))
        train_dirs, valid_dirs = make_jpeg_tree(base)
        import numpy as _np
        jpeg_block = (JPEG_TICKS_PER_DISPATCH * JPEG_BATCH,
                      JPEG_SIZE, JPEG_SIZE, 3)
        bw_before = measure_upload_bandwidth(shape=jpeg_block,
                                             dtype=_np.float32)
        _, wf = build_jpeg_streamed(train_dirs, valid_dirs)
        decode_ips = measure_decode_throughput(wf.loader)
        ips = measure(wf, epochs=2)
        # The upload bandwidth can drift between probes; probing
        # only before the run can understate the ceiling and report
        # efficiency > 1.  Probe again after and use the max.
        bw = max(bw_before, measure_upload_bandwidth(
            shape=jpeg_block, dtype=_np.float32))
        bw_ceiling = bw / JPEG_BYTES_PER_IMG
        ceiling = min(bw_ceiling, decode_ips)
        print(json.dumps({
            "metric": "jpeg_streamed_train_images_per_sec",
            "value": round(ips, 1),
            "unit": "images/sec",
            # The model here is a deliberately small conv net (the
            # bench is IO-bound by design), so an AlexNet throughput
            # ratio would be meaningless: the figure of merit IS the
            # pipeline efficiency vs the measured ceilings.
            "vs_baseline": round(ips / ceiling, 4),
            "vs_baseline_meaning": "pipeline_efficiency_vs_ceiling",
            "upload_gbps": round(bw / 1e9, 4),
            "upload_gbps_before": round(bw_before / 1e9, 4),
            "decode_images_per_sec": round(decode_ips, 1),
            "bw_ceiling_images_per_sec": round(bw_ceiling, 1),
            "pipeline_efficiency": round(ips / ceiling, 4),
        }))
        return
    if "--streamed" in sys.argv:
        bw_before = measure_upload_bandwidth()
        _, wf = build_alexnet_streamed()
        ips = measure(wf, epochs=2)
        # Before+after probes, max wins: the upload bandwidth can
        # drift mid-run, and a stale low probe would report an
        # impossible efficiency > 1 (same treatment as the JPEG
        # mode).
        bw = max(bw_before, measure_upload_bandwidth())
        bw_ceiling = bw / STREAM_BYTES_PER_IMG
        print(json.dumps({
            "metric": "alexnet_streamed_train_images_per_sec",
            "value": round(ips, 1),
            "unit": "images/sec",
            "vs_baseline": round(ips / A100_ALEXNET_IMG_PER_SEC, 4),
            "upload_gbps": round(bw / 1e9, 4),
            "upload_gbps_before": round(bw_before / 1e9, 4),
            "bw_ceiling_images_per_sec": round(bw_ceiling, 1),
            "pipeline_efficiency": round(ips / bw_ceiling, 4),
        }))
        return
    if "--lm" in sys.argv or "--lm-toy" in sys.argv:
        toy = "--lm-toy" in sys.argv
        # A/B hook for the attention fast path (BENCHNOTES r6):
        # --attn-stages=fused,bf16,pallas toggles each stage's engine
        # knob before the workflow is built, and the stage set rides
        # the JSON line so per-stage attribution is in the record.
        stages = parse_attn_stages(sys.argv)
        apply_attn_stages(stages)
        opt_name = parse_optimizer(sys.argv)
        net_dtype = parse_net_dtype(sys.argv)
        # Both MFU figures on the JSON line — the analytic one below
        # and the live attribution gauge — divide by this device's
        # peak from the one device_kind table; resolved BEFORE the
        # model is built so an unknown device costs nothing.
        peak_tflops = device_peak_tflops()
        if toy:
            geom = dict(vocab=LM_TOY_VOCAB, seq=LM_TOY_SEQ,
                        embed=LM_TOY_EMBED, heads=LM_TOY_HEADS,
                        blocks=LM_TOY_BLOCKS, batch=LM_TOY_BATCH,
                        n_train=LM_TOY_N_TRAIN,
                        n_valid=LM_TOY_N_VALID, remat=False)
            _, wf = build_lm(**geom)
        else:
            # The default geometry lives ONCE in build_lm's defaults
            # (the LM_* constants); geom here only feeds the FLOP
            # accounting below.
            geom = dict(vocab=LM_VOCAB, seq=LM_SEQ, embed=LM_EMBED,
                        blocks=LM_BLOCKS, n_train=LM_N_TRAIN,
                        n_valid=LM_N_VALID)
            _, wf = build_lm()
        ips = measure(wf, epochs=2)
        trace_out = next(
            (a.split("=", 1)[1] for a in sys.argv
             if a.startswith("--trace-out=")), None)
        step_wall_ms = trace_one_step(wf, trace_out) \
            if trace_out else None
        tokens_per_sec = ips * geom["seq"]
        # Validation sequences run forward-only (~1/3 of the train
        # FLOP cost); weight them accordingly in the FLOP accounting.
        n_total = geom["n_train"] + geom["n_valid"]
        flop_weight = (geom["n_train"] + geom["n_valid"] / 3.0) / \
            n_total
        flop_per_token = lm_train_flop_per_token(
            geom["embed"], geom["blocks"], geom["seq"],
            geom["vocab"])
        tflops = tokens_per_sec * flop_weight * flop_per_token / 1e12
        mfu = tflops / peak_tflops
        print(json.dumps({
            "metric": "tinylm_gpt_small_train_tokens_per_sec" if toy
            else "lm_640m_remat_train_tokens_per_sec",
            "value": round(tokens_per_sec, 1),
            "unit": "tokens/sec",
            # No reference LM baseline exists (the reference predates
            # attention): vs_baseline here is the MFU fraction, NOT a
            # throughput ratio like the other modes.
            "vs_baseline": round(mfu, 4),
            "vs_baseline_meaning": "mfu_fraction_no_reference_lm",
            "model_tflops_per_sec": round(tflops, 1),
            "mfu_vs_device_bf16_peak": round(mfu, 4),
            "device": device_entry(),
            "peak_tflops": peak_tflops,
            "attn_stages": list(stages),
            # Per-stage attribution (BENCH_r06): wall ms of one
            # traced dispatch, device ms + live-MFU gauges measured
            # at the dispatch (observability.attribution).
            "step_wall_ms": step_wall_ms,
            "trace_out": trace_out,
            **attribution_fields(),
            **optimizer_fields(wf, opt_name),
            **net_dtype_fields(wf, net_dtype),
        }))
        return
    if "--mlp" in sys.argv:
        opt_name = parse_optimizer(sys.argv)
        _, wf = build_mlp()
        ips = measure(wf, epochs=3)
        print(json.dumps({
            "metric": "mnist784_fc_train_images_per_sec",
            "value": round(ips, 1),
            "unit": "images/sec",
            "vs_baseline": round(ips / A100_MLP_IMG_PER_SEC, 4),
            **optimizer_fields(wf, opt_name),
        }))
        return
    peak_tflops = device_peak_tflops()
    _, wf = build_alexnet()
    ips = measure(wf, epochs=2)
    # Validation images run forward-only (~1/3 of the train FLOP
    # cost) — weight them like the LM bench does instead of billing
    # every served image at the full train cost (VERDICT r4 weak
    # item 2: the old accounting overstated TFLOP/s by ~2%).
    n_total = ALEXNET_N_TRAIN + ALEXNET_N_VALID
    flop_weight = (ALEXNET_N_TRAIN + ALEXNET_N_VALID / 3.0) / n_total
    tflops = ips * flop_weight * ALEXNET_TRAIN_GFLOP_PER_IMG / 1000.0
    print(json.dumps({
        "metric": "alexnet_train_images_per_sec",
        "value": round(ips, 1),
        "unit": "images/sec",
        "vs_baseline": round(ips / A100_ALEXNET_IMG_PER_SEC, 4),
        "model_tflops_per_sec": round(tflops, 1),
        "mfu_vs_device_bf16_peak": round(tflops / peak_tflops, 4),
        "device": device_entry(),
        "peak_tflops": peak_tflops,
    }))


if __name__ == "__main__":
    main()
