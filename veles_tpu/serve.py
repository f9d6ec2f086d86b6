"""``python -m veles_tpu.serve model.veles.tgz [--port N]`` — serve an
exported artifact over HTTP (reference analogue: running a workflow
under velescli with the RESTfulAPI unit, restful_api.py:78), through
the production serving engine: shape-bucketed dynamic batching,
paged KV-cache decode-step continuous batching for LM artifacts
(``--kv-blocks`` / ``--kv-block-size`` / ``--no-paged-decode``),
speculative decoding (``--spec`` n-gram drafting, ``--spec-draft``
draft model, ``--spec-max-k`` verify width),
``--warmup`` grid precompilation, per-client rate limiting,
queue-depth backpressure, hot weight reload (``--reload-watch`` /
authenticated ``POST /admin/reload``), graceful SIGTERM drain
(``--drain-timeout``) — and the serving FABRIC above one engine:
``--fabric-replicas`` prefix-affinity routing over N replicas,
``--fabric-disagg`` prefill/decode disaggregation, ``--tenant``
multi-tenant quota admission — docs/serving.md."""

import argparse
import signal
import sys
import threading

from .backends import enable_compilation_cache
from .export import KV_DTYPES
from .restful import ModelServer


def build_server(argv=None):
    """Parses the command line and builds the :class:`ModelServer`
    it describes, not yet listening — :func:`main` serves it in the
    foreground; ``chip_smoke.py`` starts the same server on a thread
    and talks to it over loopback."""
    parser = argparse.ArgumentParser(
        prog="veles_tpu.serve",
        description="Serve an exported veles_tpu model over HTTP "
                    "(POST /api, POST /api/generate, GET /health, "
                    "GET /stats, GET /metrics Prometheus "
                    "exposition)")
    parser.add_argument("artifact", help="model .veles.tgz path")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8180)
    parser.add_argument(
        "--max-batch", type=int, default=8, metavar="N",
        help="max rows coalesced into one device batch (default 8)")
    parser.add_argument(
        "--queue-depth", type=int, default=64, metavar="N",
        help="bounded request-queue depth; beyond it requests get "
             "429 + Retry-After (default 64)")
    parser.add_argument(
        "--rate-limit", type=float, default=None, metavar="R",
        help="per-client token-bucket rate in requests/s "
             "(default: no limit)")
    parser.add_argument(
        "--deadline", type=float, default=30.0, metavar="SEC",
        help="per-request deadline; expired requests are cancelled "
             "unserved (default 30)")
    parser.add_argument(
        "--token", default=None, metavar="SECRET",
        help="require X-Status-Token on /api/generate (the same "
             "shared-secret scheme web_status uses)")
    parser.add_argument(
        "--warmup", action="store_true",
        help="precompile the shape-bucket grid before serving so "
             "the first request never pays an XLA compile")
    parser.add_argument(
        "--kv-blocks", type=int, default=None, metavar="N",
        help="paged KV cache pool size in blocks (default: sized so "
             "max-batch rows can each hold a full-length sequence)")
    parser.add_argument(
        "--kv-block-size", type=int, default=16, metavar="N",
        help="tokens per paged KV cache block (default 16)")
    parser.add_argument(
        "--kv-dtype", default=None, choices=KV_DTYPES,
        help="paged KV cache storage dtype (default f32); int8/fp8 "
             "quantize per (block, head) with f32 scales stored "
             "alongside the block tables — 4x the streams per byte "
             "of HBM (docs/serving.md 'Quantized KV')")
    parser.add_argument(
        "--weight-dtype", default=None, choices=("f32", "int8"),
        help="decode-matmul weight storage (default f32); int8 = "
             "weight-only quantization, per-output-channel scales "
             "dequantized inside the matmul")
    parser.add_argument(
        "--no-paged-decode", action="store_true",
        help="disable paged decode-step continuous batching and "
             "fall back to whole-request generate batching")
    parser.add_argument(
        "--spec", action="store_true",
        help="enable speculative decoding on the paged decode loop "
             "with the zero-cost prompt-lookup (n-gram) drafter — "
             "greedy output stays bit-identical to plain decode")
    parser.add_argument(
        "--spec-draft", default=None, metavar="PATH",
        help="speculative draft model: a second exported artifact "
             "(same vocabulary, geometry-checked) proposing tokens "
             "through its own paged pool; implies --spec")
    parser.add_argument(
        "--spec-max-k", type=int, default=4, metavar="K",
        help="max draft tokens verified per dispatch (1..15; "
             "per-row adaptive K backs off to plain decode on "
             "streams whose drafts keep missing; default 4)")
    parser.add_argument(
        "--spec-draft-blocks", type=int, default=None, metavar="N",
        help="draft-model KV pool size in blocks (default: the "
             "target pool's size)")
    parser.add_argument(
        "--drain-timeout", type=float, default=30.0, metavar="SEC",
        help="graceful-stop budget: on SIGTERM admissions close "
             "with 503 + Retry-After and live decode rows get this "
             "long to finish before the process exits 0 (default "
             "30)")
    parser.add_argument(
        "--reload-watch", default=None, metavar="PATH",
        help="hot-reload watch target: a serving artifact or a "
             "snapshotter *_current.lnk pointer — when it changes, "
             "the sha256-manifest-verified artifact is hot-swapped "
             "in without dropping live streams")
    parser.add_argument(
        "--reload-poll", type=float, default=5.0, metavar="SEC",
        help="reload-watch poll interval (default 5)")
    parser.add_argument(
        "--fabric-replicas", type=int, default=1, metavar="N",
        help="serving fabric: run N engine replicas behind the "
             "prefix-affinity consistent-hash router — requests "
             "sharing a prompt prefix land on the same replica and "
             "hit its KV prefix cache (default 1: no fabric)")
    parser.add_argument(
        "--fabric-disagg", action="store_true",
        help="serving fabric: disaggregate prefill from decode — a "
             "dedicated prefill worker fills KV blocks and ships "
             "them to decode replicas as versioned tensors over the "
             "zero-copy wire, so long prefills never stall decoding "
             "streams")
    parser.add_argument(
        "--tenant", action="append", default=None,
        metavar="NAME=RATE[:BURST][@ARTIFACT]",
        help="serving fabric: register a tenant with a token-bucket "
             "quota (repeatable); once any tenant is registered, "
             "requests without a known X-Tenant get 403 and "
             "over-quota tenants get 429 + Retry-After — without "
             "shedding sibling tenants")
    args = parser.parse_args(argv)
    enable_compilation_cache()
    if args.weight_dtype is not None:
        # export.py reads the decode weight mode from config — the
        # paged/bucketed programs re-quantize lazily on their next
        # _lm_params() look.
        from .config import root
        root.common.serving.weight_dtype = args.weight_dtype
    return ModelServer(
        args.artifact, host=args.host, port=args.port,
        token=args.token, max_batch=args.max_batch,
        queue_depth=args.queue_depth, rate_limit=args.rate_limit,
        deadline=args.deadline, warmup=args.warmup,
        paged=False if args.no_paged_decode else None,
        kv_blocks=args.kv_blocks, kv_block_size=args.kv_block_size,
        kv_dtype=args.kv_dtype,
        spec=args.spec, spec_draft=args.spec_draft,
        spec_max_k=args.spec_max_k,
        spec_draft_blocks=args.spec_draft_blocks,
        drain_timeout=args.drain_timeout,
        reload_watch=args.reload_watch,
        reload_poll=args.reload_poll,
        fabric_replicas=args.fabric_replicas,
        fabric_disagg=args.fabric_disagg,
        tenant=args.tenant)


def main(argv=None):
    server = build_server(argv)
    install_sigterm_drain(server)
    try:
        server.serve()
    except KeyboardInterrupt:
        server.stop(drain=True)
    return 0


def install_sigterm_drain(server):
    """SIGTERM → graceful drain → exit 0 (the supervisor-facing
    shutdown contract: in-flight requests finish, late arrivals get
    503 + Retry-After, and a clean exit code says this was an
    orderly stop, not a crash).  The drain runs on a helper thread —
    signal handlers must return quickly, and ``server.stop`` joins
    the device thread.  No-op outside the main thread (tests import
    and drive ``main`` directly)."""
    def on_term(_signum, _frame):
        threading.Thread(target=lambda: server.stop(drain=True),
                         daemon=True,
                         name="veles-sigterm-drain").start()

    try:
        signal.signal(signal.SIGTERM, on_term)
    except ValueError:
        pass  # not the main thread


if __name__ == "__main__":
    sys.exit(main())
