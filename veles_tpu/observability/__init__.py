"""Unified observability: span tracing, typed metrics, device/MFU
attribution (docs/observability.md).

Five legs over one substrate:

* :mod:`.tracing` — ``span("net.send")`` context managers feeding a
  bounded ring collector, cross-node clock alignment, and Chrome
  trace-event export (``--trace-out``);
* :mod:`.metrics` — counters/gauges/histograms in a process
  registry with Prometheus text exposition (``GET /metrics`` on
  web_status and the serving ModelServer); ``resilience.stats`` is
  a thin shim over it, so every PR-1 counter is scrapeable;
* :mod:`.attribution` — one record per dispatch of the fused step
  (``step`` span and children on the profiler's clock,
  ``block_until_ready`` device time, the host's split, gc seconds)
  + ``cost_analysis()`` FLOPs → a live MFU gauge (heartbeat ``perf``
  section, web_status row), and the ``--xprof DIR`` capture window,
  which :mod:`.profile` reduces and prints;
* :mod:`.startup` — one record per program the process compiles
  (JAX's own trace, lowering and compile events: seconds, cache hit
  or miss, the span it fell inside) and the set-up spans
  (``launcher.initialize``, ``step.build``), on the dispatch
  records' clock;
* :mod:`.programs` — the scope table of each compiled step program:
  instruction name → (phase, unit, inner scope), read back from the
  ``jax.named_scope``s the step is traced under.

Tracing defaults OFF and compiles to a near-zero no-op; metrics are
passive counters; attribution adds one host sync per dispatched
block (``root.common.observability.attribution=False`` disables).
"""

from . import metrics, tracing, startup, attribution  # noqa: F401


def init_parser(parser):
    """Observability flags, aggregated into the velescli parser."""
    parser.add_argument(
        "--trace-out", default=None, metavar="FILE",
        help="enable span tracing and write a Chrome trace-event "
             "JSON (chrome://tracing / Perfetto) here at exit; "
             "worker spans ride the job protocol back to the "
             "master and land on one aligned timeline")
    parser.add_argument(
        "--trace-ring", type=int, default=None, metavar="N",
        help="bounded span-collector size (default 16384 spans; "
             "oldest dropped first)")
    parser.add_argument(
        "--xprof", default=None, metavar="DIR",
        help="open a jax.profiler capture window around the next "
             "--xprof-steps fused step dispatches, write the "
             "trace into DIR (inspect with tensorboard/xprof) and "
             "print device seconds by phase and unit, the kernels "
             "and the idle gaps when the window closes")
    parser.add_argument(
        "--xprof-steps", type=int, default=4, metavar="N",
        help="fused dispatches inside the --xprof capture window "
             "(default 4)")
