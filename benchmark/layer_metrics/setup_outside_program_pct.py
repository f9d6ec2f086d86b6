"""``setup_outside_program_pct``: per cent of ``setup_s`` that the
program's own timeline does not hold — 100 x (1 - (``setup_init_s`` +
``setup_trace_s`` + ``setup_compile_s`` + ``setup_dispatch_s``) /
``setup_s``): interpreter, JAX and TPU start, and the benchmark's own
weights, reading of state and helper programs."""

from benchmark.layer_metrics import startup


def read(record, name):
    found = startup.parts(record)
    setup_s = record["end_to_end"].get("setup_s")
    if found is None or not setup_s:
        return None
    inside = sum(found[k] for k in ("init", "trace", "compile", "dispatch"))
    return 100.0 * (1.0 - inside / setup_s)
