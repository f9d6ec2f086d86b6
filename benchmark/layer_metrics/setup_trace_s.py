"""``setup_trace_s``: seconds JAX spent tracing the step programs to
jaxprs and lowering them to MLIR modules before the window (``trace_s +
lower_s`` of the step programs' records in
``veles_tpu.observability.startup.compiles()``; both lowerings, if a
program was lowered twice)."""

from benchmark.layer_metrics import startup


def read(record, name):
    return startup.part(record, "trace")
