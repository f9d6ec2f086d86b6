"""``setup_compile_s``: seconds of the step programs' backend compiles
before the window, or of the loads of their cached executables
(``compile_s`` of the step programs' records in
``veles_tpu.observability.startup.compiles()``: cache key, retrieval,
deserialisation, Mosaic kernels compiled again at the load)."""

from benchmark.layer_metrics import startup


def read(record, name):
    return startup.part(record, "compile")
