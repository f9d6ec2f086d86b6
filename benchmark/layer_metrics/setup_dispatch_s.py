"""``setup_dispatch_s``: seconds of the dispatches before the window
without what compiling took of them (over those records of
``attribution.recent()``, ``t1 - t0 - compile_s - lower_s - build_s``):
the first block's run and the warm ones."""

from benchmark.layer_metrics import startup


def read(record, name):
    return startup.part(record, "dispatch")
