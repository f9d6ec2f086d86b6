"""``loader_host_ms.<kind>``: mean host time a dispatch of the loader's
index serving and stacking (the program's ``loader.serve_block`` span:
``serve_s`` of ``attribution.recent()``), over the window's dispatches."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.mean_ms(record, "serve_s")
