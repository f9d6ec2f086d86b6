"""``enqueue_ms.<kind>``: mean host time a dispatch from the upload of
the stacked block to the return of the jitted call (the program's
``step.upload`` and ``step.enqueue`` spans: ``upload_s + enqueue_s`` of
``attribution.recent()``), over the window's dispatches."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.mean_ms(record, "upload_s", "enqueue_s")
