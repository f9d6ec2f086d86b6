"""``host_gc_ms.<kind>``: host time of garbage collections that fell
inside the window's dispatches (the program's ``gc.callbacks`` hook:
``gc_s`` of ``attribution.recent()``), summed over the window."""

from benchmark.layer_metrics import scoped


def read(record, name):
    rows = scoped.window_dispatches(record)
    if rows is None:
        return None
    return 1e3 * sum(r["gc_s"] for r in rows)
