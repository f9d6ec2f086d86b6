"""``dispatch_late_ms.<kind>``: how late the window's latest dispatch
was — over the window's records of ``attribution.recent()`` but the
first, the longest period (``t1[i] - t1[i - 1]``: the dispatch AND the
gap before it) minus the median period, in milliseconds."""

from benchmark.layer_metrics import startup


def read(record, name):
    return startup.late_ms(record)
