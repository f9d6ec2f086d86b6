"""``flash_full_roofline``: as ``flash_window_roofline``, for the FULL
causal layers (every key up to a row's own: S (S + 1) / 2 pairs a
head)."""

from benchmark.layer_metrics.flash_window_roofline import roofline


def read(record, name):
    return roofline(record, "full")
