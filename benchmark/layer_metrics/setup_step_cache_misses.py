"""``setup_step_cache_misses``: step programs compiled before the window
whose compile record says ``cache == "miss"``: not found in the
persistent cache, compiled and written (0 on a warm start, 1 on a
checkout's first run)."""

from benchmark.layer_metrics import startup


def read(record, name):
    return startup.part(record, "misses")
