"""``fwd_share_pct.<kind>``: device time of the first forward pass (phase
``forward`` of the program's scope table) over the busy seconds of the
traced stretch."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        phase == "forward")
