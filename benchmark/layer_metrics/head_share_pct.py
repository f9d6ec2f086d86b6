"""``head_share_pct.<kind>``: device time of the units ``head`` and
``evaluator`` (logits over the vocabulary and their loss), all phases,
over the busy seconds of the traced stretch."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        unit in ("head", "evaluator"))
