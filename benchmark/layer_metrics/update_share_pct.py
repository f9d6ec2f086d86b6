"""``update_share_pct.<kind>``: device time of the optimizer's update
rules and of the health sentinel with the global gradient norm (scopes
``update`` and ``health``, phase ``update``) over the busy seconds of
the traced stretch."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        phase == "update")
