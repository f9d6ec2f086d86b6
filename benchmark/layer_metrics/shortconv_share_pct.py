"""``shortconv_share_pct.<kind>``: device time under the inner scope
``shortconv`` (the gated short convolution's gates and taps; its in
and out projections carry no inner scope, as attention's do not), all
phases, over the busy seconds of the traced stretch."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        inner == "shortconv")
