"""``moe_route_share_pct.<kind>``: device time of the expert layer
that is NOT an expert product — the inner scopes ``moe_route``,
``moe_dispatch`` and ``moe_combine`` (router, top-k, ordering, gather,
weighted scatter-add), all phases, over the busy seconds of the traced
stretch."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner: inner in
                        ("moe_route", "moe_dispatch", "moe_combine"))
