"""``moe_share_pct.<kind>``: device time under the expert layer's four
inner scopes (``moe_route``: router, top-k, ordering; ``moe_dispatch``;
``moe_experts``: the grouped products and the gate; ``moe_combine``),
all phases, over the busy seconds of the traced stretch."""

from benchmark.layer_metrics import scoped

SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        inner in SCOPES)
