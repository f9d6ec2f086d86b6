"""``moe_shared_share_pct.<kind>``: device time under the inner scope
``moe_shared`` (the shared expert's gated MLP over every token: three
matmuls and the gate; beside the routed experts' ``moe_*`` scopes, not
among them), all phases, over the busy seconds of the traced stretch."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        inner == "moe_shared")
