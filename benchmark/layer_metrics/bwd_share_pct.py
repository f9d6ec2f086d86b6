"""``bwd_share_pct.<kind>``: device time of the backward pass proper
(phase ``backward``: ``transpose(jvp(...))`` without the recomputation)
over the busy seconds of the traced stretch."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        phase == "backward")
