"""``remat_share_pct.<kind>``: device time of the forward pass run a
second time inside the backward pass (phase ``recompute``: what
``jax.checkpoint`` marks ``rematted_computation``) over the busy
seconds of the traced stretch."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        phase == "recompute")
