"""``attn_share_pct.<kind>``: device time under the inner scope
``attention`` (q, k, v -> o: scores, softmax and value matmul, or the
flash kernels; not the projections), all phases, over the busy seconds
of the traced stretch."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        inner == "attention")
