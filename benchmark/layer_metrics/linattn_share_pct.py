"""``linattn_share_pct.<kind>``: device time under the inner scope
``gated_delta`` — the gated delta rule alone (decays, the chunk
products, the triangular inverse, the scan that carries the state, the
outputs; not the projections, the taps, the gates or the gated norm) —
all phases, over the busy seconds of the traced stretch.  Nothing where
the program opens no such scope."""

from benchmark.layer_metrics import scoped


def read(record, name):
    found = scoped.share(record, lambda phase, unit, inner:
                         inner == "gated_delta")
    return found or None
