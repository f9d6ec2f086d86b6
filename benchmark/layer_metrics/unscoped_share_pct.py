"""``unscoped_share_pct.<kind>``: device time of instructions to which
the program's scope table gives no phase, or which it does not hold
(scan bookkeeping, copies and layout changes the compiler made with no
``op_name``), over the busy seconds of the traced stretch.  With the
four phase shares it adds up to 100."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.share(record, lambda phase, unit, inner:
                        phase is None)
