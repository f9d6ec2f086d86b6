"""``moe_max_load_frac``: the fullest held expert's share of what
landed on its layer, the largest over the expert layers
(``moe.max_load_frac`` of the last whole epoch; 1 / held where the
routing is even, 1 where one expert takes all)."""

from benchmark.layer_metrics.moe_landed_pct import counted


def read(record, name):
    moe = counted(record)
    return moe["max_load_frac"] if moe else None
