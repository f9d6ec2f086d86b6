"""``linattn_gates_share_pct.<kind>``: device time of what stands
around the gated delta rule in the LINEAR-attention layers' units — the
inner scopes ``shortconv`` (taps and SiLU over q, k, v), ``gdn_gate``
(beta, g, the l2 norms of q and k) and ``gdn_norm`` (the gated norm a
head) — all phases, over the busy seconds of the traced stretch.  Which
units those are the family says (the trainer's ``gated_delta`` record);
nothing where it says none."""

from benchmark.layer_metrics import scoped
from benchmark.layer_metrics.gated_delta_roofline import needed

AROUND = ("shortconv", "gdn_gate", "gdn_norm")


def read(record, name):
    need = needed(record)
    if need is None:
        return None
    units = set(need["units"])
    found = scoped.share(record, lambda phase, unit, inner:
                         inner in AROUND and unit in units)
    return found or None
