"""``moe_experts_roofline``: the expert layers' grouped products' share
of their roofline.  Least time: for the assignments that LANDED (the
program's own count, ``moe_landed_pct``'s source) the larger of needed
FLOPs over the bf16 peak and needed bytes over the HBM peak, forward +
backward (the family's ``expert_products_cost``; the same work whatever
implements the product), times the layer-ticks of the traced
dispatches.  Device time: self time of the instructions under the inner
scope ``moe_experts``, all phases — the recompute's time is counted,
its work is not, as for the flash kernels."""

from benchmark.layer_metrics import scoped
from benchmark.layer_metrics.moe_landed_pct import counted


def read(record, name):
    moe, found = counted(record), scoped.placed_seconds(record)
    if not moe or found is None:
        return None
    spent = sum(s for (_phase, _unit, inner), s in found[0].items()
                if inner == "moe_experts")
    calls = moe["products_per_dispatch"] * \
        record["trace"].get("dispatches", 0)
    if not spent or not calls:
        return None
    need, peaks = moe["products"], record["peaks"]
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * calls / spent
