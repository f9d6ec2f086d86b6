"""``gated_delta_roofline``: the gated delta rule's share of its
roofline.  Least time: what the family's ``gated_delta_cost`` says the
rule NEEDS for one linear-attention layer and one tick, forward +
backward — the larger of the recurrence's own FLOPs (18 · Dk · Dv a row
and a value head) over the bf16 peak and of q, k, v, g, beta and o moved
once forward and, as cotangents, once backward over the HBM peak: a true
lower bound, whatever implements the rule — times the layer-ticks of the
traced dispatches.  Device time: self time of the instructions under the
inner scope ``gated_delta``, all phases (the recompute's time counted,
its work not, as for the flash kernels).  By SCOPE and not by kernel
name: an XLA formulation and a Pallas kernel read the same work.
Nothing where the trace, the scope or the family's cost is absent."""

from benchmark.layer_metrics import scoped


def needed(record):
    """The family's needed work for the rule (with ``units``,
    ``layers`` and ``calls_per_dispatch``), or None."""
    need = (record["counters"].get("attention") or {}).get("gated_delta")
    return need if isinstance(need, dict) and "units" in need else None


def read(record, name):
    need, found = needed(record), scoped.placed_seconds(record)
    peaks = record.get("peaks")
    if need is None or found is None or not peaks:
        return None
    spent = sum(s for (_phase, _unit, inner), s in found[0].items()
                if inner == "gated_delta")
    calls = need["calls_per_dispatch"] * \
        record["trace"].get("dispatches", 0)
    if not spent or not calls:
        return None
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * calls / spent
