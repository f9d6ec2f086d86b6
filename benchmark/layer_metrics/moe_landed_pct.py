"""``moe_landed_pct``: of the assignments the routers made (tokens ×
experts a token × expert layers), the share that landed on the experts
held here — ``moe.assignments_landed`` over ``moe.assignments_made``,
the gauges ``DecisionGD`` publishes from the layers' on-device
accumulators at the end of an epoch (the last whole one of the run);
100 × held / experts where the routing is even."""


def counted(record):
    """What the trainer read of the program's ``moe.*`` gauges, or
    None where the program published none."""
    return (record["counters"].get("attention") or {}).get("moe")


def read(record, name):
    moe = counted(record)
    if not moe or not moe["assignments_made"]:
        return None
    return 100.0 * moe["assignments_landed"] / moe["assignments_made"]
