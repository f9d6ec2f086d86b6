"""The comparison that decides ``correct``: each number compared has a
limit of its own (from the cell's workload file), and every run prints
each beside its limit."""

import statistics


def check(name, value, limit):
    """One compared number: ok when it is finite and within its limit."""
    ok = value is not None and value == value and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def worst_leaf_gap(program, reference):
    """The worst leaf's gap between the program's norm and the
    reference's (the gap of the norms, not the norm of a difference),
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger.  Returns (gap, leaf)."""
    median = statistics.median(reference.values())
    worst, where = 0.0, None
    for leaf, ref in reference.items():
        gap = abs(program[leaf] - ref) / max(ref, median, 1e-30)
        if gap != gap:           # a NaN is the worst there is
            return gap, leaf
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def direction_gaps(program, reference):
    """Per leaf, the relative difference between the program's and the
    reference's seeded samples of one state, ||a - b|| / ||b||, sorted
    (a NaN last).  Unlike a gap of norms this is of first order in
    rounding noise, so it is what tells one matmul precision from the
    next."""
    import numpy
    gaps = []
    for leaf, ref in reference.items():
        norm = float(numpy.linalg.norm(ref))
        if norm > 0.0:
            gaps.append((float(numpy.linalg.norm(program[leaf] - ref)) /
                         norm, leaf))
    gaps.sort(key=lambda g: (g[0] != g[0], g[0]))
    return gaps


def direction_gap(program, reference):
    """The MEDIAN leaf of ``direction_gaps``, which keeps the number
    steady from seed to seed (a NaN anywhere is returned instead).
    Returns (gap, leaf)."""
    gaps = direction_gaps(program, reference)
    if gaps[-1][0] != gaps[-1][0]:
        return gaps[-1]
    return gaps[len(gaps) // 2]


def train_checks(program, reference, limits):
    """Loss of the first dispatch, the momentum state after it (the
    gradients as the optimizer got them) by the worst leaf's norm and by
    the median leaf's direction, and the parameters' change, each
    against its limit."""
    out = [check("loss_gap",
                 abs(program["loss"] - reference["loss"]) /
                 abs(reference["loss"]), limits["loss_gap"])]
    for key in ("velocity", "change"):
        gap, leaf = worst_leaf_gap(program[key], reference[key])
        entry = check(key + "_gap", gap, limits[key + "_gap"])
        entry["leaf"] = leaf
        out.append(entry)
    gaps = direction_gaps(program["velocity_sample"],
                          reference["velocity_sample"])
    gap, leaf = direction_gap(program["velocity_sample"],
                              reference["velocity_sample"])
    entry = check("direction_gap", gap, limits["direction_gap"])
    entry["leaf"] = leaf
    # beside it, not compared: the worst of the leaves whose gradient
    # is not nought to rounding in the reference (a key's bias under
    # softmax reads thousands; small leaves swing)
    floor = 1e-3 * statistics.median(reference["velocity"].values())
    entry["worst"], entry["worst_leaf"] = [
        g for g in gaps if reference["velocity"][g[1]] >= floor][-1]
    out.append(entry)
    return out
