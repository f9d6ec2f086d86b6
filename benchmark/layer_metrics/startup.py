"""Shared by the readers of the program's own account of its start-up
and of its dispatches' pace (PR 37): the compile record a program and
the set-up spans that ``veles_tpu.observability.startup`` keeps, and
the clock (``t0``, ``t1``), ``lower_s``, ``compile_s`` and ``build_s``
on each record of ``veles_tpu.observability.attribution.recent()``.

The cut between set-up and window needs no clock of the benchmark's:
the window's dispatches are the last ``record["window"]["dispatches"]``
``block_step`` records (as ``scoped.window_dispatches`` has it), and
everything that ended before the first of them opened is set-up.

Every function returns None, and never raises, where the program keeps
no such record: a parent commit from before PR 37, a rehearsal (no
peaks, as in ``scoped``), a window longer than the 64 records kept.
"""

import statistics

from benchmark.layer_metrics import scoped


def dispatches(record):
    """``(set-up's dispatch records, the window's)`` of the block
    program, each with its clock."""
    recent = scoped._program("attribution", "recent")
    if recent is None or not record.get("peaks"):
        return None
    rows = [r for r in recent() if r.get("program") == scoped.PROGRAM]
    wanted = record["window"]["dispatches"]
    if not 0 < wanted <= len(rows) or "t0" not in rows[-wanted]:
        return None
    return rows[:-wanted], rows[-wanted:]


def parts(record):
    """Seconds of set-up that the program's own timeline holds, by the
    four metrics that read them, and the step programs that missed the
    persistent cache::

        init      spans launcher.initialize + step.build
        trace     trace_s + lower_s of the step programs' compile
                  records (every tracing and lowering of them)
        compile   compile_s of the same records: backend compile, or
                  the load of a cached executable
        dispatch  the set-up dispatches without what compiling took of
                  them: t1 - t0 - compile_s - lower_s - build_s
        misses    of those records, the ones with cache == "miss"
    """
    found = dispatches(record)
    compiles = scoped._program("startup", "compiles")
    spans = scoped._program("startup", "spans")
    if found is None or compiles is None or spans is None:
        return None
    before, window = found
    opened = window[0]["t0"]
    steps = [r for r in compiles() if r["step"] and r["t1"] <= opened]
    return {
        "init": sum(s["seconds"] for s in spans()
                    if s["name"] in ("launcher.initialize", "step.build")
                    and s["t1"] <= opened),
        "trace": sum(r["trace_s"] + r["lower_s"] for r in steps),
        "compile": sum(r["compile_s"] for r in steps),
        "dispatch": sum(r["t1"] - r["t0"] - r["compile_s"] - r["lower_s"]
                        - r["build_s"] for r in before),
        "misses": sum(r["cache"] == "miss" for r in steps)}


def part(record, name):
    found = parts(record)
    return None if found is None else found[name]


def late_ms(record):
    """Over the window's dispatch records but the first: the longest
    period (``t1[i] - t1[i - 1]``) minus the median period, in
    milliseconds."""
    found = dispatches(record)
    if found is None or len(found[1]) < 2:
        return None
    closed = [r["t1"] for r in found[1]]
    periods = [b - a for a, b in zip(closed, closed[1:])]
    return 1e3 * (max(periods) - statistics.median(periods))
