"""Shared by the readers of what the program says about itself (PR 26):
the scope table of its compiled block program
(``veles_tpu.observability.programs.scopes``: instruction name ->
(phase, unit, inner scope)) joined with the device trace's seconds by
instruction, and the one record a dispatch that
``veles_tpu.observability.attribution.recent()`` keeps.

Every function returns None, and never raises, where its source is
absent: a rehearsal, a run without a trace, a program that keeps no
scope table or no dispatch records (a parent commit from before PR 26),
a trace in which ``jit_block_step`` is not all but the whole of the
device's time.
"""

from benchmark.layer_metrics.flash_roofline import KERNEL_MARK

PROGRAM = "block_step"


def _program(module, attribute):
    try:
        found = __import__("veles_tpu.observability." + module,
                           fromlist=[attribute])
        return getattr(found, attribute)
    except (ImportError, AttributeError):
        return None


def placed_seconds(record):
    """``({(phase, unit, inner): device seconds}, busy seconds)`` of the
    traced stretch: each operation's self time under the placing the
    program's scope table gives its instruction, ``(None, None, None)``
    for an instruction without a phase or absent from the table."""
    trace = record.get("trace")
    scopes = _program("programs", "scopes")
    if not trace or not record.get("peaks") or scopes is None:
        return None
    seconds = {k: v[1] for k, v in trace["programs"].items()}
    if not seconds.get("jit_" + PROGRAM) or \
            seconds["jit_" + PROGRAM] <= 0.99 * sum(seconds.values()):
        return None
    table = scopes(PROGRAM)
    if not table or not trace["busy_s"]:
        return None
    placed = {}
    for name, spent in trace["op_seconds"].items():
        key = table.get(name.split(" ", 1)[0].lstrip("%"))
        if key is None or key[0] is None:
            key = (None, None, None)
        placed[key] = placed.get(key, 0.0) + spent
    return placed, trace["busy_s"]


def share(record, wanted):
    """Per cent of the busy seconds whose placing ``wanted(phase, unit,
    inner)`` accepts."""
    found = placed_seconds(record)
    if found is None:
        return None
    placed, busy = found
    return 100.0 * sum(s for key, s in placed.items()
                       if wanted(*key)) / busy


def kernel_roofline(record, kernel, cost):
    """A flash kernel's share of its roofline: the least time
    ``yardstick["flash"][cost]`` needs a call (the larger of FLOPs over
    the bf16 peak and bytes over the HBM peak) times the calls the
    traced dispatches NEEDED, over the self time of the
    ``tpu_custom_call`` events whose instruction is named ``kernel``
    (``pallas_call(name=...)``).  The forward's time holds the remat's
    second call and its needed work does not, as in ``flash_roofline``."""
    trace, peaks = record.get("trace"), record.get("peaks")
    if not trace or not peaks:
        return None
    spent = sum(s for op, s in trace["kernel_seconds"].items()
                if KERNEL_MARK in op and
                op.split(" ", 1)[0].lstrip("%").rsplit(".", 1)[0]
                == kernel)
    calls = record["yardstick"]["flash_calls_per_dispatch"] * \
        trace.get("dispatches", 0)
    if not spent or not calls:
        return None
    need = record["yardstick"]["flash"][cost]
    least = max(need["flops"] / peaks["bf16_flops_per_s"],
                need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least * calls / spent


def window_dispatches(record):
    """The window's dispatch records: the last
    ``record["window"]["dispatches"]`` that the program kept, or None
    where it kept fewer than the window had (it keeps 64)."""
    recent = _program("attribution", "recent")
    if recent is None or not record.get("peaks"):
        return None
    rows = [r for r in recent() if r.get("program") == PROGRAM]
    wanted = record["window"]["dispatches"]
    if not 0 < wanted <= len(rows):
        return None
    return rows[-wanted:]


def mean_ms(record, *fields):
    """Mean over the window's dispatches of the sum of ``fields``
    (seconds in the program's record), in milliseconds."""
    rows = window_dispatches(record)
    if rows is None:
        return None
    return 1e3 * sum(r[f] for r in rows for f in fields) / len(rows)
