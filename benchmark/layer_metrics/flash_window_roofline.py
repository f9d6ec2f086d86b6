"""``flash_window_roofline``: the flash kernels' share of their roofline
in the SLIDING-WINDOW layers.  Least time: what the family's
``flash_call_cost(...)["window"]`` says the three kernels need for one
layer and one tick — the visible (row, key) pairs only, the larger of
FLOPs over the bf16 peak and bytes over the HBM peak, kernel by kernel —
times the layer-ticks of the traced dispatches.  Device time: self time
of the ``tpu_custom_call`` events named ``flash_fwd`` / ``flash_dq`` /
``flash_dkv`` (every chunk pair's call) whose instruction the program's
scope table places in one of those layers' units.  The partials' merges
are XLA's and are not in it.  Returns nothing where the trace, the scope
table or the family's cost by kind is absent."""

from benchmark.layer_metrics import scoped
from benchmark.layer_metrics.flash_roofline import KERNEL_MARK

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
KINDS = ("window", "full")


def kind_cost(record, kind):
    """The family's needed work for a layer of ``kind``, with the
    ``units`` and the number of ``layers`` of that kind, or None where
    the family's yardstick is not split by kind."""
    flash = (record.get("yardstick") or {}).get("flash") or {}
    need = flash.get(kind)
    return need if isinstance(need, dict) and "units" in need else None


def kernel_seconds_in(record, units):
    """Self seconds of the flash kernels the scope table places in
    ``units``, or None without a trace or a table."""
    trace = record.get("trace")
    scopes = scoped._program("programs", "scopes")
    if not trace or scopes is None:
        return None
    table = scopes(scoped.PROGRAM)
    if not table:
        return None
    spent = 0.0
    for op, seconds in trace["kernel_seconds"].items():
        name = op.split(" ", 1)[0].lstrip("%")
        if KERNEL_MARK in op and name.rsplit(".", 1)[0] in KERNELS and \
                (table.get(name) or (None, None))[1] in units:
            spent += seconds
    return spent


def roofline(record, kind):
    need, peaks = kind_cost(record, kind), record.get("peaks")
    if need is None or not peaks or not need["layers"]:
        return None
    spent = kernel_seconds_in(record, set(need["units"]))
    layers = sum((kind_cost(record, k) or {"layers": 0})["layers"]
                 for k in KINDS)
    calls = record["yardstick"]["flash_calls_per_dispatch"] * \
        need["layers"] / layers * record["trace"].get("dispatches", 0) \
        if spent else 0
    if not spent or not calls:
        return None
    least = sum(max(need[k]["flops"] / peaks["bf16_flops_per_s"],
                    need[k]["bytes"] / peaks["hbm_bytes_per_s"])
                for k in ("fwd", "dq", "dkv"))
    return 100.0 * least * calls / spent


def read(record, name):
    return roofline(record, "window")
