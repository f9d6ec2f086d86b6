"""``flash_roofline``: the flash-attention kernels' share of their
roofline.  Device time: summed self time of the forward, dq and dk/dv
kernel events of the traced stretch.  Least time: for each call the
larger of needed FLOPs over the bf16 peak and needed bytes over the HBM
peak (the family's ``flash_call_cost``: causal half counted, the
backward's recomputed QK^T not counted), times the calls that ran.
Returns nothing where the trace holds no such kernel."""

#: How the flash kernels show in the device trace today (no
#: ``pallas_call(name=...)`` in the program yet): the only Mosaic
#: custom calls of the train step (``%jvp__.30 = ... custom-call(...),
#: custom_call_target="tpu_custom_call"``).  ``ConcatBitcast`` custom
#: calls and ``kind=kCustom`` fusions are XLA's own and are not these.
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def kernel_seconds(trace):
    return sum(s for op, s in trace["kernel_seconds"].items()
               if KERNEL_MARK in op)


def least_seconds(record):
    peaks, yard = record["peaks"], record["yardstick"]
    per_call = 0.0
    for cost in yard["flash"].values():
        per_call += max(cost["flops"] / peaks["bf16_flops_per_s"],
                        cost["bytes"] / peaks["hbm_bytes_per_s"])
    return per_call


def read(record, name):
    trace = record.get("trace")
    if not trace or not record.get("peaks"):
        return None
    spent = kernel_seconds(trace)
    if not spent:
        return None
    calls = record["yardstick"]["flash_calls_per_dispatch"] * \
        trace.get("dispatches", 0)
    if not calls:
        return None
    return 100.0 * least_seconds(record) * calls / spent
