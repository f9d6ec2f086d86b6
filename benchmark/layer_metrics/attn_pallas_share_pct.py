"""``attn_pallas_share_pct``: of the attention calls traced into this
process's programs, the share built from the Pallas flash kernel
(``attention.kernel.pallas`` / ``.xla`` trace counters of
``ops/attention.py``).  Cross-checked against the ``tpu_custom_call``
count of the compiled block program: a share above 0 with no custom
call in the program (or the reverse) is an error, not a number."""


def read(record, name):
    traces = record["counters"].get("attention")
    if not traces or not (traces["pallas"] + traces["xla"]):
        return None
    share = 100.0 * traces["pallas"] / (traces["pallas"] + traces["xla"])
    calls = record["counters"].get("tpu_custom_calls")
    if calls is not None and record.get("peaks") and \
            bool(calls) != bool(traces["pallas"]):
        raise RuntimeError("attention traces %r but %d tpu_custom_call "
                           "in the compiled program" % (traces, calls))
    return share
