"""``device_idle_pct.<kind>``: 1 - union of device-operation intervals
over the traced window, from the device trace."""


def read(record, name):
    trace = record.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
