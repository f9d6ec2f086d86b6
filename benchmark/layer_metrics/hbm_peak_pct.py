"""``hbm_peak_pct.<kind>``: ``memory_stats()["peak_bytes_in_use"]`` of
the fullest device, read when the window closed, over its HBM from the
peaks table."""


def read(record, name):
    peaks, peak = record.get("peaks"), record.get("memory_peak_bytes")
    if not peaks or peak is None:
        return None
    return 100.0 * peak / peaks["hbm_bytes"]
