"""``dispatch_ms.<kind>``: mean time of one dispatch over the window,
from the benchmark's own span around ``loader.run()`` to
``block_until_ready`` (host clock; a dispatch lasts seconds)."""


def read(record, name):
    spans = [s for s in record["spans"] if s["name"] == "dispatch"]
    if not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / len(spans)
