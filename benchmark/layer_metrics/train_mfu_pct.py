"""``train_mfu_pct``: the whole step's share of the chip's bf16 peak.
Required FLOPs of forward + backward an item (from shapes, the family's
``train_flops_per_item``: causal attention counted as the half that is
needed, recomputation not counted) x items of the window / (window wall
x chips x peak)."""


def read(record, name):
    peaks = record.get("peaks")
    if not peaks:
        return None
    window = record["window"]
    needed = record["yardstick"]["flops_per_item"] * window["items"]
    return 100.0 * needed / (window["seconds"] * record["chips"] *
                             peaks["bf16_flops_per_s"])
