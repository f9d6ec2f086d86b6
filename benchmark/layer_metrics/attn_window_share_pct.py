"""``attn_window_share_pct.<kind>``: device time under the inner scope
``attention`` (q, k, v -> o: the flash kernels of every visible chunk
pair, the partials' merges, the transposes; not the projections, not
the output gate) in the units of the SLIDING-WINDOW layers, all phases,
over the busy seconds of the traced stretch.  Which units those are the
family says (``flash_call_cost(...)["window"]["units"]``);
``attn_share_pct`` beside it holds the full layers' too."""

from benchmark.layer_metrics import scoped
from benchmark.layer_metrics.flash_window_roofline import kind_cost


def read(record, name):
    need = kind_cost(record, "window")
    if need is None:
        return None
    units = set(need["units"])
    return scoped.share(record, lambda phase, unit, inner:
                        inner == "attention" and unit in units)
