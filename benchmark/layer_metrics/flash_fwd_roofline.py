"""``flash_fwd_roofline``: the flash forward kernel's share of its
roofline (``scoped.kernel_roofline``: time of both calls a block, the
remat's second included; needed work of one)."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.kernel_roofline(record, "flash_fwd", "fwd")
