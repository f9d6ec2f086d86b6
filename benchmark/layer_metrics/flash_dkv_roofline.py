"""``flash_dkv_roofline``: the flash dk/dv kernel's share of its
roofline (``scoped.kernel_roofline``)."""

from benchmark.layer_metrics import scoped


def read(record, name):
    return scoped.kernel_roofline(record, "flash_dkv", "dkv")
