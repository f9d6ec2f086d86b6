"""``flash_bd_roofline``: the flash kernels' share of their roofline in
the BLOCK-DIFFUSION layers (two streams of ``L`` rows in one call, the
mask judged in the kernel).  Least time: what the family's
``flash_call_cost(...)["block_diffusion"]`` says the three kernels need
for one layer and one tick — the mask's visible (row, key) pairs only,
``L² + L · B`` a head, 2 needed matmuls a kernel, the larger of FLOPs
over the bf16 peak and bytes over the HBM peak, kernel by kernel — times
the layer-ticks of the traced dispatches.  Device time: self time of the
``tpu_custom_call`` events named ``flash_fwd`` / ``flash_dq`` /
``flash_dkv`` that the program's scope table places in one of those
layers' units.  Nothing where the trace, the scope table or the family's
cost of that kind is absent.

The arithmetic is ``flash_window_roofline.roofline``'s, handed the
block-diffusion cost as its one kind, as ``flash_mla_roofline`` hands it
the latent one."""

from benchmark.layer_metrics.flash_window_roofline import (
    kind_cost, roofline)


def read(record, name):
    need = kind_cost(record, "block_diffusion")
    if need is None:
        return None
    yardstick = dict(record["yardstick"], flash={"full": need})
    return roofline(dict(record, yardstick=yardstick), "full")
