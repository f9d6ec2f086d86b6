"""``bd_noise_share_pct.<kind>``: device time under the inner scope
``bd_noise`` — block diffusion's noising inside the step: each block's
mask rate and each position's mask drawn from the tick's key, MASK put
in, the two streams put together and the loss's weights — all phases,
over the busy seconds of the traced stretch.  Nothing where the program
opens no such scope."""

from benchmark.layer_metrics import scoped


def read(record, name):
    found = scoped.share(record, lambda phase, unit, inner:
                         inner == "bd_noise")
    return found or None
