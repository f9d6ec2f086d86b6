"""``setup_init_s``: seconds of set-up inside the program's spans
``launcher.initialize`` (the units' ``initialize``, the loader's
``load_data``, the device) and ``step.build`` (``StepCompiler.compile()``:
``analyze``, the closures, their ``jax.jit`` wrappers), as
``veles_tpu.observability.startup.spans()`` keeps them, before the
window's first dispatch opened."""

from benchmark.layer_metrics import startup


def read(record, name):
    return startup.part(record, "init")
