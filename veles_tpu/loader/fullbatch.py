"""Device-resident full-batch loader.

Capability parity with the reference fullbatch loader (reference:
veles/loader/fullbatch.py — ``FullBatchLoader:79``, on-device originals
``_gpu_init:197``, on-device index gather ``fill_indices:292`` backed by
the ocl/fullbatch_loader.cl / cuda/fullbatch_loader.cu kernels):
the ENTIRE dataset lives in device memory and each minibatch is
assembled on-device by gathering rows for the served indices.

TPU-era mapping: the originals are jax.Arrays in HBM (sharding-aware —
on a mesh they can be replicated or sharded along the data axis) and
the gather is ``jnp.take`` traced INTO the fused step, so XLA fuses
minibatch assembly with the first layer's compute; no custom gather
kernel and no host round-trip.  The indices + mask are the only
per-tick host→device traffic (a few hundred bytes).
"""

import numpy

from ..accelerated_units import TracedUnit
from ..memory import Vector
from .base import Loader, TRAIN, VALID, TEST  # noqa: F401


class FullBatchLoader(Loader, TracedUnit):
    """Keeps originals on device; gathers minibatches in-step
    (reference: fullbatch.py:79)."""

    hide_from_registry = True

    scope_name = "loader"

    def __init__(self, workflow, **kwargs):
        super(FullBatchLoader, self).__init__(workflow, **kwargs)
        self.original_data = Vector()
        self.original_labels = Vector()
        self.original_targets = Vector()
        self.minibatch_data = Vector()
        self.minibatch_labels = Vector()
        self.minibatch_targets = Vector()
        self.normalizer = kwargs.get("normalizer")
        self.validation_ratio = kwargs.get("validation_ratio", 0.0)

    # -- ILoader -----------------------------------------------------------

    def create_minibatch_data(self):
        """Allocates minibatch output shells (shapes drive downstream
        layer initialization; contents are produced in-step)."""
        mb = self.max_minibatch_size
        sample_shape = self.original_data.shape[1:]
        self.minibatch_data.mem = numpy.zeros(
            (mb,) + tuple(sample_shape),
            dtype=self.original_data.dtype)
        if self.original_labels:
            self.minibatch_labels.mem = numpy.zeros(
                mb, dtype=numpy.int32)
        if self.original_targets:
            self.minibatch_targets.mem = numpy.zeros(
                (mb,) + tuple(self.original_targets.shape[1:]),
                dtype=self.original_targets.dtype)

    def dataset_labels(self):
        """Class-sliced views of the resident labels (originals are
        stored [test, validation, train] concatenated)."""
        if not self.original_labels:
            return None
        return self.slice_labels_by_class(self.original_labels.mem)

    def resplit_validation(self):
        """Moves a ratio of train samples into the validation class
        (reference: fullbatch.py:349 ``validation_ratio`` resplit)."""
        if not self.validation_ratio:
            return
        take = int(self.class_lengths[TRAIN] * self.validation_ratio)
        self.class_lengths[VALID] += take
        self.class_lengths[TRAIN] -= take

    def initialize(self, **kwargs):
        super(FullBatchLoader, self).initialize(**kwargs)
        # Upload originals once (lazy: first devmem access).
        for vec in (self.original_data, self.original_labels,
                    self.original_targets):
            if vec:
                vec.initialize(self.device)

    # -- fused-step contract -----------------------------------------------

    def step_batch_vectors(self):
        """Per-tick host→device inputs."""
        return [self.minibatch_indices, self.minibatch_mask,
                self.minibatch_class_vec]

    def step_const_vectors(self):
        """Large device-resident constants passed (not donated) to the
        step."""
        consts = [self.original_data]
        if self.original_labels:
            consts.append(self.original_labels)
        if self.original_targets:
            consts.append(self.original_targets)
        return consts

    def tforward(self, read, write, params, ctx, state=None):
        """On-device minibatch gather (replaces
        ocl/fullbatch_loader.cl)."""
        import jax.numpy as jnp
        idx = read(self.minibatch_indices)
        data = jnp.take(read(self.original_data), idx, axis=0)
        write(self.minibatch_data, data)
        if self.original_labels:
            write(self.minibatch_labels,
                  jnp.take(read(self.original_labels), idx, axis=0))
        if self.original_targets:
            write(self.minibatch_targets,
                  jnp.take(read(self.original_targets), idx, axis=0))

    def run(self):
        """Host part of the tick: serve indices, then trigger the fused
        step (which performs the gather + everything downstream).  In
        block mode, serves a whole same-class block of minibatches and
        dispatches one scanned computation."""
        wf = self.workflow
        ticks = getattr(wf, "ticks_per_dispatch", 1)
        if ticks > 1 and getattr(wf, "fused", False):
            from ..observability import attribution
            # One ``step`` span a dispatch, from the host's index
            # serving to the device's last output
            # (docs/observability.md); the compiler joins it.
            with attribution.dispatch(ticks=ticks) as step:
                with step.serve():
                    blocks = self.serve_block(ticks)
                wf.begin_tick()
                wf.execute_block(blocks)
            return
        self.serve_next_minibatch()
        if wf is not None and hasattr(wf, "begin_tick"):
            wf.begin_tick()
        TracedUnit.run(self)
