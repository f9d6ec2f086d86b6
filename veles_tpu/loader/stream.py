"""Streamed (non-HBM-resident) minibatch loader.

Capability parity with the reference's directory-scale image streaming
(reference: veles/loader/fullbatch_image.py:56-268 +
veles/loader/image.py:106 — datasets far larger than device memory are
decoded minibatch-by-minibatch on the host), redesigned for the fused
TPU step:

* the dataset stays on disk / in host memory — nothing resident in
  HBM beyond the in-flight blocks;
* a host-side worker pool (:class:`concurrent.futures.ThreadPoolExecutor`)
  materializes (decodes / augments / normalizes) each block of K
  minibatch ticks into staging numpy buffers;
* blocks ride ``jax.device_put`` which is **asynchronous**: the upload
  of block K+1 overlaps the device compute of block K, and because the
  fused dispatch itself is asynchronous, the host decode of block K+1
  also overlaps device compute of block K — double buffering with one
  block of lookahead and no extra threads in the control path.

The epoch walk therefore runs one block AHEAD of what the rest of the
graph observes.  Flag publication is split: the inherited serve
machinery advances the *walk* (private), and :meth:`run` publishes the
flags describing the block it actually DISPATCHED, so the decision
unit, heartbeats, and snapshots see truthful epoch accounting.

Distributed parity: the coordinator still serves only indices
(reference: loader/base.py:629-661); a streamed worker materializes
its assigned indices locally in :meth:`apply_data_from_master`.
"""

import os

import numpy

from ..accelerated_units import TracedUnit
from ..error import BadFormatError
from ..memory import Vector
from .base import Loader, TRAIN, VALID, TEST  # noqa: F401


class StreamLoader(Loader, TracedUnit):
    """Serves minibatch *data* from host each tick (contrast
    :class:`..fullbatch.FullBatchLoader`, which keeps originals in HBM
    and gathers in-step).

    Subclasses implement :meth:`materialize` (one sample) or override
    :meth:`fill_rows` (a batch of samples — vectorize when the source
    allows it), and ``load_data`` must set :attr:`sample_shape` /
    :attr:`sample_dtype` in addition to ``class_lengths``.

    kwargs: ``decode_workers`` — host decode pool size (default:
    ``os.cpu_count()``); ``prefetch`` — one-block lookahead on
    (default True; turn off for strictly synchronous debugging).
    """

    hide_from_registry = True

    scope_name = "loader"

    #: Published epoch_number of the dispatched block (class-level
    #: default so the property works before/without publication).
    _pub_ = None
    _serving_ = False

    def __init__(self, workflow, **kwargs):
        super(StreamLoader, self).__init__(workflow, **kwargs)
        self.minibatch_data = Vector()
        self.minibatch_labels = Vector()
        self.decode_workers = int(kwargs.get(
            "decode_workers", os.cpu_count() or 4))
        self.prefetch = bool(kwargs.get("prefetch", True))
        self.sample_shape = None
        self.sample_dtype = numpy.float32

    def init_unpickled(self):
        super(StreamLoader, self).init_unpickled()
        self._staged_ = None
        self._pool_ = None
        self._pub_ = None
        self._serving_ = False

    # -- walk/published epoch split ----------------------------------------
    # serve_* both reads and writes epoch_number (the ``+= 1`` at epoch
    # end, the shuffle-limit check), so the walk's value must stay
    # private while the published value describes the dispatched
    # block.  The other flags are write-before-read per serve and are
    # simply re-assigned at publication time.

    @property
    def epoch_number(self):
        if not self._serving_ and self._pub_ is not None:
            return self._pub_["epoch_number"]
        return self._w_epoch_number

    @epoch_number.setter
    def epoch_number(self, value):
        self._w_epoch_number = value

    # -- ILoader ------------------------------------------------------------

    def create_minibatch_data(self):
        if self.sample_shape is None:
            raise BadFormatError(
                "%s.load_data must set sample_shape" % self)
        mb = self.max_minibatch_size
        self.minibatch_data.mem = numpy.zeros(
            (mb,) + tuple(self.sample_shape), dtype=self.sample_dtype)
        self.minibatch_labels.mem = numpy.zeros(mb, dtype=numpy.int32)

    def fill_minibatch(self):
        self._fill_current()

    # -- materialization hooks ----------------------------------------------

    def materialize(self, index):
        """Returns (sample_array, label) for one global index."""
        raise NotImplementedError()

    def fill_rows(self, indices, out_data, out_labels):
        """Materializes samples for 1-D global ``indices`` into
        ``out_data[i]`` / ``out_labels[i]``.  Default loops over
        :meth:`materialize`; override to vectorize (memmap fancy
        indexing, batched decode, ...)."""
        for i, gi in enumerate(indices):
            arr, lab = self.materialize(int(gi))
            out_data[i] = arr
            out_labels[i] = lab

    @property
    def pool(self):
        if self._pool_ is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool_ = ThreadPoolExecutor(
                max_workers=self.decode_workers,
                thread_name_prefix="veles-decode")
        return self._pool_

    def _fill_block(self, idxs, masks):
        """(K, mb) indices+masks → (K, mb, *sample) staging arrays,
        decode parallelized across the worker pool."""
        K, mb = idxs.shape
        data = numpy.zeros((K, mb) + tuple(self.sample_shape),
                           dtype=self.sample_dtype)
        labels = numpy.zeros((K, mb), dtype=numpy.int32)
        jobs = []
        for t in range(K):
            n = int(masks[t].sum())
            if n == 0:
                continue
            if K == 1 and self.decode_workers > 1:
                # Single-tick block: split the rows instead so the
                # pool still parallelizes the decode.
                step = max(1, -(-n // self.decode_workers))
                for lo in range(0, n, step):
                    hi = min(n, lo + step)
                    jobs.append((idxs[t][lo:hi], data[t][lo:hi],
                                 labels[t][lo:hi]))
            else:
                jobs.append((idxs[t][:n], data[t][:n], labels[t][:n]))
        if len(jobs) == 1:
            self.fill_rows(*jobs[0])
        elif jobs:
            futures = [self.pool.submit(self.fill_rows, *j)
                       for j in jobs]
            for f in futures:
                f.result()
        return data, labels

    def _fill_current(self):
        """Synchronous fill of the single current minibatch (eager
        path + worker-side materialization)."""
        if self.minibatch_size:
            data, labels = self._fill_block(
                self.minibatch_indices.mem[None, :],
                self.minibatch_mask.mem[None, :])
            data, labels = data[0], labels[0]
        else:
            mb = self.max_minibatch_size
            data = numpy.zeros((mb,) + tuple(self.sample_shape),
                               dtype=self.sample_dtype)
            labels = numpy.zeros(mb, dtype=numpy.int32)
        self.minibatch_data.mem = data
        self.minibatch_labels.mem = labels

    # -- fused-step contract -----------------------------------------------

    def step_batch_vectors(self):
        """The DATA is the per-tick host→device feed (contrast
        fullbatch: indices only)."""
        return [self.minibatch_data, self.minibatch_labels,
                self.minibatch_mask, self.minibatch_class_vec]

    def tforward(self, read, write, params, ctx, state=None):
        """Nothing traced: the minibatch tensors enter the step as
        batch inputs; downstream units read them from the bag."""

    # -- the tick ----------------------------------------------------------

    def _produce_block(self, ticks):
        """Advances the private walk by one block and stages its
        materialized tensors on device (async upload)."""
        import jax
        self._serving_ = True
        try:
            served = self.serve_block(ticks)
            flags = {
                "minibatch_class": self.minibatch_class,
                "minibatch_size": self.minibatch_size,
                "last_minibatch": self.last_minibatch,
                "epoch_ended": self.epoch_ended,
                "epoch_number": self._w_epoch_number,
            }
        finally:
            self._serving_ = False
        idxs = served[str(id(self.minibatch_indices))]
        masks = served[str(id(self.minibatch_mask))]
        cls_arr = served[str(id(self.minibatch_class_vec))]
        data, labels = self._fill_block(idxs, masks)
        blocks = {
            str(id(self.minibatch_data)): jax.device_put(data),
            str(id(self.minibatch_labels)): jax.device_put(labels),
            str(id(self.minibatch_mask)): jax.device_put(masks),
            str(id(self.minibatch_class_vec)): jax.device_put(cls_arr),
        }
        return {"blocks": blocks, "flags": flags,
                "in_flight": list(self._in_flight_)}

    def _apply_flags(self, flags):
        self.minibatch_class = flags["minibatch_class"]
        self.minibatch_size = flags["minibatch_size"]
        self.last_minibatch = flags["last_minibatch"]
        self.epoch_ended = flags["epoch_ended"]
        self._pub_ = flags

    def run(self):
        wf = self.workflow
        if getattr(wf, "fused", False):
            ticks = max(1, getattr(wf, "ticks_per_dispatch", 1))
            entry = self._staged_
            self._staged_ = None
            if entry is None:
                entry = self._produce_block(ticks)
            # Publish BEFORE dispatch: wf.training consults
            # minibatch_is_training for this block.
            self._apply_flags(entry["flags"])
            wf.begin_tick()
            wf.execute_block(entry["blocks"])
            if self.prefetch:
                # Stage the next block while the device crunches this
                # one; its serve tramples the flag attrs, so re-publish
                # the dispatched block's flags for the decision.
                self._staged_ = self._produce_block(ticks)
                self._apply_flags(entry["flags"])
                self._in_flight_ = (entry["in_flight"] +
                                    self._staged_["in_flight"])
            else:
                self._in_flight_ = entry["in_flight"]
            return
        # Eager fallback (debug / non-fused graphs).
        self.serve_next_minibatch()
        self._fill_current()
        if hasattr(wf, "begin_tick"):
            wf.begin_tick()
        TracedUnit.run(self)

    def invalidate_staged(self):
        """Drops the prefetched block (elastic rebuild: its device
        arrays live on the old device set and its indices were
        requeued from ``_in_flight_``)."""
        self._staged_ = None

    # -- distributed: worker materializes its assigned indices --------------

    def apply_data_from_master(self, data):
        super(StreamLoader, self).apply_data_from_master(data)
        self.minibatch_class_vec.mem = numpy.array(
            self.minibatch_class, dtype=numpy.int32)
        self._fill_current()

    # -- pickling: the staged (undispatched) block is requeued --------------

    def __getstate__(self):
        state = super(StreamLoader, self).__getstate__()
        staged = self._staged_
        if staged is not None:
            state["failed_minibatches"] = (
                list(state["failed_minibatches"]) +
                [(idx, cls) for idx, cls in staged["in_flight"]])
        return state
