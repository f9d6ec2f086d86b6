"""Loss evaluator units.

Reconstructed znicz capability surface (BASELINE.json: softmax/MSE
evaluators).  The evaluator closes the forward chain: it computes the
scalar loss (``ctx.set_loss`` → differentiated by the fused step) and
the batch metrics (error count, loss) that the Decision unit consumes.

The reference's evaluators emitted ``err_output`` to seed hand-written
backprop; with autodiff that plumbing disappears — the loss IS the
backward seed.  Partial (padded) minibatches are handled with the
loader's mask (see loader/base.py docstring).
"""

import numpy

from ..accelerated_units import TracedUnit
from ..memory import Vector


class EvaluatorBase(TracedUnit):
    """Common evaluator machinery, including the ON-DEVICE epoch
    accumulator: per-tick metrics are added into ``epoch_acc`` —
    a (3 classes × 4) array of [err_sum, n_valid, loss_sum, n_ticks] —
    inside the fused step, so the host only syncs at epoch boundaries
    (one transfer per class-epoch instead of one per tick; essential
    when the TPU is reached over a high-latency link)."""

    hide_from_registry = True

    scope_name = "evaluator"

    ACC_ERR, ACC_VALID, ACC_LOSS, ACC_TICKS = range(4)

    #: health_acc columns: per-class [non-finite ticks, grad-norm sum
    #: (finite ticks only), grad-norm max, ticks observed].  Written
    #: by the fused step (StepCompiler health sentinel), fetched by
    #: the Decision with the ordinary epoch accumulator — no extra
    #: host syncs.
    HEALTH_NONFINITE, HEALTH_GNORM_SUM, HEALTH_GNORM_MAX, \
        HEALTH_TICKS = range(4)

    def __init__(self, workflow, **kwargs):
        super(EvaluatorBase, self).__init__(workflow, **kwargs)
        self.view_group = "EVALUATOR"
        self.input = None        # linked: last layer's output/logits
        self.mask = None         # linked: loader.minibatch_mask
        self.minibatch_class_vec = None  # linked from loader
        self.epoch_acc = Vector(numpy.zeros((3, 4),
                                            dtype=numpy.float32))
        # Kahan carry for compensated epoch sums (precision_level>=1;
        # the reference's levels 1/2 were compensated/multipartial
        # summation in its OpenCL kernels, config.py:244-247).
        self.epoch_acc_c = Vector(numpy.zeros((3, 4),
                                              dtype=numpy.float32))
        self.health_acc = Vector(numpy.zeros((3, 4),
                                             dtype=numpy.float32))
        self.demand("input")

    @staticmethod
    def _compensated():
        from ..config import root, get as config_get
        return config_get(root.common.engine.precision_level, 0) >= 1

    @property
    def tstate(self):
        state = {"epoch_acc": self.epoch_acc}
        health = getattr(self, "health_acc", None)
        if health is None:  # evaluator from a pre-guardian snapshot
            health = Vector(numpy.zeros((3, 4),
                                        dtype=numpy.float32))
            self.health_acc = health
        state["health_acc"] = health
        if self._compensated():
            acc_c = getattr(self, "epoch_acc_c", None)
            if acc_c is None:  # evaluator from a pre-Kahan snapshot
                acc_c = Vector(numpy.zeros((3, 4),
                                           dtype=numpy.float32))
                self.epoch_acc_c = acc_c
            state["epoch_acc_c"] = acc_c
        return state

    def _accumulate(self, read, state, err_sum, n_valid, loss):
        import jax.numpy as jnp
        if state is None:  # eager (per-unit) execution: no accumulator
            return None
        cls = read(self.minibatch_class_vec)
        # Padded block ticks (all-zero mask) must not count: gate the
        # whole row, including the tick counter, by validity.
        valid = (n_valid > 0).astype(jnp.float32)
        row = jnp.stack([err_sum, n_valid, loss * valid, valid])
        if "epoch_acc_c" in state:
            # Kahan step: the carry row absorbs the low-order bits a
            # plain f32 add would drop over a long epoch.
            acc = state["epoch_acc"][cls]
            carry = state["epoch_acc_c"][cls]
            y = row - carry
            t = acc + y
            new_carry = (t - acc) - y
            return {"epoch_acc": state["epoch_acc"].at[cls].set(t),
                    "epoch_acc_c":
                        state["epoch_acc_c"].at[cls].set(new_carry)}
        return {"epoch_acc":
                state["epoch_acc"].at[cls].add(row)}

    def read_epoch_acc(self, cls):
        """Host fetch of one class's accumulated row (epoch-boundary
        sync point)."""
        self.epoch_acc.map_read()
        return numpy.array(self.epoch_acc.mem[cls])

    def reset_epoch_acc(self, cls):
        self.epoch_acc.map_write()
        self.epoch_acc.mem[cls] = 0.0
        acc_c = getattr(self, "epoch_acc_c", None)  # absent in old
        if acc_c:                                   # snapshots
            acc_c.map_write()
            acc_c.mem[cls] = 0.0

    def read_health_acc(self, cls):
        """Host fetch of one class's health row (rides the same
        epoch-boundary sync as :meth:`read_epoch_acc`)."""
        health = getattr(self, "health_acc", None)
        if not health:  # pre-guardian snapshot, nothing accumulated
            return numpy.zeros(4, dtype=numpy.float32)
        health.map_read()
        return numpy.array(health.mem[cls])

    def reset_health_acc(self, cls):
        health = getattr(self, "health_acc", None)
        if health:
            health.map_write()
            health.mem[cls] = 0.0


class EvaluatorSoftmax(EvaluatorBase):
    """Masked softmax cross-entropy + error count.

    Links: ``input`` ← softmax layer's ``logits``; ``labels`` ←
    loader's ``minibatch_labels``; ``mask`` ← loader's
    ``minibatch_mask``.
    """

    def __init__(self, workflow, **kwargs):
        super(EvaluatorSoftmax, self).__init__(workflow, **kwargs)
        self.labels = None
        # Per-sample probability capture (ensemble testing / serving):
        # a (total_samples + 1, n_classes) on-device buffer scattered
        # at minibatch indices inside the step — the +1 row absorbs
        # padded lanes (their index pads with 0, which may collide
        # with a real sample).
        self.capture_outputs = False
        self.sample_indices = None
        self.capture = Vector()
        self.demand("labels", "mask", "minibatch_class_vec")

    def enable_capture(self, loader):
        """Arms probability capture; call after initialize (the
        output width comes from the allocated logits Vector).  The
        compiler picks the new state tensor up on its next
        fingerprint check."""
        self.capture_outputs = True
        self.sample_indices = loader.minibatch_indices
        width = int(self.input.shape[-1])
        self.capture.mem = numpy.zeros(
            (loader.total_samples + 1, width), dtype=numpy.float32)

    def read_capture(self):
        """Host copy of the captured per-sample probabilities
        (trash row stripped)."""
        self.capture.map_read()
        return numpy.array(self.capture.mem[:-1])

    @property
    def tstate(self):
        state = dict(super(EvaluatorSoftmax, self).tstate)
        if self.capture_outputs and self.capture:
            state["capture"] = self.capture
        return state

    def tforward(self, read, write, params, ctx, state=None):
        import jax
        import jax.numpy as jnp
        logits = read(self.input)
        labels = read(self.labels)
        mask = read(self.mask)
        n_valid = jnp.maximum(mask.sum(), 1.0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(
            logp, labels[:, None].astype(jnp.int32), axis=1)[:, 0]
        loss = (nll * mask).sum() / n_valid
        pred = jnp.argmax(logits, axis=-1)
        n_err = ((pred != labels) * mask).sum()
        ctx.set_loss(loss)
        ctx.add_metric("n_err", n_err)
        ctx.add_metric("n_valid", mask.sum())
        updates = self._accumulate(read, state, n_err, mask.sum(),
                                   loss)
        if state is not None and "capture" in state:
            idx = read(self.sample_indices).astype(jnp.int32)
            trash = state["capture"].shape[0] - 1
            safe = jnp.where(mask > 0, idx, trash)
            updates = dict(updates or {})
            updates["capture"] = state["capture"].at[safe].set(
                jnp.exp(logp) * mask[:, None])
        return updates


class EvaluatorMSE(EvaluatorBase):
    """Masked mean-squared-error against ``target``.

    Links: ``input`` ← last layer output; ``target`` ← loader's
    ``minibatch_targets`` (or data for autoencoders); ``mask``.

    ``OWNS_LOSS=False`` subclasses (EvaluatorRBM) compute the same
    metrics without claiming the step loss — used when another unit
    (e.g. the RBM's CD pseudo-loss) is the differentiated objective.
    """

    OWNS_LOSS = True

    def __init__(self, workflow, **kwargs):
        super(EvaluatorMSE, self).__init__(workflow, **kwargs)
        self.target = None
        # Autoencoder fallback: when ``target`` stays unallocated
        # (loader serves no targets), reconstruct ``fallback_target``
        # (usually the input data) instead.
        self.fallback_target = None
        self.root_metric = kwargs.get("root", True)
        self.demand("target", "mask", "minibatch_class_vec")

    def tforward(self, read, write, params, ctx, state=None):
        import jax.numpy as jnp
        tgt = self.target
        if not tgt and self.fallback_target is not None:
            tgt = self.fallback_target
        y = read(self.input).astype(jnp.float32)
        t = read(tgt).astype(jnp.float32)
        mask = read(self.mask)
        batch = y.shape[0]
        n_valid = jnp.maximum(mask.sum(), 1.0)
        se = ((y.reshape(batch, -1) - t.reshape(batch, -1)) ** 2
              ).sum(axis=1)
        loss = (se * mask).sum() / n_valid
        if self.OWNS_LOSS:
            ctx.set_loss(loss)
        metric = jnp.sqrt(loss) if self.root_metric else loss
        ctx.add_metric("mse", metric)
        ctx.add_metric("n_valid", mask.sum())
        # err_sum column carries the summed squared error so the
        # decision can report per-epoch MSE.
        return self._accumulate(read, state, (se * mask).sum(),
                                mask.sum(), loss)
