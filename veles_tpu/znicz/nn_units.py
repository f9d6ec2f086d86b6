"""Base classes for neural-network layer units.

Reconstructed capability surface of the znicz submodule's nn_units
(the submodule is empty in the reference checkout; hooks survive in
veles/accelerated_units.py and the kernels in ocl/, cuda/):

  * :class:`ForwardBase` — a forward layer with ``input``/``output``
    Vectors and optional ``weights``/``bias`` trainables;
  * :class:`GradientDescentBase` — the per-layer trainer unit holding
    hyperparameters (learning rate, momentum, L2 decay) and momentum
    state; in the reference each GD unit implemented the hand-written
    backward kernels for its layer type, here the backward comes from
    ``jax.grad`` over the composed forward and the GD unit only
    applies its update rule inside the same jitted step.
"""

import numpy

from .. import prng
from ..accelerated_units import TracedUnit
from ..config import root, get as config_get
from ..memory import Vector
from ..registry import MappedUnitRegistry
from . import optimizers


# -- shared activation bodies (one definition for the all2all / conv /
# standalone-activation families; znicz constants) -------------------------

#: znicz scaled-tanh constants (1.7159·tanh(0.6666·x)).
TANH_A = 1.7159
TANH_B = 0.6666


def act_tanh(v):
    import jax.numpy as jnp
    return TANH_A * jnp.tanh(TANH_B * v)


def act_softplus(v):
    """znicz "RELU": log(1 + e^x)."""
    import jax
    return jax.nn.softplus(v)


def act_strict_relu(v):
    import jax.numpy as jnp
    return jnp.maximum(v, 0)


def act_sigmoid(v):
    import jax
    return jax.nn.sigmoid(v)


def _proto_of_slave(unit, slave):
    """The negotiated wire protocol for one worker session ({} =
    legacy) — shared by every unit participating in the data plane."""
    get = getattr(unit.workflow, "slave_protocol", None)
    return get(slave) if get is not None else {}


def _proto_of_net(unit):
    """This worker session's negotiated protocol ({} = legacy)."""
    return getattr(unit.workflow, "net_proto", None) or {}


class ForwardUnitRegistry(MappedUnitRegistry):
    """String → forward-layer class (the reference's MappedUnitRegistry
    role for znicz layers, unit_registry.py:178)."""
    registry = {}


class GDUnitRegistry(MappedUnitRegistry):
    """String → trainer class; same MAPPING strings as the forward
    registry, so ``gd_for(layer)`` pairs them."""
    registry = {}


def gd_for(layer_or_mapping):
    """Returns the GD unit class paired with a forward layer (by its
    MAPPING string)."""
    mapping = getattr(layer_or_mapping, "MAPPING", layer_or_mapping)
    return GDUnitRegistry.get_factory(mapping)


class ForwardBase(TracedUnit, metaclass=ForwardUnitRegistry):
    """A forward layer unit (znicz ``Forward`` analogue)."""

    hide_from_registry = True

    #: Whether this layer type owns trainable parameters — static so
    #: workflow builders can pair GD units BEFORE weights are
    #: allocated (trainables itself is dynamic, post-initialize).
    HAS_PARAMS = True

    def __init__(self, workflow, **kwargs):
        super(ForwardBase, self).__init__(workflow, **kwargs)
        self.view_group = "WORKER"
        self.input = None            # linked Vector
        self.output = Vector()
        self.weights = Vector()
        self.bias = Vector()
        self.include_bias = kwargs.get("include_bias", True)
        self.weights_stddev = kwargs.get("weights_stddev")
        self.bias_stddev = kwargs.get("bias_stddev")
        self.weights_transposed = kwargs.get("weights_transposed", False)
        self.prng_key = kwargs.get("prng_key", 0)
        self.demand("input")

    @property
    def trainables(self):
        t = {}
        if self.weights:
            t["weights"] = self.weights
        if self.include_bias and self.bias:
            t["bias"] = self.bias
        return t

    @property
    def compute_dtype(self):
        """Activation-stream dtype (see
        accelerated_units.step_compute_dtype)."""
        from ..accelerated_units import step_compute_dtype
        return step_compute_dtype()

    def rand(self):
        return prng.get(self.prng_key)

    # -- distributed contract (reference: znicz GD units shipped
    # weights in jobs and aggregated slave results centrally;
    # workflow.py:518-535 is the core contract).
    #
    # Two wire dialects, negotiated per worker in the handshake
    # (docs/distributed.md):
    #
    # * legacy (pickle-compat): full trainables both directions; the
    #   master keeps a FIFO of shipped copies per worker and folds
    #   updates as ``current + (theirs − shipped)``;
    # * delta: the WORKER computes ``theirs − shipped`` locally and
    #   returns only that, so the master's fold is a plain
    #   ``current + delta`` (bit-identical to the legacy fold — the
    #   worker subtracts the same fp32 values the master would have)
    #   and the shipped-copy FIFO disappears.  Downstream, full
    #   weights ship only at join/rebase; later jobs carry the
    #   accumulated change since that worker's last sync as a
    #   BITWISE XOR delta (exact reconstruction — an arithmetic
    #   delta would drift the worker off the master's exact values),
    #   leaving O(1) master bookkeeping per WORKER (the last synced
    #   state) instead of one full copy per in-flight job.
    # ----------------------------------------------------------------------

    def init_unpickled(self):
        super(ForwardBase, self).init_unpickled()
        self._shipped_ = {}          # legacy per-worker FIFO
        self._synced_ = {}           # delta: slave -> (version, arrays)
        self._base_ = None           # worker: last synced arrays
        self._base_version_ = None
        # Error-feedback plane for the lossy int8 wire: per-attr f32
        # quantization error of the LAST shipped delta, added back
        # into the next one before it is quantized — the master
        # eventually receives every gradient bit, just a sync late,
        # which is what keeps int8-delta training converging.
        self._residual_ = {}

    def _trainable_arrays(self):
        import numpy
        out = {}
        for attr, vec in self.trainables.items():
            vec.map_read()
            out[attr] = numpy.array(vec.mem)
        return out

    def _slave_proto(self, slave):
        return _proto_of_slave(self, slave)

    def _net_proto(self):
        return _proto_of_net(self)

    @staticmethod
    def _as_bits(arr):
        import numpy
        return arr.view(numpy.dtype("u%d" % arr.dtype.itemsize))

    def generate_data_for_slave(self, slave=None):
        """Ships trainables (or the change since this worker's last
        sync) — see the dialect note above."""
        if not self.trainables:
            return None
        import numpy
        arrays = self._trainable_arrays()
        if not self._slave_proto(slave).get("delta"):
            # Legacy peer: full copy + FIFO.  Pipelined (async)
            # workers hold several jobs in flight and replies come
            # back in serve order on the one TCP stream — a single
            # slot would mis-base job N's fold.
            self._shipped_.setdefault(slave, []).append(arrays)
            return arrays
        version = getattr(self.workflow, "weights_version", 0)
        prev = self._synced_.get(slave)
        self._synced_[slave] = (version, arrays)
        if prev is None:
            return {"F": arrays, "v": version}
        base_version, base = prev
        delta = {}
        for attr, arr in arrays.items():
            b = base.get(attr)
            if b is None or b.shape != arr.shape or \
                    b.dtype != arr.dtype:
                # Reshaped/grown trainables (rare): rebase with a
                # full ship rather than an undecodable delta.
                return {"F": arrays, "v": version}
            bits = numpy.bitwise_xor(self._as_bits(arr),
                                     self._as_bits(b))
            # Unchanged tensors collapse to a None marker — with one
            # worker (or an idle interval) the whole delta vanishes.
            delta[attr] = bits if bits.any() else None
        return {"D": delta, "v": version, "bv": base_version}

    def apply_data_from_master(self, data):
        if not data:
            return
        import numpy
        from ..resilience import ProtocolError
        if "F" in data:
            self._base_ = {}
            # A full rebase starts a fresh delta session: any owed
            # quantization error was relative to the old base and
            # must not leak into the new one.
            self._residual_ = {}
            for attr, arr in data["F"].items():
                vec = self.trainables.get(attr)
                if vec is not None:
                    vec.mem = arr
                    # Own copy: the base must survive however the
                    # wire buffer or vec.mem is reused later.
                    self._base_[attr] = numpy.array(arr)
            self._base_version_ = data.get("v")
            return
        if "D" in data:
            if self._base_ is None:
                raise ProtocolError(
                    "weights delta received before any full sync — "
                    "the session is desynchronized; reconnecting "
                    "will trigger a full rebase")
            if data.get("bv") != self._base_version_:
                raise ProtocolError(
                    "weights delta based on version %s but this "
                    "worker is synced to %s — reconnecting will "
                    "trigger a full rebase" %
                    (data.get("bv"), self._base_version_))
            for attr, bits in data["D"].items():
                vec = self.trainables.get(attr)
                base = self._base_.get(attr)
                if vec is None or base is None:
                    raise ProtocolError(
                        "weights delta names unknown trainable %r"
                        % attr)
                # vec.mem always gets its OWN copy, like the "F"
                # branch: the base must survive however vec.mem is
                # reused (in-place mutation of an aliased trainable
                # would corrupt the next delta's subtraction base
                # silently — version tags still match).
                if bits is None:  # unchanged since last sync
                    vec.mem = numpy.array(base)
                    continue
                new = numpy.bitwise_xor(
                    self._as_bits(base),
                    bits.reshape(base.shape)).view(base.dtype)
                self._base_[attr] = new
                vec.mem = numpy.array(new)
            self._base_version_ = data.get("v")
            return
        # Legacy master: plain attr → array dict, full overwrite.
        for attr, arr in data.items():
            vec = self.trainables.get(attr)
            if vec is not None:
                vec.mem = arr

    def generate_data_for_master(self):
        if not self.trainables:
            return None
        arrays = self._trainable_arrays()
        proto = self._net_proto()
        if not proto.get("delta") or self._base_ is None:
            return arrays
        import zlib
        from ..network_common import encode_delta, decode_delta
        dtype = proto.get("dtype") or "fp32"
        feedback = dtype == "int8"
        delta = {}
        for attr, arr in arrays.items():
            b = self._base_.get(attr)
            if b is None or b.shape != arr.shape:
                return arrays  # desynced trainable set: full rebase
            d = arr - b
            if not d.any():
                # Untouched trainables (every validation/test job)
                # collapse to a None marker, mirroring the
                # master→worker direction — with codec=none a dense
                # zero delta would ship full-weights-sized payloads.
                # Any error-feedback residual stays parked and rides
                # the next REAL update instead of shipping alone.
                delta[attr] = None
                continue
            if feedback and d.dtype == "float32":
                r = self._residual_.get(attr)
                if r is not None and r.shape == d.shape:
                    d = d + r
            # Deterministic stochastic-rounding seed: the same
            # (tensor, base version) quantizes identically on every
            # replay, so seeded loopback sessions stay reproducible.
            seed = zlib.crc32(attr.encode("utf-8")) ^ \
                ((self._base_version_ or 0) & 0xFFFFFFFF)
            payload = encode_delta(d, dtype, seed=seed)
            if payload is None:
                # Exact-f32 rung (or a codec refusal, e.g. a
                # non-finite delta int8 cannot carry): nothing is
                # lost, so nothing is owed.
                if feedback:
                    self._residual_.pop(attr, None)
                delta[attr] = d
                continue
            if feedback:
                self._residual_[attr] = d - decode_delta(payload)
            delta[attr] = payload
        return {"U": delta, "bv": self._base_version_}

    def apply_data_from_slave(self, data, slave=None):
        """Delta aggregation (delayed/async SGD): the worker trained
        from the version we shipped it; fold ITS update into OUR
        current values as (theirs − shipped).  In the delta dialect
        the worker already did the subtraction — the fold reduces to
        one add and the master needs no shipped copy."""
        if not data:
            return
        if "U" in data:
            from ..network_common import decode_delta
            for attr, d in data["U"].items():
                vec = self.trainables.get(attr)
                if vec is None or d is None:  # None = unchanged
                    continue
                d = decode_delta(d)
                vec.map_read()  # device copy (if any) is not newer
                vec.mem = vec.mem + d.reshape(vec.mem.shape)
            return
        bases = self._shipped_.get(slave)
        base = bases.pop(0) if bases else None
        if bases is not None and not bases:
            self._shipped_.pop(slave, None)
        for attr, arr in data.items():
            vec = self.trainables.get(attr)
            if vec is None:
                continue
            if base is not None and attr in base:
                vec.map_read()  # device copy (if any) is not newer
                vec.mem = vec.mem + (arr - base[attr])
            else:
                vec.mem = arr

    def drop_slave(self, slave=None):
        self._shipped_.pop(slave, None)
        self._synced_.pop(slave, None)

    # -- population member contexts (docs/population.md) -------------------

    def export_sync_state(self):
        """Worker side: this unit's delta-session base (arrays +
        version) as an opaque snapshot.  The population worker swaps
        these per member id around every job, so lineages interleaved
        on one worker never cross-apply a delta against a sibling's
        base.  Arrays are rebound, never mutated in place, so the
        snapshot stays valid while another member is installed."""
        return (self._base_, self._base_version_, dict(self._residual_))

    def import_sync_state(self, state):
        """Worker side: installs a member's delta-session base
        (``None`` state = fresh member, forces a full-ship sync).
        Accepts pre-int8 two-tuples (no error-feedback residual
        plane) from older snapshots."""
        state = state or (None, None, {})
        if len(state) == 2:
            state = state + ({},)
        self._base_, self._base_version_, residual = state
        self._residual_ = dict(residual or {})

    def adopt_synced_from(self, src, slave):
        """Master side, exploit-as-delta (docs/population.md): seeds
        this lineage unit's synced base for ``slave`` with the LEADER
        lineage unit's — after an exploit copied the leader's
        last-shipped weights here, the next job to that worker ships
        only the (collapsing) xor delta against a base the worker
        already holds for the leader, instead of a full weight ship.
        Returns False when the leader has no synced base at that
        worker, None when this unit has nothing to sync at all."""
        if not self.trainables:
            return None
        prev = src._synced_.get(slave)
        if prev is None:
            return False
        version, arrays = prev
        self._synced_[slave] = (version, dict(arrays))
        return True

    def adopt_shipped_values(self, src, slave):
        """Master side: overwrites this lineage unit's trainables
        with the values the LEADER unit last SHIPPED to ``slave``
        (its synced base there).  The exploit copies exactly the
        generation the worker already holds, so the follow-up delta
        ship collapses to unchanged-None markers.  Returns False when
        the leader has no synced base at that worker, None when not
        applicable."""
        import numpy
        if not self.trainables:
            return None
        prev = src._synced_.get(slave)
        if prev is None:
            return False
        _version, arrays = prev
        for attr, vec in self.trainables.items():
            arr = arrays.get(attr)
            if arr is None or arr.shape != vec.shape:
                return False
            vec.map_write()
            vec.mem = numpy.array(arr)
        return True


class GradientDescentBase(TracedUnit, metaclass=GDUnitRegistry):
    """Per-layer trainer (znicz ``GradientDescentBase`` analogue).

    Holds the update hyperparameters and optimizer slots for its
    ``target`` forward unit; ``tupdate`` is called inside the fused
    step with the autodiff gradient and delegates to the registered
    optimizer's pure update rule (``optimizers.py`` — sgd is the
    bit-identical default; adam/adamw/lion declare their own slots,
    which flow through sharding plans, snapshots and rollback exactly
    like the historic ``velocity_*`` momentum did).
    """

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super(GradientDescentBase, self).__init__(workflow, **kwargs)
        self.view_group = "TRAINER"
        self.target = kwargs.get("target")
        #: Update rule (optimizers registry).  Explicit kwarg pins it
        #: against the ``--optimizer`` config override; the override
        #: otherwise applies at initialize (so a RESUMED unit meets
        #: the slot-mismatch check instead of silently reinit'ing).
        self.optimizer = kwargs.get("optimizer") or config_get(
            root.common.engine.optimizer, "sgd")
        self._optimizer_explicit = "optimizer" in kwargs
        optimizers.get(self.optimizer)  # validate early, actionably
        #: Adam/Lion moment coefficients + epsilon; None = the
        #: optimizer's own default (HYPER_DEFAULTS).
        self.beta1 = kwargs.get("beta1")
        self.beta2 = kwargs.get("beta2")
        self.eps = kwargs.get("eps")
        self.learning_rate = kwargs.get("learning_rate", 0.01)
        self.learning_rate_bias = kwargs.get(
            "learning_rate_bias", self.learning_rate)
        # L2 weight decay (the reference's "lambda"/weights_decay).
        self.weights_decay = kwargs.get("weights_decay", 0.0)
        self.weights_decay_bias = kwargs.get("weights_decay_bias", 0.0)
        # Momentum (the reference's "gradient_moment").
        self.gradient_moment = kwargs.get("gradient_moment", 0.0)
        self.gradient_moment_bias = kwargs.get(
            "gradient_moment_bias", self.gradient_moment)
        # Bias-hyper tying is STRUCTURAL (was *_bias omitted at
        # construction?), not value equality: a user who explicitly
        # sets learning_rate_bias to the same number as learning_rate
        # has decoupled it, and a traced population gene must then
        # not leak onto the bias slot.
        self._bias_tied = {
            "learning_rate": "learning_rate_bias" not in kwargs,
            "gradient_moment": "gradient_moment_bias" not in kwargs,
        }
        self._velocities = {}

    def init_unpickled(self):
        super(GradientDescentBase, self).init_unpickled()
        # Slot-shard wire sync (docs/distributed.md ZeRO section):
        # transient per-session state, mirroring ForwardBase's delta
        # bookkeeping — master: slave -> (version, shard arrays);
        # worker: last-synced shard arrays + version.
        self._slot_synced_ = {}
        self._slot_base_ = None
        self._slot_base_version_ = None
        # A snapshot from before the structural flag existed carries
        # no _bias_tied: reconstruct it from value equality (the old
        # semantics) so a restored population keeps tying the way it
        # trained.  During construction the hyper attrs don't exist
        # yet and __init__ sets the flags right after.
        if not hasattr(self, "_bias_tied") and \
                hasattr(self, "learning_rate"):
            self._bias_tied = {
                "learning_rate":
                    self.learning_rate_bias == self.learning_rate,
                "gradient_moment":
                    self.gradient_moment_bias == self.gradient_moment,
            }
        # Pre-registry snapshots carry no optimizer attrs: they were
        # trained by the inline momentum-SGD rule, which "sgd"
        # reproduces bit-identically.
        if not hasattr(self, "optimizer") and \
                hasattr(self, "learning_rate"):
            self.optimizer = "sgd"
            self._optimizer_explicit = False
            self.beta1 = self.beta2 = self.eps = None

    def link_target(self, target):
        self.target = target
        return self

    @property
    def tstate(self):
        return dict(self._velocities)

    @property
    def optimizer_obj(self):
        """The registered optimizer implementing this unit's rule."""
        return optimizers.get(self.optimizer)

    def initialize(self, device=None, **kwargs):
        super(GradientDescentBase, self).initialize(
            device=device, **kwargs)
        if self.target is None:
            raise ValueError("%s has no target forward unit" % self)
        if not self.target.is_initialized:
            # Requeued by workflow.initialize until the target's
            # weights exist (reference: workflow.py:307-331).
            raise AttributeError(
                "%s: target %s not initialized yet" %
                (self.name, self.target.name))
        # CLI/config override (--optimizer): applies only to units
        # that did not pin a rule explicitly — on a resumed snapshot
        # this is what routes a changed optimizer into the
        # slot-mismatch check below.
        override = config_get(root.common.engine.optimizer, None)
        if override and not getattr(self, "_optimizer_explicit",
                                    False):
            optimizers.get(override)
            self.optimizer = override
        opt = self.optimizer_obj
        stale = sorted(
            s for s in self._velocities
            if not any(s.startswith(p) for p in opt.SLOT_PREFIXES))
        if stale:
            # A momentum snapshot resumed into an Adam run (or any
            # other optimizer switch): silently reinitializing the
            # slots would discard the optimizer state the snapshot
            # carried — fail with the fix spelled out.
            raise optimizers.SlotMismatchError(
                "%s holds optimizer slots %s that do not belong to "
                "optimizer %r (its slot prefixes: %s) — the snapshot "
                "was trained with a different optimizer; resume with "
                "the matching --optimizer, or clear the unit's slots "
                "to start optimizer state fresh"
                % (self.name, stale, self.optimizer,
                   ", ".join(opt.SLOT_PREFIXES) or "none"))
        for attr, vec in self.target.trainables.items():
            for slot, shape, dtype in opt.slots(attr, vec, self):
                if slot not in self._velocities:
                    # Host-zeros init, uploaded lazily by the first
                    # dispatch like every other Vector.
                    v = Vector(numpy.zeros(shape, dtype=dtype))
                    v.initialize(self.device)
                    self._velocities[slot] = v

    def _hyper(self, attr, hypers=None):
        if attr == "bias":
            own = (self.learning_rate_bias, self.weights_decay_bias,
                   self.gradient_moment_bias)
            suffix = "_bias"
        else:
            own = (self.learning_rate, self.weights_decay,
                   self.gradient_moment)
            suffix = ""
        if not hypers:
            return own
        # Traced overrides (population evaluation).  A plain traced
        # hyper reaches the bias slot only when the unit's own bias
        # value is TIED to its plain value (the constructor-default
        # case: learning_rate_bias/gradient_moment_bias default to
        # the plain ones, weights_decay_bias defaults to 0.0) — an
        # explicitly decoupled *_bias keeps its own value, so the
        # vmapped path trains the same model the per-chromosome path
        # does.
        names = ("learning_rate", "weights_decay", "gradient_moment")
        out = []
        for name, own_v in zip(names, own):
            if suffix:
                # weights_decay_bias constructor-defaults to 0.0, NOT
                # to weights_decay — so a traced plain decay must
                # never leak onto biases (the per-chromosome path
                # keeps bias decay at its own value).
                ties = getattr(self, "_bias_tied", {}).get(name, False)
                tied_default = hypers.get(name, own_v) if ties \
                    else own_v
                out.append(hypers.get(name + suffix, tied_default))
            else:
                out.append(hypers.get(name, own_v))
        return tuple(out)

    def _hyper_dict(self, attr, hypers=None):
        """The full hyperparameter dict handed to the optimizer's
        update rule: the classic lr/decay/moment triple (bias-aware,
        see :meth:`_hyper`) plus the optimizer's extra hypers
        (beta1/beta2/eps), each overridable by a traced scalar from
        ``hypers`` (the vmapped population path)."""
        lr, decay, moment = self._hyper(attr, hypers)
        out = {"learning_rate": lr, "weights_decay": decay,
               "gradient_moment": moment}
        defaults = self.optimizer_obj.HYPER_DEFAULTS
        for name in ("beta1", "beta2", "eps"):
            if hypers and name in hypers:
                out[name] = hypers[name]
                continue
            own = getattr(self, name, None)
            out[name] = defaults.get(name) if own is None else own
        return out

    def tupdate(self, attr, param, grad, state, ctx, hypers=None):
        """Applies this unit's optimizer rule (``optimizers.py``;
        sgd = the classic momentum-SGD-with-L2 znicz rule,
        bit-identical to the pre-registry inline code).

        ``hypers`` optionally overrides the Python-float
        hyperparameters with traced scalars (the vmapped population
        path evaluates every chromosome in one compiled program, so
        its hypers must be step *inputs*, not baked constants)."""
        return self.optimizer_obj.update(
            attr, param, grad, state, self._hyper_dict(attr, hypers),
            traced=bool(hypers))

    # -- slot-shard wire sync (ZeRO over the delta data plane) -------------
    #
    # Opt-in (``--net-zero K``, handshake-negotiated as proto
    # ``zero``/``zero_rank``): optimizer slots join the master–slave
    # delta protocol, but SHARDED — each worker syncs only its
    # 1/dp flat slice of every slot tensor, so per-minibatch slot
    # wire bytes and the master's per-worker synced-base bookkeeping
    # both divide by dp instead of replicating (docs/distributed.md).
    # The machinery mirrors ForwardBase's trainable sync exactly:
    # master→worker full-ship at join then XOR deltas tagged with
    # weights_version, worker→master arithmetic deltas folded as
    # ``shard += delta``, unchanged tensors collapsing to None.
    # Default (zero absent) ships NOTHING — today's behavior, where
    # worker optimizer state is purely local.

    def _zero_shard(self, proto):
        """(rank, dp) for a negotiated slot-sync session, else None
        (no slot shipping).  Requires the delta dialect: shard folds
        lean on the same synced-base discipline."""
        dp = int(proto.get("zero") or 0)
        if dp <= 0 or not proto.get("delta"):
            return None
        return int(proto.get("zero_rank") or 0), dp

    @staticmethod
    def _shard_bounds(vec, rank, dp):
        """Flat [lo, hi) slice of ``vec`` owned by ``rank`` (the last
        rank absorbs the remainder; scalars land on the last rank)."""
        n = vec.size
        return rank * n // dp, (rank + 1) * n // dp

    def _slot_shard_arrays(self, rank, dp):
        out = {}
        for slot, vec in self.tstate.items():
            lo, hi = self._shard_bounds(vec, rank, dp)
            if hi <= lo:
                continue
            vec.map_read()
            out[slot] = numpy.array(vec.mem.reshape(-1)[lo:hi])
        return out

    def _check_shard(self, slot, size, rank, dp):
        """Raises ProtocolError unless ``slot`` exists here and rank
        owns exactly ``size`` of its elements — called on EVERY shard
        of a message before any of them mutates local state, so a bad
        frame never leaves a half-applied base behind."""
        from ..resilience import ProtocolError
        vec = self.tstate.get(slot)
        if vec is None:
            raise ProtocolError(
                "slot sync names unknown optimizer slot %r on %s"
                % (slot, self.name))
        lo, hi = self._shard_bounds(vec, rank, dp)
        if hi - lo != size:
            raise ProtocolError(
                "slot shard for %s/%s is %d elements but rank %d/%d "
                "owns %d — shard geometry desync" %
                (self.name, slot, size, rank, dp, hi - lo))

    def _store_shard(self, slot, arr, rank, dp):
        vec = self.tstate[slot]
        lo, hi = self._shard_bounds(vec, rank, dp)
        vec.map_write()
        vec.mem.reshape(-1)[lo:hi] = arr

    def generate_data_for_slave(self, slave=None):
        """Master side: ships this worker's slot SHARD — full at
        join/rebase, XOR delta after (same dialect as ForwardBase
        trainables; unchanged slots collapse to None)."""
        proto = self._slave_proto(slave)
        shard = self._zero_shard(proto)
        if shard is None or not self.tstate:
            return None
        rank, dp = shard
        arrays = self._slot_shard_arrays(rank, dp)
        if not arrays:
            return None
        from .. import resilience
        version = getattr(self.workflow, "weights_version", 0)
        prev = self._slot_synced_.get(slave)
        self._slot_synced_[slave] = (version, arrays)
        if prev is None:
            resilience.stats.incr(
                "net.slot_bytes",
                sum(a.nbytes for a in arrays.values()))
            return {"F": arrays, "v": version}
        base_version, base = prev
        delta = {}
        sent = 0
        for slot, arr in arrays.items():
            b = base.get(slot)
            if b is None or b.shape != arr.shape or \
                    b.dtype != arr.dtype:
                # Mid-session rebase ships the full shard — counted
                # like the join-time ship above.
                resilience.stats.incr(
                    "net.slot_bytes",
                    sum(a.nbytes for a in arrays.values()))
                return {"F": arrays, "v": version}
            bits = numpy.bitwise_xor(ForwardBase._as_bits(arr),
                                     ForwardBase._as_bits(b))
            if bits.any():
                delta[slot] = bits
                sent += bits.nbytes
            else:
                delta[slot] = None
        resilience.stats.incr("net.slot_bytes", sent)
        return {"D": delta, "v": version, "bv": base_version}

    def apply_data_from_master(self, data):
        """Worker side: lands the master's slot shard into the local
        slot Vectors (the rest of each tensor stays this worker's own
        state, exactly as all of it did before slot sync existed)."""
        if not data:
            return
        from ..resilience import ProtocolError
        shard = self._zero_shard(self._net_proto())
        if shard is None:
            return
        rank, dp = shard
        if "F" in data:
            # Validate EVERY shard before mutating anything: a bad
            # frame must not leave a partially-populated base (a
            # non-None partial base would later ship a bogus full
            # rebase instead of triggering the reconnect recovery).
            for slot, arr in data["F"].items():
                self._check_shard(slot, arr.size, rank, dp)
            base = {}
            for slot, arr in data["F"].items():
                self._store_shard(slot, arr, rank, dp)
                base[slot] = numpy.array(arr)
            self._slot_base_ = base
            self._slot_base_version_ = data.get("v")
            return
        if "D" not in data:
            return
        if self._slot_base_ is None:
            raise ProtocolError(
                "slot-shard delta received before any full sync — "
                "the session is desynchronized; reconnecting will "
                "trigger a full rebase")
        if data.get("bv") != self._slot_base_version_:
            raise ProtocolError(
                "slot-shard delta based on version %s but this "
                "worker is synced to %s — reconnecting will trigger "
                "a full rebase" % (data.get("bv"),
                                   self._slot_base_version_))
        updates = {}  # validate-then-commit, like the "F" branch
        for slot, bits in data["D"].items():
            base = self._slot_base_.get(slot)
            if base is None:
                raise ProtocolError(
                    "slot-shard delta names unsynced slot %r" % slot)
            if bits is None:  # unchanged since last sync
                updates[slot] = (base, False)
                continue
            self._check_shard(slot, base.size, rank, dp)
            if bits.size != base.size:
                raise ProtocolError(
                    "slot-shard delta for %r is %d elements against "
                    "a %d-element base — shard geometry desync"
                    % (slot, bits.size, base.size))
            new = numpy.bitwise_xor(
                ForwardBase._as_bits(base),
                bits.reshape(base.shape)).view(base.dtype)
            updates[slot] = (new, True)
        for slot, (new, changed) in updates.items():
            if changed:
                self._slot_base_[slot] = new
            self._store_shard(slot, numpy.array(new), rank, dp)
        self._slot_base_version_ = data.get("v")

    def generate_data_for_master(self):
        """Worker side: BITWISE XOR deltas of this worker's slot
        shard against its synced base — the master reconstructs the
        worker's exact values (xor is exact, unlike an arithmetic
        ``base + (theirs − base)`` fold, which can drift a ulp), so
        the canonical optimizer state the master snapshots is
        bit-identical to what the trainer computed.  Untouched slots
        collapse to None markers; the base advances to what was just
        shipped, so the master→worker direction zero-collapses in
        steady state too.  No bf16 option here: exact reconstruction
        is the whole point (same stance as the master→worker weights
        XOR path)."""
        proto = self._net_proto()
        shard = self._zero_shard(proto)
        if shard is None or self._slot_base_ is None or \
                not self.tstate:
            return None
        rank, dp = shard
        arrays = self._slot_shard_arrays(rank, dp)
        from .. import resilience
        delta = {}
        sent = 0
        for slot, arr in arrays.items():
            b = self._slot_base_.get(slot)
            if b is None or b.shape != arr.shape or \
                    b.dtype != arr.dtype:
                # Desynced slot set: full shard rebase.
                resilience.stats.incr(
                    "net.slot_bytes",
                    sum(a.nbytes for a in arrays.values()))
                self._slot_base_ = {s: numpy.array(a)
                                    for s, a in arrays.items()}
                return {"S": arrays}
            bits = numpy.bitwise_xor(ForwardBase._as_bits(arr),
                                     ForwardBase._as_bits(b))
            if bits.any():
                delta[slot] = bits
                sent += bits.nbytes
                self._slot_base_[slot] = arr
            else:
                delta[slot] = None
        resilience.stats.incr("net.slot_bytes", sent)
        return {"X": delta}

    def apply_data_from_slave(self, data, slave=None):
        """Master side: reconstructs the owner's shard values from
        the XOR delta against what this master last synced to that
        worker (bit-exact; concurrent owners of one shard — dp=1
        replication, or churn-induced overlap — resolve
        last-writer-wins, which is the right semantics for optimizer
        state: the owner's state IS canonical, unlike weight updates,
        which must compose additively)."""
        if not data:
            return
        shard = self._zero_shard(self._slave_proto(slave))
        if shard is None:
            return
        rank, dp = shard
        prev = self._slot_synced_.get(slave)
        synced = prev[1] if prev else {}
        if prev is None:
            self._slot_synced_[slave] = (None, synced)
        # Peer-supplied bytes NEVER raise here: a master-side
        # exception while folding stops the whole coordinator
        # (server._serve_slave's loud-stop contract is for MASTER
        # faults) — a desynced/misconfigured worker's slot piece is
        # dropped with a warning instead, exactly like the weight
        # fold tolerates unknown attrs.  The worker's own training
        # update still folded; only its slot mirror is skipped.
        from .. import resilience
        if "S" in data:  # full shard rebase from the worker
            for slot, arr in data["S"].items():
                try:
                    self._check_shard(slot, arr.size, rank, dp)
                except Exception as e:
                    resilience.stats.incr("net.slot_dropped")
                    self.warning("dropping slot rebase from %s: %s",
                                 slave, e)
                    continue
                self._store_shard(slot, arr, rank, dp)
                synced[slot] = numpy.array(arr)
            return
        if "X" not in data:
            return
        for slot, bits in data["X"].items():
            if bits is None:  # unchanged
                continue
            base = synced.get(slot)
            if base is None or base.size != bits.size:
                resilience.stats.incr("net.slot_dropped")
                self.warning(
                    "slot-shard XOR delta for %s/%s has no matching "
                    "synced base — dropped (worker %s will rebase "
                    "on its next full sync)", self.name, slot, slave)
                continue
            new = numpy.bitwise_xor(ForwardBase._as_bits(base),
                                    bits).view(base.dtype)
            try:
                self._check_shard(slot, new.size, rank, dp)
            except Exception as e:
                resilience.stats.incr("net.slot_dropped")
                self.warning("dropping slot delta from %s: %s",
                             slave, e)
                continue
            self._store_shard(slot, new, rank, dp)
            synced[slot] = new

    def drop_slave(self, slave=None):
        self._slot_synced_.pop(slave, None)

    # -- population member contexts (docs/population.md) -------------------

    def export_sync_state(self):
        """Worker side: the slot-shard sync base, mirroring
        ``ForwardBase.export_sync_state`` (population member-context
        swaps cover optimizer slots the same way they cover
        weights)."""
        return (self._slot_base_, self._slot_base_version_)

    def import_sync_state(self, state):
        # Slot deltas always ship exact (fp32/bf16 rungs only), so a
        # context copied through the 3-tuple weight-state shape just
        # drops its (always-None) residual slot here.
        self._slot_base_, self._slot_base_version_ = \
            tuple(state)[:2] if state else (None, None)

    def adopt_synced_from(self, src, slave):
        """Master side: exploit-as-delta for the slot shards (see
        ``ForwardBase.adopt_synced_from``)."""
        if not self.tstate:
            return None
        prev = src._slot_synced_.get(slave)
        if prev is None:
            return False
        version, arrays = prev
        self._slot_synced_[slave] = (version, dict(arrays))
        return True

    def adopt_shipped_values(self, src, slave, rank=0, dp=1):
        """Master side: overwrites this unit's slot shard with the
        values the leader last synced to ``slave`` (see
        ``ForwardBase.adopt_shipped_values``)."""
        if not self.tstate:
            return None
        prev = src._slot_synced_.get(slave)
        if prev is None:
            return False
        _version, arrays = prev
        for slot, arr in arrays.items():
            vec = self.tstate.get(slot)
            if vec is None:
                return False
            lo, hi = self._shard_bounds(vec, rank, dp)
            if hi - lo != arr.size:
                return False
            self._store_shard(slot, arr, rank, dp)
        return True

    def _slave_proto(self, slave):
        return _proto_of_slave(self, slave)

    def _net_proto(self):
        return _proto_of_net(self)

    def tforward(self, read, write, params, ctx, state=None):
        """GD units contribute no forward compute."""
