"""Accelerated units and the fused-step compiler.

Capability parity with the reference acceleration layer (reference:
veles/accelerated_units.py — ``AcceleratedUnit:126``,
``AcceleratedWorkflow:820``, kernel build/cache machinery ``:503-666``;
veles/backends.py device dispatch).

The reference's model: every unit carries THREE implementations
(``numpy_run``/``ocl_run``/``cuda_run``), compiles its own kernels at
initialize, and the workflow tick is a chain of kernel enqueues with
host synchronization at every Vector map/unmap.

The TPU-native model inverts this: a unit in the training loop is a
**TracedUnit** that contributes a *pure function* over tracers, and the
workflow fuses loader-gather → forward stack → loss → backward →
optimizer updates into ONE jitted XLA computation per tick
(BASELINE.json north star).  Data flow between traced units is derived
from shared :class:`~veles_tpu.memory.Vector` identity — ``link_attrs``
already aliases the same Vector object on both sides, so the compiler
keys its tensor bag by ``id(vector)`` and no string plumbing is needed.
Backward passes come from ``jax.value_and_grad`` over the composed
forward instead of hand-written per-layer gradient kernels; per-layer
GradientDescent units keep their identity (hyperparameters, momentum
state, update rule) and are applied inside the same jit.

The reference's per-device compiled-program tar cache
(accelerated_units.py:599-666) maps to XLA's persistent compilation
cache, enabled in :func:`backends.enable_compilation_cache`.
"""

from . import resilience
from .backends import enable_compilation_cache
from .observability.programs import STEP_SCOPES
from .config import root, get as config_get
from .memory import Vector
from .units import Unit
from .workflow import Workflow

class StepContext(object):
    """Per-tick traced context handed to every TracedUnit: the RNG key,
    the training flag, the scalar loss slot, and the metrics dict."""

    def __init__(self, key=None, training=True):
        self.key = key
        self.training = training
        self.loss = None
        self.metrics = {}
        self._key_uses = 0

    def next_key(self):
        import jax
        if self.key is None:
            raise ValueError("step was compiled without an RNG key")
        self._key_uses += 1
        return jax.random.fold_in(self.key, self._key_uses)

    def add_metric(self, name, value):
        self.metrics[name] = value

    def set_loss(self, value):
        self.loss = value


def step_compute_dtype():
    """Activation-stream dtype for the fused step: bf16 when
    ``root.common.engine.precision_level`` is 0 (default), f32 above
    (replaces the reference's OpenCL precision defines,
    config.py:244-247).  Single source of truth — layer units and the
    mean-disp normalizer all consult this."""
    import jax.numpy as jnp
    level = config_get(root.common.engine.precision_level, 0)
    return jnp.bfloat16 if level == 0 else jnp.float32


def select_by_training(ctx, train_fn, eval_fn):
    """Train/eval branch select that works in BOTH step modes: with a
    static Python bool (single-tick steps) it evaluates only the taken
    branch; with a traced 0/1 ``training`` scalar (block mode, where
    train and validation blocks share one compiled program) it
    evaluates both and selects with ``jnp.where``."""
    if isinstance(ctx.training, bool):
        return train_fn() if ctx.training else eval_fn()
    import jax.numpy as jnp
    return jnp.where(ctx.training > 0, train_fn(), eval_fn())


class AcceleratedUnit(Unit):
    """A unit owning device-resident Vectors (reference:
    accelerated_units.py:126).  ``initialize`` binds the device and
    attaches every Vector attribute to it."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super(AcceleratedUnit, self).__init__(workflow, **kwargs)
        self.intermediate_sync = False

    def init_unpickled(self):
        super(AcceleratedUnit, self).init_unpickled()
        self._device_ = None

    @property
    def device(self):
        return self._device_

    @device.setter
    def device(self, value):
        self._device_ = value

    def initialize(self, device=None, **kwargs):
        super(AcceleratedUnit, self).initialize(**kwargs)
        if device is not None:
            self._device_ = device
        for vec in self._own_vectors():
            vec.initialize(self._device_)

    def _own_vectors(self):
        return [v for v in self.__dict__.values()
                if isinstance(v, Vector)]


class TracedUnit(AcceleratedUnit):
    """A unit participating in the fused jitted step.

    Subclasses implement :meth:`tforward` and declare their tensors:

      * ``trainables``  — attr → Vector, differentiated + updated;
      * ``tstate``      — attr → Vector, carried/updated but NOT
        differentiated (optimizer slots, batch-norm stats, …);
      * inputs/outputs — ordinary Vector attributes read/written via
        the ``read``/``write`` callbacks inside ``tforward``.

    ``run()`` delegates to the workflow's fused-step executor; the
    first traced unit reached in a tick triggers the single compiled
    step, the rest are no-ops (their compute already happened inside
    that step).
    """

    hide_from_registry = True

    @property
    def trainables(self):
        return {}

    @property
    def tstate(self):
        return {}

    @property
    def scope_name(self):
        """The ``jax.named_scope`` the fused step wraps this unit's
        ``tforward`` in — what the compiled program's instructions
        carry in their ``op_name`` and ``observability.programs``
        reads back as the instruction's unit
        (docs/observability.md, scope vocabulary).  A STABLE name:
        the unit's own (``embedding``, ``block3``, ``head``), or its
        role (``loader``, ``evaluator``); never an ``id()``."""
        return self.name

    def tforward(self, read, write, params, ctx, state=None):
        """Pure traced computation.  ``read(vec)``/``write(vec, val)``
        move tracers through the tensor bag; ``params`` maps this
        unit's trainable attr names to tracers; ``state`` maps this
        unit's tstate attr names to tracers (None when the unit has
        none); return a dict of state updates (or None).  ``ctx`` is
        the :class:`StepContext`."""
        raise NotImplementedError()

    def run(self):
        wf = self.workflow
        if isinstance(wf, AcceleratedWorkflow) and wf.fused:
            wf.execute_step(trigger=self)
        else:
            self.eager_run()

    def eager_run(self):
        """Single-unit eager forward (inference/debugging path — the
        reference's numpy_run analogue)."""
        ctx = StepContext(training=False)

        def read(vec):
            return vec.devmem

        def write(vec, val):
            vec.devmem = val

        params = {a: v.devmem for a, v in self.trainables.items()}
        state = {a: v.devmem for a, v in self.tstate.items()}
        upd = self.tforward(read, write, params, ctx,
                            state=state or None) or {}
        for a, val in upd.items():
            self.tstate[a].devmem = val


def unit_scopes(units):
    """unit -> the ``jax.named_scope`` its ``tforward`` is traced
    under: its ``scope_name``, made unique by the unit's position
    where two units share one (default names are class names)."""
    names = [u.scope_name.replace("/", "_") for u in units]
    return {u: name if names.count(name) == 1 and
            name not in STEP_SCOPES else "%s_%d" % (name, i)
            for i, (u, name) in enumerate(zip(units, names))}


class StepCompiler(object):
    """Builds the fused jitted train step for an AcceleratedWorkflow.

    The compiled function signature is::

        step(params, states, batch, key) ->
            (new_params, new_states, outputs, metrics)

    where ``params``/``states`` are dicts keyed by "unit_name/attr",
    ``batch`` is a dict keyed by id(Vector) as str for the loader-fed
    vectors, ``outputs`` are persisted evaluator output vectors.
    Donation of ``params``/``states`` makes updates in-place in HBM.
    """

    def __init__(self, workflow):
        self.workflow = workflow
        self.forward_units = []
        self.gd_map = {}          # forward unit -> gd unit
        self.batch_vectors = []   # Vectors fed from host each tick
        self.const_vectors = []   # large device-resident constants
        self.persist_vectors = []  # evaluator outputs etc.
        self._compiled = None
        self._fingerprint = None
        # (blocks, key, flag) of the newest block dispatch —
        # lower_last_block() lowers that program again.
        self._last_block_ = None
        # Per-program FLOP estimate for the live MFU gauge
        # (_program_flops); 0.0 = registered, no estimate.
        self._step_flops_ = {}
        self.scope_units = ()

    # -- graph analysis ----------------------------------------------------

    def analyze(self):
        from .znicz.nn_units import GradientDescentBase
        wf = self.workflow
        order = wf.units_in_dependency_order
        self.forward_units = [
            u for u in order
            if isinstance(u, TracedUnit) and
            not isinstance(u, GradientDescentBase)]
        self.gd_map = {}
        for u in wf.units:
            if isinstance(u, GradientDescentBase) and \
                    u.target is not None:
                self.gd_map[u.target] = u
        # Batch vectors: declared by the loader via
        # ``step_batch_vectors`` (duck-typed).
        self.batch_vectors = []
        self.const_vectors = []
        for u in wf.units:
            get_bv = getattr(u, "step_batch_vectors", None)
            if get_bv is not None:
                self.batch_vectors.extend(get_bv())
            get_cv = getattr(u, "step_const_vectors", None)
            if get_cv is not None:
                self.const_vectors.extend(get_cv())
        self.persist_vectors = []
        for u in self.forward_units:
            get_pv = getattr(u, "step_persist_vectors", None)
            if get_pv is not None:
                self.persist_vectors.extend(get_pv())

    def param_name(self, unit, attr):
        return "%s/%s" % (unit.name, attr)

    def _collect(self, which):
        out = {}
        for u in self.forward_units:
            mapping = u.trainables if which == "params" else u.tstate
            for attr, vec in mapping.items():
                out[self.param_name(u, attr)] = vec
            if which == "state":
                gd = self.gd_map.get(u)
                if gd is not None:
                    for attr, vec in gd.tstate.items():
                        out[self.param_name(gd, attr)] = vec
        return out

    # -- compilation -------------------------------------------------------

    def _note_optimizer_stats(self):
        """Publishes the optimizer observability gauges
        (``optimizer.state_bytes`` / ``optimizer.shard_frac`` with an
        ``optimizer.kind`` label — heartbeat perf section, web_status
        perf row, /metrics): one cheap walk per compile, not per
        dispatch.  Called from compile() after analyze()."""
        gds = list(dict.fromkeys(self.gd_map.values()))
        if not gds:
            return
        kinds = sorted({getattr(gd, "optimizer", "sgd")
                        for gd in gds})
        state_bytes = sum(vec.nbytes for gd in gds
                          for vec in gd.tstate.values())
        zero = getattr(self.workflow, "_zero_", None)
        shard_frac = 1.0 / zero[1] if zero and zero[1] else 1.0
        from .observability import attribution
        attribution.note_optimizer("+".join(kinds), state_bytes,
                                   shard_frac)

    def fingerprint(self):
        """Shapes/dtypes of all step tensors — recompile trigger."""
        parts = []
        for vec in (list(self._collect("params").values()) +
                    list(self._collect("state").values()) +
                    self.batch_vectors):
            parts.append((vec.shape, str(vec.dtype)))
        return tuple(parts)

    def compile(self):
        """Builds the step's closures and their ``jax.jit`` wrappers
        (nothing is traced before the first dispatch) inside the
        ``step.build`` set-up span (docs/observability.md,
        "Start-up")."""
        from .observability import startup
        with startup.span("step.build"):
            self._build()

    def _build(self):
        import jax

        # Compile sentinel (analysis.runtime.strict_step): a re-trace
        # inside a wrapped steady-state region is a hot-path bug.
        from .analysis import runtime as _art
        _art.note_compile("step:%s" % type(self.workflow).__name__)
        enable_compilation_cache()
        self.analyze()
        param_vecs = self._collect("params")
        state_vecs = self._collect("state")
        forward_units = list(self.forward_units)
        gd_map = dict(self.gd_map)
        batch_ids = [str(id(v)) for v in self.batch_vectors]
        batch_vecs = list(self.batch_vectors)
        const_ids = [str(id(v)) for v in self.const_vectors]
        const_vecs = list(self.const_vectors)
        # (str key for the outputs dict the executor reads, int key
        # for the bag — paired HERE so the traced closure never
        # parses strings.)
        persist_ids = [(str(id(v)), id(v))
                       for v in self.persist_vectors]
        pname = self.param_name
        scope_of = unit_scopes(forward_units)
        self.scope_units = tuple(scope_of.values())
        # Health sentinel (guardian.py): evaluators expose a
        # ``health_acc`` state row; the step accumulates per-class
        # tick finiteness (isfinite(loss) & isfinite(grad_norm)) and
        # the grad-norm scalar into it — fetched with the ordinary
        # epoch accumulator, so detection costs no extra host syncs.
        health_specs = []
        for u in forward_units:
            if "health_acc" in u.tstate:
                cv = getattr(u, "minibatch_class_vec", None)
                health_specs.append(
                    (pname(u, "health_acc"),
                     str(id(cv)) if cv is not None else None))
        # Non-finite updates are dropped ON DEVICE (the gate below)
        # unless the guardian's policy wants the poison to land so a
        # rollback can be exercised (policy="rollback" sets this
        # False at initialize; changing it later needs invalidate()).
        device_skip = bool(getattr(self.workflow,
                                   "health_device_skip", True))
        # ZeRO-2 (parallel.apply_zero_sharding level 2): sharding
        # constraints pinning each slot-backed gradient to its slot's
        # data-axis layout, so XLA lowers the gradient psum to a
        # reduce-scatter feeding the sharded update instead of a full
        # all-reduce + slice.
        zero_grad_specs = dict(getattr(
            self.workflow, "_zero_grad_shardings_", None) or {})
        self._note_optimizer_stats()

        @jax.named_scope("health")
        def global_grad_norm(grads):
            import jax.numpy as jnp
            total = jnp.float32(0.0)
            for g in grads.values():
                total = total + jnp.sum(
                    jnp.square(g.astype(jnp.float32)))
            return jnp.sqrt(total)

        @jax.named_scope("health")
        def health_update(new_states, batch, gnorm, loss,
                          valid=None):
            """Adds this tick's health row — [nonfinite, gnorm sum,
            gnorm max, ticks] at the minibatch's class — and returns
            the tick's finite flag (a bool tracer).  ``valid`` gates
            the whole row like the epoch accumulator gates its own:
            padded block ticks (all-zero mask) must not count as
            healthy ticks or dilute the mean grad norm."""
            import jax.numpy as jnp
            finite = jnp.isfinite(gnorm)
            if loss is not None:
                finite = jnp.logical_and(finite, jnp.isfinite(loss))
            f32 = finite.astype(jnp.float32)
            # A non-finite tick ALWAYS counts, even when the poison
            # wrecked n_valid itself (NaN > 0 is False) — only
            # padded-but-healthy ticks are gated out.
            v = jnp.float32(1.0) if valid is None else \
                jnp.logical_or(valid,
                               jnp.logical_not(finite)).astype(
                    jnp.float32)
            safe_gnorm = jnp.where(finite, gnorm, 0.0) * v
            for state_key, cvid in health_specs:
                if cvid is not None and cvid in batch:
                    cls = batch[cvid].astype(jnp.int32)
                else:
                    cls = jnp.int32(2)  # loaderless graph: TRAIN
                acc = new_states[state_key]
                acc = acc.at[cls].add(jnp.stack(
                    [(1.0 - f32) * v, safe_gnorm,
                     jnp.float32(0.0), v]))
                acc = acc.at[cls, 2].max(safe_gnorm)
                new_states[state_key] = acc
            return finite

        def run_forward(params, states, batch, consts, key, training):
            bag = {}
            for bid, vec in zip(batch_ids, batch_vecs):
                bag[id(vec)] = batch[bid]
            for cid, vec in zip(const_ids, const_vecs):
                bag[id(vec)] = consts[cid]
            # Trainables are readable by OTHER units through the bag
            # (tied-weight Deconv reads its conv's filters); gradient
            # flows because these are the differentiated inputs.
            for u in forward_units:
                for a in u.trainables:
                    bag[id(u.trainables[a])] = params[pname(u, a)]
            ctx = StepContext(key=key, training=training)

            def read(vec):
                try:
                    return bag[id(vec)]
                except KeyError:
                    raise KeyError(
                        "traced read of vector %r not yet produced — "
                        "check control links imply data order" % vec)

            def write(vec, val):
                bag[id(vec)] = val

            new_states = dict(states)
            for u in forward_units:
                uparams = {a: params[pname(u, a)]
                           for a in u.trainables}
                ustate = {a: states[pname(u, a)] for a in u.tstate}
                # Units may update their own non-trainable state
                # (e.g. epoch accumulators, batch-norm stats) by
                # returning a dict from tforward.
                with jax.named_scope(scope_of[u]):
                    upd = u.tforward(read, write, uparams, ctx,
                                     state=ustate or None) or {}
                for a, val in upd.items():
                    new_states[pname(u, a)] = val
            outputs = {pid: bag[vid] for pid, vid in persist_ids
                       if vid in bag}
            metrics = dict(ctx.metrics)
            loss = ctx.loss
            if loss is not None:
                metrics["loss"] = loss
            return loss, metrics, new_states, outputs

        @jax.named_scope("update")
        def apply_updates(params, grads, new_states, gate,
                          hypers=None):
            """Runs every GD unit's update rule; ``gate`` (None or a
            0/1 tracer) masks updates out for padded/validation ticks
            in block mode; ``hypers`` optionally overrides the GD
            hyperparameters with traced scalars (population path)."""
            import jax.numpy as jnp
            if zero_grad_specs:
                from jax import lax
                grads = {
                    k: lax.with_sharding_constraint(
                        g, zero_grad_specs[k])
                    if k in zero_grad_specs else g
                    for k, g in grads.items()}
            new_params = dict(params)
            for u in forward_units:
                if not u.trainables:
                    continue
                gd = gd_map.get(u)
                if gd is None:
                    continue
                for attr in u.trainables:
                    key_ = pname(u, attr)
                    gstate = {a: new_states[pname(gd, a)]
                              for a in gd.tstate}
                    new_p, new_gs = gd.tupdate(
                        attr, params[key_], grads[key_], gstate, None,
                        hypers=hypers)
                    if gate is not None:
                        new_p = jnp.where(gate, new_p, params[key_])
                    new_params[key_] = new_p
                    for a, val in new_gs.items():
                        if gate is not None:
                            val = jnp.where(
                                gate, val, new_states[pname(gd, a)])
                        new_states[pname(gd, a)] = val
            return new_params, new_states

        def train_core(params, states, batch, consts, key, hypers):
            def loss_fn(p):
                loss, metrics, new_states, outputs = run_forward(
                    p, states, batch, consts, key, True)
                if loss is None:
                    raise ValueError(
                        "no unit called ctx.set_loss() — an evaluator "
                        "must be present in the traced chain")
                return loss, (metrics, new_states, outputs)
            grads, (metrics, new_states, outputs) = jax.grad(
                loss_fn, has_aux=True)(params)
            gate = None
            if health_specs:
                import jax.numpy as jnp
                gnorm = global_grad_norm(grads)
                metrics["grad_norm"] = gnorm
                nv = metrics.get("n_valid")
                finite = health_update(
                    new_states, batch, gnorm, metrics.get("loss"),
                    valid=None if nv is None else nv > 0)
                metrics["step_finite"] = finite
                if device_skip:
                    gate = finite
            new_params, new_states = apply_updates(
                params, grads, new_states, gate, hypers=hypers)
            return new_params, new_states, outputs, metrics

        def train_step(params, states, batch, consts, key):
            return train_core(params, states, batch, consts, key,
                              None)

        def infer_step(params, states, batch, consts, key):
            loss, metrics, new_states, outputs = run_forward(
                params, states, batch, consts, key, False)
            if health_specs and loss is not None:
                # No gradients on eval ticks: the health row records
                # loss finiteness with a zero grad-norm contribution.
                import jax.numpy as jnp
                nv = metrics.get("n_valid")
                health_update(new_states, batch, jnp.float32(0.0),
                              loss,
                              valid=None if nv is None else nv > 0)
            return new_states, outputs, metrics

        def block_core(params, states, blocks, consts, key, training,
                       hypers):
            """K minibatch ticks in ONE dispatch: lax.scan over the
            stacked per-tick inputs.  ``training`` is a traced 0/1
            scalar, so train and validation blocks share one compiled
            program; updates are gated by training AND per-tick
            validity (padded ticks have all-zero masks).  This is the
            latency-robust path: host→device traffic is one stacked
            upload per K ticks and there is NO per-tick host sync —
            epoch metrics accumulate on-device (EvaluatorBase)."""
            import jax.numpy as jnp
            from jax import lax
            K = next(iter(blocks.values())).shape[0]
            tick_ids = jnp.arange(K)

            def body(carry, xs):
                p, s = carry
                batch_t, t = xs
                tick_key = jax.random.fold_in(key, t)

                def loss_fn(pp):
                    loss, metrics, new_s, _ = run_forward(
                        pp, s, batch_t, consts, tick_key, training)
                    return loss, (metrics, new_s)
                grads, (metrics, new_s) = jax.grad(
                    loss_fn, has_aux=True)(p)
                valid = metrics.get("n_valid", jnp.float32(1.0)) > 0
                gate = jnp.logical_and(training > 0, valid)
                if health_specs:
                    gnorm = global_grad_norm(grads)
                    finite = health_update(new_s, batch_t, gnorm,
                                           metrics.get("loss"),
                                           valid=valid)
                    if device_skip:
                        gate = jnp.logical_and(gate, finite)
                new_p, new_s = apply_updates(p, grads, new_s, gate,
                                             hypers=hypers)
                return (new_p, new_s), None

            (params, states), _ = lax.scan(
                body, (params, states), (blocks, tick_ids))
            return params, states

        def block_step(params, states, blocks, consts, key, training):
            return block_core(params, states, blocks, consts, key,
                              training, None)

        # precision_level 2: force full-f32 MXU passes (the TPU
        # equivalent of the reference's level-2 multipartial
        # summation, config.py:244-247) — the decorator holds the
        # context during tracing, where dot precisions bind.
        if config_get(root.common.engine.precision_level, 0) >= 2:
            highest = jax.default_matmul_precision("highest")
            train_step = highest(train_step)
            infer_step = highest(infer_step)
            block_step = highest(block_step)
        self._train = jax.jit(train_step, donate_argnums=(0, 1))
        self._infer = jax.jit(infer_step, donate_argnums=(1,))
        self._block = jax.jit(block_step, donate_argnums=(0, 1))
        # Raw (un-jitted) callables for AOT export / compile checks.
        self._train_fn = train_step
        self._infer_fn = infer_step
        self._block_fn = block_step
        # Core closures reused by compile_population and the
        # hyper-traced per-member variants (population engine).
        self._core_ = (run_forward, apply_updates, block_core,
                       train_core)
        self._param_vecs = param_vecs
        self._state_vecs = state_vecs
        self._fingerprint = self.fingerprint()
        self._step_flops_ = {}
        self._hyper_progs_ = {}
        self._hyper_vals_ = {}
        # Truthy, and a token of THIS compile: programs.register()
        # lets a newer compile's program take a name over.
        self._compiled = object()

    @staticmethod
    def _block_sharding(vec, rank):
        """Where a batch vector's stacked block of ``rank`` dimensions
        lives: the vector's own sharding with the leading tick
        dimension left whole — the dispatch splits each tick's batch
        exactly as a single-tick step would.  A vector whose ticks
        stack as scalars (the sample class) is replicated.  None
        (default placement) without a mesh."""
        sharding = vec.sharding
        if sharding is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        spec = (None,) + tuple(sharding.spec)
        if len(spec) > rank:
            spec = ()
        return NamedSharding(sharding.mesh, PartitionSpec(*spec))

    def lower_last_block(self):
        """The block program of the newest dispatch, lowered again
        from the very arguments it ran on (params and state are read
        where they live now — same shapes, same layouts).
        ``.compile().as_text()`` is then the program that dispatch
        ran, so a caller can show what is IN it — ``chip_smoke.py``
        looks for the flash kernel's ``tpu_custom_call`` and, on a
        mesh, for the all-reduce and the batch split — instead of
        inferring it from a flag.  Nothing is donated or executed."""
        blocks, key, flag = self._last_block_
        params = {n: v.devmem for n, v in self._param_vecs.items()}
        states = {n: v.devmem for n, v in self._state_vecs.items()}
        consts = {str(id(v)): v.devmem for v in self.const_vectors}
        return self._block.lower(params, states, blocks, consts, key,
                                 flag)

    def invalidate(self):
        """Drops the compiled step so the next execute re-traces.
        Needed when a Python-constant hyperparameter baked into the
        trace changes without a shape change — e.g. the guardian's
        LR backoff rewriting ``gd.learning_rate`` mid-run."""
        self._compiled = None

    # -- hyper-traced variants (population lineages) -----------------------

    def _hyper_program(self, mode, names):
        """A jitted step whose GD hyperparameters ride as ONE traced
        f32 vector aligned with ``names`` — the single-member form of
        ``compile_population``'s traced hypers.  Population jobs carry
        per-member gene overrides (docs/population.md): baking them as
        Python constants would recompile the worker's step on every
        member switch; as traced inputs there is exactly one extra
        program per (mode, hyper-name layout)."""
        prog = self._hyper_progs_.get((mode, names))
        if prog is not None:
            return prog
        import jax
        from .analysis import runtime as _art
        _art.note_compile("step_h:%s:%s" % (mode, ",".join(names)))
        block_core, train_core = self._core_[2], self._core_[3]
        if mode == "train":
            def train_step_hyper(params, states, batch, consts, key,
                                 hvals):
                hypers = {n: hvals[i] for i, n in enumerate(names)}
                return train_core(params, states, batch, consts, key,
                                  hypers)
            fn = train_step_hyper
        else:
            def block_step_hyper(params, states, blocks, consts, key,
                                 training, hvals):
                hypers = {n: hvals[i] for i, n in enumerate(names)}
                return block_core(params, states, blocks, consts, key,
                                  training, hypers)
            fn = block_step_hyper
        if config_get(root.common.engine.precision_level, 0) >= 2:
            fn = jax.default_matmul_precision("highest")(fn)
        prog = jax.jit(fn, donate_argnums=(0, 1))
        self._hyper_progs_[(mode, names)] = prog
        return prog

    #: LRU bound on the cached per-value hyper vectors: every PBT
    #: exploit mints a fresh value tuple, so an uncapped cache would
    #: grow one device scalar vector per exploit for the process
    #: lifetime.  Live members re-serving the same genes stay hits;
    #: the cap only needs to exceed the concurrent member count.
    HYPER_VALS_CAP = 64

    def _hyper_values(self, hypers):
        """(names, device vector) for a ``{name: float}`` override
        dict, LRU-cached per distinct value tuple — the upload is
        explicit (device_put) and members re-serving the same genes
        reuse the same device array (strict_step-clean steady
        state)."""
        import jax
        import numpy
        names = tuple(sorted(hypers))
        key = (names, tuple(float(hypers[n]) for n in names))
        cached = self._hyper_vals_.pop(key, None)
        if cached is None:
            cached = jax.device_put(numpy.asarray(
                [hypers[n] for n in names], numpy.float32))
        # Re-insert at the newest end (dicts preserve insertion
        # order); evict from the oldest end past the cap.
        self._hyper_vals_[key] = cached
        while len(self._hyper_vals_) > self.HYPER_VALS_CAP:
            self._hyper_vals_.pop(next(iter(self._hyper_vals_)))
        return names, cached

    # -- execution ---------------------------------------------------------

    def _program_flops(self, step, key, fn, ticks, *args):
        """What is done ONCE per compiled program, cached under
        ``key`` — ("block", K) for block mode: a remainder block
        (epoch length % ticks_per_dispatch) is a different program
        with different FLOPs.  Registers the program under the open
        dispatch's ``step.program`` with ``observability.programs`` as
        a thunk that lowers it from its arguments' shapes, once (so
        ``scopes(name)`` can answer later, when the arrays are gone),
        and returns the per-dispatch FLOP estimate for the live MFU
        gauge.  The estimate calls that thunk (XLA HLO cost analysis,
        no compile) inside the dispatch's ``step.lower`` span and
        only runs when a peak FLOP/s is known for this device (the
        MFU denominator) — never on CPU test hardware, where the
        program is lowered only if somebody asks for its scopes."""
        import functools
        import jax
        from .observability import attribution, programs
        cached = self._step_flops_.get(key)
        if cached is not None:
            return cached or None  # 0.0 = no estimate
        # A sharding only where the array is committed to one, as
        # the dispatch sees its arguments: the dispatch then reuses
        # the module the estimate lowered.
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if x.committed else None), args)
        lower = functools.lru_cache(None)(lambda: fn.lower(*shapes))
        programs.register(step.program, lower, self.scope_units,
                          ticks, compiled_by=self._compiled)
        flops = None
        if attribution.enabled() and \
                attribution.peak_flops() is not None:
            with step.lower():
                flops = attribution.lowered_flops(lower())
        self._step_flops_[key] = flops or 0.0
        return flops

    @staticmethod
    def _sync_leaf(*trees):
        """A small output leaf to ``block_until_ready`` on — every
        output of one XLA computation completes together, so waiting
        on any leaf times the whole dispatch."""
        for tree in trees:
            if tree:
                return next(iter(tree.values()))
        return None

    def execute(self, key=None, training=True, hypers=None):
        from .observability import attribution
        if not self._compiled or self.fingerprint() != self._fingerprint:
            self.compile()
        mode = "train" if training else "infer"
        with attribution.dispatch(program=mode + "_step",
                                  ticks=1) as step:
            with step.upload():
                # Reading ``devmem`` uploads what the host wrote
                # since the last tick: the minibatch vectors.
                params = {n: v.devmem
                          for n, v in self._param_vecs.items()}
                states = {n: v.devmem
                          for n, v in self._state_vecs.items()}
                batch = {str(id(v)): v.devmem
                         for v in self.batch_vectors}
                consts = {str(id(v)): v.devmem
                          for v in self.const_vectors}
            if key is None:
                from . import prng
                key = prng.get().jax_key()
            # Hyper overrides apply to TRAIN dispatches only
            # (inference runs no update rule, so member genes cannot
            # matter there).
            hyper_args = ()
            step_fn = self._train if training else self._infer
            if training and hypers:
                names, hvals = self._hyper_values(hypers)
                step_fn = self._hyper_program("train", names)
                hyper_args = (hvals,)
                step.program = "train_step_hyper"
            step.flops = self._program_flops(
                step, mode, step_fn, 1,
                params, states, batch, consts, key, *hyper_args)
            with step.enqueue():
                if training:
                    new_params, new_states, outputs, metrics = \
                        step_fn(params, states, batch, consts, key,
                                *hyper_args)
                else:
                    new_states, outputs, metrics = step_fn(
                        params, states, batch, consts, key)
            if training:
                for n, v in self._param_vecs.items():
                    v.devmem = new_params[n]
            for n, v in self._state_vecs.items():
                v.devmem = new_states[n]
            for vec in self.persist_vectors:
                pid = str(id(vec))
                if pid in outputs:
                    vec.devmem = outputs[pid]
            step.wait(self._sync_leaf(metrics, new_states))
        return metrics

    def _training_flag(self, training):
        """The traced 0/1 training scalar as a CACHED device array:
        building it per dispatch with ``jnp.float32(...)`` is an
        implicit host→device scalar transfer every block — exactly
        what ``analysis.runtime.strict_step`` exists to forbid."""
        flags = getattr(self, "_train_flags_", None)
        if flags is None:
            import jax
            import numpy
            flags = self._train_flags_ = (
                jax.device_put(numpy.float32(0.0)),
                jax.device_put(numpy.float32(1.0)))
        return flags[1 if training else 0]

    def execute_block(self, blocks, training, key=None, hypers=None):
        """Dispatches K stacked ticks at once; ``blocks`` maps batch
        vector id → (K, ...) numpy/jax array."""
        import jax
        from .observability import attribution
        if not self._compiled or self.fingerprint() != self._fingerprint:
            self.compile()
        ticks = next(iter(blocks.values())).shape[0] if blocks else 1
        with attribution.dispatch(program="block_step",
                                  ticks=ticks) as step:
            params = {n: v.devmem for n, v in self._param_vecs.items()}
            states = {n: v.devmem for n, v in self._state_vecs.items()}
            consts = {str(id(v)): v.devmem
                      for v in self.const_vectors}
            if key is None:
                from . import prng
                key = prng.get().jax_key()
            # The stacked tick upload is EXPLICIT (device_put) so the
            # strict-step transfer guard distinguishes it from a
            # stray host-sync inside the hot loop — and it lands in
            # the batch vector's own layout, so a data-parallel mesh
            # splits every tick of the block instead of replicating
            # it.
            vecs = {str(id(v)): v for v in self.batch_vectors}
            with step.upload():
                blocks = {k: jax.device_put(
                    v, self._block_sharding(vecs[k], v.ndim))
                    for k, v in blocks.items()}
            flag = self._training_flag(training)
            # Hyper-traced block variant (population member genes):
            # the traced training flag already gates updates, so one
            # program serves train and validation blocks alike.
            hyper_args = ()
            block_fn = self._block
            if hypers:
                names, hvals = self._hyper_values(hypers)
                block_fn = self._hyper_program("block", names)
                hyper_args = (hvals,)
                step.program = "block_step_hyper"
            step.flops = self._program_flops(
                step, ("block", ticks), block_fn, ticks,
                params, states, blocks, consts, key, flag,
                *hyper_args)
            with step.enqueue():
                new_params, new_states = block_fn(
                    params, states, blocks, consts, key, flag,
                    *hyper_args)
            for n, v in self._param_vecs.items():
                v.devmem = new_params[n]
            for n, v in self._state_vecs.items():
                v.devmem = new_states[n]
            # What lower_last_block() re-lowers: a few small index
            # and mask arrays, none of them donated.
            self._last_block_ = (blocks, key, flag)
            step.wait(self._sync_leaf(new_states))
        return {}

    # -- population mode (vmapped hyperparameter sweeps) -------------------

    def compile_population(self, hyper_names):
        """Compiles a population block step: ``jax.vmap`` of the block
        core over (params, states, hypers), data broadcast.  One XLA
        program trains EVERY chromosome of a genetics generation
        simultaneously — hyperparameters become traced step inputs
        instead of baked constants, so there is exactly one compile
        per population instead of one per chromosome (SURVEY §7
        milestone 8: "population evaluation as vmapped short runs")."""
        import jax
        if not self._compiled:
            self.compile()
        block_core = self._core_[2]
        names = tuple(hyper_names)

        def pop_block(pop_params, pop_states, blocks, consts, key,
                      training, pop_hypers):
            def one(p, s, h):
                hypers = {n: h[i] for i, n in enumerate(names)}
                return block_core(p, s, blocks, consts, key,
                                  training, hypers)
            return jax.vmap(one)(pop_params, pop_states, pop_hypers)

        # Same precision contract as the sequential steps (compile()
        # wraps them under default_matmul_precision at level >= 2).
        if config_get(root.common.engine.precision_level, 0) >= 2:
            pop_block = jax.default_matmul_precision("highest")(
                pop_block)
        self._pop_block = jax.jit(pop_block, donate_argnums=(0, 1))
        self._pop_hyper_names = names
        return self._pop_block

    def population_arrays(self, pop_size):
        """Tiles the current params/states to a leading population
        axis (identical initial weights per chromosome — the same
        fairness the reference got by seeding every subprocess
        identically)."""
        import jax.numpy as jnp
        if not self._compiled:
            self.compile()
        params = {n: jnp.broadcast_to(
            v.devmem, (pop_size,) + tuple(v.shape))
            for n, v in self._param_vecs.items()}
        states = {n: jnp.broadcast_to(
            v.devmem, (pop_size,) + tuple(v.shape))
            for n, v in self._state_vecs.items()}
        return params, states


class AcceleratedWorkflow(Workflow):
    """Workflow whose traced inner loop runs as one jitted step
    (reference: accelerated_units.py:820 ``AcceleratedWorkflow``)."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super(AcceleratedWorkflow, self).__init__(workflow, **kwargs)
        self.fused = kwargs.get("fused", True)
        # >1 enables block mode: lax.scan over this many minibatches
        # per dispatch (latency-robust; one XLA computation per block).
        self.ticks_per_dispatch = kwargs.get("ticks_per_dispatch", 1)
        # Test mode: weights frozen — every tick runs the infer step
        # (ensemble testing / REST serving on a restored snapshot).
        self.frozen = kwargs.get("frozen", False)
        self.step_metrics = {}

    def init_unpickled(self):
        super(AcceleratedWorkflow, self).init_unpickled()
        self._compiler_ = None
        self._tick_id_ = 0
        self._step_done_tick_ = -1
        # Master-side job accounting, keyed by (epoch, class): the
        # epoch-boundary decision must wait until every job served
        # for that bucket has been answered or requeued, or late
        # updates would pollute the next epoch's metrics.
        self._inflight_by_slave_ = {}
        self._inflight_count_ = {}
        self._finish_pending_ = {}

    @property
    def compiler(self):
        if self._compiler_ is None:
            self._compiler_ = StepCompiler(self)
        return self._compiler_

    def begin_tick(self):
        """Called by the loader at the start of every minibatch tick."""
        self._tick_id_ += 1

    @property
    def training(self):
        """Whether the current tick is a training minibatch; loaders
        override the source of truth via link.  ``frozen`` (test mode)
        forces inference regardless of minibatch class."""
        if getattr(self, "frozen", False):
            return False
        for u in self.units:
            is_train = getattr(u, "minibatch_is_training", None)
            if is_train is not None:
                return bool(is_train)
        return True

    def execute_step(self, trigger):
        """Runs the fused step exactly once per tick, whichever traced
        unit's gate fires first."""
        if self._step_done_tick_ == self._tick_id_:
            return
        self._step_done_tick_ = self._tick_id_
        try:
            # step.nan chaos point (process-wide --chaos plan): the
            # poison rides the REAL minibatch through the REAL step.
            resilience.effective(None).check("step.nan")
        except resilience.InjectedStepNaN:
            self._poison_minibatch()
        from . import prng
        metrics = self.compiler.execute(
            key=prng.get().jax_key(), training=self.training)
        self.step_metrics = metrics

    def _poison_minibatch(self):
        """Feeds NaN into the current tick's minibatch mask (the
        loader rewrites it on the next serve, so exactly one tick is
        poisoned): loss and every gradient go NaN inside the fused
        step — the bad-record scenario the health sentinel exists to
        catch, exercised through production code."""
        import numpy
        loader = getattr(self, "loader", None)
        mask = getattr(loader, "minibatch_mask", None)
        if mask is None or not mask:
            self.warning("step.nan fired but the workflow has no "
                         "loader mask to poison — ignored")
            return
        mask.map_write()
        mask.mem[...] = numpy.nan
        self.warning("chaos: poisoned minibatch (epoch %s, class %s)",
                     getattr(loader, "epoch_number", "?"),
                     getattr(loader, "minibatch_class", "?"))

    def execute_block(self, blocks, training=None, key=None,
                      hypers=None):
        """Dispatches a stacked block of ticks (see
        StepCompiler.execute_block)."""
        if self._step_done_tick_ == self._tick_id_:
            return
        self._step_done_tick_ = self._tick_id_
        try:
            resilience.effective(None).check("step.nan")
        except resilience.InjectedStepNaN:
            # Block mode: the stacked arrays were already copied out
            # of the loader vectors — poison the first tick in-place.
            import numpy
            loader = getattr(self, "loader", None)
            mask = getattr(loader, "minibatch_mask", None)
            mask_id = str(id(mask)) if mask is not None else None
            if mask_id in blocks:
                blocks[mask_id][0, ...] = numpy.nan
                self.warning("chaos: poisoned first tick of block")
            else:
                self.warning("step.nan fired but the block carries "
                             "no loader mask to poison — ignored")
        from . import prng
        if training is None:
            training = self.training
        self.compiler.execute_block(
            blocks, training,
            key=key if key is not None else prng.get().jax_key(),
            hypers=hypers)
        self.step_metrics = {}

    def fetch_metrics(self):
        """Host values of the last step metrics (small transfers)."""
        import jax
        return {k: jax.device_get(v)
                for k, v in self.step_metrics.items()}

    # -- master–slave protocol bridging (reference: workflow.py:445-543
    # aggregated IDistributable; server/client drive these) ---------------

    @property
    def decision_unit(self):
        return getattr(self, "decision", None)

    def should_stop_serving(self):
        """Master-side serve predicate (consulted by Server)."""
        d = self.decision_unit
        if d is not None:
            return bool(d.complete)
        return bool(self.stopped)

    def generate_data_for_slave(self, slave=None):
        """A job = unit pieces (loader indices, layer trainables) plus
        the serve-time flags the master's decision needs echoed back
        with the update."""
        loader = getattr(self, "loader", None)
        # The serve below advances epoch_number when it hands out the
        # epoch's last minibatch, so the PRE-serve value is the only
        # label every job of this epoch agrees on — it keys the
        # (epoch, class) accounting bucket.
        epoch_key = loader.epoch_number if loader is not None else None
        data = super(AcceleratedWorkflow,
                     self).generate_data_for_slave(slave)
        if loader is not None:
            meta = {
                "minibatch_class": loader.minibatch_class,
                "last_minibatch": bool(loader.last_minibatch),
                "epoch_ended": bool(loader.epoch_ended),
                "epoch_number": loader.epoch_number,
                "epoch_key": epoch_key,
                # Staleness observability: which weights version this
                # job was generated from (delta-sync bookkeeping).
                "weights_version": self.weights_version,
            }
            data["__job__"] = meta
            key = (epoch_key, meta["minibatch_class"])
            self._inflight_by_slave_.setdefault(slave, []).append(
                (key, meta["last_minibatch"], meta["epoch_ended"]))
            self._inflight_count_[key] = \
                self._inflight_count_.get(key, 0) + 1
        return data

    def apply_data_from_master(self, data):
        super(AcceleratedWorkflow, self).apply_data_from_master(data)
        if data and "__job__" in data:
            self._job_meta_ = data["__job__"]

    def do_job(self, data, update, callback):
        """Worker-side job execution: apply master data, run the
        job's ticks, return updated trainables + metrics.  (The
        reference ran the whole gate-driven graph per job,
        workflow.py:545; with the fused step that collapses to one
        compiled call.)

        Single-tick jobs run one fused step; multi-tick jobs
        (``--job-ticks``) run ALL K minibatches as one scan-block
        dispatch (StepCompiler block mode) — one weight sync, one
        host→device upload, one dispatch per K ticks.  Block metrics
        come from the on-device epoch accumulator (reset before,
        read after — a single host sync per job)."""
        self.apply_data_from_master(data)
        if update is not None:
            self.apply_update_from_master(update)
        meta = getattr(self, "_job_meta_", None) or {}
        from .loader.base import TRAIN
        cls = meta.get("minibatch_class", TRAIN)
        training = cls == TRAIN
        loader = getattr(self, "loader", None)
        take_block = getattr(loader, "take_staged_block", None)
        block = take_block() if take_block is not None else None
        self.begin_tick()
        from . import prng
        # Population jobs (docs/population.md) pin the step RNG key:
        # the master draws it from the MEMBER's own key chain at serve
        # time, so a member's trajectory is bit-identical to the same
        # seeds trained standalone no matter how members interleave on
        # this worker.  Per-member gene overrides ride as traced
        # hypers the same way.  Ordinary sessions carry neither field
        # and keep drawing from the worker's local stream.
        key = meta.get("rng")
        if key is not None:
            import jax
            import numpy
            key = jax.device_put(numpy.ascontiguousarray(key))
        hypers = meta.get("hypers") or None
        if block is not None:
            host_metrics = self._run_job_block(block, cls, training,
                                               key=key, hypers=hypers)
        else:
            metrics = self.compiler.execute(
                key=key if key is not None else prng.get().jax_key(),
                training=training, hypers=hypers)
            import jax
            host_metrics = {k: float(jax.device_get(v))
                            for k, v in metrics.items()}
        result = self.generate_data_for_master()
        result["__metrics__"] = host_metrics
        # The echoed meta keys the master's decision bucket; the rng
        # key and hyper overrides were inputs, not accounting — keep
        # them off the update wire.
        result["__job__"] = {k: v for k, v in meta.items()
                             if k not in ("rng", "hypers")}
        callback(result)

    def _run_job_block(self, block, cls, training, key=None,
                       hypers=None):
        """Dispatches a multi-tick job block and returns aggregate
        metrics for the master's decision bucket ("ticks" marks them
        as pre-summed over K minibatches)."""
        ev = getattr(self, "evaluator", None)
        if ev is not None and hasattr(ev, "reset_epoch_acc"):
            ev.reset_epoch_acc(cls)
            if hasattr(ev, "reset_health_acc"):
                ev.reset_health_acc(cls)
        self.execute_block(block, training, key=key, hypers=hypers)
        metrics = {}
        if ev is not None and hasattr(ev, "read_epoch_acc"):
            row = ev.read_epoch_acc(cls)
            metrics = {"n_err": float(row[0]),
                       "n_valid": float(row[1]),
                       "loss": float(row[2]),
                       "ticks": float(row[3])}
            ev.reset_epoch_acc(cls)
            if hasattr(ev, "read_health_acc"):
                health = ev.read_health_acc(cls)
                metrics["nonfinite"] = float(health[0])
                metrics["grad_norm_sum"] = float(health[1])
                ev.reset_health_acc(cls)
        return metrics

    def apply_data_from_slave(self, data, slave=None):
        """Master-side update application + decision bookkeeping."""
        meta = (data or {}).pop("__job__", None)
        metrics = (data or {}).pop("__metrics__", None)
        if meta is not None:
            key = (meta.get("epoch_key"), meta.get("minibatch_class"))
            if not self._release_inflight(slave, key):
                # Untracked job: it was already dropped/requeued
                # (e.g. the watchdog blacklisted this worker) — the
                # batch will be re-trained, so both its deltas and
                # its metrics must be discarded entirely.
                return
            # Release the loader's pending-indices record for this
            # job (replies carry no loader piece, so the unit sweep
            # below never reaches it): one answered job = one FIFO
            # entry; what remains is exactly what a drop requeues.
            loader = getattr(self, "loader", None)
            if loader is not None:
                loader.apply_data_from_slave(None, slave)
        super(AcceleratedWorkflow, self).apply_data_from_slave(
            data, slave)
        try:
            d = self.decision_unit
            if d is None or meta is None:
                return
            cls = meta.get("minibatch_class")
            epoch = meta.get("epoch_key")
            key = (epoch, cls)
            if metrics is not None and \
                    hasattr(d, "accumulate_remote"):
                d.accumulate_remote(cls, metrics, epoch)
            if meta.get("last_minibatch"):
                # Don't finish the class yet: other jobs from the
                # same (epoch, class) may still be outstanding on
                # other workers; finishing now would let their
                # metrics leak into the next epoch's bucket.
                self._finish_pending_[key] = bool(
                    meta.get("epoch_ended"))
            self._maybe_finish_remote(key)
        finally:
            # Always after the release above — a deferred snapshot
            # must fire even for decision-less workflows.
            self._notify_if_drained()

    def total_inflight_jobs(self):
        """Outstanding worker jobs (served, not yet answered or
        requeued) — consulted by the snapshotter so checkpoints never
        race in-flight updates."""
        return sum(self._inflight_count_.values())

    def _notify_if_drained(self):
        if self._inflight_count_:
            return
        for unit in self.units:
            drained = getattr(unit, "on_jobs_drained", None)
            if drained is not None:
                drained()

    def _release_inflight(self, slave, key):
        """Removes one tracked job for (slave, key) and decrements
        the bucket count.  Returns False when no such job is tracked
        (already released by a drop)."""
        lst = self._inflight_by_slave_.get(slave)
        if not lst:
            return False
        for i, (k, _last, _ended) in enumerate(lst):
            if k == key:
                lst.pop(i)
                break
        else:
            return False
        if not lst:
            self._inflight_by_slave_.pop(slave, None)
        n = self._inflight_count_.get(key, 0)
        if n <= 1:
            self._inflight_count_.pop(key, None)
        else:
            self._inflight_count_[key] = n - 1
        return True

    def _maybe_finish_remote(self, key):
        """Fires the deferred epoch-boundary decision once every job
        served for (epoch, class) has been answered or requeued."""
        if key not in self._finish_pending_ or \
                self._inflight_count_.get(key, 0) > 0:
            return
        epoch_ended = self._finish_pending_.pop(key)
        d = self.decision_unit
        if d is None:
            return
        epoch, cls = key
        if hasattr(d, "finish_remote_class"):
            # (decision.epoch_number stays linked to the master
            # loader, which advanced at serve time.)
            d.finish_remote_class(cls, epoch)
            # Master-side health check: worker metrics carried the
            # sentinel's step_finite/grad_norm, the decision just
            # folded them — the guardian reacts exactly as it would
            # standalone (a rollback restores the MASTER's Vectors,
            # which ship to workers with the next jobs).
            guardian = getattr(self, "guardian", None)
            if guardian is not None and \
                    hasattr(guardian, "check_class"):
                guardian.check_class(cls)
            if epoch_ended:
                d.on_epoch_ended()

    def drop_slave(self, slave=None):
        """A dropped worker's in-flight jobs are requeued by the
        loader (failed-minibatch queue); their accounting must be
        released too, or the epoch-boundary decision would wait on
        updates that will never arrive.  If the dropped worker held
        the epoch's LAST minibatch, the boundary is restored here —
        the loader re-serves that batch with last_minibatch=False
        (its metrics land in the successor bucket), so without this
        the epoch would never close and training would run long."""
        super(AcceleratedWorkflow, self).drop_slave(slave)
        entries = list(self._inflight_by_slave_.get(slave, ()))
        for key, was_last, epoch_ended in entries:
            self._release_inflight(slave, key)
            if was_last:
                self._finish_pending_.setdefault(key, epoch_ended)
            self._maybe_finish_remote(key)
        self._notify_if_drained()
