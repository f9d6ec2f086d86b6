"""Mesh construction and data-parallel sharding.

This replaces the reference's master–slave data-parallel engine
(reference: veles/server.py, veles/client.py, veles/distributable.py —
minibatch indices sharded to slaves over ZeroMQ, weights shipped in job
pickles, gradients aggregated in ``apply_data_from_slave``) with the
TPU-native formulation:

  * the device mesh (`jax.sharding.Mesh`) spans all local chips (and,
    multi-host, all processes' chips via ``jax.distributed``);
  * the LOADER still thinks in minibatch indices — exactly like the
    reference coordinator (loader/base.py:629-661) — but instead of
    mailing index lists to worker processes, the index array is laid
    out along the mesh's ``data`` axis, so each chip gathers and
    processes its shard of the global minibatch;
  * parameters are replicated; ``jax.grad`` of the mean loss over a
    sharded batch makes XLA insert the gradient all-reduce (psum) over
    ICI — the explicit ``apply_data_from_slave`` aggregation loop
    disappears into the compiled step.

Elasticity note: the reference drops slaves and requeues their
minibatches (server.py:315-338).  SPMD equivalents operate at mesh
granularity: on chip loss :func:`rebuild_mesh` re-forms the mesh over
the survivors, re-places every step tensor, requeues the interrupted
minibatch (the failed-minibatch queue survives as-is), and the next
tick compiles for the new topology.
"""

from jax.sharding import Mesh, NamedSharding, PartitionSpec


def make_mesh(devices=None, axes=None):
    """Builds a Mesh; ``axes`` maps name → size with -1 = remaining."""
    import jax
    import numpy as np
    if devices is None:
        devices = jax.devices()
    if axes is None:
        axes = {"data": len(devices)}
    names = list(axes)
    sizes = [axes[n] for n in names]
    if -1 in sizes:
        known = 1
        for s in sizes:
            if s != -1:
                known *= s
        sizes[sizes.index(-1)] = len(devices) // known
    count = 1
    for s in sizes:
        count *= s
    return Mesh(np.array(devices[:count]).reshape(sizes), names)


def apply_dp_sharding(workflow, mesh, axis="data"):
    """Marks the workflow's step tensors for data parallelism:
    per-tick batch vectors are sharded along ``axis`` (dim 0), params /
    optimizer state / dataset originals are replicated.

    After this, the SAME compiled step runs 1-chip or N-chip — XLA
    inserts the gradient psum over ICI because the loss is a mean over
    a sharded batch with replicated params.
    """
    compiler = workflow.compiler
    compiler.analyze()
    replicated = NamedSharding(mesh, PartitionSpec())
    sharded = NamedSharding(mesh, PartitionSpec(axis))
    n = mesh.shape[axis]
    for vec in compiler.batch_vectors:
        shape = vec.shape
        if shape and len(shape) >= 1 and shape[0] % n == 0:
            vec.sharding = sharded
        else:
            vec.sharding = replicated
    for vec in compiler._collect("params").values():
        vec.sharding = replicated
    for vec in compiler._collect("state").values():
        vec.sharding = replicated
    for vec in compiler.const_vectors:
        vec.sharding = replicated
    # Persisted step outputs are batch-shaped: shard them like batch
    # vectors so host reads after a rebuild never touch buffers on a
    # departed device set.
    for vec in compiler.persist_vectors:
        shape = vec.shape
        if shape and len(shape) >= 1 and shape[0] % n == 0:
            vec.sharding = sharded
        else:
            vec.sharding = replicated
    workflow.mesh = mesh
    workflow._parallel_style_ = ("dp", axis)
    return workflow


#: Megatron column/row pairing of the OPT block's leaves, by NAME
#: (fused layout included): how :func:`_transformer_tp_plan` lays
#: the trailing dims of each on the model axis.
_OPT_TP_LEAVES = {
    "wq": "col", "wk": "col", "wv": "col", "wo": "row",
    "bq": "vec", "bk": "vec", "bv": "vec", "bo": "rep",
    "wqkv": "col", "bqkv": "vec",
    "ln1_g": "rep", "ln1_b": "rep", "ln2_g": "rep", "ln2_b": "rep",
    "w1": "col", "b1": "vec", "w2": "row", "b2": "rep",
}


def _transformer_tp_plan(unit, n_model, model_axis):
    """Megatron-style PartitionSpecs for one transformer-family unit,
    or None when its geometry does not divide the model axis.

    The layout is the standard column→row pairing, expressed as
    GSPMD annotations instead of manual collectives (XLA inserts the
    all-reduce after each row-parallel matmul), and it is chosen by
    the unit's parameter NAMES (:data:`_OPT_TP_LEAVES`), not by its
    class:

      * attention: wq/wk/wv COLUMN-sharded (each model shard computes
        E/n output features = H/n whole heads; the (B,S,H,D) reshape
        keeps the head dim sharded because n | H), wo ROW-sharded
        (partial sums psum to a replicated residual); the FUSED
        (E, 3E) wqkv shards its 3E column dim the same way — its
        head-major layout ([q_h|k_h|v_h] per head) means a contiguous
        3E/n column shard is H/n whole heads' q/k/v, so the
        (B,S,H,3,D) reshape keeps the head dim sharded and the q/k/v
        split indexes a replicated axis;
      * MLP: w1 column, w2 row — the hidden dim lives sharded, the
        residual stream stays replicated;
      * a layer holding a name the plan does not know — the short
        convolution's ``w_in`` / ``w_conv``, a gated MLP's ``w3``, an
        expert layer's ``router``, grouped key/value heads' narrower
        ``wk`` — stays replicated whole (correct, merely not
        tensor-parallel);
      * pipelined stacks: same specs with the leading stage dim left
        to the STAGE axis;
      * LMHead: vocab (output) column-sharded — the loss's
        log-softmax reduction over the sharded vocab becomes an XLA
        collective;
      * Embedding: embed dim sharded (the vocab-dim gather stays
        local per shard); a TIED head then contracts over the sharded
        embed dim — a row-parallel linear ending in a psum.
    """
    from ..znicz.attention import Embedding, LMHead

    def spec(*axes):
        return PartitionSpec(*axes)

    names = set(getattr(unit, "params", ()))
    if "wo" in names:
        trainables = unit.trainables
        if not trainables:
            # Pre-initialize sharding (no parameter allocated yet):
            # degrade to replicated instead of raising.
            return None
        wk = trainables.get("wk")
        if not names <= set(_OPT_TP_LEAVES) or \
                (wk is not None and
                 wk.shape != trainables["wo"].shape):
            return None
        # A stage-stacked unit's leaves carry a leading dim the
        # STAGE axis owns.
        lead = (None,) * (len(trainables["wo"].shape) - 2)
        embed, hidden = trainables["w1"].shape[-2:]
        if embed % n_model or hidden % n_model or \
                unit.n_heads % n_model:
            return None
        axes = {"col": (None, model_axis), "row": (model_axis, None),
                "vec": (model_axis,), "rep": ()}
        return {name: spec(*(lead + axes[_OPT_TP_LEAVES[name]]))
                for name in trainables}
    if isinstance(unit, LMHead):
        plan = {}
        w = unit.trainables.get("weights")
        if w and w.shape[-1] % n_model == 0:
            plan["weights"] = spec(None, model_axis)
            b = unit.trainables.get("bias")
            if b:
                plan["bias"] = spec(model_axis)
        return plan or None
    if isinstance(unit, Embedding):
        w = unit.trainables.get("weights")
        if w is None or not w or w.shape[-1] % n_model:
            return None
        plan = {"weights": spec(None, model_axis)}
        if unit.pos:
            plan["pos"] = spec(None, model_axis)
        return plan
    return None


def apply_dp_tp_sharding(workflow, mesh, data_axis="data",
                         model_axis="model"):
    """Data × tensor parallelism over a 2-axis mesh — the "natural
    XLA extension" beyond the reference's DP-only engine (SURVEY
    §2.3): dense layers' weight matrices shard along their OUTPUT
    dimension on ``model_axis`` (so each model-shard computes a slice
    of the layer's neurons from the full input), the transformer
    family gets the full Megatron-style column/row pairing
    (:func:`_transformer_tp_plan`), optimizer momentum shards
    identically, batches shard on ``data_axis``.  No manual
    collectives: XLA's sharding propagation inserts the
    all-gather/reduce-scatter pattern between layers and the gradient
    psum over the data axis — the same compiled step, just annotated
    differently.

    Layers whose geometry does not divide the model-axis size stay
    replicated (correct, merely less parallel).
    """
    from ..znicz.all2all import All2All

    apply_dp_sharding(workflow, mesh, axis=data_axis)
    n_model = mesh.shape[model_axis]
    col_sharded = NamedSharding(mesh,
                                PartitionSpec(None, model_axis))
    vec_sharded = NamedSharding(mesh, PartitionSpec(model_axis))
    gd_of = {gd.target: gd
             for gd in getattr(workflow, "gds", [])
             if getattr(gd, "target", None) is not None}

    def shard_slots_by_name(unit, gd):
        """Optimizer slots mirror their parameter BY NAME
        (velocity_<param>, adam_m_<param>, … — any registered prefix,
        znicz.optimizers.param_of_slot) — shape matching alone could
        collide (e.g. wq/wk/wv are all (E, E)).  Non-mirror slots
        (Adam's scalar step counters) stay replicated."""
        if gd is None:
            return
        from ..znicz.optimizers import param_of_slot
        for name, vec in gd.tstate.items():
            pname = param_of_slot(name) or name
            target = unit.trainables.get(pname)
            if vec and target is not None and \
                    tuple(vec.shape) == tuple(target.shape):
                vec.sharding = target.sharding

    sharded_layers = 0
    for unit in getattr(workflow, "forwards", []):
        plan = _transformer_tp_plan(unit, n_model, model_axis)
        if plan:
            for pname, pspec in plan.items():
                unit.trainables[pname].sharding = \
                    NamedSharding(mesh, pspec)
            shard_slots_by_name(unit, gd_of.get(unit))
            if getattr(unit, "n_heads", 0) and \
                    unit.n_heads % n_model == 0:
                # The column-sharded projections leave whole heads
                # on each model shard; attention that must name its
                # own layout (ops.attention.mesh_attention: a Mosaic
                # kernel under shard_map) keeps them there.
                unit.head_axis = model_axis
            sharded_layers += 1
            continue
        if not isinstance(unit, All2All):
            continue
        weights = unit.trainables.get("weights")
        if weights is None or not weights or \
                weights.shape[-1] % n_model:
            continue
        weights.sharding = col_sharded
        bias = unit.trainables.get("bias")
        if bias:
            bias.sharding = vec_sharded
        sharded_layers += 1
        gd = gd_of.get(unit)
        if gd is not None:
            # Optimizer slots that MIRROR a parameter's shape ride
            # its sharding (velocity_weights ≡ weights); anything
            # non-mirror stays replicated — shape matching cannot
            # mis-shard the way name/rank heuristics can.
            for name, vec in gd.tstate.items():
                if not vec:
                    continue
                if tuple(vec.shape) == tuple(weights.shape):
                    vec.sharding = col_sharded
                elif bias and \
                        tuple(vec.shape) == tuple(bias.shape):
                    vec.sharding = vec_sharded
    if sharded_layers == 0:
        workflow.warning(
            "apply_dp_tp_sharding: no layer geometry divides the "
            "model axis (%d) — the workflow runs data-parallel only"
            % n_model)
    workflow._parallel_style_ = ("dp_tp", data_axis, model_axis)
    return workflow


def apply_dp_tp_sp_sharding(workflow, mesh, data_axis="data",
                            model_axis="model", seq_axis="seq",
                            sp_kernel=None):
    """COMPOSED 3-axis layout: data × tensor × sequence parallelism.

    The Megatron column/row weight sharding comes from
    :func:`apply_dp_tp_sharding`; every transformer unit that
    declares this ``seq_axis`` additionally runs its attention
    sequence-parallel (ring or Ulysses) INSIDE a shard_map whose
    specs now carry the model axis on the HEAD dim — attention is
    per-head, so head-sharding composes with the sequence collectives
    for free: the ring's ppermutes involve only ``seq_axis``, each
    model shard rotates only its own heads' k/v — and the ring-flash
    body (``sp_ring_kernel`` "auto" default) runs the Pallas kernel
    on exactly that local-heads shard, so tp × sp × flash composes
    with no extra collective.  ``sp_kernel`` overrides the knob on
    every sequence-parallel unit ("xla" forces the lax scan,
    "pallas" the flash body — the dryrun's self-verify handle).

    Mesh shape: (data, model, seq).  Activations (B, S, H, D) inside
    attention are sharded (data, seq, model, None).
    """
    apply_dp_tp_sharding(workflow, mesh, data_axis=data_axis,
                         model_axis=model_axis)
    n_model = mesh.shape[model_axis]
    sp_blocks = 0
    for unit in getattr(workflow, "forwards", []):
        if getattr(unit, "seq_axis", None) != seq_axis:
            continue
        unit.batch_axis = data_axis
        if getattr(unit, "n_heads", 0) % n_model == 0:
            unit.head_axis = model_axis
        if sp_kernel is not None:
            unit.sp_kernel = sp_kernel
        sp_blocks += 1
    if sp_blocks == 0:
        workflow.warning(
            "apply_dp_tp_sp_sharding: no forward unit declares "
            "seq_axis=%r — attention runs without sequence "
            "parallelism" % seq_axis)
    workflow._parallel_style_ = ("dp_tp_sp", data_axis, model_axis,
                                 seq_axis)
    return workflow


def apply_dp_sp_sharding(workflow, mesh, data_axis="data",
                         seq_axis="seq"):
    """Data × sequence parallelism — the long-context layout
    (SURVEY §5: absent in the 2013-15 reference; first-class here):
    batches shard on ``data_axis`` exactly as in DP, and every
    decoder layer whose ``seq_axis`` names a mesh axis runs its
    attention as a ``shard_map`` ring over that axis
    (ops/attention.py ``ring_attention`` — k/v shards rotate over ICI
    with a streaming-softmax accumulator, so per-device activation
    memory scales as S/N and no device ever holds full K/V).

    Params stay replicated; gradients of the mean loss psum over the
    data axis via GSPMD as in DP; the ring's own collectives are
    explicit ppermutes inserted by the unit.
    """
    apply_dp_sharding(workflow, mesh, axis=data_axis)
    ring_blocks = 0
    for unit in getattr(workflow, "forwards", []):
        if getattr(unit, "seq_axis", None) == seq_axis:
            unit.batch_axis = data_axis
            ring_blocks += 1
    if ring_blocks == 0:
        workflow.warning(
            "apply_dp_sp_sharding: no forward unit declares "
            "seq_axis=%r — the workflow runs data-parallel only"
            % seq_axis)
    workflow._parallel_style_ = ("dp_sp", data_axis, seq_axis)
    return workflow


def apply_dp_pp_sharding(workflow, mesh, data_axis="data",
                         stage_axis="stage"):
    """Data × PIPELINE parallelism (znicz/attention.py
    PipelinedTransformerStack + ops/pipeline.py ``gpipe``): each
    stack's stage-stacked parameters (leading ``n_blocks`` dim) and
    their mirroring optimizer slots shard one stage per device along
    ``stage_axis``; inside the step the stack runs the collective-
    permute pipeline over that axis with microbatching.  Everything
    else follows DP.

    Stacks whose ``n_blocks`` does not divide the stage-axis size
    stay replicated (they then run the sequential scan — correct,
    merely not pipelined).
    """
    apply_dp_sharding(workflow, mesh, axis=data_axis)
    if _overlay_stage_axis(workflow, mesh, stage_axis) == 0:
        workflow.warning(
            "apply_dp_pp_sharding: no pipelined stack's n_blocks "
            "divides the stage axis (%d) — the workflow runs "
            "data-parallel only" % mesh.shape[stage_axis])
    workflow._parallel_style_ = ("dp_pp", data_axis, stage_axis)
    return workflow


def _overlay_stage_axis(workflow, mesh, stage_axis):
    """The leading-dim overlay of the dp×pp applier AND its ×tp
    composition: for every unit exposing ``stage_params`` whose
    ``n_blocks`` divides the ``stage_axis`` size, put ``stage_axis``
    on dim 0 ON TOP of whatever trailing axes are already assigned
    (all-None after plain dp, the Megatron column/row pairing after
    :func:`apply_dp_tp_sharding`), then re-point the mirroring
    optimizer slots by name (``znicz.optimizers.param_of_slot`` —
    shape matching alone could collide).  Returns the number of
    units overlaid."""
    from ..znicz.optimizers import param_of_slot
    n_lead = mesh.shape[stage_axis]
    gd_of = {gd.target: gd
             for gd in getattr(workflow, "gds", [])
             if getattr(gd, "target", None) is not None}
    overlaid = 0
    for unit in getattr(workflow, "forwards", []):
        stacked = getattr(unit, "stage_params", None)
        if stacked is None or unit.n_blocks % n_lead:
            continue
        for vec in stacked.values():
            cur = ()
            if isinstance(vec.sharding, NamedSharding):
                cur = tuple(vec.sharding.spec)
            axes = list(cur) + [None] * (len(vec.shape) - len(cur))
            axes[0] = stage_axis
            vec.sharding = NamedSharding(mesh, PartitionSpec(*axes))
        overlaid += 1
        gd = gd_of.get(unit)
        if gd is not None:
            for name, vec in gd.tstate.items():
                pname = param_of_slot(name) or name
                target = stacked.get(pname)
                if vec and target is not None and \
                        tuple(vec.shape) == tuple(target.shape):
                    vec.sharding = target.sharding
    return overlaid


def apply_dp_pp_tp_sharding(workflow, mesh, data_axis="data",
                            stage_axis="stage", model_axis="model"):
    """COMPOSED 3-axis layout: data × pipeline × tensor parallelism
    (ISSUE 12).  :func:`apply_dp_tp_sharding` lays the Megatron
    column/row pairing on every transformer unit — the pipelined
    stack's plan deliberately leaves dim 0 alone — then the stage
    axis overlays the stacks' leading dim, so each device stores
    1/(pp·tp) of the stack.  Inside the step the stack runs its
    ppermute schedule over ``stage_axis`` via shard_map whose
    in_specs name only the stage axis: XLA re-gathers the model-dim
    shards at pipeline entry (storage stays sharded; the embedding/
    LM-head compute outside the stack is genuinely tensor-parallel).
    ``dryrun_multichip`` self-verifies the composition against the
    1-device step."""
    apply_dp_tp_sharding(workflow, mesh, data_axis=data_axis,
                         model_axis=model_axis)
    if _overlay_stage_axis(workflow, mesh, stage_axis) == 0:
        workflow.warning(
            "apply_dp_pp_tp_sharding: no pipelined stack's n_blocks "
            "divides the stage axis (%d) — the workflow runs dp×tp "
            "only" % mesh.shape[stage_axis])
    workflow._parallel_style_ = ("dp_pp_tp", data_axis, stage_axis,
                                 model_axis)
    return workflow


def apply_zero_sharding(workflow, mesh=None, data_axis="data",
                        level=1):
    """ZeRO-1/2 optimizer-state sharding over the ``data`` axis —
    call AFTER one of the ``apply_*_sharding`` appliers (it composes
    with all of them).

    * **Level 1** re-annotates every GD unit's optimizer slot whose
      leading dimension divides the data-axis size: dim 0 gains the
      ``data`` axis ON TOP of whatever model/stage axes the
      style applier put on the other dims, so each dp rank
      persistently stores 1/dp of the optimizer state in HBM.  XLA's
      sharding propagation then computes the slot update shard-local
      and all-gathers the parameter delta — the ZeRO-1 dataflow
      (update your shard, all-gather params) expressed as GSPMD
      annotations instead of hand-written ``shard_map``/
      ``psum_scatter`` collectives (same collectives on the wire,
      zero bespoke step code, and it composes with dp×tp for free).
    * **Level 2** additionally records a sharding constraint for each
      slot-backed gradient (consumed by ``StepCompiler``'s
      ``apply_updates``), so the gradient all-reduce over ``data``
      lowers to a reduce-scatter feeding the sharded update instead
      of a full all-reduce followed by a slice — the ZeRO-2
      grad-shard variant.

    Slots whose geometry does not divide the axis — or whose dim 0
    is already owned by the stage axis — stay as the style
    applier left them (correct, merely not ZeRO-sharded); scalar
    slots (Adam's step counters) always stay replicated.

    Numerics: allclose, not bit-identical — collective reduction
    orders move; ``dryrun_multichip`` self-verifies sharded ==
    1-device under the usual per-precision tolerances.

    Snapshots are UNAFFECTED in shape: Vector pickling gathers the
    full host value regardless of layout, so a ZeRO snapshot restores
    at any dp (re-shard on resume = re-run the appliers + this).
    """
    from ..znicz.nn_units import GradientDescentBase
    from ..znicz.optimizers import param_of_slot
    if mesh is None:
        mesh = getattr(workflow, "mesh", None)
    if mesh is None or data_axis not in mesh.shape:
        raise ValueError(
            "apply_zero_sharding needs a mesh carrying axis %r — "
            "apply a dp/dp×tp/... sharding first" % data_axis)
    dp = mesh.shape[data_axis]
    grad_specs = {}
    compiler = workflow.compiler
    compiler.analyze()
    sharded = 0
    for gd in [u for u in workflow.units
               if isinstance(u, GradientDescentBase)]:
        target = getattr(gd, "target", None)
        for name, vec in gd.tstate.items():
            if not vec or not vec.shape or len(vec.shape) < 1:
                continue  # scalar slots stay replicated
            if dp <= 1 or vec.shape[0] % dp:
                continue
            cur = ()
            if isinstance(vec.sharding, NamedSharding):
                cur = tuple(vec.sharding.spec)
            axes = list(cur) + [None] * (len(vec.shape) - len(cur))
            if axes[0] is not None:
                continue  # dim 0 already owned (stage axis)
            axes[0] = data_axis
            spec = NamedSharding(mesh, PartitionSpec(*axes))
            vec.sharding = spec
            sharded += 1
            if level >= 2 and target is not None:
                pattr = param_of_slot(name)
                pvec = target.trainables.get(pattr) if pattr else None
                if pvec is not None and \
                        tuple(pvec.shape) == tuple(vec.shape):
                    grad_specs[compiler.param_name(target, pattr)] = \
                        spec
    if sharded == 0:
        workflow.warning(
            "apply_zero_sharding: no optimizer slot's leading "
            "dimension divides the data axis (%d) — optimizer state "
            "stays replicated" % dp)
    workflow._zero_grad_shardings_ = grad_specs
    # The recorded dp feeds the optimizer.shard_frac gauge: when
    # nothing sharded, each rank still stores the FULL state — the
    # gauge must say 1.0, not 1/dp (level is kept so rebuild_mesh
    # retries ZeRO over whatever mesh the survivors form).
    workflow._zero_ = (level, dp if sharded else 1, data_axis)
    # The compiled step (and its captured grad constraints)
    # specialized on the old layout.
    compiler._compiled = None
    return workflow


#: Style name → the sharding applier re-run over the shrunk mesh.
#: (2-axis styles all carry (name, data_axis, other_axis); the 3-axis
#: dp_tp_sp carries (name, data, model, seq).)
def _style_appliers():
    return {
        "dp_tp": apply_dp_tp_sharding,
        "dp_sp": apply_dp_sp_sharding,
        "dp_pp": apply_dp_pp_sharding,
    }


def _seq_axis_fits(workflow, n_seq):
    """Whether every sequence-parallel unit can run over an n_seq-wide
    seq axis: the shard_map specs need S % n_seq == 0, and Ulysses
    additionally needs heads % n_seq == 0.  Unlike tp/pp (whose
    appliers degrade to replicated), an sp unit runs its shard_map
    unconditionally once the mesh carries the axis — an unvalidated
    rebuild would crash the next step instead of degrading."""
    for u in getattr(workflow, "forwards", []):
        if not getattr(u, "seq_axis", None):
            continue
        shape = getattr(getattr(u, "input", None), "shape", None)
        if shape and len(shape) >= 2 and shape[1] % n_seq:
            return False
        if getattr(u, "sp_mode", None) == "ulysses" and \
                getattr(u, "n_heads", 0) % n_seq:
            return False
    return True


def _rebuild_styled_mesh(workflow, surviving_devices, n, style):
    """Re-forms the workflow's non-DP layout over the survivors when
    divisibility allows; returns the new mesh or None (→ dp
    fallback).  On a shrink, every style preserves the OLD data-axis
    size first (so the model/seq/stage axis — which layer
    geometry was validated against — shrinks as little as possible),
    then tries data=2; the non-data axis must keep >= 2 devices or
    the style is meaningless.  On GROWTH the preference inverts: the
    non-data axis keeps its exact old size and the data axis widens.
    A 3-axis style that no longer divides falls to a 2-axis partial
    fit (keep tp, then keep sp) before the DP cliff.

    Host-syncing sharded params during the re-place gathers across
    the OLD device set — fine while the runtime still serves reads,
    the documented precondition."""
    old_mesh = getattr(workflow, "mesh", None)
    if style[0] in _style_appliers() and len(style) == 3:
        name, data_axis, other_axis = style
        old_data = (old_mesh.shape.get(data_axis)
                    if old_mesh is not None else None)
        old_other = (old_mesh.shape.get(other_axis)
                     if old_mesh is not None else None)
        candidates = [old_data, 2]
        if old_data and old_other and n > old_data * old_other \
                and n % old_other == 0:
            # GROWTH: joiners widen the data axis while the non-data
            # axis keeps its exact old size — layer geometry was
            # validated against that size, and the new capacity
            # belongs to batch throughput, not to an unvalidated
            # re-split of the model/seq/stage plane.
            candidates.insert(0, n // old_other)
        seen = set()
        for candidate in candidates:
            if not candidate or candidate in seen:
                continue
            seen.add(candidate)
            if n % candidate == 0 and n // candidate >= 2:
                if name == "dp_sp" and \
                        not _seq_axis_fits(workflow, n // candidate):
                    continue
                mesh = make_mesh(surviving_devices,
                                 {data_axis: candidate,
                                  other_axis: n // candidate})
                kwargs = {"data_axis": data_axis,
                          {"dp_tp": "model_axis",
                           "dp_sp": "seq_axis",
                           "dp_pp": "stage_axis"}[name]: other_axis}
                _style_appliers()[name](workflow, mesh, **kwargs)
                return mesh
        return None
    if style[0] == "dp_tp_sp" and len(style) == 4:
        # Exact fit first: model and seq sizes preserved (both were
        # validated against layer geometry / sequence length), the
        # data axis alone absorbing the change.
        _, data_axis, model_axis, seq_axis = style
        if old_mesh is None:
            return None
        m = old_mesh.shape.get(model_axis)
        s = old_mesh.shape.get(seq_axis)
        if not m or not s:
            return None
        if n % (m * s) == 0 and n // (m * s) >= 1 and \
                _seq_axis_fits(workflow, s):
            mesh = make_mesh(surviving_devices,
                             {data_axis: n // (m * s),
                              model_axis: m, seq_axis: s})
            apply_dp_tp_sp_sharding(workflow, mesh,
                                    data_axis=data_axis,
                                    model_axis=model_axis,
                                    seq_axis=seq_axis)
            return mesh
        # Partial fit: the survivors cannot hold the exact m×s plane
        # — shrink ONE axis at a time before the DP cliff wipes both.
        # Keep the tensor axis (drop sequence parallelism) first:
        # tp shards weights, so losing it costs per-chip memory,
        # while losing sp only costs long-sequence activation
        # headroom.  Then keep the seq axis (drop tp).  The applier
        # records the surviving 2-axis style, so later rebuilds walk
        # from what actually survived.
        if m >= 2 and n % m == 0 and n // m >= 1:
            mesh = make_mesh(surviving_devices,
                             {data_axis: n // m, model_axis: m})
            apply_dp_tp_sharding(workflow, mesh,
                                 data_axis=data_axis,
                                 model_axis=model_axis)
            return mesh
        if s >= 2 and n % s == 0 and n // s >= 1 and \
                _seq_axis_fits(workflow, s):
            mesh = make_mesh(surviving_devices,
                             {data_axis: n // s, seq_axis: s})
            apply_dp_sp_sharding(workflow, mesh,
                                 data_axis=data_axis,
                                 seq_axis=seq_axis)
            return mesh
        return None
    return None


def rebuild_mesh(workflow, surviving_devices=None, axis="data",
                 requeue_in_flight=True, epoch=None):
    """Elastic membership change at mesh granularity — SHRINK (the
    drop_slave+requeue equivalent of the reference's server.py:315-338)
    and GROWTH alike: re-form the mesh over the new device set,
    re-place every step tensor (the Vector sharding setter host-syncs
    and frees old buffers when its sharding changes), requeue
    whatever the loader had in flight — the whole block in block
    mode — and force the step to recompile for the new topology.

    ``epoch`` stamps the workflow with the caller's membership epoch
    (the server's ``FleetScheduler`` epoch for a fleet-driven
    rebuild); without one a local monotonic count advances, so every
    rebuild is a numbered event either way.  The stamp is published
    as the ``membership.epoch`` gauge and counted under
    ``membership.rebuilds`` / ``membership.grow`` /
    ``membership.shrink``.

    ``requeue_in_flight`` gives AT-LEAST-ONCE semantics: without a
    commit marker there is no telling whether the interrupted
    dispatch landed, so its minibatches re-train (pass False when the
    caller knows the last step committed — e.g. loss detected between
    epochs).  The in-flight record clears either way, so repeated
    rebuilds (progressive loss 8→4→2) never double-queue.

    Precondition: the jax runtime is still serving reads — parameter
    buffers are replicated, and the host-sync path reads a LOCAL
    addressable shard for replicated arrays (memory._host_sync), so a
    healthy chip sources them; a lost chip only loses its batch
    shard, which the failed-minibatch queue re-serves.  When the
    runtime itself died with the chip (the common real-hardware
    failure), recovery is snapshot-resume (snapshotter.py), not this
    in-process path.
    """
    import jax
    from ..memory import host_resharding
    if surviving_devices is None:
        surviving_devices = jax.devices()
    n = len(surviving_devices)
    prior = getattr(workflow, "mesh", None)
    old_n = int(prior.devices.size) if prior is not None else None
    style = getattr(workflow, "_parallel_style_", None) or \
        ("dp", axis)
    # Recovery context: every re-placement must round-trip through
    # the host (reads a healthy replica shard) — a device-to-device
    # reshard sourced from the departed chips could fail
    # asynchronously past any except clause.
    with host_resharding():
        mesh = _rebuild_styled_mesh(workflow, surviving_devices, n,
                                    style)
        if mesh is None:
            if style[0] != "dp":
                workflow.warning(
                    "rebuild_mesh: %d survivors cannot hold the %s "
                    "layout — falling back to data parallelism"
                    % (n, style[0]))
            mesh = make_mesh(surviving_devices, {axis: n})
            apply_dp_sharding(workflow, mesh, axis=axis)
        # ZeRO re-applies over the shrunk mesh (the style appliers
        # just reset every slot to its non-ZeRO layout); the data
        # axis may now be a different size — slots re-shard 1/dp'.
        zero = getattr(workflow, "_zero_", None)
        if zero:
            level, _old_dp, zaxis = zero
            apply_zero_sharding(
                workflow, mesh,
                data_axis=zaxis if zaxis in mesh.shape else axis,
                level=level)
    # The jitted step specialized on the old device set/shardings.
    workflow.compiler._compiled = False
    loader = getattr(workflow, "loader", None)
    if loader is not None:
        in_flight = list(getattr(loader, "_in_flight_", []))
        loader._in_flight_ = []
        if requeue_in_flight:
            loader.failed_minibatches.extend(in_flight)
        # A streamed loader's prefetched block holds device arrays
        # placed on the PRE-rebuild device set (and its indices were
        # just requeued above) — drop it, never dispatch it.
        invalidate = getattr(loader, "invalidate_staged", None)
        if invalidate is not None:
            invalidate()
    # Membership-epoch stamp: this rebuild is a numbered event.  The
    # gauge is what the heartbeat "fleet" row, web_status, and
    # /metrics surface; the counters say which direction the fleet
    # walked.
    from .. import resilience
    from ..observability import metrics
    workflow._membership_epoch_ = int(epoch) if epoch is not None \
        else getattr(workflow, "_membership_epoch_", 0) + 1
    resilience.stats.incr("membership.rebuilds")
    if old_n is not None and n > old_n:
        resilience.stats.incr("membership.grow")
    elif old_n is not None and n < old_n:
        resilience.stats.incr("membership.shrink")
    metrics.registry.gauge("membership.epoch").set(
        workflow._membership_epoch_)
    return mesh
