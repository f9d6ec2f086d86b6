"""Mixture-of-Experts dispatch (GShard/Switch-style top-k routing
with capacity) — the expert-parallel building block.

Not in the 2013-15 reference (SURVEY §5); part of the TPU build's
first-class scaling matrix (dp/tp/sp/ep).  The formulation is the
standard einsum dispatch: a (tokens, experts, capacity) one-hot
dispatch tensor gathers each expert's tokens, the expert FFNs run as
one batched einsum over the expert dimension, and a combine einsum
scatters outputs back weighted by the router gate.  Under a mesh with
an ``expert`` axis the expert dimension of the parameters and of the
dispatched activations shards there — XLA lowers the dispatch/combine
einsums to all-to-alls over ICI, exactly the manual A2A of expert-
parallel frameworks, without hand-written collectives.

Routing (ISSUE 12): :func:`top1_routing` is the historical GShard
top-1 path, kept verbatim — seeded trajectories depend on its exact
bits; :func:`topk_routing` generalizes it to k ≥ 2 choices per token
with rank-major capacity priority (all first choices queue before
any second choice), renormalized gates, the Switch load-balance
auxiliary (eq. 4) and the ST-MoE router z-loss.  Capacity scales
with k: ``C = capacity_factor · k · T / E``.

The capacity dispatch drops what overflows and builds a dense
``(T, E, C)`` one-hot.  :func:`moe_dropless` is the other path, for
an expert layer that holds a SHARE of the experts (expert
parallelism's unit of work, docs/moe.md): a sigmoid router with a
selection bias scores all ``E`` experts, the assignments to the
``count`` experts held here are ordered by expert and go through
grouped matrix products (:func:`grouped_dot`), gated, and are added
back by their weights; no assignment is ever dropped.
"""

import functools

import jax
import jax.numpy as jnp

from .pallas_lrn import tpu_available


def init_parser(parser):
    """MoE routing flags, aggregated into the velescli parser
    (handed to ``root.common.engine`` by
    ``__main__.apply_subsystem_flags``)."""
    parser.add_argument(
        "--moe-topk", type=int, default=None, metavar="K",
        help="Mixture-of-Experts router: experts per token (default "
             "1 = the Switch/GShard top-1 path; k>=2 dispatches each "
             "token to its k best experts with rank-major capacity "
             "priority and renormalized gates) (docs/moe.md)")
    parser.add_argument(
        "--moe-router-z", type=float, default=None, metavar="W",
        help="router z-loss weight (ST-MoE): penalizes "
             "mean(logsumexp(router logits)^2) to keep router "
             "logits small/stable; 0 (default) disables the term")


def top1_routing(logits, capacity):
    """Top-1 router (GShard): per-token expert choice with a
    per-expert capacity limit.

    Args:
      logits: (T, E) router scores.
      capacity: int — max tokens an expert accepts; overflow tokens
        are DROPPED (their combine weights are zero → residual path
        carries them, the standard top-1 behavior).

    Returns:
      dispatch: (T, E, C) 0/1 — token t occupies slot c of expert e;
      combine:  (T, E, C) float — dispatch · gate probability;
      aux_loss: load-balance auxiliary (mean_e f_e · p_e · E, the
        Switch/GShard formulation);
      expert_load: (E,) tokens routed per expert (pre-capacity).
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate = probs.max(axis=-1)
    expert = probs.argmax(axis=-1)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)
    # Position of each token within its expert's queue.
    position = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot
    keep = (position < capacity) * onehot          # (T, E)
    slot = position.sum(axis=-1).astype(jnp.int32)  # queue index
    dispatch = keep[:, :, None] * jax.nn.one_hot(
        slot, capacity, dtype=jnp.float32)[:, None, :]
    combine = dispatch * gate[:, None, None]
    # Load-balance aux: fraction routed × mean prob, summed over
    # experts, scaled by E (Switch Transformer eq. 4).
    f = onehot.mean(axis=0)
    p = probs.mean(axis=0)
    aux_loss = (f * p).sum() * E
    return dispatch, combine, aux_loss, onehot.sum(axis=0)


def topk_routing(logits, k, capacity):
    """Top-k router (GShard/Switch): per-token k expert choices with
    a per-expert capacity limit and rank-major queue priority —
    every token's FIRST choice queues before any token's second.

    Args:
      logits: (T, E) router scores; k: choices per token (k <= E);
      capacity: int — max tokens an expert accepts per rank-merged
        queue; overflow assignments are DROPPED (combine weight zero
        → the residual path carries them).

    Returns:
      dispatch: (T, E, C) 0/1 — token t occupies slot c of expert e
        through any of its k choices;
      combine:  (T, E, C) float — dispatch · renormalized gate
        (k = 1 keeps the raw top probability, matching
        :func:`top1_routing`'s Switch convention);
      aux_loss: Switch load-balance auxiliary (eq. 4) over the
        rank-0 choices: mean_e f_e · p_e · E;
      z_loss:   ST-MoE router z-loss, mean(logsumexp(logits)²);
      expert_load: (E,) assignments per expert over all k ranks,
        pre-capacity.
    """
    T, E = logits.shape
    if not 1 <= k <= E:
        raise ValueError("top_k=%d must satisfy 1 <= k <= %d experts"
                         % (k, E))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, k)          # (T, k)
    if k > 1:
        # Renormalize the selected gates (GShard top-2 convention);
        # k = 1 keeps the raw probability so the top-1 path's bits
        # are reproducible through this function too.
        gate = gate / jnp.maximum(gate.sum(axis=-1, keepdims=True),
                                  1e-9)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # (T,k,E)
    # Queue positions over the RANK-MAJOR flattening: all rank-0
    # choices first, so capacity overflow drops low-rank assignments
    # before anyone's primary expert.
    flat = onehot.transpose(1, 0, 2).reshape(k * T, E)
    position = (jnp.cumsum(flat, axis=0) - 1.0) * flat
    keep = (position < capacity) * flat             # (k·T, E)
    slot = position.sum(axis=-1).astype(jnp.int32)
    disp = (keep[:, :, None] * jax.nn.one_hot(
        slot, capacity, dtype=jnp.float32)[:, None, :]).reshape(
        k, T, E, capacity)
    dispatch = disp.sum(axis=0)
    combine = (disp * gate.T[:, :, None, None]).sum(axis=0)
    # Switch load-balance aux (eq. 4): fraction of rank-0 choices
    # per expert × mean router probability, scaled by E.
    f = onehot[:, 0, :].mean(axis=0)
    p = probs.mean(axis=0)
    aux_loss = (f * p).sum() * E
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return dispatch, combine, aux_loss, z_loss, onehot.sum(
        axis=(0, 1))


def moe_capacity(capacity_factor, n_tokens, n_experts, top_k=1):
    """The per-expert slot count: ``capacity_factor · k · T / E``,
    floored at 1 — a compile-time Python int (shapes depend on it)."""
    # lint-ok: VL101 static shape math — T/E/k are Python ints, the
    # capacity is a compile-time constant, never a traced value.
    return max(1, int(capacity_factor * top_k * n_tokens /
                      n_experts))


def moe_ffn_topk(x, router_w, w1, b1, w2, b2, capacity_factor=1.25,
                 top_k=1):
    """Top-k MoE feed-forward over tokens.

    Args:
      x: (T, D) tokens; router_w: (D, E);
      w1: (E, D, H); b1: (E, H); w2: (E, H, D); b2: (E, D);
      top_k: experts per token (1 = the historical top-1 path,
        bit-identical to the pre-top-k :func:`moe_ffn`).

    Returns (y (T, D), aux_loss, z_loss, expert_load (E,)) — the
    load-balance aux and the router z-loss ride back SEPARATELY so
    the caller weights them independently.
    """
    T, D = x.shape
    E = router_w.shape[1]
    capacity = moe_capacity(capacity_factor, T, E, top_k)
    logits = x.astype(jnp.float32) @ router_w
    if top_k == 1:
        # The pre-top-k code path, bit-for-bit (seeded MoE
        # trajectories are pinned on it); z computed on the side.
        dispatch, combine, aux, load = top1_routing(logits, capacity)
        z = jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32),
                                      axis=-1) ** 2)
    else:
        dispatch, combine, aux, z, load = topk_routing(
            logits, top_k, capacity)
    # Gather each expert's tokens: (E, C, D).
    expert_in = jnp.einsum("tec,td->ecd", dispatch,
                           x.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
    h = jnp.maximum(jnp.einsum(
        "ecd,edh->ech", expert_in, w1,
        preferred_element_type=jnp.float32) + b1[:, None, :], 0.0)
    expert_out = jnp.einsum(
        "ech,ehd->ecd", h, w2,
        preferred_element_type=jnp.float32) + b2[:, None, :]
    # Scatter back with gate weighting: dropped tokens get zeros.
    y = jnp.einsum("tec,ecd->td", combine, expert_out,
                   preferred_element_type=jnp.float32)
    return y, aux, z, load


def moe_ffn(x, router_w, w1, b1, w2, b2, capacity_factor=1.25,
            top_k=1, router_z_weight=0.0):
    """Compatibility wrapper over :func:`moe_ffn_topk`: returns
    (y, aux, load) with ``router_z_weight·z_loss`` folded into the
    auxiliary (0 keeps the historical top-1 bits exactly)."""
    y, aux, z, load = moe_ffn_topk(
        x, router_w, w1, b1, w2, b2,
        capacity_factor=capacity_factor, top_k=top_k)
    if router_z_weight:
        aux = aux + router_z_weight * z
    return y, aux, load


# -- the dropless share path -------------------------------------------------

#: Rows of a grouped product's row tile on the TPU; the row counts
#: :func:`moe_dropless` compiles for are multiples of it.
ROW_TILE = 512
#: Room over the even share that the common path is compiled for:
#: 5 / 4.
DROPLESS_SLACK = (5, 4)


def _tile(n, most=1024, lane=128):
    """The largest multiple of ``lane`` that divides ``n`` and is at
    most ``most``; ``n`` itself where there is none."""
    fits = [t for t in range(lane, min(n, most) + 1, lane) if n % t == 0]
    return fits[-1] if fits else n


def grouped_dot(lhs, rhs, group_sizes, interpret=False):
    """``out[r] = lhs[r] @ rhs[g(r)]`` where the rows are ordered by
    group and ``group_sizes`` (G,) int32 says how many each group has:
    lhs (N, K), rhs (G, K, M) → (N, M) float32.  Rows past
    ``sum(group_sizes)`` hold NOTHING a caller may use (the kernel
    never writes them): mask them.

    On a TPU this is the megablox Pallas kernel (``gmm``; ``tgmm`` for
    the weights' gradient): its grid runs over the row tiles that
    hold a group's rows, so the work follows ``sum(group_sizes)`` and
    not N, and, a ``pallas_call``, it keeps the ``jax.named_scope`` it
    is traced under — XLA's own expansion of ``lax.ragged_dot`` does
    the same work but names its instructions ``ragged-dot-none`` and
    the scope table cannot place them.  Elsewhere (the CPU's tests)
    ``lax.ragged_dot``.  ``interpret`` runs the kernel's interpreter
    wherever the process is (tests only)."""
    if not (interpret or tpu_available()):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    N, K = lhs.shape
    tile = (min(ROW_TILE, N), _tile(K), _tile(rhs.shape[2]))
    pad = -N % tile[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes, jnp.float32, tile,
              interpret=interpret)
    return out[:N] if pad else out


def dropless_rows(n_tokens, top_k, n_experts, count):
    """``(chunk, n_chunks)``: the assignment rows the common path of
    :func:`moe_dropless` is compiled for — :data:`DROPLESS_SLACK`
    times the even share ``T · k · count / E``, up to a row tile —
    and how many such chunks cover all ``T · k`` assignments."""
    worst = n_tokens * top_k
    more, than = DROPLESS_SLACK
    tiles = -(-worst * count * more // (n_experts * than * ROW_TILE))
    chunk = min(ROW_TILE * tiles, worst)
    return chunk, -(-worst // chunk)


def sigmoid_route(x, gate_w, expert_bias, top_k, norm_topk=True,
                  scaling=1.0):
    """The LFM2 / DeepSeek-V3 router: ``s = sigmoid(x @ W_gate)`` over
    all E experts, the CHOICE by ``s + expert_bias`` (a buffer that
    balances loads and that no gradient reaches), the WEIGHTS from
    ``s`` alone, normalised over the chosen k.  Scores and choice are
    float32 from float32 operands at ``highest``: a flipped choice is
    a discrete error, not a rounding.  Returns (idx (T, k) int32,
    weights (T, k) float32)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(expert_bias.astype(jnp.float32)),
        top_k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return idx, weights * scaling


def moe_dropless(x, gate_w, expert_bias, w1, w3, w2, top_k, held,
                 norm_topk=True, scaling=1.0, cdt=jnp.bfloat16):
    """A share of a dropless top-k expert layer with gated experts.

    Args:
      x: (T, D) tokens; gate_w: (D, E) router over ALL E experts;
      expert_bias: (E,) selection bias (:func:`sigmoid_route`);
      w1, w3: (count, D, F), w2: (count, F, D) — the experts held
        here, ``silu(x @ w1) * (x @ w3) @ w2`` each;
      held: ``(first, count)`` — experts ``first … first + count − 1``
        of the E are the ones held (static Python ints);
      cdt: type of the products' operands (accumulation is float32).

    Returns ``(y (T, D) float32, stats)``: ``y`` is what the held
    experts add for the tokens routed to them — what the absent
    experts would add is left out, it is another chip's — and
    ``stats`` counts ``made`` (T · k), ``landed`` (assignments to held
    experts) and ``load`` ((count,), per held expert).

    Static shapes (:func:`dropless_rows`).  The assignments that
    landed, in expert order, are walked in chunks of ``chunk`` rows.
    The common path is ONE chunk: gather, grouped products (whose
    work follows the rows that landed), weighted scatter-add.  Where
    more than ``chunk`` assignments land, ``lax.cond`` takes the path
    that maps the same chunk function over all ``n_chunks`` (each
    rematerialised, so it holds one chunk's intermediates whatever
    lands): no routing drops a token, and only a routing far from
    even pays for the walk.  The inner scopes ``moe_route``,
    ``moe_dispatch``, ``moe_experts``, ``moe_combine`` are the scope
    vocabulary's (docs/observability.md)."""
    T, D = x.shape
    first, count = held
    E = gate_w.shape[1]
    if w1.shape[0] != count or not 0 <= first <= E - count:
        raise ValueError("held=%r of %d experts, %d expert matrices"
                         % (held, E, w1.shape[0]))
    chunk, n_chunks = dropless_rows(T, top_k, E, count)
    with jax.named_scope("moe_route"):
        idx, weights = sigmoid_route(x, gate_w, expert_bias, top_k,
                                     norm_topk, scaling)
        local = idx.reshape(-1) - first
        # an assignment to an expert not held sorts past every held one
        key = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.pad(jnp.argsort(key, stable=True),
                        (0, chunk * n_chunks - T * top_k))
        sizes = jnp.bincount(key, length=count + 1)[:count].astype(
            jnp.int32)
        ends = jnp.cumsum(sizes)
        landed = ends[-1]

    def held_part(x, weights, w1, w3, w2, start):
        """Rows ``start … start + chunk`` of the ordered assignments:
        (their tokens, what their experts give, weighted)."""
        with jax.named_scope("moe_route"):
            rows = jax.lax.dynamic_slice(order, (start,), (chunk,))
            token = rows // top_k
            stop = start + chunk
            here = jnp.clip(ends, start, stop) - \
                jnp.clip(ends - sizes, start, stop)
            valid = (start + jnp.arange(chunk) < landed)[:, None]
        with jax.named_scope("moe_dispatch"):
            xs = jnp.where(valid, x[token].astype(cdt), 0)
        with jax.named_scope("moe_experts"):
            dot = functools.partial(grouped_dot, group_sizes=here)
            h = jax.nn.silu(dot(xs, w1)) * dot(xs, w3)
            ys = dot(jnp.where(valid, h, 0).astype(cdt), w2)
        with jax.named_scope("moe_combine"):
            ys = jnp.where(valid, ys, 0) * \
                weights.reshape(-1)[rows][:, None]
        return token, ys

    def combine(token, ys):
        with jax.named_scope("moe_combine"):
            return jnp.zeros((T, D), jnp.float32).at[token].add(ys)

    def one_chunk(*operands):
        return combine(*held_part(*operands, 0))

    def every_chunk(*operands):
        token, ys = jax.lax.map(
            jax.checkpoint(functools.partial(held_part, *operands)),
            chunk * jnp.arange(n_chunks))
        return combine(token.reshape(-1), ys.reshape(-1, D))

    with jax.named_scope("moe_experts"):
        operands = (x, weights, w1.astype(cdt), w3.astype(cdt),
                    w2.astype(cdt))
    if n_chunks == 1:
        y = one_chunk(*operands)
    else:
        y = jax.lax.cond(landed <= chunk, one_chunk, every_chunk,
                         *operands)
    stats = {"made": jnp.float32(T * top_k),
             "landed": landed.astype(jnp.float32),
             "load": sizes.astype(jnp.float32)}
    return y, stats
