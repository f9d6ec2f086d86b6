"""The expert layer: a dropless share of a top-k Mixture-of-Experts
(docs/moe.md).

Not in the 2013-15 reference (SURVEY §5).  An expert layer here HOLDS
a share of the experts — expert parallelism's unit of work: a router
scores all ``E`` experts (:func:`sigmoid_route`, with a selection
bias, or :func:`softmax_route`), the assignments to the ``count``
experts held here are ordered by expert and go through grouped
matrix products
(:func:`grouped_dot`: the megablox kernels on a TPU), gated, and are
added back by their weights (:func:`moe_dropless`).  No assignment is
ever dropped, and the work follows the assignments that landed, not
a capacity.  ``LMLayer`` runs it for a spec
with ``ffn="experts"`` (``znicz/attention.py``); the exchange that
would carry tokens between chips' shares is not written (ROADMAP,
Reach A3).
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..backends import tpu_available


#: Rows of a grouped product's row tile on the TPU; the row counts
#: :func:`moe_dropless` compiles for are multiples of it.
ROW_TILE = 512
#: Room over the even share that the common path is compiled for,
#: unless a caller says otherwise (``layer_spec(slack=…)``): 5 / 4.
DROPLESS_SLACK = (5, 4)


#: Elements of the (K tile × M tile) block a grouped product's kernels
#: keep in VMEM — the weights' gradient (``tgmm``) accumulates one in
#: float32 beside its double-buffered copies: 1024 × 768 compiles for
#: a v5e's 16 MB, 1024 × 1024 does not (tests/test_tpu_compile.py).
TILE_ELEMENTS = 1024 * 768

#: Names (``jax.ad_checkpoint.checkpoint_name``) on what an expert
#: layer's backward pass reads of its forward pass: the router's
#: scores, the choice, the chosen scores (a gather of ``T · k``
#: scalars), the order and the group sizes; on the common
#: path the gathered rows and the two products before the gate.  The
#: layers' checkpoint (``znicz.attention.checkpointed``) keeps them, so
#: a layer's recompute holds no router, no sort, no gather and one
#: grouped product (docs/moe.md gives the bytes).
MOE_SCORES = "moe_scores"
MOE_IDX = "moe_idx"
MOE_WEIGHTS = "moe_weights"
MOE_ORDER = "moe_order"
MOE_SIZES = "moe_sizes"
MOE_ROWS = "moe_rows"
MOE_GATE = "moe_gate"
MOE_UP = "moe_up"
MOE_KEPT = (MOE_SCORES, MOE_IDX, MOE_WEIGHTS, MOE_ORDER, MOE_SIZES,
            MOE_ROWS, MOE_GATE, MOE_UP)


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
    tile_k = _tile(K)
    tile = (min(ROW_TILE, N), tile_k,
            _tile(rhs.shape[2], min(1024, TILE_ELEMENTS // tile_k)))
    pad = -N % tile[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes, jnp.float32, tile,
              interpret=interpret)
    return out[:N] if pad else out


def dropless_rows(n_tokens, top_k, n_experts, count,
                  slack=DROPLESS_SLACK):
    """``(chunk, n_chunks)``: the assignment rows the common path of
    :func:`moe_dropless` is compiled for — ``slack`` (a ratio ``(more,
    than)``, :data:`DROPLESS_SLACK` by default) times the even share
    ``T · k · count / E``, up to a row tile — and how many such chunks
    cover all ``T · k`` assignments."""
    worst = n_tokens * top_k
    more, than = slack
    tiles = -(-worst * count * more // (n_experts * than * ROW_TILE))
    chunk = min(ROW_TILE * tiles, worst)
    return chunk, -(-worst // chunk)


def _named_scores(logits):
    return checkpoint_name(jax.nn.sigmoid(logits), MOE_SCORES)


#: ``sigmoid(logits)`` under the name :data:`MOE_SCORES`, with a
#: derivative that reads the NAMED value: ``lax.logistic``'s own rule
#: reads its unnamed output, which a checkpoint that keeps the name
#: would rebuild — and the router's product with it.
_scores = jax.custom_jvp(_named_scores)


@_scores.defjvp
def _scores_jvp(primals, tangents):
    scores = _named_scores(*primals)
    return scores, tangents[0] * scores * (1 - scores)


def _choose(scores, chosen_by, top_k, norm_topk, scaling, eps):
    """The ``top_k`` largest of ``chosen_by`` a row and their
    ``scores`` as weights, under the names the layers' checkpoint
    keeps."""
    _, idx = jax.lax.top_k(chosen_by, top_k)
    idx = checkpoint_name(idx, MOE_IDX)
    weights = checkpoint_name(
        jnp.take_along_axis(scores, idx, axis=-1), MOE_WEIGHTS)
    if norm_topk:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + eps)
    return idx, weights * scaling


def sigmoid_route(x, gate_w, expert_bias, top_k, norm_topk=True,
                  scaling=1.0, eps=1e-6):
    """The LFM2 / DeepSeek-V3 router: ``s = sigmoid(x @ W_gate)`` over
    all E experts, the CHOICE by ``s + expert_bias`` (a buffer that
    balances loads and that no gradient reaches), the WEIGHTS from
    ``s`` alone, normalised over the chosen k (their sum + ``eps``)
    and scaled by ``scaling``.  Scores and choice are
    float32 from float32 operands at ``highest``: a flipped choice is
    a discrete error, not a rounding.  Returns (idx (T, k) int32,
    weights (T, k) float32)."""
    scores = _scores(jnp.dot(
        x.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    return _choose(
        scores,
        scores + jax.lax.stop_gradient(expert_bias.astype(jnp.float32)),
        top_k, norm_topk, scaling, eps)


def _named_probabilities(logits):
    return checkpoint_name(jax.nn.softmax(logits, axis=-1), MOE_SCORES)


#: ``softmax(logits)`` under the name :data:`MOE_SCORES`, its
#: derivative from the NAMED value, as :data:`_scores`.
_probabilities = jax.custom_jvp(_named_probabilities)


@_probabilities.defjvp
def _probabilities_jvp(primals, tangents):
    p = _named_probabilities(*primals)
    lift = tangents[0] * p
    return p, lift - p * lift.sum(axis=-1, keepdims=True)


def softmax_route(x, gate_w, expert_bias, top_k, norm_topk=True,
                  scaling=1.0, eps=1e-6):
    """The Qwen-MoE router: ``p = softmax(x @ W_gate)`` over ALL E
    experts, the choice the k largest ``p``, the weights those ``p``
    normalised over the chosen k (their sum + ``eps``) and scaled.
    There is no selection bias: ``expert_bias`` is taken, as
    :func:`sigmoid_route` takes it, and not read.  Float32 from
    float32 operands at ``highest`` and the same checkpoint names as
    :func:`sigmoid_route`.  Returns (idx (T, k) int32, weights (T, k)
    float32)."""
    del expert_bias
    p = _probabilities(jnp.dot(
        x.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    return _choose(p, p, top_k, norm_topk, scaling, eps)


#: The score functions :func:`moe_dropless` routes by.
ROUTERS = {"sigmoid": sigmoid_route, "softmax": softmax_route}


def moe_dropless(x, gate_w, expert_bias, w1, w3, w2, top_k, held,
                 norm_topk=True, scaling=1.0, cdt=jnp.bfloat16,
                 eps=1e-6, slack=DROPLESS_SLACK, score="sigmoid"):
    """A share of a dropless top-k expert layer with gated experts.

    Args:
      x: (T, D) tokens; gate_w: (D, E) router over ALL E experts;
      expert_bias: (E,) selection bias (:func:`sigmoid_route`);
      score: ``sigmoid`` | ``softmax`` (:data:`ROUTERS`);
      w1, w3: (count, D, F), w2: (count, F, D) — the experts held
        here, ``silu(x @ w1) * (x @ w3) @ w2`` each;
      held: ``(first, count)`` — experts ``first … first + count − 1``
        of the E are the ones held (static Python ints);
      cdt: type of the products' operands (accumulation is float32);
      scaling, eps: :func:`sigmoid_route`'s;
      slack: :func:`dropless_rows`'s — how far over the even share
        the routing may go before the walk below is paid for.

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
    even pays for the walk.  Under the layers' checkpoint the routing
    and, on the common path, the gathered rows and the two products
    before the gate are kept (:data:`MOE_KEPT`); the walk names
    nothing, which is its purpose.  The inner scopes ``moe_route``,
    ``moe_dispatch``, ``moe_experts``, ``moe_combine`` are the scope
    vocabulary's (docs/observability.md)."""
    T, D = x.shape
    first, count = held
    E = gate_w.shape[1]
    if w1.shape[0] != count or not 0 <= first <= E - count:
        raise ValueError("held=%r of %d experts, %d expert matrices"
                         % (held, E, w1.shape[0]))
    chunk, n_chunks = dropless_rows(T, top_k, E, count, slack)
    with jax.named_scope("moe_route"):
        idx, weights = ROUTERS[score](x, gate_w, expert_bias, top_k,
                                      norm_topk, scaling, eps)
        local = idx.reshape(-1) - first
        # an assignment to an expert not held sorts past every held one
        key = jnp.where((local >= 0) & (local < count), local, count)
        order = checkpoint_name(
            jnp.pad(jnp.argsort(key, stable=True),
                    (0, chunk * n_chunks - T * top_k)), MOE_ORDER)
        sizes = checkpoint_name(
            jnp.bincount(key, length=count + 1)[:count].astype(
                jnp.int32), MOE_SIZES)
        ends = jnp.cumsum(sizes)
        landed = ends[-1]

    def held_part(x, weights, w1, w3, w2, start,
                  kept=lambda value, name: value):
        """Rows ``start … start + chunk`` of the ordered assignments:
        (their tokens, what their experts give, weighted).  ``kept``
        names the gathered rows and the two products before the gate
        (the common path's ``checkpoint_name``; the walk names
        nothing)."""
        with jax.named_scope("moe_route"):
            rows = jax.lax.dynamic_slice(order, (start,), (chunk,))
            token = rows // top_k
            stop = start + chunk
            here = jnp.clip(ends, start, stop) - \
                jnp.clip(ends - sizes, start, stop)
            valid = (start + jnp.arange(chunk) < landed)[:, None]
        with jax.named_scope("moe_dispatch"):
            xs = kept(jnp.where(valid, x[token].astype(cdt), 0),
                      MOE_ROWS)
        with jax.named_scope("moe_experts"):
            dot = functools.partial(grouped_dot, group_sizes=here)
            h = jax.nn.silu(kept(dot(xs, w1), MOE_GATE)) * \
                kept(dot(xs, w3), MOE_UP)
            ys = dot(jnp.where(valid, h, 0).astype(cdt), w2)
        with jax.named_scope("moe_combine"):
            ys = jnp.where(valid, ys, 0) * \
                weights.reshape(-1)[rows][:, None]
        return token, ys

    def combine(token, ys):
        with jax.named_scope("moe_combine"):
            return jnp.zeros((T, D), jnp.float32).at[token].add(ys)

    def one_chunk(*operands):
        return combine(*held_part(*operands, 0, kept=checkpoint_name))

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
