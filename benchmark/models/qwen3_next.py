"""Family ``qwen3_next``: Qwen's hybrid LMs of Gated DeltaNet linear
attention and gated full attention over sparse experts (``model_type``
``qwen3_next``; https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct),
as veles_tpu trains a chip's SHARE of one
(``znicz/samples/qwen3_next.py``).

What lives here, as in ``dense_lm``, ``lfm2_moe`` and ``afmoe``: how
the PROGRAM is built for this family and how its state is read; the
weights from the seed; the yardstick's arithmetic (FLOPs a token, the
gated delta rule's needed work, the flash kernels' and the expert
products'); and the PLAIN REFERENCE — forward, loss, gradients and the
momentum-SGD update in float32 ``jax.numpy`` at ``highest``, importing
nothing of the program.

One sequence, hidden state ``h`` (S, E), float32::

    h = h + operator(rms(h, ln1_g));  h = h + ffn(rms(h, ln2_g))
    rms(x, g) = x * rsqrt(mean(x^2, -1) + eps) * g

    linear layer (Gated DeltaNet), Hv value heads over Hk key heads:
      [q | k | v | z] = u w_qkvz;  [b | a] = u w_ba
      [q | k | v] = silu(conv([q | k | v]))     causal, depthwise, K taps
      beta = sigmoid(b);  g = -exp(a_log) * softplus(a + dt_bias)
      q = q / sqrt(sum q^2 + 1e-6) / sqrt(Dk);  k = k / sqrt(sum k^2 + 1e-6)
      a head, S_0 = 0 (Dk, Dv), ROW BY ROW:
        S = exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S = S + k_t d^T
        o_t = S^T q_t
      (gdn_norm_g * o / rms(o) * silu(z)).reshape(S, Hv Dv) w_out
    full layer:  q, gate = u wq, u wg -> (S, H, D);  k, v -> (S, KV, D)
      q, k = rms per head;  rope on the FIRST rope_dim of each head
      a = softmax(q k^T / sqrt(D) + causal) v, kv heads shared
      (a.reshape(S, H D) * sigmoid(gate)) wo
    every layer's ffn:  p = softmax(u router) over ALL the experts
      idx = top_k(p);  w = p[idx] / sum p[idx]
      sigmoid(u wsg) * gated(u; ws1, ws3, ws2)
        + sum_i w_i * gated(u; expert idx_i) over the experts HELD
    logits = rms(h, final_norm) @ head                       untied

The rule is run as the recurrence that DEFINES it, one row at a time
(``lax.scan``, rematerialised in blocks of rows so that its backward
pass holds a block's states and not the sequence's): no chunk algebra,
nothing of ``ops/linear_attention.py``'s formulation.  The share, as
``afmoe``'s: the configuration holds experts ``0 … held − 1`` of the
router's ``experts`` and the first ``vocab`` rows of the vocabulary;
the shared expert and its gate are whole.
"""

import functools
import math

from benchmark.models import lfm2_moe
from benchmark.models.afmoe import HEAD_PARTS, _attention, _head_group
from benchmark.models.dense_lm import (
    SAMPLE, _dot, _settle_vector_order, leaf_norms, make_tokens, seed_key)
from benchmark.models.lfm2_moe import (
    _block_leaves, _gated, _rms_norm, _rope, _say)

#: Faults ``reference_train`` can plant.  ``state_dropped``: every
#: block of ``chunk`` rows starts from a state of nought, as a chunked
#: program that did not carry its state would; ``decay_ignored``: g =
#: 0, nothing is ever forgotten.  ``half_batch`` as ``afmoe``'s (at
#: one sequence a tick, the second half of its positions).
FAULTS = ("half_batch", "state_unchanged", "state_dropped",
          "decay_ignored")
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6
LINEAR_LEAVES = ("ln1_g", "w_qkvz", "w_ba", "w_conv", "a_log", "dt_bias",
                 "gdn_norm_g", "w_out")
FULL_LEAVES = ("ln1_g", "wq", "wk", "wv", "wo", "wg", "q_norm_g",
               "k_norm_g")
EXPERT_LEAVES = ("ln2_g", "router", "w1", "w3", "w2", "ws1", "ws3",
                 "ws2", "wsg")


def sizes(config, rehearse=False):
    """The numbers this family reads from a configuration file.
    ``experts`` is the router's width (the PUBLISHED count), ``held``
    how many of them live here (the file's ``num_experts``)."""
    src = dict(config)
    if rehearse:
        src.update(config["rehearsal"])
    layers, every = src["num_hidden_layers"], src["full_attention_interval"]
    if src["mlp_only_layers"] or src["decoder_sparse_step"] != 1 or \
            src["num_attention_heads"] % src["num_key_value_heads"] or \
            src["linear_num_value_heads"] % src["linear_num_key_heads"]:
        raise ValueError("dense layers / heads of %r" % config.get("name"))
    types = tuple(FULL if (i + 1) % every == 0 else LINEAR
                  for i in range(layers))
    assumed = config["assumed_sizes"]
    return {"hidden": src["hidden_size"],
            "heads": src["num_attention_heads"],
            "kv_heads": src["num_key_value_heads"],
            "head_dim": src["head_dim"],
            "rope_dim": int(src["head_dim"] * src["partial_rotary_factor"]),
            "rope_fraction": src["partial_rotary_factor"],
            "rope_theta": float(src["rope_theta"]),
            "key_heads": src["linear_num_key_heads"],
            "value_heads": src["linear_num_value_heads"],
            "key_dim": src["linear_key_head_dim"],
            "value_dim": src["linear_value_head_dim"],
            "conv_kernel": src["linear_conv_kernel_dim"],
            "chunk": src["linear_chunk"],
            "expert_ffn": src["moe_intermediate_size"],
            "shared_ffn": src["shared_expert_intermediate_size"],
            "experts": src["published"]["num_experts"],
            "held": src["num_experts"],
            "top_k": src["num_experts_per_tok"],
            "norm_topk": bool(src["norm_topk_prob"]),
            "vocab": src["vocab_size"],
            "interval": every,
            "layer_types": types,
            "norm_eps": src["rms_norm_eps"],
            "a_range": (assumed["a_min"], assumed["a_max"]),
            "dt_range": (assumed["dt_min"], assumed["dt_max"]),
            # what the driver multiplies the flash kernels' calls by
            "blocks": types.count(FULL)}


def _layers_of(sz, kind):
    return [i for i, t in enumerate(sz["layer_types"]) if t == kind]


def leaf_shapes(sz, seq=None):
    """Trainable leaf name -> shape, in a fixed order: ``embedding.
    weights``, ``block<i>.<leaf>``, ``final_norm.weights``,
    ``head.weights``."""
    E, D = sz["hidden"], sz["head_dim"]
    inner, kv = sz["heads"] * D, sz["kv_heads"] * D
    keys = sz["key_heads"] * sz["key_dim"]
    values = sz["value_heads"] * sz["value_dim"]
    G, C, Hv = sz["expert_ffn"], sz["held"], sz["value_heads"]
    per = {"ln1_g": (E,), "ln2_g": (E,),
           "w_qkvz": (E, 2 * keys + 2 * values), "w_ba": (E, 2 * Hv),
           "w_conv": (2 * keys + values, sz["conv_kernel"]),
           "a_log": (Hv,), "dt_bias": (Hv,),
           "gdn_norm_g": (sz["value_dim"],), "w_out": (values, E),
           "wq": (E, inner), "wk": (E, kv), "wv": (E, kv),
           "wo": (inner, E), "wg": (E, inner), "q_norm_g": (D,),
           "k_norm_g": (D,), "router": (E, sz["experts"]),
           "w1": (C, E, G), "w3": (C, E, G), "w2": (C, G, E),
           "ws1": (E, sz["shared_ffn"]), "ws3": (E, sz["shared_ffn"]),
           "ws2": (sz["shared_ffn"], E), "wsg": (E, 1)}
    shapes = {"embedding.weights": (sz["vocab"], E)}
    for i, kind in enumerate(sz["layer_types"]):
        for leaf in (LINEAR_LEAVES if kind == LINEAR else FULL_LEAVES) \
                + EXPERT_LEAVES:
            shapes["block%d.%s" % (i, leaf)] = per[leaf]
    shapes["final_norm.weights"] = (E,)
    shapes["head.weights"] = (E, sz["vocab"])
    return shapes


def parameter_count(sz, seq=None):
    return sum(math.prod(s) for s in leaf_shapes(sz).values())


# -- weights from the seed -------------------------------------------------

def _leaf_kind(name, sz):
    """How a leaf is drawn: the other configurations' initialisation
    carried over — N(0, 0.02) embedding, N(0, 1/hidden) every matrix
    (the router, the gates, the shared expert and the free head too),
    taps N(0, 1/K), gains 1 — and the rule's own two: ``a_log`` the
    log of U(a_range), ``dt_bias`` the inverse softplus of a step
    log-uniform in ``dt_range`` (the configuration's ``assumed``)."""
    leaf = name.split(".")[-1]
    if name.startswith("embedding."):
        return ("normal", 0.02)
    if leaf == "w_conv":
        return ("normal", 1.0 / math.sqrt(sz["conv_kernel"]))
    if leaf == "a_log":
        return ("a_log",) + tuple(sz["a_range"])
    if leaf == "dt_bias":
        return ("dt_bias",) + tuple(sz["dt_range"])
    if leaf.startswith("w") or leaf == "router":      # head.weights too
        return ("normal", 1.0 / math.sqrt(sz["hidden"]))
    return ("ones",)


@functools.lru_cache(maxsize=None)
def _leaf_programs(kind, shape):
    """Three small programs for leaves of one kind and shape, the
    leaf's index a traced argument (as ``dense_lm._leaf_programs``):
    the leaf from the seed, the norm of a leaf's change from it, and a
    seeded sample of a leaf."""
    import jax
    import jax.numpy as jnp

    def first(key, index):
        key = jax.random.fold_in(key, index)
        if kind[0] == "normal":
            return kind[1] * jax.random.normal(key, shape, jnp.float32)
        if kind[0] == "a_log":
            return jnp.log(jax.random.uniform(
                key, shape, jnp.float32, kind[1], kind[2]))
        if kind[0] == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(kind[1]),
                math.log(kind[2])))
            return dt + jnp.log(-jnp.expm1(-dt))
        return jnp.ones(shape, jnp.float32)

    @jax.jit
    def change(leaf, key, index):
        return jnp.sqrt(jnp.sum(jnp.square(leaf - first(key, index))))

    @jax.jit
    def sample(leaf, key, index):
        flat = leaf.reshape(-1)
        where = jax.random.randint(jax.random.fold_in(key, index),
                                   (min(SAMPLE, flat.size),), 0,
                                   flat.size)
        return flat[where].astype(jnp.float32)

    return jax.jit(first), change, sample


def _programs_for(name, shape, sz):
    return _leaf_programs(_leaf_kind(name, sz), tuple(shape))


def init_params(seed, sz, seq=None):
    """Every leaf, float32, on the device; a leaf's draw follows from
    its position in ``leaf_shapes``."""
    key = seed_key(seed)
    return {name: _programs_for(name, shape, sz)[0](key, i)
            for i, (name, shape) in enumerate(leaf_shapes(sz).items())}


# -- the yardstick's arithmetic ---------------------------------------------

def matmul_params_per_token(sz):
    """Parameters a token is multiplied by, forward, on THIS chip: a
    linear layer's two input projections and its output projection
    (the taps are no matmul); a full layer's q, gate and output
    projections and k, v; of every layer the router, the shared expert
    and its gate and the ``top_k × held / experts`` routed experts a
    token meets here on average (0.625 of one, at 10 of 512 with 32
    held); the free head over the slice."""
    E = sz["hidden"]
    keys = sz["key_heads"] * sz["key_dim"]
    values = sz["value_heads"] * sz["value_dim"]
    inner = sz["heads"] * sz["head_dim"]
    linear = E * (2 * keys + 2 * values) + E * 2 * sz["value_heads"] + \
        values * E
    full = 3 * E * inner + 2 * E * sz["kv_heads"] * sz["head_dim"]
    expert = 3 * E * sz["expert_ffn"]
    ffn = E * sz["experts"] + 3 * E * sz["shared_ffn"] + E + \
        expert * sz["top_k"] * sz["held"] / sz["experts"]
    return sz["vocab"] * E + sum(
        (linear if kind == LINEAR else full) + ffn
        for kind in sz["layer_types"])


def attention_flops_forward(sz, seq):
    """QK^T and PV of ONE sequence through ONE full layer, forward,
    over the causal half: 2 FLOPs a visible pair and a head-dim
    element, twice, over heads · head_dim."""
    return 4.0 * sz["heads"] * sz["head_dim"] * (seq * (seq + 1) // 2)


def rule_flops_forward(sz, seq):
    """The gated delta rule of ONE sequence through ONE linear layer,
    forward, at its RECURRENT count: a row and a value head decay,
    read, write and read a (Dk, Dv) state — 6 · Dk · Dv."""
    return 6.0 * sz["key_dim"] * sz["value_dim"] * sz["value_heads"] * seq


def train_flops_per_item(sz, seq):
    """Required FLOPs of forward + backward for one token: 6 a matmul
    parameter it meets, three times the forward attention of the full
    layers and three times the rule of the linear ones.  Recomputation
    is not counted; norms, taps, gates and routing are left out."""
    kinds = sz["layer_types"]
    return 6.0 * matmul_params_per_token(sz) + 3.0 * (
        kinds.count(FULL) * attention_flops_forward(sz, seq) +
        kinds.count(LINEAR) * rule_flops_forward(sz, seq)) / seq


def gated_delta_cost(sz, batch, seq, operand_bytes=2):
    """FLOPs and HBM bytes the gated delta rule NEEDS for one tick of
    ``batch`` sequences through ONE linear layer, forward + backward,
    whatever implements it — a true lower bound: the recurrence's own
    count three times over (18 · Dk · Dv a row and a value head; a
    chunked form does more), and q, k (at the key heads' width), v and
    o (``operand_bytes`` an element) with g and beta (float32) moved
    once forward and, as cotangents, once backward — with the
    ``layers`` of that kind and their ``units`` in the scope table."""
    rows = batch * seq
    wide = sz["value_heads"] * sz["value_dim"]
    narrow = sz["key_heads"] * sz["key_dim"]
    once = rows * ((2 * narrow + 2 * wide) * operand_bytes +
                   2 * sz["value_heads"] * 4)
    layers = _layers_of(sz, LINEAR)
    return {"flops": 3 * batch * rule_flops_forward(sz, seq),
            "bytes": 2 * once, "layers": len(layers),
            "units": ["block%d" % i for i in layers]}


def flash_call_cost(sz, batch, seq, operand_bytes=2):
    """As ``afmoe.flash_call_cost``, for the one kind this family has
    (``full``): FLOPs and HBM bytes the three flash kernels need for
    one tick through ONE full layer, over the visible pairs and over
    heads · head_dim; q, o, dO and dq at the query heads' width, k, v,
    dk and dv at the key/value heads'."""
    one = attention_flops_forward(sz, seq) / 2 * batch
    wide = batch * seq * sz["heads"] * sz["head_dim"] * operand_bytes
    narrow = batch * seq * sz["kv_heads"] * sz["head_dim"] * operand_bytes
    rows = batch * sz["heads"] * seq * 4
    layers = _layers_of(sz, FULL)
    return {"full": {
        "fwd": {"flops": 2 * one, "bytes": 2 * wide + 2 * narrow + rows},
        "dq": {"flops": 2 * one,
               "bytes": 3 * wide + 2 * narrow + 2 * rows},
        "dkv": {"flops": 2 * one,
                "bytes": 2 * wide + 4 * narrow + 2 * rows},
        "layers": len(layers),
        "units": ["block%d" % i for i in layers]}}


# -- the plain reference ----------------------------------------------------

def _taps(x, w):
    """Causal depthwise convolution of (S, C) by (C, K) taps, noughts
    to the left: tap ``j`` reads row ``t - (K - 1 - j)``."""
    import jax.numpy as jnp
    K = w.shape[1]
    z = jnp.zeros_like(x)
    for j in range(K):
        back = K - 1 - j
        z = z + w[:, j] * jnp.concatenate(
            [jnp.zeros_like(x[:back]), x[:x.shape[0] - back]])
    return z


def delta_recurrence(q, k, v, g, beta, block, dropped=False):
    """The gated delta rule of one sequence, a row at a time: q, k
    (S, H, Dk), v (S, H, Dv), g, beta (S, H) -> o (S, H, Dv).  Rows go
    in blocks of ``block``, each block rematerialised (its backward
    pass rebuilds the block's states from the one it started with);
    ``dropped`` starts every block from nought."""
    import jax
    import jax.numpy as jnp
    S, H, Dk = q.shape
    if S % block:
        raise ValueError("%d rows in blocks of %d" % (S, block))

    def row(state, x):
        qt, kt, vt, gt, bt = x
        state = state * jnp.exp(gt)[:, None, None]
        read = (state * kt[:, :, None]).sum(axis=1)             # (H, Dv)
        delta = (vt - read) * bt[:, None]
        state = state + kt[:, :, None] * delta[:, None, :]
        return state, (state * qt[:, :, None]).sum(axis=1)

    @jax.checkpoint
    def rows(state, xs):
        if dropped:
            state = jnp.zeros_like(state)
        return jax.lax.scan(row, state, xs)

    xs = tuple(x.reshape((S // block, block) + x.shape[1:])
               for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(
        rows, jnp.zeros((H, Dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape(S, H, -1)


def _linear_operator(p, u, sz, dot, fault):
    import jax
    import jax.numpy as jnp
    S = u.shape[0]
    Hk, Hv = sz["key_heads"], sz["value_heads"]
    Dk, Dv = sz["key_dim"], sz["value_dim"]
    keys, values = Hk * Dk, Hv * Dv
    qkvz = dot(u, p["w_qkvz"])
    b, a = jnp.split(dot(u, p["w_ba"]), 2, axis=-1)
    qkv = jax.nn.silu(_taps(qkvz[:, :2 * keys + values], p["w_conv"]))
    z = qkvz[:, 2 * keys + values:].reshape(S, Hv, Dv)
    q = qkv[:, :keys].reshape(S, Hk, Dk)
    k = qkv[:, keys:2 * keys].reshape(S, Hk, Dk)
    v = qkv[:, 2 * keys:].reshape(S, Hv, Dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + L2_EPS) / \
        math.sqrt(Dk)
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + L2_EPS)
    q, k = (jnp.repeat(t, Hv // Hk, axis=1) for t in (q, k))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    if fault == "decay_ignored":
        g = jnp.zeros_like(g)
    o = delta_recurrence(q, k, v, g, jax.nn.sigmoid(b), sz["chunk"],
                         dropped=fault == "state_dropped")
    y = _rms_norm(o, p["gdn_norm_g"], sz["norm_eps"]) * jax.nn.silu(z)
    return dot(y.reshape(S, values), p["w_out"])


def _rope_first(x, theta, width):
    """Rotary positions on the first ``width`` of each head of (S, H,
    D), halves and frequencies taken inside them; the rest untouched."""
    import jax.numpy as jnp
    return jnp.concatenate([_rope(x[..., :width], theta), x[..., width:]],
                           axis=-1)


def _full_operator(p, u, sz, dot, head_group):
    import jax
    import jax.numpy as jnp
    S = u.shape[0]
    H, KV = sz["heads"], sz["kv_heads"]
    q = dot(u, p["wq"]).reshape(S, H, -1)
    k = dot(u, p["wk"]).reshape(S, KV, -1)
    v = dot(u, p["wv"]).reshape(S, KV, -1)
    q = _rope_first(_rms_norm(q, p["q_norm_g"], sz["norm_eps"]),
                    sz["rope_theta"], sz["rope_dim"])
    k = _rope_first(_rms_norm(k, p["k_norm_g"], sz["norm_eps"]),
                    sz["rope_theta"], sz["rope_dim"])
    k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
    a = _attention(q, k, v, dot, head_group, None).reshape(S, -1)
    return dot(a * jax.nn.sigmoid(dot(u, p["wg"])), p["wo"])


def route(u, router, sz):
    """(idx (S, k), weights (S, k)): float32 at ``highest`` whatever
    the matmuls' operands are — the configuration keeps the router
    there."""
    import jax
    import jax.numpy as jnp
    p = jax.nn.softmax(jnp.matmul(
        u, router, precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(p, sz["top_k"])
    if sz["norm_topk"]:
        w = w / w.sum(axis=-1, keepdims=True)
    return idx, w


def routed_ffn(p, u, sz, dot, first=0, held=None):
    """The held experts' part of one layer's FFN for one sequence, and
    how many assignments landed on them: every held expert's FFN over
    EVERY token, weighed by the routing (nought where the token did
    not choose it), one expert after the other.  ``first`` / ``held``
    (default: the configuration's share) let a test walk every share
    of an uncut layer."""
    import jax
    import jax.numpy as jnp
    held = sz["held"] if held is None else held
    idx, w = route(u, p["router"], sz)

    @jax.checkpoint
    def one(e, w1, w3, w2):
        hit = idx == first + e                               # (S, k)
        return (w * hit).sum(axis=-1)[:, None] * _gated(
            u, w1, w3, w2, dot), hit.sum().astype(jnp.float32)

    def step(carry, expert):
        y, landed = one(*expert)
        return (carry[0] + y, carry[1] + landed), None

    (y, landed), _ = jax.lax.scan(
        step, (jnp.zeros_like(u), jnp.float32(0.0)),
        (jnp.arange(held), p["w1"][:held], p["w3"][:held],
         p["w2"][:held]))
    return y, landed


def shared_ffn(p, u, dot):
    """The shared expert behind its gate: what every chip of the
    sixteen computes alike."""
    import jax
    return jax.nn.sigmoid(dot(u, p["wsg"])) * _gated(
        u, p["ws1"], p["ws3"], p["ws2"], dot)


def expert_ffn(p, u, sz, dot, first=0, held=None):
    y, landed = routed_ffn(p, u, sz, dot, first, held)
    return shared_ffn(p, u, dot) + y, landed


def _layer(p, h, sz, i, dot, head_group, fault):
    eps = sz["norm_eps"]
    u = _rms_norm(h, p["ln1_g"], eps)
    if sz["layer_types"][i] == LINEAR:
        h = h + _linear_operator(p, u, sz, dot, fault)
    else:
        h = h + _full_operator(p, u, sz, dot, head_group)
    f, landed = expert_ffn(p, _rms_norm(h, p["ln2_g"], eps), sz, dot)
    return h + f, landed


def forward_hidden(params, tokens, sz, operand=None, fault=None):
    """(the last layer's output (S, E), assignments landed on the held
    experts over the layers) for ONE sequence; a layer at a time, each
    rematerialised."""
    import jax
    import jax.numpy as jnp
    dot = _dot(operand)
    group = _head_group(sz, tokens.shape[0])
    h = params["embedding.weights"][tokens]
    landed = jnp.float32(0.0)
    for i in range(len(sz["layer_types"])):
        h, here = jax.checkpoint(functools.partial(
            _layer, sz=sz, i=i, dot=dot, head_group=group, fault=fault))(
                _block_leaves(params, i), h)
        landed = landed + here
    return h, landed


def sequence_loss(params, tokens, labels, sz, operand=None, fault=None,
                  positions=None):
    """(mean next-token cross-entropy over the first ``positions`` of
    ONE sequence (None: all), assignments landed).  The head and its
    loss go ``HEAD_PARTS`` parts of the positions one after the other,
    each rematerialised, as ``afmoe``'s."""
    import jax
    import jax.numpy as jnp
    dot = _dot(operand)
    h, landed = forward_hidden(params, tokens, sz, operand, fault)
    h = _rms_norm(h, params["final_norm.weights"], sz["norm_eps"])
    S, E = h.shape
    counted = S if positions is None else positions
    weight = (jnp.arange(S) < counted).astype(jnp.float32) / counted
    parts = math.gcd(HEAD_PARTS, S)

    @jax.checkpoint
    def part(xs):
        x, wanted, share = xs
        logp = jax.nn.log_softmax(dot(x, params["head.weights"]), axis=-1)
        return -(jnp.take_along_axis(logp, wanted[:, None],
                                     axis=-1)[:, 0] * share).sum()

    loss = jax.lax.map(part, (h.reshape(parts, S // parts, E),
                              labels.reshape(parts, S // parts),
                              weight.reshape(parts, S // parts))).sum()
    return loss, jax.lax.stop_gradient(landed)


_TICK_FNS = {}


def _tick_fn(sz, operand, learning_rate, momentum, fault):
    key = (tuple(sorted(sz.items())), operand, learning_rate, momentum,
           fault)
    if key not in _TICK_FNS:
        _TICK_FNS[key] = _make_tick_fn(sz, operand, learning_rate,
                                       momentum, fault)
    return _TICK_FNS[key]


def _make_tick_fn(sz, operand, learning_rate, momentum, fault):
    """One optimizer tick of the reference, as ``afmoe``'s: (params,
    velocity, tokens (B, S), labels) -> (params, velocity, loss,
    landed), ``v = momentum * v - learning_rate * mean_b(g_b);  p = p
    + v``, the batch walked one sequence at a time BY THE HOST and each
    sequence's gradients folded straight into the velocity.  ``fault``
    plants what a broken program would do."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("positions",))
    def fold(params, velocity, tokens, labels, decay, step,
             positions=None):
        (loss, landed), grads = jax.value_and_grad(
            sequence_loss, has_aux=True)(params, tokens, labels, sz,
                                         operand, fault, positions)
        return {k: decay * velocity[k] - step * grads[k]
                for k in velocity}, loss, landed

    @functools.partial(jax.jit, donate_argnums=(0,))
    def move(params, velocity):
        return {k: params[k] + velocity[k] for k in params}

    def tick(params, velocity, tokens, labels):
        rows, positions = tokens.shape[0], None
        if fault == "half_batch" and rows > 1:
            rows //= 2
        elif fault == "half_batch":
            positions = tokens.shape[1] // 2
        unchanged = fault == "state_unchanged"
        losses, landed = [], []
        for row in range(rows):
            velocity, loss, here = fold(
                params, velocity, tokens[row], labels[row],
                1.0 if unchanged or row else momentum,
                0.0 if unchanged else learning_rate / rows,
                positions=positions)
            losses.append(loss)
            landed.append(here)
        if not unchanged:
            params = move(params, velocity)
        return params, velocity, sum(losses) / rows, sum(landed)

    return tick


def change_norms(seed, params, sz):
    """Leaf name -> ||p - p0||, p0 made again from the seed leaf by
    leaf inside the program that takes the norm."""
    import jax
    key = seed_key(seed)
    out = {name: _programs_for(name, shape, sz)[1](params[name], key, i)
           for i, (name, shape) in enumerate(leaf_shapes(sz).items())}
    return {k: float(v) for k, v in jax.device_get(out).items()}


def leaf_samples(seed, tree, sz):
    """Leaf name -> ``dense_lm.SAMPLE`` elements at places drawn from
    the seed and the leaf's position in ``leaf_shapes``."""
    import jax
    import numpy
    key = jax.random.fold_in(seed_key(seed), 1 << 20)
    order = {name: i for i, name in enumerate(leaf_shapes(sz))}
    out = {name: _programs_for(name, leaf.shape, sz)[2](leaf, key,
                                                        order[name])
           for name, leaf in tree.items()}
    return {k: numpy.asarray(v) for k, v in jax.device_get(out).items()}


def reference_train(seed, sz, traffic, ticks, operand=None, fault=None):
    """Follows the first ``ticks`` optimizer ticks from the seed and
    returns what is compared (as ``dense_lm.reference_train``), with
    the assignments that landed on the held experts a tick under
    ``landed`` — printed too, beside the program's own count."""
    import jax.numpy as jnp
    seq, batch = traffic["seq"], traffic["batch"]
    params = init_params(seed, sz)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    tokens, labels = make_tokens(seed, batch * ticks, seq, sz["vocab"])
    tick = _tick_fn(sz, operand, traffic["learning_rate"],
                    traffic["momentum"], fault)
    losses, landed = [], []
    for t in range(ticks):
        rows = slice(t * batch, (t + 1) * batch)
        params, velocity, loss, here = tick(
            params, velocity, tokens[rows], labels[rows])
        losses.append(loss)
        landed.append(here)
    out = {"loss": float(jnp.mean(jnp.stack(losses))),
           "tick_losses": [float(x) for x in losses],
           "landed": [float(x) for x in landed],
           "velocity": leaf_norms(velocity),
           "velocity_sample": leaf_samples(seed, velocity, sz),
           "change": change_norms(seed, params, sz)}
    del params, velocity
    _say(phase="reference.moe", seed=seed, operand=operand, fault=fault,
         assignments_landed=sum(out["landed"]))
    return out


# -- the program, built for this family -------------------------------------

def build_trainer(sz, traffic, seed, rows, backend, chips=1):
    """``Launcher`` -> ``TinyLMWorkflow(layers=qwen3_next_layers(...),
    tied_head=False)``, the resident full-batch loader over ``rows``
    seeded sequences in their given order, weights from ``init_params``
    put in before ``initialize``: ``afmoe.build_trainer`` with this
    family's body."""
    from veles_tpu.znicz.samples.qwen3_next import qwen3_next_layers
    import veles_tpu.prng as prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import (FirstTokenLoader,
                                                TinyLMWorkflow)
    if chips != 1:
        raise ValueError("qwen3_next: the share is one chip's; the "
                         "exchange between chips is not in the program")
    seq, vocab = traffic["seq"], sz["vocab"]
    tokens, labels = make_tokens(seed, rows, seq, vocab)

    class SeededCorpus(FirstTokenLoader):
        def __init__(self, workflow, **kwargs):
            super(SeededCorpus, self).__init__(workflow, **kwargs)
            _settle_vector_order(self)

        def load_data(self):
            self.original_data.mem = tokens
            self.original_labels.mem = labels
            self.class_lengths = [0, 0, rows]

    root.common.engine.backend = backend
    root.common.engine.remat = bool(traffic.get("remat", True))
    prng.reset()
    prng.get(0).seed(int(seed) % (2 ** 32))
    launcher = Launcher()
    wf = TinyLMWorkflow(
        launcher, vocab_size=vocab, seq_len=seq, embed_dim=sz["hidden"],
        tied_head=False,
        layers=qwen3_next_layers(
            len(sz["layer_types"]), n_heads=sz["heads"],
            kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
            linear_key_heads=sz["key_heads"],
            linear_value_heads=sz["value_heads"],
            linear_key_dim=sz["key_dim"],
            linear_value_dim=sz["value_dim"],
            moe_intermediate_size=sz["expert_ffn"],
            n_experts=sz["experts"], top_k=sz["top_k"],
            shared_expert_intermediate_size=sz["shared_ffn"],
            full_attention_interval=sz["interval"],
            linear_conv_kernel=sz["conv_kernel"],
            partial_rotary_factor=sz["rope_fraction"],
            rope_theta=sz["rope_theta"], held=(0, sz["held"]),
            norm_topk=sz["norm_topk"], norm_eps=sz["norm_eps"],
            linear_chunk=sz["chunk"]),
        minibatch_size=traffic["batch"],
        ticks_per_dispatch=traffic["ticks"], max_epochs=1 << 30,
        learning_rate=traffic["learning_rate"],
        gradient_moment=traffic["momentum"], loader_cls=SeededCorpus,
        loader_config={"validate_labels": False, "shuffle_limit": 0})
    _put_weights(wf, seed, sz)
    launcher.initialize()
    return Trainer(launcher, wf, sz, traffic, seed)


def _vectors(wf, sz):
    """Leaf name -> the program's Vector."""
    layers = [u for u in wf.forwards if hasattr(u, "spec")]
    norm = wf.forwards[wf.forwards.index(wf.head) - 1]
    leaves = {"embedding.weights": wf.embedding.weights,
              "final_norm.weights": norm.weights,
              "head.weights": wf.head.weights}
    for name in leaf_shapes(sz):
        unit, leaf = name.split(".")
        if unit.startswith("block"):
            leaves[name] = layers[int(unit[5:])].params[leaf]
    return leaves


def _put_weights(wf, seed, sz):
    import jax
    device = jax.local_devices()[0]
    weights = init_params(seed, sz)
    for name, vec in _vectors(wf, sz).items():
        vec.devmem = jax.device_put(weights[name], device)


class Trainer(lfm2_moe.Trainer):
    """What the train driver needs of the built program:
    ``lfm2_moe.Trainer`` (the expert layers' counts, and their needed
    work under ``moe``: this family's sizes carry the keys it reads)
    with this family's leaves and the rule's needed work."""

    def reseed(self, seed):
        import jax
        import jax.numpy as jnp
        import numpy
        device = jax.local_devices()[0]
        wf, traffic = self.wf, self.traffic
        self.seed = seed
        _put_weights(wf, seed, self.sz)
        for gd in wf.gds:
            for vec in gd._velocities.values():
                vec.devmem = jax.device_put(
                    jnp.zeros(vec.shape, vec.dtype), device)
        for vec in [wf.evaluator.epoch_acc, wf.evaluator.health_acc] + \
                [layer.moe_acc for layer in self._layers()]:
            vec.mem = numpy.zeros(vec.shape, vec.dtype)
        loader = wf.loader
        tokens, labels = make_tokens(seed, loader.total_samples,
                                     traffic["seq"], self.sz["vocab"])
        loader.original_data.mem = tokens
        loader.original_labels.mem = labels
        loader.global_offset = 0

    def state_norms(self):
        leaves = _vectors(self.wf, self.sz)
        params = {n: v.devmem for n, v in leaves.items()}
        gd_of = {gd.target: gd for gd in self.wf.gds}
        owner = {id(vec): unit for unit in self.wf.forwards
                 for vec in unit.trainables.values()}
        velocity = {}
        for name, vec in leaves.items():
            slots = gd_of[owner[id(vec)]]._velocities
            velocity[name] = slots["velocity_" + name.split(".")[1]].devmem
        _say(phase="program.moe", seed=self.seed, **self.assignments())
        return {"velocity": leaf_norms(velocity),
                "velocity_sample": leaf_samples(self.seed, velocity,
                                                self.sz),
                "change": change_norms(self.seed, params, self.sz)}

    def attention_traces(self):
        """``lfm2_moe.Trainer``'s (the attention counters and ``moe``),
        and under ``gated_delta`` the rule's needed work a layer and a
        tick with the layer-ticks of a dispatch."""
        out = super(Trainer, self).attention_traces()
        need = gated_delta_cost(self.sz, self.traffic["batch"],
                                self.traffic["seq"])
        need["calls_per_dispatch"] = need["layers"] * self.traffic["ticks"]
        out["gated_delta"] = need
        return out
