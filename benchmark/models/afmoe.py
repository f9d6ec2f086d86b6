"""Family ``afmoe``: Arcee's Trinity LMs (``model_type`` ``afmoe``;
https://huggingface.co/arcee-ai/Trinity-Mini), as veles_tpu trains a
chip's SHARE of one (``znicz/samples/trinity.py``).

What lives here, as in ``dense_lm`` and ``lfm2_moe``: how the PROGRAM
is built for this family and how its state is read; the weights from
the seed; the yardstick's arithmetic (FLOPs a token, the flash
kernels' needed work by KIND of layer, the expert products'); and the
PLAIN REFERENCE — forward, loss, gradients and the momentum-SGD update
in float32 ``jax.numpy`` at ``highest``, importing nothing of the
program.

One sequence, hidden state ``h`` (S, E), float32::

    h0 = embedding[tokens] * sqrt(E)                        mup_enabled
    h = h + rms(operator(rms(h, ln1_g)), ln1_post_g)        sandwich
    h = h + rms(ffn(rms(h, ln2_g)), ln2_post_g)
    rms(x, g) = x * rsqrt(mean(x^2, -1) + eps) * g

    attention:   q = u wq -> (S, H, D);  k, v = u wk, u wv -> (S, KV, D)
                 q, k = rms per head (q_norm_g, k_norm_g)
                 sliding layers: q, k = rope(q, k);  full layers: none
                 a = softmax(q k^T / sqrt(D) + mask) v, each kv head
                 serving H / KV consecutive query heads
                 mask: key j visible to row i iff 0 <= i - j < window
                 (sliding) | j <= i (full)
                 (a.reshape(S, H D) * sigmoid(u wg)) wo      output gate
    dense ffn:   (silu(u w1) * (u w3)) w2
    expert ffn:  s = sigmoid(u router);  idx = top_k(s + expert_bias)
                 w = s[idx] / (sum s[idx] + 1e-20) * route_scale
                 shared(u) + sum_i w_i * ffn_{idx_i}(u) over the HELD
    logits = rms(h, final_norm) @ head                       untied

The share, as ``lfm2_moe``'s: the configuration holds experts ``0 …
held − 1`` of the router's ``experts`` and the first ``vocab`` rows of
the vocabulary; the shared expert is whole (every chip of the eight
computes it alike).  The reference computes every held expert on
EVERY token and weighs the result by the routing: no sort, no groups.
Its attention is an explicit (S, S) mask over one group of heads at a
time; its head and loss go a part of the positions at a time, so that
an 8,192-token sequence over 25,024 rows fits beside the state.
"""

import functools
import math

from benchmark.models import lfm2_moe
from benchmark.models.dense_lm import (
    _dot, _leaf_programs, _settle_vector_order, leaf_norms,
    make_tokens, seed_key)
from benchmark.models.lfm2_moe import (
    CAPACITY_DROP, _block_leaves, _gated, _leaf_maker, _rms_norm,
    _rope, _say, expert_products_cost)

#: Faults ``reference_train`` can plant.  ``window_ignored``: the
#: sliding layers see the whole prefix, as a program that dropped the
#: window would; ``capacity_drop`` as ``lfm2_moe``'s.  ``half_batch``
#: leaves out half of a tick's tokens: half of its sequences, or at
#: one sequence a tick the second half of its positions.
FAULTS = ("half_batch", "state_unchanged", "capacity_drop",
          "window_ignored")
SLIDING, FULL = "sliding_attention", "full_attention"
ROUTE_EPS = 1e-20
ATTENTION_LEAVES = ("ln1_g", "wq", "wk", "wv", "wo", "wg", "q_norm_g",
                    "k_norm_g", "ln1_post_g")
DENSE_LEAVES = ("ln2_g", "w1", "w3", "w2", "ln2_post_g")
EXPERT_LEAVES = ("ln2_g", "router", "w1", "w3", "w2", "ws1", "ws3",
                 "ws2", "ln2_post_g")
#: Parts the head and its loss are computed in, one after the other.
HEAD_PARTS = 8


def sizes(config, rehearse=False):
    """The numbers this family reads from a configuration file.
    ``experts`` is the router's width (the PUBLISHED count), ``held``
    how many of them live here (the file's ``num_experts``)."""
    src = dict(config)
    if rehearse:
        src.update(config["rehearsal"])
    types = tuple(src["layer_types"])
    if len(types) != src["num_hidden_layers"] or \
            set(types) - {SLIDING, FULL} or \
            src["num_attention_heads"] % src["num_key_value_heads"] or \
            src["num_shared_experts"] != 1:
        raise ValueError("layer_types / num_hidden_layers / heads / "
                         "shared experts of %r" % config.get("name"))
    return {"hidden": src["hidden_size"],
            "heads": src["num_attention_heads"],
            "kv_heads": src["num_key_value_heads"],
            "head_dim": src["head_dim"],
            "dense_ffn": src["intermediate_size"],
            "expert_ffn": src["moe_intermediate_size"],
            "experts": src["published"]["num_experts"],
            "held": src["num_experts"],
            "top_k": src["num_experts_per_tok"],
            "vocab": src["vocab_size"],
            "dense_layers": src["num_dense_layers"],
            "layer_types": types,
            "window": src["sliding_window"],
            "rope_theta": float(src["rope_theta"]),
            "norm_eps": src["rms_norm_eps"],
            "route_norm": bool(src["route_norm"]),
            "scaling": float(src["route_scale"]),
            "embed_scale": math.sqrt(src["hidden_size"])
            if src["mup_enabled"] else 1.0,
            "bias_std": config["assumed_sizes"]["expert_bias_std"],
            # what the driver multiplies the flash kernels' calls by:
            # every layer is an attention layer
            "blocks": len(types)}


def _expert_layer(sz, i):
    return i >= sz["dense_layers"]


def _layers_of(sz, kind):
    return [i for i, t in enumerate(sz["layer_types"]) if t == kind]


def leaf_shapes(sz, seq=None):
    """Trainable leaf name -> shape, in a fixed order: ``embedding.
    weights``, ``block<i>.<leaf>``, ``final_norm.weights``,
    ``head.weights``."""
    E, D = sz["hidden"], sz["head_dim"]
    inner, kv = sz["heads"] * D, sz["kv_heads"] * D
    F, G, C = sz["dense_ffn"], sz["expert_ffn"], sz["held"]
    per = {"ln1_g": (E,), "ln2_g": (E,), "ln1_post_g": (E,),
           "ln2_post_g": (E,), "wq": (E, inner), "wk": (E, kv),
           "wv": (E, kv), "wo": (inner, E), "wg": (E, inner),
           "q_norm_g": (D,), "k_norm_g": (D,)}
    dense = {"w1": (E, F), "w3": (E, F), "w2": (F, E)}
    experts = {"router": (E, sz["experts"]), "w1": (C, E, G),
               "w3": (C, E, G), "w2": (C, G, E), "ws1": (E, G),
               "ws3": (E, G), "ws2": (G, E)}
    shapes = {"embedding.weights": (sz["vocab"], E)}
    for i in range(len(sz["layer_types"])):
        ffn = experts if _expert_layer(sz, i) else dense
        for leaf in ATTENTION_LEAVES + (
                EXPERT_LEAVES if _expert_layer(sz, i) else DENSE_LEAVES):
            shapes["block%d.%s" % (i, leaf)] = ffn.get(leaf) or per[leaf]
    shapes["final_norm.weights"] = (E,)
    shapes["head.weights"] = (E, sz["vocab"])
    return shapes


def buffer_shapes(sz):
    """What the model reads and neither trains nor compares: each
    expert layer's selection bias."""
    return {"block%d.expert_bias" % i: (sz["experts"],)
            for i in range(len(sz["layer_types"])) if _expert_layer(sz, i)}


def parameter_count(sz, seq=None):
    return sum(math.prod(s) for s in leaf_shapes(sz).values())


# -- weights from the seed -------------------------------------------------

def _leaf_std(name, sz):
    """The OPT configurations' initialisation carried over: N(0, 0.02)
    embedding, N(0, 1/hidden) every matrix (the router, the gate, the
    shared expert and the free head too), gains 1; the selection bias
    N(0, bias_std)."""
    leaf = name.split(".")[-1]
    if name.startswith("embedding."):
        return 0.02
    if leaf == "expert_bias":
        return sz["bias_std"]
    if leaf.startswith("w") or leaf == "router":      # head.weights too
        return 1.0 / math.sqrt(sz["hidden"])
    return None


def _all_shapes(sz):
    shapes = dict(leaf_shapes(sz))
    shapes.update(buffer_shapes(sz))
    return shapes


def init_params(seed, sz, seq=None):
    """Every leaf and buffer, float32, on the device; a leaf's draw
    follows from its position in ``leaf_shapes``."""
    key = seed_key(seed)
    return {name: _leaf_maker(_leaf_std(name, sz), tuple(shape))(key, i)
            for i, (name, shape) in enumerate(_all_shapes(sz).items())}


def _split(tree, sz):
    buffers = {k: tree[k] for k in buffer_shapes(sz)}
    return {k: v for k, v in tree.items() if k not in buffers}, buffers


# -- the yardstick's arithmetic ---------------------------------------------

def matmul_params_per_token(sz):
    """Parameters a token is multiplied by, forward, on THIS chip:
    every layer's q, gate and output projections (hidden × heads ·
    head_dim each) and k, v; the dense MLP; of each expert layer the
    router, the shared expert and the ``top_k × held / experts``
    routed experts a token meets here on average (one, at 8 of 128
    with 16 held); the free head over the slice."""
    E = sz["hidden"]
    inner = sz["heads"] * sz["head_dim"]
    attention = 3 * E * inner + 2 * E * sz["kv_heads"] * sz["head_dim"]
    expert = 3 * E * sz["expert_ffn"]
    total = sz["vocab"] * E
    for i in range(len(sz["layer_types"])):
        total += attention
        if _expert_layer(sz, i):
            total += E * sz["experts"] + expert + \
                expert * sz["top_k"] * sz["held"] / sz["experts"]
        else:
            total += 3 * E * sz["dense_ffn"]
    return total


def visible_pairs(sz, kind, seq):
    """(row, key) pairs ONE head of one sequence scores in a layer of
    ``kind``, counted exactly: ``Σ_i min(i + 1, window)`` under the
    sliding window, ``S (S + 1) / 2`` under the causal mask alone."""
    w = min(sz["window"], seq) if kind == SLIDING else seq
    return w * (w + 1) // 2 + (seq - w) * w


def attention_flops_forward(sz, kind, seq):
    """QK^T and PV of ONE sequence through ONE layer of ``kind``,
    forward: 2 FLOPs a pair and a head-dim element, twice, over heads
    · head_dim (4,096 here, not the stream's 2,048)."""
    return 4.0 * sz["heads"] * sz["head_dim"] * visible_pairs(sz, kind,
                                                               seq)


def train_flops_per_item(sz, seq):
    """Required FLOPs of forward + backward for one token: 6 a matmul
    parameter it meets and three times the forward attention of every
    layer, by its kind.  Recomputation is not counted; norms, rotary,
    the gate's sigmoid and routing are left out (< 0.1%)."""
    attention = sum(attention_flops_forward(sz, kind, seq)
                    for kind in sz["layer_types"])
    return 6.0 * matmul_params_per_token(sz) + 3.0 * attention / seq


def flash_call_cost(sz, batch, seq, operand_bytes=2):
    """By KIND of layer (``window``, ``full``): FLOPs and HBM bytes the
    three flash kernels NEED for one tick of ``batch`` sequences
    through ONE such layer — as ``dense_lm.flash_call_cost`` (2 needed
    matmuls a kernel, the backward's recomputed QK^T not counted) over
    the visible pairs only and over heads · head_dim; q, o, dO and dq
    at the query heads' width, k, v, dk and dv at the key/value heads'
    (what an algorithm needs; the kernels read them broadcast) — with
    the ``layers`` of that kind and their ``units`` in the scope
    table, so a reader can tell the calls apart."""
    out = {}
    for name, kind in (("window", SLIDING), ("full", FULL)):
        one = attention_flops_forward(sz, kind, seq) / 2 * batch
        wide = batch * seq * sz["heads"] * sz["head_dim"] * operand_bytes
        narrow = batch * seq * sz["kv_heads"] * sz["head_dim"] * \
            operand_bytes
        rows = batch * sz["heads"] * seq * 4
        layers = _layers_of(sz, kind)
        out[name] = {
            "fwd": {"flops": 2 * one,
                    "bytes": 2 * wide + 2 * narrow + rows},
            "dq": {"flops": 2 * one,
                   "bytes": 3 * wide + 2 * narrow + 2 * rows},
            "dkv": {"flops": 2 * one,
                    "bytes": 2 * wide + 4 * narrow + 2 * rows},
            "layers": len(layers),
            "units": ["block%d" % i for i in layers]}
    return out


# -- the plain reference ----------------------------------------------------

def _head_group(sz, seq):
    """Heads a group: a group's (S, S) scores stay near 256 MB."""
    group = max(1, (1 << 26) // (seq * seq))
    while sz["heads"] % group:
        group -= 1
    return group


def _attention(q, k, v, dot, head_group, window):
    """Softmax attention of one sequence, (S, H, D) each, under the
    causal mask and, where ``window`` is a number, the window's; heads
    in groups of ``head_group`` so the S x S scores fit."""
    import jax
    import jax.numpy as jnp
    S, H, D = q.shape
    row = jnp.arange(S)[:, None]
    col = jnp.arange(S)[None, :]
    mask = col <= row
    if window is not None:
        mask = mask & (row - col < window)

    @jax.checkpoint
    def group(qkv):
        qg, kg, vg = qkv                                # (G, S, D)
        scores = dot(qg, kg.transpose(0, 2, 1)) / math.sqrt(D)
        scores = jnp.where(mask, scores, -jnp.inf)
        return dot(jax.nn.softmax(scores, axis=-1), vg)

    def split(x):
        return x.transpose(1, 0, 2).reshape(H // head_group,
                                            head_group, S, D)
    out = jax.lax.map(group, (split(q), split(k), split(v)))
    return out.reshape(H, S, D).transpose(1, 0, 2)


def _attention_operator(p, u, sz, kind, dot, head_group, fault):
    import jax
    import jax.numpy as jnp
    S = u.shape[0]
    H, KV = sz["heads"], sz["kv_heads"]
    q = dot(u, p["wq"]).reshape(S, H, -1)
    k = dot(u, p["wk"]).reshape(S, KV, -1)
    v = dot(u, p["wv"]).reshape(S, KV, -1)
    q = _rms_norm(q, p["q_norm_g"], sz["norm_eps"])
    k = _rms_norm(k, p["k_norm_g"], sz["norm_eps"])
    window = None
    if kind == SLIDING:
        q, k = _rope(q, sz["rope_theta"]), _rope(k, sz["rope_theta"])
        if fault != "window_ignored":
            window = sz["window"]
    k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
    a = _attention(q, k, v, dot, head_group, window).reshape(S, -1)
    # the gate's sigmoid in float32 from the float32 product
    return dot(a * jax.nn.sigmoid(dot(u, p["wg"])), p["wo"])


def route(u, router, bias, sz):
    """(idx (S, k), weights (S, k)): float32 at ``highest`` whatever
    the matmuls' operands are — the configuration keeps the router
    there."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(jnp.matmul(u, router,
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias, sz["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sz["route_norm"]:
        w = w / (w.sum(axis=-1, keepdims=True) + ROUTE_EPS)
    return idx, w * sz["scaling"]


def routed_ffn(p, bias, u, sz, dot, fault=None, first=0, held=None):
    """The held experts' part of one expert layer for one sequence,
    and how many assignments landed on them: every held expert's FFN
    over EVERY token, weighed by the routing (zero where the token did
    not choose it), one expert after the other, added up as they
    come.  ``first`` / ``held`` (default: the configuration's share,
    experts 0 … held − 1; ``p``'s matrices are the held ones) let a
    test walk every share of an uncut layer."""
    import jax
    import jax.numpy as jnp
    held = sz["held"] if held is None else held
    idx, w = route(u, p["router"], bias, sz)
    cap = CAPACITY_DROP * u.shape[0] * sz["top_k"] / sz["experts"]

    @jax.checkpoint
    def one(e, w1, w3, w2):
        hit = idx == first + e                               # (S, k)
        if fault == "capacity_drop":
            queue = jnp.cumsum(hit.any(axis=-1))             # 1, 2, ...
            hit = hit & (queue <= cap)[:, None]
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


def expert_ffn(p, bias, u, sz, dot, fault=None, first=0, held=None):
    """One expert layer's FFN for one sequence: the shared expert over
    every token and the routed part of the share."""
    y, landed = routed_ffn(p, bias, u, sz, dot, fault, first, held)
    return _gated(u, p["ws1"], p["ws3"], p["ws2"], dot) + y, landed


def _layer(p, bias, h, sz, i, dot, head_group, fault):
    import jax.numpy as jnp
    eps = sz["norm_eps"]
    a = _attention_operator(p, _rms_norm(h, p["ln1_g"], eps), sz,
                            sz["layer_types"][i], dot, head_group, fault)
    h = h + _rms_norm(a, p["ln1_post_g"], eps)
    u = _rms_norm(h, p["ln2_g"], eps)
    if _expert_layer(sz, i):
        f, landed = expert_ffn(p, bias, u, sz, dot, fault)
    else:
        f, landed = _gated(u, p["w1"], p["w3"], p["w2"], dot), \
            jnp.float32(0.0)
    return h + _rms_norm(f, p["ln2_post_g"], eps), landed


def forward_hidden(params, buffers, tokens, sz, operand=None,
                   fault=None):
    """(the last layer's output (S, E), assignments landed on the held
    experts over the layers) for ONE sequence; a layer at a time, each
    rematerialised."""
    import jax
    import jax.numpy as jnp
    dot = _dot(operand)
    group = _head_group(sz, tokens.shape[0])
    h = params["embedding.weights"][tokens] * sz["embed_scale"]
    landed = jnp.float32(0.0)
    for i in range(len(sz["layer_types"])):
        h, here = jax.checkpoint(functools.partial(
            _layer, sz=sz, i=i, dot=dot, head_group=group, fault=fault))(
                _block_leaves(params, i),
                buffers.get("block%d.expert_bias" % i), h)
        landed = landed + here
    return h, landed


def sequence_loss(params, buffers, tokens, labels, sz, operand=None,
                  fault=None, positions=None):
    """(mean next-token cross-entropy over the first ``positions`` of
    ONE sequence (None: all), assignments landed).  The head and its
    loss go ``HEAD_PARTS`` parts of the positions one after the
    other, each rematerialised: a part's logits are 100 MB at 8,192
    tokens over 25,024 rows, not 820."""
    import jax
    import jax.numpy as jnp
    dot = _dot(operand)
    h, landed = forward_hidden(params, buffers, tokens, sz, operand,
                               fault)
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
    """One optimizer tick of the reference, as ``lfm2_moe``'s:
    (params, velocity, buffers, tokens (B, S), labels) -> (params,
    velocity, loss, landed), ``v = momentum * v - learning_rate *
    mean_b(g_b);  p = p + v``, the batch walked one sequence at a time
    BY THE HOST and each sequence's gradients folded straight into
    the velocity.  ``fault`` plants what a broken program would do."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("positions",))
    def fold(params, velocity, buffers, tokens, labels, decay, step,
             positions=None):
        (loss, landed), grads = jax.value_and_grad(
            sequence_loss, has_aux=True)(params, buffers, tokens, labels,
                                         sz, operand, fault, positions)
        return {k: decay * velocity[k] - step * grads[k]
                for k in velocity}, loss, landed

    @functools.partial(jax.jit, donate_argnums=(0,))
    def move(params, velocity):
        return {k: params[k] + velocity[k] for k in params}

    def tick(params, velocity, buffers, tokens, labels):
        rows, positions = tokens.shape[0], None
        if fault == "half_batch" and rows > 1:
            rows //= 2
        elif fault == "half_batch":
            positions = tokens.shape[1] // 2
        unchanged = fault == "state_unchanged"
        losses, landed = [], []
        for row in range(rows):
            velocity, loss, here = fold(
                params, velocity, buffers, tokens[row], labels[row],
                1.0 if unchanged or row else momentum,
                0.0 if unchanged else learning_rate / rows,
                positions=positions)
            losses.append(loss)
            landed.append(here)
        if not unchanged:
            params = move(params, velocity)
        return params, velocity, sum(losses) / rows, sum(landed)

    return tick


def _programs_for(name, shape, sz):
    return _leaf_programs(_leaf_std(name, sz),
                          _leaf_std(name, sz) is None, tuple(shape))


def change_norms(seed, params, sz):
    """Leaf name -> ||p - p0||, p0 made again from the seed leaf by
    leaf inside the program that takes the norm."""
    import jax
    key = seed_key(seed)
    out = {name: _programs_for(name, shape, sz)[0](params[name], key, i)
           for i, (name, shape) in enumerate(leaf_shapes(sz).items())}
    return {k: float(v) for k, v in jax.device_get(out).items()}


def leaf_samples(seed, tree, sz):
    """Leaf name -> ``dense_lm.SAMPLE`` elements at places drawn from
    the seed and the leaf's position in ``leaf_shapes``."""
    import jax
    import numpy
    key = jax.random.fold_in(seed_key(seed), 1 << 20)
    order = {name: i for i, name in enumerate(leaf_shapes(sz))}
    out = {name: _programs_for(name, leaf.shape, sz)[1](leaf, key,
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
    params, buffers = _split(init_params(seed, sz), sz)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    tokens, labels = make_tokens(seed, batch * ticks, seq, sz["vocab"])
    tick = _tick_fn(sz, operand, traffic["learning_rate"],
                    traffic["momentum"], fault)
    losses, landed = [], []
    for t in range(ticks):
        rows = slice(t * batch, (t + 1) * batch)
        params, velocity, loss, here = tick(
            params, velocity, buffers, tokens[rows], labels[rows])
        losses.append(loss)
        landed.append(here)
    out = {"loss": float(jnp.mean(jnp.stack(losses))),
           "tick_losses": [float(x) for x in losses],
           "landed": [float(x) for x in landed],
           "velocity": leaf_norms(velocity),
           "velocity_sample": leaf_samples(seed, velocity, sz),
           "change": change_norms(seed, params, sz)}
    del params, velocity, buffers
    _say(phase="reference.moe", seed=seed, operand=operand, fault=fault,
         assignments_landed=sum(out["landed"]))
    return out


# -- the program, built for this family -------------------------------------

def build_trainer(sz, traffic, seed, rows, backend, chips=1):
    """``Launcher`` -> ``TinyLMWorkflow(layers=trinity_layers(...),
    tied_head=False, embed_scale=sqrt(hidden))``, the resident
    full-batch loader over ``rows`` seeded sequences in their given
    order, weights and selection biases from ``init_params`` put in
    before ``initialize``: ``lfm2_moe.build_trainer`` with this
    family's body."""
    from veles_tpu.znicz.samples.trinity import trinity_layers
    import veles_tpu.prng as prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import (FirstTokenLoader,
                                                TinyLMWorkflow)
    if chips != 1:
        raise ValueError("afmoe: the share is one chip's; the exchange "
                         "between chips is not in the program")
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
        tied_head=False, embed_scale=sz["embed_scale"],
        layers=trinity_layers(
            sz["layer_types"], n_heads=sz["heads"],
            kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
            intermediate_size=sz["dense_ffn"],
            moe_intermediate_size=sz["expert_ffn"],
            n_experts=sz["experts"], top_k=sz["top_k"],
            sliding_window=sz["window"],
            num_dense_layers=sz["dense_layers"], held=(0, sz["held"]),
            rope_theta=sz["rope_theta"], route_norm=sz["route_norm"],
            route_scale=sz["scaling"], norm_eps=sz["norm_eps"]),
        minibatch_size=traffic["batch"],
        ticks_per_dispatch=traffic["ticks"], max_epochs=1 << 30,
        learning_rate=traffic["learning_rate"],
        gradient_moment=traffic["momentum"], loader_cls=SeededCorpus,
        loader_config={"validate_labels": False, "shuffle_limit": 0})
    _put_weights(wf, seed, sz)
    launcher.initialize()
    return Trainer(launcher, wf, sz, traffic, seed)


def _vectors(wf, sz):
    """(leaf name -> the program's Vector, buffer name -> Vector)."""
    layers = [u for u in wf.forwards if hasattr(u, "spec")]
    norm = wf.forwards[wf.forwards.index(wf.head) - 1]
    leaves = {"embedding.weights": wf.embedding.weights,
              "final_norm.weights": norm.weights,
              "head.weights": wf.head.weights}
    for name in leaf_shapes(sz):
        unit, leaf = name.split(".")
        if unit.startswith("block"):
            leaves[name] = layers[int(unit[5:])].params[leaf]
    buffers = {name: layers[int(name.split(".")[0][5:])].expert_bias
               for name in buffer_shapes(sz)}
    return leaves, buffers


def _put_weights(wf, seed, sz):
    import jax
    device = jax.local_devices()[0]
    weights = init_params(seed, sz)
    for vectors in _vectors(wf, sz):
        for name, vec in vectors.items():
            vec.devmem = jax.device_put(weights[name], device)


def window_tiles(window):
    """What the flash forward's traces counted for calls with this
    window (``attention.flash.tiles_visited`` / ``.tiles_total``
    labelled ``window=``), or None where the program counted none: off
    a TPU, or a program without the labelled series."""
    try:
        from veles_tpu.observability.metrics import registry
    except ImportError:
        return None
    label = {"window": str(window)}
    found = [registry.peek("attention.flash.tiles_" + what, label)
             for what in ("visited", "total")]
    if None in found:
        return None
    return {"visited": found[0].value, "total": found[1].value}


class Trainer(lfm2_moe.Trainer):
    """What the train driver needs of the built program:
    ``lfm2_moe.Trainer`` (the expert layers' counts, and their needed
    work under ``moe``: this family's sizes carry the same keys) with
    this family's leaves and the window layers' tile counts."""

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
        leaves, _ = _vectors(self.wf, self.sz)
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
        and under ``window_tiles`` the window layers' tile counts."""
        out = super(Trainer, self).attention_traces()
        tiles = window_tiles(self.sz["window"])
        if tiles:
            out["window_tiles"] = tiles
        return out
