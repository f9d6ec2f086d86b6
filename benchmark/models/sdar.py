"""Family ``sdar``: JetLM's SDAR MoE LMs (``model_type`` ``sdar_moe``;
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat), a Qwen3-MoE body
trained by BLOCK DIFFUSION (BD3-LM, arXiv:2503.09573; masked
diffusion's weighted loss, MDLM, arXiv:2406.07524), as veles_tpu trains
a chip's SHARE of one (``znicz/samples/sdar.py``).

What lives here, as in ``afmoe`` and ``joyai_llm_flash``: how the
PROGRAM is built for this family and how its state is read; the weights
from the seed; the yardstick's arithmetic (FLOPs an item, the flash
kernels' needed work over the block-diffusion mask's visible pairs, the
expert products'); and the PLAIN REFERENCE — noising, forward, loss,
gradients and the momentum-SGD update in float32 ``jax.numpy`` at
``highest``, importing nothing of the program.

Clean ids ``x0`` (L,), blocks of ``B`` positions, ``β(i) = i // B``::

    t_b ~ U(eps, 1) a block;  m_i = u_i < t_β(i), u_i ~ U(0, 1)
        drawn from key = fold_in(seed_key(seed), tick), the tick counted
        from the start: t from fold_in(key, 0) as (batch, L / B), u from
        fold_in(key, 1) as (batch, L), row b of each for sequence b
    xt_i = MASK (the slice's last row) where m_i, else x0_i
    X = [xt ; x0] (2L rows),  P = [0 … L−1 ; 0 … L−1]
    mask: a noised row r sees noised keys c with β(c) = β(r) and clean
          keys with β(c) < β(r); a clean row sees clean keys with
          β(c) ≤ β(r) and no noised key
    a layer:  u = rms(h, ln1_g)
              q, k = rope_P(rms per head(u wq | u wk, q_norm_g | k_norm_g))
              v = u wv;  each of the kv heads serves H / KV query heads
              a = h + softmax(q k^T / sqrt(D) + mask) v wo
              r = rms(a, ln2_g);  p = softmax(r router) over ALL experts
              the top k, their weights renormalised to sum 1
              h' = a + sum over the top k ∩ HELD of w_e (silu(r w1_e) *
                   (r w3_e)) w2_e
    logits_i = rms(h_final[i], final_norm) @ head, noised rows i < L
    loss = (1 / L) Σ_{i: m_i} CE(logits_i, x0_i) / t_β(i)

The share, as ``afmoe``'s: experts ``0 … held − 1`` of the router's
``experts`` and the first ``vocab`` rows of the vocabulary.  The
reference's attention is a dense mask built a block of query rows at a
time (all heads, each block rematerialised), its experts a held expert
at a time over every row (``qwen3_next.routed_ffn``), its head a part
of the noised rows at a time.
"""

import functools
import math

from benchmark.models import lfm2_moe
from benchmark.models.afmoe import HEAD_PARTS
from benchmark.models.dense_lm import (
    _dot, _leaf_programs, _settle_vector_order, leaf_norms, make_tokens,
    seed_key)
from benchmark.models.lfm2_moe import (
    _block_leaves, _leaf_maker, _rms_norm, _say)
from benchmark.models.qwen3_next import routed_ffn

#: Faults ``reference_train`` can plant.  ``block_leak``: the noised
#: rows see their OWN block's clean keys too — the one mask bug the
#: kernels' walk could have that leaves every shape as it is;
#: ``half_batch``: at one sequence a tick, the loss of the first half
#: of its positions alone.
FAULTS = ("half_batch", "state_unchanged", "block_leak")
LAYER_LEAVES = ("ln1_g", "wq", "wk", "wv", "wo", "q_norm_g", "k_norm_g",
                "ln2_g", "router", "w1", "w3", "w2")
#: Query rows a block of the reference's attention takes at once.
QUERY_BLOCK = 256


def sizes(config, rehearse=False):
    """The numbers this family reads from a configuration file.
    ``experts`` is the router's width (the PUBLISHED count), ``held``
    how many of them live here (the file's ``num_experts``)."""
    src = dict(config)
    if rehearse:
        src.update(config["rehearsal"])
    if src["decoder_sparse_step"] != 1 or src["mlp_only_layers"] or \
            src["num_attention_heads"] % src["num_key_value_heads"] or \
            src["use_sliding_window"] or src["attention_bias"] or \
            src["tie_word_embeddings"] or src["rope_scaling"] is not None:
        raise ValueError("layers / heads / window / head of %r"
                         % config.get("name"))
    assumed = config["assumed_sizes"]
    if src["max_position_embeddings"] % assumed["block_length"]:
        raise ValueError("blocks of %d" % assumed["block_length"])
    return {"hidden": src["hidden_size"],
            "heads": src["num_attention_heads"],
            "kv_heads": src["num_key_value_heads"],
            "head_dim": src["head_dim"],
            "expert_ffn": src["moe_intermediate_size"],
            "experts": src["published"]["num_experts"],
            "held": src["num_experts"],
            "top_k": src["num_experts_per_tok"],
            "norm_topk": bool(src["norm_topk_prob"]),
            "vocab": src["vocab_size"],
            "layers": src["num_hidden_layers"],
            "rope_theta": float(src["rope_theta"]),
            "norm_eps": src["rms_norm_eps"],
            "block": assumed["block_length"],
            "eps": assumed["noise_eps"],
            # the depth the initialisation scales by: the published model's
            "published_layers": src["published"].get(
                "num_hidden_layers", src["num_hidden_layers"]),
            # what the driver multiplies the flash kernels' calls by:
            # every layer is an attention layer
            "blocks": src["num_hidden_layers"]}


def mask_id(sz):
    """MASK is the slice's last row; the traffic draws clean ids from
    the rows before it."""
    return sz["vocab"] - 1


def _units(sz):
    return ["block%d" % i for i in range(sz["layers"])]


def leaf_shapes(sz, seq=None):
    """Trainable leaf name -> shape, in a fixed order: ``embedding.
    weights``, ``block<i>.<leaf>``, ``final_norm.weights``,
    ``head.weights``."""
    E, D, C, G = sz["hidden"], sz["head_dim"], sz["held"], sz["expert_ffn"]
    inner, kv = sz["heads"] * D, sz["kv_heads"] * D
    per = {"ln1_g": (E,), "wq": (E, inner), "wk": (E, kv), "wv": (E, kv),
           "wo": (inner, E), "q_norm_g": (D,), "k_norm_g": (D,),
           "ln2_g": (E,), "router": (E, sz["experts"]), "w1": (C, E, G),
           "w3": (C, E, G), "w2": (C, G, E)}
    shapes = {"embedding.weights": (sz["vocab"], E)}
    for i in range(sz["layers"]):
        for leaf in LAYER_LEAVES:
            shapes["block%d.%s" % (i, leaf)] = per[leaf]
    shapes["final_norm.weights"] = (E,)
    shapes["head.weights"] = (E, sz["vocab"])
    return shapes


def parameter_count(sz, seq=None):
    return sum(math.prod(s) for s in leaf_shapes(sz).values())


# -- weights from the seed -------------------------------------------------

#: The leaves torchtitan draws at its depth-scaled ``init_std``: its
#: Llama ``wo``, ``w2``, ``w3`` and its MoE router.
DEPTH_SCALED = ("wo", "w2", "w3", "router")


def _leaf_std(name, sz):
    """torchtitan's Llama / MoE initialisation (``depth_init`` off),
    with ``1/sqrt(hidden)`` for its 0.02: N(0, 1) embedding; N(0,
    1/hidden) every other matrix (the free head too), ``wo``, ``w2``,
    ``w3`` and the router scaled by ``1/sqrt(2 n)`` for the published
    model's ``n`` layers (:data:`DEPTH_SCALED`; 1/sqrt(96)); the layers'
    gains 1 (the final norm's, a leaf named ``weights``, is drawn as a
    matrix, as every family here draws it).

    Why this one (PERF.md §6): only masked rows carry the loss, and they
    all start from the ONE MASK row, so they share their expert choice;
    where that choice holds no held expert, a layer's router learns from
    the few masked rows at its edge alone, and the bfloat16 rounding of
    what attention and the experts add flipped one of them often enough
    to move the router's state by several percent against the float32
    reference.  At this scale what the layers add is small beside the
    float32 embedding, and the masked rows sit as one block, far from a
    tie.  The other configurations' N(0, 0.02) embedding is too small
    here: from the second layer on, the part of attention's output that
    every row shares outweighs a row's own token and every row of a
    sequence picks the same experts."""
    leaf = name.split(".")[-1]
    if name.startswith("embedding."):
        return 1.0
    if leaf.startswith("w") or leaf == "router":      # head.weights too
        std = 1.0 / math.sqrt(sz["hidden"])
        if name.startswith("block") and leaf in DEPTH_SCALED:
            std /= math.sqrt(2 * sz["published_layers"])
        return std
    return None


def init_params(seed, sz, seq=None):
    """Every leaf, float32, on the device; a leaf's draw follows from
    its position in ``leaf_shapes``."""
    key = seed_key(seed)
    return {name: _leaf_maker(_leaf_std(name, sz), tuple(shape))(key, i)
            for i, (name, shape) in enumerate(leaf_shapes(sz).items())}


# -- the yardstick's arithmetic ---------------------------------------------

def row_params(sz):
    """Parameters ONE row (of either stream) is multiplied by in one
    layer, forward, on THIS chip: q, k, v, o, the router and the
    ``top_k × held / experts`` routed experts a row meets here on
    average (one, at 8 of 128 with 16 held)."""
    E, D = sz["hidden"], sz["head_dim"]
    attention = 2 * E * sz["heads"] * D + 2 * E * sz["kv_heads"] * D
    expert = 3 * E * sz["expert_ffn"]
    return attention + E * sz["experts"] + \
        expert * sz["top_k"] * sz["held"] / sz["experts"]


def visible_pairs(sz, seq):
    """(row, key) pairs ONE head scores in one layer over the two
    streams of a sequence of ``seq`` clean ids, counted exactly: a clean
    row sees ``B (β + 1)`` clean keys, a noised row ``B β`` clean keys
    and its own block's ``B`` noised ones — ``L² + L · B``."""
    return seq * seq + seq * sz["block"]


def attention_flops_forward(sz, seq):
    """QK^T and PV of ONE sequence's two streams through ONE layer,
    forward: 2 FLOPs a visible pair and a head-dim element, twice, over
    heads · head_dim."""
    return 4.0 * sz["heads"] * sz["head_dim"] * visible_pairs(sz, seq)


def train_flops_per_item(sz, seq):
    """Required FLOPs of forward + backward for one item, a CLEAN token
    (``seq`` of them a sequence): 6 a matmul parameter a row meets,
    over its TWO rows and every layer; 6 the head's slice, once (the
    noised row); and three times every layer's forward attention over
    the visible pairs, a sequence's over its ``seq`` items.
    Recomputation is not counted; norms, rotary and routing are left
    out."""
    return 6.0 * 2 * sz["layers"] * row_params(sz) + \
        6.0 * sz["hidden"] * sz["vocab"] + \
        3.0 * sz["layers"] * attention_flops_forward(sz, seq) / seq


def flash_call_cost(sz, batch, seq, operand_bytes=2):
    """For the one kind this family has (``block_diffusion``): FLOPs and
    HBM bytes the three flash kernels NEED for one tick of ``batch``
    sequences through ONE layer, as ``afmoe.flash_call_cost`` over the
    block-diffusion mask's visible pairs (2 needed matmuls a kernel at
    ``head_dim``) and over the ``2 seq`` rows of the two streams; q, o,
    dO and dq at the query heads' width, k, v, dk and dv at the
    key/value heads' — with the ``layers`` and their ``units`` in the
    scope table."""
    H, KV, D = sz["heads"], sz["kv_heads"], sz["head_dim"]
    rows = 2 * seq * batch
    flops = 4.0 * H * D * visible_pairs(sz, seq) * batch
    wide = rows * H * D * operand_bytes
    narrow = rows * KV * D * operand_bytes
    stats = rows * H * 4
    units = _units(sz)
    return {"block_diffusion": {
        "fwd": {"flops": flops, "bytes": 2 * wide + 2 * narrow + stats},
        "dq": {"flops": flops, "bytes": 3 * wide + 2 * narrow + 2 * stats},
        "dkv": {"flops": flops,
                "bytes": 2 * wide + 4 * narrow + 2 * stats},
        "layers": len(units), "units": units}}


# -- the plain reference ----------------------------------------------------

def noise(seed, tick, batch, seq, sz):
    """``(t, m)`` (batch, seq) of one tick: each block's mask rate at
    its positions and the mask (module docstring)."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(seed_key(seed), tick)
    t = jax.random.uniform(jax.random.fold_in(key, 0),
                           (batch, seq // sz["block"]), jnp.float32,
                           sz["eps"], 1.0)
    t = jnp.repeat(t, sz["block"], axis=1)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (batch, seq),
                           jnp.float32)
    return t, u < t


def visible(rows, cols, seq, block, leak=False):
    """The block-diffusion mask of the module docstring (``leak``: a
    noised row sees its own block's clean keys as well)."""
    import jax.numpy as jnp
    r_clean, c_clean = rows >= seq, cols >= seq
    rb = jnp.where(r_clean, rows - seq, rows) // block
    cb = jnp.where(c_clean, cols - seq, cols) // block
    sees_clean = cb <= rb if leak else cb < rb
    return jnp.where(r_clean, c_clean & (cb <= rb),
                     jnp.where(c_clean, sees_clean, cb == rb))


def _rope_at(x, theta, positions):
    """Rotate-half rotary positions of (S, H, D) at ``positions``."""
    import jax.numpy as jnp
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + half * sin


def _attention(q, k, v, dot, sz, leak):
    """Softmax attention of the two streams, (2L, H, D) each, under the
    block-diffusion mask, ``QUERY_BLOCK`` query rows at a time (every
    head; the block's mask and (H, rows, 2L) scores rematerialised)."""
    import jax
    import jax.numpy as jnp
    S, H, D = q.shape
    rows = math.gcd(QUERY_BLOCK, S)
    cols = jnp.arange(S)[None, :]
    kt, vt = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # (H, S, D)

    @jax.checkpoint
    def block(xs):
        qb, first = xs                                    # (rows, H, D)
        mask = visible(first + jnp.arange(rows)[:, None], cols, S // 2,
                       sz["block"], leak)
        scores = dot(qb.transpose(1, 0, 2), kt.transpose(0, 2, 1)) / \
            math.sqrt(D)                                  # (H, rows, S)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return dot(jax.nn.softmax(scores, axis=-1), vt).transpose(1, 0, 2)

    out = jax.lax.map(block, (q.reshape(S // rows, rows, H, D),
                              jnp.arange(0, S, rows)))
    return out.reshape(S, H, D)


def _layer(p, h, sz, dot, leak):
    import jax.numpy as jnp
    eps = sz["norm_eps"]
    S = h.shape[0]
    H, KV = sz["heads"], sz["kv_heads"]
    positions = jnp.tile(jnp.arange(S // 2), 2)
    u = _rms_norm(h, p["ln1_g"], eps)
    q = _rms_norm(dot(u, p["wq"]).reshape(S, H, -1), p["q_norm_g"], eps)
    k = _rms_norm(dot(u, p["wk"]).reshape(S, KV, -1), p["k_norm_g"], eps)
    v = dot(u, p["wv"]).reshape(S, KV, -1)
    q = _rope_at(q, sz["rope_theta"], positions)
    k = _rope_at(k, sz["rope_theta"], positions)
    k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
    a = h + dot(_attention(q, k, v, dot, sz, leak).reshape(S, -1), p["wo"])
    y, landed = routed_ffn(p, _rms_norm(a, p["ln2_g"], eps), sz, dot)
    return a + y, landed


def forward_hidden(params, ids, sz, operand=None, fault=None):
    """(the last layer's output (2L, E) of the two streams ``ids``,
    assignments landed on the held experts over the layers) for ONE
    sequence; a layer at a time, each rematerialised."""
    import jax
    import jax.numpy as jnp
    dot = _dot(operand)
    h = params["embedding.weights"][ids]
    landed = jnp.float32(0.0)
    for i in range(sz["layers"]):
        h, here = jax.checkpoint(functools.partial(
            _layer, sz=sz, dot=dot, leak=fault == "block_leak"))(
                _block_leaves(params, i), h)
        landed = landed + here
    return h, landed


def sequence_loss(params, clean, t, masked, sz, operand=None, fault=None,
                  positions=None):
    """(masked diffusion's loss of ONE sequence — ``(1 / n) Σ_{i < n,
    m_i} CE(logits_i, x0_i) / t_i`` over the first ``n = positions``
    (None: all ``L``) noised rows — , assignments landed).  The head
    and its loss go ``HEAD_PARTS`` parts of the noised rows one after
    the other, each rematerialised."""
    import jax
    import jax.numpy as jnp
    dot = _dot(operand)
    L = clean.shape[0]
    ids = jnp.concatenate([jnp.where(masked, mask_id(sz), clean), clean])
    h, landed = forward_hidden(params, ids, sz, operand, fault)
    h = _rms_norm(h[:L], params["final_norm.weights"], sz["norm_eps"])
    E = h.shape[1]
    counted = L if positions is None else positions
    weight = masked / t * (jnp.arange(L) < counted) / counted
    parts = math.gcd(HEAD_PARTS, L)

    @jax.checkpoint
    def part(xs):
        x, wanted, share = xs
        logp = jax.nn.log_softmax(dot(x, params["head.weights"]), axis=-1)
        return -(jnp.take_along_axis(logp, wanted[:, None],
                                     axis=-1)[:, 0] * share).sum()

    loss = jax.lax.map(part, (h.reshape(parts, L // parts, E),
                              clean.reshape(parts, L // parts),
                              weight.reshape(parts, L // parts))).sum()
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
    velocity, clean (B, L), t, masked) -> (params, velocity, loss,
    landed), ``v = momentum * v - learning_rate * mean_b(g_b);  p = p +
    v``, the batch walked one sequence at a time BY THE HOST."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("positions",))
    def fold(params, velocity, clean, t, masked, decay, step,
             positions=None):
        (loss, landed), grads = jax.value_and_grad(
            sequence_loss, has_aux=True)(params, clean, t, masked, sz,
                                         operand, fault, positions)
        return {k: decay * velocity[k] - step * grads[k]
                for k in velocity}, loss, landed

    @functools.partial(jax.jit, donate_argnums=(0,))
    def move(params, velocity):
        return {k: params[k] + velocity[k] for k in params}

    def tick(params, velocity, clean, t, masked):
        rows, positions = clean.shape[0], None
        if fault == "half_batch" and rows > 1:
            rows //= 2
        elif fault == "half_batch":
            positions = clean.shape[1] // 2
        unchanged = fault == "state_unchanged"
        losses, landed = [], []
        for row in range(rows):
            velocity, loss, here = fold(
                params, velocity, clean[row], t[row], masked[row],
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


def clean_tokens(seed, rows, seq, sz):
    """The traffic's clean ids: uniform over the slice's rows but the
    last (MASK)."""
    return make_tokens(seed, rows, seq, mask_id(sz))[0]


def reference_train(seed, sz, traffic, ticks, operand=None, fault=None):
    """Follows the first ``ticks`` optimizer ticks from the seed and
    returns what is compared (as ``dense_lm.reference_train``), with the
    assignments that landed on the held experts a tick under ``landed``
    and the masked share of the tokens under ``masked_frac`` — printed
    too, beside the program's own counts."""
    import jax.numpy as jnp
    seq, batch = traffic["seq"], traffic["batch"]
    params = init_params(seed, sz)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    clean = jnp.asarray(clean_tokens(seed, batch * ticks, seq, sz))
    tick = _tick_fn(sz, operand, traffic["learning_rate"],
                    traffic["momentum"], fault)
    losses, landed, masked_n = [], [], 0.0
    for n in range(ticks):
        t, masked = noise(seed, n, batch, seq, sz)
        masked_n += float(masked.sum())
        params, velocity, loss, here = tick(
            params, velocity, clean[n * batch:(n + 1) * batch], t, masked)
        losses.append(loss)
        landed.append(here)
    out = {"loss": float(jnp.mean(jnp.stack(losses))),
           "tick_losses": [float(x) for x in losses],
           "landed": [float(x) for x in landed],
           "masked_frac": masked_n / (ticks * batch * seq),
           "velocity": leaf_norms(velocity),
           "velocity_sample": leaf_samples(seed, velocity, sz),
           "change": change_norms(seed, params, sz)}
    del params, velocity
    _say(phase="reference.moe", seed=seed, operand=operand, fault=fault,
         assignments_landed=sum(out["landed"]),
         masked_frac=out["masked_frac"])
    return out


# -- the program, built for this family -------------------------------------

def program_layers(sz):
    """The specs of ``samples/sdar.py`` at these sizes."""
    from veles_tpu.znicz.samples.sdar import sdar_layers
    return sdar_layers(
        sz["layers"], n_heads=sz["heads"], kv_heads=sz["kv_heads"],
        head_dim=sz["head_dim"], moe_intermediate_size=sz["expert_ffn"],
        n_experts=sz["experts"], top_k=sz["top_k"], held=(0, sz["held"]),
        rope_theta=sz["rope_theta"], norm_topk=sz["norm_topk"],
        norm_eps=sz["norm_eps"])


def build_trainer(sz, traffic, seed, rows, backend, chips=1):
    """``Launcher`` -> ``TinyLMWorkflow(layers=..., tied_head=False,
    diffusion=...)``, the resident full-batch loader over ``rows``
    seeded sequences of clean ids in their given order, weights and the
    noising seed put in before ``initialize``: ``afmoe``'s with this
    family's body."""
    import veles_tpu.prng as prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import (FirstTokenLoader,
                                                TinyLMWorkflow)
    if chips != 1:
        raise ValueError("sdar: the share is one chip's; the exchange "
                         "between chips is not in the program")
    seq, vocab = traffic["seq"], sz["vocab"]
    tokens = clean_tokens(seed, rows, seq, sz)

    class SeededCorpus(FirstTokenLoader):
        def __init__(self, workflow, **kwargs):
            super(SeededCorpus, self).__init__(workflow, **kwargs)
            _settle_vector_order(self)

        def load_data(self):
            self.original_data.mem = tokens
            self.original_labels.mem = tokens.copy()
            self.class_lengths = [0, 0, rows]

    root.common.engine.backend = backend
    root.common.engine.remat = bool(traffic.get("remat", True))
    prng.reset()
    prng.get(0).seed(int(seed) % (2 ** 32))
    launcher = Launcher()
    wf = TinyLMWorkflow(
        launcher, vocab_size=vocab, seq_len=seq, embed_dim=sz["hidden"],
        tied_head=False, layers=program_layers(sz),
        diffusion={"block": sz["block"], "eps": sz["eps"],
                   "mask_id": mask_id(sz), "seed": int(seed)},
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


def bd_tiles():
    """What the flash forward's traces counted for block-diffusion calls
    (``attention.flash.tiles_visited`` / ``.tiles_total`` labelled
    ``mask="block_diffusion"``), or None where the program counted none:
    off a TPU, or a program without the labelled series."""
    try:
        from veles_tpu.observability.metrics import registry
    except ImportError:
        return None
    label = {"mask": "block_diffusion"}
    found = [registry.peek("attention.flash.tiles_" + what, label)
             for what in ("visited", "total")]
    if None in found:
        return None
    return {"visited": found[0].value, "total": found[1].value}


class Trainer(lfm2_moe.Trainer):
    """What the train driver needs of the built program:
    ``lfm2_moe.Trainer`` (the expert layers' counts and their needed
    work under ``moe``) with this family's leaves, its noising state and
    the block-diffusion calls' tile counts."""

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
        for vec in [wf.evaluator.epoch_acc, wf.evaluator.health_acc,
                    wf.evaluator.diffusion_acc, wf.embedding.noise_tick] + \
                [layer.moe_acc for layer in self._layers()]:
            vec.mem = numpy.zeros(vec.shape, vec.dtype)
        wf.embedding.noise_seed.mem = numpy.array(
            [int(seed) >> 31, int(seed) & 0x7FFFFFFF], dtype=numpy.uint32)
        loader = wf.loader
        tokens = clean_tokens(seed, loader.total_samples, traffic["seq"],
                              self.sz)
        loader.original_data.mem = tokens
        loader.original_labels.mem = tokens.copy()
        loader.global_offset = 0

    def state_norms(self):
        from veles_tpu.loader.base import TRAIN
        leaves = _vectors(self.wf, self.sz)
        params = {n: v.devmem for n, v in leaves.items()}
        gd_of = {gd.target: gd for gd in self.wf.gds}
        owner = {id(vec): unit for unit in self.wf.forwards
                 for vec in unit.trainables.values()}
        velocity = {}
        for name, vec in leaves.items():
            slots = gd_of[owner[id(vec)]]._velocities
            velocity[name] = slots["velocity_" + name.split(".")[1]].devmem
        _say(phase="program.moe", seed=self.seed,
             masked_frac=self.wf.evaluator.read_masked_frac(TRAIN),
             **self.assignments())
        return {"velocity": leaf_norms(velocity),
                "velocity_sample": leaf_samples(self.seed, velocity,
                                                self.sz),
                "change": change_norms(self.seed, params, self.sz)}

    def attention_traces(self):
        """``lfm2_moe.Trainer``'s (the attention counters and ``moe``),
        and under ``bd_tiles`` the block-diffusion calls' tile counts."""
        out = super(Trainer, self).attention_traces()
        tiles = bd_tiles()
        if tiles:
            out["bd_tiles"] = tiles
        return out
