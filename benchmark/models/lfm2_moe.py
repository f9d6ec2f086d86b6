"""Family ``lfm2_moe``: LiquidAI's hybrid LM with sparse experts
(``model_type`` ``lfm2_moe``; https://huggingface.co/LiquidAI/LFM2-24B-A2B),
as veles_tpu trains a chip's SHARE of it (``znicz/samples/lfm2.py``).

What lives here, as in ``dense_lm``: how the PROGRAM is built for this
family and how its state is read; the weights from the seed; the
yardstick's arithmetic (FLOPs a token, the flash kernels' and the
expert products' needed work); and the PLAIN REFERENCE — forward, loss,
gradients and the momentum-SGD update in float32 ``jax.numpy`` at
``highest``, importing nothing of the program.

One layer, for hidden state ``h`` (S, E) of one sequence::

    h = h + operator(rmsnorm(h, ln1_g));  h = h + ffn(rmsnorm(h, ln2_g))
    rmsnorm(x, g) = x * rsqrt(mean(x^2, -1) + eps) * g

    conv operator:  b, c, x = split(u @ w_in, 3)
                    z[t] = sum_j w_conv[:, j] * (b * x)[t - (K-1) + j]
                    (c * z) @ w_out                  causal, depthwise
    attention:      q, k = rope(rmsnorm(u @ wq | wk per head, *_norm_g))
                    softmax(q k^T / sqrt(D) + causal) v, each kv head
                    serving H / KV query heads;  @ wo
    dense ffn:      (silu(u @ w1) * (u @ w3)) @ w2
    expert ffn:     s = sigmoid(u @ router);  idx = top_k(s + expert_bias)
                    w = s[idx] / (sum s[idx] + 1e-6) * scaling
                    sum_i w_i * ffn_{idx_i}(u)   over the experts HELD
    logits = rmsnorm(h, final_norm) @ embedding^T    (tied: assumed)

The share: the configuration holds experts ``0 … held − 1`` of the
router's ``experts`` and the first ``vocab`` rows of the vocabulary.
The reference is given the same share: what the absent experts would
add is left out, as in the program, and that partial result goes on.
The reference computes every held expert on EVERY token and weighs the
result by the routing (zero where the token did not choose it): no
sort, no groups, nothing the program's dropless path is made of.
"""

import functools
import json
import math

from benchmark.models import dense_lm
from benchmark.models.dense_lm import (
    _attention, _dot, _head_group, _leaf_programs, _settle_vector_order,
    leaf_norms, make_tokens, seed_key)

#: Faults ``reference_train`` can plant.  ``capacity_drop``: each held
#: expert takes, of a sequence's assignments to it, only the first
#: 0.75 × the mean load — what a capacity dispatch does on overflow;
#: the comparison has to SEE a dropped token.
FAULTS = ("half_batch", "state_unchanged", "capacity_drop")
CAPACITY_DROP = 0.75
ATTENTION_LEAVES = ("ln1_g", "wq", "wk", "wv", "wo", "q_norm_g",
                    "k_norm_g")
CONV_LEAVES = ("ln1_g", "w_in", "w_conv", "w_out")
DENSE_LEAVES = ("ln2_g", "w1", "w3", "w2")
EXPERT_LEAVES = ("ln2_g", "router", "w1", "w3", "w2")


def sizes(config, rehearse=False):
    """The numbers this family reads from a configuration file.
    ``experts`` is the router's width (the PUBLISHED count), ``held``
    how many of them live here (the file's ``num_experts``)."""
    src = dict(config)
    if rehearse:
        src.update(config["rehearsal"])
    types = tuple(src["layer_types"])
    if len(types) != src["num_hidden_layers"] or \
            src["hidden_size"] % src["num_attention_heads"]:
        raise ValueError("layer_types / num_hidden_layers / heads of %r"
                         % config.get("name"))
    return {"hidden": src["hidden_size"],
            "heads": src["num_attention_heads"],
            "kv_heads": src["num_key_value_heads"],
            "dense_ffn": src["intermediate_size"],
            "expert_ffn": src["moe_intermediate_size"],
            "experts": src["published"]["num_experts"],
            "held": src["num_experts"],
            "top_k": src["num_experts_per_tok"],
            "vocab": src["vocab_size"],
            "dense_layers": src["num_dense_layers"],
            "layer_types": types,
            "conv_kernel": src["conv_L_cache"],
            "rope_theta": float(src["rope_parameters"]["rope_theta"]),
            "norm_eps": src["norm_eps"],
            "norm_topk": bool(src["norm_topk_prob"]),
            "scaling": float(src["routed_scaling_factor"]),
            "bias_std": config["assumed_sizes"]["expert_bias_std"],
            # what the driver multiplies the flash kernels' calls by
            "blocks": types.count("full_attention")}


def _expert_layer(sz, i):
    return i >= sz["dense_layers"]


def leaf_shapes(sz, seq=None):
    """Trainable leaf name -> shape, in a fixed order: ``embedding.
    weights``, ``block<i>.<leaf>``, ``final_norm.weights``."""
    E, H = sz["hidden"], sz["heads"]
    D = E // H
    kv = sz["kv_heads"] * D
    K, F, G, C = (sz["conv_kernel"], sz["dense_ffn"], sz["expert_ffn"],
                  sz["held"])
    per = {"ln1_g": (E,), "ln2_g": (E,), "wq": (E, E), "wk": (E, kv),
           "wv": (E, kv), "wo": (E, E), "q_norm_g": (D,),
           "k_norm_g": (D,), "w_in": (E, 3 * E), "w_conv": (E, K),
           "w_out": (E, E)}
    dense = {"w1": (E, F), "w3": (E, F), "w2": (F, E)}
    experts = {"router": (E, sz["experts"]), "w1": (C, E, G),
               "w3": (C, E, G), "w2": (C, G, E)}
    shapes = {"embedding.weights": (sz["vocab"], E)}
    for i, kind in enumerate(sz["layer_types"]):
        ffn = experts if _expert_layer(sz, i) else dense
        for leaf in (CONV_LEAVES if kind == "conv" else ATTENTION_LEAVES) \
                + (EXPERT_LEAVES if _expert_layer(sz, i) else DENSE_LEAVES):
            shapes["block%d.%s" % (i, leaf)] = ffn.get(leaf) or per[leaf]
    shapes["final_norm.weights"] = (E,)
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
    embedding, N(0, 1/hidden) every matrix (the router too), gains 1;
    the taps N(0, 1/K); the selection bias N(0, bias_std)."""
    leaf = name.split(".")[-1]
    if name.startswith("embedding."):
        return 0.02
    if leaf == "expert_bias":
        return sz["bias_std"]
    if leaf == "w_conv":
        return 1.0 / math.sqrt(sz["conv_kernel"])
    if leaf.startswith("w") or leaf == "router":
        return 1.0 / math.sqrt(sz["hidden"])
    return None


def _all_shapes(sz):
    shapes = dict(leaf_shapes(sz))
    shapes.update(buffer_shapes(sz))
    return shapes


@functools.lru_cache(maxsize=None)
def _leaf_maker(std, shape):
    """One small program for leaves of one kind and shape, the leaf's
    index a traced argument (as ``dense_lm._leaf_programs``): a dozen
    compiles for a hundred leaves, where one program over the whole
    tree took 45 s to compile on the chip's host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, index):
        if std is None:
            return jnp.ones(shape, jnp.float32)
        return std * jax.random.normal(jax.random.fold_in(key, index),
                                       shape, jnp.float32)
    return make


def init_params(seed, sz, seq=None):
    """Every leaf and buffer, float32, on the device; a leaf's draw
    follows from its position in ``leaf_shapes`` (what
    ``dense_lm._leaf_programs`` makes again to take a change's norm)."""
    key = seed_key(seed)
    return {name: _leaf_maker(_leaf_std(name, sz), tuple(shape))(key, i)
            for i, (name, shape) in enumerate(_all_shapes(sz).items())}


def _split(tree, sz):
    buffers = {k: tree[k] for k in buffer_shapes(sz)}
    return {k: v for k, v in tree.items() if k not in buffers}, buffers


# -- the yardstick's arithmetic ---------------------------------------------

def matmul_params_per_token(sz):
    """Parameters a token is multiplied by, forward, on THIS chip: the
    operators' projections, the dense MLP, the router, the tied head
    over the slice, and of each expert layer the ``top_k × held /
    experts`` experts a token meets here on average (0.5 of one at 4
    of 64 with 8 held)."""
    E, D = sz["hidden"], sz["hidden"] // sz["heads"]
    conv = 4 * E * E
    attention = 2 * E * E + 2 * E * sz["kv_heads"] * D
    total = sz["vocab"] * E
    for i, kind in enumerate(sz["layer_types"]):
        total += conv if kind == "conv" else attention
        if _expert_layer(sz, i):
            total += E * sz["experts"] + 3 * E * sz["expert_ffn"] * \
                sz["top_k"] * sz["held"] / sz["experts"]
        else:
            total += 3 * E * sz["dense_ffn"]
    return total


def train_flops_per_item(sz, seq):
    """Required FLOPs of forward + backward for one token: 6 a matmul
    parameter it meets and three times the forward attention (causal
    half) of the attention layers.  Recomputation is not counted; the
    taps, norms, rotary and routing are left out (< 0.1%)."""
    attention = 4.0 * seq * seq * sz["hidden"] / 2
    return 6.0 * matmul_params_per_token(sz) + \
        3.0 * attention * sz["blocks"] / seq


def flash_call_cost(sz, batch, seq, operand_bytes=2):
    """As ``dense_lm.flash_call_cost``: after the key/value heads are
    broadcast the kernels see ``heads`` heads of ``hidden / heads``."""
    return dense_lm.flash_call_cost(sz, batch, seq, operand_bytes)


def expert_products_cost(sz, landed, operand_bytes=2):
    """FLOPs and HBM bytes the grouped products of ONE expert layer
    NEED for ``landed`` assignments, forward + backward, whatever
    implements them: three products of 2 · hidden · expert_ffn FLOPs
    an assignment forward and twice that backward; the held experts'
    three matrices and the assignments' rows in and out, once forward
    and twice backward (the operands' and the weights' gradients)."""
    E, G = sz["hidden"], sz["expert_ffn"]
    forward_flops = 3 * 2.0 * E * G * landed
    forward_bytes = 3 * sz["held"] * E * G * operand_bytes + \
        landed * E * (operand_bytes + 4)
    return {"flops": 3 * forward_flops, "bytes": 3 * forward_bytes}


# -- the plain reference ----------------------------------------------------

def _rms_norm(x, gain, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt((x * x).mean(axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """Rotate-half rotary positions of (S, H, D)."""
    import jax.numpy as jnp
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + half * sin


def _conv_operator(p, u, dot, K):
    import jax.numpy as jnp
    b, c, x = jnp.split(dot(u, p["w_in"]), 3, axis=-1)
    bx = b * x
    z = jnp.zeros_like(bx)
    for j in range(K):
        back = K - 1 - j                       # tap j reads t - back
        z = z + p["w_conv"][:, j] * jnp.concatenate(
            [jnp.zeros_like(bx[:back]), bx[:bx.shape[0] - back]])
    return dot(c * z, p["w_out"])


def _attention_operator(p, u, sz, dot, head_group):
    import jax.numpy as jnp
    S = u.shape[0]
    H, KV = sz["heads"], sz["kv_heads"]
    q = dot(u, p["wq"]).reshape(S, H, -1)
    k = dot(u, p["wk"]).reshape(S, KV, -1)
    v = dot(u, p["wv"]).reshape(S, KV, -1)
    q = _rope(_rms_norm(q, p["q_norm_g"], sz["norm_eps"]), sz["rope_theta"])
    k = _rope(_rms_norm(k, p["k_norm_g"], sz["norm_eps"]), sz["rope_theta"])
    k, v = (jnp.repeat(t, H // KV, axis=1) for t in (k, v))
    return dot(_attention(q, k, v, dot, head_group).reshape(S, -1),
               p["wo"])


def _gated(u, w1, w3, w2, dot):
    import jax
    return dot(jax.nn.silu(dot(u, w1)) * dot(u, w3), w2)


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
    if sz["norm_topk"]:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    return idx, w * sz["scaling"]


def expert_ffn(p, bias, u, sz, dot, fault=None, first=0, held=None):
    """The held experts' part of one expert layer for one sequence,
    and how many assignments landed on them: every held expert's FFN
    over EVERY token, weighed by the routing (zero where the token did
    not choose it), one expert after the other.  ``first`` / ``held``
    (default: the configuration's share, experts 0 … held − 1; ``p``'s
    matrices are the held ones) let a test walk every share of an
    uncut layer."""
    import jax
    import jax.numpy as jnp
    held = sz["held"] if held is None else held
    idx, w = route(u, p["router"], bias, sz)
    cap = CAPACITY_DROP * u.shape[0] * sz["top_k"] / sz["experts"]

    def one(expert):
        e, w1, w3, w2 = expert
        hit = idx == first + e                               # (S, k)
        if fault == "capacity_drop":
            queue = jnp.cumsum(hit.any(axis=-1))             # 1, 2, ...
            hit = hit & (queue <= cap)[:, None]
        return (w * hit).sum(axis=-1)[:, None] * _gated(
            u, w1, w3, w2, dot), hit.sum().astype(jnp.float32)

    y, landed = jax.lax.map(one, (jnp.arange(held), p["w1"][:held],
                                  p["w3"][:held], p["w2"][:held]))
    return y.sum(axis=0), landed.sum()


def _layer(p, bias, h, sz, i, dot, head_group, fault):
    import jax.numpy as jnp
    u = _rms_norm(h, p["ln1_g"], sz["norm_eps"])
    if sz["layer_types"][i] == "conv":
        h = h + _conv_operator(p, u, dot, sz["conv_kernel"])
    else:
        h = h + _attention_operator(p, u, sz, dot, head_group)
    u = _rms_norm(h, p["ln2_g"], sz["norm_eps"])
    if not _expert_layer(sz, i):
        return h + _gated(u, p["w1"], p["w3"], p["w2"], dot), \
            jnp.float32(0.0)
    y, landed = expert_ffn(p, bias, u, sz, dot, fault)
    return h + y, landed


def _block_leaves(tree, i):
    prefix = "block%d." % i
    return {k[len(prefix):]: v for k, v in tree.items()
            if k.startswith(prefix)}


def sequence_loss(params, buffers, tokens, labels, sz, operand=None,
                  fault=None):
    """(mean next-token cross-entropy over ONE sequence, assignments
    landed on the held experts over its layers); a layer at a time,
    each rematerialised."""
    import jax
    import jax.numpy as jnp
    dot = _dot(operand)
    group = _head_group(sz, tokens.shape[0])
    h = params["embedding.weights"][tokens]
    landed = jnp.float32(0.0)
    for i in range(len(sz["layer_types"])):
        h, here = jax.checkpoint(functools.partial(
            _layer, sz=sz, i=i, dot=dot, head_group=group, fault=fault))(
                _block_leaves(params, i),
                buffers.get("block%d.expert_bias" % i), h)
        landed = landed + here
    h = _rms_norm(h, params["final_norm.weights"], sz["norm_eps"])
    logp = jax.nn.log_softmax(dot(h, params["embedding.weights"].T),
                              axis=-1)
    loss = -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
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
    """One optimizer tick of the reference: (params, velocity, buffers,
    tokens (B, S), labels) -> (params, velocity, loss, landed), as
    ``v = momentum * v - learning_rate * mean_b(g_b);  p = p + v``.
    The batch is walked one sequence at a time BY THE HOST and each
    sequence's gradients go straight into the velocity (scaled by the
    momentum once, before the first): what lives is the parameters,
    the velocity, one sequence's gradients and one layer of it — a
    sum of the batch's gradients beside them does not fit the chip.
    ``fault`` plants what a broken program would do."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(1,))
    def fold(params, velocity, buffers, tokens, labels, decay, step):
        (loss, landed), grads = jax.value_and_grad(
            sequence_loss, has_aux=True)(params, buffers, tokens, labels,
                                         sz, operand, fault)
        return {k: decay * velocity[k] - step * grads[k]
                for k in velocity}, loss, landed

    @functools.partial(jax.jit, donate_argnums=(0,))
    def move(params, velocity):
        return {k: params[k] + velocity[k] for k in params}

    def tick(params, velocity, buffers, tokens, labels):
        rows = tokens.shape[0] // 2 if fault == "half_batch" \
            else tokens.shape[0]
        unchanged = fault == "state_unchanged"
        losses, landed = [], []
        for row in range(rows):
            velocity, loss, here = fold(
                params, velocity, buffers, tokens[row], labels[row],
                1.0 if unchanged or row else momentum,
                0.0 if unchanged else learning_rate / rows)
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


def _say(**fields):
    """One JSON object a line on stdout, as the harness's own lines."""
    print(json.dumps(fields), flush=True)


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
    """``Launcher`` -> ``TinyLMWorkflow(layers=lfm2_layers(...))``, the
    resident full-batch loader over ``rows`` seeded sequences in their
    given order, weights and selection biases from ``init_params`` put
    in before ``initialize``: ``dense_lm.build_trainer`` with this
    family's body."""
    from veles_tpu.znicz.samples.lfm2 import lfm2_layers
    import veles_tpu.prng as prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import (FirstTokenLoader,
                                                TinyLMWorkflow)
    if chips != 1:
        raise ValueError("lfm2_moe: the share is one chip's; the "
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
        layers=lfm2_layers(
            sz["layer_types"], n_heads=sz["heads"],
            kv_heads=sz["kv_heads"], intermediate_size=sz["dense_ffn"],
            moe_intermediate_size=sz["expert_ffn"],
            n_experts=sz["experts"], top_k=sz["top_k"],
            num_dense_layers=sz["dense_layers"], held=(0, sz["held"]),
            rope_theta=sz["rope_theta"], conv_kernel=sz["conv_kernel"],
            norm_topk=sz["norm_topk"], routed_scaling=sz["scaling"],
            norm_eps=sz["norm_eps"]),
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
              "final_norm.weights": norm.weights}
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


class Trainer(dense_lm.Trainer):
    """What the train driver needs of the built program:
    ``dense_lm.Trainer`` with this family's leaves and the expert
    layers' counts."""

    def _layers(self):
        return [u for u in self.wf.forwards
                if getattr(u, "has_experts", False)]

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

    def assignments(self):
        """What the expert layers' on-device accumulators hold for the
        train class since the start (the benchmark drives the loader
        alone, so no ``DecisionGD`` empties them): assignments made
        and landed over the layers, ticks, and the fullest held
        expert's share of what landed on its layer."""
        from veles_tpu.loader.base import TRAIN
        rows = [layer.read_moe_share(TRAIN) for layer in self._layers()]
        return {"assignments_made": float(sum(r[0] for r in rows)),
                "assignments_landed": float(sum(r[1] for r in rows)),
                "ticks": float(max(r[2] for r in rows)),
                "max_load_frac": float(max(
                    r[3:].max() / max(r[1], 1.0) for r in rows))}

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
        """The attention counters, and under ``moe`` what the expert
        layers counted (``assignments``) with the needed work of their
        grouped products a layer and a tick."""
        out = super(Trainer, self).attention_traces()
        moe = self.assignments()
        if moe["ticks"]:
            layers = len(self._layers())
            moe["products"] = expert_products_cost(
                self.sz, moe["assignments_landed"] /
                (moe["ticks"] * layers))
            moe["products_per_dispatch"] = layers * self.traffic["ticks"]
            out["moe"] = moe
        return out
