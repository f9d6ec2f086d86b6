"""Family ``dense_lm``: the dense pre-LN decoder veles_tpu trains and
serves (``znicz/attention.py``, ``samples/tinylm.py``).

Four things live here and nowhere else:

* how the PROGRAM is built for this family (``build_trainer``:
  ``Launcher`` -> ``TinyLMWorkflow``, the weights put in from the
  benchmark's own seeded generator) and how its state is read;
* the weights themselves, made on the device in one jitted call from
  the seed (``init_params``);
* the yardstick's arithmetic: required FLOPs and bytes per item and per
  kernel call, from shapes alone (``train_flops_per_item`` ...);
* the PLAIN REFERENCE: forward, loss, gradients and the momentum-SGD
  update in straightforward float32 ``jax.numpy`` at ``highest`` matmul
  precision.  It imports nothing of the program and takes nothing the
  program made.  The same function computed with lower-precision matmul
  operands, or with a fault planted, stands in for the program when the
  control is read (``benchmark/control.py``).

The block follows OPT (arXiv:2205.01068): pre-LN, LayerNorm eps 1e-5,
learned positions, full causal multi-head attention, ReLU MLP of
``ffn_dim``, tied head.  The repo's departures from OPT are mirrored
here, never patched in the program: no final LayerNorm before the
head, no offset of 2 in the position table, the repo's initialisation
(N(0, 0.02) embeddings, N(0, 1/hidden) block matrices, zero biases),
SGD with momentum.
"""

import functools
import math

MATRIX_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w2")
BLOCK_LEAVES = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo",
                "bq", "bk", "bv", "bo",
                "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")
LN_EPS = 1e-5
#: Faults ``reference_train`` can plant (what a broken program would
#: do); anything else ``benchmark/control.py`` is given as a variant is
#: a matmul operand type.
FAULTS = ("half_batch", "state_unchanged")


def sizes(config, rehearse=False):
    """The numbers this family reads from a configuration file."""
    src = dict(config)
    if rehearse:
        src.update(config["rehearsal"])
    out = {"hidden": src["hidden_size"],
           "heads": src["num_attention_heads"],
           "ffn": src["ffn_dim"], "vocab": src["vocab_size"],
           "positions": src["max_position_embeddings"],
           "blocks": src["num_hidden_layers"]}
    if out["ffn"] != 4 * out["hidden"]:
        raise ValueError("the repo's block has an MLP of 4 x hidden; "
                         "ffn_dim %d is not" % out["ffn"])
    return out


def leaf_shapes(sz, seq):
    """Leaf name -> shape, in a fixed order.  Names are
    ``embedding.weights``, ``embedding.pos``, ``block<i>.<leaf>``."""
    E, F = sz["hidden"], sz["ffn"]
    per_block = {"ln1_g": (E,), "ln1_b": (E,), "wq": (E, E),
                 "wk": (E, E), "wv": (E, E), "wo": (E, E),
                 "bq": (E,), "bk": (E,), "bv": (E,), "bo": (E,),
                 "ln2_g": (E,), "ln2_b": (E,), "w1": (E, F),
                 "b1": (F,), "w2": (F, E), "b2": (E,)}
    shapes = {"embedding.weights": (sz["vocab"], E),
              "embedding.pos": (seq, E)}
    for i in range(sz["blocks"]):
        for leaf in BLOCK_LEAVES:
            shapes["block%d.%s" % (i, leaf)] = per_block[leaf]
    return shapes


def parameter_count(sz, seq):
    return sum(math.prod(s) for s in leaf_shapes(sz, seq).values())


# -- weights from the seed -------------------------------------------------

def seed_key(seed):
    """A JAX key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


def _leaf_std(name, sz):
    leaf = name.split(".")[-1]
    if name.startswith("embedding."):
        return 0.02
    if leaf in MATRIX_LEAVES:
        return 1.0 / math.sqrt(sz["hidden"])
    return None


def init_leaf(key, index, name, shape, sz):
    """One leaf from the seed: the repo's initialisation in
    distribution (not bit for bit: the generator is the benchmark's)."""
    import jax
    import jax.numpy as jnp
    std = _leaf_std(name, sz)
    if std is not None:
        return std * jax.random.normal(jax.random.fold_in(key, index),
                                       shape, jnp.float32)
    if name.endswith("_g"):
        return jnp.ones(shape, jnp.float32)
    return jnp.zeros(shape, jnp.float32)


@functools.lru_cache(maxsize=None)
def _init_program(sizes, seq):
    import jax
    sz = dict(sizes)
    shapes = leaf_shapes(sz, seq)

    def make(key):
        return {name: init_leaf(key, i, name, shape, sz)
                for i, (name, shape) in enumerate(shapes.items())}

    return jax.jit(make)


def init_params(seed, sz, seq):
    """Every leaf, float32, on the device, in ONE jitted call."""
    return _init_program(tuple(sorted(sz.items())), seq)(seed_key(seed))


def make_tokens(seed, rows, seq, vocab):
    """Uniform random tokens and next-token labels, host arrays."""
    import numpy
    rng = numpy.random.default_rng(int(seed))
    tokens = rng.integers(0, vocab, (rows, seq), dtype=numpy.int32)
    return tokens, numpy.roll(tokens, -1, axis=1)


# -- the yardstick's arithmetic ---------------------------------------------

def matmul_params_per_token(sz):
    """Parameters a token is multiplied by, forward: 12 E^2 a block
    (q, k, v, o and the 8 E^2 MLP) and the tied head V E."""
    E = sz["hidden"]
    return 12 * E * E * sz["blocks"] + sz["vocab"] * E


def attention_flops_forward(sz, seq, causal_half=True):
    """QK^T and PV of ONE sequence through ONE block, forward: 4 S^2 E,
    or the half a causal mask needs."""
    flops = 4.0 * seq * seq * sz["hidden"]
    return flops / 2 if causal_half else flops


def train_flops_per_item(sz, seq):
    """Required FLOPs of forward + backward for one token: 6 a matmul
    parameter, and three times the forward attention (backward is two
    matmuls for each forward one), causal half counted.  Recomputation
    (remat, flash backward's second QK^T) is NOT counted; the update
    and the LayerNorm/softmax elementwise work are left out (< 0.1%)."""
    return 6.0 * matmul_params_per_token(sz) + \
        3.0 * attention_flops_forward(sz, seq) * sz["blocks"] / seq


def flash_call_cost(sz, batch, seq, operand_bytes=2):
    """FLOPs and HBM bytes the three flash kernels NEED for one tick of
    ``batch`` sequences through ONE block: forward (QK^T, PV), dq
    (QK^T recomputed is not needed work: dP = dO V^T and dQ = dS K
    are), dk/dv (dV = P^T dO, dK = dS^T Q).  Needed matmuls: 2 forward,
    1 + 1 for dq (dP, dQ), 2 for dk/dv; each 2 S^2 D per head, causal
    half counted.  Bytes: each kernel reads its (B, S, H, D) operands
    (bfloat16, the activation stream's type) and writes its results
    once, with the float32 softmax statistics rows."""
    E = sz["hidden"]
    # one S x S x D matmul, all heads, causal half
    one = 2.0 * seq * seq * E / 2 * batch
    tensor = batch * seq * E * operand_bytes  # one (B, S, H, D) array
    rows = batch * sz["heads"] * seq * 4      # lse / delta rows, f32
    return {
        "fwd": {"flops": 2 * one, "bytes": 4 * tensor + rows},
        "dq": {"flops": 2 * one, "bytes": 5 * tensor + 2 * rows},
        "dkv": {"flops": 2 * one, "bytes": 6 * tensor + 2 * rows},
    }


# -- the plain reference ----------------------------------------------------

#: What ``operand`` may name besides None: significand bits kept and the
#: exponent's width, as ``jax.lax.reduce_precision`` takes them.
OPERAND_FORMATS = {"bfloat16": (8, 7), "fp8_e4m3": (4, 3)}


def _round_operand(x, exponent_bits, mantissa_bits):
    """``x`` on the grid of a narrower float format, scaled per tensor
    by a power of two so that its largest element sits just under the
    format's top (what any fp8 matmul path does: unscaled, N(0, 1/64)
    weights fall under e4m3's smallest normal).  ``reduce_precision``
    and not ``astype``: the compiler may drop a narrowing cast that is
    widened again at once (``xla_allow_excess_precision``), and the
    control would then read the precision it was meant to undercut."""
    import jax
    import jax.numpy as jnp
    top = 2.0 ** (2 ** (exponent_bits - 1) - 1)      # e4m3: 128 .. 240
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 2.0 ** jnp.floor(jnp.log2(top / amax))
    r = jax.lax.reduce_precision(x * scale, exponent_bits,
                                 mantissa_bits) / scale
    # Straight through: the operand is rounded, its cotangent is not
    # (a weaker control than a real fp8 path, which rounds both).
    return x + jax.lax.stop_gradient(r - x)


def _dot(operand):
    """The reference's matmul.  ``operand`` None: float32 operands at
    ``highest`` precision (the reference proper).  A name from
    ``OPERAND_FORMATS``: both operands rounded to that format first,
    products accumulated in float32: the configuration's own arithmetic
    for ``bfloat16``, the control's for ``fp8_e4m3``."""
    import jax
    import jax.numpy as jnp
    if operand is None:
        return functools.partial(jnp.matmul,
                                 precision=jax.lax.Precision.HIGHEST)
    exponent_bits, mantissa_bits = OPERAND_FORMATS[operand]

    def rounded(x):
        if operand == "bfloat16":
            return x.astype(jnp.bfloat16)
        # exact in bfloat16: 3 bits of a power-of-two multiple
        return _round_operand(x, exponent_bits, mantissa_bits).astype(
            jnp.bfloat16)

    def dot(a, b):
        return jnp.matmul(rounded(a), rounded(b),
                          preferred_element_type=jnp.float32)
    return dot


def _layer_norm(x, gamma, beta):
    import jax.numpy as jnp
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * gamma + beta


def _attention(q, k, v, dot, head_group):
    """Causal softmax attention of one sequence, (S, H, D) each; heads
    in groups of ``head_group`` so the S x S scores fit."""
    import jax
    import jax.numpy as jnp
    S, H, D = q.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def group(qkv):
        qg, kg, vg = qkv                                # (G, S, D)
        scores = dot(qg, kg.transpose(0, 2, 1)) / math.sqrt(D)
        scores = jnp.where(causal, scores, -jnp.inf)
        return dot(jax.nn.softmax(scores, axis=-1), vg)

    def split(x):
        return x.transpose(1, 0, 2).reshape(H // head_group,
                                            head_group, S, D)
    out = jax.lax.map(group, (split(q), split(k), split(v)))
    return out.reshape(H, S, D).transpose(1, 0, 2)


def _block(p, x, heads, dot, head_group):
    S, E = x.shape
    h = _layer_norm(x, p["ln1_g"], p["ln1_b"])
    q = (dot(h, p["wq"]) + p["bq"]).reshape(S, heads, -1)
    k = (dot(h, p["wk"]) + p["bk"]).reshape(S, heads, -1)
    v = (dot(h, p["wv"]) + p["bv"]).reshape(S, heads, -1)
    a = _attention(q, k, v, dot, head_group).reshape(S, E)
    x = x + dot(a, p["wo"]) + p["bo"]
    h = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    import jax.numpy as jnp
    h = jnp.maximum(dot(h, p["w1"]) + p["b1"], 0.0)
    return x + dot(h, p["w2"]) + p["b2"]


def _head_group(sz, seq):
    """Heads per group: scores of a group stay near 128 MB."""
    group = max(1, (1 << 25) // (seq * seq))
    while sz["heads"] % group:
        group -= 1
    return group


def forward_hidden(params, tokens, sz, operand=None):
    """The last block's output (S, E) for ONE sequence of tokens (S,)."""
    import jax
    dot = _dot(operand)
    S = tokens.shape[0]
    group = _head_group(sz, S)
    x = params["embedding.weights"][tokens] + \
        params["embedding.pos"][:S]
    for i in range(sz["blocks"]):
        p = {leaf: params["block%d.%s" % (i, leaf)]
             for leaf in BLOCK_LEAVES}
        x = jax.checkpoint(functools.partial(
            _block, heads=sz["heads"], dot=dot,
            head_group=group))(p, x)
    return x


def forward_logits(params, tokens, sz, operand=None):
    """Logits (S, V) of ONE sequence of tokens (S,): the architecture's
    forward pass, nothing cached, nothing batched."""
    return _dot(operand)(forward_hidden(params, tokens, sz, operand),
                         params["embedding.weights"].T)


def sequence_loss(params, tokens, labels, sz, operand=None):
    """Mean next-token cross-entropy over one sequence."""
    import jax
    import jax.numpy as jnp
    logits = forward_logits(params, tokens, sz, operand)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


def _lean_sequence_loss(params, tokens, labels, sz, operand):
    """``sequence_loss`` for a stand-in with rounded operands, whose
    rounded copies of the weights take the room that a sequence's
    logits are kept in: nothing of a sequence is kept for the backward
    pass, and the head and its loss go a quarter of the positions at a
    time."""
    import jax
    import jax.numpy as jnp
    dot = _dot(operand)

    @jax.checkpoint
    def quarter(x_and_labels):
        x, part = x_and_labels
        logp = jax.nn.log_softmax(
            dot(x, params["embedding.weights"].T), axis=-1)
        return -jnp.take_along_axis(logp, part[:, None], axis=-1).sum()

    @jax.checkpoint
    def loss(params, tokens, labels):
        x = forward_hidden(params, tokens, sz, operand)
        S, E = x.shape
        return jax.lax.map(quarter, (x.reshape(4, S // 4, E),
                                     labels.reshape(4, S // 4))).sum() / S
    return loss(params, tokens, labels)


_TICK_FNS = {}


def _tick_fn(sz, operand, learning_rate, momentum, fault):
    """One jitted tick for each distinct set of arguments, kept, so that
    following a second seed traces and loads nothing again."""
    key = (tuple(sorted(sz.items())), operand, learning_rate, momentum,
           fault)
    if key not in _TICK_FNS:
        _TICK_FNS[key] = _make_tick_fn(sz, operand, learning_rate,
                                       momentum, fault)
    return _TICK_FNS[key]


def _make_tick_fn(sz, operand, learning_rate, momentum, fault):
    """One optimizer tick of the reference: (params, velocity, tokens
    (B, S), labels) -> (params, velocity, loss).  The batch is walked
    one sequence at a time, so only one sequence's activations live.
    ``fault`` plants what a broken program would do (read by the
    control and by the tests, never by a benchmark run)."""
    import jax

    def batch_loss(params, tokens, labels):
        if fault == "half_batch":
            half = tokens.shape[0] // 2
            tokens, labels = tokens[:half], labels[:half]
        one = functools.partial(
            sequence_loss if operand is None else _lean_sequence_loss,
            sz=sz, operand=operand)
        losses = jax.lax.map(lambda tl: one(params, tl[0], tl[1]),
                             (tokens, labels))
        return losses.mean()

    def tick(params, velocity, tokens, labels):
        loss, grads = jax.value_and_grad(batch_loss)(params, tokens,
                                                     labels)
        if fault == "state_unchanged":
            return params, velocity, loss
        new_p, new_v = {}, {}
        for name in params:
            v = momentum * velocity[name] - learning_rate * grads[name]
            new_v[name] = v
            new_p[name] = params[name] + v
        return new_p, new_v, loss

    return jax.jit(tick, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _norms_program():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})


def leaf_norms(tree):
    """Leaf name -> L2 norm, as Python floats (one small fetch)."""
    import jax
    return {k: float(v) for k, v in
            jax.device_get(_norms_program()(tree)).items()}


@functools.lru_cache(maxsize=None)
def _leaf_programs(std, gain, shape):
    """Two small programs for leaves of one kind and shape, the leaf's
    index a traced argument: a model of many blocks compiles a dozen
    programs, not one for each leaf (a single program over the whole
    tree took minutes to compile at 16 blocks)."""
    import jax
    import jax.numpy as jnp

    def first(key, index):
        if std is not None:
            return std * jax.random.normal(jax.random.fold_in(key, index),
                                           shape, jnp.float32)
        return jnp.full(shape, 1.0 if gain else 0.0, jnp.float32)

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

    return change, sample


def _programs_for(name, shape, sz):
    return _leaf_programs(_leaf_std(name, sz), name.endswith("_g"),
                          tuple(shape))


def change_norms(seed, params, sz, seq):
    """Leaf name -> ||p - p0||, p0 made again from the seed leaf by
    leaf inside the program that takes the norm, so no second copy of
    the weights is held."""
    import jax
    key = seed_key(seed)
    out = {name: _programs_for(name, shape, sz)[0](params[name], key, i)
           for i, (name, shape) in enumerate(
               leaf_shapes(sz, seq).items())}
    return {k: float(v) for k, v in jax.device_get(out).items()}


SAMPLE = 4096


def leaf_samples(seed, tree, sz):
    """Leaf name -> ``SAMPLE`` elements at places drawn from the seed
    (host arrays, a few megabytes in all): what lets two states that
    never share the device be compared element by element."""
    import jax
    import numpy
    key = jax.random.fold_in(seed_key(seed), 1 << 20)
    # a leaf's places follow from its position in ``leaf_shapes``, not
    # from the order the tree happens to come in (jit sorts its keys)
    order = {name: i for i, name in enumerate(
        leaf_shapes(sz, tree["embedding.pos"].shape[0]))}
    out = {name: _programs_for(name, leaf.shape, sz)[1](leaf, key,
                                                        order[name])
           for name, leaf in tree.items()}
    return {k: numpy.asarray(v) for k, v in jax.device_get(out).items()}


def reference_train(seed, sz, traffic, ticks, operand=None, fault=None):
    """Follows the first ``ticks`` optimizer ticks from the seed and
    returns what is compared: the mean loss, and per leaf the norm of
    the momentum state (the gradients as the optimizer got them), a
    seeded sample of its elements, and the norm of the parameters'
    change."""
    import jax.numpy as jnp
    seq, batch = traffic["seq"], traffic["batch"]
    params = init_params(seed, sz, seq)
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    tokens, labels = make_tokens(seed, batch * ticks, seq, sz["vocab"])
    tick = _tick_fn(sz, operand, traffic["learning_rate"],
                    traffic["momentum"], fault)
    losses = []
    for t in range(ticks):
        rows = slice(t * batch, (t + 1) * batch)
        params, velocity, loss = tick(params, velocity,
                                      jnp.asarray(tokens[rows]),
                                      jnp.asarray(labels[rows]))
        losses.append(loss)
    out = {"loss": float(jnp.mean(jnp.stack(losses))),
           "tick_losses": [float(x) for x in losses],
           "velocity": leaf_norms(velocity),
           "velocity_sample": leaf_samples(seed, velocity, sz),
           "change": change_norms(seed, params, sz, seq)}
    del params, velocity
    return out


# -- the program, built for this family -------------------------------------

def build_trainer(sz, traffic, seed, rows, backend, chips=1):
    """``Launcher`` -> ``TinyLMWorkflow`` (as ``chip_smoke.build_lm``),
    resident full-batch loader over ``rows`` seeded sequences in their
    given order, weights from ``init_params`` put in before
    ``initialize`` so the program's host initialisation is skipped the
    way a restored snapshot skips it.  A cell on several chips is data
    parallel over the first ``chips`` local devices (``make_mesh`` +
    ``apply_dp_sharding``, as ``chip_smoke.dp_phase``): its traffic's
    ``batch`` is the whole tick's.  Returns a handle the driver drives
    without knowing the family."""
    import veles_tpu.prng as prng
    from veles_tpu.config import root
    from veles_tpu.launcher import Launcher
    from veles_tpu.znicz.samples.tinylm import (FirstTokenLoader,
                                                TinyLMWorkflow)
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
        launcher, vocab_size=vocab, seq_len=seq,
        embed_dim=sz["hidden"], n_heads=sz["heads"],
        n_blocks=sz["blocks"], minibatch_size=traffic["batch"],
        ticks_per_dispatch=traffic["ticks"], max_epochs=1 << 30,
        learning_rate=traffic["learning_rate"],
        gradient_moment=traffic["momentum"], loader_cls=SeededCorpus,
        # Rows are served in their given order, every epoch alike, so
        # the reference knows each tick's rows from the seed alone.
        loader_config={"validate_labels": False, "shuffle_limit": 0})
    _put_weights(wf, seed, sz, seq)
    launcher.initialize()
    if chips > 1:
        import jax
        from veles_tpu.parallel import apply_dp_sharding, make_mesh
        apply_dp_sharding(wf, make_mesh(jax.local_devices()[:chips]))
    return Trainer(launcher, wf, sz, traffic, seed)


#: The loader's Vectors that the fused step takes as arguments, in the
#: order their addresses are given.
_STEP_ARGUMENT_VECTORS = ("minibatch_indices", "minibatch_mask",
                          "minibatch_class_vec", "original_data",
                          "original_labels", "minibatch_data",
                          "minibatch_labels")


def _settle_vector_order(loader):
    """Gives the loader's (still empty) Vectors addresses in one fixed
    order.  The program keys the fused step's arguments by
    ``str(id(vector))`` and JAX sorts those keys, so the order of the
    step's parameters, and with it the persistent cache's key, follows
    from where the allocator happened to put five small objects: a
    fresh process hits the cache one time in about twelve (PERF.md,
    Open questions).  Fresh Vectors handed out by ascending address
    make every process lower the same program, so only a checkout's
    first run compiles it and ``setup_s`` is steady."""
    from veles_tpu.memory import Vector
    names = [n for n in _STEP_ARGUMENT_VECTORS
             if isinstance(getattr(loader, n, None), Vector)]
    fresh = sorted((Vector() for _ in names), key=lambda v: str(id(v)))
    for name, vector in zip(names, fresh):
        setattr(loader, name, vector)


def _put_weights(wf, seed, sz, seq):
    """The seed's weights into the program's Vectors, placed the way
    the program's own uploads are (committed to the first device):
    arrays that differ in that from what a dispatch returns would make
    the second dispatch compile the block program a second time."""
    import jax
    device = jax.local_devices()[0]
    weights = init_params(seed, sz, seq)
    for name, vec in _param_vectors(wf).items():
        vec.devmem = jax.device_put(weights[name], device)


def _param_vectors(wf):
    """Leaf name (this file's) -> the program's Vector."""
    out = {"embedding.weights": wf.embedding.weights,
           "embedding.pos": wf.embedding.pos}
    blocks = [u for u in wf.forwards if hasattr(u, "params")]
    for i, block in enumerate(blocks):
        for leaf in BLOCK_LEAVES:
            out["block%d.%s" % (i, leaf)] = block.params[leaf]
    return out


class Trainer(object):
    """What a train driver needs of a built program."""

    def __init__(self, launcher, wf, sz, traffic, seed):
        self.launcher, self.wf = launcher, wf
        self.sz, self.traffic, self.seed = sz, traffic, seed
        self.items_per_dispatch = (traffic["batch"] * traffic["seq"] *
                                   traffic["ticks"])

    def reseed(self, seed):
        """The same compiled program on another seed's weights and
        rows, state zeroed: how ``benchmark/control.py`` reads a dozen
        seeds without compiling the step a dozen times.  A benchmark
        run never calls this."""
        import jax
        import jax.numpy as jnp
        import numpy
        device = jax.local_devices()[0]
        wf, traffic = self.wf, self.traffic
        self.seed = seed
        _put_weights(wf, seed, self.sz, traffic["seq"])
        for gd in wf.gds:
            for vec in gd._velocities.values():
                vec.devmem = jax.device_put(
                    jnp.zeros(vec.shape, vec.dtype), device)
        for vec in (wf.evaluator.epoch_acc, wf.evaluator.health_acc):
            vec.mem = numpy.zeros(vec.shape, vec.dtype)
        loader = wf.loader
        tokens, labels = make_tokens(seed, loader.total_samples,
                                     traffic["seq"], self.sz["vocab"])
        loader.original_data.mem = tokens
        loader.original_labels.mem = labels
        loader.global_offset = 0

    def dispatch(self):
        """The window's own call: one block of ticks, enqueued."""
        self.wf.loader.run()

    def wait(self):
        import jax
        state = self.wf.compiler._state_vecs
        jax.block_until_ready(next(iter(state.values())).devmem)

    def loss_sum_and_ticks(self):
        """The evaluator's on-device accumulator for the train class:
        (sum of tick losses, ticks) since the start."""
        from veles_tpu.loader.base import TRAIN
        ev = self.wf.evaluator
        row = ev.read_epoch_acc(TRAIN)
        return float(row[ev.ACC_LOSS]), float(row[ev.ACC_TICKS])

    def nonfinite_ticks(self):
        from veles_tpu.loader.base import TRAIN
        ev = self.wf.evaluator
        return float(ev.read_health_acc(TRAIN)[ev.HEALTH_NONFINITE])

    def state_norms(self):
        """Per leaf: norm of the momentum state and of the parameters'
        change since the seed's weights."""
        params = {n: v.devmem for n, v in _param_vectors(self.wf).items()}
        velocity = {}
        gd_of = {gd.target: gd for gd in self.wf.gds}
        units = {"embedding": self.wf.embedding}
        blocks = [u for u in self.wf.forwards if hasattr(u, "params")]
        units.update({"block%d" % i: b for i, b in enumerate(blocks)})
        for name in params:
            unit, leaf = name.split(".")
            slot = gd_of[units[unit]]._velocities["velocity_" + leaf]
            velocity[name] = slot.devmem
        return {"velocity": leaf_norms(velocity),
                "velocity_sample": leaf_samples(self.seed, velocity,
                                                self.sz),
                "change": change_norms(self.seed, params, self.sz,
                                       self.traffic["seq"])}

    def attention_traces(self):
        from veles_tpu import resilience
        counters = resilience.stats.snapshot()
        return {"pallas": counters.get("attention.kernel.pallas", 0),
                "xla": counters.get("attention.kernel.xla", 0)}

    def compiled_custom_calls(self):
        text = self.wf.compiler.lower_last_block().compile().as_text()
        return text.count("tpu_custom_call")

    def close(self):
        """Stops the program and frees the device."""
        self.launcher.stop()
        self.launcher = self.wf = None
        free_device()


def free_device():
    """Frees everything this process holds on the device.  Called once
    the window has closed and what is compared has been read: nothing
    of the program is needed any more, and the reference needs the
    room."""
    import gc
    import jax
    gc.collect()
    for array in jax.live_arrays():
        array.delete()
    jax.clear_caches()
    gc.collect()
