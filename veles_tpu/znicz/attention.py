"""Transformer / long-context units.

The reference framework predates attention (SURVEY §5: long-context
"ABSENT in reference" — 2013-15, no attention anywhere), but the TPU
build treats long sequences as first-class: these units extend the
znicz layer family with an embedding, a pre-LN transformer block
whose attention can run **ring sequence-parallel** over a mesh
``seq`` axis (``ops/attention.py``: streaming-softmax k/v rotation
via ``lax.ppermute`` — no device materializes full K/V), and a
language-model evaluator wired into the standard on-device epoch
accounting.  Everything composes with the existing machinery: the
fused StepCompiler differentiates through the ring, the generic
GradientDescentBase momentum rule updates every trainable, snapshots
and the distributed contract come from ForwardBase.
"""

import functools

import numpy

from ..config import root, get as config_get
from ..memory import Vector
from .nn_units import ForwardBase, GradientDescentBase
from .evaluator import EvaluatorBase


def remat_enabled(unit_flag):
    """Whether a transformer unit should rematerialize its block
    application (:func:`checkpointed`): the unit kwarg wins when
    set, otherwise ``root.common.engine.remat`` (default off).  Remat
    trades ~1/3 more FLOPs (forward re-run in backward) for
    O(layers) → O(1) residual activation memory per block — THE
    long-context/deep-stack enabler: ring attention already gives
    O(S/N) attention memory, but without remat the backward still
    stores every block's full residual stream.

    What a checkpointed layer KEEPS from its forward pass: its input;
    where its attention ran the flash kernel, that kernel's output and
    log-sum-exp rows (one compute-dtype activation of the layer,
    B·S·E elements, plus B·H·S float32: 64 MB + 1 MB at 4 × 2048
    tokens of 4096 in bfloat16; as much at 2048 wide with heads of
    64, whose rows are padded to a lane tile); and, where it has an
    expert layer, the router's scores, the choice and the chosen
    scores, the order and the held experts' counts (5 MB) and, on the
    common path, the
    gathered rows and the two grouped products before the gate (42 +
    2 × 63 MB at 16,384 tokens of 2048, top 4, an eighth of the
    experts 1536 wide held: 172.8 MB a layer; docs/moe.md); and,
    where its operator is the gated delta rule run by the Pallas
    kernels, their output, the chunks' inverses and the states at the
    chunks' starts (134 + 33.5 + 268 MB at 8,192 rows of 32 value
    heads of 128 x 128 in bfloat16; docs/attention.md).
    Everything else is computed again in the backward pass."""
    if unit_flag is not None:
        return bool(unit_flag)
    return bool(config_get(root.common.engine.remat, False))


def checkpointed(fn):
    """``fn`` under THE layers' checkpoint — both sites
    (``LMLayer.tforward``, the pipelined stack's block function) go
    through here.  ``jax.checkpoint`` with one rule: save what carries
    one of the names below, recompute the rest.  The names are given
    where the dearest parts of a layer hand over what the backward
    pass reads: inside the flash forward kernel's rule
    (``ops/pallas_attention.py``: its output and rows — the kernel
    runs at a fifth of its roofline, so its second call was the
    dearest part of the recompute for the bytes it costs to keep) and
    inside the expert layer (``ops/moe.py`` ``MOE_KEPT``: the router's
    scores, the choice, the chosen scores, the order, the sizes, the
    gathered rows and the two grouped products before the gate — the
    router and the sort were run again to recover 5 MB, two megablox
    calls at
    a quarter of their roofline to recover 126; the third product and
    the gate are rebuilt, and the walk over every chunk names
    nothing) and inside the gated delta rule's kernels' rule
    (``ops/pallas_gated_delta.py`` ``GATED_DELTA_KEPT``: the output,
    the chunks' inverses, the states at the chunks' starts — all the
    backward kernel reads beside the operands, so the recompute runs
    neither forward kernel).  :func:`remat_enabled` gives the bytes;
    q, k and v and every dense product are XLA matmuls and are
    rebuilt as before.  The rule observes only that a value carries a
    name: where none does — XLA's attention or gated delta rule, a
    short convolution, a dense MLP — nothing is saved and the program
    is a bare ``jax.checkpoint``'s."""
    import jax
    from ..ops.moe import MOE_KEPT
    from ..ops.pallas_attention import FLASH_OUT, FLASH_LSE
    from ..ops.pallas_gated_delta import GATED_DELTA_KEPT
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(
            FLASH_OUT, FLASH_LSE, *MOE_KEPT, *GATED_DELTA_KEPT))


def fused_qkv_enabled(unit_flag):
    """Whether a transformer unit computes q/k/v with ONE (E, 3E)
    matmul (the attention fast path's stage (a)): the unit kwarg wins
    when set, otherwise ``root.common.engine.fused_qkv`` (default
    off).  The fused weight's column layout is HEAD-MAJOR —
    ``[q_h | k_h | v_h]`` per head — so a Megatron column shard of
    the 3E dim holds whole heads' q/k/v together and the
    (B, S, H, 3, D) reshape splits q/k/v on a replicated axis (no
    resharding), which is what lets the fused projection compose
    with tensor parallelism."""
    if unit_flag is not None:
        return bool(unit_flag)
    return bool(config_get(root.common.engine.fused_qkv, False))


def fuse_qkv_arrays(wq, wk, wv, n_heads):
    """Fuses three projection arrays into the head-major (…, 3·O)
    layout.  Trailing-dim based, so it handles (E, O) weights, (O,)
    biases, and stage-stacked (L, E, O) weights alike."""
    wq, wk, wv = (numpy.asarray(w) for w in (wq, wk, wv))
    O = wq.shape[-1]
    D = O // n_heads
    parts = [w.reshape(w.shape[:-1] + (n_heads, 1, D))
             for w in (wq, wk, wv)]
    return numpy.ascontiguousarray(
        numpy.concatenate(parts, axis=-2).reshape(
            wq.shape[:-1] + (3 * O,)))


def split_qkv_arrays(wqkv, n_heads):
    """Inverse of :func:`fuse_qkv_arrays`: (…, 3·O) → three (…, O)
    arrays (wq, wk, wv)."""
    wqkv = numpy.asarray(wqkv)
    O = wqkv.shape[-1] // 3
    D = O // n_heads
    r = wqkv.reshape(wqkv.shape[:-1] + (n_heads, 3, D))
    return tuple(
        numpy.ascontiguousarray(
            r[..., t, :].reshape(wqkv.shape[:-1] + (O,)))
        for t in range(3))


def _layer_norm(x, gamma, beta, eps=1e-5):
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    return ((xf - mu) * jnp.reciprocal(jnp.sqrt(var + eps)) * gamma +
            beta).astype(x.dtype)


#: What a layer spec may say (:func:`layer_spec`).
NORMS = ("layer", "rms")
OPERATORS = ("attention", "shortconv", "gated_delta", "latent")
FFNS = ("relu-mlp", "gated-mlp", "experts")
SCORES = ("sigmoid", "softmax")


def layer_spec(norm="layer", operator="attention", ffn="relu-mlp",
               n_heads=4, kv_heads=None, qk_norm=False,
               rope_theta=None, bias=True, ffn_dim=None,
               conv_kernel=3, n_experts=0, top_k=1, held=None,
               norm_topk=True, routed_scaling=1.0, norm_eps=1e-5,
               head_dim=None, window=None, attn_gate=False,
               post_norm=False, shared_ffn_dim=None, route_eps=1e-6,
               slack=(5, 4), rope_fraction=None, score="sigmoid",
               shared_gate=False, linear_key_heads=None,
               linear_value_heads=None, linear_key_dim=None,
               linear_value_dim=None, linear_chunk=64, q_lora_rank=None,
               kv_lora_rank=None, qk_nope_dim=None, qk_rope_dim=None,
               v_head_dim=None, rope_interleave=False):
    """One decoder layer, said as data: ``h + operator(norm(h))`` then
    ``+ ffn(norm(·))``.  A plain dict (it rides snapshots).

    ``norm``: ``layer`` (LayerNorm, gain and bias) | ``rms`` (gain);
    ``post_norm`` puts a second norm of the same kind BEHIND the
    operator and behind the FFN (the sandwich: ``h + norm(operator(
    norm(h)))``).  ``operator``: ``attention`` — ``n_heads`` query
    heads of ``head_dim`` (None: the layer's width over ``n_heads``)
    over ``kv_heads`` key/value heads (None: as many), ``qk_norm`` a
    per-head RMS norm of q and k, ``rope_theta`` rotary positions
    (None: the positions are the embedding's, or none) on the first
    ``rope_fraction`` of each head (None: all of it), ``window`` how
    many keys back a row sees (None: all; ``ops.attention``),
    ``attn_gate`` an output gate ``sigmoid(u Wg)`` on the heads'
    result before ``Wo`` — | ``shortconv``, the gated short
    convolution of ``conv_kernel`` taps (``ops/shortconv.py``) — |
    ``gated_delta``, linear attention with a carried state
    (``ops/linear_attention.py``): ``linear_key_heads`` query/key
    heads of ``linear_key_dim`` serving ``linear_value_heads`` value
    heads of ``linear_value_dim``, q, k and v through ``conv_kernel``
    causal taps a channel and a SiLU, the rule in chunks of
    ``linear_chunk`` rows, a gated RMS norm a head behind it — |
    ``latent``, multi-head latent attention (DeepSeek-V3 MLA,
    :func:`_latent_operator`): queries through a ``q_lora_rank``-wide
    compression, keys and values through ONE ``kv_lora_rank``-wide
    latent, each RMS-normed; ``n_heads`` heads whose keys are
    ``qk_nope_dim`` from the latent and ``qk_rope_dim`` of rotary
    positions (one such part shared by every head) and whose values
    are ``v_head_dim`` wide; rotary positions at ``rope_theta`` on the
    rotated parts alone, on interleaved pairs where
    ``rope_interleave``.
    ``ffn``: ``relu-mlp`` | ``gated-mlp`` (``silu(u W1) ⊙ (u W3))
    W2``), both ``ffn_dim`` wide (None: 4 × the layer's width), |
    ``experts``: ``top_k`` of ``n_experts`` gated experts ``ffn_dim``
    wide, dropless, of which this layer HOLDS ``held = (first,
    count)`` (None: all) — ``ops.moe.moe_dropless``, its weights
    normalised over ``sum + route_eps``, its common path compiled for
    ``slack`` (a ratio) times the even share of the assignments
    (``ops.moe.dropless_rows``), routed by ``score`` (``sigmoid``
    with the selection bias | ``softmax`` over all the experts, which
    reads no bias: the buffer stays, at nought) — and beside them,
    where ``shared_ffn_dim`` is set, one gated MLP that wide over
    every token (the shared expert), times ``sigmoid(u w_sg)`` a token
    where ``shared_gate``.  ``bias``: whether the projections and the
    MLP carry biases."""
    if norm not in NORMS or operator not in OPERATORS or \
            ffn not in FFNS:
        raise ValueError("layer spec: norm %r of %s, operator %r of "
                         "%s, ffn %r of %s" % (norm, NORMS, operator,
                                               OPERATORS, ffn, FFNS))
    if score not in SCORES:
        raise ValueError("a router's score %r of %s" % (score, SCORES))
    kv_heads = kv_heads or n_heads
    if n_heads % kv_heads:
        raise ValueError("%d query heads over %d key/value heads"
                         % (n_heads, kv_heads))
    if operator != "attention" and (head_dim or window or attn_gate
                                    or rope_fraction):
        raise ValueError("head_dim, window, attn_gate and "
                         "rope_fraction are attention's, not %r's"
                         % operator)
    if rope_fraction is not None and not (
            rope_theta and 0 < rope_fraction <= 1):
        raise ValueError("rope_fraction %r of a head, rope_theta %r"
                         % (rope_fraction, rope_theta))
    linear = (linear_key_heads, linear_value_heads, linear_key_dim,
              linear_value_dim)
    if operator == "gated_delta":
        if not all(linear) or linear_value_heads % linear_key_heads \
                or linear_chunk < 1:
            raise ValueError(
                "gated_delta: %r key heads of %r, %r value heads of "
                "%r, chunks of %r" % (linear_key_heads, linear_key_dim,
                                      linear_value_heads,
                                      linear_value_dim, linear_chunk))
    elif any(linear):
        raise ValueError("linear_* are gated_delta's, not %r's"
                         % operator)
    latent = (q_lora_rank, kv_lora_rank, qk_nope_dim, qk_rope_dim,
              v_head_dim)
    if operator == "latent":
        if not all(d is not None and d >= 1 for d in latent) or \
                not rope_theta or qk_norm or bias or \
                kv_heads != n_heads:
            raise ValueError(
                "latent: q_lora_rank %r, kv_lora_rank %r, heads of %r + "
                "%r over values of %r, rope_theta %r, qk_norm %r, bias "
                "%r, %d of %d key/value heads" % (
                    q_lora_rank, kv_lora_rank, qk_nope_dim, qk_rope_dim,
                    v_head_dim, rope_theta, qk_norm, bias, kv_heads,
                    n_heads))
    elif any(d is not None for d in latent) or rope_interleave:
        raise ValueError("q_lora_rank, kv_lora_rank, qk_*_dim, "
                         "v_head_dim and rope_interleave are latent's, "
                         "not %r's" % operator)
    if window is not None and window < 1:
        raise ValueError("a window of %r keys" % (window,))
    if ffn == "experts":
        held = tuple(held or (0, n_experts))
        if not (1 <= top_k <= n_experts and held[1] >= 1 and
                0 <= held[0] <= n_experts - held[1]):
            raise ValueError("top %d of %d experts, held %r"
                             % (top_k, n_experts, held))
    elif shared_ffn_dim:
        raise ValueError("a shared expert beside %r" % ffn)
    if shared_gate and not shared_ffn_dim:
        raise ValueError("shared_gate without a shared expert")
    return {"norm": norm, "operator": operator, "ffn": ffn,
            "n_heads": n_heads, "kv_heads": kv_heads,
            "qk_norm": bool(qk_norm), "rope_theta": rope_theta,
            "bias": bool(bias), "ffn_dim": ffn_dim,
            "conv_kernel": conv_kernel, "n_experts": n_experts,
            "top_k": top_k, "held": held, "norm_topk": bool(norm_topk),
            "routed_scaling": routed_scaling, "norm_eps": norm_eps,
            "head_dim": head_dim, "window": window,
            "attn_gate": bool(attn_gate), "post_norm": bool(post_norm),
            "shared_ffn_dim": shared_ffn_dim, "route_eps": route_eps,
            "slack": tuple(slack), "rope_fraction": rope_fraction,
            "score": score, "shared_gate": bool(shared_gate),
            "linear_key_heads": linear_key_heads,
            "linear_value_heads": linear_value_heads,
            "linear_key_dim": linear_key_dim,
            "linear_value_dim": linear_value_dim,
            "linear_chunk": linear_chunk, "q_lora_rank": q_lora_rank,
            "kv_lora_rank": kv_lora_rank, "qk_nope_dim": qk_nope_dim,
            "qk_rope_dim": qk_rope_dim, "v_head_dim": v_head_dim,
            "rope_interleave": bool(rope_interleave)}


def layer_param_shapes(spec, embed, fused_qkv=False):
    """Parameter geometry of one layer — single source of truth for
    LMLayer and the pipelined stack (which prepends a stage dim).
    ``fused_qkv`` swaps the three projections for the single fused
    weight.  The heads' width ``n_heads · head_dim`` is the layer's
    own where the spec names no ``head_dim``.

    Dict ORDER is load-bearing: initialization draws from the seeded
    prng in iteration order, so the unfused OPT layout keeps the
    historical ordering bit-for-bit (seeded trajectories — and the
    tests pinning them — depend on it); what a newer spec key adds
    (``wg``, ``ln1_post_*``, ``ws1`` / ``ws3`` / ``ws2``, ``wsg``,
    ``ln2_post_*``) comes behind its part's older leaves, and a
    ``gated_delta`` operator's own (``w_qkvz``, ``w_ba``, ``w_conv``,
    ``a_log``, ``dt_bias``, ``gdn_norm_g``, ``w_out``) and a
    ``latent`` one's (``w_dq``, ``dq_norm_g``, ``w_uq``, ``w_dkv``,
    ``dkv_norm_g``, ``w_ukv``, ``wo``) where an older operator's would
    stand."""
    spec = layer_spec(**spec)      # a snapshot's older spec lacks keys
    bias = spec["bias"]

    def norm(name):
        out = {name + "_g": (embed,)}
        if spec["norm"] == "layer":
            out[name + "_b"] = (embed,)
        return out

    shapes = norm("ln1")
    if spec["operator"] == "attention":
        head = spec["head_dim"] or embed // spec["n_heads"]
        inner = spec["n_heads"] * head
        kv = spec["kv_heads"] * head
        if fused_qkv:
            shapes.update({"wqkv": (embed, 3 * inner),
                           "wo": (inner, embed)})
            if bias:
                shapes.update({"bqkv": (3 * inner,), "bo": (embed,)})
        else:
            shapes.update({"wq": (embed, inner), "wk": (embed, kv),
                           "wv": (embed, kv), "wo": (inner, embed)})
            if bias:
                shapes.update({"bq": (inner,), "bk": (kv,),
                               "bv": (kv,), "bo": (embed,)})
        if spec["attn_gate"]:
            shapes["wg"] = (embed, inner)
        if spec["qk_norm"]:
            shapes.update({"q_norm_g": (head,), "k_norm_g": (head,)})
    elif spec["operator"] == "latent":
        heads = spec["n_heads"]
        qk = spec["qk_nope_dim"] + spec["qk_rope_dim"]
        rank, kv_rank = spec["q_lora_rank"], spec["kv_lora_rank"]
        shapes.update({
            "w_dq": (embed, rank), "dq_norm_g": (rank,),
            "w_uq": (rank, heads * qk),
            "w_dkv": (embed, kv_rank + spec["qk_rope_dim"]),
            "dkv_norm_g": (kv_rank,),
            "w_ukv": (kv_rank, heads * (spec["qk_nope_dim"] +
                                        spec["v_head_dim"])),
            "wo": (heads * spec["v_head_dim"], embed)})
    elif spec["operator"] == "shortconv":
        shapes.update({"w_in": (embed, 3 * embed),
                       "w_conv": (embed, spec["conv_kernel"]),
                       "w_out": (embed, embed)})
    else:
        heads = spec["linear_value_heads"]
        keys = spec["linear_key_heads"] * spec["linear_key_dim"]
        values = heads * spec["linear_value_dim"]
        shapes.update({
            "w_qkvz": (embed, 2 * keys + 2 * values),
            "w_ba": (embed, 2 * heads),
            "w_conv": (2 * keys + values, spec["conv_kernel"]),
            "a_log": (heads,), "dt_bias": (heads,),
            "gdn_norm_g": (spec["linear_value_dim"],),
            "w_out": (values, embed)})
    if spec["post_norm"]:
        shapes.update(norm("ln1_post"))
    shapes.update(norm("ln2"))
    hidden = spec["ffn_dim"] or 4 * embed
    if spec["ffn"] == "experts":
        count = spec["held"][1]
        shapes.update({"router": (embed, spec["n_experts"]),
                       "w1": (count, embed, hidden),
                       "w3": (count, embed, hidden),
                       "w2": (count, hidden, embed)})
        shared = spec["shared_ffn_dim"]
        if shared:
            shapes.update({"ws1": (embed, shared),
                           "ws3": (embed, shared),
                           "ws2": (shared, embed)})
            if spec["shared_gate"]:
                shapes["wsg"] = (embed, 1)
    elif spec["ffn"] == "gated-mlp":
        shapes.update({"w1": (embed, hidden), "w3": (embed, hidden),
                       "w2": (hidden, embed)})
    else:
        shapes["w1"] = (embed, hidden)
        if bias:
            shapes["b1"] = (hidden,)
        shapes["w2"] = (hidden, embed)
        if bias:
            shapes["b2"] = (embed,)
    if spec["post_norm"]:
        shapes.update(norm("ln2_post"))
    return shapes


def layer_apply(spec, params, x, cdt, causal=True, attend=None,
                buffers=None, positions=None):
    """Pure decoder layer from its spec (:func:`layer_spec`):
    ``x + operator(norm(x))``, then ``+ ffn(norm(·))``.  Shared by
    LMLayer.tforward, the pipelined stack (whose stages must be a
    pure (params, x) → y function) and the serving forward.
    ``attend(q, k, v)`` is where the caller places attention (None:
    ``ops.attention.attention``).  ``buffers`` holds what the layer
    reads and no gradient reaches (``expert_bias``).  ``positions``
    (S,) are the rows' rotary positions (None: 0 … S − 1).  Returns
    ``(y float32, stats)``; ``stats`` is ``ops.moe.moe_dropless``'s
    for an ``experts`` layer, else None.

    The inner ``jax.named_scope``s are the scope vocabulary
    ``observability.programs`` reads back from the compiled program
    (docs/observability.md): ``ln1``, ``attention`` (q, k, v → o:
    scores, softmax, value matmul or the kernel — NOT the
    projections), ``rope`` (per-head norm and rotary positions),
    ``attn_gate`` (the output gate's sigmoid and product, not its
    projection), ``shortconv`` (gates and taps, not the projections),
    ``ln1_post`` / ``ln2_post`` (the sandwich's second norms),
    ``ln2``, ``mlp``, the expert layer's ``moe_*`` and the shared
    expert's ``moe_shared`` (its gate too); of a ``gated_delta``
    operator ``shortconv`` (taps and SiLU), ``gdn_gate`` (beta, g, the
    l2 norms of q and k), ``gated_delta`` (the rule alone) and
    ``gdn_norm`` (the gated norm a head); of a ``latent`` operator
    ``mla_compress`` (the two down-projections and their norms),
    ``mla_expand`` (the two up-projections, the rotated key part
    broadcast to every head and the heads put together), ``rope`` and
    ``attention``."""
    import jax
    import jax.numpy as jnp
    from ..ops import attention as A
    from ..ops.rotary import rms_norm, rotary
    spec = layer_spec(**spec)      # a snapshot's older spec lacks keys
    B, S, E = x.shape

    def dot(a, w, b=None):
        y = jnp.dot(a.astype(cdt), w.astype(cdt),
                    preferred_element_type=jnp.float32)
        return y if b is None else y + b

    def norm(name, x):
        with jax.named_scope(name):
            if spec["norm"] == "layer":
                return _layer_norm(x, params[name + "_g"],
                                   params[name + "_b"])
            return rms_norm(x, params[name + "_g"], spec["norm_eps"])

    def gated(h, w1, w3, w2):
        return dot(jax.nn.silu(dot(h, params[w1])) *
                   dot(h, params[w3]), params[w2])

    def post(name, y):
        return norm(name, y) if spec["post_norm"] else y

    h = norm("ln1", x)
    if spec["operator"] == "shortconv":
        from ..ops.shortconv import gated_short_conv
        b_, c_, x_ = jnp.split(dot(h, params["w_in"]).astype(cdt), 3,
                               axis=-1)
        with jax.named_scope("shortconv"):
            mixed = gated_short_conv(b_, c_, x_, params["w_conv"])
        x = x + post("ln1_post", dot(mixed, params["w_out"]))
    elif spec["operator"] == "gated_delta":
        x = x + post("ln1_post", _gated_delta_operator(
            spec, params, h, cdt, dot))
    elif spec["operator"] == "latent":
        x = x + post("ln1_post", _latent_operator(
            spec, params, h, cdt, dot, attend or functools.partial(
                A.attention, causal=causal), positions))
    else:
        n_heads, kv_heads = spec["n_heads"], spec["kv_heads"]
        if "wqkv" in params:
            # Fast path stage (a): one (E, 3E) matmul; the head-major
            # column layout makes the q/k/v split a reshape + index
            # on a replicated axis (tensor-parallel-safe, see
            # fused_qkv_enabled).
            qkv = dot(h, params["wqkv"], params.get("bqkv")).reshape(
                B, S, n_heads, 3, -1)
            q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        else:
            q = dot(h, params["wq"], params.get("bq")).reshape(
                B, S, n_heads, -1)
            k = dot(h, params["wk"], params.get("bk")).reshape(
                B, S, kv_heads, -1)
            v = dot(h, params["wv"], params.get("bv")).reshape(
                B, S, kv_heads, -1)
        if spec["qk_norm"] or spec["rope_theta"]:
            with jax.named_scope("rope"):
                if spec["qk_norm"]:
                    q = rms_norm(q, params["q_norm_g"],
                                 spec["norm_eps"])
                    k = rms_norm(k, params["k_norm_g"],
                                 spec["norm_eps"])
                if spec["rope_theta"]:
                    q = rotary(q, spec["rope_theta"],
                               spec["rope_fraction"],
                               positions=positions)
                    k = rotary(k, spec["rope_theta"],
                               spec["rope_fraction"],
                               positions=positions)
        if attend is None:
            attend = functools.partial(A.attention, causal=causal,
                                       window=spec["window"])
        with jax.named_scope("attention"):
            attn = attend(q.astype(cdt), k.astype(cdt), v.astype(cdt))
        attn = attn.reshape(B, S, -1)
        if spec["attn_gate"]:
            gate = dot(h, params["wg"])
            with jax.named_scope("attn_gate"):
                attn = attn * jax.nn.sigmoid(gate)
        x = x + post("ln1_post", dot(attn, params["wo"],
                                     params.get("bo")))
    h = norm("ln2", x)
    stats = None
    if spec["ffn"] == "experts":
        from ..ops.moe import moe_dropless
        y, stats = moe_dropless(
            h.reshape(B * S, E), params["router"],
            buffers["expert_bias"], params["w1"], params["w3"],
            params["w2"], top_k=spec["top_k"], held=spec["held"],
            norm_topk=spec["norm_topk"],
            scaling=spec["routed_scaling"], cdt=cdt,
            eps=spec["route_eps"], slack=spec["slack"],
            score=spec["score"])
        y = y.reshape(B, S, E)
        if spec["shared_ffn_dim"]:
            with jax.named_scope("moe_shared"):
                shared = gated(h, "ws1", "ws3", "ws2")
                if spec["shared_gate"]:
                    shared = shared * jax.nn.sigmoid(
                        dot(h, params["wsg"]))
                y = y + shared
        x = x + post("ln2_post", y)
    else:
        with jax.named_scope("mlp"):
            if spec["ffn"] == "gated-mlp":
                y = gated(h, "w1", "w3", "w2")
            else:
                h = jnp.maximum(
                    dot(h, params["w1"], params.get("b1")), 0.0)
                y = dot(h, params["w2"], params.get("b2"))
            if not spec["post_norm"]:
                # the residual add stays under the MLP's scope, where
                # the scope table of the older specs has it
                x = x + y
        if spec["post_norm"]:
            x = x + norm("ln2_post", y)
    return x.astype(jnp.float32), stats


def _gated_delta_operator(spec, params, h, cdt, dot):
    """The Gated DeltaNet operator between its norms: ``h`` (B, S, E)
    normed → what is added to the stream, before any post norm.
    ``[q | k | v | z] = h W_qkvz``, ``[b | a] = h W_ba``; q, k, v
    through the causal taps and a SiLU; ``beta = sigmoid(b)``, ``g =
    -exp(a_log) softplus(a + dt_bias)``; q and k l2-normalised a head
    (q over ``sqrt(Dk)`` more); the rule; a gated RMS norm a head,
    ``gain * o / rms(o) * silu(z)``; ``W_out``.  Gates, norms, taps
    and the rule's decays are float32; q, k, v and z travel in
    ``cdt``."""
    import jax
    import jax.numpy as jnp
    from ..ops.linear_attention import gated_delta_rule
    from ..ops.rotary import rms_norm
    from ..ops.shortconv import conv_silu
    B, S, _ = h.shape
    kh, vh = spec["linear_key_heads"], spec["linear_value_heads"]
    kd, vd = spec["linear_key_dim"], spec["linear_value_dim"]
    keys, values = kh * kd, vh * vd
    qkvz = dot(h, params["w_qkvz"]).astype(cdt)
    z = qkvz[..., 2 * keys + values:]
    b, a = jnp.split(dot(h, params["w_ba"]), 2, axis=-1)
    with jax.named_scope("shortconv"):
        # the taps read q, k and v, the first channels, in place
        qkv = conv_silu(qkvz, params["w_conv"])
    with jax.named_scope("gdn_gate"):
        q, k, v = jnp.split(qkv, [keys, 2 * keys], axis=-1)
        q, k = (t.reshape(B, S, kh, kd) for t in (q, k))
        q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6) \
            * kd ** -0.5
        k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(params["a_log"]) * \
            jax.nn.softplus(a + params["dt_bias"])
    with jax.named_scope("gated_delta"):
        o = gated_delta_rule(q.astype(cdt), k.astype(cdt),
                             v.reshape(B, S, vh, vd), g, beta,
                             chunk=spec["linear_chunk"])
    with jax.named_scope("gdn_norm"):
        y = rms_norm(o, params["gdn_norm_g"], spec["norm_eps"]) * \
            jax.nn.silu(z.reshape(B, S, vh, vd).astype(jnp.float32))
    return dot(y.reshape(B, S, values), params["w_out"])


def _latent_operator(spec, params, h, cdt, dot, attend, positions=None):
    """Multi-head latent attention between its norms: ``h`` (B, S, E)
    normed → what is added to the stream, before any post norm.
    ``c_q = rms(h W_dq)``, ``[q_nope | q_pe] = c_q W_uq`` a head;
    ``[c_kv | k_pe] = h W_dkv``, ``[k_nope | v] = rms(c_kv) W_ukv`` a
    head; ``q = [q_nope | rope(q_pe)]``, ``k = [k_nope | rope(k_pe)]``
    with the one ``k_pe`` broadcast to every head; ``o = softmax(q kᵀ
    / sqrt(nope + rope), causal) v`` — keys ``nope + rope`` wide,
    values ``v_head_dim``, through the flash kernels where they take
    the widths (``ops.attention``); ``o W_o``.  The norms and the
    rotation are float32; q, k and v go to the core in ``cdt``."""
    import jax
    import jax.numpy as jnp
    from ..ops.rotary import rms_norm, rotary
    B, S, _ = h.shape
    H, eps = spec["n_heads"], spec["norm_eps"]
    nope, rope, vd = (spec["qk_nope_dim"], spec["qk_rope_dim"],
                      spec["v_head_dim"])
    kv_rank = spec["kv_lora_rank"]
    with jax.named_scope("mla_compress"):
        c_q = rms_norm(dot(h, params["w_dq"]), params["dq_norm_g"], eps)
        c_kv, k_pe = jnp.split(dot(h, params["w_dkv"]), [kv_rank],
                               axis=-1)
        c_kv = rms_norm(c_kv, params["dkv_norm_g"], eps)
    with jax.named_scope("mla_expand"):
        q_nope, q_pe = jnp.split(dot(c_q, params["w_uq"]).reshape(
            B, S, H, nope + rope), [nope], axis=-1)
        k_nope, v = jnp.split(dot(c_kv, params["w_ukv"]).reshape(
            B, S, H, nope + vd), [nope], axis=-1)
    with jax.named_scope("rope"):
        q_pe = rotary(q_pe, spec["rope_theta"],
                      interleave=spec["rope_interleave"],
                      positions=positions)
        k_pe = rotary(k_pe.reshape(B, S, 1, rope), spec["rope_theta"],
                      interleave=spec["rope_interleave"],
                      positions=positions)
    with jax.named_scope("mla_expand"):
        q = jnp.concatenate([q_nope.astype(cdt), q_pe.astype(cdt)],
                            axis=-1)
        k = jnp.concatenate([k_nope.astype(cdt), jnp.broadcast_to(
            k_pe.astype(cdt), (B, S, H, rope))], axis=-1)
    with jax.named_scope("attention"):
        o = attend(q, k, v.astype(cdt))
    return dot(o.reshape(B, S, H * vd), params["wo"])


def transformer_block_apply(params, x, n_heads, causal, cdt,
                            attend=None):
    """The OPT block — pre-LN LayerNorm, full multi-head attention,
    ReLU MLP, biases — as :func:`layer_apply` traces it: what the
    pipelined stack and the serving forward run."""
    return layer_apply(layer_spec(n_heads=n_heads), params, x, cdt,
                       causal=causal, attend=attend)[0]


def _block_param_shapes(embed, hidden, fused_qkv=False):
    """Parameter geometry of one dense pre-LN OPT block."""
    return layer_param_shapes(
        layer_spec(ffn_dim=hidden), embed, fused_qkv=fused_qkv)


def block_noise(key, rows, length, block, eps):
    """Block diffusion's forward process for ``rows`` sequences of
    ``length`` clean ids in blocks of ``block``: a mask rate ``t ~ U(eps,
    1)`` a block (from ``fold_in(key, 0)``) and a mask ``m_i ~
    Bernoulli(t_β(i))`` a position (``u_i < t_β(i)``, ``u`` uniform from
    ``fold_in(key, 1)``).  Returns ``(t, m)``, both (rows, length): the
    block's rate at each of its positions, and True where the position
    is masked."""
    import jax
    import jax.numpy as jnp
    t = jax.random.uniform(jax.random.fold_in(key, 0),
                           (rows, length // block), jnp.float32, eps, 1.0)
    t = jnp.repeat(t, block, axis=1)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (rows, length),
                           jnp.float32)
    return t, u < t


def diffusion_key(seed_words, tick):
    """The noising key of one tick: ``fold_in(fold_in(PRNGKey(hi), lo),
    tick)`` where the seed is ``hi · 2^31 + lo`` (``seed_words`` =
    ``[hi, lo]``) and ``tick`` counts the ticks since the start."""
    import jax
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed_words[0]), seed_words[1]), tick)


class Embedding(ForwardBase):
    """Token + learned positional embedding: int32 tokens (B, S) →
    activations (B, S, E), times ``scale`` where one is given (the
    ``sqrt(E)`` of a μP-scaled LM, ``samples/trinity.py``).  With
    ``diffusion`` (``TinyLMWorkflow(diffusion=...)``, ``samples/sdar.py``)
    it is block diffusion's noising step and two-stream input: (B, S)
    clean ids → (B, 2S, E), the noised stream then the clean one, and
    the loss's weights a position in ``noise_weights``.

    ``diffusion`` (a dict: ``block``, ``eps``, ``mask_id``, ``seed``):
    under the inner scope ``bd_noise`` the step draws each block's mask
    rate and each position's mask (:func:`block_noise`, keyed by
    :func:`diffusion_key` from the state ``noise_seed`` and the tick
    count ``noise_tick`` (1,)), puts ``mask_id`` where a position is
    masked, and embeds the two streams ``[noised ; clean]``, while
    ``noise_weights`` (B, S) gets ``m_i / t_β(i)``, the loss's weight a
    position (0 where unmasked).  Its output stays float32, the
    residual stream's type: every masked position starts from the ONE
    MASK row, so that row's rounding to the compute type would be one
    offset shared by them all, moving their common expert choice as a
    block (PERF.md §6)."""

    MAPPING = "embedding"

    def __init__(self, workflow, **kwargs):
        super(Embedding, self).__init__(workflow, **kwargs)
        self.vocab_size = kwargs["vocab_size"]
        self.embed_dim = kwargs["embed_dim"]
        self.max_len = kwargs.get("max_len")
        #: False: no learned position table (the layers carry rotary
        #: positions, or none).
        self.positions = kwargs.get("positions", True)
        self.scale = float(kwargs.get("scale", 1.0))
        self.diffusion = kwargs.get("diffusion")
        self.include_bias = False
        self.pos = Vector()
        self.noise_seed = Vector()
        self.noise_tick = Vector()
        self.noise_weights = Vector()

    @property
    def trainables(self):
        t = {"weights": self.weights} if self.weights else {}
        if self.pos:
            t["pos"] = self.pos
        return t

    @property
    def tstate(self):
        if not getattr(self, "diffusion", None):
            return {}
        return {"noise_seed": self.noise_seed,
                "noise_tick": self.noise_tick}

    def initialize(self, device=None, **kwargs):
        super(Embedding, self).initialize(device=device, **kwargs)
        batch, seq = self.input.shape[:2]
        diffusion = getattr(self, "diffusion", None)
        if diffusion:
            if seq % diffusion["block"] or self.positions:
                raise ValueError(
                    "block diffusion over %d ids in blocks of %d, learned "
                    "positions %r" % (seq, diffusion["block"],
                                      self.positions))
            if not self.noise_seed:
                seed = diffusion.get("seed")
                if seed is None:
                    seed = int(self.rand().randint(0, 2 ** 31))
                self.noise_seed.mem = numpy.array(
                    [int(seed) >> 31, int(seed) & 0x7FFFFFFF],
                    dtype=numpy.uint32)
            if not self.noise_tick:
                self.noise_tick.mem = numpy.zeros(1, dtype=numpy.int32)
            self.noise_weights.mem = numpy.zeros((batch, seq),
                                                 dtype=numpy.float32)
            for vec in (self.noise_seed, self.noise_tick,
                        self.noise_weights):
                vec.initialize(self.device)
            seq *= 2
        max_len = self.max_len or seq
        if not self.weights:
            stddev = self.weights_stddev or 0.02
            w = numpy.zeros((self.vocab_size, self.embed_dim),
                            dtype=numpy.float32)
            self.rand().fill_normal(w, stddev=stddev)
            self.weights.mem = w
            self.weights.initialize(self.device)
        if not self.pos and getattr(self, "positions", True):
            p = numpy.zeros((max_len, self.embed_dim),
                            dtype=numpy.float32)
            self.rand().fill_normal(p, stddev=0.02)
            self.pos.mem = p
            self.pos.initialize(self.device)
        self.output.mem = numpy.zeros(
            (batch, seq, self.embed_dim), dtype=numpy.float32)
        self.output.initialize(self.device)

    def tforward(self, read, write, params, ctx, state=None):
        tokens = read(self.input).astype("int32")
        w = params["weights"]
        diffusion = getattr(self, "diffusion", None)
        update = None
        if diffusion:
            import jax
            import jax.numpy as jnp
            with jax.named_scope("bd_noise"):
                t, masked = block_noise(
                    diffusion_key(state["noise_seed"],
                                  state["noise_tick"][0]),
                    tokens.shape[0], tokens.shape[1], diffusion["block"],
                    diffusion["eps"])
                write(self.noise_weights, masked / t)
                tokens = jnp.concatenate(
                    [jnp.where(masked, diffusion["mask_id"], tokens),
                     tokens], axis=1)
            update = {"noise_tick": state["noise_tick"] + 1}
        seq = tokens.shape[1]
        out = w[tokens]
        if "pos" in params:
            out = out + params["pos"][:seq]
        if getattr(self, "scale", 1.0) != 1.0:
            out = out * self.scale
        write(self.output, out if diffusion else
              out.astype(self.compute_dtype))
        return update


class LMLayer(ForwardBase):
    """THE decoder-layer unit, built from a spec (:func:`layer_spec`):
    the norm kind, the operator (attention with grouped keys/values,
    per-head norm and rotary positions, the gated short convolution,
    or the gated delta rule's linear attention) and the FFN (ReLU
    MLP, gated MLP, or a held share of a dropless expert layer) are
    data, not classes
    (docs/attention.md, "Layers from a spec").

    kwargs: ``spec`` (a :func:`layer_spec` dict, or its keyword
    arguments as a dict); ``causal`` (default True); ``diffusion``
    (None, or ``(L, B)``: the input is block diffusion's two streams of
    ``L`` rows, noised then clean, both at positions 0 … L − 1, and
    attention takes the block-diffusion mask in place of the causal one
    — a property of the sequence the workflow hands every layer, as it
    hands ``causal``, not a key of the spec); ``remat``;
    ``fused_qkv`` (one (E, 3E) projection where the spec's attention
    has as many key/value heads as query heads); and for an
    ``experts`` layer the loader's ``minibatch_class_vec`` /
    ``minibatch_mask``, which bucket and gate its accumulator.

    Placement on a mesh is :meth:`_attend`'s alone.  ``seq_axis`` —
    when set AND the workflow's mesh carries that axis, attention
    runs sequence-parallel (``sp_mode``: "ring", ppermute k/v
    streaming at O(S/N) memory, or "ulysses", two all-to-alls around
    dense local attention; ``sp_kernel`` / ``sp_interpret`` pick the
    ring's body, the latter for the CPU's tests only); under any
    other mesh ``ops.attention.mesh_attention`` keeps the batch on
    ``batch_axis`` and, where ``apply_dp_tp_sharding`` set it, the
    heads on ``head_axis``; without a mesh ``ops.attention.
    attention``.  The ring does not broadcast grouped key/value
    heads: such a spec with ``seq_axis`` is refused here, as is a
    ``gated_delta`` layer, whose state no exchange carries from one
    chip's rows to the next's.

    An ``experts`` layer carries two buffers beside its trainables:
    ``expert_bias`` (n_experts,), the router's selection bias, which
    no gradient reaches and no update rule touches (zeros unless
    put), and ``moe_acc``, a (3 classes × 3 + count) on-device
    accumulator — [assignments made, assignments landed on the held
    experts, ticks, load of each held expert] — added to inside the
    fused step and fetched by DecisionGD at epoch boundaries
    (``moe.assignments_made`` / ``moe.assignments_landed`` /
    ``moe.max_load_frac``, docs/moe.md)."""

    MAPPING = "lm_layer"

    #: What ``ffn_dim=None`` in a spec means, in layer widths.
    mlp_ratio = 4

    def __init__(self, workflow, **kwargs):
        super(LMLayer, self).__init__(workflow, **kwargs)
        self.spec = spec = layer_spec(**kwargs["spec"])
        self.causal = kwargs.get("causal", True)
        self.diffusion = kwargs.get("diffusion")
        self.seq_axis = kwargs.get("seq_axis")
        self.sp_mode = kwargs.get("sp_mode", "ring")
        from ..ops.attention import SP_MODES
        if self.sp_mode not in SP_MODES:
            raise ValueError("unknown sp_mode %r — valid: %s" %
                             (self.sp_mode, list(SP_MODES)))
        grouped = spec["kv_heads"] != spec["n_heads"]
        if self.seq_axis and grouped:
            raise ValueError(
                "seq_axis=%r with %d key/value heads under %d query "
                "heads: sequence-parallel attention does not "
                "broadcast groups" % (self.seq_axis, spec["kv_heads"],
                                      spec["n_heads"]))
        if self.seq_axis and spec["operator"] == "latent":
            raise ValueError(
                "seq_axis=%r with a latent layer: sequence-parallel "
                "attention takes no value head of its own" %
                (self.seq_axis,))
        if self.seq_axis and spec["operator"] == "gated_delta":
            raise ValueError(
                "seq_axis=%r with a gated_delta layer: its state is "
                "carried along the sequence on one chip" %
                (self.seq_axis,))
        if self.seq_axis and spec["window"]:
            raise ValueError(
                "seq_axis=%r with a window of %d keys: "
                "sequence-parallel attention takes no window" %
                (self.seq_axis, spec["window"]))
        if self.diffusion and (self.seq_axis or spec["window"] or
                               spec["operator"] not in ("attention",
                                                        "latent")):
            raise ValueError(
                "block diffusion %r with seq_axis=%r, a window of %r, "
                "operator %r: its mask is attention's, over the whole "
                "two-stream sequence on one chip" % (
                    self.diffusion, self.seq_axis, spec["window"],
                    spec["operator"]))
        self.batch_axis = kwargs.get("batch_axis", "data")
        #: Set by apply_dp_tp_sharding: attention keeps the head dim
        #: on this mesh axis inside its shard_map (tp, tp × sp).
        self.head_axis = kwargs.get("head_axis")
        #: None → the ``sp_ring_kernel`` knob; "xla" forces the lax
        #: streaming scan, "pallas" the flash body.
        self.sp_kernel = kwargs.get("sp_kernel")
        self.sp_interpret = kwargs.get("sp_interpret")
        #: None → follow root.common.engine.remat; True/False forces.
        self.remat = kwargs.get("remat")
        #: Resolved at construction (None → the engine knob) so the
        #: parameter LAYOUT is frozen into the unit — a snapshot
        #: trained fused restores fused whatever the config says.
        self.fused_qkv = spec["operator"] == "attention" and \
            not grouped and fused_qkv_enabled(kwargs.get("fused_qkv"))
        self.minibatch_class_vec = kwargs.get("minibatch_class_vec")
        self.minibatch_mask = kwargs.get("minibatch_mask")
        self.params = {name: Vector() for name in layer_param_shapes(
            spec, 1, fused_qkv=self.fused_qkv)}
        self.expert_bias = Vector()
        self.moe_acc = Vector()

    @property
    def n_heads(self):
        return self.spec["n_heads"]

    @property
    def has_experts(self):
        return self.spec["ffn"] == "experts"

    @property
    def trainables(self):
        return {n: v for n, v in self.params.items() if v}

    @property
    def tstate(self):
        if not self.has_experts:
            return {}
        return {"expert_bias": self.expert_bias,
                "moe_acc": self.moe_acc}

    def read_moe_share(self, cls):
        """Host fetch of one class's row — [made, landed, ticks,
        per-held-expert load] (rides the Decision's epoch-boundary
        sync like the evaluator accumulators)."""
        self.moe_acc.map_read()
        return numpy.array(self.moe_acc.mem[cls])

    def reset_moe_share(self, cls):
        self.moe_acc.map_write()
        self.moe_acc.mem[cls] = 0.0

    def initialize(self, device=None, **kwargs):
        super(LMLayer, self).initialize(device=device, **kwargs)
        batch, seq, embed = self.input.shape
        spec = self.spec
        if not spec["head_dim"] and embed % spec["n_heads"]:
            raise ValueError("embed dim %d not divisible by %d heads"
                             % (embed, spec["n_heads"]))
        self._fill(layer_param_shapes(
            dict(spec, ffn_dim=spec["ffn_dim"] or
                 self.mlp_ratio * embed),
            embed, fused_qkv=self.fused_qkv), embed)
        self.output.mem = numpy.zeros((batch, seq, embed),
                                      dtype=numpy.float32)
        self.output.initialize(self.device)

    def _fill(self, shapes, embed):
        """Draws every parameter of ``shapes`` not put already (in
        their order, from the unit's prng) and the expert layer's two
        buffers."""
        spec = self.spec
        stddev = self.weights_stddev or (1.0 / numpy.sqrt(embed))
        for name, shape in shapes.items():
            vec = self.params[name]
            if vec:
                continue
            arr = numpy.zeros(shape, dtype=numpy.float32)
            if name == "w_conv":
                self.rand().fill_normal(
                    arr, stddev=1.0 / numpy.sqrt(shape[-1]))
            elif name.startswith("w") or name == "router":
                self.rand().fill_normal(arr, stddev=stddev)
            elif name.endswith("_g"):
                arr[...] = 1.0
            elif name == "a_log":
                # Gated DeltaNet's own: g = -A softplus(a + dt_bias), A
                # uniform under 16 and the step log-uniform in
                # [1e-3, 1e-1], so that heads forget at every pace
                arr[...] = numpy.log(self.rand().uniform(
                    1e-3, 16.0, shape))
            elif name == "dt_bias":
                dt = numpy.exp(self.rand().uniform(
                    numpy.log(1e-3), numpy.log(1e-1), shape))
                arr[...] = dt + numpy.log(-numpy.expm1(-dt))
            vec.mem = arr
            vec.initialize(self.device)
        if self.has_experts:
            if not self.expert_bias:
                self.expert_bias.mem = numpy.zeros(
                    spec["n_experts"], dtype=numpy.float32)
            if not self.moe_acc:
                self.moe_acc.mem = numpy.zeros(
                    (3, 3 + spec["held"][1]), dtype=numpy.float32)
            self.expert_bias.initialize(self.device)
            self.moe_acc.initialize(self.device)

    def _attend(self, q, k, v):
        """WHERE attention runs — the one placement decision of the
        one decoder-layer unit (class docstring)."""
        from ..ops import attention as A
        mesh = getattr(self.workflow, "mesh", None)
        if self.seq_axis and mesh is not None and \
                self.seq_axis in mesh.axis_names:
            return A.sequence_parallel_attention(
                q, k, v, mesh, self.seq_axis, causal=self.causal,
                batch_axis=self.batch_axis, mode=self.sp_mode,
                head_axis=self.head_axis, kernel=self.sp_kernel,
                interpret=self.sp_interpret)
        diffusion = getattr(self, "diffusion", None)
        causal = self.causal and not diffusion
        if mesh is not None:
            return A.mesh_attention(
                q, k, v, mesh, causal=causal,
                batch_axis=self.batch_axis, head_axis=self.head_axis,
                window=self.spec["window"], diffusion=diffusion)
        return A.attention(q, k, v, causal=causal,
                           window=self.spec["window"], diffusion=diffusion)

    def _positions(self):
        """The rows' rotary positions: block diffusion's two streams
        both at 0 … L − 1; None (0 … S − 1) otherwise."""
        diffusion = getattr(self, "diffusion", None)
        if not diffusion:
            return None
        import jax.numpy as jnp
        return jnp.tile(jnp.arange(diffusion[0]), 2)

    def tforward(self, read, write, params, ctx, state=None):
        x = read(self.input)
        buffers = {"expert_bias": state["expert_bias"]} \
            if self.has_experts else {}

        def apply(p, b, h):
            return layer_apply(self.spec, p, h, self.compute_dtype,
                               causal=self.causal,
                               attend=self._attend, buffers=b,
                               positions=self._positions())

        if remat_enabled(self.remat):
            apply = checkpointed(apply)
        out, stats = apply(params, buffers, x)
        write(self.output, out)
        return self._moe_update(read, state, stats)

    def _moe_update(self, read, state, stats):
        """The expert layer's accumulator row of this tick (None
        without one)."""
        import jax.numpy as jnp
        if stats is None:
            return None
        # Bucketed by the minibatch class (TRAIN when no loader
        # link); padded block ticks (all-zero mask) are gated out
        # whole, as the evaluator's epoch row gates its own.
        cvec, mvec = self.minibatch_class_vec, self.minibatch_mask
        cls = read(cvec).astype(jnp.int32) if cvec is not None \
            else jnp.int32(2)
        valid = (read(mvec).sum() > 0).astype(jnp.float32) \
            if mvec is not None else jnp.float32(1.0)
        row = jnp.concatenate([
            jnp.stack([stats["made"], stats["landed"],
                       jnp.float32(1.0)]), stats["load"]]) * valid
        return {"moe_acc": state["moe_acc"].at[cls].add(row)}


class MTPModule(LMLayer):
    """One multi-token-prediction module (DeepSeek-V3 §2.2, depth 1):
    from the last layer's output ``h_i`` (``input``, before the final
    norm) and the embedding of the NEXT token ``t_{i+1}`` (the
    loader's ``labels``), ``h'_i = W_eh [rms(Emb(t_{i+1}), enorm) ;
    rms(h_i, hnorm)]``; one more decoder layer of ``spec`` (an
    :class:`LMLayer`'s, experts and all); ``logits'_i = Head(rms(·,
    norm))`` through the SHARED embedding and head (``embedding``,
    ``head``: the units whose weights it reads); and the mean
    cross-entropy of ``logits'_i`` against ``t_{i+2}`` over the
    positions ``i ≤ S − 2`` of the valid rows (``mask``), written to
    ``output`` as a scalar — the term ``EvaluatorLM`` weighs into the
    loss (its ``mtp_loss``).  Its parameters: ``enorm_g``,
    ``hnorm_g``, ``w_eh`` (2E, E), the layer's, ``norm_g``; its
    layer is checkpointed as the body's are; its unit scope is its
    name (``mtp``)."""

    MAPPING = "mtp"

    def __init__(self, workflow, **kwargs):
        super(MTPModule, self).__init__(workflow, **kwargs)
        if self.fused_qkv:
            raise ValueError("an MTP module's layer takes no fused "
                             "projection")
        self.embedding = kwargs["embedding"]
        self.head = kwargs["head"]
        self.labels = None       # linked: the loader's labels
        self.mask = None         # linked: the loader's row mask
        layer = self.params
        self.params = {name: Vector() for name in ("enorm_g", "hnorm_g",
                                                   "w_eh")}
        self.params.update(layer)
        self.params["norm_g"] = Vector()
        self.demand("labels", "mask")

    def param_shapes(self, embed):
        shapes = {"enorm_g": (embed,), "hnorm_g": (embed,),
                  "w_eh": (2 * embed, embed)}
        shapes.update(layer_param_shapes(
            dict(self.spec, ffn_dim=self.spec["ffn_dim"] or
                 self.mlp_ratio * embed), embed))
        shapes["norm_g"] = (embed,)
        return shapes

    def initialize(self, device=None, **kwargs):
        super(LMLayer, self).initialize(device=device, **kwargs)
        embed = self.input.shape[-1]
        self._fill(self.param_shapes(embed), embed)
        self.output.mem = numpy.zeros((), dtype=numpy.float32)
        self.output.initialize(self.device)

    def tforward(self, read, write, params, ctx, state=None):
        import jax.numpy as jnp
        from ..ops.rotary import rms_norm
        cdt, eps = self.compute_dtype, self.spec["norm_eps"]
        h = read(self.input)
        labels = read(self.labels).astype(jnp.int32)
        emb = read(self.embedding.weights)
        head = read(self.head.weights)
        x = jnp.concatenate(
            [rms_norm(emb[labels].astype(cdt), params["enorm_g"], eps),
             rms_norm(h, params["hnorm_g"], eps)], axis=-1)
        x = jnp.dot(x.astype(cdt), params["w_eh"].astype(cdt),
                    preferred_element_type=jnp.float32)
        buffers = {"expert_bias": state["expert_bias"]} \
            if self.has_experts else {}
        layer = {k: v for k, v in params.items()
                 if k not in ("enorm_g", "hnorm_g", "w_eh", "norm_g")}

        def apply(p, b, x):
            return layer_apply(self.spec, p, x, cdt, causal=self.causal,
                               attend=self._attend, buffers=b)

        if remat_enabled(self.remat):
            apply = checkpointed(apply)
        x, stats = apply(layer, buffers, x)
        y = rms_norm(x, params["norm_g"], eps)
        logits = jnp.dot(y.astype(cdt), head.astype(cdt),
                         preferred_element_type=jnp.float32)
        write(self.output, second_token_loss(logits, labels,
                                             read(self.mask)))
        return self._moe_update(read, state, stats)


def second_token_loss(logits, labels, mask):
    """The MTP term: logits (B, S, V) at position ``i`` against
    ``labels[:, i + 1]`` (``t_{i+2}`` where ``labels`` are the next
    tokens), mean cross-entropy over the positions ``i ≤ S − 2`` of
    the rows ``mask`` (B,) keeps."""
    import jax
    import jax.numpy as jnp
    S = logits.shape[1]
    logp = jax.nn.log_softmax(logits[:, :S - 1], axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, 1:, None], axis=-1)[..., 0]
    rows = mask[:, None] * jnp.ones((1, S - 1), jnp.float32)
    return (nll * rows).sum() / jnp.maximum(rows.sum(), 1.0)


class TransformerBlock(LMLayer):
    """:class:`LMLayer` at the OPT spec — pre-LN LayerNorm, full
    multi-head attention, ReLU MLP ``mlp_ratio`` × the width, biases
    — under the name artifacts and snapshots know.  kwargs:
    ``n_heads`` (default 4), ``mlp_ratio`` (default 4), and
    LMLayer's but ``spec``."""

    MAPPING = "transformer_block"

    PARAM_NAMES = ("ln1_g", "ln1_b", "wq", "wk", "wv", "wo",
                   "bq", "bk", "bv", "bo",
                   "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")

    def __init__(self, workflow, **kwargs):
        self.mlp_ratio = kwargs.get("mlp_ratio", 4)
        super(TransformerBlock, self).__init__(
            workflow, **dict(kwargs, spec={
                "n_heads": kwargs.get("n_heads", 4)}))


class RMSNorm(ForwardBase):
    """``x · rsqrt(mean(x², -1) + eps) · gain`` over the last axis:
    the norm a spec-built LM puts before its head (``weights`` is
    the gain)."""

    MAPPING = "rms_norm"

    def __init__(self, workflow, **kwargs):
        super(RMSNorm, self).__init__(workflow, **kwargs)
        self.eps = kwargs.get("eps", 1e-5)
        self.include_bias = False

    def initialize(self, device=None, **kwargs):
        super(RMSNorm, self).initialize(device=device, **kwargs)
        if not self.weights:
            self.weights.mem = numpy.ones(self.input.shape[-1],
                                          dtype=numpy.float32)
            self.weights.initialize(self.device)
        self.output.mem = numpy.zeros(self.input.shape,
                                      dtype=numpy.float32)
        self.output.initialize(self.device)

    def tforward(self, read, write, params, ctx, state=None):
        from ..ops.rotary import rms_norm
        write(self.output, rms_norm(read(self.input),
                                    params["weights"], self.eps))


class PipelinedTransformerStack(ForwardBase):
    """N homogeneous transformer blocks as ONE unit with stage-
    stacked parameters (leading ``n_blocks`` dim) — the pipeline-
    parallel formulation (ops/pipeline.py ``gpipe``): under a mesh
    with a ``stage`` axis the stack shards one block per device, the
    minibatch splits into ``n_microbatches``, and activations hand
    off stage-to-stage via ppermute.  Without the mesh axis the same
    stacked parameters run as a plain ``lax.scan`` — bit-identical
    math, so pipelined vs sequential parity is testable.
    """

    MAPPING = "pipelined_transformer_stack"

    def __init__(self, workflow, **kwargs):
        super(PipelinedTransformerStack, self).__init__(workflow,
                                                        **kwargs)
        self.n_blocks = kwargs.get("n_blocks", 4)
        self.n_heads = kwargs.get("n_heads", 4)
        self.mlp_ratio = kwargs.get("mlp_ratio", 4)
        self.causal = kwargs.get("causal", True)
        self.stage_axis = kwargs.get("stage_axis")
        self.n_microbatches = kwargs.get("n_microbatches", 4)
        #: Pipeline schedule (ops/pipeline.py SCHEDULES): "gpipe"
        #: fill-and-drain, "1f1b" PipeDream-flush memory class,
        #: "interleaved" Megatron virtual chunks.  None → the
        #: root.common.engine.pp_schedule knob (--pp-schedule).
        schedule = kwargs.get("schedule")
        if schedule is None:
            schedule = config_get(root.common.engine.pp_schedule,
                                  "gpipe")
        from ..ops.pipeline import SCHEDULES
        if schedule not in SCHEDULES:
            raise ValueError("unknown pipeline schedule %r — valid: "
                             "%s" % (schedule, list(SCHEDULES)))
        self.schedule = schedule
        #: Interleaved only: virtual chunks per stage (None → one
        #: chunk per local block; root.common.engine.pp_chunks /
        #: --pp-chunks overrides).
        n_chunks = kwargs.get("n_chunks")
        if n_chunks is None:
            n_chunks = config_get(root.common.engine.pp_chunks, None)
        self.n_chunks = n_chunks
        #: None → follow root.common.engine.remat; True/False forces.
        self.remat = kwargs.get("remat")
        #: Fused-QKV layout, frozen at construction like
        #: TransformerBlock's.
        self.fused_qkv = fused_qkv_enabled(kwargs.get("fused_qkv"))
        self.params = {name: Vector() for name in _block_param_shapes(
            1, 1, fused_qkv=self.fused_qkv)}

    @property
    def trainables(self):
        return {n: v for n, v in self.params.items() if v}

    def initialize(self, device=None, **kwargs):
        super(PipelinedTransformerStack, self).initialize(
            device=device, **kwargs)
        batch, seq, embed = self.input.shape
        if embed % self.n_heads:
            raise ValueError("embed dim %d not divisible by %d heads"
                             % (embed, self.n_heads))
        hidden = embed * self.mlp_ratio
        stddev = self.weights_stddev or (1.0 / numpy.sqrt(embed))
        shapes = _block_param_shapes(embed, hidden,
                                     fused_qkv=self.fused_qkv)
        for name, shape in shapes.items():
            vec = self.params[name]
            if vec:
                continue
            arr = numpy.zeros((self.n_blocks,) + shape,
                              dtype=numpy.float32)
            if name.startswith("w"):
                self.rand().fill_normal(arr, stddev=stddev)
            elif name.endswith("_g"):
                arr[...] = 1.0
            vec.mem = arr
            vec.initialize(self.device)
        self.output.mem = numpy.zeros((batch, seq, embed),
                                      dtype=numpy.float32)
        self.output.initialize(self.device)

    @property
    def stage_params(self):
        """The stage-stacked Vectors — what a pipeline sharding
        helper shards on the stage axis (leading dim)."""
        return dict(self.trainables)

    def tforward(self, read, write, params, ctx, state=None):
        from ..ops import pipeline as PL
        x = read(self.input)
        cdt = self.compute_dtype

        def block_fn(p, h):
            return transformer_block_apply(p, h, self.n_heads,
                                           self.causal, cdt)

        if remat_enabled(getattr(self, "remat", None)):
            # Per-BLOCK checkpointing: the pipeline (or the
            # sequential scan) re-runs each block's forward during
            # its backward instead of storing every block's
            # residuals — per-stage activation memory drops from
            # O(blocks/stage) to O(1) per microbatch in flight.
            block_fn = checkpointed(block_fn)

        mesh = getattr(self.workflow, "mesh", None)
        if self.stage_axis and mesh is not None and \
                self.stage_axis in mesh.axis_names and \
                self.n_blocks % mesh.shape[self.stage_axis] == 0:
            # Mirrors apply_dp_pp_sharding's divisibility contract:
            # an indivisible stack stays replicated and runs the
            # sequential scan instead of crashing inside shard_map.
            out = PL.pipeline(
                block_fn, params, x, mesh, self.stage_axis,
                self.n_microbatches,
                schedule=getattr(self, "schedule", "gpipe"),
                n_chunks=getattr(self, "n_chunks", None))
        else:
            out = PL.sequential_stack(block_fn, params, x)
        write(self.output, out)


class GDPipelinedStack(GradientDescentBase):
    MAPPING = "pipelined_transformer_stack"


class LMHead(ForwardBase):
    """Tied or free projection to vocabulary logits:
    (B, S, E) → (B, S, V) — of the first ``rows`` rows only where that
    is given (block diffusion's head reads the noised stream alone)."""

    MAPPING = "lm_head"

    def __init__(self, workflow, **kwargs):
        super(LMHead, self).__init__(workflow, **kwargs)
        self.vocab_size = kwargs["vocab_size"]
        self.rows = kwargs.get("rows")
        #: Weight tying to an Embedding unit (standard LM practice;
        #: gradients flow to the embedding through the read).
        self.tie_to = kwargs.get("tie_to")

    @property
    def trainables(self):
        if self.tie_to is not None:
            return {"bias": self.bias} if self.include_bias and \
                self.bias else {}
        return super(LMHead, self).trainables

    def initialize(self, device=None, **kwargs):
        if self.tie_to is not None and \
                not self.tie_to.is_initialized:
            raise AttributeError("%s: tied embedding %s not "
                                 "initialized yet" %
                                 (self.name, self.tie_to.name))
        super(LMHead, self).initialize(device=device, **kwargs)
        batch, seq, embed = self.input.shape
        if self.tie_to is None and not self.weights:
            stddev = self.weights_stddev or (1.0 / numpy.sqrt(embed))
            w = numpy.zeros((embed, self.vocab_size),
                            dtype=numpy.float32)
            self.rand().fill_normal(w, stddev=stddev)
            self.weights.mem = w
            self.weights.initialize(self.device)
        if self.include_bias and not self.bias:
            self.bias.mem = numpy.zeros(self.vocab_size,
                                        dtype=numpy.float32)
            self.bias.initialize(self.device)
        self.output.mem = numpy.zeros(
            (batch, getattr(self, "rows", None) or seq, self.vocab_size),
            dtype=numpy.float32)
        self.output.initialize(self.device)

    def tforward(self, read, write, params, ctx, state=None):
        import jax.numpy as jnp
        x = read(self.input)
        if getattr(self, "rows", None):
            x = x[:, :self.rows]
        cdt = self.compute_dtype
        if self.tie_to is not None:
            w = read(self.tie_to.weights).T
        else:
            w = params["weights"]
        y = jnp.dot(x.astype(cdt), w.astype(cdt),
                    preferred_element_type=jnp.float32)
        if self.include_bias:
            y = y + params["bias"]
        write(self.output, y)


def diffusion_loss(nll, pred, labels, weights, tok_mask):
    """Masked diffusion's loss from per-position cross-entropies ``nll``
    (B, S) against the clean ids ``labels``, the loss's ``weights`` (B,
    S) — ``m_i / t_β(i)``, 0 where unmasked — and the valid rows' token
    mask: ``Σ w · nll`` over the valid tokens' count (``(1 / S) Σ_i w_i
    · CE_i`` a row, averaged over the rows).  Returns ``(loss, errors
    among the masked positions, the masked positions (B, S) as 0 / 1)``."""
    import jax.numpy as jnp
    w = weights * tok_mask
    masked = (w > 0).astype(jnp.float32)
    return (nll * w).sum() / jnp.maximum(tok_mask.sum(), 1.0), \
        ((pred != labels) * masked).sum(), masked


class EvaluatorLM(EvaluatorBase):
    """Next-token cross-entropy over (B, S, V) logits vs (B, S)
    labels, with per-SAMPLE validity mask; rides the on-device epoch
    accumulator like every evaluator (n_err/n_valid count tokens).
    Under block diffusion (``weights`` linked) it is masked diffusion's
    loss instead: the cross-entropy of each masked position against its
    own clean id, weighed by ``1 / t``, over the tokens
    (:func:`diffusion_loss`; gauge ``diffusion.masked_frac``).

    Where ``mtp_loss`` is linked (an :class:`MTPModule`'s output) the
    loss is ``CE + mtp_weight · mtp_loss`` — what is differentiated
    and accumulated — and ``mtp_acc`` (3 classes × [the MTP term's
    sum, ticks]) keeps the term apart: :meth:`read_mtp_loss`.

    Where ``weights`` is linked (block diffusion: the embedding's
    ``noise_weights``, ``m_i / t_β(i)``) the labels are the clean ids
    and the loss is masked diffusion's: ``(1 / S) Σ_i w_i ·
    CE(logits_i, labels_i)`` a valid row, averaged over the rows;
    ``n_err`` counts the MASKED positions whose argmax misses, and
    ``n_valid`` the tokens; ``diffusion_acc`` (3 classes × [masked
    positions, tokens]) feeds :meth:`read_masked_frac`."""

    def __init__(self, workflow, **kwargs):
        super(EvaluatorLM, self).__init__(workflow, **kwargs)
        self.labels = None
        self.mtp_loss = None
        self.weights = None
        self.mtp_weight = kwargs.get("mtp_weight", 0.3)
        self.mtp_acc = Vector(numpy.zeros((3, 2), dtype=numpy.float32))
        self.diffusion_acc = Vector(numpy.zeros((3, 2),
                                                dtype=numpy.float32))
        self.demand("labels", "mask", "minibatch_class_vec")

    @property
    def tstate(self):
        state = super(EvaluatorLM, self).tstate
        if getattr(self, "mtp_loss", None) is not None:
            state["mtp_acc"] = self.mtp_acc
        if getattr(self, "weights", None) is not None:
            state["diffusion_acc"] = self.diffusion_acc
        return state

    def read_masked_frac(self, cls):
        """Of the tokens since the last read of ``cls``, the share whose
        position was masked (None without block diffusion or a token),
        published as the gauge ``diffusion.masked_frac`` and then
        cleared."""
        if getattr(self, "weights", None) is None:
            return None
        self.diffusion_acc.map_read()
        masked, tokens = (float(v) for v in self.diffusion_acc.mem[cls])
        self.diffusion_acc.map_write()
        self.diffusion_acc.mem[cls] = 0.0
        if not tokens:
            return None
        from ..observability.metrics import registry
        registry.gauge("diffusion.masked_frac").set(masked / tokens)
        return masked / tokens

    def read_mtp_loss(self, cls):
        """The MTP term's mean a tick since the last read of ``cls``
        (None without an MTP module or a tick), published as the
        gauge ``mtp.loss`` and then cleared."""
        if getattr(self, "mtp_loss", None) is None:
            return None
        self.mtp_acc.map_read()
        total, ticks = (float(v) for v in self.mtp_acc.mem[cls])
        self.mtp_acc.map_write()
        self.mtp_acc.mem[cls] = 0.0
        if not ticks:
            return None
        from ..observability.metrics import registry
        registry.gauge("mtp.loss").set(total / ticks)
        return total / ticks

    def tforward(self, read, write, params, ctx, state=None):
        import jax
        import jax.numpy as jnp
        logits = read(self.input)
        labels = read(self.labels).astype(jnp.int32)
        mask = read(self.mask)
        tokens_per = labels.shape[1]
        tok_mask = mask[:, None] * jnp.ones((1, tokens_per),
                                            jnp.float32)
        n_valid = jnp.maximum(tok_mask.sum(), 1.0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                  axis=-1)
        nll = -jnp.take_along_axis(
            logp, labels[..., None], axis=-1)[..., 0]
        pred = jnp.argmax(logits, axis=-1)
        weights = getattr(self, "weights", None)
        if weights is None:
            loss = (nll * tok_mask).sum() / n_valid
            n_err = ((pred != labels) * tok_mask).sum()
        else:
            loss, n_err, masked = diffusion_loss(
                nll, pred, labels, read(weights), tok_mask)
        mtp = getattr(self, "mtp_loss", None)
        if mtp is not None:
            term = read(mtp)
            loss = loss + self.mtp_weight * term
        ctx.set_loss(loss)
        ctx.add_metric("n_err", n_err)
        ctx.add_metric("n_valid", tok_mask.sum())
        update = self._accumulate(read, state, n_err, tok_mask.sum(),
                                  loss)
        if mtp is not None and update is not None:
            valid = (tok_mask.sum() > 0).astype(jnp.float32)
            update["mtp_acc"] = state["mtp_acc"].at[
                read(self.minibatch_class_vec)].add(
                    jnp.stack([term * valid, valid]))
        if weights is not None and update is not None:
            update["diffusion_acc"] = state["diffusion_acc"].at[
                read(self.minibatch_class_vec)].add(
                    jnp.stack([masked.sum(), tok_mask.sum()]))
        return update


class GDEmbedding(GradientDescentBase):
    MAPPING = "embedding"


class GDTransformerBlock(GradientDescentBase):
    MAPPING = "transformer_block"


class GDLMHead(GradientDescentBase):
    MAPPING = "lm_head"


class GDLMLayer(GradientDescentBase):
    MAPPING = "lm_layer"


class GDRMSNorm(GradientDescentBase):
    MAPPING = "rms_norm"


class GDMTPModule(GradientDescentBase):
    MAPPING = "mtp"
