"""Trinity (``afmoe``) — a causal LM of sliding-window and full
attention layers with a shared expert, built from layer specs.

The architecture of Arcee's ``afmoe`` checkpoints
(https://huggingface.co/arcee-ai/Trinity-Mini): every layer is
grouped-query attention whose q and k pass a per-head RMS norm and
whose heads (``head_dim`` wide, wider together than the residual
stream) pass an OUTPUT GATE ``sigmoid(u Wg)`` before ``Wo``; three
layers of four see a SLIDING WINDOW of keys and carry rotary
positions, the fourth sees the whole prefix and carries none; every
operator and every FFN sits in a SANDWICH of RMS norms (one before,
one after); the first ``num_dense_layers`` carry a dense gated MLP,
the rest a dropless top-k expert layer with a sigmoid router, a
selection bias and a ``route_scale``, beside ONE SHARED expert that
every token passes; the embedding's output is scaled by ``sqrt(
hidden)`` and the head is a matrix of its own behind a final RMS
norm.  Every layer is one ``LMLayer`` from a ``layer_spec``
(docs/attention.md, "Layers from a spec"); the workflow is
``TinyLMWorkflow(layers=..., tied_head=False, embed_scale=...)``.

``held=(first, count)`` gives the layers a SHARE of the routed
experts, as one chip of an expert-parallel group holds it
(docs/moe.md); the shared expert is whole on every chip.  Run the
small default on the first-token recall task::

    python -m veles_tpu veles_tpu/znicz/samples/trinity.py
"""

from ...config import root, get as config_get
from ..attention import layer_spec
from .tinylm import TinyLMWorkflow

LAYER_TYPES = ("sliding_attention", "full_attention")


def trinity_layers(layer_types, n_heads, kv_heads, head_dim,
                   intermediate_size, moe_intermediate_size, n_experts,
                   top_k, sliding_window, num_dense_layers=2, held=None,
                   n_shared_experts=1, rope_theta=1e4, route_norm=True,
                   route_scale=1.0, norm_eps=1e-5, slack=(2, 1)):
    """The model's layers as specs: ``layer_types`` is the published
    list (``sliding_attention`` | ``full_attention``); the first
    ``num_dense_layers`` get the dense gated MLP, the others the
    experts and ``n_shared_experts`` (0 or 1) shared ones of an
    expert's width.  ``slack``: the expert layers' common path is
    compiled for TWICE the even share of the assignments, not
    ``ops.moe``'s 5 / 4 — with seeded weights attention over
    thousands of keys is close to a mean, a sequence's common part
    comes out of the sandwich's post norm at full size, and the
    router sends up to half again the even share to one chip's
    experts (PERF.md §6, PR 33); a trained model's selection bias
    keeps its loads even."""
    if n_shared_experts not in (0, 1):
        raise ValueError("%d shared experts: the layer holds one"
                         % n_shared_experts)
    specs = []
    for i, kind in enumerate(layer_types):
        if kind not in LAYER_TYPES:
            raise ValueError("layer_types[%d] = %r" % (i, kind))
        sliding = kind == "sliding_attention"
        ffn = {"ffn": "gated-mlp", "ffn_dim": intermediate_size} \
            if i < num_dense_layers else \
            {"ffn": "experts", "ffn_dim": moe_intermediate_size,
             "n_experts": n_experts, "top_k": top_k, "held": held,
             "norm_topk": route_norm, "routed_scaling": route_scale,
             "route_eps": 1e-20, "slack": slack,
             "shared_ffn_dim": moe_intermediate_size
             if n_shared_experts else None}
        specs.append(layer_spec(
            norm="rms", post_norm=True, n_heads=n_heads,
            kv_heads=kv_heads, head_dim=head_dim, qk_norm=True,
            attn_gate=True, bias=False, norm_eps=norm_eps,
            window=sliding_window if sliding else None,
            rope_theta=rope_theta if sliding else None, **ffn))
    return specs


def run(load, main):
    cfg = root.trinity
    embed = config_get(cfg.embed_dim, 32)
    load(TinyLMWorkflow,
         vocab_size=config_get(cfg.vocab_size, 16),
         seq_len=config_get(cfg.seq_len, 32),
         embed_dim=embed, tied_head=False, embed_scale=embed ** 0.5,
         layers=trinity_layers(
             config_get(cfg.layer_types,
                        ["sliding_attention", "sliding_attention",
                         "full_attention"]),
             n_heads=config_get(cfg.n_heads, 4),
             kv_heads=config_get(cfg.kv_heads, 2),
             head_dim=config_get(cfg.head_dim, 16),
             intermediate_size=config_get(cfg.intermediate_size,
                                          3 * embed),
             moe_intermediate_size=config_get(
                 cfg.moe_intermediate_size, embed),
             n_experts=config_get(cfg.n_experts, 8),
             top_k=config_get(cfg.top_k, 2),
             sliding_window=config_get(cfg.sliding_window, 8),
             num_dense_layers=config_get(cfg.num_dense_layers, 1),
             route_scale=config_get(cfg.route_scale, 1.0)),
         minibatch_size=config_get(cfg.minibatch_size, 64),
         learning_rate=config_get(cfg.learning_rate, 0.03),
         max_epochs=config_get(cfg.max_epochs, 12))
    main()
