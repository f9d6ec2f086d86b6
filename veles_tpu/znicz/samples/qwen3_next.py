"""Qwen3-Next — a causal LM of Gated DeltaNet linear-attention layers
and gated full-attention layers over sparse experts, built from layer
specs.

The architecture of the ``qwen3_next`` checkpoints
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct): of every
``full_attention_interval`` layers the last is grouped-query softmax
attention — per-head RMS norms of q and k, rotary positions on the
first ``partial_rotary_factor`` of each head, an OUTPUT GATE
``sigmoid(u Wg)`` before ``Wo`` — and the others are LINEAR attention
by the gated delta rule (``ops/linear_attention.py``): q, k and v
through a short causal convolution and a SiLU, a per-head state that
each row decays, corrects and reads, a gated RMS norm a head.  Every
layer's FFN is a dropless top-k expert layer routed by a SOFTMAX over
all the experts, beside one shared expert behind a sigmoid gate of its
own.  RMS norms before the operator and before the FFN, no learned
positions, a head matrix of its own behind a final RMS norm.  Every
layer is one ``LMLayer`` from a ``layer_spec`` (docs/attention.md,
"Layers from a spec"); the workflow is ``TinyLMWorkflow(layers=...,
tied_head=False)``.

``held=(first, count)`` gives the layers a SHARE of the routed
experts, as one chip of an expert-parallel group holds it
(docs/moe.md); the shared expert is whole on every chip.  Run the
small default on the first-token recall task, which a layer without a
working state cannot learn::

    python -m veles_tpu veles_tpu/znicz/samples/qwen3_next.py
"""

from ...config import root, get as config_get
from ..attention import layer_spec
from .tinylm import TinyLMWorkflow

LINEAR, FULL = "linear_attention", "full_attention"


def qwen3_next_layers(num_hidden_layers, n_heads, kv_heads, head_dim,
                      linear_key_heads, linear_value_heads,
                      linear_key_dim, linear_value_dim,
                      moe_intermediate_size, n_experts, top_k,
                      shared_expert_intermediate_size,
                      full_attention_interval=4, linear_conv_kernel=4,
                      partial_rotary_factor=0.25, rope_theta=1e7,
                      held=None, norm_topk=True, norm_eps=1e-6,
                      linear_chunk=64, slack=(2, 1)):
    """The model's layers as specs, from the published keys: layer
    ``i`` is full attention where ``(i + 1) % full_attention_interval
    == 0`` and linear attention otherwise (the config's own rule for
    ``layer_types``).  ``slack``: the expert layers' common path is
    compiled for twice the even share of the assignments, as
    ``samples/trinity.py``'s and for its reason."""
    ffn = {"ffn": "experts", "ffn_dim": moe_intermediate_size,
           "n_experts": n_experts, "top_k": top_k, "held": held,
           "norm_topk": norm_topk, "score": "softmax", "route_eps": 0.0,
           "slack": slack,
           "shared_ffn_dim": shared_expert_intermediate_size,
           "shared_gate": True}
    specs = []
    for i in range(num_hidden_layers):
        if (i + 1) % full_attention_interval:
            operator = {"operator": "gated_delta",
                        "linear_key_heads": linear_key_heads,
                        "linear_value_heads": linear_value_heads,
                        "linear_key_dim": linear_key_dim,
                        "linear_value_dim": linear_value_dim,
                        "conv_kernel": linear_conv_kernel,
                        "linear_chunk": linear_chunk}
        else:
            operator = {"n_heads": n_heads, "kv_heads": kv_heads,
                        "head_dim": head_dim, "qk_norm": True,
                        "attn_gate": True, "rope_theta": rope_theta,
                        "rope_fraction": partial_rotary_factor}
        specs.append(layer_spec(norm="rms", bias=False,
                                norm_eps=norm_eps, **operator, **ffn))
    return specs


def run(load, main):
    cfg = root.qwen3_next
    embed = config_get(cfg.embed_dim, 32)
    load(TinyLMWorkflow,
         vocab_size=config_get(cfg.vocab_size, 16),
         seq_len=config_get(cfg.seq_len, 32),
         embed_dim=embed, tied_head=False,
         layers=qwen3_next_layers(
             config_get(cfg.num_hidden_layers, 2),
             n_heads=config_get(cfg.n_heads, 4),
             kv_heads=config_get(cfg.kv_heads, 2),
             head_dim=config_get(cfg.head_dim, 16),
             linear_key_heads=config_get(cfg.linear_key_heads, 2),
             linear_value_heads=config_get(cfg.linear_value_heads, 4),
             linear_key_dim=config_get(cfg.linear_key_dim, 16),
             linear_value_dim=config_get(cfg.linear_value_dim, 16),
             moe_intermediate_size=config_get(
                 cfg.moe_intermediate_size, embed),
             n_experts=config_get(cfg.n_experts, 8),
             top_k=config_get(cfg.top_k, 2),
             shared_expert_intermediate_size=config_get(
                 cfg.shared_expert_intermediate_size, embed),
             full_attention_interval=config_get(
                 cfg.full_attention_interval, 2),
             rope_theta=config_get(cfg.rope_theta, 1e4),
             linear_chunk=config_get(cfg.linear_chunk, 16)),
         minibatch_size=config_get(cfg.minibatch_size, 64),
         learning_rate=config_get(cfg.learning_rate, 0.03),
         max_epochs=config_get(cfg.max_epochs, 12))
    main()
