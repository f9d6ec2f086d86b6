"""LFM2-MoE — a hybrid causal LM built from layer specs.

The architecture of LiquidAI's ``lfm2_moe`` checkpoints
(https://huggingface.co/LiquidAI/LFM2-24B-A2B): most layers mix the
sequence with a GATED SHORT CONVOLUTION (kernel 3), every fourth with
grouped-query attention whose q and k pass a per-head RMS norm and
rotary positions; the first ``num_dense_layers`` carry a dense gated
MLP, the rest a dropless top-k expert layer with a sigmoid router and
a selection bias; RMS norms throughout, no learned positions, the
head tied to the embedding behind a final RMS norm.  Every layer is
one ``LMLayer`` from a ``layer_spec`` (docs/attention.md, "Layers
from a spec"); the workflow is ``TinyLMWorkflow(layers=...)``, so the
loader, the evaluator, the decision and the fused step are the dense
LM's.

``held=(first, count)`` gives the layers a SHARE of the experts, as
one chip of an expert-parallel group holds it (docs/moe.md).  Run the
small default on the first-token recall task (validation error 0.94
at chance, 0.04 after its 12 epochs)::

    python -m veles_tpu veles_tpu/znicz/samples/lfm2.py
"""

from ...config import root, get as config_get
from ..attention import layer_spec
from .tinylm import TinyLMWorkflow


def lfm2_layers(layer_types, n_heads, kv_heads, intermediate_size,
                moe_intermediate_size, n_experts, top_k,
                num_dense_layers=2, held=None, rope_theta=1e6,
                conv_kernel=3, norm_topk=True, routed_scaling=1.0,
                norm_eps=1e-5):
    """The model's layers as specs: ``layer_types`` is the published
    list (``conv`` | ``full_attention``); the first
    ``num_dense_layers`` get the dense gated MLP, the others the
    experts."""
    specs = []
    for i, kind in enumerate(layer_types):
        if kind not in ("conv", "full_attention"):
            raise ValueError("layer_types[%d] = %r" % (i, kind))
        operator = {"operator": "shortconv", "conv_kernel": conv_kernel} \
            if kind == "conv" else \
            {"operator": "attention", "kv_heads": kv_heads,
             "qk_norm": True, "rope_theta": rope_theta}
        ffn = {"ffn": "gated-mlp", "ffn_dim": intermediate_size} \
            if i < num_dense_layers else \
            {"ffn": "experts", "ffn_dim": moe_intermediate_size,
             "n_experts": n_experts, "top_k": top_k, "held": held,
             "norm_topk": norm_topk, "routed_scaling": routed_scaling}
        specs.append(layer_spec(norm="rms", n_heads=n_heads, bias=False,
                                norm_eps=norm_eps, **operator, **ffn))
    return specs


def run(load, main):
    cfg = root.lfm2
    embed = config_get(cfg.embed_dim, 32)
    load(TinyLMWorkflow,
         vocab_size=config_get(cfg.vocab_size, 16),
         seq_len=config_get(cfg.seq_len, 32),
         embed_dim=embed,
         layers=lfm2_layers(
             config_get(cfg.layer_types,
                        ["conv", "full_attention", "conv"]),
             n_heads=config_get(cfg.n_heads, 4),
             kv_heads=config_get(cfg.kv_heads, 2),
             intermediate_size=config_get(cfg.intermediate_size,
                                          3 * embed),
             moe_intermediate_size=config_get(
                 cfg.moe_intermediate_size, embed),
             n_experts=config_get(cfg.n_experts, 8),
             top_k=config_get(cfg.top_k, 2),
             num_dense_layers=config_get(cfg.num_dense_layers, 1)),
         minibatch_size=config_get(cfg.minibatch_size, 64),
         learning_rate=config_get(cfg.learning_rate, 0.03),
         max_epochs=config_get(cfg.max_epochs, 12))
    main()
