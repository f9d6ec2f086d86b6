"""SDAR — a Qwen3-MoE body trained by BLOCK DIFFUSION, built from layer
specs.

The architecture of JetLM's ``sdar_moe`` checkpoints
(https://huggingface.co/JetLM/SDAR-30B-A3B-Chat; *SDAR: A Synergistic
Diffusion–AutoRegression Paradigm for Scalable Sequence Generation*):
every layer grouped-query attention whose q and k pass a per-head RMS
norm and rotary positions over the whole head, then a dropless top-k
expert layer routed by a SOFTMAX over all experts with the chosen
weights renormalised (``norm_topk_prob``), no shared expert and no
selection bias; RMS norms, no biases, a head of its own behind a final
RMS norm.  Training is block diffusion (BD3-LM, arXiv:2503.09573, with
masked diffusion's weighted loss, arXiv:2406.07524): the clean ids are
cut into blocks of ``B``; each block draws a mask rate ``t ~ U(eps,
1)`` and each position is replaced by MASK with probability ``t``; the
layers run the noised and the clean sequence as ONE input of ``2 S``
rows, both at positions ``0 … S − 1``, under the block-diffusion mask
(a noised row sees its own block's noised keys and the clean keys of
earlier blocks, a clean row the clean keys up to its own block;
``ops.pallas_attention.diffusion_visible``); the head reads the noised
rows, and the loss is the cross-entropy of each masked position against
its own clean id, weighed by ``1 / t``, over ``S``.  The workflow is
``TinyLMWorkflow(layers=..., tied_head=False, diffusion=...)``
(docs/units.md).

``held=(first, count)`` gives the expert layers a SHARE of the routed
experts, as one chip of an expert-parallel group holds it
(docs/moe.md).  Run the small default on sequences that repeat their
first id — a masked position is recovered from a clean earlier block or
an unmasked neighbour in its own::

    python -m veles_tpu veles_tpu/znicz/samples/sdar.py
"""

import numpy

from ...config import root, get as config_get
from ..attention import layer_spec
from .tinylm import FirstTokenLoader, TinyLMWorkflow


def sdar_layers(num_hidden_layers, n_heads, kv_heads, head_dim,
                moe_intermediate_size, n_experts, top_k, held=None,
                rope_theta=1e6, norm_topk=True, norm_eps=1e-6):
    """The model's layers as specs from the published keys: every layer
    (``decoder_sparse_step`` 1, no ``mlp_only_layers``) attention with
    ``kv_heads`` key/value heads, per-head q/k norms and rotary positions
    at ``rope_theta``, over a softmax-routed expert layer.  The
    expert layers' common path is compiled for FOUR times the even
    share of the assignments, where the other families take twice.  A
    tick here is ONE sequence, whose rows may all pick the same experts;
    a layer's held share then swings from nought to several times even,
    and at twice the share the walk over every chunk runs often
    (PERF.md §6)."""
    return [layer_spec(
        norm="rms", n_heads=n_heads, kv_heads=kv_heads, head_dim=head_dim,
        qk_norm=True, rope_theta=rope_theta, bias=False, norm_eps=norm_eps,
        ffn="experts", ffn_dim=moe_intermediate_size, n_experts=n_experts,
        top_k=top_k, held=held, norm_topk=norm_topk, score="softmax",
        route_eps=0.0, slack=(4, 1)) for _ in range(num_hidden_layers)]


class RepeatedTokenLoader(FirstTokenLoader):
    """Sequences that repeat their first id, drawn from all rows of the
    vocabulary but the last (the MASK)."""

    MAPPING = "repeated_token_loader"

    def load_data(self):
        rng = numpy.random.RandomState(7)
        n = self.n_valid + self.n_train
        first = rng.randint(0, self.vocab_size - 1, (n, 1))
        tokens = numpy.repeat(first, self.seq_len, axis=1).astype(
            numpy.int32)
        self.original_data.mem = tokens
        self.original_labels.mem = tokens.copy()
        self.class_lengths = [0, self.n_valid, self.n_train]


def run(load, main):
    cfg = root.sdar
    embed = config_get(cfg.embed_dim, 32)
    vocab = config_get(cfg.vocab_size, 16)
    load(TinyLMWorkflow,
         vocab_size=vocab,
         seq_len=config_get(cfg.seq_len, 32),
         embed_dim=embed, tied_head=False,
         layers=sdar_layers(
             config_get(cfg.num_hidden_layers, 2),
             n_heads=config_get(cfg.n_heads, 4),
             kv_heads=config_get(cfg.kv_heads, 2),
             head_dim=config_get(cfg.head_dim, 16),
             moe_intermediate_size=config_get(
                 cfg.moe_intermediate_size, embed),
             n_experts=config_get(cfg.n_experts, 8),
             top_k=config_get(cfg.top_k, 2),
             rope_theta=config_get(cfg.rope_theta, 1e4)),
         diffusion={"block": config_get(cfg.block, 4), "eps": 1e-3,
                    "mask_id": vocab - 1},
         loader_cls=RepeatedTokenLoader,
         minibatch_size=config_get(cfg.minibatch_size, 64),
         learning_rate=config_get(cfg.learning_rate, 0.03),
         max_epochs=config_get(cfg.max_epochs, 12))
    main()
