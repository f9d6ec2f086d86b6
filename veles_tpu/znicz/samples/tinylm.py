"""TinyLM — a causal transformer language-model workflow.

The reference has no attention models (SURVEY §5: long-context absent
in the 2013-15 framework); this sample exercises the TPU build's
long-context stack end-to-end: Embedding → N decoder layers
(TransformerBlock, or LMLayer from a spec; optionally ring
sequence-parallel over a mesh ``seq`` axis) → LMHead (tied weights)
→ EvaluatorLM → DecisionGD → per-unit GD, the whole tick one fused
XLA computation like every other workflow.

The bundled dataset is the **first-token recall** task: every label
equals the sequence's FIRST token, so the model cannot succeed
without attending across the whole (causal) context — a pure test of
the attention path that a bag-of-last-tokens model fails at chance
level (1/vocab).  Run::

    python -m veles_tpu veles_tpu/znicz/samples/tinylm.py
"""

import numpy

from ...config import root, get as config_get
from ...loader.fullbatch import FullBatchLoader
from ...plumbing import Repeater
from ...accelerated_units import AcceleratedWorkflow
from ..attention import (Embedding, EvaluatorLM, GDEmbedding,
                         GDLMHead, GDLMLayer, GDPipelinedStack,
                         GDRMSNorm, GDTransformerBlock, LMHead,
                         LMLayer, PipelinedTransformerStack, RMSNorm,
                         TransformerBlock, layer_spec)
from ..decision import DecisionGD


class FirstTokenLoader(FullBatchLoader):
    """Synthetic sequences whose every label is the first token."""

    MAPPING = "first_token_loader"

    def __init__(self, workflow, **kwargs):
        super(FirstTokenLoader, self).__init__(workflow, **kwargs)
        self.vocab_size = kwargs.get("vocab_size", 16)
        self.seq_len = kwargs.get("seq_len", 32)
        self.n_train = kwargs.get("n_train", 512)
        self.n_valid = kwargs.get("n_valid", 128)

    def load_data(self):
        rng = numpy.random.RandomState(7)
        n = self.n_valid + self.n_train
        tokens = rng.randint(0, self.vocab_size,
                             (n, self.seq_len)).astype(numpy.int32)
        labels = numpy.repeat(tokens[:, :1], self.seq_len, axis=1)
        self.original_data.mem = tokens
        self.original_labels.mem = labels.astype(numpy.int32)
        self.class_lengths = [0, self.n_valid, self.n_train]


class TinyLMWorkflow(AcceleratedWorkflow):
    """The LM training workflow (long-context capability sample).

    The body is ``n_blocks`` OPT blocks (``TransformerBlock``), or —
    ``layers``: a list of ``znicz.attention.layer_spec`` dicts — one
    ``LMLayer`` a spec, with no learned positions in the embedding
    and an RMS norm (``final_norm``) before the head: the shape of
    the hybrid LMs (``samples/lfm2.py``, ``samples/trinity.py``,
    ``samples/qwen3_next.py``).
    Either way the units are ``block<i>`` and take the same placement
    arguments.  ``tied_head``: the head is the embedding transposed
    (default), or a matrix of its own; ``embed_scale``: what the
    embedding's output is multiplied by."""

    def __init__(self, workflow, vocab_size=16, seq_len=32,
                 embed_dim=32, n_heads=4, n_blocks=1,
                 minibatch_size=64, learning_rate=0.01,
                 gradient_moment=0.9, max_epochs=8, seq_axis=None,
                 sp_mode="ring", sp_kernel=None, sp_interpret=None,
                 pipelined=False, stage_axis=None, n_microbatches=4,
                 schedule=None, n_chunks=None, fused_qkv=None,
                 layers=None, tied_head=True, embed_scale=1.0,
                 loader_cls=FirstTokenLoader, loader_config=None,
                 **kwargs):
        super(TinyLMWorkflow, self).__init__(workflow, **kwargs)
        self.repeater = Repeater(self)
        self.repeater.link_from(self.start_point)

        self.loader = loader_cls(
            self, minibatch_size=minibatch_size,
            vocab_size=vocab_size, seq_len=seq_len,
            **(loader_config or {}))
        self.loader.link_from(self.repeater)

        self.embedding = Embedding(
            self, vocab_size=vocab_size, embed_dim=embed_dim,
            positions=layers is None, scale=embed_scale,
            name="embedding")
        self.embedding.link_from(self.loader)
        self.embedding.input = self.loader.minibatch_data

        self.forwards = [self.embedding]
        prev = self.embedding
        if layers is not None and pipelined:
            raise ValueError(
                "layers=[specs] builds the whole body: it does not "
                "go with pipelined=True (the pipelined stack holds "
                "OPT blocks only)")
        if pipelined:
            stack = PipelinedTransformerStack(
                self, n_blocks=n_blocks, n_heads=n_heads,
                causal=True, stage_axis=stage_axis,
                n_microbatches=n_microbatches, schedule=schedule,
                n_chunks=n_chunks, fused_qkv=fused_qkv,
                name="stack")
            stack.link_from(prev)
            stack.input = prev.output
            self.forwards.append(stack)
            prev = stack
            n_blocks = 0
        bodies = [(LMLayer, {"spec": spec}) for spec in layers] \
            if layers is not None else \
            [(TransformerBlock, {"n_heads": n_heads})] * n_blocks
        for i, (cls, what) in enumerate(bodies):
            block = cls(
                self, causal=True, seq_axis=seq_axis, sp_mode=sp_mode,
                sp_kernel=sp_kernel, sp_interpret=sp_interpret,
                fused_qkv=fused_qkv,
                # Bucket an expert layer's accumulator rows by
                # sample class and gate padded ticks out of them.
                minibatch_class_vec=self.loader.minibatch_class_vec,
                minibatch_mask=self.loader.minibatch_mask,
                name="block%d" % i, **what)
            block.link_from(prev)
            block.input = prev.output
            self.forwards.append(block)
            prev = block
        if layers is not None:
            # the layers' own norm once more, at the last one's eps
            norm = RMSNorm(self, name="final_norm",
                           eps=layer_spec(**layers[-1])["norm_eps"])
            norm.link_from(prev)
            norm.input = prev.output
            self.forwards.append(norm)
            prev = norm

        self.head = LMHead(
            self, vocab_size=vocab_size,
            tie_to=self.embedding if tied_head else None, name="head")
        self.head.link_from(prev)
        self.head.input = prev.output
        self.forwards.append(self.head)

        self.evaluator = EvaluatorLM(self)
        self.evaluator.link_from(self.head)
        self.evaluator.input = self.head.output
        self.evaluator.labels = self.loader.minibatch_labels
        self.evaluator.mask = self.loader.minibatch_mask
        self.evaluator.minibatch_class_vec = \
            self.loader.minibatch_class_vec

        self.decision = DecisionGD(self, max_epochs=max_epochs,
                                   evaluator=self.evaluator)
        self.decision.link_from(self.evaluator)
        self.decision.link_attrs(
            self.loader, "minibatch_class", "last_minibatch",
            "epoch_ended", "epoch_number")

        gd_kw = {"learning_rate": learning_rate,
                 "gradient_moment": gradient_moment}
        self.gds = []
        prev_gd = self.decision
        for unit in reversed(self.forwards):
            cls = {Embedding: GDEmbedding,
                   TransformerBlock: GDTransformerBlock,
                   PipelinedTransformerStack: GDPipelinedStack,
                   LMLayer: GDLMLayer, RMSNorm: GDRMSNorm,
                   LMHead: GDLMHead}[type(unit)]
            gd = cls(self, target=unit, **gd_kw)
            gd.link_from(prev_gd)
            self.gds.append(gd)
            prev_gd = gd

        self.repeater.link_from(prev_gd)
        self.repeater.gate_block = self.decision.complete
        self.end_point.link_from(prev_gd)
        self.end_point.gate_block = ~self.decision.complete


def run(load, main):
    cfg = root.tinylm
    load(TinyLMWorkflow,
         vocab_size=config_get(cfg.vocab_size, 16),
         seq_len=config_get(cfg.seq_len, 32),
         embed_dim=config_get(cfg.embed_dim, 32),
         n_heads=config_get(cfg.n_heads, 4),
         n_blocks=config_get(cfg.n_blocks, 1),
         pipelined=config_get(cfg.pipelined, False),
         schedule=config_get(cfg.schedule, None),
         n_chunks=config_get(cfg.n_chunks, None),
         minibatch_size=config_get(cfg.minibatch_size, 64),
         learning_rate=config_get(cfg.learning_rate, 0.01),
         max_epochs=config_get(cfg.max_epochs, 8))
    main()
