"""The train step named from the inside (ISSUE 26;
docs/observability.md): unit and phase scopes in the compiled block
program and the scope table ``programs.scopes`` reads back from it,
the ``step`` span with its children on the dispatch path, the one
record a dispatch ``attribution.recent()`` keeps (gc hook included),
the ``--xprof`` window's reduction, and that everything that compiles
carries a name.  CPU only; nothing here asserts a time.
"""

import gc
import json
import os
import re

import pytest

from veles_tpu.config import root
from veles_tpu.launcher import Launcher
from veles_tpu.observability import (attribution, profile, programs,
                                     startup, tracing)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
UNITS = ("loader", "embedding", "block0", "block1", "head",
         "evaluator")
#: What the OPT block opens (``programs.INNER_SCOPES`` holds the
#: spec-built layers' scopes too).
OPT_INNER_SCOPES = ("ln1", "attention", "ln2", "mlp")
STEP_CHILDREN = ["loader.serve_block", "step.upload", "step.enqueue",
                 "step.wait"]


def _reset():
    tracing.reset()
    attribution.reset()
    programs.reset()
    root.common.observability.peak_tflops = None
    root.common.engine.remat = False


@pytest.fixture(autouse=True)
def _clean():
    _reset()
    yield
    _reset()


def _tiny_lm(**kwargs):
    from veles_tpu.znicz.samples.tinylm import TinyLMWorkflow
    import veles_tpu.prng as prng
    prng.reset()
    prng.get(0).seed(7)
    launcher = Launcher()
    wf = TinyLMWorkflow(
        launcher, vocab_size=32, seq_len=8, embed_dim=16, n_heads=2,
        n_blocks=2, minibatch_size=4, max_epochs=1 << 30,
        loader_config={"validate_labels": False}, **kwargs)
    launcher.initialize()
    return launcher, wf


def _run_until_a_train_tick(wf):
    """Single-tick mode serves the validation class first."""
    for _ in range(64):
        wf.loader.run()
        if "train_step" in programs.registered():
            return
    raise AssertionError("no train tick in 64")


@pytest.fixture(scope="module")
def block_run():
    """ONE two-block tiny LM with per-block remat, three block
    dispatches under ``tracing.capture``, then stopped and its device
    arrays deleted BEFORE ``scopes()`` is first asked: what every
    test below reads."""
    _reset()
    root.common.engine.remat = True
    launcher, wf = _tiny_lm(ticks_per_dispatch=4)
    with tracing.capture() as spans:
        tracing.enable()
        for _ in range(3):
            wf.loader.run()
        tracing.disable()
    out = {"spans": list(spans), "recent": attribution.recent(),
           "perf": attribution.perf_summary(),
           "compiled": wf.compiler.lower_last_block().compile()
           .as_text()}
    compiler = wf.compiler
    arrays = [v.devmem for group in (
        compiler._param_vecs.values(), compiler._state_vecs.values(),
        compiler.const_vectors) for v in group]
    launcher.stop()
    for array in arrays:
        array.delete()
    out["registered"] = programs.registered()
    out["scopes"] = programs.scopes("block_step")
    _reset()
    return out


# -- names in the compiled program -------------------------------------------

def test_block_program_names_every_unit_and_the_update(block_run):
    text = block_run["compiled"]
    for unit in UNITS:
        assert re.search(r"jvp\(%s\)" % unit, text), unit
    for inner in OPT_INNER_SCOPES:
        assert "jvp(block1)/%s/" % inner in text, inner
    for scope in programs.STEP_SCOPES:
        assert "/%s/" % scope in text, scope
    assert "rematted_computation" in text


#: sample -> (its layers, unit -> inner scopes it must open)
def _lfm2_body():
    from veles_tpu.znicz.samples.lfm2 import lfm2_layers
    return lfm2_layers(["conv", "full_attention", "conv"], n_heads=2,
                       kv_heads=1, intermediate_size=32,
                       moe_intermediate_size=16, n_experts=4, top_k=2,
                       num_dense_layers=1, held=(0, 2))


def _trinity_body():
    from veles_tpu.znicz.samples.trinity import trinity_layers
    return trinity_layers(
        ["sliding_attention", "sliding_attention", "full_attention"],
        n_heads=2, kv_heads=1, head_dim=8, intermediate_size=32,
        moe_intermediate_size=16, n_experts=4, top_k=2, sliding_window=4,
        num_dense_layers=1, held=(0, 2))


def _qwen3_next_body():
    from veles_tpu.znicz.samples.qwen3_next import qwen3_next_layers
    return qwen3_next_layers(
        2, n_heads=2, kv_heads=1, head_dim=8, linear_key_heads=1,
        linear_value_heads=2, linear_key_dim=8, linear_value_dim=8,
        moe_intermediate_size=16, n_experts=4, top_k=2,
        shared_expert_intermediate_size=16, full_attention_interval=2,
        rope_theta=1e4, held=(0, 2), linear_chunk=4)


def _joyai_body():
    from veles_tpu.znicz.samples.joyai_llm_flash import \
        joyai_llm_flash_layers
    layers, mtp = joyai_llm_flash_layers(
        2, n_heads=2, q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=4,
        qk_rope_head_dim=4, v_head_dim=4, intermediate_size=32,
        moe_intermediate_size=16, n_experts=4, top_k=2, held=(0, 2),
        rope_theta=1e4)
    return {"layers": layers, "mtp": mtp, "tied_head": False}


def _sdar_body():
    from veles_tpu.znicz.samples.sdar import sdar_layers
    return {"layers": sdar_layers(2, n_heads=2, kv_heads=1, head_dim=8,
                                  moe_intermediate_size=16, n_experts=4,
                                  top_k=2, held=(0, 2)),
            "tied_head": False,
            "diffusion": {"block": 4, "eps": 1e-3, "mask_id": 31}}


SPEC_BODIES = {
    "lfm2": (_lfm2_body, {
        "block0": ("ln1", "shortconv", "ln2", "mlp"),
        "block1": ("ln1", "rope", "attention", "ln2", "moe_route",
                   "moe_dispatch", "moe_experts", "moe_combine"),
        "block2": ("shortconv", "moe_experts")}),
    "trinity": (_trinity_body, {
        "block0": ("ln1", "rope", "attention", "attn_gate", "ln1_post",
                   "ln2", "mlp", "ln2_post"),
        "block1": ("attention", "attn_gate", "ln1_post", "moe_route",
                   "moe_experts", "moe_shared", "ln2_post"),
        "block2": ("attention", "attn_gate", "moe_shared", "ln2_post")}),
    "qwen3_next": (_qwen3_next_body, {
        "block0": ("ln1", "shortconv", "gdn_gate", "gated_delta",
                   "gdn_norm", "ln2", "moe_route", "moe_experts",
                   "moe_shared"),
        "block1": ("rope", "attention", "attn_gate", "moe_shared")}),
    "joyai_llm_flash": (_joyai_body, {
        "block0": ("ln1", "mla_compress", "mla_expand", "rope",
                   "attention", "ln2", "mlp"),
        "mtp": ("mla_compress", "mla_expand", "rope", "attention",
                "moe_route", "moe_experts", "moe_shared")}),
    "sdar": (_sdar_body, {
        "embedding": ("bd_noise",),
        "block0": ("ln1", "rope", "attention", "ln2", "moe_route",
                   "moe_experts")}),
}


@pytest.mark.parametrize("sample", sorted(SPEC_BODIES))
def test_spec_built_layers_name_their_inner_scopes(sample):
    """Every inner scope the vocabulary holds beyond the OPT block's
    is opened by a layer built from a spec (``samples/lfm2.py``:
    convolution, attention with rotary positions, dense gated MLP,
    experts; ``samples/trinity.py``: the output gate, the sandwich's
    second norms, the shared expert; ``samples/qwen3_next.py``: the
    gated delta rule, its gates and its gated norm, whose scan of
    sequence / chunk steps the gauges ``linear_attention.scan_steps``
    / ``.chunk`` report; ``samples/joyai_llm_flash.py``: latent
    attention's compressions and expansions, in the body and in the
    MTP module, a unit of its own; ``samples/sdar.py``: block
    diffusion's noising in the embedding), and the scope table places
    each in every phase — but the routing's ordering, which has no
    gradient, the gather of the rows, which the layers' checkpoint
    keeps (docs/moe.md): no recompute holds it, and the noising, which
    no gradient reaches and no checkpoint wraps: forward alone."""
    body, inner = SPEC_BODIES[sample]
    root.common.engine.remat = True
    made = body()
    launcher, wf = _tiny_lm(ticks_per_dispatch=2, **(
        made if isinstance(made, dict) else {"layers": made}))
    wf.loader.run()
    launcher.stop()
    placed = set(programs.scopes("block_step").values())
    for unit, scopes in inner.items():
        for scope in scopes:
            for phase in ("forward", "recompute", "backward"):
                if (scope, phase) == ("moe_route", "backward") or \
                        (scope == "bd_noise" and phase != "forward"):
                    continue
                if (scope, phase) == ("moe_dispatch", "recompute"):
                    assert (phase, unit, scope) not in placed
                    continue
                assert (phase, unit, scope) in placed, (phase, unit,
                                                        scope)
    assert set(s for _body, units in SPEC_BODIES.values()
               for scopes in units.values() for s in scopes) == \
        set(programs.INNER_SCOPES)
    assert ("forward", "final_norm", None) in placed
    from veles_tpu.observability.metrics import registry
    found = [registry.peek("linear_attention." + what,
                           {"program": "block_step"})
             for what in ("scan_steps", "chunk")]
    assert [g.value for g in found] == (
        [2, 4] if sample == "qwen3_next" else [0, 0])     # 8 rows by 4


def test_scopes_answer_after_stop_and_array_deletion(block_run):
    assert block_run["registered"] == ["block_step"]
    table = block_run["scopes"]
    placed = set(table.values())
    for unit in ("block0", "block1"):
        for phase in ("forward", "recompute", "backward"):
            assert (phase, unit, "attention") in placed
            assert (phase, unit, "mlp") in placed
    assert ("forward", "loader", None) in placed
    assert ("backward", "head", None) in placed
    assert ("forward", "evaluator", None) in placed
    assert ("update", "update", None) in placed
    assert ("update", "health", None) in placed
    assert (None, None, None) in placed     # scan bookkeeping
    assert programs.scopes("no_such_program") is None


@pytest.mark.parametrize("op_name,expected", [
    ("jit(block_step)/while/body/closed_call/jvp(block1)/attention/"
     "flash_fwd/pallas_call", ("forward", "block1", "attention")),
    ("jit(block_step)/while/body/closed_call/transpose(jvp(block0))/"
     "jvp(block0)/checkpoint/rematted_computation/mlp/dot_general",
     ("recompute", "block0", "mlp")),
    ("jit(block_step)/while/body/closed_call/transpose(jvp(block0))/"
     "jvp(block0)/checkpoint/ln2/reduce_sum",
     ("backward", "block0", "ln2")),
    ("transpose(jvp(head))/dot_general;jvp(head)/transpose",
     ("backward", "head", None)),
    ("jit(block_step)/while/body/closed_call/update/mul",
     ("update", "update", None)),
    ("jit(block_step)/while/body/closed_call/health/sqrt",
     ("update", "health", None)),
    ("jit(infer_step)/block0/attention/exp",
     ("forward", "block0", "attention")),
    ("jit(block_step)/while/body/dynamic_slice", (None, None, None)),
    ("jit(block_step)/while/body/closed_call/jvp(stranger)/mul",
     (None, None, None)),
], ids=["forward", "recompute", "backward", "merged", "update",
        "health", "infer", "bookkeeping", "unknown-unit"])
def test_classify(op_name, expected):
    assert programs.classify(op_name, set(UNITS)) == expected


def test_parse_hlo_gives_a_nameless_fusion_its_bodys_placing():
    text = "\n".join([
        "HloModule jit_block_step, is_scheduled=true",
        "%fused.1 (p: f32[4]) -> f32[4] {",
        '  %p = f32[4]{0} parameter(0)',
        '  ROOT %m = f32[4]{0} multiply(%p, %p), metadata={op_name='
        '"jit(block_step)/jvp(block0)/mlp/mul" stack_frame_id=3}',
        "}",
        "ENTRY %main (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        "  %copy.1 = f32[4]{0} copy(%a)",
        "  ROOT %fusion.9 = f32[4]{0} fusion(%copy.1), kind=kLoop, "
        "calls=%fused.1",
        "}"])
    table = programs.parse_hlo(text, {"block0"})
    assert table["fusion.9"] == ("forward", "block0", "mlp")
    assert table["m"] == ("forward", "block0", "mlp")
    assert table["copy.1"] == (None, None, None)


def _scopes_of_kernel_calls(calls):
    """The scope table of a registered program whose text holds one
    ``tpu_custom_call`` of each name in ``calls`` (its gauges are
    set on the way)."""
    class Lowered(object):
        def compile(self, compiler_options=None):
            return self

        def as_text(self):
            return "ENTRY %main () -> f32[] {\n" + "".join(
                "  %%%s = f32[] custom-call(), custom_call_target="
                '"tpu_custom_call"\n' % name for name in calls) + "}"

    programs.reset()
    programs.register("block_step", Lowered, ())
    try:
        return programs.scopes("block_step")
    finally:
        programs.reset()


@pytest.mark.parametrize("forwards", [2, 4], ids=["kept", "twice"])
def test_scopes_count_the_flash_calls_of_the_program(forwards):
    """``scopes()`` sets ``attention.flash.fwd_calls`` / ``.bwd_calls``
    (labelled with the program) from the parse it makes anyway: by
    instruction name, ``flash_fwd`` alone or ``flash_fwd.<n>``; the
    backward is ``flash_dkv``, one call a layer that makes dq too."""
    from veles_tpu.observability.metrics import registry
    table = _scopes_of_kernel_calls(
        ["flash_fwd"] + ["flash_fwd.%d" % i for i in range(1, forwards)]
        + ["flash_dkv", "flash_dkv.7", "flash_fwdish.3"])
    label = {"program": "block_step"}
    assert registry.peek("attention.flash.fwd_calls",
                         label).value == forwards
    assert registry.peek("attention.flash.bwd_calls", label).value == 2
    assert programs.kernel_calls(table, "flash_dkv") == 2
    assert programs.kernel_calls(table, "flash_dq") == 0
    assert registry.peek("attention.flash.fwd_calls") is None
    assert registry.peek("moe.gmm_calls", label).value == 0


@pytest.mark.parametrize("gmm", [16, 18], ids=["kept", "again"])
def test_scopes_count_the_megablox_calls_of_the_program(gmm):
    """``scopes()`` sets ``moe.gmm_calls`` / ``moe.tgmm_calls`` the
    same way, by the names the megablox kernels carry (``gmm.<n>``,
    ``tgmm.<n>``: a ``tgmm`` is no ``gmm``): an expert layer holds six
    ``tgmm`` and 16 ``gmm`` where its checkpoint kept the two
    products before the gate, 18 where the recompute ran them
    again."""
    from veles_tpu.observability.metrics import registry
    table = _scopes_of_kernel_calls(
        ["gmm"] + ["gmm.%d" % i for i in range(1, gmm)] +
        ["tgmm.%d" % i for i in range(6)] + ["gmmish.1", "flash_fwd.3"])
    label = {"program": "block_step"}
    assert registry.peek("moe.gmm_calls", label).value == gmm
    assert registry.peek("moe.tgmm_calls", label).value == 6
    assert registry.peek("attention.flash.fwd_calls", label).value == 1
    assert programs.kernel_calls(table, "gmm") == gmm
    assert registry.peek("moe.gmm_calls") is None


@pytest.mark.parametrize("forwards", [3, 6], ids=["kept", "twice"])
def test_scopes_count_the_gated_delta_calls_of_the_program(forwards):
    """``scopes()`` sets ``linear_attention.fwd_calls`` / ``.bwd_calls``
    the same way, by the names the rule's kernels carry
    (``ops/pallas_gated_delta.py``): as many forward sweeps as reverse
    ones where every checkpointed layer kept what the sweep produced,
    twice as many where each recompute runs it again; the inverse's
    kernel is neither."""
    from veles_tpu.observability.metrics import registry
    table = _scopes_of_kernel_calls(
        ["gated_delta_fwd"] +
        ["gated_delta_fwd.%d" % i for i in range(1, forwards)] +
        ["gated_delta_bwd", "gated_delta_bwd.4", "gated_delta_bwd.5",
         "gated_delta_inv.1", "gated_delta_fwdish.2", "flash_fwd.3"])
    label = {"program": "block_step"}
    assert registry.peek("linear_attention.fwd_calls",
                         label).value == forwards
    assert registry.peek("linear_attention.bwd_calls", label).value == 3
    assert programs.kernel_calls(table, "gated_delta_inv") == 1
    assert registry.peek("linear_attention.fwd_calls") is None
    assert registry.peek("linear_attention.scan_steps", label).value == 0


@pytest.mark.parametrize("path,scan", [("xla", [2, 64]),
                                       ("pallas", [0, 0])])
def test_scopes_read_the_rules_scan_where_the_program_holds_one(
        monkeypatch, path, scan):
    """One checkpointed ``gated_delta`` layer, forward + backward, 128
    rows in chunks of 64: XLA's form holds a ``while`` of 128 / 64
    steps under the scope ``gated_delta/chunk64``, and the gauges
    ``linear_attention.scan_steps`` / ``.chunk`` say so; the kernels
    (interpreted here: the grid is a loop of its own, under no such
    scope) carry the state themselves and both read 0."""
    import functools
    import jax
    import jax.numpy as jnp
    from veles_tpu.observability.metrics import registry
    from veles_tpu.ops import linear_attention as L
    from veles_tpu.znicz import attention as Z
    if path == "pallas":
        monkeypatch.setattr(L, "_selects_pallas", lambda *shape: True)
        monkeypatch.setattr(L.PG, "gated_delta", functools.partial(
            L.PG.gated_delta, interpret=True))
    spec = Z.layer_spec(
        norm="rms", bias=False, operator="gated_delta", n_heads=2,
        linear_key_heads=1, linear_value_heads=2, linear_key_dim=8,
        linear_value_dim=8, conv_kernel=4, ffn="gated-mlp", ffn_dim=16)
    params = {name: jnp.full(shape, 0.1)
              for name, shape in Z.layer_param_shapes(spec, 16).items()}
    layer = Z.checkpointed(lambda p, h: Z.layer_apply(
        spec, p, h, jnp.float32)[0])

    def loss(params, x):
        with jax.named_scope("block0"):
            return (layer(params, x) ** 2).sum()

    programs.register("block_step", lambda: jax.jit(jax.grad(loss)).lower(
        params, jnp.ones((1, 128, 16))), ("block0",))
    placed = set(programs.scopes("block_step").values())
    for phase in ("forward", "backward"):
        assert (phase, "block0", "gated_delta") in placed
    label = {"program": "block_step"}
    assert [registry.peek("linear_attention." + what, label).value
            for what in ("scan_steps", "chunk")] == scan
    assert registry.peek("linear_attention.fwd_calls", label).value == 0


def test_a_newer_compiles_program_takes_the_name_over():
    """Within one compile the program with more ticks a dispatch
    keeps the name (a remainder block does not take it); a program of
    another compile (a recompile, a second workflow) does, whatever
    its ticks.  The table is compiled and parsed once."""
    class Lowered(object):
        def __init__(self, unit):
            self.unit, self.compiles = unit, 0

        def compile(self, compiler_options=None):
            self.compiles += 1
            return self

        def as_text(self):
            return ("ENTRY %%main () -> f32[] {\n  ROOT %%c.1 = f32[] "
                    'constant(0), metadata={op_name="jit(block_step)/'
                    '%s/mul"}\n}' % self.unit)

    first, second = object(), object()
    old, new = Lowered("block0"), Lowered("block1")
    programs.register("block_step", lambda: old, UNITS, ticks=8,
                      compiled_by=first)
    programs.register("block_step", lambda: None, UNITS, ticks=3,
                      compiled_by=first)
    assert programs.scopes("block_step") == {
        "c.1": ("forward", "block0", None)}
    assert programs.scopes("block_step")["c.1"][1] == "block0"
    assert old.compiles == 1
    programs.register("block_step", lambda: new, UNITS, ticks=2,
                      compiled_by=second)
    assert programs.scopes("block_step") == {
        "c.1": ("forward", "block1", None)}


def test_scopes_are_this_builds_whatever_the_cache_holds(tmp_path):
    """JAX leaves location metadata out of the persistent cache's key
    (the premise, checked here: if JAX ever keys on metadata this
    fails and ``_compiled_text`` can go), so a build of the program
    with OTHER scopes fills the cache and this build is served that
    entry.  ``scopes()`` compiles past the cache and reads this
    build's scopes — a renamed scope as well as a new one — stores
    nothing, and leaves the cache in use as it found it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    options = {"jax_compilation_cache_dir": str(tmp_path),
               "jax_persistent_cache_min_compile_time_secs": 0.0,
               "jax_persistent_cache_min_entry_size_bytes": 0}
    saved = {k: getattr(jax.config, k) for k in options}

    def build(scope):
        def block_step(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x)
        return jax.jit(block_step).lower(jnp.ones((32, 32)))

    try:
        for k, v in options.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        build("health").compile()
        stored = sorted(os.listdir(str(tmp_path)))
        assert stored
        lowered = build("update")
        stale = lowered.compile().as_text()
        assert "health/" in stale and "update/" not in stale
        # the Lowered keeps that executable, as the one the step
        # registers keeps the executable the dispatch ran from
        assert lowered.compile().as_text() == stale
        programs.register("block_step", lambda: lowered, (), ticks=1)
        assert set(programs.scopes("block_step").values()) == {
            ("update", "update", None), (None, None, None)}
        assert sorted(os.listdir(str(tmp_path))) == stored
        assert jax.config.jax_enable_compilation_cache
        assert "health/" in build("update").compile().as_text()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_scopes_from_the_lowered_kept_for_the_flop_estimate():
    """With a peak known (a TPU; forced here) the step is lowered
    once, from its arguments' shapes, for XLA's FLOP count and
    ``scopes()`` reuses that ``Lowered``; the single-tick programs
    register under their own names."""
    root.common.observability.peak_tflops = 1.0
    launcher, wf = _tiny_lm()
    _run_until_a_train_tick(wf)
    launcher.stop()
    assert programs.registered() == ["infer_step", "train_step"]
    assert attribution.perf_summary()["mfu"] > 0
    placed = set(programs.scopes("train_step").values())
    assert ("backward", "block1", "attention") in placed
    assert ("update", "update", None) in placed


_LOWERINGS = []


def _note_lowering(event, seconds, **kwargs):
    if event.endswith("jaxpr_to_mlir_module_duration"):
        _LOWERINGS.append(event)


def test_the_dispatch_reuses_the_module_the_flop_estimate_lowered():
    """The estimate lowers the step from its arguments' shapes, with
    a sharding only where the array is committed to one: the dispatch
    then finds that module and lowers nothing again (a second
    lowering of sixteen blocks was 4 s of ``opt-1.3b.train``'s
    set-up)."""
    import jax
    if not _LOWERINGS:
        jax.monitoring.register_event_duration_secs_listener(
            _note_lowering)
        _LOWERINGS.append("listening")
    counted = []
    # the first run also lowers the helper programs every later one
    # finds again: it is not compared
    for peak in (None, None, 1.0):
        _reset()
        root.common.observability.peak_tflops = peak
        launcher, wf = _tiny_lm(ticks_per_dispatch=4)
        before = len(_LOWERINGS)
        wf.loader.run()
        counted.append(len(_LOWERINGS) - before)
        # (the compiling dispatch stays out of the live gauges: PR 37)
        wf.loader.run()
        launcher.stop()
    assert "mfu" in attribution.perf_summary()    # the estimate ran
    assert counted[1] >= 1 and counted[2] == counted[1], counted


# -- spans on the dispatch path ----------------------------------------------

def test_step_span_has_four_children_parent_ids_and_ordinal(
        block_run):
    spans = block_run["spans"]
    steps = [s for s in spans if s["name"] == "step"]
    assert [s["attrs"]["ordinal"] for s in steps] == [1, 2, 3]
    for step in steps:
        assert step["parent"] is None
        assert step["attrs"]["ticks"] == 4
        assert step["attrs"]["program"] == "block_step"
        children = [s for s in spans if s["parent"] == step["id"]]
        # the first dispatch also builds the step (PR 37), and the
        # helper programs it compiles on the way are compile.* spans
        built = [c["name"] for c in children
                 if c["name"] not in STEP_CHILDREN
                 and not c["name"].startswith("compile.")]
        assert built == (["step.build"] if step is steps[0] else [])
        assert [c["name"] for c in children
                if c["name"] in STEP_CHILDREN] == STEP_CHILDREN
        assert {c["trace_id"] for c in children} == {step["id"]}
    assert not [s for s in spans if s["name"] == "step.dispatch"]


def test_single_tick_step_has_upload_enqueue_wait():
    launcher, wf = _tiny_lm()
    with tracing.capture() as spans:
        tracing.enable()
        _run_until_a_train_tick(wf)
        tracing.disable()
    launcher.stop()
    steps = [s for s in spans if s["name"] == "step"]
    assert [s["attrs"]["ordinal"] for s in steps] == \
        list(range(1, len(steps) + 1))
    assert {s["attrs"]["program"] for s in steps} == {
        "infer_step", "train_step"}
    for step in steps:
        assert [s["name"] for s in spans
                if s["parent"] == step["id"]] == STEP_CHILDREN[1:]


def test_annotated_span_times_itself_whether_or_not_the_ring_collects():
    with tracing.annotated("step.enqueue") as span:
        pass
    assert span.seconds >= 0.0 and tracing.spans() == []
    tracing.enable()
    with tracing.annotated("step.enqueue", mode="x") as span:
        span.set(more=1)
    assert [(s["name"], s["attrs"]) for s in tracing.spans()] == [
        ("step.enqueue", {"mode": "x", "more": 1})]


# -- one record per dispatch -------------------------------------------------

def test_recent_keeps_the_host_split_and_perf_summary_carries_it(
        block_run):
    recent = block_run["recent"]
    assert [r["ordinal"] for r in recent] == [1, 2, 3]
    for record in recent:
        assert record["program"] == "block_step"
        assert record["ticks"] == 4
        for field in ("serve_s", "upload_s", "enqueue_s", "wait_s"):
            assert record[field] > 0.0, field
        assert record["device_s"] >= \
            record["enqueue_s"] + record["wait_s"]
        assert record["gc_s"] >= 0.0 and record["gc_full"] >= 0
    perf = block_run["perf"]
    assert perf["dispatches"] == 3
    for field in ("serve", "upload", "enqueue", "wait", "gc"):
        assert perf["last_%s_ms" % field] == round(
            recent[-1][field + "_s"] * 1e3, 3)


def test_recent_is_bounded():
    for _ in range(attribution.RECENT + 6):
        with attribution.dispatch(program="block_step", ticks=2) as step:
            with step.enqueue():
                pass
            step.wait(None)
    recent = attribution.recent()
    assert len(recent) == attribution.RECENT == 64
    assert [r["ordinal"] for r in recent] == list(range(7, 71))
    assert attribution.perf_summary()["dispatches"] == 70


def test_no_record_and_no_sync_with_attribution_off():
    root.common.observability.attribution = False
    try:
        class Leaf(object):
            def block_until_ready(self):
                raise AssertionError("synced with attribution off")

        with attribution.dispatch(program="block_step") as step:
            with step.enqueue():
                pass
            step.wait(Leaf())
        assert attribution.recent() == []
        assert attribution.perf_summary() is None
    finally:
        root.common.observability.attribution = True


def test_gc_hook_counts_a_collection_inside_a_step_only():
    gc.collect()                      # outside any step: not counted
    with attribution.dispatch(program="block_step") as step:
        with step.enqueue():
            gc.collect()              # a full one, inside
        step.wait(None)
    gc.collect()                      # after it: not counted
    with attribution.dispatch(program="block_step") as step:
        with step.enqueue():
            pass
        step.wait(None)
    first, second = attribution.recent()
    assert first["gc_full"] == 1 and first["gc_s"] > 0.0
    assert second["gc_full"] == 0 and second["gc_s"] == 0.0
    assert gc.callbacks.count(attribution._on_gc) == 1


def test_closing_the_xprof_window_is_not_the_dispatchs_time(
        monkeypatch):
    """The dispatch that closes the ``--xprof`` window also reduces
    the trace (and may compile for the scope table): its record is
    taken before that."""
    clock = [0.0]
    monkeypatch.setattr(startup, "_timer", lambda: clock[0])

    def close_window(leaf):
        clock[0] += 57.0

    monkeypatch.setattr(attribution, "_xprof_step_end", close_window)
    with attribution.dispatch(program="block_step") as step:
        with step.enqueue():
            clock[0] += 2.0
        step.wait(None)
    assert attribution.recent()[0]["device_s"] == 2.0
    assert clock[0] == 59.0


# -- the --xprof window's reduction ------------------------------------------

def _recorded_planes():
    with open(os.path.join(REPO, "tests", "data",
                           "xprof_planes.json")) as fin:
        return json.load(fin)


def test_profile_reduction_on_a_recorded_plane_set():
    recorded = _recorded_planes()
    table = {k: tuple(v) for k, v in recorded["scopes"].items()}
    reduced = profile.reduce_planes(
        recorded["planes"],
        scopes=lambda program: table if program == "block_step"
        else None)
    assert reduced["devices"] == 1
    assert 0.0 < reduced["busy_s"] <= reduced["window_s"]
    # every operation's self time lands in exactly one placing
    assert sum(reduced["placed"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-6)
    phases = {phase for phase, _unit in reduced["placed"]}
    assert {"forward", "recompute", "backward", "update"} <= phases
    assert set(STEP_CHILDREN + ["step"]) <= set(reduced["spans"])
    # the gaps inside a dispatch are laid against the program's spans
    assert reduced["gaps"]
    for length, _at, inside in reduced["gaps"]:
        assert set(inside) <= set(STEP_CHILDREN + ["step"])
        # (a gap may begin in one step's wait and end in the next
        # step's enqueue: the few microseconds between are nobody's)
        assert 0.9 * length < inside["step"] <= length * (1 + 1e-9)
        assert len(inside) > 1
    text = profile.report(reduced)
    assert "device seconds by phase x unit" in text
    assert "idle gaps over 1 ms" in text


def test_profile_lays_a_gap_against_the_spans_it_lies_in():
    planes = {
        "devices": {"/device:TPU:0": [
            ["%while.1 = () while()", 0.0, 4e6],
            ["%fusion.1 = f32[] fusion()", 0.0, 1e6],
            ["%flash_fwd.3 = f32[] custom-call(), custom_call_target="
             '"tpu_custom_call"', 2e6, 2e6],
            ["%fusion.1 = f32[] fusion()", 9e6, 1e6]]},
        "modules": {"/device:TPU:0": [
            ["jit_block_step(123)", 0.0, 4e6],
            ["jit_block_step(123)", 9e6, 1e6]]},
        "host": [["veles.step#ordinal=2,ticks=8#", 3e6, 8e6],
                 ["veles.step.upload", 5e6, 3e6]]}
    table = {"fusion.1": ("forward", "block0", "mlp"),
             "flash_fwd.3": ("recompute", "block0", "attention")}
    reduced = profile.reduce_planes(planes, scopes=lambda p: table)
    assert reduced["busy_s"] == pytest.approx(5e-3)
    assert reduced["placed"] == {
        ("forward", "block0"): pytest.approx(2e-3),
        ("recompute", "block0"): pytest.approx(2e-3),
        ("unscoped", "-"): pytest.approx(1e-3)}     # the while's own
    assert reduced["kernels"] == {"flash_fwd": [1, pytest.approx(2e-3)]}
    assert reduced["gaps"] == [[
        pytest.approx(5e-3), pytest.approx(4e-3),
        {"step": pytest.approx(5e-3),
         "step.upload": pytest.approx(3e-3)}]]
    assert "step.upload 0.003000" in profile.report(reduced)
    assert "flash_fwd" in profile.report(reduced)
    assert profile.reduce_planes(
        {"devices": {}, "modules": {}, "host": []}) is None


def test_xprof_window_holds_the_programs_spans_and_prints(
        tmp_path, capsys):
    """The ``--xprof`` window on the CPU: no device plane here, but
    the ``veles.*`` spans sit on the host plane of the same
    ``.xplane.pb``, and the window prints its reduction when it
    closes."""
    launcher, wf = _tiny_lm(ticks_per_dispatch=4)
    wf.loader.run()
    attribution.configure_xprof(str(tmp_path), steps=2)
    for _ in range(3):
        wf.loader.run()
    launcher.stop()
    assert "xprof:" in capsys.readouterr().out
    planes = profile.read_planes(profile.find_xplane(str(tmp_path)))
    names = [profile.span_name(e[0]) for e in planes["host"]]
    for name in ["step"] + STEP_CHILDREN:
        assert names.count(name) == 2, name


# -- everything that compiles carries a name ---------------------------------

def _source(*parts):
    with open(os.path.join(REPO, *parts)) as fin:
        return fin.read()


def test_every_pallas_call_is_named():
    calls = 0
    for name in os.listdir(os.path.join(REPO, "veles_tpu", "ops")):
        if not name.endswith(".py"):
            continue
        text = _source("veles_tpu", "ops", name)
        for match in re.finditer(r"pl\.pallas_call\(", text):
            calls += 1
            depth, end = 1, match.end()
            while depth:
                depth += {"(": 1, ")": -1}.get(text[end], 0)
                end += 1
            assert re.search(r'\bname="[a-z_]+"', text[match.end():end]), \
                "%s: pallas_call without name= at offset %d" % (
                    name, match.start())
    assert calls == 10


def test_every_exported_program_is_named():
    text = _source("veles_tpu", "export.py")
    jitted = re.findall(r"jax\.jit\(\s*([A-Za-z_][\w.]*|lambda)", text)
    assert len(jitted) == 8
    assert "run" not in jitted and "lambda" not in jitted
    for name in ("lm_generate", "lm_generate_bucketed", "kv_copy",
                 "paged_extend", "paged_step", "paged_verify"):
        assert name in jitted
    # and no scope or program name of the fused step holds an id()
    step = _source("veles_tpu", "accelerated_units.py")
    assert not re.search(r"named_scope\([^)]*id\(", step)
