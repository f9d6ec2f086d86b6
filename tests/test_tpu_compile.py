"""The kernels of the main path, compiled for a DESCRIBED TPU v5e at
the geometries the LM trains and serves at — no chip needed: the TPU
compiler installed with JAX compiles for a topology that is described
and not attached (the ``on-chip-measurement`` guide, section 2).

Interpret-mode parity tests cannot see what these see: a block shape
the TPU lowering refuses, or a kernel that asks for more VMEM than a
core has.  A compile that passes is not a chip run — it says nothing
about results or speed; ``chip_smoke.py`` is the chip run.

Everything that touches the topology lives in the module-scoped
fixture below: only one process at a time may load the TPU library,
so the call must not happen while any module is imported (every xdist
worker imports every test file), and all of these tests stay in THIS
file so one worker gets them all.  The persistent compile cache is
turned off around them — an entry written for a described chip cannot
be read back without one.
"""

import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

#: The LM bench geometry (bench.py LM_*, chip_smoke.LM_GEOMETRY).
LM = (8, 1024, 16, 128)  # B, S, H, D
#: ``opt-1.3b.train`` (benchmark/): 4 x 2048 tokens, 32 heads of 64.
HEAD_64 = (4, 2048, 32, 64)
#: ``opt-6.7b.train``: 4 x 2048 tokens, 32 heads of 128.
OPT_6_7B = (4, 2048, 32, 128)
#: ``lfm2-24b-a2b.train``: 8 x 2048 tokens, the 8 kv heads broadcast
#: to the 32 query heads of 64 before the call.
LFM2 = (8, 2048, 32, 64)
#: ``trinity-mini.train-8k``: 1 x 8192 tokens, the 4 kv heads
#: broadcast to the 32 query heads of 128; one call of each kernel.
TRINITY = (1, 8192, 32, 128)
TRINITY_WINDOW = 2048
#: ``qwen3-next.train-8k``: 1 x 8192 tokens on a 2,048-wide stream;
#: the full layer's 2 kv heads broadcast to 16 query heads of 256.
QWEN3_NEXT = (1, 8192, 16, 256)
#: ``joyai-flash.train-8k``: 1 x 8192 tokens, 32 latent-attention
#: heads whose keys are 192 wide (128 from the latent, 64 rotated) and
#: whose values are 128.
JOYAI = (1, 8192, 32, 192)
JOYAI_VALUES = 128
#: ``sdar-30b.train-4k``: two streams of 4,096 ids (noised, clean) in
#: blocks of 4 — 8,192 rows, the 4 kv heads broadcast to 32 query heads
#: of 128.
SDAR = (1, 8192, 32, 128)
SDAR_DIFFUSION = (4096, 4)
#: Heads of 512 at 8,192 rows: the forward's call fits ``VMEM_BUDGET``
#: and the fused backward does not (79 MiB), so dq and dk/dv are two
#: calls.
HEAD_512 = (1, 8192, 8, 512)
#: Decode: one new token per row over a 2048-slot gathered table.
DECODE_L = 2048
#: Ring shard: S=1024 over a 2-way seq axis.
RING_SHARD = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s"
                    % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(text, *kernels):
    """How many instructions of a compiled program's text are calls of
    each of the Pallas ``kernels``, by the name it gives them."""
    return tuple(
        len(re.findall(r"%%%s[.\d]* = [^\n]*tpu_custom_call" % kernel,
                       text))
        for kernel in kernels)


def _flash_calls(text):
    """How many ``flash_fwd``, ``flash_dq`` and ``flash_dkv`` calls a
    compiled program holds: ``%flash_dkv.3`` under a layer's named
    scopes, the kernel's name inside autodiff's in a program traced
    with none (``jvp_flash_fwd_.1``, ``transpose_jvp_flash_dkv__.1``)."""
    return tuple(
        len(re.findall(r"%%[\w.]*%s[\w.]* = [^\n]*tpu_custom_call"
                       % kernel, text))
        for kernel in ("flash_fwd", "flash_dq", "flash_dkv"))


def _megablox_calls(text):
    return _kernel_calls(text, "gmm", "tgmm")


def _wrap(name):
    """What a case wraps a layer in: nothing, the layers' checkpoint,
    or a ``jax.checkpoint`` with no policy."""
    from veles_tpu.znicz.attention import checkpointed
    return {"none": lambda fn: fn, "checkpointed": checkpointed,
            "bare": jax.checkpoint}[name]


#: ``lfm2-24b-a2b.train``'s attention layer (the MLP cut narrow: the
#: compile is of the attention path).
LFM2_LAYER = dict(norm="rms", kv_heads=8, qk_norm=True, rope_theta=1e6,
                  bias=False, ffn="gated-mlp")


def _layer_step(wrap, shape, sharding, **spec):
    """Loss and gradients of two decoder layers, each under the
    layers' checkpoint (``wrap`` "checkpointed") or a ``jax.checkpoint``
    with no policy ("bare"), at a cell's attention geometry, and the
    shapes to lower it from.  The loss is not linear, so its own
    forward pass stays in the program beside the backward's
    recompute."""
    from veles_tpu.znicz import attention as Z
    B, S, H, D = shape
    spec = Z.layer_spec(n_heads=H, ffn_dim=256, **spec)
    layer = _wrap(wrap)(lambda p, h: Z.layer_apply(spec, p, h,
                                                   jnp.bfloat16)[0])

    def loss(params, x):
        for p in params:
            x = layer(p, x)
        return (x * x).sum()

    params = [{name: _struct(shape, jnp.float32, sharding)
               for name, shape in
               Z.layer_param_shapes(spec, H * D).items()}] * 2
    return jax.jit(jax.value_and_grad(loss)), \
        (params, _struct((B, S, H * D), jnp.float32, sharding))


@pytest.mark.parametrize("shape,inputs", [
    (LM, "float32"),
    ((LM[0], 2048) + LM[2:], "float32"),
    (HEAD_64, "bfloat16"),
    (OPT_6_7B, "bfloat16"),
    (LFM2, "bfloat16"),
], ids=["1024", "2048", "head64", "opt-6.7b", "lfm2"])
@pytest.mark.parametrize("operands", ["float32", "bfloat16"])
@pytest.mark.parametrize("grad", [False, True],
                         ids=["fwd", "fwd+bwd"])
def test_flash_attention_compiles(one_chip, grad, operands, shape,
                                  inputs):
    """The training kernel at the LM geometry; at ``SCOPED_SEQ`` — the
    longest call made with no compiler parameters, where the backward's
    dk/dv kernel (q, dO, lse, delta resident) must still fit Mosaic's
    default scoped VMEM;
    and at ``opt-1.3b.train``'s own geometry, head size 64 (half a
    lane row) with bf16 inputs as the block hands them over: a
    (1, block, 64) block spans the array's whole last dimension,
    which Mosaic takes as it is; and at the other two cells' calls.
    Every case is causal: the kernels' loop bounds are scalars
    computed from the offset operands and ``program_id``, which
    Mosaic must take as the bounds of an ``scf.for``.  The gradient is
    one forward and ONE backward call, the fused ``flash_dkv`` (its dq
    accumulator over the call's rows in VMEM), in float32 operands
    too."""
    from veles_tpu.ops import pallas_attention as PA
    assert shape[1] <= PA.SCOPED_SEQ and PA.supports(shape, shape)
    od = jnp.dtype(operands).type

    def fwd(q, k, v):
        return PA.pallas_attention(q, k, v, causal=True,
                                   operand_dtype=od)

    fn = fwd
    if grad:
        fn = jax.grad(
            lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))
    x = _struct(shape, jnp.dtype(inputs), one_chip)
    text = _compiled_text(fn, x, x, x)
    assert _flash_calls(text) == ((1, 0, 1) if grad else (1, 0, 0))


@pytest.mark.parametrize("shape,spec,wrap,calls", [
    (HEAD_64, {}, "checkpointed", (2, 0, 2)),
    (OPT_6_7B, {}, "checkpointed", (2, 0, 2)),
    (LFM2, LFM2_LAYER, "checkpointed", (2, 0, 2)),
    (HEAD_64, {}, "bare", (4, 0, 2)),
], ids=["head64", "opt-6.7b", "lfm2", "head64, no policy"])
def test_checkpointed_layer_runs_the_flash_forward_once(
        one_chip, monkeypatch, shape, spec, wrap, calls):
    """The three cells' attention layers under the layers'
    checkpoint, forward + backward: the compiled program holds
    ``flash_fwd`` : ``flash_dkv`` = 1 : 1 and no ``flash_dq`` — the
    one backward call makes dq too, and the recompute reads the
    forward kernel's kept output.  A bare ``jax.checkpoint`` runs the
    kernel again in every recompute."""
    from veles_tpu.ops import attention as A
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    step, structs = _layer_step(wrap, shape, one_chip, **spec)
    assert _flash_calls(step.lower(*structs).compile().as_text()) \
        == calls


@pytest.mark.parametrize("wrap,fwd_calls", [("checkpointed", 2),
                                            ("bare", 4)])
def test_scopes_publish_the_flash_call_counts(one_chip, monkeypatch,
                                              wrap, fwd_calls):
    """``programs.scopes()`` sets ``attention.flash.fwd_calls`` /
    ``.bwd_calls`` (the ``flash_dkv`` calls) from its parse of the
    program: equal where every layer kept its forward kernel's output,
    two to one where the recompute runs it again."""
    from veles_tpu.observability import programs
    from veles_tpu.observability.metrics import registry
    from veles_tpu.ops import attention as A
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    step, structs = _layer_step(wrap, HEAD_64, one_chip)
    programs.reset()
    programs.register("block_step", lambda: step.lower(*structs), ())
    try:
        table = programs.scopes("block_step")
    finally:
        programs.reset()
    label = {"program": "block_step"}
    assert registry.peek("attention.flash.fwd_calls",
                         label).value == fwd_calls
    assert registry.peek("attention.flash.bwd_calls", label).value == 2
    assert programs.kernel_calls(table, "flash_dkv") == 2
    assert programs.kernel_calls(table, "flash_dq") == 0


@pytest.mark.parametrize("window", [TRINITY_WINDOW, None],
                         ids=["window", "full"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_past_max_seq_compiles(one_chip, grad, window):
    """``trinity-mini.train-8k``'s two calls: 8,192 tokens past
    ``SCOPED_SEQ`` in ONE forward call and ONE fused backward call, k
    and v (the backward's q and dO, and its dq accumulator) whole in
    VMEM under the limit each call states —
    under the window of 2,048 the walk's lower bound, computed in the
    kernel from the global origins, is one more scalar Mosaic must take
    as an ``scf.for`` bound; no S x S array, so the program's
    temporaries stay far under one (268 MB a head)."""
    from veles_tpu.ops import pallas_attention as PA
    assert TRINITY[1] > PA.SCOPED_SEQ and PA.supports(TRINITY, TRINITY)
    assert PA.call_rows(8192, 128, 128, 2) == 8192

    def fwd(q, k, v):
        return PA.pallas_attention(q, k, v, causal=True, window=window,
                                   operand_dtype=jnp.bfloat16)

    fn = fwd
    if grad:
        fn = jax.grad(
            lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))
    x = _struct(TRINITY, jnp.bfloat16, one_chip)
    compiled = jax.jit(fn).lower(x, x, x).compile()
    assert _flash_calls(compiled.as_text()) == \
        ((1, 0, 1) if grad else (1, 0, 0))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


#: ``trinity-mini.train-8k``'s attention (the FFN cut narrow: the
#: compile is of the attention path): 32 gated heads of 128 over 4
#: key/value heads on a 2,048-wide stream, sandwich norms.
TRINITY_LAYER = dict(norm="rms", post_norm=True, kv_heads=4,
                     head_dim=128, qk_norm=True, attn_gate=True,
                     bias=False, ffn="gated-mlp")


@pytest.mark.parametrize("kind,spec", [
    ("sliding", dict(TRINITY_LAYER, window=TRINITY_WINDOW,
                     rope_theta=1e4)),
    ("full", TRINITY_LAYER)])
def test_trinity_layers_run_each_flash_kernel_once_a_pair(
        one_chip, monkeypatch, kind, spec):
    """The cell's two kinds of spec-built layer, two of each under the
    layers' checkpoint, forward + backward: each layer's 8,192 rows are
    one forward and one fused backward call and its forward output is
    kept, so the program holds 2 ``flash_fwd``, 2 ``flash_dkv`` and no
    ``flash_dq`` — where the chunked walk would hold one of each a
    visible pair of 2,048-row chunks: 14 for two sliding layers, 20
    for two full ones."""
    from veles_tpu.ops import attention as A
    from veles_tpu.znicz import attention as Z
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    B, S, H, _ = TRINITY
    spec = Z.layer_spec(n_heads=H, ffn_dim=256, **spec)
    layer = Z.checkpointed(lambda p, h: Z.layer_apply(
        spec, p, h, jnp.bfloat16)[0])

    def loss(params, x):
        for p in params:
            x = layer(p, x)
        return (x * x).sum()

    params = [{name: _struct(shape, jnp.float32, one_chip)
               for name, shape in
               Z.layer_param_shapes(spec, 2048).items()}] * 2
    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, _struct((B, S, 2048), jnp.float32, one_chip)
    ).compile().as_text()
    assert _flash_calls(text) == (2, 0, 2)


#: ``qwen3-next.train-8k``'s two kinds of layer (the FFN cut narrow:
#: the compile is of the operator's path).
QWEN3_NEXT_LINEAR = dict(
    norm="rms", bias=False, norm_eps=1e-6, operator="gated_delta",
    linear_key_heads=16, linear_value_heads=32, linear_key_dim=128,
    linear_value_dim=128, conv_kernel=4, ffn="gated-mlp")
QWEN3_NEXT_FULL = dict(
    norm="rms", bias=False, norm_eps=1e-6, kv_heads=2, head_dim=256,
    qk_norm=True, attn_gate=True, rope_theta=1e7, rope_fraction=0.25,
    ffn="gated-mlp")


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_at_a_value_head_of_its_own_compiles(one_chip,
                                                              grad):
    """``joyai-flash.train-8k``'s call: keys 192 wide — a block that
    spans the array's whole last dimension, as head 64's does — against
    values 128 wide, 8,192 tokens in ONE forward and ONE fused
    backward call (38.9 MiB reckoned, dq's accumulator 8 MiB of it)."""
    from veles_tpu.ops import pallas_attention as PA
    B, S, H, D = JOYAI
    values = (B, S, H, JOYAI_VALUES)
    assert PA.supports(JOYAI, JOYAI, v_shape=values)

    def fwd(q, k, v):
        return PA.pallas_attention(q, k, v, causal=True,
                                   operand_dtype=jnp.bfloat16)

    fn = fwd
    if grad:
        fn = jax.grad(
            lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))
    x = _struct(JOYAI, jnp.bfloat16, one_chip)
    compiled = jax.jit(fn).lower(
        x, x, _struct(values, jnp.bfloat16, one_chip)).compile()
    assert _flash_calls(compiled.as_text()) == \
        ((1, 0, 1) if grad else (1, 0, 0))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("shape,values", [
    (JOYAI, JOYAI_VALUES), (QWEN3_NEXT, QWEN3_NEXT[3]),
    (TRINITY, TRINITY[3]), (HEAD_512, HEAD_512[3])],
    ids=["joyai", "qwen3-next", "trinity", "head 512, split"])
def test_whole_sequence_kernels_compile_under_their_vmem_limit(
        one_chip, shape, values):
    """Each 8k cell's call, forward + backward at 8,192 tokens: ONE
    forward call stating the scoped VMEM limit its shapes reckon
    (``call_bytes``, twice over, under the core's 128 MiB) and ONE
    fused backward stating its own (``fused_bytes``, twice over),
    each compiling under it; the program's temporaries fall against
    the chunked walk at the same shapes (chunks of 2,048: no slices of
    every pair, no partials to merge; there the forward states nothing
    and the fused backward its limit at 2,048 rows).  Where the fused
    backward does not fit ``VMEM_BUDGET`` (heads of 512 at 8,192 rows;
    at 2,048 it does) the backward is the two kernels, stating the
    forward's limit."""
    from veles_tpu.ops import pallas_attention as PA
    B, S, H, D = shape
    limit = -(-2 * PA.call_bytes(S, D, values, 2, 512, 512) >> 20) << 20
    assert PA.call_rows(S, D, values, 2) == S and limit < 128 << 20
    fused = PA.fuses(S, S, D, values, 2, 512, 512)
    assert fused == (shape != HEAD_512)

    def fused_limit(rows):
        return -(-2 * PA.fused_bytes(rows, D, values, 2, 512, 512)
                 >> 20) << 20

    def compiled(chunk):
        def loss(q, k, v):
            return PA.pallas_attention(
                q, k, v, causal=True, operand_dtype=jnp.bfloat16,
                chunk=chunk).astype(jnp.float32).sum()
        fn = jax.grad(loss, argnums=(0, 1, 2))
        x = _struct(shape, jnp.bfloat16, one_chip)
        structs = (x, x, _struct((B, S, H, values), jnp.bfloat16,
                                 one_chip))
        return _vmem_limits(fn, *structs), \
            jax.jit(fn).lower(*structs).compile()

    limits, whole = compiled(None)
    if fused:
        assert sorted(limits) == sorted([limit, fused_limit(S)])
        assert fused_limit(S) < 128 << 20
        assert _flash_calls(whole.as_text()) == (1, 0, 1)
    else:
        assert limits == [limit] * 3
        assert _flash_calls(whole.as_text()) == (1, 1, 1)
    unstated, chunked = compiled(2048)
    assert PA.fuses(2048, 2048, D, values, 2, 512, 512)
    assert sorted(unstated, key=str) == \
        [fused_limit(2048)] * 10 + [None] * 10
    assert _flash_calls(chunked.as_text()) == (10, 0, 10)
    assert whole.memory_analysis().temp_size_in_bytes < \
        chunked.memory_analysis().temp_size_in_bytes


def test_block_diffusion_kernels_compile_as_one_call_each(one_chip):
    """``sdar-30b.train-4k``'s call, forward + backward over the 8,192
    rows of the two streams: ONE forward and ONE fused backward call,
    each stating the scoped VMEM limit the causal call at this shape
    states, the walk's bounds and the mask judged inside the kernels
    (no (2L)² mask among the operands), and a trace counts 80 of 256
    tiles."""
    from veles_tpu import resilience
    from veles_tpu.ops import pallas_attention as PA
    B, S, H, D = SDAR
    limit = -(-2 * PA.call_bytes(S, D, D, 2, 512, 512) >> 20) << 20
    fused = -(-2 * PA.fused_bytes(S, D, D, 2, 512, 512) >> 20) << 20
    label = {"mask": "block_diffusion"}
    counter = resilience.stats.registry.counter
    before = [counter("attention.flash.tiles_" + what, label).value
              for what in ("visited", "total")]

    def loss(q, k, v):
        return PA.pallas_attention(
            q, k, v, diffusion=SDAR_DIFFUSION,
            operand_dtype=jnp.bfloat16).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2))
    x = _struct(SDAR, jnp.bfloat16, one_chip)
    assert sorted(_vmem_limits(fn, x, x, x)) == [limit, fused]
    compiled = jax.jit(fn).lower(x, x, x).compile()
    assert _flash_calls(compiled.as_text()) == (1, 0, 1)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    after = [counter("attention.flash.tiles_" + what, label).value
             for what in ("visited", "total")]
    traces = (after[1] - before[1]) // 256
    assert traces >= 1 and after[0] - before[0] == 80 * traces


def _vmem_limits(fn, *structs):
    """The scoped VMEM limit each ``pallas_call`` of ``fn``'s jaxpr
    states (None: Mosaic's default), nested calls included."""
    from jax._src import core
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                params = eqn.params["compiler_params"].get("mosaic_tpu")
                found.append(params and params.vmem_limit_bytes)
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*structs).jaxpr)
    return found


#: ``joyai-flash.train-8k``'s latent operator (the FFN cut narrow).
JOYAI_LAYER = dict(
    norm="rms", bias=False, norm_eps=1e-6, operator="latent",
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128, rope_theta=3.2e7, rope_interleave=True,
    ffn="gated-mlp")


def test_joyai_latent_layers_run_each_flash_kernel_once_a_pair(
        one_chip, monkeypatch):
    """Two latent layers under the layers' checkpoint, forward +
    backward at 1 x 8,192 tokens of 2,048: each layer runs the forward
    kernel and the fused backward once over the whole sequence (the
    forward's output kept), and the program needs under 4 GB."""
    from veles_tpu.ops import attention as A
    from veles_tpu.znicz import attention as Z
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    B, S, H, _ = JOYAI
    spec = Z.layer_spec(n_heads=H, ffn_dim=256, **JOYAI_LAYER)
    layer = Z.checkpointed(lambda p, h: Z.layer_apply(
        spec, p, h, jnp.bfloat16)[0])

    def loss(params, x):
        for p in params:
            x = layer(p, x)
        return (x * x).sum()

    params = [{name: _struct(shape, jnp.float32, one_chip)
               for name, shape in
               Z.layer_param_shapes(spec, 2048).items()}] * 2
    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        params, _struct((B, S, 2048), jnp.float32, one_chip)).compile()
    assert _flash_calls(compiled.as_text()) == (2, 0, 2)
    assert _needed_bytes(compiled) < 4 << 30


def _needed_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes +
            m.temp_size_in_bytes - m.alias_size_in_bytes)


def _gated_delta_calls(text):
    return _kernel_calls(text, "gated_delta_inv", "gated_delta_fwd",
                         "gated_delta_bwd")


@pytest.mark.parametrize("kind,spec,flash,rule", [
    ("linear", QWEN3_NEXT_LINEAR, 0, (2, 2, 2)),
    ("full", QWEN3_NEXT_FULL, 2, (0, 0, 0))])
def test_qwen3_next_layers_compile(one_chip, monkeypatch, kind, spec,
                                   flash, rule):
    """The cell's two kinds of spec-built layer, two of each under the
    layers' checkpoint, forward + backward at 1 x 8,192 tokens of
    2,048.  The linear layer's gated delta rule is the Pallas kernels
    of ``ops/pallas_gated_delta.py``: one inverse, one forward sweep
    and one reverse sweep a layer — the checkpoint kept what the two
    forward kernels produced, so the recompute holds neither — and no
    scan for ``programs.linear_scan`` to find; the program needs no
    more bytes than XLA's form of the rule (a scan of 8,192 / 64 = 128
    steps in chunks of 64) at the same shapes.  The full layer is the
    flash kernels' first call at a head of 256: one forward and one
    fused backward call a layer over the whole sequence, its forward
    kept."""
    from veles_tpu.observability import programs
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import linear_attention as L
    from veles_tpu.znicz import attention as Z
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    B, S, H, _ = QWEN3_NEXT
    spec = Z.layer_spec(n_heads=H, ffn_dim=256, **spec)
    params = [{name: _struct(shape, jnp.float32, one_chip)
               for name, shape in
               Z.layer_param_shapes(spec, 2048).items()}] * 2

    def compiled(on_tpu):
        # traced anew each time: the path is chosen at trace time
        monkeypatch.setattr(L, "tpu_available", lambda: on_tpu)
        layer = Z.checkpointed(lambda p, h: Z.layer_apply(
            spec, p, h, jnp.bfloat16)[0])

        def loss(params, x):
            for p in params:
                x = layer(p, x)
            return (x * x).sum()

        return jax.jit(jax.value_and_grad(loss)).lower(
            params, _struct((B, S, 2048), jnp.float32, one_chip)
        ).compile()

    program = compiled(True)
    text = program.as_text()
    assert _flash_calls(text) == (flash, 0, flash)
    assert _gated_delta_calls(text) == rule
    assert programs.linear_scan(text) is None
    if kind == "linear":
        xla = compiled(False)
        assert _gated_delta_calls(xla.as_text()) == (0, 0, 0)
        assert programs.linear_scan(xla.as_text()) == (128, 64)
        assert _needed_bytes(program) <= _needed_bytes(xla)


def _shortconv_calls(text):
    return _kernel_calls(text, "shortconv_fwd", "shortconv_bwd")


#: ``lfm2-24b-a2b.train``'s short-convolution layer: 8 x 2048 tokens of
#: 2,048, three taps behind and before their gates.
LFM2_CONV = dict(norm="rms", bias=False, operator="shortconv",
                 conv_kernel=3, ffn="gated-mlp")


@pytest.mark.parametrize("kind,spec,shape,calls", [
    ("qwen3-next linear", QWEN3_NEXT_LINEAR, (1, 8192, 16), (4, 2)),
    ("lfm2 gated", LFM2_CONV, (8, 2048, 32), (0, 0))])
def test_the_operators_taps_compile_as_the_shortconv_kernels(
        one_chip, monkeypatch, kind, spec, shape, calls):
    """Two spec-built layers under the layers' checkpoint, forward +
    backward at a cell's size (1 x 8,192 tokens of 2,048 at
    ``qwen3-next.train-8k``: q, k and v are C = 8,192 channels, K = 4).
    The gated delta operator's taps and SiLU are the Pallas kernels of
    ``ops/pallas_shortconv.py``: ``shortconv_fwd`` twice a layer (the
    forward and the recompute) and ``shortconv_bwd`` once, reading the
    whole ``[q | k | v | z]`` projection in place, and the program
    needs fewer bytes than with XLA's taps.  LFM2's gated short
    convolution keeps XLA's form: no kernel."""
    from veles_tpu.ops import attention as A
    from veles_tpu.ops import linear_attention as L
    from veles_tpu.ops import shortconv as SC
    from veles_tpu.znicz import attention as Z
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    monkeypatch.setattr(L, "tpu_available", lambda: True)
    B, S, H = shape
    spec = Z.layer_spec(n_heads=H, ffn_dim=256, **spec)
    params = [{name: _struct(shape, jnp.float32, one_chip)
               for name, shape in
               Z.layer_param_shapes(spec, 2048).items()}] * 2

    def compiled(on_tpu):
        monkeypatch.setattr(SC, "tpu_available", lambda: on_tpu)
        layer = Z.checkpointed(lambda p, h: Z.layer_apply(
            spec, p, h, jnp.bfloat16)[0])

        def loss(params, x):
            for p in params:
                x = layer(p, x)
            return (x * x).sum()

        return jax.jit(jax.value_and_grad(loss)).lower(
            params, _struct((B, S, 2048), jnp.float32, one_chip)
        ).compile()

    program = compiled(True)
    text = program.as_text()
    assert _shortconv_calls(text) == calls
    if calls[0]:
        # the kernel's operand is the projection, not a copy of its slice
        assert re.search(r"shortconv_fwd[.\d]* = [^\n]*operand_layout_"
                         r"constraints=\{bf16\[1,8192,12288\]", text)
        xla = compiled(False)
        assert _shortconv_calls(xla.as_text()) == (0, 0)
        assert _needed_bytes(program) < _needed_bytes(xla)


#: The MoE cells' expert shares: tokens a tick, width, experts'
#: width, routed experts, top k, how many are held here, what
#: ``dropless_rows`` compiles the common path for, whether a norm
#: reads the share's result inside the layer (the sandwich's), and the
#: rest of the call.
EXPERT_SHARES = {
    "lfm2": dict(T=16384, D=2048, F=1536, E=64, k=4, held=8,
                 rows=(10240, 7), post_norm=False, call={}),
    "trinity": dict(T=8192, D=2048, F=1024, E=128, k=8, held=16,
                    rows=(16384, 4), post_norm=True,
                    call=dict(scaling=2.826, eps=1e-20, slack=(2, 1))),
    "qwen3-next": dict(T=8192, D=2048, F=512, E=512, k=10, held=32,
                       rows=(10240, 8), post_norm=False,
                       call=dict(eps=0.0, slack=(2, 1),
                                 score="softmax"))}
#: ``gmm`` : ``tgmm`` calls of an expert share's forward + gradients.
#: With no checkpoint: the common path 3 + 3 (``dlhs``) and 3
#: ``tgmm``, the walk 3 + its inner checkpoint's 3 + 3 and 3.  Under
#: a bare ``jax.checkpoint`` the recompute adds the common path's 3
#: and, where a norm inside the layer reads the share's result, the
#: walk's 3 (lfm2 adds the result to the stream and no more: its
#: recompute's walk is empty).  The layers' checkpoint keeps the two
#: products before the gate: two ``gmm`` fewer.
MEGABLOX_CALLS = {
    "lfm2": {"none": (15, 6), "checkpointed": (16, 6), "bare": (18, 6)},
    "trinity": {"none": (15, 6), "checkpointed": (19, 6),
                "bare": (21, 6)},
    "qwen3-next": {"checkpointed": (16, 6), "bare": (18, 6)}}


def _expert_share(cell, wrap, sharding):
    """The loss of a cell's expert share (``moe_dropless``, and the
    norm behind it where the cell's layers have one) under ``wrap``,
    and the shapes to lower it from."""
    from veles_tpu.ops import moe as M
    c = EXPERT_SHARES[cell]
    T, D, F, E, k, held = (c[n] for n in ("T", "D", "F", "E", "k",
                                          "held"))
    assert M.dropless_rows(T, k, E, held,
                           c["call"].get("slack", (5, 4))) == c["rows"]
    @_wrap(wrap)
    def share(x, gate, bias, w1, w3, w2):
        y, stats = M.moe_dropless(x, gate, bias, w1, w3, w2, top_k=k,
                                  held=(0, held), **c["call"])
        if c["post_norm"]:
            y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + 1e-5)
        return y, stats["landed"]

    def loss(*operands):
        y, landed = share(*operands)
        return (y * y).sum() + landed

    f32 = jnp.float32
    return loss, (
        _struct((T, D), f32, sharding), _struct((D, E), f32, sharding),
        _struct((E,), f32, sharding), _struct((held, D, F), f32, sharding),
        _struct((held, D, F), f32, sharding),
        _struct((held, F, D), f32, sharding))


def _expert_share_text(cell, wrap, sharding):
    loss, structs = _expert_share(cell, wrap, sharding)
    return _compiled_text(jax.grad(loss, argnums=(0, 1, 3, 4, 5)),
                          *structs)


@pytest.mark.parametrize("wrap", ["none", "checkpointed", "bare"])
def test_trinity_expert_share_compiles(one_chip, monkeypatch, wrap):
    """``trinity-mini.train-8k``'s routed experts, forward and
    gradients: 8,192 tokens, top 8 of 128, 16 experts of 2048 x 1024
    held — ``lfm2-24b-a2b.train``'s even share, the common path
    compiled for twice it (``trinity_layers``' ``slack``: 16,384
    rows, 4 chunks).  A (1024, 1024) tile of the
    weights' gradient does not fit VMEM; ``grouped_dot`` keeps the
    tile's elements at 1024 x 768 (``TILE_ELEMENTS``).  Under the
    layers' checkpoint the share holds two ``gmm`` fewer than under a
    bare one (:data:`MEGABLOX_CALLS`)."""
    from veles_tpu.ops import moe as M
    monkeypatch.setattr(M, "tpu_available", lambda: True)
    assert M.dropless_rows(8192, 8, 128, 16) == (10240, 7)
    text = _expert_share_text("trinity", wrap, one_chip)
    assert "conditional" in text
    assert _megablox_calls(text) == MEGABLOX_CALLS["trinity"][wrap]


@pytest.mark.parametrize("wrap", ["checkpointed", "bare"])
def test_qwen3_next_expert_share_compiles(one_chip, monkeypatch, wrap):
    """``qwen3-next.train-8k``'s routed experts, forward and
    gradients: 8,192 tokens, a softmax over 512, top 10, 32 experts of
    2048 x 512 held — groups of about 160 rows, the many-small-experts
    end of the megablox tiling: (512, 1024, 512) and (512, 512, 1024)
    tiles.  81,920 assignments are sorted; the common path is compiled
    for twice the even share (10,240 rows, 8 chunks).  The kept values
    hold under the softmax router as under the sigmoid one: two
    ``gmm`` fewer than under a bare checkpoint."""
    from veles_tpu.ops import moe as M
    monkeypatch.setattr(M, "tpu_available", lambda: True)
    assert M.dropless_rows(8192, 10, 512, 32) == (6656, 13)
    text = _expert_share_text("qwen3-next", wrap, one_chip)
    assert "conditional" in text
    assert _megablox_calls(text) == MEGABLOX_CALLS["qwen3-next"][wrap]


@pytest.mark.parametrize("rows,wrap", [
    (10240, "none"), (65536, "none"), (65536, "checkpointed"),
    (65536, "bare")],
    ids=["common path", "every chunk", "every chunk, checkpointed",
         "every chunk, bare"])
def test_dropless_expert_share_compiles(one_chip, monkeypatch, rows,
                                        wrap):
    """``lfm2-24b-a2b.train``'s expert layer, forward and gradients:
    16,384 tokens, top 4 of 64, 8 experts of 2048 x 1536 held.  On a
    TPU the grouped products are the megablox kernels (the selection
    is by platform: steered here, the process sees a CPU); their
    (512, 1024, 768 | 1024) tiles must fit VMEM.  ``rows``: the
    grouped product alone at the common path's 10,240 rows, and the
    whole layer, which holds the walk over all 65,536 — with no
    checkpoint, under the layers' and under a bare one
    (:data:`MEGABLOX_CALLS`)."""
    from veles_tpu.ops import moe as M
    monkeypatch.setattr(M, "tpu_available", lambda: True)
    if rows == 10240:
        c = EXPERT_SHARES["lfm2"]
        bf16 = jnp.bfloat16

        def loss(lhs, rhs, sizes):
            return M.grouped_dot(lhs, rhs, sizes).sum()
        text = _compiled_text(
            jax.grad(loss, argnums=(0, 1)),
            _struct((rows, c["D"]), bf16, one_chip),
            _struct((c["held"], c["D"], c["F"]), bf16, one_chip),
            _struct((c["held"],), jnp.int32, one_chip))
        # the forward product is dead under a sum: dlhs (gmm), drhs (tgmm)
        assert text.count("tpu_custom_call") >= 2
        return
    text = _expert_share_text("lfm2", wrap, one_chip)
    assert "conditional" in text
    assert _megablox_calls(text) == MEGABLOX_CALLS["lfm2"][wrap]


@pytest.mark.parametrize("cell,kept_bytes", [
    ("lfm2", 172777504), ("trinity", 206307392)])
def test_kept_bytes_of_a_checkpointed_expert_share(one_chip,
                                                   monkeypatch, cell,
                                                   kept_bytes):
    """What the layers' checkpoint keeps of a cell's expert share,
    beside a bare ``jax.checkpoint``'s residuals: the scores (T, E)
    float32, choice, order and sizes as int32, the chosen scores
    (T, k) float32, the gathered rows (chunk, D) bfloat16 and — twice
    — a product (chunk, F) float32: 172.8 MB a layer at
    ``lfm2-24b-a2b.train`` (4.2 + 0.8 + 41.9 + 2 x 62.9), 206.3 at
    ``trinity-mini.train-8k`` (4.2 + 0.8 + 67.1 + 2 x 67.1);
    docs/moe.md."""
    from jax._src.ad_checkpoint import saved_residuals
    from veles_tpu.ops import moe as M
    monkeypatch.setattr(M, "tpu_available", lambda: True)
    c = EXPERT_SHARES[cell]

    def stored(wrap):
        loss, structs = _expert_share(cell, wrap, one_chip)
        return {(tuple(a.shape), jnp.dtype(a.dtype))
                for a, why in saved_residuals(loss, *structs)}

    kept = stored("checkpointed") - stored("bare")
    product = ((c["rows"][0], c["F"]), jnp.dtype("float32"))
    assert product in kept and len(kept) == 7
    assert sum(jnp.dtype(dtype).itemsize * math.prod(shape)
               for shape, dtype in list(kept) + [product]) == kept_bytes


@pytest.mark.parametrize("grad", [False, True],
                         ids=["fwd", "fwd+bwd"])
def test_flash_chunk_compiles_at_ring_shard(one_chip, grad):
    """One ring step: local queries against one streamed k/v shard,
    global causal offsets arriving as TRACED scalars; its gradient the
    fused backward."""
    from veles_tpu.ops import pallas_attention as PA
    shape = (LM[0], RING_SHARD) + LM[2:]

    def chunk(q, k, v, q_off, k_off):
        out, lse = PA.flash_chunk(q, k, v, causal=True,
                                  q_offset=q_off, k_offset=k_off,
                                  operand_dtype=jnp.float32)
        return out.sum() + lse.sum()

    fn = jax.grad(chunk, argnums=(0, 1, 2)) if grad else chunk
    x = _struct(shape, jnp.float32, one_chip)
    off = _struct((), jnp.float32, one_chip)
    assert _flash_calls(_compiled_text(fn, x, x, x, off, off)) == \
        ((1, 0, 1) if grad else (1, 0, 0))


@pytest.mark.parametrize("pool", ["float32", "int8+scales"])
def test_decode_attention_compiles(one_chip, pool):
    """The serving kernel at decode shape: f32 pool, and the int8
    pool whose per-position scales are dequantized in the kernel."""
    from veles_tpu.ops import pallas_attention as PA
    B, _, H, D = LM
    q = _struct((B, 1, H, D), jnp.float32, one_chip)
    mask = _struct((B, 1, DECODE_L), jnp.bool_, one_chip)
    quant = pool != "float32"
    kv = _struct((B, DECODE_L, H, D),
                 jnp.int8 if quant else jnp.float32, one_chip)
    args = [q, kv, kv, mask]
    if quant:
        scale = _struct((B, DECODE_L, H), jnp.float32, one_chip)
        args += [scale, scale]

    def decode(q, k, v, mask, k_scale=None, v_scale=None):
        return PA.pallas_decode_attention(
            q, k, v, mask, operand_dtype=jnp.float32,
            k_scale=k_scale, v_scale=v_scale)

    assert "tpu_custom_call" in _compiled_text(decode, *args)


def test_lrn_compiles(one_chip):
    """AlexNet's first LRN at the bench batch, forward + backward."""
    from veles_tpu.ops.pallas_lrn import lrn_pallas
    x = _struct((512, 27, 27, 96), jnp.bfloat16, one_chip)

    def loss(x):
        # Squared, so the cotangent needs the forward's output and
        # both kernels stay in the program.
        y = lrn_pallas(x, 5, 1e-4, 0.75, 2.0).astype(jnp.float32)
        return (y * y).sum()

    assert _compiled_text(jax.grad(loss), x).count(
        "tpu_custom_call") >= 2


def test_flash_attention_partitions_over_a_dp_mesh(topo, monkeypatch):
    """GSPMD cannot partition a Mosaic call, so under a mesh the
    kernel must arrive wrapped in shard_map
    (``ops.attention.mesh_attention``): forward + backward for FOUR
    described chips, batch split over ``data``, kernel in the text.
    The platform check is steered here, in the test — the process
    runs on the CPU and would select the XLA path."""
    import numpy
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from veles_tpu.ops import attention as A
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    mesh = Mesh(numpy.array(topo.devices[:4]), ("data",))
    x = _struct(LM, jnp.float32,
                NamedSharding(mesh, P("data", None, None, None)))

    def loss(q, k, v):
        return A.mesh_attention(q, k, v, mesh, causal=True,
                                batch_axis="data").sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert _flash_calls(text) == (1, 0, 1)


def test_grouped_query_layer_partitions_over_a_dp_mesh(topo,
                                                       monkeypatch):
    """A spec-built layer — ``lfm2-24b-a2b.train``'s attention: 32
    query heads of 64 over 8 key/value heads, per-head norm, rotary
    positions — forward + backward through ``LMLayer``'s own
    placement for FOUR described chips, batch over ``data``: the unit
    hands ``layer_apply`` an ``attend`` that wraps the kernels in
    ``shard_map``, so the partitioned program compiles and holds
    them — one call of each kernel, under the unit's checkpoint.  (A
    unit that passes no ``attend`` traces a bare Mosaic
    call into the GSPMD program, which the compiler refuses.)"""
    import numpy
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.ops import attention as A
    from veles_tpu.znicz import attention as Z
    monkeypatch.setattr(A, "tpu_available", lambda: True)
    B, S, H, D = LFM2
    spec = Z.layer_spec(norm="rms", n_heads=H, kv_heads=8,
                        qk_norm=True, rope_theta=1e6, bias=False,
                        ffn="gated-mlp", ffn_dim=256)
    mesh = Mesh(numpy.array(topo.devices[:4]), ("data",))
    wf = DummyWorkflow()
    wf.mesh = mesh
    layer = Z.LMLayer(wf, spec=spec, remat=True)
    replicated = NamedSharding(mesh, P())
    params = {name: _struct(shape, jnp.float32, replicated)
              for name, shape in
              Z.layer_param_shapes(spec, H * D).items()}
    x = _struct((B, S, H * D), jnp.float32,
                NamedSharding(mesh, P("data", None, None)))

    def loss(p, x):
        out = []
        layer.tforward(lambda vec: x, lambda vec, val: out.append(val),
                       p, None)
        return (out[0] * out[0]).sum()

    text = _compiled_text(jax.value_and_grad(loss), params, x)
    # The names are given inside ``shard_map``: the unit's checkpoint
    # keeps each shard's forward output, so the recompute holds the
    # fused backward and no second forward.
    assert _flash_calls(text) == (1, 0, 1)
