"""What a compiled step program says about its own instructions.

A device trace names an operation by its HLO instruction
(``%fusion.1300 = ...``) and drops the instruction's ``metadata``.
The program put a name there: ``StepCompiler`` traces every unit
under ``jax.named_scope(unit.scope_name)``, the update rules under
``update``, the health sentinel under ``health``, and the block
function opens ``ln1`` / ``attention`` / ``ln2`` / ``mlp`` (and
``rope``, ``shortconv``, ``moe_*``, ``attn_gate``, ``ln1_post`` /
``ln2_post``, ``gdn_gate``, ``gated_delta``, ``gdn_norm`` where a
spec asks for them) inside
a unit; JAX adds ``jvp(...)``, ``transpose(jvp(...))`` and the
checkpoint's ``rematted_computation`` by itself.  This module keeps,
per program name (``block_step``, ``train_step``, ``infer_step``),
what it takes to read that back::

    scopes("block_step") -> {"fusion.1300": ("backward", "block2",
                                             "attention"), ...}

so a reduction of the trace (``observability.profile``, the
benchmark's per-layer readers) can split device time by phase, unit
and inner scope.  Nothing is compiled or parsed until
:func:`scopes` is first called for a program; ``StepCompiler`` hands
over a thunk that lowers the program from its arguments' shapes
(once: the FLOP estimate shares it), so the answer is there after
the workflow has stopped and every device array is deleted.
"""

import re
import threading

from .metrics import registry

#: What an instruction's phase can be; ``None`` is "unscoped" (scan
#: bookkeeping, copies, an instruction without ``op_name``).
PHASES = ("forward", "recompute", "backward", "update")

#: Scopes the fused step opens around what belongs to no unit; both
#: are phase ``update``.
STEP_SCOPES = ("update", "health")

#: Scopes a unit may open inside its own (``znicz.attention.
#: layer_apply``, ``ops.moe.moe_dropless``).
INNER_SCOPES = ("ln1", "attention", "ln2", "mlp", "rope", "shortconv",
                "moe_route", "moe_dispatch", "moe_experts",
                "moe_combine", "attn_gate", "moe_shared", "ln1_post",
                "ln2_post", "gdn_gate", "gated_delta", "gdn_norm")

_lock = threading.Lock()
_programs = {}

_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|body|to_apply)=%?([\w.\-]+)")


class _Program(object):
    __slots__ = ("lower", "units", "ticks", "compiled_by", "table")

    def __init__(self, lower, units, ticks, compiled_by):
        self.lower, self.units, self.ticks = lower, units, ticks
        self.compiled_by = compiled_by
        self.table = None


def register(program, lower, units, ticks=1, compiled_by=None):
    """Called by ``StepCompiler`` once per compiled program: ``lower``
    is a zero-argument callable that returns the program's
    ``jax.stages.Lowered``, ``units`` the scope names of its traced
    units, ``compiled_by`` a token of the ``StepCompiler.compile()``
    the program came from.  A program of a newer compile (a recompile,
    another workflow of this process) takes the name over: the table
    is of what runs now.  Within one compile, of two programs under
    one name (a remainder block) the one with more ticks a dispatch
    is kept: it is the one a trace is full of."""
    with _lock:
        known = _programs.get(program)
        if known is None or known.compiled_by is not compiled_by \
                or ticks >= known.ticks:
            _programs[program] = _Program(lower, frozenset(units), ticks,
                                          compiled_by)


def registered():
    """Names of the programs :func:`scopes` can answer for."""
    with _lock:
        return sorted(_programs)


def reset():
    """Forgets every program (test isolation)."""
    with _lock:
        _programs.clear()


def classify(op_name, units):
    """``(phase, unit, inner scope)`` of one ``op_name`` path, each
    None where the path does not say.  ``units`` are the program's
    unit scope names."""
    unit = inner = None
    backward = recompute = False
    for part in op_name.split(";")[0].split("/"):
        match = _WRAPPED.match(part)
        while match:
            backward = backward or match.group(1) == "transpose"
            part = match.group(2)
            match = _WRAPPED.match(part)
        if part == "rematted_computation":
            recompute = True
        elif unit is None:
            if part in units or part in STEP_SCOPES:
                unit = part
        elif inner is None and part in INNER_SCOPES:
            inner = part
    if unit is None:
        return None, None, None
    if unit in STEP_SCOPES:
        return "update", unit, inner
    if not backward:
        return "forward", unit, inner
    return "recompute" if recompute else "backward", unit, inner


def parse_hlo(text, units):
    """The scope table of a compiled program's HLO text.  An
    instruction that carries no ``op_name`` of its own but calls a
    computation (a fusion) takes the commonest placing among that
    computation's instructions."""
    table, calls, members = {}, {}, {}
    computation = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name = found.group(1)
        members.setdefault(computation, []).append(name)
        op_name = _OP_NAME.search(line)
        table[name] = classify(op_name.group(1), units) if op_name \
            else (None, None, None)
        if table[name][0] is None:
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
    for name, called in calls.items():
        votes = {}
        for member in members.get(called, ()):
            if table[member][0] is not None:
                votes[table[member]] = votes.get(table[member], 0) + 1
        if votes:
            table[name] = max(votes, key=votes.get)
    return table


def kernel_calls(table, kernel):
    """How many instructions of a scope table are calls of the Pallas
    kernel ``kernel`` (``pallas_call(name=…)`` → ``flash_fwd.28``)."""
    return sum(1 for name in table
               if name.split(".")[0] == kernel)


_WHILE = re.compile(r" while\(.*condition=%?([\w.\-]+)")
_TRIPS = re.compile(r'known_trip_count[^0-9]*(\d+)')
_BOUND = re.compile(r" = s32\[\][^ ]* constant\((\d+)\)")
_CHUNK = re.compile(r"gated_delta\)?/chunk(\d+)[/\"]")


def linear_scan(text):
    """``(steps, chunk)`` of the gated delta rule's scan (XLA's form)
    in a compiled program's HLO text, or None where the program holds
    no such loop (no linear layer, or the Pallas kernels).
    ``chunk``: the rows of a chunk, which ``ops.linear_attention``
    writes into its scope (``gated_delta/chunk64``).  ``steps``: the
    trip count of a ``while`` under that scope (the longest, were they
    to differ) — its ``known_trip_count`` where the backend prints
    one, else the bound its condition compares the counter with."""
    bounds, loops, computation = {}, [], None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        bound = _BOUND.search(line)
        if bound:
            bounds.setdefault(computation, []).append(int(bound.group(1)))
        rows, loop = _CHUNK.search(line), _WHILE.search(line)
        if rows and loop:
            loops.append((int(rows.group(1)), loop.group(1),
                          _TRIPS.search(line)))
    steps = [int(trips.group(1)) if trips else max(bounds.get(cond, [0]))
             for _rows, cond, trips in loops]
    return (max(steps), loops[0][0]) if loops else None


#: A compiler option at its default.  ``Lowered.compile()`` hands
#: back the executable it already has, and the lowering is the one
#: the dispatch ran from; with an option it compiles anew.
_ANEW = {"xla_embed_ir_in_executable": False}


def _compiled_text(lowered):
    """The compiled HLO text of ``lowered``, with THIS build's
    ``op_name`` on its instructions: compiled anew, past the
    persistent cache.  JAX strips locations before it hashes a module
    for the cache's key, so the entry the dispatch ran from may be
    another build's (a parent commit's, one from before a scope was
    renamed) and carry that build's scopes.  A key that holds the
    metadata would hold the ``id()``-keyed argument names of the step
    too and never be found again, so nothing is looked up and nothing
    stored.  Same module, same compiler: the instruction names are
    the ones that ran."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    # JAX asks once a process whether the cache is used.
    compilation_cache.reset_cache()
    try:
        return lowered.compile(compiler_options=_ANEW).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def scopes(program):
    """``{instruction name: (phase, unit, inner scope)}`` for every
    instruction of the compiled ``program``, or None where no such
    program was dispatched.  Instruction names are as the device
    trace gives them, without the ``%``.  The first call compiles
    (:func:`_compiled_text`) and parses, and sets the gauges
    ``attention.flash.fwd_calls`` / ``.dq_calls`` and
    ``moe.gmm_calls`` / ``.tgmm_calls``, ``linear_attention.
    fwd_calls`` / ``.bwd_calls`` and ``linear_attention.scan_steps`` /
    ``.chunk`` (all labelled with the program's name) from that
    parse; later calls return the same table."""
    with _lock:
        entry = _programs.get(program)
    if entry is None:
        return None
    if entry.table is None:
        text = _compiled_text(entry.lower())
        entry.table = parse_hlo(text, entry.units)
        entry.lower = None
        # The flash pair equal: every checkpointed layer kept its
        # forward kernel's output (``znicz.attention.checkpointed``);
        # two to one: each recompute runs the kernel again.  The
        # megablox pair (``ops.moe.grouped_dot``; ``tgmm`` is the
        # weights' gradient, six an expert layer): two ``gmm`` a layer
        # fewer where the checkpoint kept the two products before the
        # gate (16 to 6 against 18 to 6; docs/observability.md).
        label = {"program": program}
        registry.gauge("attention.flash.fwd_calls", label).set(
            kernel_calls(entry.table, "flash_fwd"))
        registry.gauge("attention.flash.dq_calls", label).set(
            kernel_calls(entry.table, "flash_dq"))
        registry.gauge("moe.gmm_calls", label).set(
            kernel_calls(entry.table, "gmm"))
        registry.gauge("moe.tgmm_calls", label).set(
            kernel_calls(entry.table, "tgmm"))
        # The gated delta rule's kernels
        # (``ops/pallas_gated_delta.py``): equal — every checkpointed
        # layer kept what its forward sweep produced; two to one: each
        # recompute runs the sweep again; nought: XLA's form.
        registry.gauge("linear_attention.fwd_calls", label).set(
            kernel_calls(entry.table, "gated_delta_fwd"))
        registry.gauge("linear_attention.bwd_calls", label).set(
            kernel_calls(entry.table, "gated_delta_bwd"))
        # XLA's form of the rule, a chunked scan: sequence / chunk
        # dependent steps a layer (``ops/linear_attention.py``);
        # nought where the program holds no such loop — it carries no
        # such state, or the kernels carry it themselves.
        steps, chunk = linear_scan(text) or (0, 0)
        registry.gauge("linear_attention.scan_steps", label).set(steps)
        registry.gauge("linear_attention.chunk", label).set(chunk)
    return entry.table
