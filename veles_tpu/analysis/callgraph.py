"""Trace-hazard pass (VL101/VL102): a call-graph walk from the known
jit entry points, flagging host-sync and retrace-nondeterminism calls
in everything the tracer can reach.

Entry points are discovered, not configured per-file:

* any function passed to a JAX tracing transform (``jax.jit``,
  ``jax.pmap``, ``jax.vmap``, ``jax.grad``, ``jax.value_and_grad``,
  ``jax.checkpoint``/``remat``, ``lax.scan``/``cond``/``while_loop``/
  ``fori_loop``/``switch``) — this is how ``StepCompiler.compile``'s
  ``train_step``/``infer_step``/``block_step`` and every
  ``export.py`` decode program register themselves;
* the project's traced-method conventions: every ``tforward`` /
  ``tupdate`` method (called inside the fused step's trace by
  ``StepCompiler.run_forward``/``apply_updates``) and every
  ``update`` method on an ``Optimizer`` subclass (called from
  ``tupdate`` through the registry).

From those roots the walk follows calls it can resolve statically:
local/nested functions, module-level functions, ``self.method`` (with
project-wide base-class resolution), imported-module attributes, and
single-assignment local aliases (``sample = _sample_rows``).  Code
inside a reached function but lexically inside a NESTED def is only
scanned once that nested def is itself reached — host-side builder
functions that merely *define* jitted closures stay host code.
"""

import ast

from .core import Finding

#: Transform attributes whose function arguments get traced.
TRACERS = frozenset((
    "jit", "pmap", "vmap", "grad", "value_and_grad", "checkpoint",
    "remat", "custom_jvp", "custom_vjp", "shard_map",
))
#: lax control-flow: every callable argument is traced.
LAX_TRACERS = frozenset((
    "scan", "cond", "while_loop", "fori_loop", "switch", "map",
    "associative_scan",
))
#: Method names the fused step calls inside its trace, and the base
#: class gating them (None = any class).
TRACED_METHODS = (("tforward", None), ("tupdate", None),
                  ("update", "Optimizer"))

#: VL101: modules whose array-materializing calls force a device→host
#: sync (or break the trace) when reached from traced code.
_NUMPY_SYNC_ATTRS = frozenset(("asarray", "array", "copyto",
                               "ascontiguousarray"))
#: VL102 hazards: attribute calls keyed by resolved module name.
_NONDET = {
    "time": frozenset(("time", "time_ns", "monotonic",
                       "monotonic_ns", "perf_counter",
                       "perf_counter_ns")),
    "os": frozenset(("urandom", "getpid")),
    "uuid": frozenset(("uuid1", "uuid4", "getnode")),
}


class FuncInfo(object):
    __slots__ = ("node", "sf", "qualname", "parent", "cls",
                 "nested", "reached_from")

    def __init__(self, node, sf, qualname, parent, cls):
        self.node = node
        self.sf = sf
        self.qualname = qualname
        self.parent = parent    # enclosing FuncInfo or None
        self.cls = cls          # owning ClassInfo or None
        self.nested = {}        # name -> FuncInfo defined directly in
        self.reached_from = None


class ClassInfo(object):
    __slots__ = ("node", "sf", "name", "methods", "bases")

    def __init__(self, node, sf):
        self.node = node
        self.sf = sf
        self.name = node.name
        self.methods = {}
        self.bases = []
        for base in node.bases:
            if isinstance(base, ast.Name):
                self.bases.append(base.id)
            elif isinstance(base, ast.Attribute):
                self.bases.append(base.attr)


class ModuleIndex(object):
    """Per-file symbol tables: functions, classes, imports."""

    def __init__(self, sf, project):
        self.sf = sf
        self.project = project
        self.functions = {}      # module-level name -> FuncInfo
        self.classes = {}        # name -> ClassInfo
        self.import_mods = {}    # alias -> dotted module
        self.from_imports = {}   # name -> (dotted module, attr)
        self.all_funcs = []
        self._index_body(sf.tree.body, parent=None, cls=None,
                         prefix=sf.modname)

    def _index_body(self, body, parent, cls, prefix):
        for node in body:
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                qual = "%s.%s" % (prefix, node.name)
                info = FuncInfo(node, self.sf, qual, parent, cls)
                self.all_funcs.append(info)
                if parent is not None:
                    parent.nested[node.name] = info
                elif cls is not None:
                    cls.methods[node.name] = info
                else:
                    self.functions[node.name] = info
                self._index_body(node.body, parent=info, cls=cls,
                                 prefix=qual)
            elif isinstance(node, ast.ClassDef):
                cinfo = ClassInfo(node, self.sf)
                self.classes[node.name] = cinfo
                self._index_body(node.body, parent=None, cls=cinfo,
                                 prefix="%s.%s" % (prefix, node.name))
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    self.import_mods[alias.asname or
                                     alias.name.split(".")[0]] = \
                        alias.name
            elif isinstance(node, ast.ImportFrom):
                mod = self.project.resolve_relative(
                    self.sf, node.level, node.module)
                for alias in node.names:
                    self.from_imports[alias.asname or alias.name] = \
                        (mod, alias.name)
            elif isinstance(node, (ast.If, ast.Try, ast.With)):
                # Conditional imports / guarded defs still index.
                for sub in ast.iter_child_nodes(node):
                    if isinstance(sub, ast.stmt):
                        self._index_body([sub], parent, cls, prefix)
                    elif hasattr(sub, "body"):
                        self._index_body(sub.body, parent, cls,
                                         prefix)


def _own_statements(fn_node):
    """The function's own AST nodes, stopping at nested function /
    class definitions (their bodies are separate walk subjects)."""
    out = []
    stack = list(fn_node.body)
    while stack:
        node = stack.pop()
        out.append(node)
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef, ast.Lambda,
                                  ast.ClassDef)):
                continue
            stack.append(child)
    return out


class TraceWalker(object):
    def __init__(self, project):
        self.project = project
        self.modules = {}
        for sf in project.files:
            self.modules[sf.modname] = ModuleIndex(sf, project)
        # Global class index (by bare name) for base-class method
        # resolution across modules.
        self.class_index = {}
        for idx in self.modules.values():
            for cinfo in idx.classes.values():
                self.class_index.setdefault(cinfo.name, cinfo)

    # -- resolution --------------------------------------------------------

    def _local_aliases(self, info):
        """Single-target ``name = resolvable`` aliases in the
        function's own body."""
        aliases = {}
        for node in _own_statements(info.node):
            if isinstance(node, ast.Assign) and \
                    len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                aliases[node.targets[0].id] = node.value
        return aliases

    def resolve_call(self, func, info, idx, aliases, depth=0):
        """FuncInfo a call expression statically resolves to, or
        None."""
        if depth > 3:
            return None
        if isinstance(func, ast.Name):
            name = func.id
            # scope chain: nested defs of enclosing functions
            cur = info
            while cur is not None:
                if name in cur.nested:
                    return cur.nested[name]
                cur = cur.parent
            if name in aliases:
                target = aliases[name]
                if isinstance(target, (ast.Name, ast.Attribute)):
                    return self.resolve_call(target, info, idx,
                                             {}, depth + 1)
                return None
            if name in idx.functions:
                return idx.functions[name]
            if name in idx.from_imports:
                mod, attr = idx.from_imports[name]
                other = self.modules.get(mod)
                if other is not None:
                    return other.functions.get(attr)
            return None
        if isinstance(func, ast.Attribute):
            value = func.value
            if isinstance(value, ast.Name) and value.id in ("self",
                                                            "cls"):
                return self._resolve_method(info.cls, func.attr)
            if isinstance(value, ast.Name):
                mod = idx.import_mods.get(value.id)
                if mod is None and value.id in idx.from_imports:
                    fmod, fattr = idx.from_imports[value.id]
                    # ``from . import export`` style module import.
                    mod = ("%s.%s" % (fmod, fattr)) if fmod else fattr
                if mod is not None:
                    other = self.modules.get(mod)
                    if other is not None:
                        fn = other.functions.get(func.attr)
                        if fn is not None:
                            return fn
                        # Class-level staticmethod reference.
                        cinfo = other.classes.get(func.attr)
                        _ = cinfo
            return None
        return None

    def _is_partial_call(self, call, idx):
        """``functools.partial(fn, ...)`` in either import form —
        the wrapper ops/attention's sequence-parallel dispatch hands
        to ``shard_map`` (the ring/ulysses bodies register through
        it, ISSUE 13)."""
        func = call.func
        if isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name):
            return idx.import_mods.get(func.value.id) == \
                "functools" and func.attr == "partial"
        if isinstance(func, ast.Name):
            return idx.from_imports.get(func.id) == \
                ("functools", "partial")
        return False

    def _tracer_arg_targets(self, arg, info, idx, aliases, depth=0):
        """Every FuncInfo a tracer-call argument may statically
        denote.  Beyond plain names/attributes this unwraps
        ``functools.partial(fn, ...)`` (yielding fn's targets) and
        follows single-assignment aliases through DICT-LITERAL
        subscripts (``modes = {"ring": ring_attention, ...};
        inner = modes[mode]`` — the sequence-parallel dispatch
        table: every value is a potential entry, so ALL are
        yielded)."""
        if depth > 6:
            # partial(name) → alias → subscript → alias → dict is a
            # 5-hop chain; 6 bounds pathological self-references.
            return []
        if isinstance(arg, (ast.Name, ast.Attribute)):
            targets = []
            target = self.resolve_call(arg, info, idx, aliases)
            if target is not None:
                targets.append(target)
            if isinstance(arg, ast.Name) and arg.id in aliases:
                value = aliases[arg.id]
                if not isinstance(value, (ast.Name, ast.Attribute)):
                    targets.extend(self._tracer_arg_targets(
                        value, info, idx, aliases, depth + 1))
            return targets
        if isinstance(arg, ast.Call) and \
                self._is_partial_call(arg, idx):
            if arg.args:
                return self._tracer_arg_targets(
                    arg.args[0], info, idx, aliases, depth + 1)
            return []
        if isinstance(arg, ast.Subscript):
            return self._tracer_arg_targets(
                arg.value, info, idx, aliases, depth + 1)
        if isinstance(arg, ast.Dict):
            out = []
            for value in arg.values:
                if isinstance(value, (ast.Name, ast.Attribute)):
                    target = self.resolve_call(value, info, idx,
                                               aliases)
                    if target is not None:
                        out.append(target)
            return out
        return []

    def _resolve_method(self, cls, name, seen=None):
        if cls is None:
            return None
        seen = seen or set()
        if cls.name in seen:
            return None
        seen.add(cls.name)
        if name in cls.methods:
            return cls.methods[name]
        for base in cls.bases:
            binfo = self.class_index.get(base)
            if binfo is not None:
                found = self._resolve_method(binfo, name, seen)
                if found is not None:
                    return found
        return None

    # -- entry discovery ---------------------------------------------------

    def _is_tracer_call(self, call, idx):
        """True when ``call`` is a JAX tracing transform whose
        function arguments become traced."""
        func = call.func
        if isinstance(func, ast.Attribute):
            base = func.value
            if isinstance(base, ast.Name):
                mod = idx.import_mods.get(base.id)
                if mod == "jax" and func.attr in TRACERS:
                    return True
                if base.id == "lax" and func.attr in LAX_TRACERS:
                    return True
                if mod in ("jax.lax",) and func.attr in LAX_TRACERS:
                    return True
            if isinstance(base, ast.Attribute) and \
                    base.attr == "lax" and func.attr in LAX_TRACERS:
                return True
        elif isinstance(func, ast.Name):
            fi = idx.from_imports.get(func.id)
            if fi is not None:
                mod, attr = fi
                if mod == "jax" and attr in TRACERS:
                    return True
                if mod in ("jax.lax", "jax") and attr in LAX_TRACERS:
                    return True
        return False

    def entries(self):
        out = []
        for modname, idx in self.modules.items():
            for info in idx.all_funcs:
                name = info.node.name
                for mname, base in TRACED_METHODS:
                    if name != mname or info.cls is None:
                        continue
                    if base is None or base in info.cls.bases or \
                            info.cls.name == base:
                        out.append(info)
                        break
            for info in idx.all_funcs:
                aliases = self._local_aliases(info)
                for node in _own_statements(info.node):
                    if not isinstance(node, ast.Call) or \
                            not self._is_tracer_call(node, idx):
                        continue
                    for arg in node.args:
                        out.extend(self._tracer_arg_targets(
                            arg, info, idx, aliases))
            # Module-level tracer calls (decorator-style jit at
            # import time: ``fn = jax.jit(fn)`` or ``@jax.jit``).
            for info in idx.all_funcs:
                for deco in info.node.decorator_list:
                    call = deco if isinstance(deco, ast.Call) \
                        else None
                    target = deco.func if call is not None else deco
                    if isinstance(target, ast.Attribute) and \
                            isinstance(target.value, ast.Name) and \
                            idx.import_mods.get(target.value.id) == \
                            "jax" and target.attr in TRACERS:
                        out.append(info)
        return out

    # -- reachability + hazard scan ----------------------------------------

    def walk(self):
        reached = {}
        queue = []
        for info in self.entries():
            if id(info.node) not in reached:
                reached[id(info.node)] = info
                info.reached_from = info.qualname
                queue.append(info)
        while queue:
            info = queue.pop()
            idx = self.modules[info.sf.modname]
            aliases = self._local_aliases(info)
            for node in _own_statements(info.node):
                callees = []
                if isinstance(node, ast.Call):
                    callee = self.resolve_call(node.func, info, idx,
                                               aliases)
                    if callee is not None:
                        callees.append(callee)
                    if self._is_tracer_call(node, idx):
                        for a in node.args:
                            callees.extend(self._tracer_arg_targets(
                                a, info, idx, aliases))
                for callee in callees:
                    if id(callee.node) not in reached:
                        reached[id(callee.node)] = callee
                        callee.reached_from = info.reached_from
                        queue.append(callee)
        return list(reached.values())

    def hazards(self, info):
        idx = self.modules[info.sf.modname]
        sf = info.sf
        out = []

        def emit(rule, node, what):
            out.append(Finding(
                sf.rel, node.lineno, rule,
                "%s inside jit-traced code (reachable from %s)" %
                (what, info.reached_from)))

        for node in _own_statements(info.node):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute):
                recv = func.value
                # .item() on anything: a device sync by definition.
                if func.attr == "item" and not node.args:
                    emit("VL101", node, "`.item()` host sync")
                    continue
                if isinstance(recv, ast.Name):
                    mod = idx.import_mods.get(recv.id)
                    if mod == "numpy" and \
                            func.attr in _NUMPY_SYNC_ATTRS:
                        emit("VL101", node,
                             "`%s.%s` materializes on host" %
                             (recv.id, func.attr))
                        continue
                    if mod == "jax" and func.attr == "device_get":
                        emit("VL101", node,
                             "`jax.device_get` host sync")
                        continue
                    if mod in _NONDET and \
                            func.attr in _NONDET[mod]:
                        emit("VL102", node,
                             "`%s.%s()` is retrace-nondeterministic" %
                             (mod, func.attr))
                        continue
                    if mod == "random":
                        emit("VL102", node,
                             "stdlib `random.%s` draws hidden "
                             "global state" % func.attr)
                        continue
                # numpy.random.* / np.random.*
                if isinstance(recv, ast.Attribute) and \
                        recv.attr == "random" and \
                        isinstance(recv.value, ast.Name) and \
                        idx.import_mods.get(recv.value.id) == \
                        "numpy":
                    emit("VL102", node,
                         "`numpy.random.%s` draws host-side state" %
                         func.attr)
                    continue
            elif isinstance(func, ast.Name):
                fi = idx.from_imports.get(func.id)
                if fi == ("jax", "device_get"):
                    emit("VL101", node, "`device_get` host sync")
                    continue
                if func.id in ("float", "int") and \
                        len(node.args) == 1 and not isinstance(
                            node.args[0], ast.Constant):
                    emit("VL101", node,
                         "`%s()` on a traced value forces a host "
                         "sync / concretization" % func.id)
                    continue
        return out


def run(project):
    walker = TraceWalker(project)
    findings = []
    for info in walker.walk():
        findings.extend(walker.hazards(info))
    return findings
