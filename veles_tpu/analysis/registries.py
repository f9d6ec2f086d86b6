"""Registry-contract pass (VL301/VL302).

**VL301 — names are literals.**  The docs-consistency gate
(tests/test_docs_consistency.py) proves every documented stat / span
/ chaos / metric name exists as a *source literal* — which only works
if call sites actually pass literals.  This pass closes the loop:
the name argument of ``stats.incr``, ``set_gauge``,
``observe_latency`` / ``observe_batch`` / ``observe_request``,
registry ``counter``/``gauge``/``histogram``, ``tracing.span`` /
``tracing.begin`` / ``tracing.annotated``, and injector
``check``/``tick`` must be a string
literal or a ``"prefix.%s" % …`` format with a literal left side.
A bare ``Name`` is accepted only when it is a parameter of the
enclosing function (the pass-through idiom: ``RetryPolicy.call(...,
stat=...)`` — its call sites pass literals and are themselves
checked) or a local assigned from a literal.

**VL302 — no silent broad excepts.**  A ``except Exception`` (or
bare ``except:``) handler must do at least one of: re-raise, call a
logging method (``self.exception``/``warning``/…, ``log.*``,
``logging.*``), count via ``stats.incr``, or USE the bound exception
object (storing it for a caller — ``req.error = e`` — propagates it;
dropping it swallows it).  Handlers in device-thread and server-loop
paths should log **and** count (see docs/analysis.md).
"""

import ast
import re

from .core import Finding

#: Dotted observability-name literals (``"net.bytes_sent"``,
#: ``"chaos.%s"``) — the docs-consistency gate's source-scan
#: pattern, owned here so the gate and the linter share ONE
#: definition of "registered literal".
DOTTED_LITERAL_RE = re.compile(
    r"""["']([a-z][a-z0-9_%]*(?:\.[a-z0-9_%]+)+)["']""")


def dotted_source_literals(project):
    """Every dotted string literal in the project's sources as
    ``(exact, wildcards)``: a set of exact names plus compiled
    regexes for ``%s``/``%d``-parameterized families.  This is
    tests/test_docs_consistency.py's scan, generalized into a
    reusable pass — documented stat/span/chaos names must resolve
    against it, and VL301 keeps call sites literal so the scan stays
    sound."""
    literals = set()
    for sf in project.files:
        literals.update(DOTTED_LITERAL_RE.findall(sf.text))
    exact = {lit for lit in literals if "%" not in lit}
    wildcards = [
        re.compile("^" + re.sub(r"%[sd]", r"[a-z0-9_.]+",
                                re.escape(lit).replace(
                                    r"\%s", "%s").replace(
                                    r"\%d", "%d")) + "$")
        for lit in literals if "%" in lit]
    return exact, wildcards

_NAME_SINKS = frozenset((
    "incr", "set_gauge", "observe_latency", "observe_batch",
    "observe_request", "counter", "gauge", "histogram", "span",
    "begin", "annotated", "check", "tick",
))

#: Receiver spellings that make an attribute call a registry sink.
_RECV_HINTS = ("stats", "registry", "tracing", "trace", "injector",
               "inj")

_LOG_METHODS = frozenset(("debug", "info", "warning", "warn",
                          "error", "exception", "critical", "log",
                          "print_exc"))


def _recv_text(expr):
    """Best-effort dotted text of a receiver expression."""
    parts = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
    elif isinstance(expr, ast.Call):
        func = expr.func
        if isinstance(func, ast.Attribute):
            parts.append(func.attr + "()")
        elif isinstance(func, ast.Name):
            parts.append(func.id + "()")
    return ".".join(reversed(parts))


def _is_sink(call):
    func = call.func
    if not isinstance(func, ast.Attribute) or \
            func.attr not in _NAME_SINKS:
        return False
    recv = _recv_text(func.value)
    last = recv.split(".")[-1] if recv else ""
    if func.attr in ("span", "begin", "annotated"):
        return last in ("tracing", "trace")
    if func.attr in ("check", "tick"):
        return ("injector" in recv or last in ("inj",) or
                "effective()" in recv)
    if func.attr in ("counter", "gauge", "histogram"):
        return "registry" in recv
    return "stats" in recv or last == "stats"


def _literal_ok(arg):
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return True
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Mod):
        return _literal_ok(arg.left)
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
        return _literal_ok(arg.left)
    return False


def _enclosing_scopes(tree):
    """Yields (function node, [statement nodes]) with parent links
    enough to know params + local literal assignments."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _module_literal_consts(sf):
    """Module-level ``_NAME = "literal"`` constants — a registered
    literal by definition (the docs gate's source scan sees them)."""
    out = set()
    for node in sf.tree.body:
        if isinstance(node, ast.Assign) and \
                len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                _literal_ok(node.value):
            out.add(node.targets[0].id)
    return out


def _check_names(sf):
    findings = []
    module_consts = _module_literal_consts(sf)
    for fn in _enclosing_scopes(sf.tree):
        params = {a.arg for a in fn.args.args +
                  fn.args.kwonlyargs + fn.args.posonlyargs}
        if fn.args.vararg:
            params.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            params.add(fn.args.kwarg.arg)
        literal_locals = set()
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Assign) and \
                    len(sub.targets) == 1 and \
                    isinstance(sub.targets[0], ast.Name) and \
                    _literal_ok(sub.value):
                literal_locals.add(sub.targets[0].id)
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call) or not _is_sink(sub):
                continue
            if not sub.args:
                continue
            arg = sub.args[0]
            if _literal_ok(arg):
                continue
            if isinstance(arg, ast.Name) and (
                    arg.id in params or arg.id in literal_locals or
                    arg.id in module_consts):
                continue
            func = sub.func
            findings.append(Finding(
                sf.rel, sub.lineno, "VL301",
                "name passed to %s.%s() is not a registered string "
                "literal" % (_recv_text(func.value) or "?",
                             func.attr)))
    # Deduplicate: nested function defs are walked once per
    # enclosing scope.
    seen = set()
    out = []
    for f in findings:
        if (f.line, f.message) not in seen:
            seen.add((f.line, f.message))
            out.append(f)
    return out


def _is_broad(handler):
    if handler.type is None:
        return True
    t = handler.type
    if isinstance(t, ast.Name) and t.id in ("Exception",
                                            "BaseException"):
        return True
    if isinstance(t, ast.Tuple):
        return any(isinstance(e, ast.Name) and
                   e.id in ("Exception", "BaseException")
                   for e in t.elts)
    return False


def _handler_is_silent(handler):
    """True when the handler neither raises, logs, counts, nor uses
    the bound exception."""
    exc_name = handler.name
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return False
        if isinstance(node, ast.Call):
            func = node.func
            if isinstance(func, ast.Attribute) and \
                    func.attr in _LOG_METHODS:
                return False
            if isinstance(func, ast.Attribute) and \
                    func.attr == "incr":
                return False
        if exc_name and isinstance(node, ast.Name) and \
                node.id == exc_name and \
                isinstance(node.ctx, ast.Load):
            return False
    return True


def _check_excepts(sf):
    findings = []
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Try):
            continue
        for handler in node.handlers:
            if not _is_broad(handler):
                continue
            if _handler_is_silent(handler):
                findings.append(Finding(
                    sf.rel, handler.lineno, "VL302",
                    "broad except swallows the error silently — "
                    "log it (self.exception/log.*), count it "
                    "(resilience.stats.incr), use it, or re-raise"))
    return findings


def run(project):
    findings = []
    for sf in project.files:
        findings.extend(_check_names(sf))
        findings.extend(_check_excepts(sf))
    return findings
