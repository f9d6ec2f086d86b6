"""veles-lint core: file model, rule registry, suppressions,
baselines, and the pass driver.

The linter is PROJECT-AWARE, not generic: every rule encodes a
contract this codebase already promises elsewhere (the docs
consistency gate, ``Vector.host_sync_count`` pins, the ``SniffedLock``
threading discipline) and turns it from reviewer vigilance into a
tier-1 zero-findings gate.  See docs/analysis.md for the rule catalog
and the annotation conventions.

Two suppression mechanisms:

* **inline** — a trailing ``# lint-ok: VL101 reason`` comment
  suppresses the named rule(s) on that line; the reason is mandatory
  culture, not parsed syntax;
* **baseline** — ``--baseline FILE`` subtracts previously recorded
  findings (keyed by ``(path, rule, message)`` so line drift does not
  resurrect them); ``--write-baseline`` records the current set.
"""

import ast
import os
import re
import tokenize
from collections import namedtuple

#: rule id → one-line description (the catalog docs/analysis.md
#: renders; ``python -m veles_tpu.analysis --list-rules`` prints it).
RULES = {
    "VL101": "host-sync call reachable inside jit-traced code "
             "(.item(), float()/int() on arrays, numpy.asarray, "
             "jax.device_get)",
    "VL102": "retrace/nondeterminism hazard reachable inside "
             "jit-traced code (time.*, random.*, numpy.random.*, "
             "os.urandom, uuid.*)",
    "VL201": "field annotated `# guarded-by: <lock>` written outside "
             "`with <lock>`",
    "VL202": "static lock-acquisition-order cycle",
    "VL301": "observability/chaos name is not a registered string "
             "literal",
    "VL302": "broad `except Exception` swallows silently (no log, "
             "stat counter, re-raise, or use of the error)",
}

Finding = namedtuple("Finding", "path line rule message")


def format_finding(f):
    """The greppable ``path:line: RULE-ID message`` form."""
    return "%s:%d: %s %s" % (f.path, f.line, f.rule, f.message)


def baseline_key(f):
    """Baseline identity: line numbers drift with unrelated edits, so
    a recorded finding is keyed by (path, rule, message) instead."""
    return (f.path, f.rule, f.message)


_SUPPRESS_RE = re.compile(r"#\s*lint-ok:\s*((?:VL\d{3}[\s,]*)+)")
_FINDING_RE = re.compile(r"^(?P<path>[^:]+):(?P<line>\d+):\s+"
                         r"(?P<rule>VL\d{3})\s+(?P<msg>.*)$")


class SourceFile(object):
    """One parsed source file: AST, raw lines, and the per-line
    suppression map (``# lint-ok: VLnnn``)."""

    def __init__(self, path, rel, modname):
        self.path = path
        self.rel = rel
        self.modname = modname
        with tokenize.open(path) as fin:
            self.text = fin.read()
        self.lines = self.text.splitlines()
        self.tree = ast.parse(self.text, filename=path)
        self.suppress = {}
        for lineno, line in enumerate(self.lines, 1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = set(re.findall(r"VL\d{3}", m.group(1)))
            self.suppress.setdefault(lineno, set()).update(rules)
            if line.lstrip().startswith("#"):
                # A standalone suppression comment covers the next
                # non-comment line (comment-above style for long
                # statements).
                nxt = lineno + 1
                while nxt <= len(self.lines) and \
                        self.lines[nxt - 1].lstrip().startswith("#"):
                    nxt += 1
                self.suppress.setdefault(nxt, set()).update(rules)

    def suppressed(self, lineno, rule):
        return rule in self.suppress.get(lineno, ())

    def line_text(self, lineno):
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""


class Project(object):
    """The file set one lint run analyzes (package dirs + scripts)."""

    def __init__(self, root, paths):
        self.root = os.path.abspath(root)
        self.files = []
        self.by_module = {}
        self.errors = []
        for path in sorted(self._expand(paths)):
            rel = os.path.relpath(path, self.root)
            modname = self._modname(rel)
            try:
                sf = SourceFile(path, rel, modname)
            except SyntaxError as e:
                self.errors.append(Finding(
                    rel, e.lineno or 1, "VL000",
                    "file does not parse: %s" % e.msg))
                continue
            self.files.append(sf)
            self.by_module[modname] = sf

    @staticmethod
    def _expand(paths):
        for path in paths:
            path = os.path.abspath(path)
            if os.path.isfile(path):
                yield path
                continue
            for base, dirs, files in os.walk(path):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for name in files:
                    if name.endswith(".py"):
                        yield os.path.join(base, name)

    @staticmethod
    def _modname(rel):
        mod = rel[:-3] if rel.endswith(".py") else rel
        mod = mod.replace(os.sep, ".")
        if mod.endswith(".__init__"):
            mod = mod[:-len(".__init__")]
        return mod

    def resolve_relative(self, sf, level, module):
        """Absolute dotted name for a ``from ...X import`` in ``sf``."""
        if level == 0:
            return module or ""
        parts = sf.modname.split(".")
        # A package __init__ counts as the package itself.
        is_pkg = sf.rel.endswith("__init__.py")
        base = parts[:len(parts) - level + (1 if is_pkg else 0)]
        if module:
            base.append(module)
        return ".".join(base)


def default_targets(root):
    """The tier-1 gate's file set: the package plus the top-level
    entry scripts."""
    out = [os.path.join(root, "veles_tpu")]
    for extra in ("bench.py", "__graft_entry__.py", "chip_smoke.py"):
        path = os.path.join(root, extra)
        if os.path.isfile(path):
            out.append(path)
    return out


def repo_root():
    """The checkout root (parent of the installed package dir)."""
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def run(paths=None, root=None):
    """Runs every pass over ``paths`` (default: the tier-1 target
    set) and returns the sorted, suppression-filtered findings."""
    from . import callgraph, locks, registries
    root = root or repo_root()
    paths = paths or default_targets(root)
    project = Project(root, paths)
    findings = list(project.errors)
    for pass_fn in (callgraph.run, locks.run, registries.run):
        findings.extend(pass_fn(project))
    out = []
    for f in findings:
        sf = next((s for s in project.files if s.rel == f.path), None)
        if sf is not None and sf.suppressed(f.line, f.rule):
            continue
        out.append(f)
    out.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return out


def load_baseline(path):
    """Recorded findings as a set of baseline keys (missing file =
    empty baseline)."""
    keys = set()
    if not path or not os.path.isfile(path):
        return keys
    with open(path) as fin:
        for line in fin:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            m = _FINDING_RE.match(line)
            if m:
                keys.add((m.group("path"), m.group("rule"),
                          m.group("msg")))
    return keys


def write_baseline(path, findings):
    with open(path, "w") as fout:
        fout.write("# veles-lint baseline — regenerate with\n"
                   "#   python -m veles_tpu.analysis "
                   "--write-baseline\n")
        for f in findings:
            fout.write(format_finding(f) + "\n")


def apply_baseline(findings, baseline_keys):
    return [f for f in findings if baseline_key(f)
            not in baseline_keys]
