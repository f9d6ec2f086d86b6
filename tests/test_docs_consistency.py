"""Docs ↔ code consistency gate (ISSUE 7 tooling satellite).

Dashboards and docs drifted from the code before (renamed counters,
dropped flags); this tier-1 test pins them together: every CLI flag,
chaos fault/injection point, and dotted stat/metric/span name that
``docs/*.md`` references must exist in the parser or source that
defines it.

* **Flags**: the union of every ``add_argument("--…")`` in the
  package (velescli aggregation, serve.py, web_status, scripts) plus
  ``bench.BENCH_FLAGS`` (bench parses argv ad-hoc — the tuple IS its
  flag registry).  A doc flag may also be a prefix reference like
  ``--serve-kv-*``.
* **Dotted names**: for the observability namespaces (``net.*``,
  ``chaos.*``, ``server.*``, ``device.*``, …) a name mentioned in
  docs must appear as a string literal somewhere in the source
  (``%s``-parameterized literals act as wildcards) or be a declared
  fault/point.
"""

import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC_FILES = sorted(glob.glob(os.path.join(REPO, "docs", "*.md")))

_CODE_SPAN_RE = re.compile(r"`([^`\n]+)`")
_FLAG_RE = re.compile(r"--[a-z][a-z0-9-]*[a-z0-9]")
_ADD_ARG_RE = re.compile(r"add_argument\(\s*\n?\s*[\"'](--[a-z0-9-]+)")
_DOTTED_RE = re.compile(r"\b[a-z][a-z0-9_]*(?:\.[a-z][a-z0-9_]*)+\b")

#: First components of dotted names subject to the consistency
#: check — the observability/stat namespaces.  Dotted tokens outside
#: these (module paths, config keys, filenames) are not checked.
CHECKED_PREFIXES = frozenset((
    "net", "chaos", "server", "client", "master", "worker",
    "snapshot", "step", "serving", "guardian", "device", "kv",
    "requests", "batches", "tokens", "rejected", "cancelled",
    "stalled", "warmup", "ttft", "itl", "perf", "optimizer", "moe",
    "spec", "drained", "population", "pbt", "fleet", "membership",
    "fabric", "router", "tenant", "quant",
))


def _doc_code_spans():
    for path in DOC_FILES:
        with open(path) as fin:
            text = fin.read()
        for match in _CODE_SPAN_RE.finditer(text):
            yield os.path.basename(path), match.group(1)


def _source_files():
    out = [os.path.join(REPO, "bench.py")]
    for base, _dirs, files in os.walk(os.path.join(REPO,
                                                   "veles_tpu")):
        if "__pycache__" in base:
            continue
        out.extend(os.path.join(base, f) for f in files
                   if f.endswith(".py"))
    return out


def _known_flags():
    import bench
    flags = set(bench.BENCH_FLAGS)
    for path in _source_files():
        with open(path) as fin:
            flags.update(_ADD_ARG_RE.findall(fin.read()))
    # The aggregated velescli tree must ALSO build cleanly and agree
    # with the per-module sources (a registration typo would leave a
    # documented flag unparseable despite existing in source).
    from veles_tpu.cmdline import init_argparser
    parser = init_argparser(prog="veles_tpu")
    for action in parser._actions:
        flags.update(o for o in action.option_strings
                     if o.startswith("--"))
    return flags


def _known_dotted():
    """Literal dotted names in the source, with %-format fields as
    wildcards, plus the chaos fault/point registry.  The scan itself
    lives in veles_tpu.analysis.registries (a reusable pass — the
    VL301 lint rule keeps call sites literal so this scan stays
    sound); the gate only adds the declared fault/point names."""
    from veles_tpu import resilience
    from veles_tpu.analysis import core as acore
    from veles_tpu.analysis import registries as areg
    project = acore.Project(REPO, acore.default_targets(REPO))
    exact, wildcards = areg.dotted_source_literals(project)
    exact |= set(resilience.FAULTS) | set(resilience.POINTS)
    return exact, wildcards


def test_documented_flags_exist():
    known = _known_flags()
    missing = []
    for doc, span in _doc_code_spans():
        for flag in _FLAG_RE.findall(span):
            if flag in known:
                continue
            # Prefix references like `--serve-kv-*` / family globs.
            if any(k.startswith(flag) for k in known):
                continue
            missing.append("%s: %s (in `%s`)" % (doc, flag, span))
    assert not missing, (
        "docs reference CLI flags no parser defines:\n  " +
        "\n  ".join(sorted(set(missing))))


def test_documented_stat_and_chaos_names_exist():
    exact, wildcards = _known_dotted()
    missing = []
    for doc, span in _doc_code_spans():
        for token in _DOTTED_RE.findall(span):
            if token.split(".", 1)[0] not in CHECKED_PREFIXES:
                continue
            if token.endswith((".py", ".md", ".json", ".html",
                               ".tgz", ".lnk", ".npz", ".yaml")):
                continue  # a filename, not a stat/span name
            if token in exact:
                continue
            if any(w.match(token) for w in wildcards):
                continue
            missing.append("%s: %s (in `%s`)" % (doc, token, span))
    assert not missing, (
        "docs reference stat/chaos/span names the code does not "
        "define:\n  " + "\n  ".join(sorted(set(missing))))


def test_chaos_registry_is_documented():
    """The reverse direction: every declared fault appears somewhere
    in docs/resilience.md (operators discover chaos plans there)."""
    from veles_tpu import resilience
    with open(os.path.join(REPO, "docs", "resilience.md")) as fin:
        text = fin.read()
    undocumented = [f for f in resilience.FAULTS if f not in text]
    assert not undocumented, (
        "chaos faults missing from docs/resilience.md: %s"
        % ", ".join(undocumented))


def test_heartbeat_sections_match_dashboard_rows():
    """Every heartbeat section web_status re-exposes on /metrics has
    a renderer in render_page, and vice versa — the dashboard cannot
    silently drop a section the launcher ships."""
    import inspect
    from veles_tpu import web_status
    src = inspect.getsource(web_status.WebStatusServer.render_page)
    for section in web_status.WebStatusServer.METRIC_SECTIONS:
        assert 'info.get("%s"' % section in src, (
            "heartbeat section %r is scraped on /metrics but never "
            "rendered by render_page" % section)


def test_cli_reference_lists_what_the_parser_has():
    """``docs/cli.md`` is generated from the aggregated parser: its
    rows are the parser's options, no more (a flag that was removed —
    ``--moe-topk``, ``--moe-router-z`` with the capacity-routed
    expert block — is gone from both) and no fewer."""
    from veles_tpu.cmdline import init_argparser
    parser = init_argparser(prog="veles_tpu")
    options = {o for action in parser._actions
               for o in action.option_strings if o.startswith("--")}
    assert not {"--moe-topk", "--moe-router-z"} & options
    with open(os.path.join(REPO, "docs", "cli.md")) as fin:
        rows = [line.split("|")[1] for line in fin
                if line.startswith("| `")]
    documented = {flag for row in rows
                  for flag in _FLAG_RE.findall(row)}
    assert documented == options - {"--help"}, (
        sorted(documented ^ (options - {"--help"})))
