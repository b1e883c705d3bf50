#!/usr/bin/env python
"""Static counter-registration pass.

Every ``veles_*`` counter the tree increments (``inc("veles_...")`` /
``counters.inc("veles_...")``) or reads (``counters.get("veles_...")``)
must be registered with a HELP string in
``veles_tpu/telemetry/counters.py::DESCRIPTIONS`` — an unregistered
name still counts, but renders on ``/metrics`` with the generic HELP
and belongs to no family that a test holds at zero. This
script fails (exit 1) on any used-but-unregistered name, so the drift
is caught at CI time instead of on a dashboard.

No imports of the package (and no jax): the registry is read by
AST-parsing counters.py, the usages by regexing the tree — runs in
milliseconds anywhere.

Usage: ``python scripts/check_counters.py`` (from any cwd);
wired into tier-1 via tests/test_tensormon.py.
"""

from __future__ import annotations

import ast
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS_PY = os.path.join(REPO, "veles_tpu", "telemetry",
                           "counters.py")

#: literal counter-name usages: inc("veles_x") — the module helper,
#: the registry method (matches after the dot) AND import aliases
#: ending in `inc` like recorder.py's `_counter_inc(` — plus
#: counters.get("veles_x"). Dynamically-built
#: names cannot be checked statically and are out of scope.
USE_RE = re.compile(
    r"""\b[A-Za-z_]*inc\(\s*["'](veles_[a-z0-9_]+)["']"""
    r"""|\bcounters\.get\(\s*["'](veles_[a-z0-9_]+)["']""")

#: literal histogram-name usages: observe("veles_x") — the module
#: helper and the registry method — plus the quantile/count/sum reads
#: through any registry-looking receiver (``histograms.quantile``,
#: a ``_hists.count`` alias: a name containing ``hist``).
#: Every such name must be registered in counters.py HISTOGRAMS with
#: a HELP string AND bucket bounds — same fail-closed rule as
#: counters: an unregistered histogram still records (on DEFAULT
#: buckets) but escapes the zero-leakage test.
HIST_USE_RE = re.compile(
    r"""\b[A-Za-z_]*observe\(\s*["'](veles_[a-z0-9_]+)["']"""
    r"""|\b[A-Za-z_]*[Hh]ist[A-Za-z_]*\.(?:quantile|count|sum)"""
    r"""\(\s*["'](veles_[a-z0-9_]+)["']""")

#: directories scanned for usages (tests may inc ad-hoc names on
#: purpose and are excluded)
SCAN = ("veles_tpu", "scripts")

#: the operator-facing registry mirror: every REGISTERED veles_*
#: counter/histogram must have a row here (the --docs pass)
DOCS_MD = os.path.join(REPO, "docs", "observability.md")

#: a veles_* name as the docs spell it — either literal, or with ONE
#: brace group (`veles_journal_{appends,replayed}_total`), which the
#: docs pass expands so prose families count as documented
DOC_NAME_RE = re.compile(
    r"veles_[a-z0-9_]*(?:\{[a-z0-9_,]+\}[a-z0-9_]*)?")


def registered_counters(path: str = COUNTERS_PY) -> set:
    """Keys of the DESCRIPTIONS dict, read via AST (no import)."""
    with open(path) as fin:
        tree = ast.parse(fin.read())
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(getattr(t, "id", None) == "DESCRIPTIONS"
                   for t in node.targets):
            continue
        if not isinstance(node.value, ast.Dict):
            break
        return {key.value for key in node.value.keys
                if isinstance(key, ast.Constant)}
    raise SystemExit("DESCRIPTIONS dict literal not found in %s" % path)


def registered_histograms(path: str = COUNTERS_PY) -> dict:
    """{name: entry-is-complete} from the HISTOGRAMS dict literal,
    read via AST (no import). An entry is complete when its value is
    a dict literal carrying non-empty "help" and "buckets" — a
    histogram registered without bounds would silently fall back to
    DEFAULT_BUCKETS, exactly the drift this script exists to stop."""
    with open(path) as fin:
        tree = ast.parse(fin.read())
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(getattr(t, "id", None) == "HISTOGRAMS"
                   for t in node.targets):
            continue
        if not isinstance(node.value, ast.Dict):
            break
        out = {}
        for key, val in zip(node.value.keys, node.value.values):
            if not isinstance(key, ast.Constant):
                continue
            complete = False
            if isinstance(val, ast.Dict):
                fields = {k.value: v for k, v in
                          zip(val.keys, val.values)
                          if isinstance(k, ast.Constant)}
                help_node = fields.get("help")
                bucket_node = fields.get("buckets")
                complete = (
                    help_node is not None and bucket_node is not None
                    and not (isinstance(bucket_node,
                                        (ast.Tuple, ast.List))
                             and not bucket_node.elts))
            out[key.value] = complete
        return out
    raise SystemExit("HISTOGRAMS dict literal not found in %s" % path)


def _scan_paths(repo: str = REPO):
    this_file = os.path.abspath(__file__)
    paths = []
    for entry in SCAN:
        full = os.path.join(repo, entry)
        if os.path.isfile(full):
            paths.append(full)
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            paths.extend(os.path.join(dirpath, f)
                         for f in sorted(filenames)
                         if f.endswith(".py"))
    return [p for p in paths if os.path.abspath(p) != this_file]


def _used_names(regex, repo: str = REPO):
    """{name: first use site} for one usage regex over the tree."""
    uses = {}
    for path in _scan_paths(repo):
        with open(path, errors="replace") as fin:
            for lineno, line in enumerate(fin, 1):
                for match in regex.finditer(line):
                    name = next(g for g in match.groups() if g)
                    uses.setdefault(
                        name, "%s:%d"
                        % (os.path.relpath(path, repo), lineno))
    return uses


def used_counters(repo: str = REPO):
    """{counter name: first use site} over the scanned tree."""
    return _used_names(USE_RE, repo)


def used_histograms(repo: str = REPO):
    """{histogram name: first use site} over the scanned tree."""
    return _used_names(HIST_USE_RE, repo)


def find_unregistered():
    """[(name, first use site)] for every used-but-unregistered
    counter — the list main() fails on."""
    known = registered_counters()
    return sorted((name, site) for name, site in used_counters().items()
                  if name not in known)


def find_unregistered_histograms():
    """[(name, first use site)] for every observed histogram that is
    missing from HISTOGRAMS or registered without help/buckets."""
    known = registered_histograms()
    return sorted((name, site)
                  for name, site in used_histograms().items()
                  if not known.get(name, False))


#: the watchtower rule engine — its shipped default rules (and the
#: gauge whitelist its fail-closed validation accepts) are read via
#: AST like the registries above
ALERTS_PY = os.path.join(REPO, "veles_tpu", "telemetry", "alerts.py")


def known_alert_gauges(path: str = ALERTS_PY) -> set:
    """The KNOWN_GAUGES tuple literal of telemetry/alerts.py — the
    gauge names the rule engine's fail-closed validation accepts."""
    with open(path) as fin:
        tree = ast.parse(fin.read())
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(getattr(t, "id", None) == "KNOWN_GAUGES"
                   for t in node.targets):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            return {el.value for el in node.value.elts
                    if isinstance(el, ast.Constant)}
        break
    raise SystemExit("KNOWN_GAUGES tuple literal not found in %s"
                     % path)


def default_rule_series(path: str = ALERTS_PY) -> dict:
    """{series name: site} for every ``series="veles_..."`` literal
    inside :func:`default_rules` — the shipped alert rules. Read via
    AST so the pass needs no package import (and no jax)."""
    with open(path) as fin:
        tree = ast.parse(fin.read())
    out = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef) \
                or node.name != "default_rules":
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            if not getattr(sub.func, "id", "").endswith("Rule"):
                continue
            # Rule constructors take (name, series, ...): the series
            # is the second positional arg, or a series= keyword
            candidates = []
            if len(sub.args) >= 2:
                candidates.append(sub.args[1])
            candidates += [kw.value for kw in sub.keywords
                           if kw.arg == "series"]
            for cand in candidates:
                if isinstance(cand, ast.Constant) \
                        and isinstance(cand.value, str) \
                        and cand.value.startswith("veles_"):
                    out.setdefault(
                        cand.value,
                        "%s:%d" % (os.path.relpath(path, REPO),
                                   cand.lineno))
        return out
    raise SystemExit("default_rules() not found in %s" % path)


def find_unknown_alert_series():
    """[(series, site)] for every series a SHIPPED default alert
    rule watches that is registered nowhere — not a counter
    (DESCRIPTIONS), not a histogram (HISTOGRAMS), not an accepted
    gauge (alerts.KNOWN_GAUGES). Such a rule would refuse at config
    parse (the engine validates fail-closed) and take every default
    rule down with it — caught here at CI time instead."""
    known = (registered_counters() | set(registered_histograms())
             | known_alert_gauges())
    return sorted((name, site)
                  for name, site in default_rule_series().items()
                  if name not in known)


def documented_names(path: str = DOCS_MD) -> set:
    """Every veles_* name docs/observability.md mentions, brace
    families (`veles_resume_{attempts,tokens}_total`) expanded."""
    with open(path, errors="replace") as fin:
        text = fin.read()
    out = set()
    for token in DOC_NAME_RE.findall(text):
        if "{" in token:
            head, rest = token.split("{", 1)
            group, tail = rest.split("}", 1)
            for part in group.split(","):
                out.add(head + part + tail)
        else:
            out.add(token)
    return out


def find_undocumented(path: str = DOCS_MD):
    """[(name, kind)] for every REGISTERED counter/histogram that
    docs/observability.md never mentions — the --docs pass (a
    registered metric an operator cannot look up is observability
    debt; this catches the drift at CI time, like the registration
    pass catches unregistered names)."""
    docs = documented_names(path)
    missing = [(name, "counter")
               for name in sorted(registered_counters())
               if name not in docs]
    missing += [(name, "histogram")
                for name in sorted(registered_histograms())
                if name not in docs]
    return missing


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    check_docs = "--docs" in argv
    missing = find_unregistered()
    for name, site in missing:
        print("UNREGISTERED counter %s (first use: %s)" % (name, site),
              file=sys.stderr)
    missing_hist = find_unregistered_histograms()
    for name, site in missing_hist:
        print("UNREGISTERED histogram %s (first use: %s) — needs a "
              "HISTOGRAMS entry with help AND bucket bounds"
              % (name, site), file=sys.stderr)
    bad_series = find_unknown_alert_series()
    for name, site in bad_series:
        print("UNKNOWN alert series %s (%s) — a shipped default rule "
              "watches a series that is no registered counter, "
              "histogram or KNOWN_GAUGES entry; the fail-closed rule "
              "validation would refuse EVERY default rule at runtime"
              % (name, site), file=sys.stderr)
    undocumented = find_undocumented() if check_docs else []
    for name, kind in undocumented:
        print("UNDOCUMENTED %s %s — registered in telemetry/"
              "counters.py but missing from docs/observability.md"
              % (kind, name), file=sys.stderr)
    if missing or missing_hist or bad_series or undocumented:
        print("%d counter(s) / %d histogram(s) used but not "
              "registered in telemetry/counters.py; %d unknown alert "
              "series%s"
              % (len(missing), len(missing_hist), len(bad_series),
                 "; %d registered name(s) undocumented"
                 % len(undocumented) if undocumented else ""),
              file=sys.stderr)
        return 1
    print("counter registration OK (%d counters registered, %d "
          "distinct names used; %d histograms registered, %d "
          "observed; %d default alert series validated%s)"
          % (len(registered_counters()), len(used_counters()),
             len(registered_histograms()), len(used_histograms()),
             len(default_rule_series()),
             "; all documented" if check_docs else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
