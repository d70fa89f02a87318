"""observability-drift: the metrics schema / docs contract as a checker.

This is ``scripts/metrics_lint.py`` folded into the graftlint
framework (that script survives as a thin delegating shim, so every
documented command keeps working). The contract it holds is unchanged:

- OBS001 — a ``bigdl_*`` instrument registered OUTSIDE
  ``bigdl_tpu/observability/instruments.py`` (one module is the
  schema; the fix is always an ``*_instruments`` entry there).
- OBS002 — an instrument registered in that module but missing from
  the instrument table in ``docs/programming-guide/observability.md``
  (an operator reading the docs must see every series a scrape can
  emit).
- OBS003 — a documented table row whose instrument is no longer
  registered (a ghost row promising a series no scrape will emit).

Doc-table grammar (unchanged): a row may spell a name exactly, expand
one ``{a,b,c}`` alternation, or end in ``*`` for a family prefix;
only markdown table rows (lines starting with ``|``) count.

Repo-level checker: it compares three artifacts (code tree, schema
module, doc table), so there is no per-file cache entry — it runs on
every scan and on every ``--changed`` run (it is milliseconds).
"""

from __future__ import annotations

import os
import re
from typing import List

from ..core import SKIP_DIRS, Checker, Finding, register

#: the one module allowed to register bigdl_* instruments
ALLOWED = ("bigdl_tpu", "observability", "instruments.py")

#: the guide whose instrument table must cover every registered name
DOCS_GUIDE = ("docs", "programming-guide", "observability.md")

# a registration call with a bigdl_* name literal as its first
# argument; assembled from pieces so this file never matches itself
_PATTERN = re.compile(
    r"\.\s*(counter|gauge|histogram)\s*\(\s*"
    r"[\"']" + "(bigdl" + r"_[A-Za-z0-9_:]*)[\"']",
    re.S)

# a documented-name token in the guide: a bigdl_ head, at most one
# {a,b,c} alternation (a {label=} brace contains '=' and is NOT an
# alternation, so it terminates the token), an optional tail, and an
# optional trailing * marking a family prefix
_DOC_TOKEN = re.compile(
    "(" + "bigdl" + r"_[A-Za-z0-9_]*)"
    r"(?:\{([A-Za-z0-9_,]+)\})?"
    r"([A-Za-z0-9_]*)"
    r"(\*)?")


def lint(root: str):
    """Yield (path, lineno, method, metric_name) out-of-place
    registrations (the historical metrics_lint API, kept verbatim for
    the shim and its tier-1 tests)."""
    allowed = os.path.join(root, *ALLOWED)
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            if os.path.abspath(path) == os.path.abspath(allowed):
                continue
            try:
                with open(path, encoding="utf-8") as f:
                    text = f.read()
            except (OSError, UnicodeDecodeError):
                continue
            for m in _PATTERN.finditer(text):
                lineno = text.count("\n", 0, m.start()) + 1
                yield (os.path.relpath(path, root), lineno,
                       m.group(1), m.group(2))


def registered_names(root: str):
    """Every metric name literal registered in the canonical module."""
    path = os.path.join(root, *ALLOWED)
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return []
    return sorted({m.group(2) for m in _PATTERN.finditer(text)})


def documented_patterns(root: str):
    """The doc guide's instrument-TABLE vocabulary: exact names,
    expanded ``{a,b,c}`` alternations, and ``prefix*`` family
    wildcards. Only markdown table rows (lines starting with ``|``)
    count — prose mentioning ``bigdl_*`` generically must not satisfy
    the per-instrument documentation requirement."""
    path = os.path.join(root, *DOCS_GUIDE)
    try:
        with open(path, encoding="utf-8") as f:
            lines = f.readlines()
    except OSError:
        return set()
    pats = set()
    for line in lines:
        if not line.lstrip().startswith("|"):
            continue
        for m in _DOC_TOKEN.finditer(line):
            head, alts, tail, star = m.groups()
            for alt in (alts.split(",") if alts else ("",)):
                pats.add(head + alt + (tail or "")
                         + ("*" if star else ""))
    return pats


def doc_drift(root: str):
    """Registered instrument names the docs table never mentions."""
    pats = documented_patterns(root)

    def covered(name):
        return any((p.endswith("*") and name.startswith(p[:-1]))
                   or name == p for p in pats)

    return [n for n in registered_names(root) if not covered(n)]


def reverse_drift(root: str):
    """Documented table names/patterns with no registered counterpart:
    an exact (or ``{a,b,c}``-expanded) name must be registered
    verbatim; a ``prefix*`` wildcard row needs at least one registered
    name under its prefix."""
    names = set(registered_names(root))

    def alive(pat):
        if pat.endswith("*"):
            return any(n.startswith(pat[:-1]) for n in names)
        return pat in names

    return sorted(p for p in documented_patterns(root) if not alive(p))


def _doc_line(root: str, name: str) -> int:
    """Best-effort line of a doc-table token (for finding anchors)."""
    path = os.path.join(root, *DOCS_GUIDE)
    probe = name[:-1] if name.endswith("*") else name
    try:
        with open(path, encoding="utf-8") as f:
            for i, line in enumerate(f, start=1):
                if line.lstrip().startswith("|") and probe in line:
                    return i
    except OSError:
        pass
    return 1


def _registration_line(root: str, name: str) -> int:
    path = os.path.join(root, *ALLOWED)
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except OSError:
        return 1
    for m in _PATTERN.finditer(text):
        if m.group(2) == name:
            return text.count("\n", 0, m.start()) + 1
    return 1


@register
class ObservabilityDriftChecker(Checker):
    name = "observability-drift"
    version = 1
    repo_level = True
    codes = {
        "OBS001": "bigdl_* instrument registered outside "
                  "observability/instruments.py",
        "OBS002": "instrument registered but undocumented in the "
                  "docs instrument table",
        "OBS003": "ghost doc row: documented instrument no longer "
                  "registered",
    }

    def check_repo(self, root: str) -> List[Finding]:
        out: List[Finding] = []
        for path, lineno, method, mname in lint(root):
            out.append(Finding(
                path, lineno, 0, "OBS001", self.name,
                f".{method}({mname!r}) — bigdl_* metrics must be "
                f"defined in {'/'.join(ALLOWED)} (add an "
                "*_instruments entry)"))
        for mname in doc_drift(root):
            out.append(Finding(
                "/".join(ALLOWED), _registration_line(root, mname), 0,
                "OBS002", self.name,
                f"{mname!r} is registered but missing from the "
                f"instrument table in {'/'.join(DOCS_GUIDE)} (add a "
                "table row)"))
        for mname in reverse_drift(root):
            out.append(Finding(
                "/".join(DOCS_GUIDE), _doc_line(root, mname), 0,
                "OBS003", self.name,
                f"{mname!r} is documented in the instrument table but "
                f"no longer registered in {'/'.join(ALLOWED)} (drop "
                "the row or restore the instrument)"))
        return out


def legacy_main(argv=None, default_root=None) -> int:
    """The historical ``scripts/metrics_lint.py`` CLI, byte-compatible
    output — the shim delegates here (passing its own repo root as
    ``default_root``) so every documented command and in-process test
    keeps working."""
    import argparse

    here = default_root or os.getcwd()
    p = argparse.ArgumentParser(
        description="Fail when a bigdl_* metric is registered outside "
                    "observability/instruments.py, or registered there "
                    "but missing from the docs instrument table. "
                    "(Deprecated shim: see scripts/graftlint.py.)")
    p.add_argument("--root", default=here)
    args = p.parse_args(argv)

    violations = list(lint(args.root))
    for path, lineno, method, name in violations:
        print(f"[metrics-lint] {path}:{lineno}: .{method}({name!r}) — "
              f"bigdl_* metrics must be defined in "
              f"{'/'.join(ALLOWED)} (add an *_instruments entry)")
    undocumented = doc_drift(args.root)
    for name in undocumented:
        print(f"[metrics-lint] {'/'.join(ALLOWED)}: {name!r} is "
              f"registered but missing from the instrument table in "
              f"{'/'.join(DOCS_GUIDE)} (add a table row)")
    ghosts = reverse_drift(args.root)
    for name in ghosts:
        print(f"[metrics-lint] {'/'.join(DOCS_GUIDE)}: {name!r} is "
              f"documented in the instrument table but no longer "
              f"registered in {'/'.join(ALLOWED)} (drop the row or "
              f"restore the instrument)")
    if violations or undocumented or ghosts:
        print(f"[metrics-lint] FAIL: {len(violations)} out-of-place "
              f"registration(s), {len(undocumented)} undocumented "
              f"instrument(s), {len(ghosts)} ghost doc row(s)")
        return 1
    print("[metrics-lint] ok: all bigdl_* metrics registered in "
          + "/".join(ALLOWED) + " and documented in "
          + "/".join(DOCS_GUIDE) + " (both directions)")
    return 0
