"""Checker registry, findings, suppressions, scoping, and the runner.

Design contracts:

- A **Finding** is (file, line, col, code, checker, message) with the
  file path always repo-relative POSIX — baselines and reports must
  diff cleanly across machines.
- **Checkers** register themselves into a module-level registry at
  import time (``@register``). Per-file checkers get one parsed AST
  per file (parsed once, shared by every checker); repo-level checkers
  (observability-drift) run once per scan against the root.
- **Suppressions**: ``# graftlint: ok[token]`` on the finding's line
  or the line directly above it, where ``token`` is a finding code
  (``LCK001``), a checker name (``lock-discipline``), or ``all``;
  several tokens may be comma-separated. A one-line reason after the
  bracket (``— immutable after construction``) is the house style.
- **Scoping**: some codes only make sense on specific subtrees (the
  lock-discipline race detector targets the serving stack; JIT005's
  pinned-out_shardings rule targets serving modules). The scope table
  lives HERE, not in the checkers, so a fixture run with explicit
  paths (``scoped=False``) exercises every rule on any file.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

#: bump when the Finding schema / cache layout changes incompatibly
SCHEMA_VERSION = 1

#: directories never scanned (mirrors metrics_lint's historical scope:
#: tests mint deliberate violations, docs show myapp_* examples,
#: native/ is C++, the rest are build/VCS droppings — .scratch and
#: chiprun_out hold the chip tool's scratch copies and outputs)
SKIP_DIRS = {
    ".git", "__pycache__", "build", "dist", "docs", "tests", ".eggs",
    "bigdl_tpu.egg-info", "native", "docker", ".claude", "related",
    ".scratch", "chiprun_out",
}


class Finding:
    """One checker hit. Comparable/sortable; hashable on identity key."""

    __slots__ = ("file", "line", "col", "code", "checker", "message")

    def __init__(self, file: str, line: int, col: int, code: str,
                 checker: str, message: str):
        self.file = file.replace(os.sep, "/")
        self.line = int(line)
        self.col = int(col)
        self.code = code
        self.checker = checker
        self.message = message

    def key(self) -> Tuple[str, str]:
        """The baseline bucket: (file, code) — see baseline.py."""
        return (self.file, self.code)

    def sort_key(self):
        return (self.file, self.line, self.col, self.code)

    def to_dict(self) -> dict:
        return {"file": self.file, "line": self.line, "col": self.col,
                "code": self.code, "checker": self.checker,
                "message": self.message}

    @classmethod
    def from_dict(cls, d: dict) -> "Finding":
        return cls(d["file"], d["line"], d.get("col", 0), d["code"],
                   d.get("checker", ""), d.get("message", ""))

    def render(self) -> str:
        return (f"{self.file}:{self.line}:{self.col}: {self.code} "
                f"{self.message} [{self.checker}]")

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"Finding({self.render()!r})"

    def __eq__(self, other):
        return (isinstance(other, Finding)
                and self.sort_key() == other.sort_key()
                and self.message == other.message)

    def __hash__(self):
        return hash((self.sort_key(), self.message))


class Checker:
    """Base class. Subclasses set ``name``, ``codes``, ``version``;
    per-file checkers implement :meth:`check_file`, repo-level ones
    set ``repo_level = True`` and implement :meth:`check_repo`.

    ``version`` participates in the cache signature — bump it whenever
    the checker's behavior changes so stale cached findings never
    survive a logic change."""

    name: str = "base"
    #: code -> one-line description (the doc page renders this table)
    codes: Dict[str, str] = {}
    version: int = 1
    repo_level: bool = False

    def check_file(self, relpath: str, tree: ast.AST,
                   text: str) -> List[Finding]:
        return []

    def check_repo(self, root: str) -> List[Finding]:
        return []

    def finding(self, relpath: str, node, code: str,
                message: str) -> Finding:
        line = getattr(node, "lineno", 0)
        col = getattr(node, "col_offset", 0)
        return Finding(relpath, line, col, code, self.name, message)


_REGISTRY: "Dict[str, Checker]" = {}


def register(cls):
    """Class decorator: instantiate and register a checker (one
    instance per process — checkers must be stateless across files)."""
    inst = cls()
    _REGISTRY[inst.name] = inst
    return cls


def all_checkers() -> List[Checker]:
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def checkers_signature() -> str:
    """Cache-busting signature: schema + every checker's (name,
    version) — a checker logic bump invalidates its cached findings."""
    parts = [f"schema={SCHEMA_VERSION}"]
    parts += [f"{c.name}={c.version}" for c in all_checkers()]
    return ";".join(parts)


# ------------------------------------------------------------- scoping
def _serving(p: str) -> bool:
    return p.startswith("bigdl_tpu/serving/")


def _lock_scope(p: str) -> bool:
    # the issue's race-detector targets: the threaded serving stack
    # and the ledger every thread writes through
    return _serving(p) or p == "bigdl_tpu/observability/accounting.py"


def _hot_path(p: str) -> bool:
    return (_serving(p) or p.startswith("bigdl_tpu/observability/")
            or p.startswith("bigdl_tpu/optim/"))


#: code (or code-prefix ending in '*') -> predicate(relpath). Codes
#: with no entry apply everywhere. Consulted only in scoped runs —
#: explicit ``--paths`` / fixture runs see every rule.
SCOPES: Dict[str, Callable[[str], bool]] = {
    "LCK*": _lock_scope,
    "JIT005": _serving,
    "RES003": _hot_path,
}


def in_scope(code: str, relpath: str) -> bool:
    for pat, pred in SCOPES.items():
        if (pat.endswith("*") and code.startswith(pat[:-1])) \
                or code == pat:
            return pred(relpath)
    return True


# -------------------------------------------------------- suppressions
_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*ok\[([A-Za-z0-9_*,\- ]+)\]")


def suppressions_for_text(text: str) -> Dict[int, set]:
    """Map line number -> set of suppression tokens active there.

    A ``# graftlint: ok[tok]`` comment suppresses matching findings on
    its OWN line and on the line directly BELOW it (so a suppression
    can sit on its own line above a long statement)."""
    out: Dict[int, set] = {}
    for i, line in enumerate(text.splitlines(), start=1):
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        toks = {t.strip() for t in m.group(1).split(",") if t.strip()}
        out.setdefault(i, set()).update(toks)
        out.setdefault(i + 1, set()).update(toks)
    return out


def is_suppressed(f: Finding, supp: Dict[int, set]) -> bool:
    toks = supp.get(f.line)
    if not toks:
        return False
    return bool(toks & {f.code, f.checker, "all"})


# ------------------------------------------------------------- walking
def iter_target_files(root: str) -> List[str]:
    """Repo-relative POSIX paths of every ``.py`` file in scan scope,
    sorted for deterministic output."""
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in SKIP_DIRS
                             and not d.endswith(".egg-info"))
        for fname in filenames:
            if fname.endswith(".py"):
                rel = os.path.relpath(os.path.join(dirpath, fname),
                                      root)
                out.append(rel.replace(os.sep, "/"))
    return sorted(out)


def check_one_file(root: str, relpath: str,
                   checkers: Optional[Iterable[Checker]] = None
                   ) -> Tuple[List[Finding], int]:
    """Run every per-file checker over one file. Returns
    ``(findings, n_suppressed)`` — suppressions already applied (they
    are a property of the file text, so the pair caches as a unit).
    Unparsable files yield a single GL000 finding: a syntax error in
    lintable code is itself a finding, never a crash."""
    path = os.path.join(root, relpath)
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
    except (OSError, UnicodeDecodeError):
        return [], 0
    try:
        tree = ast.parse(text, filename=relpath)
    except SyntaxError as e:
        return [Finding(relpath, e.lineno or 0, e.offset or 0,
                        "GL000", "graftlint",
                        f"file does not parse: {e.msg}")], 0
    supp = suppressions_for_text(text)
    findings: List[Finding] = []
    n_supp = 0
    for ch in (checkers if checkers is not None else all_checkers()):
        if ch.repo_level:
            continue
        for f in ch.check_file(relpath, tree, text):
            if is_suppressed(f, supp):
                n_supp += 1
            else:
                findings.append(f)
    findings.sort(key=Finding.sort_key)
    return findings, n_supp


def run_checkers(root: str, relpaths: Optional[Iterable[str]] = None,
                 scoped: bool = True, cache=None,
                 with_repo_level: bool = True
                 ) -> Tuple[List[Finding], int]:
    """Run the suite. ``relpaths=None`` scans the whole tree;
    otherwise only the given files (still repo-relative). Returns
    ``(findings, n_suppressed)``; ``scoped`` applies the SCOPES table
    (fixture/explicit runs pass False to exercise every rule)."""
    if relpaths is None:
        relpaths = iter_target_files(root)
    findings: List[Finding] = []
    n_supp = 0
    for rel in relpaths:
        cached = cache.get(root, rel) if cache is not None else None
        if cached is not None:
            fs, ns = cached
        else:
            fs, ns = check_one_file(root, rel)
            if cache is not None:
                cache.put(root, rel, fs, ns)
        findings.extend(fs)
        n_supp += ns
    if with_repo_level:
        for ch in all_checkers():
            if ch.repo_level:
                findings.extend(ch.check_repo(root))
    if scoped:
        findings = [f for f in findings if in_scope(f.code, f.file)]
    findings.sort(key=Finding.sort_key)
    return findings, n_supp
