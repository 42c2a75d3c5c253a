"""graftlint core: finding format, repo index, allowlist, pass runner.

This package is the repo-native static analyzer (docs/static-analysis.md).
It is deliberately **JAX-free and import-light**: every pass works on
``ast`` trees plus raw source lines, so ``scripts/lint.py`` (and the tier-1
lint stage in ``scripts/tier1.sh``) runs in seconds without initializing a
backend — importing ``veomni_tpu.analysis`` must never be the thing that
claims a TPU chip, for exactly the reason ``utils/logging.py`` resolves
rank lazily.

Shared vocabulary:

* :class:`Finding` — one defect: ``(rule, path, line, symbol, message)``.
  ``rule`` is ``<family>/<check>`` (e.g. ``trace-purity/host-sync``);
  ``path`` is repo-relative POSIX; ``symbol`` the enclosing dotted
  function/class name (or ``<module>``).
* :class:`RepoIndex` — every analyzed ``.py`` file parsed once
  (:class:`SourceFile`: path, source, lines, AST). Passes share one index
  so a full lint parses the tree exactly once.
* :class:`Allowlist` — ``analysis/allowlist.toml``. Every entry carries a
  mandatory ``justification``; entries that match no *raw* finding are
  themselves findings (``allowlist/stale-entry``), so suppressions rot
  loudly instead of silently.
* :class:`Pass` — ``run(index) -> list[Finding]``. The registry
  (:data:`ALL_PASSES`) is what ``scripts/lint.py`` and
  ``tests/test_static_analysis.py`` iterate.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional

#: directories under the repo root whose .py files the index loads. Tests
#: and the lint fixtures are deliberately excluded: fixtures POSITIVELY
#: trigger rules (tests/test_static_analysis.py runs passes over them with
#: a dedicated index), and test code is allowed to be impure.
DEFAULT_SCAN_DIRS = ("veomni_tpu", "scripts", "tasks")
EXCLUDE_PARTS = ("__pycache__",)

#: default allowlist location, relative to the repo root
ALLOWLIST_PATH = os.path.join("veomni_tpu", "analysis", "allowlist.toml")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    symbol: str
    message: str

    def format(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{self.path}:{self.line}: {self.rule}{sym}: {self.message}"

    def to_doc(self) -> Dict[str, object]:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "symbol": self.symbol,
            "message": self.message,
        }


@dataclass
class SourceFile:
    """One parsed source file shared by every pass."""

    path: str  # repo-relative POSIX
    abspath: str
    source: str
    lines: List[str]
    tree: ast.AST
    #: dotted module name for files under veomni_tpu/ ("" for scripts)
    module: str


class RepoIndex:
    """Parse-once index of the analyzed tree.

    ``files`` maps repo-relative POSIX path -> :class:`SourceFile`;
    ``by_module`` maps dotted module name -> the same objects (only files
    that live under an importable package path get one).
    """

    def __init__(self, root: str, files: Dict[str, SourceFile]):
        self.root = root
        self.files = files
        self.by_module: Dict[str, SourceFile] = {
            sf.module: sf for sf in files.values() if sf.module
        }
        self._doc_cache: Dict[tuple, str] = {}

    @classmethod
    def load(cls, root: str,
             scan_dirs: Iterable[str] = DEFAULT_SCAN_DIRS) -> "RepoIndex":
        paths: List[str] = []
        for d in scan_dirs:
            base = os.path.join(root, d)
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames[:] = [n for n in dirnames if n not in EXCLUDE_PARTS]
                for fname in sorted(filenames):
                    if fname.endswith(".py"):
                        paths.append(os.path.join(dirpath, fname))
        files: Dict[str, SourceFile] = {}
        for abspath in sorted(paths):
            rel = os.path.relpath(abspath, root).replace(os.sep, "/")
            try:
                source = open(abspath, encoding="utf-8").read()
                tree = ast.parse(source, filename=rel)
            except (OSError, SyntaxError) as e:  # pragma: no cover - defensive
                raise RuntimeError(f"graftlint cannot parse {rel}: {e}") from e
            files[rel] = SourceFile(
                path=rel, abspath=abspath, source=source,
                lines=source.splitlines(), tree=tree,
                module=_module_name(rel),
            )
        return cls(root, files)

    def doc_text(self, *names: str) -> str:
        """Concatenated text of ``docs/<name>`` files (missing ones read as
        empty — the drift pass reports the missing token, not a crash).
        Memoized: the drift sub-gates each consult the docs, and one lint
        run must not re-read the directory per gate."""
        if names in self._doc_cache:
            return self._doc_cache[names]
        parts = []
        for name in names:
            p = os.path.join(self.root, "docs", name)
            if os.path.isfile(p):
                parts.append(open(p, encoding="utf-8").read())
        text = "\n".join(parts)
        self._doc_cache[names] = text
        return text

    def all_docs_text(self) -> str:
        docs_dir = os.path.join(self.root, "docs")
        names = []
        if os.path.isdir(docs_dir):
            names = sorted(n for n in os.listdir(docs_dir) if n.endswith(".md"))
        return self.doc_text(*names)


def _module_name(rel: str) -> str:
    if not rel.endswith(".py"):
        return ""
    parts = rel[:-3].split("/")
    if parts[0] != "veomni_tpu":
        return ""
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


# --------------------------------------------------------------------- TOML
# Python 3.10 on this image has no tomllib, and the hard constraints forbid
# new dependencies — so the allowlist grammar is the small TOML subset the
# file actually needs: ``[[allow]]`` array-of-tables with double-quoted
# basic-string values and ``#`` comments. Anything else is a parse error,
# loudly, so the file can't silently drift into unparsed suppressions.
def parse_allow_toml(text: str, origin: str = "allowlist.toml"
                     ) -> List[Dict[str, str]]:
    entries: List[Dict[str, str]] = []
    current: Optional[Dict[str, str]] = None
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line == "[[allow]]":
            current = {"_line": str(lineno)}
            entries.append(current)
            continue
        if line.startswith("["):
            raise ValueError(
                f"{origin}:{lineno}: only [[allow]] tables are supported, "
                f"got {line!r}"
            )
        if "=" not in line:
            raise ValueError(f"{origin}:{lineno}: expected key = \"value\"")
        if current is None:
            raise ValueError(
                f"{origin}:{lineno}: key outside an [[allow]] table"
            )
        key, _, value = line.partition("=")
        key = key.strip()
        value = value.strip()
        # strip a trailing comment OUTSIDE the quoted string
        if not (value.startswith('"') and value.count('"') >= 2):
            raise ValueError(
                f"{origin}:{lineno}: value for {key!r} must be a "
                f"double-quoted string"
            )
        current[key] = _parse_basic_string(value, origin, lineno)
    return entries


def _parse_basic_string(value: str, origin: str, lineno: int) -> str:
    out = []
    i = 1  # skip opening quote
    while i < len(value):
        c = value[i]
        if c == '"':
            rest = value[i + 1:].strip()
            if rest and not rest.startswith("#"):
                raise ValueError(
                    f"{origin}:{lineno}: trailing garbage after string"
                )
            return "".join(out)
        if c == "\\":
            i += 1
            if i >= len(value):
                break
            esc = value[i]
            out.append({"n": "\n", "t": "\t", '"': '"', "\\": "\\"}.get(
                esc, "\\" + esc
            ))
        else:
            out.append(c)
        i += 1
    raise ValueError(f"{origin}:{lineno}: unterminated string")


@dataclass
class AllowEntry:
    rule: str
    path: str
    match: str  # substring of symbol or message; "" matches any
    justification: str
    line: int
    hits: int = 0

    def matches(self, f: Finding) -> bool:
        if f.rule != self.rule or f.path != self.path:
            return False
        if not self.match:
            return True
        return self.match in f.symbol or self.match in f.message


class Allowlist:
    """The suppression policy (docs/static-analysis.md "Allowlist policy").

    Every entry needs ``rule``, ``path`` and a non-empty ``justification``;
    ``match`` narrows to findings whose symbol or message contains it.
    After filtering, :meth:`audit` turns policy violations into findings:
    a malformed entry, a missing justification, or a STALE entry (matched
    nothing this run — the code it excused is gone or fixed) each fail the
    gate, so the allowlist can only shrink honestly.
    """

    def __init__(self, entries: List[AllowEntry], origin: str,
                 errors: Optional[List[str]] = None):
        self.entries = entries
        self.origin = origin
        self.errors = errors or []

    @classmethod
    def load(cls, path: str) -> "Allowlist":
        origin = os.path.basename(path)
        if not os.path.isfile(path):
            return cls([], origin)
        errors: List[str] = []
        entries: List[AllowEntry] = []
        try:
            raw = parse_allow_toml(open(path, encoding="utf-8").read(), origin)
        except ValueError as e:
            return cls([], origin, errors=[str(e)])
        for doc in raw:
            line = int(doc.pop("_line", "0"))
            unknown = set(doc) - {"rule", "path", "match", "justification"}
            if unknown:
                errors.append(
                    f"{origin}:{line}: unknown key(s) {sorted(unknown)}"
                )
            if not doc.get("rule") or not doc.get("path"):
                errors.append(
                    f"{origin}:{line}: entry needs 'rule' and 'path'"
                )
                continue
            entries.append(AllowEntry(
                rule=doc.get("rule", ""), path=doc.get("path", ""),
                match=doc.get("match", ""),
                justification=doc.get("justification", ""), line=line,
            ))
        return cls(entries, origin, errors=errors)

    def filter(self, findings: List[Finding]) -> List[Finding]:
        """Remove allowlisted findings, counting hits per entry."""
        kept = []
        for f in findings:
            hit = None
            for e in self.entries:
                if e.matches(f):
                    hit = e
                    break
            if hit is not None:
                hit.hits += 1
            else:
                kept.append(f)
        return kept

    def audit(self) -> List[Finding]:
        """Policy findings about the allowlist itself (run AFTER filter)."""
        out = []
        rel = ALLOWLIST_PATH.replace(os.sep, "/")
        for err in self.errors:
            out.append(Finding(
                rule="allowlist/malformed", path=rel, line=0,
                symbol="", message=err,
            ))
        for e in self.entries:
            if not e.justification.strip():
                out.append(Finding(
                    rule="allowlist/missing-justification", path=rel,
                    line=e.line, symbol=e.rule,
                    message=(
                        f"entry for {e.rule} @ {e.path} has no justification "
                        "string — every suppression must say why"
                    ),
                ))
            if e.hits == 0:
                out.append(Finding(
                    rule="allowlist/stale-entry", path=rel, line=e.line,
                    symbol=e.rule,
                    message=(
                        f"entry for {e.rule} @ {e.path}"
                        + (f" (match={e.match!r})" if e.match else "")
                        + " matched no finding — the code it excused is gone;"
                        " delete the entry"
                    ),
                ))
        return out


# --------------------------------------------------------------------- passes
@dataclass
class Pass:
    name: str  # rule family, e.g. "trace-purity"
    description: str
    run: Callable[[RepoIndex], List[Finding]]


def get_passes() -> List[Pass]:
    """The pass registry, in run order. Imported lazily so ``core`` has no
    intra-package import cycle."""
    from veomni_tpu.analysis import drift, locks, purity, recompile

    return [
        Pass("trace-purity",
             "host syncs / impure constructs reachable from jitted code",
             purity.run),
        Pass("recompile-hazard",
             "unbucketed static args at jit call sites; python branches on "
             "traced values", recompile.run),
        Pass("lock-discipline",
             "# guarded-by: annotated state touched outside its lock",
             locks.run),
        Pass("drift",
             "metrics / train.* knobs / VEOMNI_* env knobs / fault points / "
             "registry ops absent from docs", drift.run),
    ]


@dataclass
class LintResult:
    findings: List[Finding]  # what failed the gate (post-allowlist + audit)
    raw_findings: List[Finding]  # everything the passes reported
    suppressed: int

    @property
    def ok(self) -> bool:
        return not self.findings


def run_lint(root: str, rules: Optional[str] = None,
             allowlist_path: Optional[str] = None,
             index: Optional[RepoIndex] = None) -> LintResult:
    """Run every pass (optionally filtered to rule prefix ``rules``) over
    ``root``, apply the allowlist, audit it, and return the result."""
    index = index or RepoIndex.load(root)
    passes = get_passes()
    if rules:
        passes = [p for p in passes
                  if p.name.startswith(rules) or rules.startswith(p.name)]
        if not passes:
            # a typo'd --rule must not run nothing and report clean
            raise ValueError(
                f"--rule {rules!r} matches no pass family "
                f"({', '.join(p.name for p in get_passes())})"
            )
    raw: List[Finding] = []
    for p in passes:
        raw.extend(p.run(index))
    if rules:
        # a full rule id (e.g. trace-purity/host-sync) narrows past the
        # pass family it selected; a bare family prefix keeps everything
        raw = [f for f in raw if f.rule.startswith(rules)]
    raw.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    if allowlist_path is None:
        allowlist_path = os.path.join(root, ALLOWLIST_PATH)
    allow = Allowlist.load(allowlist_path)
    kept = allow.filter(raw)
    audit = allow.audit() if rules is None else [
        f for f in allow.audit() if f.rule != "allowlist/stale-entry"
    ]  # a partial run can't judge staleness: unrun passes' entries idle
    return LintResult(findings=kept + audit, raw_findings=raw,
                      suppressed=len(raw) - len(kept))


# ------------------------------------------------------------------ AST utils
def qualname_map(tree: ast.AST) -> Dict[ast.AST, str]:
    """Map every function/class def node to its dotted qualname (classes and
    enclosing functions joined with '.'); shared by the passes' symbol
    labels."""
    out: Dict[ast.AST, str] = {}

    def walk(node: ast.AST, prefix: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                q = f"{prefix}.{child.name}" if prefix else child.name
                out[child] = q
                walk(child, q)
            else:
                walk(child, prefix)

    walk(tree, "")
    return out


def parent_map(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    out: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            out[child] = node
    return out


def enclosing_symbol(node: ast.AST, parents: Dict[ast.AST, ast.AST],
                     quals: Dict[ast.AST, str]) -> str:
    cur: Optional[ast.AST] = node
    while cur is not None:
        if cur in quals:
            return quals[cur]
        cur = parents.get(cur)
    return "<module>"


def attr_chain(node: ast.AST) -> Optional[List[str]]:
    """``a.b.c`` -> ["a", "b", "c"]; None for non-trivial bases."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        parts.reverse()
        return parts
    return None


def const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def fstring_prefix(node: ast.AST) -> Optional[str]:
    """Static prefix of an f-string (``f"span.{name}"`` -> ``"span."``);
    None if the node is not a JoinedStr."""
    if not isinstance(node, ast.JoinedStr):
        return None
    prefix = []
    for part in node.values:
        if isinstance(part, ast.Constant) and isinstance(part.value, str):
            prefix.append(part.value)
        else:
            break
    return "".join(prefix)
