"""graftlint core: findings, suppressions, the baseline ratchet, runner.

The reference system kept its Python/R wrappers honest by *generating*
them from the JVM API surface and failing the build on drift (PAPER.md
§0's autogenerated bindings).  This package is the same idea turned on
our own invariants: the properties the chaos soaks and the compile
sentry can only catch at runtime — jit purity, lock discipline,
registry/telemetry drift, resource hygiene — are checked from the AST
before a single chip cycle is spent.

Mechanics shared by every pass live here:

* **Findings** carry a rule id (G1xx–G4xx, plus the M001/M002 ids
  inherited from the old metrics-lint), `file:line`, the enclosing
  symbol (``Class.method`` — baseline keys must survive line drift),
  and a one-line fix hint.
* **Suppressions**: ``# graftlint: disable=G102`` (comma-separated, or
  ``disable=all``) on the finding's line or the line directly above.
  Use for *justified-forever* exceptions — the comment next to the
  disable is the justification reviewers read.
* **Baseline** (`tools/graftlint_baseline.json`): legacy findings that
  are deliberately deferred, keyed ``rule::file::symbol`` with a count.
  New findings fail; baselined findings pass (and are counted); a
  baseline entry with no surviving finding is *stale* and fails too
  (rule B001) so the ratchet only ever tightens.
"""
from __future__ import annotations

import ast
import glob
import json
import os
import re
import tokenize
from dataclasses import dataclass, field
from io import StringIO
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["Finding", "SourceFile", "ModuleGraph", "collect_files",
           "load_source", "module_name", "load_baseline",
           "apply_baseline", "baseline_key", "write_baseline",
           "format_findings", "format_sarif", "changed_files",
           "needs_full_scan", "canonical_rule", "rule_ids",
           "DEFAULT_TARGETS", "RULE_DOCS", "RULE_ALIASES"]

# the scanned tree (relative to repo root); mirrors the old metrics-lint
# scope plus examples/ — tests/ are deliberately out (fixtures violate
# rules on purpose)
DEFAULT_TARGETS: Tuple[str, ...] = ("mmlspark_tpu", "tools", "examples",
                                    "__graft_entry__.py")

# one suppression grammar per tool: graftlint's static passes and
# graftsan's runtime sanitizer (tools/graftsan) share the mechanics but
# must not consume each other's disables
def _suppress_re(marker: str) -> "re.Pattern":
    return re.compile(r"#\s*%s:\s*disable=([A-Za-z0-9_,\s]+)"
                      % re.escape(marker))


_SUPPRESS = _suppress_re("graftlint")

# one-line catalog used by --rules and docs/static_analysis.md; the
# authoritative prose (bad/good examples) lives in the doc.
RULE_DOCS: Dict[str, str] = {
    "G101": "host side effect (telemetry/fault-point call) reachable "
            "inside a jit/pjit/shard_map trace",
    "G102": "time.* call reachable inside a trace",
    "G103": "host RNG (random.* / np.random.*) reachable inside a trace",
    "G104": "print() reachable inside a trace",
    "G105": "lock acquisition reachable inside a trace",
    "G106": "host sync (.item()/block_until_ready/device_get) on a "
            "traced value",
    "G201": "guarded attribute written without its declared lock held",
    "G202": "guarded attribute read without its declared lock held",
    "G203": "guarded-by annotation names a lock never assigned in "
            "__init__",
    "G301": "fault_point() call site missing from the "
            "docs/robustness.md registry table",
    "G302": "docs/robustness.md registry row with no fault_point() "
            "call site",
    "M001": "instrumented metric name not in DECLARED_METRICS",
    "M002": "two declared metric names sanitize to one Prometheus name",
    "M003": "declared histogram not pinned to a named bucket family "
            "(HISTOGRAM_FAMILY / BUCKET_FAMILIES)",
    "M004": "SAMPLED_SERIES entry references an undeclared or "
            "re-kinded metric (the sampler would record zeros forever)",
    "G303": "span name violates the lowercase dotted naming convention",
    "G304": "bounded-queue class declares no queue-depth/shed telemetry",
    "G501": "SPMD axis literal (PartitionSpec entry or collective "
            "axis_name) not declared in MESH_AXIS_NAMES or bound by the "
            "enclosing pmap/Mesh (a typo silently replicates the leaf); "
            "alias: G305",
    "G502": "unreachable partition rule: its regex is subsumed by an "
            "earlier first-match-wins table entry",
    "G503": "partition-rule table / PARAM_PATH_MANIFEST coverage gap "
            "(a leaf path no rule matches raises at shard time)",
    "G504": "use-after-donate: a buffer passed to a donating jit is "
            "read again afterwards (XLA may have reused its memory)",
    "G401": "thread created without an explicit name=",
    "G402": "non-daemon thread named outside the conftest leak-check "
            "prefixes",
    "G403": "unbounded Queue() on a serving/io path",
    "G404": "durable write not using the tmp+fsync+rename idiom",
    "G405": "Stage subclass without a bounded credit budget or with "
            "undeclared per-stage flow.* metrics",
    "B001": "stale baseline entry (the finding it excused is gone)",
}

# Renamed rules: old id -> canonical id.  G305 (PartitionSpec axis
# hygiene) grew into the G5 SPMD family as G501; the old id keeps
# resolving everywhere an id appears (suppression comments, baseline
# keys, --rules filters) so PR 17-era annotations survive verbatim.
RULE_ALIASES: Dict[str, str] = {"G305": "G501"}


def canonical_rule(rule: str) -> str:
    return RULE_ALIASES.get(rule, rule)


def rule_ids(rule: str) -> Tuple[str, ...]:
    """A rule's canonical id plus every alias that maps to it — the set
    an id-based filter (suppressions, --rules prefixes) matches on."""
    canon = canonical_rule(rule)
    return (canon,) + tuple(sorted(
        a for a, c in RULE_ALIASES.items() if c == canon))


@dataclass
class Finding:
    rule: str
    path: str          # repo-relative, '/'-separated
    line: int
    message: str
    hint: str = ""
    symbol: str = "<module>"

    def render(self) -> str:
        out = f"{self.path}:{self.line}: {self.rule} {self.message}"
        if self.hint:
            out += f" (hint: {self.hint})"
        return out

    def as_dict(self) -> Dict[str, object]:
        return {"rule": self.rule, "file": self.path, "line": self.line,
                "symbol": self.symbol, "message": self.message,
                "hint": self.hint}


class SourceFile:
    """One parsed source file + everything the passes need from it:
    raw lines, suppression map, and a line→enclosing-symbol index."""

    def __init__(self, path: str, rel: str, src: str,
                 marker: str = "graftlint"):
        self.path = path
        self.rel = rel.replace(os.sep, "/")
        self.src = src
        self.lines = src.splitlines()
        # the disable-comment grammar this file answers `suppressed()`
        # for: "graftlint" (static passes) or "graftsan" (the runtime
        # sanitizer reuses this class for its own suppression checks)
        self._marker_re = (_SUPPRESS if marker == "graftlint"
                           else _suppress_re(marker))
        try:
            self.tree: Optional[ast.Module] = ast.parse(src, filename=path)
        except SyntaxError:
            # the style linter owns E999; passes skip unparseable files
            self.tree = None
        self._suppress = self._parse_suppressions()
        self._symbols = self._index_symbols() if self.tree else []

    # ---- suppressions --------------------------------------------------
    def _parse_suppressions(self) -> Dict[int, set]:
        """line -> set of suppressed rule ids ('all' wildcard allowed).
        Comments are found via tokenize so a '# graftlint:' inside a
        string literal never suppresses anything."""
        out: Dict[int, set] = {}
        try:
            toks = tokenize.generate_tokens(StringIO(self.src).readline)
            for tok in toks:
                if tok.type != tokenize.COMMENT:
                    continue
                m = self._marker_re.search(tok.string)
                if not m:
                    continue
                rules = {r.strip() for r in m.group(1).split(",")
                         if r.strip()}
                out.setdefault(tok.start[0], set()).update(rules)
        except (tokenize.TokenError, IndentationError):
            pass
        return out

    def suppressed(self, rule: str, line: int) -> bool:
        """A disable on the finding's own line, or on the line directly
        above it (the own-line form collides with long lines).  Alias
        ids count: disable=G305 suppresses G501 and vice versa."""
        ids = rule_ids(rule)
        for at in (line, line - 1):
            rules = self._suppress.get(at)
            if rules and ("all" in rules
                          or any(r in rules for r in ids)):
                return True
        return False

    # ---- symbol index --------------------------------------------------
    def _index_symbols(self) -> List[Tuple[int, int, str]]:
        spans: List[Tuple[int, int, str]] = []

        def walk(node, prefix: str):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                    qual = f"{prefix}.{child.name}" if prefix else child.name
                    end = getattr(child, "end_lineno", child.lineno)
                    spans.append((child.lineno, end, qual))
                    walk(child, qual)
                else:
                    walk(child, prefix)

        walk(self.tree, "")
        return spans

    def symbol_at(self, line: int) -> str:
        best = "<module>"
        best_len = None
        for start, end, qual in self._symbols:
            if start <= line <= end:
                length = end - start
                if best_len is None or length <= best_len:
                    best, best_len = qual, length
        return best

    def finding(self, rule: str, line: int, message: str,
                hint: str = "") -> Finding:
        return Finding(rule=rule, path=self.rel, line=line,
                       message=message, hint=hint,
                       symbol=self.symbol_at(line))


def load_source(path: str, root: str) -> SourceFile:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    return SourceFile(path, os.path.relpath(path, root), src)


def collect_files(root: str,
                  targets: Sequence[str] = DEFAULT_TARGETS
                  ) -> List[SourceFile]:
    paths: List[str] = []
    for t in targets:
        p = os.path.join(root, t)
        if os.path.isfile(p):
            paths.append(p)
        else:
            paths.extend(sorted(glob.glob(
                os.path.join(p, "**", "*.py"), recursive=True)))
    return [load_source(p, root) for p in paths]


# ---------------------------------------------------- cross-module graph

def module_name(rel: str) -> str:
    """Dotted module name for a repo-relative path:
    ``mmlspark_tpu/models/training.py`` -> ``mmlspark_tpu.models.training``,
    a package ``__init__.py`` -> the package itself, ``chip_smoke.py``
    -> ``chip_smoke``."""
    rel = rel.replace(os.sep, "/")
    if rel.endswith(".py"):
        rel = rel[:-3]
    if rel.endswith("/__init__"):
        rel = rel[: -len("/__init__")]
    return rel.replace("/", ".")


class ModuleGraph:
    """Import-aware symbol resolution across the scanned file set — the
    upgrade from the per-module analysis the G1 pass started with.

    Resolution is deliberately conservative, mirroring the no-dynamic-
    dispatch stance of the call-graph passes: only top-level ``def``s
    are resolvable targets, only static ``import`` / ``from .. import``
    tables create bindings, and re-exports are chased through package
    ``__init__.py`` from-imports with a bounded hop count.  Anything
    dynamic (``getattr``, ``importlib``, star imports, assignments of
    callables) resolves to nothing — a missed edge, never a false one.
    """

    _MAX_HOPS = 4  # re-export chains (pkg __init__ -> submodule -> ...)

    def __init__(self, files: Sequence["SourceFile"]):
        self.by_module: Dict[str, SourceFile] = {}
        self.module_of: Dict[SourceFile, str] = {}
        self._defs: Dict[str, Dict[str, ast.AST]] = {}
        self._pkg: Dict[str, str] = {}         # module -> its package
        self._imports: Dict[str, Dict[str, str]] = {}
        self._from: Dict[str, Dict[str, Tuple[str, str]]] = {}
        for sf in files:
            if sf.tree is None:
                continue
            mod = module_name(sf.rel)
            self.by_module[mod] = sf
            self.module_of[sf] = mod
            is_pkg = sf.rel.endswith("__init__.py")
            self._pkg[mod] = mod if is_pkg else mod.rpartition(".")[0]
            self._defs[mod] = {
                n.name: n for n in sf.tree.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef))}
        for mod, sf in self.by_module.items():
            self._index_imports(mod, sf)

    # ---- import tables -------------------------------------------------
    def _rel_base(self, mod: str, level: int) -> Optional[str]:
        """The module a level-N relative import is anchored at."""
        base = self._pkg.get(mod, "")
        for _ in range(level - 1):
            if not base:
                return None
            base = base.rpartition(".")[0]
        return base or None

    def _index_imports(self, mod: str, sf: "SourceFile") -> None:
        ims: Dict[str, str] = {}
        frm: Dict[str, Tuple[str, str]] = {}
        for node in sf.tree.body:
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        ims[a.asname] = a.name
                    else:
                        # `import a.b.c` binds `a`; the full dotted path
                        # is reassembled at resolve time
                        head = a.name.split(".", 1)[0]
                        ims.setdefault(head, head)
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base = self._rel_base(mod, node.level)
                    if base is None:
                        continue
                    src = (base + "." + node.module if node.module
                           else base)
                else:
                    src = node.module or ""
                for a in node.names:
                    if a.name == "*":
                        continue
                    local = a.asname or a.name
                    # `from pkg import submodule` is a module binding,
                    # not a symbol binding, when the submodule is ours
                    if src + "." + a.name in self.by_module:
                        ims[local] = src + "." + a.name
                    else:
                        frm[local] = (src, a.name)
        self._imports[mod] = ims
        self._from[mod] = frm

    # ---- resolution ----------------------------------------------------
    def _lookup(self, mod: str, name: str, hops: int = 0
                ) -> Optional[Tuple["SourceFile", ast.AST, str]]:
        if hops > self._MAX_HOPS or mod not in self.by_module:
            return None
        node = self._defs.get(mod, {}).get(name)
        if node is not None:
            return (self.by_module[mod], node, mod)
        reexp = self._from.get(mod, {}).get(name)
        if reexp is not None:
            return self._lookup(reexp[0], reexp[1], hops + 1)
        return None

    def resolve(self, sf: "SourceFile", dotted: str
                ) -> Optional[Tuple["SourceFile", ast.AST, str]]:
        """(defining file, def node, defining module) for a dotted name
        as written in `sf` — ``helper``, ``mod.helper``,
        ``pkg.mod.helper`` — or None when it isn't statically ours."""
        mod = self.module_of.get(sf)
        if mod is None or not dotted:
            return None
        parts = dotted.split(".")
        head, rest = parts[0], parts[1:]
        frm = self._from.get(mod, {})
        ims = self._imports.get(mod, {})
        if not rest:
            hit = frm.get(head)
            return self._lookup(*hit) if hit else None
        if head in ims:
            base = ims[head]
        elif head in frm:
            src, orig = frm[head]
            cand = (src + "." + orig) if src else orig
            if cand not in self.by_module:
                return None
            base = cand
        else:
            return None
        target_mod = ".".join([base] + rest[:-1])
        return self._lookup(target_mod, rest[-1])

    def source_module(self, sf: "SourceFile", name: str) -> str:
        """Best-effort dotted source module for a local name (import
        alias or from-import origin) — '' when unknown/local."""
        mod = self.module_of.get(sf)
        if mod is None:
            return ""
        ims = self._imports.get(mod, {})
        if name in ims:
            return ims[name]
        frm = self._from.get(mod, {})
        if name in frm:
            return frm[name][0]
        return ""

    def from_binding(self, sf: "SourceFile",
                     name: str) -> Optional[Tuple[str, str]]:
        """(source module, original name) when `name` is a from-import
        in `sf` — lets passes resolve imported values (e.g. a jit
        wrapper bound by assignment) that aren't top-level defs."""
        mod = self.module_of.get(sf)
        if mod is None:
            return None
        return self._from.get(mod, {}).get(name)

    def alias_target(self, sf: "SourceFile",
                     name: str) -> Optional[str]:
        """The dotted module a local name aliases (``import x.y as z``
        / ``from pkg import submodule``), else None."""
        mod = self.module_of.get(sf)
        if mod is None:
            return None
        return self._imports.get(mod, {}).get(name)


# ---------------------------------------------------------------- baseline

def baseline_key(f: Finding) -> str:
    """Line numbers drift on every edit; (rule, file, enclosing symbol)
    survives reformatting while still pinning the finding to one spot."""
    return f"{f.rule}::{f.path}::{f.symbol}"


def load_baseline(path: str) -> Dict[str, Dict[str, object]]:
    """baseline key -> {'count': n, 'why': str}.  A missing file is an
    empty baseline (the strictest ratchet position)."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except FileNotFoundError:
        return {}
    out: Dict[str, Dict[str, object]] = {}
    for e in doc.get("findings", []):
        # alias-normalize so a PR 17-era G305 entry excuses the same
        # finding now reported as G501
        rule = canonical_rule(e["rule"])
        key = f"{rule}::{e['file']}::{e.get('symbol', '<module>')}"
        if key in out:
            out[key]["count"] = int(out[key]["count"]) + int(
                e.get("count", 1))
        else:
            out[key] = {"count": int(e.get("count", 1)),
                        "why": e.get("why", "")}
    return out


def write_baseline(path: str, findings: Iterable[Finding]) -> None:
    counts: Dict[str, int] = {}
    meta: Dict[str, Finding] = {}
    for f in findings:
        k = baseline_key(f)
        counts[k] = counts.get(k, 0) + 1
        meta.setdefault(k, f)
    entries = []
    for k in sorted(counts):
        f = meta[k]
        entries.append({"rule": f.rule, "file": f.path,
                        "symbol": f.symbol, "count": counts[k],
                        "why": "TODO: justify or fix"})
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"version": 1, "findings": entries}, fh, indent=2)
        fh.write("\n")


@dataclass
class BaselineResult:
    new: List[Finding] = field(default_factory=list)
    baselined: List[Finding] = field(default_factory=list)
    stale: List[Finding] = field(default_factory=list)


def apply_baseline(findings: Sequence[Finding],
                   baseline: Dict[str, Dict[str, object]]
                   ) -> BaselineResult:
    """Split findings into new (fail) vs baselined (pass); surface
    baseline entries whose findings no longer exist as B001 (fail, so a
    fixed hazard forces its excuse to be deleted — the ratchet)."""
    res = BaselineResult()
    used: Dict[str, int] = {}
    for f in findings:
        k = baseline_key(f)
        allowed = int(baseline.get(k, {}).get("count", 0))
        if used.get(k, 0) < allowed:
            used[k] = used.get(k, 0) + 1
            res.baselined.append(f)
        else:
            res.new.append(f)
    for k, e in sorted(baseline.items()):
        excess = int(e["count"]) - used.get(k, 0)
        if excess > 0:
            rule, path, symbol = k.split("::", 2)
            res.stale.append(Finding(
                rule="B001", path=path, line=0, symbol=symbol,
                message=f"stale baseline entry for {rule} in {symbol} "
                        f"({excess} excused finding(s) no longer "
                        f"reported)",
                hint="delete the entry from tools/graftlint_baseline.json"))
    return res


def format_findings(res: BaselineResult, json_out: bool = False,
                    tool: str = "graftlint") -> str:
    """Render a baseline-applied result; `tool` names the producer in
    the summary line and JSON (graftsan reuses this verbatim so both
    analyzers report through one `tools/ci.py` surface)."""
    if json_out:
        return json.dumps({
            "tool": tool,
            "findings": [f.as_dict() for f in res.new],
            "stale_baseline": [f.as_dict() for f in res.stale],
            "baselined_count": len(res.baselined),
            "ok": not (res.new or res.stale),
        }, indent=2)
    out: List[str] = []
    for f in sorted(res.new, key=lambda f: (f.path, f.line, f.rule)):
        out.append(f.render())
    for f in res.stale:
        out.append(f.render())
    n_bad = len(res.new) + len(res.stale)
    if n_bad:
        out.append(f"{tool}: {n_bad} problem(s) "
                   f"({len(res.new)} new, {len(res.stale)} stale "
                   f"baseline, {len(res.baselined)} baselined)")
    else:
        out.append(f"{tool}: clean "
                   f"({len(res.baselined)} baselined finding(s))")
    return "\n".join(out)


# ------------------------------------------------------ incremental mode

# A change to any of these invalidates results for UNCHANGED files too:
# the analyzer itself, or a registry surface other files are checked
# against (mesh axes, rule tables, metric tables, the fault-point doc,
# the leak-check prefixes, the baseline).
_FULL_SCAN_PREFIXES = ("tools/graftlint/",)
_FULL_SCAN_FILES = frozenset({
    "tools/graftlint_baseline.json",
    "tools/ci.py",
    "mmlspark_tpu/core/telemetry/metrics.py",
    "mmlspark_tpu/parallel/mesh.py",
    "mmlspark_tpu/parallel/sharding_rules.py",
    "docs/robustness.md",
    "tests/conftest.py",
})


def changed_files(root: str) -> Optional[Set[str]]:
    """Repo-relative paths changed vs HEAD (staged + unstaged +
    untracked); when the worktree is clean, the last commit's paths
    (post-commit CI still lints what just landed).  None = "could not
    tell" (not a git checkout, no git) — callers fall back to a full
    scan."""
    import subprocess

    def git(*args: str) -> Optional[List[str]]:
        try:
            out = subprocess.run(
                ("git",) + args, cwd=root, capture_output=True,
                text=True, timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            return None
        if out.returncode != 0:
            return None
        return [ln.strip() for ln in out.stdout.splitlines()
                if ln.strip()]

    diff = git("diff", "--name-only", "HEAD")
    untracked = git("ls-files", "--others", "--exclude-standard")
    if diff is None or untracked is None:
        return None
    changed = set(diff) | set(untracked)
    if not changed:
        last = git("diff", "--name-only", "HEAD~1", "HEAD")
        changed = set(last or ())
    return {p.replace(os.sep, "/") for p in changed}


def needs_full_scan(changed: Optional[Set[str]]) -> bool:
    if changed is None:
        return True
    return any(p in _FULL_SCAN_FILES
               or p.startswith(_FULL_SCAN_PREFIXES)
               for p in changed)


# ------------------------------------------------------------------ SARIF

_SARIF_VERSION = "2.1.0"
_SARIF_SCHEMA = ("https://raw.githubusercontent.com/oasis-tcs/"
                 "sarif-spec/master/Schemata/sarif-schema-2.1.0.json")


def format_sarif(res: BaselineResult, tool: str = "graftlint") -> str:
    """SARIF 2.1.0 for diff annotation: one run, one rule entry per
    distinct rule id, one result per non-baselined finding (stale
    baseline entries ride along as B001 results at line 1)."""
    reported = list(res.new) + list(res.stale)
    rules = sorted({f.rule for f in reported})
    results = []
    for f in sorted(reported, key=lambda f: (f.path, f.line, f.rule)):
        msg = f.message + (f" (hint: {f.hint})" if f.hint else "")
        results.append({
            "ruleId": f.rule,
            "level": "error",
            "message": {"text": msg},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": f.path},
                    "region": {"startLine": max(f.line, 1)},
                },
            }],
        })
    doc = {
        "$schema": _SARIF_SCHEMA,
        "version": _SARIF_VERSION,
        "runs": [{
            "tool": {"driver": {
                "name": tool,
                "informationUri":
                    "docs/static_analysis.md",
                "rules": [{
                    "id": r,
                    "shortDescription": {
                        "text": RULE_DOCS.get(r, r)},
                } for r in rules],
            }},
            "results": results,
        }],
    }
    return json.dumps(doc, indent=2)
