"""graftlint — AST-based JAX correctness/performance lint for this repo.

The framework's value proposition is that evolution runs as *compiled XLA
programs* (functional ask-tell states, jitted distribution math, one
``lax.while_loop`` rollout), so its worst bugs are the ones Python never
raises: silent retraces that turn a flagship step into a recompile storm,
PRNG key reuse that correlates "independent" samples, host-device syncs
hiding in hot loops, dtype/axis-name drift across ``shard_map`` boundaries.
This module is the machinery: finding/ baseline bookkeeping, module parsing
(import-alias resolution, symbol tables), and the runner. The checkers
themselves live in :mod:`evotorch_tpu.analysis.checkers`; the runtime
counterpart (compile counting) in
:mod:`evotorch_tpu.analysis.retrace_sentinel`.

Pure stdlib (``ast``/``json``) — linting never imports jax, so it runs in
milliseconds per file and needs no device.

Baselines: a finding's :attr:`Finding.signature` deliberately excludes the
line number, so unrelated edits moving code around do not churn
``baseline.json``; matching is multiset-aware (two identical-signature
findings need two baseline entries).

Scoped exemptions: a ``# graftlint: allow(<checker>): <reason>`` comment on
(or immediately above) the offending line suppresses that checker there —
the in-code alternative to a baseline entry for *intentional* violations
(e.g. the host pipeline's swap-point syncs). The reason is mandatory: a
reasonless allow is itself reported as a ``lint-allow`` finding.
"""

from __future__ import annotations

import ast
import io
import json
import re
import tokenize
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Finding",
    "ModuleInfo",
    "ProjectInfo",
    "run_lint",
    "lint_sources",
    "load_baseline",
    "save_baseline",
    "apply_baseline",
    "default_targets",
    "default_baseline_path",
    "repo_root",
    "scoped_allows",
]


# ---------------------------------------------------------------------------
# findings + baseline
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Finding:
    """One lint finding. ``detail`` is the stable, line-independent part of
    the identity (typically the offending symbol/pattern), so baselines
    survive unrelated line drift."""

    checker: str
    path: str  # repo-relative posix path
    line: int
    symbol: str  # enclosing function qualname, or "<module>"
    message: str
    detail: str = ""

    @property
    def signature(self) -> str:
        return f"{self.path}::{self.checker}::{self.symbol}::{self.detail}"

    def format(self) -> str:
        return f"{self.path}:{self.line}: [{self.checker}] {self.symbol}: {self.message}"

    def to_json(self) -> dict:
        return {
            "checker": self.checker,
            "path": self.path,
            "line": self.line,
            "symbol": self.symbol,
            "message": self.message,
            "detail": self.detail,
            "signature": self.signature,
        }


def load_baseline(path) -> List[dict]:
    """Baseline file: ``{"findings": [{"signature": ..., "reason": ...}]}``."""
    data = json.loads(Path(path).read_text())
    return list(data.get("findings", []))


def save_baseline(path, findings: Sequence[Finding], *, reasons: Optional[dict] = None):
    reasons = reasons or {}
    entries = [
        {
            "signature": f.signature,
            "reason": reasons.get(f.signature, ""),
            # message kept for human readers only; matching is by signature
            "message": f.message,
        }
        for f in sorted(findings, key=lambda f: (f.path, f.checker, f.line))
    ]
    Path(path).write_text(json.dumps({"findings": entries}, indent=2) + "\n")


def apply_baseline(
    findings: Sequence[Finding], baseline: Sequence[dict]
) -> Tuple[List[Finding], List[dict]]:
    """Split findings into (new, stale-baseline-entries). Multiset matching:
    each baseline entry absorbs at most one finding with its signature."""
    budget = Counter(e["signature"] for e in baseline)
    new: List[Finding] = []
    for f in findings:
        if budget.get(f.signature, 0) > 0:
            budget[f.signature] -= 1
        else:
            new.append(f)
    stale_sigs = Counter()
    for sig, n in budget.items():
        if n > 0:
            stale_sigs[sig] = n
    stale = []
    seen: Counter = Counter()
    for e in baseline:
        sig = e["signature"]
        if seen[sig] < stale_sigs.get(sig, 0):
            stale.append(e)
            seen[sig] += 1
    return new, stale


# ---------------------------------------------------------------------------
# scoped allow-comments
# ---------------------------------------------------------------------------

#: `# graftlint: allow(checker[, checker...])` with an optional `: reason`
_ALLOW_RE = re.compile(
    r"#\s*graftlint:\s*allow\(\s*([a-z0-9_\-\s,]+?)\s*\)\s*(?::\s*(\S.*))?$"
)


def scoped_allows(path: str, source: str) -> Tuple[Dict[int, set], List[Finding]]:
    """Parse ``# graftlint: allow(...)`` comments (real COMMENT tokens only —
    allow-syntax inside string literals is inert). Returns
    ``({line: {checker, ...}}, reasonless-allow findings)``. A trailing allow
    covers its own line; a standalone allow-comment line covers the next
    line — never both, so one allow cannot silently wave through an
    adjacent, unrelated violation."""
    allows: Dict[int, set] = {}
    problems: List[Finding] = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return allows, problems  # unparsable source is reported elsewhere
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _ALLOW_RE.search(tok.string)
        if m is None:
            continue
        lineno = tok.start[0]
        checkers = {c.strip() for c in m.group(1).split(",") if c.strip()}
        if not m.group(2):
            problems.append(
                Finding(
                    checker="lint-allow",
                    path=path,
                    line=lineno,
                    symbol="<comment>",
                    message=(
                        "graftlint allow-comment without a reason — write"
                        " `# graftlint: allow(<checker>): <why this is"
                        " intentional>`"
                    ),
                    detail="missing-reason",
                )
            )
            continue
        trailing = bool(tok.line[: tok.start[1]].strip())  # code before the '#'
        covered = lineno if trailing else lineno + 1
        allows.setdefault(covered, set()).update(checkers)
    return allows, problems


def _apply_scoped_allows(
    findings: List[Finding], allows_by_path: Dict[str, Dict[int, set]]
) -> List[Finding]:
    kept = []
    for f in findings:
        allowed = allows_by_path.get(f.path, {}).get(f.line, ())
        if f.checker not in allowed:
            kept.append(f)
    return kept


# ---------------------------------------------------------------------------
# module / project models
# ---------------------------------------------------------------------------


def _attach_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._gl_parent = node  # type: ignore[attr-defined]


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclass
class ModuleInfo:
    path: str  # repo-relative posix
    tree: ast.Module
    aliases: Dict[str, str] = field(default_factory=dict)
    # top-level function defs (incl. simple `x = y` aliases of them)
    defs: Dict[str, ast.AST] = field(default_factory=dict)
    name_aliases: Dict[str, str] = field(default_factory=dict)

    @classmethod
    def parse(cls, path: str, source: str) -> "ModuleInfo":
        tree = ast.parse(source, filename=path)
        _attach_parents(tree)
        info = cls(path=path, tree=tree)
        info._collect_imports()
        info._collect_defs()
        return info

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
                for a in node.names:
                    if a.name == "*":
                        continue
                    self.aliases[a.asname or a.name] = f"{node.module}.{a.name}"

    def _collect_defs(self) -> None:
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs[node.name] = node
        # simple name aliases are collected module-WIDE (bench drivers pick
        # their ask/tell implementations inside main()); first binding wins
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign):
                # `tell = pgpe_tell` / `ask, tell = pgpe_ask, pgpe_tell` /
                # chained `a = b = pgpe_tell`
                for target in node.targets:
                    if (
                        isinstance(target, ast.Tuple)
                        and isinstance(node.value, ast.Tuple)
                        and len(target.elts) == len(node.value.elts)
                    ):
                        pairs = zip(target.elts, node.value.elts)
                    else:
                        pairs = [(target, node.value)]
                    for tgt, val in pairs:
                        if isinstance(tgt, ast.Name) and isinstance(val, ast.Name):
                            self.name_aliases.setdefault(tgt.id, val.id)

    def canon(self, node: ast.AST) -> Optional[str]:
        """Canonical dotted name of a Name/Attribute chain, with the leading
        segment expanded through this module's import aliases
        (``jnp.asarray`` -> ``jax.numpy.asarray``)."""
        name = dotted_name(node)
        if name is None:
            return None
        head, _, rest = name.partition(".")
        expanded = self.aliases.get(head, head)
        return f"{expanded}.{rest}" if rest else expanded

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        cur = getattr(node, "_gl_parent", None)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                return cur
            cur = getattr(cur, "_gl_parent", None)
        return None

    def symbol_for(self, node: ast.AST) -> str:
        names = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                names.append(cur.name)
            elif isinstance(cur, ast.Lambda):
                names.append("<lambda>")
            cur = getattr(cur, "_gl_parent", None)
        return ".".join(reversed(names)) if names else "<module>"

    def enclosing_loops(self, node: ast.AST) -> List[ast.AST]:
        """Loops strictly containing ``node``, innermost-first, stopping at
        the enclosing function boundary."""
        loops = []
        cur = getattr(node, "_gl_parent", None)
        while cur is not None:
            if isinstance(cur, (ast.For, ast.While)):
                loops.append(cur)
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                break
            cur = getattr(cur, "_gl_parent", None)
        return loops

    def finding(self, checker: str, node: ast.AST, message: str, detail: str) -> Finding:
        return Finding(
            checker=checker,
            path=self.path,
            line=getattr(node, "lineno", 0),
            symbol=self.symbol_for(node),
            message=message,
            detail=detail,
        )


@dataclass
class ProjectInfo:
    modules: List[ModuleInfo] = field(default_factory=list)
    #: mesh axis names declared anywhere (Mesh(..., axis_names=...),
    #: make_mesh({...}) keys, default_mesh((...)), `axis_name="..."` defaults)
    axis_names: set = field(default_factory=set)
    #: module-level function name -> first positional parameter name
    func_first_param: Dict[str, str] = field(default_factory=dict)
    #: module-level function name -> body contains jax/jnp operations
    func_uses_jax: Dict[str, bool] = field(default_factory=dict)

    @classmethod
    def build(cls, modules: Iterable[ModuleInfo]) -> "ProjectInfo":
        project = cls(modules=list(modules))
        for mod in project.modules:
            project._collect_symbols(mod)
            project._collect_axis_names(mod)
        return project

    def _collect_symbols(self, mod: ModuleInfo) -> None:
        for name, node in mod.defs.items():
            args = node.args
            params = list(args.posonlyargs) + list(args.args)
            if params and params[0].arg not in ("self", "cls"):
                self.func_first_param.setdefault(name, params[0].arg)
            uses = False
            for sub in ast.walk(node):
                canon = mod.canon(sub) if isinstance(sub, (ast.Name, ast.Attribute)) else None
                if canon and (canon == "jax" or canon.startswith(("jax.", "jax_"))):
                    uses = True
                    break
            if uses:
                self.func_uses_jax[name] = True

    def _collect_axis_names(self, mod: ModuleInfo) -> None:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                canon = mod.canon(node.func) or ""
                tail = canon.rsplit(".", 1)[-1]
                if tail == "Mesh":
                    for kw in node.keywords:
                        if kw.arg == "axis_names":
                            self._add_str_elts(kw.value)
                    if len(node.args) >= 2:
                        self._add_str_elts(node.args[1])
                elif tail == "make_mesh" and node.args:
                    arg = node.args[0]
                    if isinstance(arg, ast.Dict):
                        for k in arg.keys:
                            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                                self.axis_names.add(k.value)
                elif tail == "default_mesh" and node.args:
                    self._add_str_elts(node.args[0])
            elif isinstance(node, ast.Assign):
                # a module-level `MESH_AXES = ("pop", "model")` declaration
                # (parallel/mesh.py) is the canonical axis registry: every
                # name it lists is a known axis, so new axes are introduced
                # by declaration, not by growing the lint baseline
                for target in node.targets:
                    if isinstance(target, ast.Name) and target.id == "MESH_AXES":
                        self._add_str_elts(node.value)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                params = list(args.posonlyargs) + list(args.args)
                defaults = list(args.defaults)
                # align defaults to the tail of params
                pairs = list(zip(params[len(params) - len(defaults):], defaults))
                pairs += [
                    (p, d) for p, d in zip(args.kwonlyargs, args.kw_defaults) if d is not None
                ]
                for param, default in pairs:
                    if param.arg in ("axis_name", "axis_names"):
                        self._add_str_elts(default)

    def _add_str_elts(self, node: ast.AST) -> None:
        if isinstance(node, (ast.Tuple, ast.List)):
            for elt in node.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    self.axis_names.add(elt.value)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            self.axis_names.add(node.value)


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------


def repo_root() -> Path:
    """The repository root, assuming the canonical layout
    ``<root>/evotorch_tpu/analysis/graftlint.py``."""
    return Path(__file__).resolve().parents[2]


def default_baseline_path() -> Path:
    return Path(__file__).resolve().parent / "baseline.json"


def default_targets(root: Optional[Path] = None) -> List[Path]:
    """The gated lint surface: the package, the bench drivers, the examples,
    the dryrun entry and the python scripts."""
    root = Path(root) if root is not None else repo_root()
    targets = [root / "evotorch_tpu", root / "examples"]
    targets += sorted(root.glob("bench*.py"))
    entry = root / "__graft_entry__.py"
    if entry.exists():
        targets.append(entry)
    targets += sorted((root / "scripts").glob("*.py"))
    return [t for t in targets if t.exists()]


def _iter_py_files(targets: Iterable[Path]) -> Iterable[Path]:
    for target in targets:
        target = Path(target)
        if target.is_dir():
            for p in sorted(target.rglob("*.py")):
                if "__pycache__" not in p.parts:
                    yield p
        elif target.suffix == ".py":
            yield target


def lint_sources(
    sources: Dict[str, str], *, checkers: Optional[Sequence[str]] = None
) -> List[Finding]:
    """Lint in-memory sources ``{relpath: source}`` — the unit-test entry
    point (the file runner below funnels through this)."""
    from . import checkers as checker_mod

    modules = []
    findings: List[Finding] = []
    allows_by_path: Dict[str, Dict[int, set]] = {}
    for path, src in sources.items():
        allows, allow_problems = scoped_allows(path, src)
        allows_by_path[path] = allows
        findings.extend(allow_problems)
        try:
            modules.append(ModuleInfo.parse(path, src))
        except SyntaxError as e:
            findings.append(
                Finding(
                    checker="parse",
                    path=path,
                    line=e.lineno or 0,
                    symbol="<module>",
                    message=f"syntax error: {e.msg}",
                    detail="syntax-error",
                )
            )
    project = ProjectInfo.build(modules)
    for mod in project.modules:
        for name, check in checker_mod.CHECKERS.items():
            if checkers is not None and name not in checkers:
                continue
            findings.extend(check(mod, project))
    findings = _apply_scoped_allows(findings, allows_by_path)
    findings.sort(key=lambda f: (f.path, f.line, f.checker))
    return findings


def run_lint(
    targets: Optional[Sequence[Path]] = None,
    *,
    root: Optional[Path] = None,
    checkers: Optional[Sequence[str]] = None,
) -> List[Finding]:
    root = Path(root) if root is not None else repo_root()
    paths = list(targets) if targets else default_targets(root)
    sources: Dict[str, str] = {}
    for p in _iter_py_files(paths):
        try:
            rel = p.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:
            rel = p.as_posix()
        sources[rel] = p.read_text()
    return lint_sources(sources, checkers=checkers)
