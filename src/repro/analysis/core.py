"""reprolint core: findings, source loading, checker registry, shared AST helpers.

The analyzer is a repo-specific lint pass over ``src/repro`` enforcing the
four load-bearing serve-stack contracts (see README "Static invariants"):
retrace hygiene, the host/device split, donation discipline, and Pallas
kernel well-formedness.  Each contract is a :class:`Checker` registered in
:data:`REGISTRY`; ``python -m repro.analysis`` runs them all.

Suppressions
------------
- inline: a ``# reprolint: disable=CODE1,CODE2`` (or ``disable=all``) comment
  on the offending line silences those codes for that line;
- module role override: ``# reprolint: module=host`` / ``module=device``
  anywhere in a file pins its host/device contract side (used by fixtures and
  by modules whose path does not imply a side);
- baseline: repo-wide suppressions live in ``ANALYSIS_baseline.json`` (see
  :mod:`repro.analysis.baseline`) and go stale loudly when the finding stops
  firing.
"""
from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Iterable, Iterator

_DISABLE_RE = re.compile(r"#\s*reprolint:\s*disable=([A-Za-z0-9_,\* ]+|all)")
_MODULE_RE = re.compile(r"#\s*reprolint:\s*module=(host|device)")


def repo_root() -> Path:
    """The repository root (directory holding pyproject.toml and src/repro)."""
    here = Path(__file__).resolve()
    for anc in here.parents:
        if (anc / "pyproject.toml").is_file() and (anc / "src" / "repro").is_dir():
            return anc
    return Path.cwd()


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation.  Identity for baseline matching is (code, path, message)
    — line numbers drift with unrelated edits and are display-only."""

    code: str
    path: str  # repo-relative, forward slashes
    line: int
    message: str

    @property
    def key(self) -> tuple[str, str, str]:
        return (self.code, self.path, self.message)

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"


@dataclasses.dataclass
class SourceModule:
    """A parsed source file plus its inline pragmas."""

    path: Path
    rel: str
    text: str
    tree: ast.Module
    disabled: dict[int, set[str]]  # lineno -> codes (or {"all"})
    role: str | None  # "host" / "device" pragma override, else None

    @classmethod
    def load(cls, path: Path, root: Path) -> "SourceModule":
        text = path.read_text()
        tree = ast.parse(text, filename=str(path))
        disabled: dict[int, set[str]] = {}
        role = None
        for i, line in enumerate(text.splitlines(), start=1):
            m = _DISABLE_RE.search(line)
            if m:
                codes = {c.strip() for c in m.group(1).split(",") if c.strip()}
                disabled.setdefault(i, set()).update(codes)
            m = _MODULE_RE.search(line)
            if m:
                role = m.group(1)
        try:
            rel = path.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:
            rel = path.as_posix()
        return cls(path=path, rel=rel, text=text, tree=tree, disabled=disabled, role=role)

    def suppressed(self, finding: Finding) -> bool:
        codes = self.disabled.get(finding.line)
        return bool(codes) and ("all" in codes or finding.code in codes)


class Checker:
    """Base class: subclass, set ``name``/``codes``, implement ``check``."""

    name: str = ""
    codes: dict[str, str] = {}

    def check(self, mod: SourceModule) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError


REGISTRY: dict[str, Checker] = {}


def register(cls: type[Checker]) -> type[Checker]:
    """Class decorator adding a checker to the registry (the extension seam:
    new contracts subclass Checker, register, and are picked up by the CLI,
    the CI lane, and the self-run test with no further wiring)."""
    inst = cls()
    if not inst.name:
        raise ValueError(f"checker {cls.__name__} has no name")
    if inst.name in REGISTRY:
        raise ValueError(f"duplicate checker {inst.name!r}")
    REGISTRY[inst.name] = inst
    return cls


def iter_source_files(paths: Iterable[Path] | None = None) -> Iterator[Path]:
    """Yield the .py files to scan: ``src/repro`` by default, or the given
    files/directories (fixture tests point this at single files)."""
    if paths is None:
        paths = [repo_root() / "src" / "repro"]
    for p in paths:
        p = Path(p)
        if p.is_dir():
            yield from sorted(p.rglob("*.py"))
        elif p.suffix == ".py":
            yield p


def load_modules(paths: Iterable[Path] | None = None) -> list[SourceModule]:
    root = repo_root()
    mods = []
    for f in iter_source_files(paths):
        try:
            mods.append(SourceModule.load(f, root))
        except SyntaxError:
            # unparseable file -> a finding, not a crash
            rel = f.as_posix()
            mods.append(
                SourceModule(
                    path=f, rel=rel, text="", tree=ast.parse(""), disabled={}, role=None
                )
            )
    return mods


def run_checks(
    paths: Iterable[Path] | None = None, checks: Iterable[str] | None = None
) -> list[Finding]:
    """Run the (selected) static checkers; returns pragma-filtered findings."""
    # import for registration side effects
    from repro.analysis import donation, hostdevice, pallas, retrace  # noqa: F401

    selected = list(checks) if checks else sorted(REGISTRY)
    unknown = set(selected) - set(REGISTRY)
    if unknown:
        raise ValueError(f"unknown checkers {sorted(unknown)}; have {sorted(REGISTRY)}")
    findings: list[Finding] = []
    for mod in load_modules(paths):
        for name in selected:
            for f in REGISTRY[name].check(mod):
                if not mod.suppressed(f):
                    findings.append(f)
    findings = list(dict.fromkeys(findings))  # nested-scope walks can revisit
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    return findings


# --------------------------------------------------------------------------
# shared AST helpers
# --------------------------------------------------------------------------

def dotted(node: ast.AST) -> str | None:
    """'self.pools' / 'jax.jit' for Name/Attribute chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def call_name(call: ast.Call) -> str | None:
    return dotted(call.func)


def last_segment(name: str | None) -> str | None:
    return name.rsplit(".", 1)[-1] if name else None


def str_tuple(node: ast.AST | None) -> tuple[str, ...] | None:
    """Literal 'x' or ('a', 'b') / ['a', 'b'] -> tuple of strings."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, str)):
                return None
            out.append(e.value)
        return tuple(out)
    return None


def int_tuple(node: ast.AST | None) -> tuple[int, ...] | None:
    """Literal 0 or (0, 1) / [0, 1] -> tuple of ints."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, int)):
                return None
            out.append(e.value)
        return tuple(out)
    return None


def kwarg(call: ast.Call, name: str) -> ast.AST | None:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit"}
_SHARD_MAP_NAMES = {"shard_map", "jax.shard_map"}


def is_jit_ref(node: ast.AST) -> bool:
    d = dotted(node)
    return d in _JIT_NAMES


def is_shard_map_ref(node: ast.AST) -> bool:
    d = dotted(node)
    return d is not None and (d in _SHARD_MAP_NAMES or d.endswith(".shard_map"))


@dataclasses.dataclass
class JittedCallable:
    """A callable known (statically) to be jit-wrapped, plus what we could
    resolve about its static / donated arguments."""

    ref: str  # how call sites name it: "step_fn", "self._decode", "fwd"
    line: int
    static_names: tuple[str, ...] = ()
    static_nums: tuple[int, ...] = ()
    donate_nums: tuple[int, ...] = ()
    donate_names: tuple[str, ...] = ()
    impl_params: tuple[str, ...] | None = None  # post-binding arg names
    kind: str = "jit"  # "jit" | "shard_map"

    def param_at(self, pos: int) -> str | None:
        if self.impl_params is not None and 0 <= pos < len(self.impl_params):
            return self.impl_params[pos]
        return None

    def is_static(self, pos: int | None, name: str | None) -> bool:
        if name is not None and name in self.static_names:
            return True
        if pos is not None and pos in self.static_nums:
            return True
        return False


def _jit_call_info(call: ast.Call) -> dict | None:
    """Decode a ``jax.jit(...)`` / ``functools.partial(jax.jit, ...)`` /
    ``shard_map(...)`` call expression into its wrap kwargs, or None."""
    name = call_name(call)
    if name is None:
        return None
    kind = None
    kws = call
    target = call.args[0] if call.args else None
    if is_jit_ref(call.func):
        kind = "jit"
    elif is_shard_map_ref(call.func):
        kind = "shard_map"
    elif last_segment(name) == "partial" and call.args and (
        is_jit_ref(call.args[0]) or is_shard_map_ref(call.args[0])
    ):
        kind = "jit" if is_jit_ref(call.args[0]) else "shard_map"
        target = call.args[1] if len(call.args) > 1 else None
    if kind is None:
        return None
    return {
        "kind": kind,
        "target": target,
        "static_names": str_tuple(kwarg(kws, "static_argnames")) or (),
        "static_nums": int_tuple(kwarg(kws, "static_argnums")) or (),
        "donate_nums": int_tuple(kwarg(kws, "donate_argnums")) or (),
        "donate_names": str_tuple(kwarg(kws, "donate_argnames")) or (),
    }


def _params_of(fn: ast.FunctionDef, drop_self: bool) -> tuple[str, ...]:
    args = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    if drop_self and args and args[0] in ("self", "cls"):
        args = args[1:]
    return tuple(args)


@dataclasses.dataclass
class JitIndex:
    """jit-wrapped callables, scoped so ``self._decode`` in two classes in
    one module (ServeEngine / ContinuousServeEngine) never collide."""

    module: dict[str, JittedCallable]
    classes: dict[str, dict[str, JittedCallable]]

    def lookup(self, ref: str | None, cls: str | None) -> JittedCallable | None:
        if not ref:
            return None
        if ref in self.module:
            return self.module[ref]
        if ref.startswith("self."):
            if cls is not None:
                return self.classes.get(cls, {}).get(ref)
            owners = [t[ref] for t in self.classes.values() if ref in t]
            if len(owners) == 1:
                return owners[0]
        return None

    def all(self) -> list[JittedCallable]:
        out = list(self.module.values())
        for t in self.classes.values():
            out.extend(t.values())
        return out


def _defs_by_scope(tree: ast.Module):
    module_defs: dict[str, ast.FunctionDef] = {}
    class_defs: dict[str, dict[str, ast.FunctionDef]] = {}

    def walk(node: ast.AST, cls: str | None) -> None:
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, ast.ClassDef):
                walk(ch, ch.name)
                continue
            if isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope = class_defs.setdefault(cls, {}) if cls else module_defs
                scope.setdefault(ch.name, ch)
            walk(ch, cls)

    walk(tree, None)
    return module_defs, class_defs


def collect_jit_index(tree: ast.Module) -> JitIndex:
    """Every statically-resolvable jit/shard_map-wrapped callable, keyed by
    the ref call sites use (``self._decode``, ``step_fn``, or the decorated
    function's own name), scoped per enclosing class."""
    module_defs, class_defs = _defs_by_scope(tree)
    idx = JitIndex(module={}, classes={})

    def resolve_impl(target: ast.AST | None, cls: str | None):
        if not isinstance(target, (ast.Name, ast.Attribute)):
            return None
        tname = dotted(target)
        if not tname:
            return None
        bound = tname.startswith("self.")
        base = last_segment(tname)
        fn = None
        if cls is not None:
            fn = class_defs.get(cls, {}).get(base)
        if fn is None:
            fn = module_defs.get(base)
        return _params_of(fn, drop_self=bound) if fn is not None else None

    def record(ref: str, line: int, info: dict, cls: str | None,
               impl_params: tuple[str, ...] | None) -> None:
        jc = JittedCallable(
            ref=ref,
            line=line,
            static_names=info["static_names"],
            static_nums=info["static_nums"],
            donate_nums=info["donate_nums"],
            donate_names=info["donate_names"],
            impl_params=impl_params,
            kind=info["kind"],
        )
        if ref.startswith("self.") and cls is not None:
            idx.classes.setdefault(cls, {})[ref] = jc
        else:
            idx.module[ref] = jc

    def walk(node: ast.AST, cls: str | None) -> None:
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, ast.ClassDef):
                walk(ch, ch.name)
                continue
            # name = jax.jit(impl, ...) / self._x = jax.jit(self._impl, ...)
            if isinstance(ch, ast.Assign) and isinstance(ch.value, ast.Call):
                info = _jit_call_info(ch.value)
                if info:
                    params = resolve_impl(info["target"], cls)
                    for t in ch.targets:
                        ref = dotted(t)
                        if ref:
                            record(ref, ch.lineno, info, cls, params)
            # @jax.jit / @functools.partial(jax.jit, ...) def f(...)
            elif isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in ch.decorator_list:
                    info = None
                    if isinstance(dec, ast.Call):
                        info = _jit_call_info(dec)
                    elif is_jit_ref(dec) or is_shard_map_ref(dec):
                        info = {
                            "kind": "jit" if is_jit_ref(dec) else "shard_map",
                            "target": None,
                            "static_names": (),
                            "static_nums": (),
                            "donate_nums": (),
                            "donate_names": (),
                        }
                    if info:
                        record(ch.name, ch.lineno, info, cls,
                               _params_of(ch, drop_self=cls is not None))
            walk(ch, cls)

    walk(tree, None)
    return idx


def functions_with_class(tree: ast.Module) -> list[tuple[ast.FunctionDef, str | None]]:
    """Every function def paired with its enclosing class name (or None)."""
    out: list[tuple[ast.FunctionDef, str | None]] = []

    def walk(node: ast.AST, cls: str | None) -> None:
        for ch in ast.iter_child_nodes(node):
            if isinstance(ch, ast.ClassDef):
                walk(ch, ch.name)
            elif isinstance(ch, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append((ch, cls))
                walk(ch, cls)
            else:
                walk(ch, cls)

    walk(tree, None)
    return out


def scoped_statements(fn: ast.AST) -> list[ast.stmt]:
    """Statements belonging to ``fn``'s own scope, in source order — descends
    into compound statements but NOT into nested function/class defs."""
    out: list[ast.stmt] = []

    def visit(body: list[ast.stmt]) -> None:
        for s in body:
            out.append(s)
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                continue
            for field in ("body", "orelse", "finalbody"):
                sub = getattr(s, field, None)
                if sub:
                    visit(sub)
            for h in getattr(s, "handlers", []) or []:
                visit(h.body)

    visit(fn.body if hasattr(fn, "body") else [])
    return sorted(out, key=lambda n: (n.lineno, n.col_offset))


def own_exprs(stmt: ast.stmt) -> list[ast.AST]:
    """The expression subtrees evaluated *by this statement itself* — for
    compound statements only the header (iter/test/items), since the nested
    body statements are visited separately by :func:`scoped_statements`."""
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
        return []
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.iter]
    if isinstance(stmt, (ast.While, ast.If)):
        return [stmt.test]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return [i.context_expr for i in stmt.items]
    if isinstance(stmt, ast.Try):
        return []
    return [stmt]


def enclosing_functions(tree: ast.Module) -> list[ast.FunctionDef]:
    return [
        n for n in ast.walk(tree) if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]


def local_assignments(fn: ast.AST) -> dict[str, list[ast.AST]]:
    """Name -> all value exprs assigned to it inside ``fn`` (simple Assigns)."""
    env: dict[str, list[ast.AST]] = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    env.setdefault(t.id, []).append(node.value)
    return env
