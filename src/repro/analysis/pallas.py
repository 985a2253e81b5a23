"""PL4xx — Pallas kernel well-formedness + the KernelPolicy interpret contract.

For every ``pallas_call`` reachable from ``kernels/`` (and every call into
the kernel wrappers from model/serve code) verify, statically:

- PL401: each ``BlockSpec`` index_map lambda takes exactly grid-rank
  parameters (plus ``num_scalar_prefetch`` under ``PrefetchScalarGridSpec``)
  — an arity mismatch is a runtime TypeError only on the first real call;
- PL402: a BlockSpec's block-shape tuple and its index_map's returned tuple
  have the same rank;
- PL403: a grid computed with ``//`` has a divisibility guard (some ``%``
  check) in the enclosing function — silent shape truncation otherwise;
- PL404: ``interpret=`` at kernel entry points routes through
  ``KernelPolicy.interpret`` (a name or ``*.interpret`` attribute) or the one
  platform resolver ``resolve_interpret(<name>)``, never an ad-hoc literal /
  ``not on_tpu()`` expression — PR 6's silent-fallback class: per-call
  booleans drift apart from the policy the engine actually built.
"""
from __future__ import annotations

import ast

from repro.analysis.core import (
    Checker,
    Finding,
    SourceModule,
    call_name,
    enclosing_functions,
    kwarg,
    last_segment,
    local_assignments,
    register,
)

KERNEL_ENTRYPOINTS = frozenset(
    {
        "pallas_call",
        "dynatran_prune",
        "block_sparse_matmul",
        "flash_attention",
        "wkv6_chunked",
        "paged_gather",
        "paged_scatter",
        "paged_decode_attention",
    }
)


def _resolve(node: ast.AST | None, env: dict[str, list[ast.AST]], depth: int = 0) -> ast.AST | None:
    """Follow a Name through single local assignment chains (one hop deep
    enough for the kernels' idiom of naming grids/specs/index-maps)."""
    while isinstance(node, ast.Name) and depth < 4:
        vals = env.get(node.id)
        if not vals:
            return node
        # multiple branch assignments: only usable if they agree structurally
        node = vals[0] if len(vals) == 1 else _agreeing(vals)
        if node is None:
            return None
        depth += 1
    return node


def _agreeing(vals: list[ast.AST]) -> ast.AST | None:
    """Branchy assignments (e.g. transposed grids) are fine when every branch
    is a tuple of the same rank; return a representative, else None."""
    if all(isinstance(v, ast.Tuple) for v in vals):
        ranks = {len(v.elts) for v in vals}
        if len(ranks) == 1:
            return vals[0]
    return None


def _tuple_rank(node: ast.AST | None) -> int | None:
    if isinstance(node, ast.Tuple):
        return len(node.elts)
    return None


def _grid_arity(call: ast.Call, env: dict[str, list[ast.AST]]) -> tuple[int | None, ast.AST | None]:
    """(index_map arity, grid expr) for a pallas_call: grid rank plus scalar
    prefetch count when wrapped in PrefetchScalarGridSpec."""
    grid = _resolve(kwarg(call, "grid"), env)
    if grid is not None:
        return _tuple_rank(grid), grid
    spec = _resolve(kwarg(call, "grid_spec"), env)
    if isinstance(spec, ast.Call) and last_segment(call_name(spec)) in (
        "PrefetchScalarGridSpec",
        "GridSpec",
    ):
        inner = _resolve(kwarg(spec, "grid"), env)
        rank = _tuple_rank(inner)
        prefetch = kwarg(spec, "num_scalar_prefetch")
        extra = 0
        if isinstance(prefetch, ast.Constant) and isinstance(prefetch.value, int):
            extra = prefetch.value
        if rank is not None:
            return rank + extra, inner
        return None, inner
    return None, None


def _blockspecs(call: ast.Call, env: dict[str, list[ast.AST]]) -> list[ast.Call]:
    """Every BlockSpec constructor reachable from this pallas_call: inline in
    the call, via named in_specs/out_specs/grid_spec, and through one level of
    list concatenation (the paged kernels build spec lists with ``+``)."""
    roots: list[ast.AST] = [call]
    for key in ("grid_spec", "in_specs", "out_specs"):
        r = _resolve(kwarg(call, key), env)
        if r is not None:
            roots.append(r)
    seen: dict[tuple[int, int], ast.Call] = {}
    for root in roots:
        for node in ast.walk(root):
            if isinstance(node, ast.Call) and last_segment(call_name(node)) == "BlockSpec":
                seen[(node.lineno, node.col_offset)] = node
    return list(seen.values())


def _index_map(spec: ast.Call, env: dict[str, list[ast.AST]]) -> ast.Lambda | None:
    cand = kwarg(spec, "index_map")
    if cand is None and len(spec.args) >= 2:
        cand = spec.args[1]
    cand = _resolve(cand, env)
    return cand if isinstance(cand, ast.Lambda) else None


def _block_shape(spec: ast.Call) -> ast.AST | None:
    shape = kwarg(spec, "block_shape")
    if shape is None and spec.args:
        shape = spec.args[0]
    return shape


def _has_floordiv(node: ast.AST | None) -> bool:
    return node is not None and any(
        isinstance(n, ast.BinOp) and isinstance(n.op, ast.FloorDiv) for n in ast.walk(node)
    )


def _has_mod_guard(fn: ast.AST) -> bool:
    return any(
        isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mod) for n in ast.walk(fn)
    )


def _interpret_ok(value: ast.AST, env: dict[str, list[ast.AST]]) -> bool:
    """interpret= must be a policy-routed value: a bare parameter name, an
    attribute chain ending in ``.interpret``, or ``resolve_interpret`` (the
    platform default's one owner) applied to one of those.  Literals and
    computed expressions (``not on_tpu()``) are ad-hoc — including laundering
    through a local variable assigned from one."""
    resolved = _resolve(value, env)
    if resolved is None:
        resolved = value
    if (isinstance(resolved, ast.Call) and last_segment(call_name(resolved)) == "resolve_interpret"
            and len(resolved.args) == 1 and not resolved.keywords):
        return _interpret_ok(resolved.args[0], env)
    if isinstance(resolved, ast.Attribute) and resolved.attr == "interpret":
        return True
    if isinstance(resolved, ast.Name):
        return True  # unresolvable name: trust dataflow (parameters etc.)
    return False


@register
class PallasChecker(Checker):
    name = "pallas"
    codes = {
        "PL401": "BlockSpec index_map arity does not match the grid rank",
        "PL402": "BlockSpec block-shape rank disagrees with its index_map result",
        "PL403": "grid computed with // but no divisibility guard in scope",
        "PL404": "interpret= at a kernel entry point bypasses KernelPolicy.interpret",
    }

    def check(self, mod: SourceModule) -> list[Finding]:
        out: list[Finding] = []
        scopes: list[ast.AST] = list(enclosing_functions(mod.tree)) or [mod.tree]
        if mod.tree not in scopes:
            scopes.append(mod.tree)
        seen_calls: set[tuple[int, int]] = set()
        for scope in scopes:
            env = local_assignments(scope)
            for node in ast.walk(scope):
                if not isinstance(node, ast.Call):
                    continue
                seg = last_segment(call_name(node))
                if seg not in KERNEL_ENTRYPOINTS:
                    continue
                key = (node.lineno, node.col_offset)
                # prefer the innermost scope's env: first visit wins because
                # enclosing_functions lists inner defs before the module tree
                if key in seen_calls:
                    continue
                seen_calls.add(key)

                iv = kwarg(node, "interpret")
                if iv is not None and not _interpret_ok(iv, env):
                    out.append(
                        Finding(
                            "PL404", mod.rel, iv.lineno,
                            f"{seg}(...): ad-hoc interpret= value — route it "
                            "through KernelPolicy.interpret so backend dispatch "
                            "has one owner",
                        )
                    )

                if seg != "pallas_call":
                    continue
                arity, grid_expr = _grid_arity(node, env)
                if _has_floordiv(grid_expr) and isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if not _has_mod_guard(scope):
                        out.append(
                            Finding(
                                "PL403", mod.rel, node.lineno,
                                "grid uses // with no % divisibility guard in "
                                "the enclosing function — ragged shapes would "
                                "silently truncate",
                            )
                        )
                for spec in _blockspecs(node, env):
                    lam = _index_map(spec, env)
                    if lam is None:
                        continue
                    if lam.args.vararg is None and arity is not None:
                        nparams = len(lam.args.posonlyargs + lam.args.args)
                        if nparams != arity:
                            out.append(
                                Finding(
                                    "PL401", mod.rel, spec.lineno,
                                    f"BlockSpec index_map takes {nparams} args "
                                    f"but the grid (incl. scalar prefetch) has "
                                    f"rank {arity}",
                                )
                            )
                    shape_rank = _tuple_rank(_block_shape(spec))
                    body_rank = _tuple_rank(lam.body)
                    if shape_rank is not None and body_rank is not None and shape_rank != body_rank:
                        out.append(
                            Finding(
                                "PL402", mod.rel, spec.lineno,
                                f"BlockSpec block shape has rank {shape_rank} "
                                f"but its index_map returns {body_rank} "
                                "coordinates",
                            )
                        )
        return out
