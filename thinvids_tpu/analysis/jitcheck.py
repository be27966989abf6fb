"""Pass 6 — jit/retrace discipline.

The wave pipeline's throughput rests on two compilation contracts the
AST can check:

TVT-X001  **pinned-shape discipline.**
          (a) `jax.jit` entry points are DEFINED only in the
          manifest's `jit_modules` — a stray jit elsewhere grows its
          own retrace cache outside the pinned-shape regime the
          planner/quantizer helpers maintain.
          (b) the quantized-slice rule (PR 4): inside a jit module, a
          slice bound derived from runtime DATA (`.max()` / `.item()`
          on a device value, directly or through a local name) must
          route through a declared shape quantizer (`cut`, ...).
          `payload[:, :used.max()]` makes every wave a fresh device
          program shape — each one jit-compiles — where
          `payload[:, :cut(used.max())]` re-hits the cache; the two
          differ by an analysis-invisible 30 s compile stall per wave,
          which is exactly why a machine check exists.

TVT-X002  **hot-loop transfer ban.** The manifest's `hot_loops`
          declare the per-wave / per-SFE-frame functions. Blocking
          transfer calls there (`device_put`, `device_get`,
          `block_until_ready`, `.item()`) serialize the pipeline —
          staging (`stage_waves`) and collect (`start_fetch`,
          `collect_wave`, `_fetch_*`) are the allowlisted transfer
          sites and are deliberately NOT declared hot. `copy_to_host_async` stays
          legal everywhere (it is the prefetch that OVERLAPS the
          pipeline, not a sync).
"""

from __future__ import annotations

import ast

from .astutil import (Finding, SourceTree, dotted_name, finding,
                      matches_any, qualified_functions)
from .manifest import Manifest

#: attribute calls whose result is data-dependent (a dynamic shape
#: bound when used to slice)
_DYNAMIC_SOURCES = {"max", "min", "item", "argmax", "argmin"}

#: calls that force a blocking transfer inside a hot loop. `.item()`
#: is only meaningful as an attribute call — matching the bare name
#: `item` would flag ordinary loop variables.
_HOT_FORBIDDEN_ATTRS = {"device_put", "device_get", "block_until_ready",
                        "item"}
_HOT_FORBIDDEN_NAMES = {"device_put", "device_get", "block_until_ready"}

#: numeric wrappers that keep a dynamic value dynamic
_PASSTHROUGH = {"int", "float", "abs", "round"}


def check_jit_confinement(tree: SourceTree, manifest: Manifest
                         ) -> list[Finding]:
    findings: list[Finding] = []
    for mod in tree.modules():
        if matches_any(mod, manifest.jit_modules):
            continue
        mtree = tree.tree(mod)
        for node in ast.walk(mtree):
            hit = None
            if isinstance(node, ast.Attribute) and node.attr == "jit":
                root = dotted_name(node) or ""
                if root.split(".")[0] in ("jax", "jx"):
                    hit = node.lineno
            elif isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.split(".")[0] == "jax":
                for alias in node.names:
                    if alias.name == "jit":
                        hit = node.lineno
            if hit is not None:
                findings.append(finding(
                    "TVT-X001", mod, hit,
                    f"`jax.jit` referenced outside the declared jit "
                    f"modules — the jit surface lives in "
                    f"{{{', '.join(m.rsplit('.', 1)[-1] for m in manifest.jit_modules)}}} "
                    f"so retrace caches stay under the pinned-shape "
                    f"regime",
                    key_detail=f"{mod}:jit"))
                break       # one per module is enough signal
    return findings


class _SliceAuditor(ast.NodeVisitor):
    """One function's dynamic-name taint + slice-bound audit. Nested
    ``def``s are NOT descended into (each is audited as its own
    function with fresh taint — closure-carried dynamics are an honest
    limit); lambdas ARE audited inline, with the enclosing taint,
    since their bodies are expressions over the enclosing scope."""

    def __init__(self, quantizers: frozenset) -> None:
        self.quantizers = quantizers
        self.dynamic: set[str] = set()
        #: (line, description) of unquantized dynamic slice bounds
        self.bad: list[tuple[int, str]] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass                    # audited separately, own taint scope

    def visit_AsyncFunctionDef(self, node) -> None:
        pass                    # audited separately, own taint scope

    # -- taint ---------------------------------------------------------

    def _is_quantizer_call(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        name = dotted_name(node.func) or ""
        return name.split(".")[-1] in self.quantizers

    def _expr_dynamic(self, node: ast.AST) -> str | None:
        """Name of the dynamic source inside `node`, quantizer calls
        excluded; None when the expression is shape-static."""
        if self._is_quantizer_call(node):
            return None
        if isinstance(node, ast.Call):
            fname = dotted_name(node.func) or ""
            term = fname.split(".")[-1]
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr in _DYNAMIC_SOURCES:
                return f".{node.func.attr}()"
            if term in _PASSTHROUGH:
                for arg in node.args:
                    d = self._expr_dynamic(arg)
                    if d:
                        return d
                return None
        if isinstance(node, ast.Name) and node.id in self.dynamic:
            return node.id
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.Lambda, ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                continue
            d = self._expr_dynamic(child)
            if d:
                return d
        return None

    def _taint_targets(self, targets, value) -> None:
        d = self._expr_dynamic(value)
        for tgt in targets:
            elts = tgt.elts if isinstance(tgt, (ast.Tuple, ast.List)) \
                else [tgt]
            for el in elts:
                if isinstance(el, ast.Name):
                    # tuple unpack: any dynamic source on the right
                    # taints every name — conservative, never a miss
                    if d:
                        self.dynamic.add(el.id)
                    else:
                        self.dynamic.discard(el.id)

    def visit_Assign(self, node: ast.Assign) -> None:
        self._taint_targets(node.targets, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._taint_targets([node.target], node.value)
        self.generic_visit(node)

    # -- slices --------------------------------------------------------

    def visit_Subscript(self, node: ast.Subscript) -> None:
        bounds: list[ast.AST] = []
        sl = node.slice
        parts = sl.elts if isinstance(sl, ast.Tuple) else [sl]
        for p in parts:
            if isinstance(p, ast.Slice):
                bounds.extend(b for b in (p.lower, p.upper)
                              if b is not None)
        for b in bounds:
            d = self._expr_dynamic(b)
            if d:
                self.bad.append((node.lineno, d))
        self.generic_visit(node)


def check_quantized_slices(tree: SourceTree, manifest: Manifest
                          ) -> list[Finding]:
    quantizers = frozenset(manifest.shape_quantizers)
    findings: list[Finding] = []
    for mod in tree.modules():
        if not matches_any(mod, manifest.jit_modules):
            continue
        # qualified names (Cls.method) keep same-named methods of
        # different classes under distinct finding keys; lambdas are
        # audited inline by the enclosing function's auditor
        for qual, fn in qualified_functions(tree.tree(mod)):
            if isinstance(fn, ast.Lambda):
                continue
            auditor = _SliceAuditor(quantizers)
            for stmt in fn.body:
                auditor.visit(stmt)
            for line, src in auditor.bad:
                findings.append(finding(
                    "TVT-X001", mod, line,
                    f"`{qual}` slices with a data-dependent bound "
                    f"({src}) not routed through a shape quantizer "
                    f"({', '.join(sorted(quantizers))}) — every "
                    f"distinct bound is a fresh jit compile; quantize "
                    f"the used prefix (PR 4 rule)",
                    key_detail=f"{mod}:{qual}:slice"))
    # one finding per (module, qualified function): repeated bounds in
    # one function are one fix
    uniq: dict[str, Finding] = {}
    for f in findings:
        uniq.setdefault(f.key, f)
    return list(uniq.values())


def check_hot_loops(tree: SourceTree, manifest: Manifest
                    ) -> list[Finding]:
    wanted: dict[str, list[str]] = {}
    for spec in manifest.hot_loops:
        mod, _, qual = spec.partition(":")
        wanted.setdefault(mod, []).append(qual)
    findings: list[Finding] = []
    for mod, quals in sorted(wanted.items()):
        if not tree.has_module(mod):
            findings.append(finding(
                "TVT-X002", mod, 0,
                f"declared hot loop module `{mod}` does not exist — "
                f"update the manifest's hot_loops",
                key_detail=f"{mod}:missing"))
            continue
        index = {qual: node
                 for qual, node in qualified_functions(tree.tree(mod))
                 if not isinstance(node, ast.Lambda)}
        for qual in quals:
            fn = index.get(qual)
            if fn is None:
                findings.append(finding(
                    "TVT-X002", mod, 0,
                    f"declared hot loop `{qual}` not found in {mod} — "
                    f"update the manifest's hot_loops",
                    key_detail=f"{mod}:{qual}:missing"))
                continue
            for node in ast.walk(fn):
                name = None
                if isinstance(node, ast.Attribute) and \
                        node.attr in _HOT_FORBIDDEN_ATTRS:
                    name = node.attr
                elif isinstance(node, ast.Name) and \
                        node.id in _HOT_FORBIDDEN_NAMES:
                    name = node.id
                if name is not None:
                    findings.append(finding(
                        "TVT-X002", mod, node.lineno,
                        f"hot loop `{qual}` references blocking "
                        f"transfer `{name}` — move it to a staging/"
                        f"collect site (stage_waves, collect_wave, "
                        f"_fetch_*) or prefetch with "
                        f"copy_to_host_async",
                        key_detail=f"{mod}:{qual}:{name}"))
    uniq: dict[str, Finding] = {}
    for f in findings:
        uniq.setdefault(f.key, f)
    return list(uniq.values())


def run(tree: SourceTree, manifest: Manifest) -> list[Finding]:
    return check_jit_confinement(tree, manifest) \
        + check_quantized_slices(tree, manifest) \
        + check_hot_loops(tree, manifest)
