"""Trace-safety rules (TRC).

Functions handed to ``jax.jit`` / ``shard_map`` / ``jax.lax.scan`` /
``pl.pallas_call`` / ``custom_vjp`` execute once at trace time with
abstract tracers; anything host-visible inside them is either a silent
no-op at run time (print fires once, at trace) or a hard error
(``.item()``/``bool()`` on a tracer).  These rules mark the traced
function set — decorators, call sites (including through
``functools.partial``), ``defvjp`` registrations, nested defs, and
module-local helpers the traced bodies call by name — and flag host
operations inside it:

  TRC001  print / breakpoint / input / open
  TRC002  .item() / .tolist() / .block_until_ready() — host sync on a
          tracer
  TRC003  wall-clock or OS calls (time.*, os.urandom) — trace-time
          constants masquerading as runtime values
  TRC004  bool(...) — concretization error on a tracer
"""
from __future__ import annotations

import ast
from typing import Iterable, Optional

from tools.repro_lint.astutil import dotted, resolve
from tools.repro_lint.diagnostics import Diagnostic
from tools.repro_lint.engine import ParsedModule, Project, Rule

# dotted names (import-resolved where possible) that make the decorated /
# passed function a traced function
_TRACING_ENTRY_POINTS = {
    "jax.jit", "jit", "jax.checkpoint", "jax.remat",
    "jax.custom_vjp", "jax.custom_jvp", "custom_vjp", "custom_jvp",
    "jax.lax.scan", "lax.scan",
    "pl.pallas_call", "pallas_call", "pl.when",
}

_HOST_CALLS = {"print", "breakpoint", "input", "open"}
_HOST_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
_HOST_CLOCK_PREFIXES = ("time.",)
_HOST_CLOCK_EXACT = {"os.urandom"}

FunctionNode = ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda


def _callee_text(node: ast.AST, imports: dict[str, str]) -> Optional[str]:
    return resolve(node, imports) or dotted(node)


def _is_tracing_callee(node: ast.AST, imports: dict[str, str]) -> bool:
    text = _callee_text(node, imports)
    if text is None:
        return False
    if text in _TRACING_ENTRY_POINTS:
        return True
    leaf = text.rsplit(".", 1)[-1]
    # local wrappers named like *shard_map*(body, mesh, ...) still trace
    # their function argument
    return "shard_map" in leaf or leaf == "pallas_call"


def _function_args(call: ast.Call) -> list[ast.AST]:
    """Positional args of `call`, looking through functools.partial."""
    out = []
    for a in call.args:
        if isinstance(a, ast.Call):
            callee = dotted(a.func)
            if callee and callee.rsplit(".", 1)[-1] == "partial" and a.args:
                out.append(a.args[0])
                continue
        out.append(a)
    return out


class TraceSafetyRule(Rule):
    codes = ("TRC001", "TRC002", "TRC003", "TRC004")
    name = "trace-safety"
    summary = "no host side effects inside jit/shard_map/pallas_call " \
              "bodies"

    def check_module(self, module: ParsedModule,
                     project: Project) -> Iterable[Diagnostic]:
        tree, imports = module.tree, module.imports
        defs: dict[str, list[ast.AST]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        traced: set[ast.AST] = set()

        def mark(target: ast.AST) -> None:
            if isinstance(target, ast.Lambda):
                traced.add(target)
            elif isinstance(target, ast.Name):
                for d in defs.get(target.id, []):
                    traced.add(d)

        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    base = dec.func if isinstance(dec, ast.Call) else dec
                    if _is_tracing_callee(base, imports):
                        traced.add(node)
                    elif isinstance(dec, ast.Call):
                        # functools.partial(jax.jit, static_argnames=...)
                        if any(_is_tracing_callee(a, imports)
                               for a in dec.args):
                            traced.add(node)
            elif isinstance(node, ast.Call):
                callee = node.func
                if _is_tracing_callee(callee, imports):
                    args = _function_args(node)
                    if args:
                        mark(args[0])
                elif isinstance(callee, ast.Attribute) \
                        and callee.attr == "defvjp":
                    for a in _function_args(node):
                        mark(a)

        if not traced:
            return

        # closure: nested defs inside traced fns, and module-local
        # helpers a traced body calls by bare name
        changed = True
        while changed:
            changed = False
            for fn in list(traced):
                for node in ast.walk(fn):
                    if node is not fn and isinstance(
                            node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)) and node not in traced:
                        traced.add(node)
                        changed = True
                    elif isinstance(node, ast.Call) \
                            and isinstance(node.func, ast.Name):
                        for d in defs.get(node.func.id, []):
                            if d not in traced:
                                traced.add(d)
                                changed = True

        seen: set[int] = set()
        for fn in traced:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Lambda)):
                continue
            label = getattr(fn, "name", "<lambda>")
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                d = self._check_call(module, node, imports, label)
                if d is not None:
                    yield d

    @staticmethod
    def _check_call(module: ParsedModule, node: ast.Call,
                    imports: dict[str, str],
                    label: str) -> Optional[Diagnostic]:
        f = node.func
        if isinstance(f, ast.Name):
            if f.id in _HOST_CALLS:
                return module.diag(
                    node, "TRC001",
                    f"{f.id}() inside traced function `{label}` runs at "
                    "trace time only (use jax.debug.print / host_callback "
                    "for runtime effects)")
            if f.id == "bool" and node.args:
                return module.diag(
                    node, "TRC004",
                    f"bool() inside traced function `{label}` raises a "
                    "ConcretizationTypeError on tracers")
        elif isinstance(f, ast.Attribute) and f.attr in _HOST_SYNC_ATTRS \
                and not f.value is None:
            return module.diag(
                node, "TRC002",
                f".{f.attr}() inside traced function `{label}` forces a "
                "host sync / fails on tracers")
        full = resolve(f, imports)
        if full is not None and (full in _HOST_CLOCK_EXACT
                                 or any(full.startswith(p)
                                        for p in _HOST_CLOCK_PREFIXES)):
            return module.diag(
                node, "TRC003",
                f"{full}() inside traced function `{label}` is evaluated "
                "once at trace time — it is a constant, not a runtime "
                "clock")
        return None
