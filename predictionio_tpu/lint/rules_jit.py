"""Family B — jit-boundary hygiene rules, applied package-wide.

These catch the host/device boundary mistakes that don't break Mosaic
but quietly destroy serving latency or recompile per request: Python
control flow on traced values, ``jax.jit`` constructed inside loops,
host syncs on the serving hot path, import-time device arrays, and
unhashable static arguments.

Detection scope (stated in docs/lint.md): jit decoration is recognized
in decorator form — ``@jax.jit``, ``@jit``, and
``@functools.partial(jax.jit, ...)``. Call-form wrapping
(``f = jax.jit(g, ...)``, the als.py idiom) is out of scope for the
traced-branch rule; the jit-in-loop rule sees call-form uses anywhere.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Sequence, Set

from .engine import (
    STATIC_VALUE_ATTRS,
    FileContext,
    Finding,
    Rule,
    call_name,
    dotted_name,
    is_partial_call,
)

#: modules whose request path must never block on the device — the
#: serving hot path (ISSUE 1 scope; extend as hot paths are added)
HOT_PATH_SUFFIXES = (
    "workflow/serving.py",
    "workflow/batching.py",
)


def _is_jit_ref(node: ast.AST) -> bool:
    """``jax.jit`` / bare ``jit`` as a name reference."""
    return dotted_name(node) in ("jax.jit", "jit")


def _jit_static_params(
    func: ast.FunctionDef, ctx: FileContext
) -> Optional[Set[str]]:
    """None when ``func`` is not jit-decorated; otherwise the set of its
    static parameter names (resolved from static_argnames/static_argnums
    literals or module-level string-tuple constants)."""
    for dec in func.decorator_list:
        keywords: Sequence[ast.keyword] = ()
        if _is_jit_ref(dec):
            keywords = ()
        elif isinstance(dec, ast.Call) and _is_jit_ref(dec.func):
            keywords = dec.keywords
        elif (
            isinstance(dec, ast.Call)
            and is_partial_call(dec)
            and dec.args
            and _is_jit_ref(dec.args[0])
        ):
            keywords = dec.keywords
        else:
            continue
        static: Set[str] = set()
        params = [a.arg for a in func.args.posonlyargs + func.args.args]
        for kw in keywords:
            if kw.arg == "static_argnames":
                static |= set(_str_seq(kw.value, ctx) or ())
            elif kw.arg == "static_argnums":
                for num in _int_seq(kw.value, ctx) or ():
                    if 0 <= num < len(params):
                        static.add(params[num])
        # kwonly params named in static_argnames are covered by the set
        return static
    return None


def _str_seq(node: ast.AST, ctx: FileContext) -> Optional[List[str]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                return None
        return out
    if isinstance(node, ast.Name):
        seq = ctx.str_tuple_constants.get(node.id)
        return list(seq) if seq is not None else None
    return None


def _int_seq(node: ast.AST, ctx: FileContext) -> Optional[List[int]]:
    value = ctx.const_int(node)
    if value is not None:
        return [value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            v = ctx.const_int(e)
            if v is None:
                return None
            out.append(v)
        return out
    return None


def _traced_names_in_test(expr: ast.AST, traced: Set[str]) -> List[str]:
    """Parameter names used as traced VALUES in a branch test. Static
    facets (``x.shape``, ``x.dtype``, ``len(x)``, ``x is None``,
    ``isinstance(x, ...)``) don't count."""
    hits: List[str] = []

    def visit(node: ast.AST) -> None:
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_VALUE_ATTRS:
                return  # x.shape[...] etc. — static at trace time
            visit(node.value)
            return
        if isinstance(node, ast.Call):
            fname = call_name(node)
            if fname in ("len", "isinstance", "hasattr", "getattr", "type"):
                return
            for child in list(node.args) + [kw.value for kw in node.keywords]:
                visit(child)
            visit(node.func)
            return
        if isinstance(node, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops
        ):
            return  # identity tests (x is None) are structural
        if isinstance(node, ast.Name):
            if node.id in traced:
                hits.append(node.id)
            return
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(expr)
    return hits


class PythonBranchOnTraced(Rule):
    """Python ``if``/``while`` on a traced argument inside ``@jit``
    raises ``TracerBoolConversionError`` at trace time — or worse, when
    the value is concrete on some call paths, silently bakes one branch
    into the compiled program. Use ``jnp.where``/``lax.cond``."""

    id = "jit-python-branch"
    severity = "error"
    short = "Python if/while on a traced argument inside a @jit function"
    motivation = (
        "the jit-boundary twin of the Mosaic control-flow rules: a "
        "branch that survives tracing only because today's callers pass "
        "concrete values is a recompile (or miscompile) waiting for the "
        "first traced caller"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            static = _jit_static_params(node, ctx)
            if static is None:
                continue
            params = {
                a.arg
                for a in (
                    node.args.posonlyargs + node.args.args
                    + node.args.kwonlyargs
                )
            }
            traced = params - static
            for stmt in ast.walk(node):
                if not isinstance(stmt, (ast.If, ast.While)):
                    continue
                hits = _traced_names_in_test(stmt.test, traced)
                if hits:
                    kind = "if" if isinstance(stmt, ast.If) else "while"
                    yield self.finding(
                        ctx,
                        stmt,
                        f"Python {kind!r} on traced argument(s) "
                        f"{sorted(set(hits))} inside @jit "
                        f"{node.name!r}: this fails (or specializes "
                        "wrongly) at trace time — use jnp.where / "
                        "lax.cond, or mark the argument static.",
                    )


class JitInLoop(Rule):
    """``jax.jit(...)`` constructed inside a loop body builds a fresh
    callable per iteration: every call re-traces and re-compiles, the
    compilation-cache win the serving path depends on evaporates."""

    id = "jit-in-loop"
    severity = "error"
    short = "jax.jit(...) constructed inside a for/while body"
    motivation = (
        "recompilation churn: one compile costs seconds, and a loop "
        "pays it on every iteration"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in ast.walk(loop):
                if isinstance(node, ast.Call) and _is_jit_ref(node.func):
                    yield self.finding(
                        ctx,
                        node,
                        "jax.jit(...) constructed inside a loop body: each "
                        "iteration builds a fresh callable that re-traces "
                        "and re-compiles — hoist the jit out of the loop "
                        "(or functools.lru_cache the wrapper).",
                    )


class HostSyncInServing(Rule):
    """Host syncs on the serving hot path serialize the request on a
    device round trip: ``block_until_ready``, ``np.asarray``/
    ``np.array``, ``.item()``, and ``float(x[i])``-style scalar pulls
    all force the dispatch pipeline to drain. Scoped to the hot-path
    modules (``HOT_PATH_SUFFIXES``)."""

    id = "jit-host-sync-serving"
    severity = "warning"
    short = (
        "host sync (block_until_ready / np.asarray / .item() / "
        "float(x[i])) in a serving hot-path module"
    )
    motivation = (
        "the micro-batcher pipelines batch_pipeline_depth dispatches to "
        "hide the host-device round trip; one stray sync re-serializes "
        "all of it (docs/serving.md)"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        if not ctx.posix_path.endswith(HOT_PATH_SUFFIXES):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if name == "block_until_ready":
                yield self.finding(
                    ctx, node,
                    "block_until_ready() on the serving hot path drains "
                    "the dispatch pipeline — let results resolve at "
                    "encode time.",
                )
            elif name in ("asarray", "array") and dotted_name(
                node.func
            ).split(".")[0] in ("np", "numpy", "onp"):
                yield self.finding(
                    ctx, node,
                    f"np.{name}() on the serving hot path synchronously "
                    "pulls the device buffer to host — keep values on "
                    "device until response encode.",
                )
            elif name == "item" and isinstance(node.func, ast.Attribute) \
                    and not node.args:
                yield self.finding(
                    ctx, node,
                    ".item() on the serving hot path is a blocking "
                    "device->host scalar pull.",
                )
            elif name in ("float", "int") and len(node.args) == 1 and \
                    isinstance(node.args[0], ast.Subscript):
                yield self.finding(
                    ctx, node,
                    f"{name}(x[...]) on the serving hot path pulls one "
                    "scalar per call from the device — batch the "
                    "conversion once per response instead.",
                )


class ModuleLevelDeviceArray(Rule):
    """A ``jnp.*`` call at module scope creates a device value (and
    initializes the backend) at import time — on whatever platform
    happens to be default — and jit closures then capture it as a baked
    constant that silently pins old data across reloads."""

    id = "jit-module-device-array"
    severity = "error"
    short = "module-level jnp.* / jax.device_put call (import-time device state)"
    motivation = (
        "the console deliberately propagates platform choice to children "
        "(utils/platform.py); an import-time jnp call defeats that by "
        "initializing the backend before configuration runs"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for stmt in ctx.tree.body:
            if not isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                continue
            value = stmt.value
            if value is None:
                continue
            for node in ast.walk(value):
                if not isinstance(node, ast.Call):
                    continue
                dn = dotted_name(node.func)
                if dn.startswith(("jnp.", "jax.numpy.")) or dn in (
                    "jax.device_put",
                ):
                    yield self.finding(
                        ctx,
                        node,
                        f"module-level {dn}(...) creates device state at "
                        "import time and gets captured by jit closures "
                        "as a baked constant — construct it lazily "
                        "inside the function (or as a plain Python "
                        "scalar/numpy value).",
                    )
                    break


class NonHashableStatic(Rule):
    """Static jit arguments are dict keys in the compilation cache: a
    parameter whose default is a list/dict/set (or that callers pass
    arrays into) raises ``Unhashable static arguments`` at call time —
    in production, on the first request that exercises the path."""

    id = "jit-nonhashable-static"
    severity = "error"
    short = (
        "static_argnames/static_argnums naming a parameter with a "
        "mutable (unhashable) default"
    )
    motivation = (
        "static args gate the serving dispatch cache; an unhashable one "
        "turns the first live query into a 500"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            static = _jit_static_params(node, ctx)
            if not static:
                continue
            args = node.args
            params = args.posonlyargs + args.args + args.kwonlyargs
            defaults: dict = {}
            pos = args.posonlyargs + args.args
            for param, default in zip(pos[len(pos) - len(args.defaults):],
                                      args.defaults):
                defaults[param.arg] = default
            for param, default in zip(args.kwonlyargs, args.kw_defaults):
                if default is not None:
                    defaults[param.arg] = default
            param_names = {p.arg for p in params}
            for name in sorted(static):
                if name not in param_names:
                    if args.kwarg is None:
                        yield self.finding(
                            ctx, node,
                            f"static_argnames names {name!r} which is not "
                            f"a parameter of {node.name!r} (typo?) — jit "
                            "raises at call time.",
                        )
                    continue
                default = defaults.get(name)
                if isinstance(
                    default, (ast.List, ast.Dict, ast.Set)
                ) or (
                    isinstance(default, ast.Call)
                    and call_name(default) in ("list", "dict", "set")
                ):
                    yield self.finding(
                        ctx, node,
                        f"static argument {name!r} of {node.name!r} has an "
                        "unhashable default: static args are hashed into "
                        "the compilation cache key — use a tuple/frozen "
                        "value.",
                    )


RULES = [
    PythonBranchOnTraced(),
    JitInLoop(),
    HostSyncInServing(),
    ModuleLevelDeviceArray(),
    NonHashableStatic(),
]
