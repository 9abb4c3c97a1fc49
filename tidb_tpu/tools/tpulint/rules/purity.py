"""jit-purity: functions handed to jax.jit / shard_map must be pure.

PR 2's contract: the traced path runs ONCE at trace time; anything
host-visible inside it either silently disappears from steady-state
execution (metrics bumps, failpoint checks, log lines — they fire at
trace time only) or forces a device->host sync in the middle of the
compiled program (`float(x)`, `.item()`, `np.asarray(x)` on a traced
value — each one a blocking device round trip). Closure
or global mutation from a traced body is a trace-time side effect that
re-runs on every retrace — the phase.py race class, inside a kernel.

Traced functions (per-file): defs decorated `@jax.jit` /
`@functools.partial(jax.jit, ...)`, and defs/lambdas passed directly to
`jax.jit(...)` / `shard_map(...)`.

Flags, inside a traced body:
  * `global` / `nonlocal` statements;
  * calls into host-effect modules: utils.metrics, utils.failpoint,
    utils.phase, utils.logutil, logging, print, time.*, random.* /
    np.random.*, os.environ;
  * host-sync calls: np.asarray / np.array / np.nonzero, `.item()` /
    `.tolist()`, and float()/int()/bool() on a traced PARAMETER;
  * assignments whose target root is not local to the traced function
    (closure/global mutation).

Pallas kernel bodies (Ref mutation is the programming model) are not
matched by these detectors — `out_ref[...] = v` has a local root.
"""
from __future__ import annotations

import ast

from ..core import Rule, register_rule
from .dispatch import _is_jit_decorator

TRACERS = ("jax.jit", "pjit", "shard_map")

IMPURE_CALLS = (
    "failpoint.inject", "failpoint.enable", "failpoint.disable",
    "phase.add", "phase.inc", "phase.reset", "phase.adopt",
    "logutil.log", "logging.info", "logging.warning", "logging.error",
    "logging.debug", "warnings.warn",
)
IMPURE_MODULES = ("utils.metrics", "utils.failpoint", "utils.phase",
                  "utils.logutil")
IMPURE_BARE = ("print",)
IMPURE_PREFIX = ("time.", "random.", "numpy.random.", "os.environ")
# host-numpy materializers. Matched by PREFIX on the resolved dotted
# name ("numpy.asarray"), never by suffix: `jnp.asarray` resolves to
# "jax.numpy.asarray" and is a device-side op, not a host sync.
HOST_SYNC_LEAVES = {"asarray", "array", "nonzero", "copyto", "frombuffer"}
SYNC_METHODS = {"item", "tolist"}
SYNC_BUILTINS = {"float", "int", "bool"}


def traced_functions(ctx) -> list:
    """[(fn_node, how)] — every def/lambda that jax will trace."""
    out = []
    seen = set()
    for fn in ctx.functions:
        if any(_is_jit_decorator(ctx, d) for d in fn.decorator_list):
            out.append((fn, "decorated"))
            seen.add(fn)
    by_name = {}
    for fn in ctx.functions:
        by_name.setdefault(fn.name, fn)
    for call in ctx.calls:
        if not ctx.matches(call.func, TRACERS):
            continue
        target = call.args[0] if call.args else None
        if isinstance(target, (ast.Lambda,)):
            if target not in seen:
                out.append((target, "inline"))
                seen.add(target)
        elif isinstance(target, ast.Name):
            fn = by_name.get(target.id)
            if fn is not None and fn not in seen:
                out.append((fn, "by-name"))
                seen.add(fn)
    return out


@register_rule
class JitPurity(Rule):
    name = "jit-purity"
    severity = "error"
    doc = ("impure or host-syncing construct inside a traced "
           "(jax.jit / shard_map) function")

    def run(self, ctx):
        for fn, _how in traced_functions(ctx):
            yield from self._check(ctx, fn)

    def _check(self, ctx, fn):
        fname = getattr(fn, "name", "<lambda>")
        locals_ = ctx.local_names(fn)
        params = set()
        for a in (fn.args.posonlyargs + fn.args.args
                  + fn.args.kwonlyargs):
            params.add(a.arg)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        stack = list(body)
        while stack:
            node = stack.pop()
            stack.extend(ast.iter_child_nodes(node))
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                yield self.finding(
                    ctx, node,
                    f"'{type(node).__name__.lower()}' inside traced "
                    f"function '{fname}': trace-time mutation of "
                    f"enclosing scope",
                    detail=f"purity:scope:{fname}")
            elif isinstance(node, ast.Call):
                yield from self._check_call(ctx, node, fname, params)
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    if not isinstance(t, (ast.Subscript, ast.Attribute)):
                        continue
                    root = ctx.root_name(t)
                    if root is not None and root not in locals_ and \
                            root not in ctx.imports:
                        yield self.finding(
                            ctx, node,
                            f"traced function '{fname}' mutates "
                            f"non-local '{root}': trace-time side "
                            f"effect, re-runs on every retrace",
                            detail=f"purity:mutate:{fname}:{root}")

    def _check_call(self, ctx, node, fname, params):
        func = node.func
        d = ctx.dotted(func)
        if d is not None:
            impure = (
                ctx.matches(func, IMPURE_CALLS)
                or any(d == m or d.startswith(m + ".")
                       or ("." + m + ".") in ("." + d)
                       for m in IMPURE_MODULES)
                or d in IMPURE_BARE
                or any(d.startswith(p) for p in IMPURE_PREFIX))
            if impure:
                yield self.finding(
                    ctx, node,
                    f"host-effect call '{d}' inside traced function "
                    f"'{fname}': fires at trace time only (or forces "
                    f"host sync), never per-execution",
                    detail=f"purity:effect:{fname}:{d}")
                return
            if d.startswith("numpy.") and \
                    d.split(".")[-1] in HOST_SYNC_LEAVES:
                yield self.finding(
                    ctx, node,
                    f"host materialization '{d}' inside traced "
                    f"function '{fname}': blocking device->host round "
                    f"trip in the compiled program",
                    detail=f"purity:sync:{fname}:{d}")
                return
        if isinstance(func, ast.Attribute) and func.attr in SYNC_METHODS:
            yield self.finding(
                ctx, node,
                f".{func.attr}() inside traced function '{fname}': "
                f"forces a blocking device->host sync",
                detail=f"purity:sync:{fname}:{func.attr}")
        elif isinstance(func, ast.Name) and func.id in SYNC_BUILTINS \
                and func.id not in ctx.imports and len(node.args) == 1 \
                and isinstance(node.args[0], ast.Name) \
                and node.args[0].id in params:
            yield self.finding(
                ctx, node,
                f"{func.id}() on traced parameter "
                f"'{node.args[0].id}' inside '{fname}': concretizes a "
                f"tracer (host sync / ConcretizationTypeError)",
                detail=f"purity:sync:{fname}:{func.id}")
