"""unguarded-dispatch: every device dispatch goes through
device_guard.guarded_dispatch.

PR 1's contract (utils/device_guard.py): invoking a compiled kernel is
a call against a device that can fail — a lost device connection, HBM
exhaustion, wedged kernels. A naked invocation turns any of those into
a statement error or a hung process instead of a supervised
retry/degrade.

What counts as a jitted callable (per-file, alias-tracked):
  * `@jax.jit` / `@functools.partial(jax.jit, ...)` decorated defs;
  * names assigned from `jax.jit(...)`;
  * names assigned from a same-file BUILDER — a function whose return
    value is a jax.jit call or a known-jitted name (the
    `_build_*_kernel` idiom; cache rebinds keep the name tainted);
  * immediate `jax.jit(fn)(args...)` invocations.

A dispatch is GUARDED when
  * it sits (lexically) inside a lambda/def that is an argument of a
    guarded_dispatch(...) call, or
  * its enclosing function is referenced by name anywhere inside a
    guarded_dispatch(...) argument subtree in the same file (the
    `lambda: self._run_agg_partition(...)` idiom), or
  * its enclosing function is itself traced (kernel-in-kernel
    composition is not a host dispatch).

Cross-FILE supervision (a kernel module whose only callers guard) is
invisible to a per-file walk by design: such sites carry an inline
waiver naming the guarding caller, so the contract stays auditable.
"""
from __future__ import annotations

import ast

from ..core import Rule, register_rule

JIT = ("jax.jit", "jax.pjit", "pjit")
PARTIAL = ("functools.partial", "partial")
GUARD = ("guarded_dispatch",)


def _is_jit_decorator(ctx, dec) -> bool:
    if ctx.matches(dec, JIT):
        return True
    if isinstance(dec, ast.Call):
        if ctx.matches(dec.func, JIT):
            return True
        if ctx.matches(dec.func, PARTIAL) and dec.args and \
                ctx.matches(dec.args[0], JIT):
            return True
    return False


def jitted_names(ctx) -> set:
    """Names bound (anywhere in the file) to jitted callables, with
    builder-function closure: iterate to a fixpoint so
    `kern = _build_kernel(...)` taints `kern` when `_build_kernel`
    returns `jax.jit(...)`."""
    jitted: set = set()
    for fn in ctx.functions:
        if any(_is_jit_decorator(ctx, d) for d in fn.decorator_list):
            jitted.add(fn.name)

    def returns_jitted(fn) -> bool:
        stack = list(fn.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if isinstance(node, ast.Return) and node.value is not None:
                v = node.value
                if isinstance(v, ast.Call) and ctx.matches(v.func, JIT):
                    return True
                if isinstance(v, ast.Name) and v.id in jitted:
                    return True
            stack.extend(ast.iter_child_nodes(node))
        return False

    for _ in range(4):                     # builder chains are shallow
        before = len(jitted)
        builders = {fn.name for fn in ctx.functions if returns_jitted(fn)}
        for a in ctx.assigns:
            if not isinstance(a, ast.Assign) or \
                    not isinstance(a.value, ast.Call):
                continue
            src = a.value.func
            is_jit = ctx.matches(src, JIT)
            is_builder = isinstance(src, ast.Name) and src.id in builders
            if not (is_jit or is_builder):
                continue
            for t in a.targets:
                if isinstance(t, ast.Name):
                    jitted.add(t.id)
        if len(jitted) == before:
            break
    return jitted


def caller_guarded_names(ctx) -> set:
    """Function names INVOKED (or passed as a bare callable) inside the
    supervised arguments of a guarded_dispatch(...) call — `fn` (first
    positional) and `host_fallback=` — their bodies are
    dispatch-supervised by that call
    (`lambda: self._run_filter_partition(...)`). Only call-position
    names count: a data argument that happens to share a function's
    name (`lambda: cache.put(key, kern)`) must NOT exempt that
    function from the rule."""
    out: set = set()
    for call in ctx.calls:
        if not ctx.matches(call.func, GUARD):
            continue
        supervised = list(call.args[:1]) + [
            kw.value for kw in call.keywords
            if kw.arg == "host_fallback"]
        for sub in supervised:
            # a bare callable reference: guarded_dispatch(self._run, …)
            if isinstance(sub, ast.Name):
                out.add(sub.id)
            elif isinstance(sub, ast.Attribute):
                out.add(sub.attr)
            for node in ast.walk(sub):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if isinstance(f, ast.Name):
                    out.add(f.id)
                elif isinstance(f, ast.Attribute) and \
                        isinstance(f.value, ast.Name) and \
                        f.value.id == "self":
                    # only self-method calls name a same-file function;
                    # `cache.put(...)` is another object's method and
                    # must not exempt a local `def put`
                    out.add(f.attr)
    return out


@register_rule
class UnguardedDispatch(Rule):
    name = "unguarded-dispatch"
    severity = "error"
    doc = ("device dispatch (jitted-callable invocation) not routed "
           "through device_guard.guarded_dispatch")

    def run(self, ctx):
        jitted = jitted_names(ctx)
        guarded_fns = caller_guarded_names(ctx)
        traced = set(jitted)               # kernel-in-kernel is fine

        for call in ctx.calls:
            callee = None
            if isinstance(call.func, ast.Name) and call.func.id in jitted:
                callee = call.func.id
            elif isinstance(call.func, ast.Call) and \
                    ctx.matches(call.func.func, JIT):
                inner = call.func.args[0] if call.func.args else None
                callee = "jax.jit(%s)" % (
                    inner.id if isinstance(inner, ast.Name) else "...")
            if callee is None:
                continue
            if self._guarded(ctx, call, guarded_fns, traced):
                continue
            yield self.finding(
                ctx, call,
                f"device dispatch '{callee}' is not routed through "
                f"device_guard.guarded_dispatch (PR 1 supervision "
                f"contract: classify/retry/degrade instead of a naked "
                f"statement error or hang)",
                detail=f"dispatch:{callee}")

    def _guarded(self, ctx, call, guarded_fns, traced) -> bool:
        # `crossed` gates the guard-call check on having passed a
        # function boundary first: `guarded_dispatch(kern(x))` evaluates
        # the dispatch EAGERLY (before supervision starts) and must
        # still be flagged; `guarded_dispatch(lambda: kern(x))` is the
        # supervised form.
        crossed = False
        for anc in ctx.ancestors(call):
            if crossed and isinstance(anc, ast.Call) and \
                    ctx.matches(anc.func, GUARD):
                return True
            if isinstance(anc, (ast.Lambda, ast.FunctionDef,
                                ast.AsyncFunctionDef)):
                crossed = True
                if isinstance(anc, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    if anc.name in guarded_fns or anc.name in traced:
                        return True
                    if any(_is_jit_decorator(ctx, d)
                           for d in anc.decorator_list):
                        return True
        return False
