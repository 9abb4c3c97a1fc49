"""host-sync-in-device-path: kernel results in the copr dispatch path
materialize through the fetch seam, never through scalar dunders.

PR 6's contract (docs/PERFORMANCE.md): a query crosses the host<->device
boundary at most twice — bind inputs, fetch final rows. Every
`int(device_array)` / `.item()` / bare `np.asarray(device_array)` in
the dispatch path is its own blocking device round trip, and a query
that pays one per scalar spends its wall time waiting, not computing
(the cost per sync has not been measured on this chip). The
sanctioned seam is `utils.fetch`: `prefetch()` overlaps
one bulk device->host copy per result tree, and `host_array` /
`host_scalar` / `host_int` read through it.

Detection is taint-based, so host-side numpy stays unflagged:

  * SOURCES — values returned by `prefetch(...)`, and calls to kernel
    callables: names bound from `jax.jit(...)`,
    `jaxcfg.guard_donation(...)`, `phase.timed_kernel(...)`, or
    `<anything>._kernel_cache.put(...)` / `.kernel_cache.put(...)`.
  * PROPAGATION — assignment, tuple unpack, subscript/attribute reads
    of a tainted name (res["ngroups"], res.states) stay tainted, as do
    method calls on a tainted root (res.block_until_ready()). Rebinding
    a name to any OTHER call result (a host helper) clears its taint.
    Analysis is flow-insensitive per function: the LAST binding of a
    name decides its taint for the whole body.
  * SINKS (flagged) — `int()` / `float()` / `bool()` on a tainted
    expression, `.item()` / `.tolist()` on a tainted root,
    `numpy.asarray` / `numpy.array` on a tainted root, and
    `jax.device_get(...)` anywhere in a scoped file.
  * SEAM — `host_array` / `host_scalar` / `host_int` consume taint;
    their results are host data.

Scope: files under `tidb_tpu/copr/` (the single-chip dispatch path)
AND `tidb_tpu/mpp/` (the mesh/exchange path — a blocking sync there
serializes every device in the collective, so the mesh path holds the
same budget with no baseline). The seam module itself lives in utils/
and is out of scope by construction.
"""
from __future__ import annotations

import ast

from ..core import Rule, register_rule

SCOPE_PREFIXES = ("tidb_tpu/copr/", "tidb_tpu/mpp/", "tidb_tpu/vector/",
                  "tidb_tpu/ml/")

PREFETCH = ("prefetch", "fetch.prefetch", "utils.fetch.prefetch")
SEAM = ("host_array", "host_scalar", "host_int",
        "fetch.host_array", "fetch.host_scalar", "fetch.host_int")
KERNEL_MAKERS = ("jax.jit", "jaxcfg.guard_donation", "guard_donation",
                 "phase.timed_kernel", "timed_kernel",
                 "_cached_kernel", "exec._cached_kernel",
                 "build_forward_kernel", "kernels.build_forward_kernel")
HOST_NUMPY = ("numpy.asarray", "numpy.array")
SCALAR_BUILTINS = {"int", "float", "bool"}
SYNC_METHODS = {"item", "tolist"}


def _root_name(node):
    """Expression -> its root ast.Name id (through Subscript/Attribute/
    Call-on-attribute chains), else None."""
    while True:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, (ast.Subscript, ast.Attribute)):
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        else:
            return None


def _is_kcache_put(call) -> bool:
    """`<recv>._kernel_cache.put(...)` / `<recv>.kernel_cache.put(...)`:
    the memoized-kernel seam — its return value is a kernel callable."""
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "put"
            and isinstance(f.value, ast.Attribute)
            and f.value.attr in ("_kernel_cache", "kernel_cache"))


@register_rule
class HostSyncInDevicePath(Rule):
    name = "host-sync-in-device-path"
    severity = "error"
    doc = ("blocking device->host sync (scalar dunder / bare "
           "np.asarray / jax.device_get) on a kernel result in the "
           "copr dispatch path; use the utils.fetch seam")

    def run(self, ctx):
        if not ctx.relpath.startswith(SCOPE_PREFIXES):
            return
        for fn in ctx.functions:
            yield from self._check_fn(ctx, fn)

    # ---- taint computation ---------------------------------------------

    def _tainted_names(self, ctx, fn) -> set:
        """Names in fn's body holding kernel-result (device) values.
        Fixed-point over the function's assignments: sources taint,
        propagation keeps taint, a seam call or any other call result
        clears it."""
        kernels = set()          # names bound to kernel callables
        tainted = set()
        body = fn.body if isinstance(fn.body, list) else [fn.body]

        def expr_tainted(v) -> bool:
            if isinstance(v, ast.Call):
                if ctx.matches(v.func, PREFETCH):
                    return True
                if ctx.matches(v.func, SEAM):
                    return False          # seam output is host data
                root = _root_name(v.func)
                if isinstance(v.func, ast.Name) and root in kernels:
                    return True           # direct kernel dispatch
                if isinstance(v.func, ast.Attribute) and root in tainted:
                    return True           # method on a kernel result
                return False
            if isinstance(v, (ast.Subscript, ast.Attribute)):
                return _root_name(v) in tainted
            if isinstance(v, ast.Name):
                return v.id in tainted
            if isinstance(v, (ast.Tuple, ast.List)):
                return any(expr_tainted(e) for e in v.elts)
            return False

        for _ in range(3):                # tiny fixed point
            changed = False
            for node in ast.walk(ast.Module(body=body,
                                            type_ignores=[])):
                if not isinstance(node, ast.Assign):
                    continue
                v = node.value
                is_kernel = isinstance(v, ast.Call) and (
                    ctx.matches(v.func, KERNEL_MAKERS)
                    or _is_kcache_put(v))
                is_taint = expr_tainted(v)
                # rebinding to any other call result (a host helper,
                # the seam) clears taint — walk order is source order
                # at statement level, so the LAST binding wins and a
                # name recycled for host data can't keep flagging
                is_clear = (isinstance(v, ast.Call)
                            and not is_kernel and not is_taint)
                for t in node.targets:
                    names = [t] if not isinstance(t, (ast.Tuple,
                                                      ast.List)) \
                        else list(t.elts)
                    for el in names:
                        if not isinstance(el, ast.Name):
                            continue
                        if is_kernel and el.id not in kernels:
                            kernels.add(el.id)
                            changed = True
                        elif is_taint and el.id not in tainted:
                            tainted.add(el.id)
                            changed = True
                        elif is_clear and el.id in tainted:
                            tainted.discard(el.id)
                            changed = True
            if not changed:
                break
        return tainted | {f"__kern__{k}" for k in kernels}

    # ---- sinks ---------------------------------------------------------

    def _check_fn(self, ctx, fn):
        marks = self._tainted_names(ctx, fn)
        tainted = {m for m in marks if not m.startswith("__kern__")}
        kernels = {m[len("__kern__"):] for m in marks
                   if m.startswith("__kern__")}

        def is_device_expr(v) -> bool:
            if isinstance(v, ast.Call):
                if ctx.matches(v.func, PREFETCH):
                    return True
                return isinstance(v.func, ast.Name) \
                    and v.func.id in kernels
            root = _root_name(v)
            return root is not None and root in tainted

        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for node in ast.walk(ast.Module(body=body, type_ignores=[])):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            # jax.device_get: never legitimate outside the seam
            if ctx.matches(f, ("jax.device_get",)):
                yield self.finding(
                    ctx, node,
                    "jax.device_get in the dispatch path: route "
                    "through utils.fetch (prefetch + host_array)",
                    detail=f"hostsync:device_get:{ctx.qualname(node)}")
                continue
            arg = node.args[0] if node.args else None
            if arg is None:
                continue
            if isinstance(f, ast.Name) and f.id in SCALAR_BUILTINS \
                    and is_device_expr(arg):
                yield self.finding(
                    ctx, node,
                    f"{f.id}() on a kernel result is a blocking "
                    "scalar sync: use utils.fetch.host_int/"
                    "host_scalar after prefetch()",
                    detail=f"hostsync:{f.id}:{ctx.qualname(node)}:"
                           f"{_root_name(arg)}")
                continue
            if ctx.matches(f, HOST_NUMPY) and is_device_expr(arg):
                yield self.finding(
                    ctx, node,
                    "bare np.asarray on a kernel result: use "
                    "utils.fetch.host_array (the designated seam) "
                    "so the copy is accounted and prefetch-overlapped",
                    detail=f"hostsync:asarray:{ctx.qualname(node)}:"
                           f"{_root_name(arg)}")
        # .item()/.tolist() method calls on tainted roots
        for node in ast.walk(ast.Module(body=body, type_ignores=[])):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in SYNC_METHODS \
                    and _root_name(f.value) in tainted:
                yield self.finding(
                    ctx, node,
                    f".{f.attr}() on a kernel result is a blocking "
                    "sync: use the utils.fetch seam",
                    detail=f"hostsync:{f.attr}:{ctx.qualname(node)}:"
                           f"{_root_name(f.value)}")
