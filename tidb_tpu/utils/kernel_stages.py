"""Which pipeline stage each compiled operation of a device program
belongs to: the catalogue that names the device's time.

A profile of the device (`jax.profiler`, the benchmark's `--trace 1`)
names an operation by its compiled instruction, `fusion.6` or
`while.15`: a name local to one program, renumbered whenever the
program's text changes, and the same in a dozen programs at once. The
fused pipeline's body (copr/pipeline.py) wraps its stages in
`jax.named_scope`, and the compiled module carries the scope in every
instruction's `metadata={op_name=...}`; the trace does not. This module
joins the two, for programs that ran under a profiler session only:

  note         phase.timed_kernel, while `TraceAnnotation.is_enabled()`:
               the jitted callable, the cache key's kind and the
               arguments as `jax.ShapeDtypeStruct`s (with shardings),
               once a distinct signature. No array is kept. With no
               session the seam's cost is the one flag read.
  materialise  metrics.update_runtime_gauges (a `/metrics` scrape or a
               read of `information_schema.metrics_summary`), with no
               session active: every noted program is lowered and
               compiled from its structs (a persistent-cache hit), its
               text parsed into {instruction: stage} and dropped. On
               the reader's thread, seconds for a cell's programs, at
               most BUDGET_S a read; what is left over is counted and
               stays uncatalogued.
  serve        `tidb_tpu_kernel_stage_ops{program, entry, stage, ops}`,
               one sample a program a stage, value = instructions;
               `tidb_tpu_kernel_stage_catalogue_total{outcome}`. A
               process never profiled serves neither.

The look-up is not the statements' work: its persistent-cache look-ups
are taken back off `tidb_tpu_xla_cache_total`, and nothing here touches
a statement's phase counters.

The stage names are those of whichever build first compiled the cached
executable (`jax_compilation_cache_include_metadata_in_key` is off): a
scope added to the pipeline stays invisible here until a cold compile.
"""
import contextlib
import re
import threading
import time
import types
import weakref

from . import logutil
from . import metrics as _metrics
from . import tracing as _tracing

STAGES = ("scan_filter", "compact", "dim_probe", "group_agg", "topn")
NONE = "none"
BUDGET_S = 90.0

STAGE_OPS = _metrics.REGISTRY.gauge(
    "tidb_tpu_kernel_stage_ops",
    "Compiled instructions of a device program that ran under a "
    "profiler session, by pipeline stage (scan_filter, compact, "
    "dim_probe, group_agg, topn: the jax.named_scope in the "
    "instruction's op_name, or the one most instructions of the "
    "computations it calls carry; else none). program: the module as a "
    "profile's XLA Modules line prints it before the number; entry: "
    "the program's ordinal within that family (nothing the program can "
    "compute equals the line's number: join a module to the entry "
    "whose ops cover the operations seen in it); ops: the stage's "
    "instruction names as the XLA Ops line prints them, space-joined. "
    "Filled by the first metrics read after the session "
    "(docs/OBSERVABILITY.md)",
    ("program", "entry", "stage", "ops"))
CATALOGUE = _metrics.REGISTRY.counter(
    "tidb_tpu_kernel_stage_catalogue_total",
    "Device programs noted under a profiler session, by what the stage "
    "catalogue made of them: built (lowered, compiled from the "
    "persistent cache, parsed), cache_miss (a built one whose compile "
    "was not a cache hit: it cost a compile, logged), lower_failed, "
    "over_budget (left uncatalogued past the read's 90 s)",
    ("outcome",))

_MU = threading.Lock()            # _NOTED, _PENDING, _ORDINALS
_BUILD_MU = threading.Lock()      # one reader materialises at a time
_NOTED = weakref.WeakKeyDictionary()   # jitted callable -> {signature}
_PENDING: list = []               # [(kind, jitted, args, kw)] as structs
_ORDINALS: dict = {}              # program family -> entries so far
_TLS = threading.local()


def session_active() -> bool:
    """The profiler's own flag (what tracing._segment reads on every
    span): a session is recording."""
    ann = _tracing._ANNOTATION or _tracing._annotation()
    return bool(ann) and ann.is_enabled()


def _jitted(fn):
    """The jitted callable under whatever wraps it (guard_donation's
    `guarded` exposes it as `__wrapped__`)."""
    while not hasattr(fn, "lower") and hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__
    return fn


def _leaf_signature(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return (tuple(x.shape), str(x.dtype),
                bool(getattr(x, "weak_type", False)),
                getattr(x, "sharding", None))
    if isinstance(x, (bool, int, float, complex)):
        return type(x)              # jit abstracts a Python scalar
    return x


def _struct(x):
    """An array argument as its ShapeDtypeStruct, anything else as it
    is."""
    import jax
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(
            tuple(x.shape), x.dtype,
            sharding=x.sharding if isinstance(x, jax.Array) and
            x.committed else None,
            weak_type=bool(getattr(x, "weak_type", False)))
    return x


def note(kind, fn, args, kw):
    """A kernel is being dispatched under a profiler session: keep what
    its program can be lowered from again, once a distinct signature of
    its arguments. Never raises."""
    try:
        import jax
        jitted = _jitted(fn)
        leaves, treedef = jax.tree_util.tree_flatten((args, kw))
        sig = (treedef, tuple(_leaf_signature(x) for x in leaves))
        with _MU:
            seen = _NOTED.get(jitted)
            if seen is None:
                seen = _NOTED[jitted] = set()
            if sig in seen:
                return
            seen.add(sig)
            sargs, skw = jax.tree_util.tree_unflatten(
                treedef, [_struct(x) for x in leaves])
            _PENDING.append((kind, jitted, sargs, skw))
    except Exception as e:                          # noqa: BLE001
        logutil.warn("kernel_stage_note_failed", kind=str(kind),
                     error=repr(e)[:200])


# ---- the compiled text -> {instruction: stage} ------------------------

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|"
    r"false_computation|branch_computations)="
    r"(?:\{([^}]*)\}|%?([\w.\-]+))")
# the attributes whose computations run as operations of their own on
# the device's line; a fusion's or a reducer's do not
_RUNS = {"body", "condition", "true_computation", "false_computation",
         "branch_computations"}
# no device time of their own: never an event on the operations' line
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


def _opcode(rest):
    """`s32[8]{0:T(1024)S(1)} fusion(...), kind=...` -> `fusion`: the
    first word before a `(` that follows a space (a layout's `T(` and
    a tuple shape's `(` do not)."""
    m = _OPCODE.search(rest)
    return m.group(1) if m else ""


def _own_stage(line):
    m = _OP_NAME.search(line)
    if m:
        for part in m.group(1).split("/"):
            if part in STAGES:
                return part
    return None


def parse_stages(text):
    """The text of a compiled module (`Compiled.as_text()`) -> (module
    name, {instruction: stage}) over the instructions that run as
    operations of their own: the entry computation's and, from there,
    those of `while` bodies and conditions, branches and calls, without
    the free ones (parameters, constants, tuples). An instruction's
    stage is the first stage name in its own op_name; without one, the
    stage most instructions of the computations it calls carry; else
    `none`."""
    header = re.match(r"HloModule\s+([\w.\-]+)", text)
    module = header.group(1) if header else ""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps[m.group(2)] = []
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(2)
        called = []
        for attr, several, one in _CALLED.findall(rest):
            for n in (several or one).split(","):
                called.append((attr, n.strip().lstrip("%")))
        cur.append((m.group(1), _opcode(rest), _own_stage(rest), called))

    memo = {}

    def votes(own, called):
        """{stage: instructions} of one instruction: its own stage's
        one, else those of the computations it calls."""
        if own is not None:
            return {own: 1}
        out = {}
        for _attr, comp in called:
            if comp not in memo:
                memo[comp] = {}         # a cycle would end here
                for _name, _op, o, c in comps.get(comp, ()):
                    for s, n in votes(o, c).items():
                        memo[comp][s] = memo[comp].get(s, 0) + n
            for s, n in memo[comp].items():
                out[s] = out.get(s, 0) + n
        return out

    def stage_of(own, called):
        v = votes(own, called)
        return max(STAGES, key=lambda s: v.get(s, 0)) if v else NONE

    stages, todo, done = {}, [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in done:
            continue
        done.add(comp)
        for name, op, own, called in comps.get(comp, ()):
            if op not in _FREE:
                stages[name] = stage_of(own, called)
            todo += [c for attr, c in called
                     if attr in _RUNS or (attr == "to_apply" and
                                          op == "call")]
    return module, stages


# ---- noted programs -> the two families --------------------------------

@contextlib.contextmanager
def _looking():
    """While open on this thread, what the compile under it does is the
    catalogue's own: a look-up in the persistent compile cache is taken
    back off tidb_tpu_xla_cache_total (jaxcfg's metering counted it),
    and a compile by the backend is remembered in `.compiled`. A program
    still in jax's in-memory caches does neither."""
    _install_taps()
    look = _TLS.look = types.SimpleNamespace(compiled=False)
    try:
        yield look
    finally:
        _TLS.look = None


def _install_taps():
    from jax._src import compilation_cache as _cc
    from jax._src import compiler as _compiler
    if getattr(_cc, "_tidb_stage_taps", False):
        return
    lookup = _cc.get_executable_and_time
    build = _compiler.backend_compile_and_load

    def tapped_lookup(cache_key, *a, **kw):
        out = lookup(cache_key, *a, **kw)
        if getattr(_TLS, "look", None) is not None and \
                getattr(_cc, "_tidb_cache_metered", False) and \
                _metrics.REGISTRY.enabled:
            hit = out is not None and out[0] is not None
            child = _metrics.XLA_CACHE.labels("hit" if hit else "miss")
            with child._mu:
                child.value -= 1
        return out

    def tapped_build(*a, **kw):
        look = getattr(_TLS, "look", None)
        if look is not None:
            look.compiled = True
        return build(*a, **kw)

    _cc.get_executable_and_time = tapped_lookup
    _compiler.backend_compile_and_load = tapped_build
    _cc._tidb_stage_taps = True


def _compile(jitted, args, kw):
    """-> (the program's Compiled, whether the backend compiled it)."""
    lowered = jitted.lower(*args, **kw)
    with _looking() as look:
        compiled = lowered.compile()
    return compiled, look.compiled


def _catalogue(kind, jitted, args, kw):
    """One noted program -> its samples. Raises what lowering raises."""
    t0 = time.perf_counter()
    compiled, cost_a_compile = _compile(jitted, args, kw)
    family, stages = parse_stages(compiled.as_text())
    by_stage = {}
    for name, stage in stages.items():
        by_stage.setdefault(stage, []).append(name)
    with _MU:
        ordinal = _ORDINALS.get(family, 0)
        _ORDINALS[family] = ordinal + 1
    entry = str(ordinal)
    for stage, names in by_stage.items():
        ops = " ".join(sorted(names))
        STAGE_OPS.labels(family, entry, stage, ops).set(len(names))
    CATALOGUE.labels("built").inc()
    if cost_a_compile:
        CATALOGUE.labels("cache_miss").inc()
        logutil.warn("kernel_stage_catalogue_compiled", program=family,
                     kind=str(kind), entry=entry,
                     seconds=round(time.perf_counter() - t0, 3),
                     why="not a cache hit: the look-up cost a compile")


def materialise():
    """Catalogue what was noted, unless a session is recording (then the
    look-ups would be in its profile). Never raises."""
    if not _PENDING or not _BUILD_MU.acquire(blocking=False):
        return
    try:
        if session_active():
            return
        deadline = time.monotonic() + BUDGET_S
        while True:
            with _MU:
                if not _PENDING:
                    return
                late = time.monotonic() > deadline
                left, rec = len(_PENDING), _PENDING.pop(0)
                if late:
                    del _PENDING[:]
            if late:
                CATALOGUE.labels("over_budget").inc(left)
                return
            try:
                _catalogue(*rec)
            except Exception as e:                  # noqa: BLE001
                CATALOGUE.labels("lower_failed").inc()
                logutil.warn("kernel_stage_lower_failed",
                             kind=str(rec[0]), error=repr(e)[:200])
    finally:
        _BUILD_MU.release()


def noted() -> int:
    """Programs noted and not yet catalogued."""
    return len(_PENDING)


def reset():
    """Test hook: forget what was noted and the ordinals (the samples
    go with metrics.reset_all)."""
    with _MU:
        _NOTED.clear()
        del _PENDING[:]
        _ORDINALS.clear()
