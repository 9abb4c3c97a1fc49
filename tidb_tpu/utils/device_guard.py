"""Device-failure supervision (reference: tikv client-go retry/backoff +
region reroute, applied to the accelerator instead of a region server).

The accelerator is a local device that can still fail under a serving
process: the runtime can lose its connection to the chip, a kernel can
wedge, a compile can fail, and HBM fills up. A statement must survive
all of them with the right rows. Every device dispatch site routes
through `guarded_dispatch`, which

  1. CLASSIFIES the error (lost device connection — class `grant_lost`
     — / RESOURCE_EXHAUSTED / compile failure / wedge / generic) into
     retryable vs degradeable vs fatal,
  2. RETRIES retryable classes with exponential backoff + jitter,
     clamped to the statement deadline (`ExecContext.deadline`) so
     retries never outlive `max_execution_time`,
  3. optionally runs the dispatch under a WATCHDOG timeout
     (`tidb_tpu_device_dispatch_timeout_ms`) so a stalled kernel
     becomes a classified `wedged` error instead of a hung process, and
  4. on exhausted retries DEGRADES to the host/numpy twin (TQP-style:
     every operator keeps a CPU implementation), recording a SHOW
     WARNINGS note + `device_retry`/`device_fallback` metrics; after N
     consecutive failures a per-family CIRCUIT BREAKER short-circuits
     straight to the host for a cooldown window.

Chaos hooks: each site checks failpoint `device_guard/<site>` before
every attempt; `utils/failpoint.py` actions (`error:<class>`,
`sleep:ms`, `nth:k`) inject each error class at each site.
"""
from __future__ import annotations

import os
import random
import threading
import time
import weakref

from . import failpoint
from . import metrics as _metrics
from . import phase as _phase
from . import tracing as _tracing
from .logutil import log
from ..errors import TiDBError, DeviceUnavailableError
from . import lockrank


# ---- error taxonomy ---------------------------------------------------

class DeviceError(Exception):
    """Base for simulated/internal device-path errors. Deliberately NOT
    a TiDBError: classification must see these before the fatal
    (semantic-error) check."""
    err_class = "generic"


class GrantLostError(DeviceError):
    """Connection to the accelerator lost (the class keeps its
    historical name: it is a metric label and a failpoint)."""
    err_class = "grant_lost"


class DeviceResourceExhausted(DeviceError):
    """HBM / RESOURCE_EXHAUSTED class — retryable (caches may free)."""
    err_class = "resource_exhausted"


class DeviceCompileError(DeviceError):
    """Kernel compile failure — deterministic, degrade without retry."""
    err_class = "compile"


class DeviceWedgedError(DeviceError):
    """Watchdog timeout: the dispatch exceeded its budget."""
    err_class = "wedged"


class DeviceDegradedError(DeviceUnavailableError):
    """A dispatch exhausted its supervision budget. Callers catch this
    and take the host path; uncaught it surfaces as a clean statement
    error (code 9013), never a hang."""

    def __init__(self, site, err_class, cause, attempts):
        cs = "" if cause is None else \
            f": {type(cause).__name__}: {str(cause)[:160]}"
        super().__init__(
            "device dispatch at %s degraded after %d attempt(s) [%s]%s",
            site, attempts, err_class, cs)
        self.site = site
        self.err_class = err_class
        self.cause = cause
        self.attempts = attempts


# retryable: transient by nature — a later attempt can succeed.
RETRYABLE = frozenset({"grant_lost", "resource_exhausted", "wedged",
                       "transient"})
# degradeable = retryable + deterministic device failures; the host twin
# is always correct, so everything non-fatal degrades.
_XLA_NAMES = frozenset({"XlaRuntimeError", "JaxRuntimeError",
                        "InternalError", "FailedPreconditionError",
                        "UnavailableError", "AbortedError",
                        "JaxStackTraceBeforeTransformation"})


def classify(exc) -> str:
    """Map an exception from a device dispatch to an error class:
    grant_lost | resource_exhausted | wedged | transient | compile |
    degraded | generic | fatal. `fatal` (semantic TiDBErrors — kill,
    quota, constraint) is never retried and never degraded.
    `degraded` makes nested guards COMPOSE: an inner guarded_dispatch
    that exhausted its own budget raises DeviceDegradedError, and the
    outer guard must take its fallback immediately (no re-retry — the
    inner guard already retried; and not `fatal`, which would skip the
    outer host twin entirely)."""
    if isinstance(exc, DeviceDegradedError):
        return "degraded"
    if isinstance(exc, DeviceError):
        return exc.err_class
    if isinstance(exc, TiDBError):
        return "fatal"
    if isinstance(exc, MemoryError):
        return "resource_exhausted"
    if isinstance(exc, (ConnectionError, TimeoutError)):
        # transport-class failures (cluster RPC, WAL ship, socket
        # timeouts): transient by nature — reconnect-and-retry can
        # succeed. Plain OSError stays "generic": file/system errors
        # are not made retryable wholesale.
        return "transient"
    name = type(exc).__name__
    mod = getattr(type(exc), "__module__", "") or ""
    if name in _XLA_NAMES or mod.startswith(("jaxlib", "jax.")) \
            or mod == "jax":
        up = str(exc).upper()
        if "RESOURCE_EXHAUSTED" in up or "OUT OF MEMORY" in up:
            return "resource_exhausted"
        if ("UNAVAILABLE" in up or "ABORTED" in up or "CANCELLED" in up
                or "GRANT" in up or "CONNECTION" in up
                or "SOCKET" in up or "DISCONNECT" in up):
            return "grant_lost"
        if "DEADLINE_EXCEEDED" in up:
            return "wedged"
        if ("INVALID_ARGUMENT" in up or "UNIMPLEMENTED" in up
                or "COMPILATION" in up or "MOSAIC" in up):
            return "compile"
        return "transient"
    return "generic"


# ---- circuit breaker --------------------------------------------------

class CircuitBreaker:
    """Consecutive-failure breaker per site family ('copr', 'fused',
    'sort', ...). `threshold` consecutive degraded dispatches open the
    breaker for `cooldown_s`; while open every dispatch in the family
    short-circuits straight to the host twin. After the cooldown the
    next dispatch is a half-open trial: success closes the breaker,
    failure re-opens it immediately."""

    def __init__(self, threshold: int = 8, cooldown_s: float = 30.0):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self.consecutive = 0
        self.open_until = 0.0
        self.trips = 0
        self._mu = lockrank.ranked_lock("device_guard.breaker")

    def allow(self) -> bool:
        with self._mu:
            return time.time() >= self.open_until

    def record_success(self):
        with self._mu:
            self.consecutive = 0
            self.open_until = 0.0

    def record_failure(self) -> bool:
        """-> True when this failure newly opened the breaker."""
        with self._mu:
            self.consecutive += 1
            if self.consecutive >= self.threshold:
                was_open = time.time() < self.open_until
                self.open_until = time.time() + self.cooldown_s
                if not was_open:
                    self.trips += 1
                    return True
            return False


_BREAKERS: dict = {}
_BREAKERS_MU = lockrank.ranked_lock("device_guard.breakers")
METRICS: dict = {}          # module-level mirror for siteless dispatches
_METRICS_MU = lockrank.ranked_lock("device_guard.metrics")


def _breaker_for(family: str, threshold: int,
                 cooldown_s: float) -> CircuitBreaker:
    with _BREAKERS_MU:
        b = _BREAKERS.get(family)
        if b is None:
            b = CircuitBreaker(threshold, cooldown_s)
            _BREAKERS[family] = b
        else:
            b.threshold = threshold      # sysvar changes apply live
            b.cooldown_s = cooldown_s
        return b


def breakers() -> dict:
    return dict(_BREAKERS)


def reset():
    """Test hook: clear breaker state and module metrics."""
    with _BREAKERS_MU:
        _BREAKERS.clear()
    with _METRICS_MU:
        METRICS.clear()


# ---- HBM pressure protocol --------------------------------------------
# A RESOURCE_EXHAUSTED dispatch means the accelerator's memory is full
# RIGHT NOW — retrying blindly just re-runs the same allocation against
# the same full HBM (what PR 1 did). Before each retry of that class the
# guard now SHEDS: every registered device-resident store (weakly held;
# test domains must stay collectable) evicts half its charged bytes —
# cold LRU entries a later statement can re-upload — then the retry
# runs against the freed headroom; only if that still fails does the
# dispatch degrade to the host twin. Outcomes land in
# tidb_tpu_mem_pressure_total{action}.

_PRESSURE_STORES: list = []
_PRESSURE_MU = lockrank.ranked_lock("device_guard.pressure")


def register_pressure_store(store):
    """Register a DeviceResidentStore (or anything with .bytes and
    .evict_bytes(n)) for pressure shedding. Weakly referenced."""
    with _PRESSURE_MU:
        _PRESSURE_STORES.append(weakref.ref(store))


def relieve_memory_pressure() -> int:
    """Shed cold HBM: ask every live registered store to evict half its
    charged bytes. -> total bytes freed."""
    with _PRESSURE_MU:
        # prune dead refs in place under the lock (rebuilding from a
        # pre-eviction snapshot would drop a store registered while
        # the evictions ran, excluding it from pressure forever)
        _PRESSURE_STORES[:] = [r for r in _PRESSURE_STORES
                               if r() is not None]
        refs = list(_PRESSURE_STORES)
    freed = 0
    for r in refs:
        s = r()
        if s is None:
            continue
        try:
            have = int(getattr(s, "bytes", 0))
            if have > 0:
                freed += s.evict_bytes(max(have // 2, 1))
        except Exception:           # noqa: BLE001 — shedding is advisory
            pass
    return freed


def _bump(domain, name: str, v: int = 1):
    with _METRICS_MU:
        METRICS[name] = METRICS.get(name, 0) + v
    if domain is not None:
        try:
            domain.inc_metric(name, v)
        except Exception:           # noqa: BLE001
            pass


# ---- knobs ------------------------------------------------------------

def _knob(sv, name: str, env: str, default: int) -> int:
    if sv is not None:
        try:
            return int(sv.get(name))
        except Exception:           # noqa: BLE001
            pass
    try:
        return int(os.environ.get(env, default))
    except ValueError:
        return default


def backoff_delay(attempt: int, base: float = 0.05,
                  cap: float = 2.0) -> float:
    """Exponential backoff with +0-25% jitter, capped. attempt is
    0-based (first retry sleeps ~base)."""
    return min(base * (2 ** attempt), cap) * (1.0 + 0.25 * random.random())


# ---- watchdog ---------------------------------------------------------

def _with_watchdog(fn, timeout_ms: int, site: str):
    """Run fn, bounding it to timeout_ms when > 0. A dispatch that
    exceeds the budget raises DeviceWedgedError (classified retryable);
    the wedged worker thread is abandoned — a truly stuck XLA call
    cannot be cancelled, only supervised around."""
    if not timeout_ms or timeout_ms <= 0:
        return fn()
    box: dict = {}
    done = threading.Event()
    # phase state is thread-local; the worker records into a PRIVATE
    # dict that is folded into the statement's counters only when the
    # dispatch finishes inside its budget — an abandoned (wedged)
    # worker that later unwedges writes into garbage, never into a
    # subsequent statement's attribution
    worker_stats: dict = {}
    # the trace context is thread-local like phase state: spans the
    # worker opens (bind, dispatch) finish into a private list, folded
    # into the statement's trace below like the stats, and dropped
    # with an abandoned worker
    handed = _tracing.handoff()

    def run():
        _phase.adopt(worker_stats)
        box["spans"] = _tracing.adopt(handed)
        try:
            box["v"] = fn()
        except BaseException as e:      # noqa: BLE001
            box["e"] = e
        finally:
            done.set()

    t = threading.Thread(target=run, daemon=True,
                         name=f"device-dispatch:{site}")
    t.start()
    if not done.wait(timeout_ms / 1000.0):
        raise DeviceWedgedError(
            f"device dispatch at {site} exceeded {timeout_ms}ms watchdog")
    for k, v in worker_stats.items():
        _phase.add(k, v)
    _tracing.absorb(box.get("spans"))
    if "e" in box:
        raise box["e"]
    return box.get("v")


# ---- the supervisor ---------------------------------------------------

def _note_fallback(ectx, domain, site, err_class, exc, attempts,
                   fallback_is_host=True):
    _bump(domain, "device_fallback")
    if fallback_is_host:
        # only a degrade that actually lands on the host twin counts in
        # the labeled/per-digest fallback signals — an MPP degrade that
        # the single-chip DEVICE path then serves is a topology retreat,
        # not a host fallback (the flat device_fallback above keeps its
        # historical any-degrade semantics)
        _metrics.DEVICE_FALLBACKS.labels(site.split("/", 1)[0],
                                         err_class).inc()
        # statement-scoped: Session._observe folds this into the
        # digest's statements_summary / tidb_top_sql fallback_count
        _phase.inc("device_fallbacks")
    detail = "" if exc is None else \
        f": {type(exc).__name__}: {str(exc)[:120]}"
    target = "host" if fallback_is_host else "single-chip device path"
    msg = (f"device dispatch at {site} fell back to {target} after "
           f"{attempts} attempt(s) [{err_class}]{detail}")
    log("warn", "device_fallback", site=site, err_class=err_class,
        attempts=attempts)
    if ectx is not None:
        try:
            ectx.sess.vars.warnings.append({
                "level": "Warning",
                "code": DeviceUnavailableError.code,
                "sqlstate": DeviceUnavailableError.sqlstate,
                "msg": msg})
        except Exception:           # noqa: BLE001
            pass


def guarded_dispatch(fn, *, site: str, ectx=None, domain=None,
                     host_fallback=None, retry_limit=None,
                     timeout_ms=None, backoff_base_s: float = 0.05,
                     fallback_is_host: bool = True):
    """Supervise one device dispatch.

    fn            — the dispatch (upload + kernel + fetch); called once
                    per attempt.
    site          — 'family/op' label ('copr/agg', 'fused', 'join', ...);
                    the family keys the circuit breaker, the full site
                    keys the failpoint 'device_guard/<site>'.
    ectx          — ExecContext when available: supplies sysvars, the
                    statement deadline clamp, check_killed, and the
                    session whose diagnostics area gets the fallback
                    note.
    host_fallback — optional zero-arg host twin; called (once) when the
                    dispatch degrades. Without it, degrade raises
                    DeviceDegradedError for the caller's host path.
    fallback_is_host — False when this site's degrade is served by
                    another DEVICE path (MPP -> single-chip): such
                    degrades are excluded from the labeled fallback
                    counters and per-digest fallback_count.
    retry_limit / timeout_ms — override the sysvars
                    tidb_tpu_device_retry_limit /
                    tidb_tpu_device_dispatch_timeout_ms (env-seeded
                    defaults when no session is attached).

    Fatal errors (TiDBError: kill, quota, constraint, injected fatal)
    always re-raise unchanged — they are statement semantics, not
    device health.
    """
    sv = getattr(ectx, "sv", None) if ectx is not None else None
    if domain is None and ectx is not None:
        domain = ectx.sess.domain
    if retry_limit is None:
        retry_limit = _knob(sv, "tidb_tpu_device_retry_limit",
                            "TIDB_TPU_DEVICE_RETRY_LIMIT", 2)
    if timeout_ms is None:
        timeout_ms = _knob(sv, "tidb_tpu_device_dispatch_timeout_ms",
                           "TIDB_TPU_DEVICE_DISPATCH_TIMEOUT_MS", 0)
    threshold = _knob(sv, "tidb_tpu_device_breaker_threshold",
                      "TIDB_TPU_DEVICE_BREAKER_THRESHOLD", 8)
    cooldown = float(os.environ.get(
        "TIDB_TPU_DEVICE_BREAKER_COOLDOWN_S", "30"))
    family = site.split("/", 1)[0]
    breaker = _breaker_for(family, threshold, cooldown)
    fp_name = "device_guard/" + site

    def attempt():
        failpoint.inject(fp_name)
        return fn()

    if not breaker.allow():
        _bump(domain, "device_breaker_short_circuit")
        _metrics.BREAKER_SHORT_CIRCUIT.labels(family).inc()
        if fallback_is_host:
            # a short-circuited dispatch IS a degrade: without these the
            # per-digest fallback_count reads 0 during the exact window
            # when every dispatch in the family runs on the host twin
            _metrics.DEVICE_FALLBACKS.labels(family, "breaker_open").inc()
            _phase.inc("device_fallbacks")
        if host_fallback is not None:
            return host_fallback()
        raise DeviceDegradedError(site, "breaker_open", None, 0)

    attempts = 0
    pressure_evicted = False
    while True:
        if ectx is not None:
            ectx.check_killed()
        # span per dispatch attempt (no-op without an active trace):
        # a retried/degraded statement's trace shows every attempt
        # with its err_class, so TRACE answers "why was this slow"
        # without a device_guard log dive
        with _tracing.span("device_attempt", site=site,
                           attempt=attempts + 1):
            try:
                out = _with_watchdog(attempt, timeout_ms, site)
                breaker.record_success()
                if pressure_evicted:
                    # the shed worked: the retry that followed an HBM
                    # pressure eviction landed
                    _metrics.MEM_PRESSURE.labels("retry_ok").inc()
                return out
            except (KeyboardInterrupt, SystemExit, GeneratorExit):
                raise                   # process control, not device health
            except BaseException as exc:    # noqa: BLE001
                if isinstance(exc, TiDBError) and \
                        not isinstance(exc, DeviceDegradedError):
                    raise               # statement semantics, not health
                err_class = classify(exc)
                _tracing.tag(err_class=err_class)
                attempts += 1
                _bump(domain, "device_dispatch_error")
                _metrics.DEVICE_DISPATCH_ERRORS.labels(family,
                                                       err_class).inc()
                if err_class in RETRYABLE and attempts <= retry_limit:
                    delay = backoff_delay(attempts - 1,
                                          base=backoff_base_s)
                    remain = None
                    if ectx is not None and ectx.deadline is not None:
                        remain = ectx.deadline - time.time()
                    if remain is None or remain > delay:
                        if err_class == "resource_exhausted":
                            # HBM pressure protocol: shed cold resident
                            # entries BEFORE retrying — a blind retry
                            # re-runs the same allocation against the
                            # same full device memory
                            freed = relieve_memory_pressure()
                            _metrics.MEM_PRESSURE.labels(
                                "evict" if freed > 0 else "evict_noop"
                            ).inc()
                            _bump(domain, "mem_pressure_evict")
                            if freed > 0:
                                pressure_evicted = True
                                log("warn", "mem_pressure_evict",
                                    site=site, freed_bytes=freed,
                                    attempt=attempts)
                        _bump(domain, "device_retry")
                        _metrics.DEVICE_RETRIES.labels(family,
                                                       err_class).inc()
                        log("warn", "device_retry", site=site,
                            err_class=err_class, attempt=attempts,
                            err=f"{type(exc).__name__}: "
                                f"{str(exc)[:120]}")
                        time.sleep(delay)
                        continue
                    # too close to the statement deadline: degrade now
                    # so retries never outlive max_execution_time
                tripped = breaker.record_failure()
                if tripped:
                    _bump(domain, "device_breaker_open")
                    _metrics.BREAKER_OPEN.labels(family).inc()
                    log("warn", "device_breaker_open", family=family,
                        threshold=breaker.threshold,
                        cooldown_s=breaker.cooldown_s)
                if err_class == "resource_exhausted":
                    # the pressure protocol (evict + retry) ran out of
                    # road: the statement degrades to the host twin
                    _metrics.MEM_PRESSURE.labels("degrade").inc()
                _note_fallback(ectx, domain, site, err_class, exc,
                               attempts,
                               fallback_is_host=fallback_is_host)
                if host_fallback is not None:
                    _tracing.tag(fallback="host")
                    return host_fallback()
                raise DeviceDegradedError(site, err_class, exc,
                                          attempts) from exc


# ---- chaos: register the injectable error classes ---------------------

failpoint.register_error(
    "grant_lost", lambda: GrantLostError(
        "injected device connection loss mid-dispatch (grant_lost)"))
failpoint.register_error(
    "resource_exhausted", lambda: DeviceResourceExhausted(
        "injected RESOURCE_EXHAUSTED (HBM allocation failed)"))
failpoint.register_error(
    "compile", lambda: DeviceCompileError(
        "injected kernel compile failure"))
failpoint.register_error(
    "generic", lambda: RuntimeError("injected generic device error"))
failpoint.register_error(
    "fatal", lambda: failpoint.FailpointError(
        "injected fatal device error"))
failpoint.register_error(
    "conn_reset", lambda: ConnectionResetError(
        "injected connection reset"))
