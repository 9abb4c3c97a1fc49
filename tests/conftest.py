"""Test env: CPU with 8 virtual devices (multi-chip sharding paths run on a
virtual mesh), x64 for int64/decimal semantics."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_X64"] = "1"
# run the whole suite with the lock-rank sanitizer armed: any lock
# acquisition that violates utils/lockrank_ranks.py raises
# LockRankError at the offending acquire (utils/lockrank.py)
os.environ.setdefault("TIDB_TPU_LOCKRANK", "1")

from tidb_tpu import force_cpu_backend  # noqa: E402

force_cpu_backend()

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _metrics_isolation():
    """Metric-state isolation: the process-global registry
    (utils/metrics), device_guard breakers/module counters, and phase
    counters all outlive a Domain — without a reset, any assertion on
    absolute metric values is test-order-dependent. Zeroed at each test
    START (module-scoped TestKit fixtures may legitimately accumulate
    WITHIN a test)."""
    from tidb_tpu.utils import metrics, phase, device_guard
    metrics.reset_all()
    device_guard.reset()
    phase.reset()
    yield


@pytest.fixture
def judged_runs():
    """-> read(since=None): {(site, kind, verdict): runs} of
    `tidb_tpu_agg_lowering_total`'s samples that have moved, since an
    earlier reading when one is given."""
    from tidb_tpu.utils.metrics import AGG_LOWERING

    def read(since=None):
        since = since or {}
        now = {(lb["site"], lb["kind"], lb["verdict"]): int(v)
               for _name, lb, v in AGG_LOWERING.sample_rows()}
        return {k: n - since.get(k, 0) for k, n in now.items()
                if n - since.get(k, 0)}
    return read
