"""Bring-up contracts (ISSUE 21): one placeable compile cache, a CPU pin
that uses public jax configuration only, entry points that name their
backend and refuse to pass without the device they were asked for, and
a chip smoke that turns any device degrade into a nonzero exit."""
import ast
import inspect
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_over, timeout=180):
    """Child python from the repo root with a clean jax environment
    (one CPU device, no inherited platform or cache directory)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "TIDB_TPU_PLATFORM",
                        "JAX_COMPILATION_CACHE_DIR", "TIDB_TPU_FAILPOINTS")}
    env.update(env_over)
    return subprocess.run([sys.executable] + args, cwd=_REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


_CACHE_PROBE = """
import jax
updates = []
_orig = jax.config.update
def _spy(name, val):
    if name == "jax_compilation_cache_dir":
        updates.append(val)
    return _orig(name, val)
jax.config.update = _spy
import tidb_tpu.utils.jaxcfg as jc
from tidb_tpu.session.sysvars import get_sysvar
print(repr((jc.persistent_cache_dir, jax.config.jax_compilation_cache_dir,
            get_sysvar("tidb_tpu_jax_cache_dir").default, updates)))
"""


def test_cache_dir_resolution_is_pure(monkeypatch):
    from tidb_tpu.utils import resolve_jax_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert resolve_jax_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert resolve_jax_cache_dir() == os.path.join(_REPO, ".cache", "jax")


def test_cache_dir_env_set_is_the_only_directory(tmp_path):
    d = str(tmp_path / "placed")
    r = _run(["-c", _CACHE_PROBE],
             {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": d})
    assert r.returncode == 0, r.stderr[-2000:]
    in_force, jax_dir, sysvar, updates = ast.literal_eval(
        r.stdout.strip().splitlines()[-1])
    assert in_force == jax_dir == sysvar == d
    # jax took the directory from the environment itself: nothing in
    # code set this one or any other
    assert updates == []


def test_cache_dir_unset_is_under_the_checkout():
    r = _run(["-c", _CACHE_PROBE], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    in_force, jax_dir, sysvar, updates = ast.literal_eval(
        r.stdout.strip().splitlines()[-1])
    want = os.path.join(_REPO, ".cache", "jax")
    # equal across processes: the child's and this process's resolution
    from tidb_tpu.utils import resolve_jax_cache_dir
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        assert resolve_jax_cache_dir() == want
    assert in_force == jax_dir == sysvar == want
    assert updates == [want]


def test_force_cpu_backend_public_configuration_only():
    import tidb_tpu
    src = inspect.getsource(tidb_tpu.force_cpu_backend)
    assert "_src" not in src and "xla_bridge" not in src
    r = _run(["-c", "from tidb_tpu import force_cpu_backend; "
                    "force_cpu_backend(); import os, sys, jax; "
                    "assert jax.default_backend() == 'cpu'; "
                    "assert os.environ['JAX_PLATFORMS'] == 'cpu'; "
                    "print(jax.devices()[0].platform)"],
             {"JAX_PLATFORMS": "tpu"})      # asked for a TPU, pinned anyway
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "cpu"


def test_cli_names_its_backend():
    r = _run(["-m", "tidb_tpu", "--cpu", "-e", "select 1+1"], {})
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "2"
    assert "backend cpu (cpu) x1" in r.stderr


def test_chip_smoke_refuses_the_cpu():
    r = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"}, timeout=60)
    assert r.returncode != 0
    assert "JAX_PLATFORMS='cpu'" in r.stdout and "TPU only" in r.stdout
    # no result line, and no query ran
    assert '"ok"' not in r.stdout and "cold" not in r.stdout


def test_bench_asked_for_a_tpu_without_one_exits_nonzero():
    r = _run(["bench.py"], {"JAX_PLATFORMS": "tpu", "BENCH_SF": "0.01"})
    assert r.returncode != 0
    assert "tpu" in r.stderr.lower()
    assert '"metric"' not in r.stdout


def test_bench_has_no_probe_fallback_or_replay():
    with open(os.path.join(_REPO, "bench.py")) as f:
        src = f.read()
    for gone in ("subprocess", "os._exit", "replay", "cpu-fallback",
                 "watchdog"):
        assert gone not in src, gone


def test_miniclient_round_trip_and_error_packet():
    from tidb_tpu.session import new_store
    from tidb_tpu.server import Server
    from tidb_tpu.testkit import MiniClient
    srv = Server(new_store(), port=0).start()
    try:
        c = MiniClient(srv.port, db="test")
        assert c.query("select 40 + 2 as a, null as b, 'x' as c") == {
            "cols": ["a", "b", "c"], "rows": [("42", None, "x")]}
        c.query("create table mc (a int primary key)")
        assert c.query("insert into mc values (1), (2)") == {"affected": 2}
        with pytest.raises(RuntimeError, match="server error 1146"):
            c.query("select * from no_such_table")
        assert c.query("select count(*) from mc")["rows"] == [("2",)]
        c.close()
    finally:
        srv.shutdown()


# chip_smoke.py with its platform check bypassed HERE ONLY: the script
# itself has no such switch. One CPU device, tiny scale.
_SMOKE_ON_CPU = ("import sys, jax, chip_smoke; "
                 "chip_smoke.require_tpu = lambda: jax; "
                 "sys.exit(chip_smoke.main(['--sf', '0.01']))")


def test_chip_smoke_passes_when_nothing_degrades(tmp_path):
    r = _run(["-c", _SMOKE_ON_CPU],
             {"JAX_PLATFORMS": "cpu",
              "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    # the last line is the driver's contract: these keys and no others
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    tag = "# smoke summary: "
    assert lines[-2].startswith(tag)
    summary = json.loads(lines[-2][len(tag):])
    assert summary["delta_applied"] > 0
    assert set(summary["smoke_timings_s"]) == {"load", "cold_pass",
                                               "warm_pass"}


def test_chip_smoke_fails_on_an_injected_device_degrade(tmp_path):
    """A deterministic compile failure at the fused site still returns
    the right rows (the host twin serves them) — and must end the smoke
    nonzero, naming the 9013 warning."""
    r = _run(["-c", _SMOKE_ON_CPU],
             {"JAX_PLATFORMS": "cpu",
              "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
              "TIDB_TPU_FAILPOINTS": "device_guard/fused=error:compile"},
             timeout=600)
    assert r.returncode != 0
    assert "FAIL" in r.stdout and "9013" in r.stdout
    assert '"ok"' not in r.stdout
