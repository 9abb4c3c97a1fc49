"""Force jax onto the CPU backend. Import FIRST in any CPU-only script
(before the first jax device op), mirroring tests/conftest.py."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tidb_tpu import force_cpu_backend  # noqa: E402

force_cpu_backend()
