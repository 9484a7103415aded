"""The benchmark's own tests: ``python -m pytest bench/tests``, on the CPU.

Four virtual host devices let the four-chip cell's mesh run here; the
flag only affects the CPU backend and must be set before JAX starts."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
