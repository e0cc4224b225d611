"""The benchmark's own tests, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests

They sit outside the repository's ``testpaths``.  The CPU is split into
four devices, so that the batch-sharded path runs too.
"""
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH_DIR, os.path.join(os.path.dirname(BENCH_DIR), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
