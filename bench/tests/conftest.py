"""The benchmark's own tests run on the CPU, with four virtual devices for
the reference's tree-merge test: ``python -m pytest bench/tests``.  Both
settings must be in place before JAX is first imported."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4").strip()

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH / "tests", BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
