"""Shared helper: run a test body in a subprocess with 8 virtual host devices.

XLA locks the device count at first init, so the main pytest process must
stay single-device for every other test; anything that needs a real
multi-device mesh runs through `run_on_devices`.  (Same pattern as
tests/test_distributed.py, factored out for the sharded-fleet test files.)
"""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import sys
sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
"""


def run_on_devices(*parts: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Execute the concatenated ``parts`` in a fresh interpreter with
    ``n_devices`` forced host devices; returns stdout, asserts a zero exit.
    Each part is dedented independently (shared preludes are flush-left,
    test bodies are indented to their call site)."""
    script = _PRELUDE.format(
        n=n_devices, src=os.path.join(ROOT, "src")
    ) + "\n".join(textwrap.dedent(p) for p in parts)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout
