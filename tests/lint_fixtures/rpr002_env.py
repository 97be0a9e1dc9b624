# repro-lint: library
"""RPR002 fixture: env resolution after trace time / at import time."""
import os
from functools import partial

import jax

_IMPORT_TIME = os.environ.get("REPRO_FIXTURE_FLAG", "0")     # line 8: RPR002
_ALSO_BAD = os.getenv("REPRO_FIXTURE_FLAG2")                 # line 9: RPR002


@jax.jit
def bad_inside_jit(x):
    if os.environ.get("REPRO_FIXTURE_FAST") == "1":          # line 14: RPR002
        return x * 2
    return x


@partial(jax.jit, static_argnames=("mode",))
def bad_getenv_inside_jit(x, mode):
    scale = float(os.getenv("REPRO_FIXTURE_SCALE", "1"))     # line 21: RPR002
    return x * scale


def clean_call_time_resolution(backend=None):
    """The compile_cache.enable() idiom: read at call time, pre-trace."""
    if backend is None:
        backend = os.environ.get("REPRO_FIXTURE_BACKEND") or "einsum"
    return backend
