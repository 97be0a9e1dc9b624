"""Select the Gram-stats producer for a block of code, the way the library
itself learns it: from the autotune cache.

    from repro.testing.producer import stats_producer

    with stats_producer("fused"):
        model = engine.fit(x)          # traced with the Pallas kernels

``stats_backend.producer()`` reads ``platforms.<platform>.preferred_backend``
of the active autotune cache.  The context points ``$REPRO_AUTOTUNE_CACHE``
at a temporary copy of that cache (block tables kept) naming ``name`` for
the running platform, and drops the in-memory cache and JAX's compiled
programs on entry and on exit, so no trace of one producer serves the other.
"""
from __future__ import annotations

import contextlib
import copy
import json
import os
import tempfile
from pathlib import Path


@contextlib.contextmanager
def stats_producer(name: str):
    """Run the block with ``stats_backend.producer() == name``."""
    import jax

    from repro.core import stats_backend
    from repro.kernels import autotune

    if name not in stats_backend.PRODUCERS:
        raise ValueError(f"unknown producer {name!r}: choose from {stats_backend.PRODUCERS}")
    cache = copy.deepcopy(autotune.load_cache())
    cache["version"] = autotune.CACHE_VERSION
    entry = cache.setdefault("platforms", {}).setdefault(jax.default_backend(), {})
    entry["preferred_backend"] = name
    before = os.environ.get(autotune.CACHE_ENV)
    with tempfile.TemporaryDirectory() as d:
        path = Path(d) / "autotune_cache.json"
        path.write_text(json.dumps(cache))
        os.environ[autotune.CACHE_ENV] = str(path)
        autotune.clear_cache()
        jax.clear_caches()
        try:
            yield name
        finally:
            if before is None:
                os.environ.pop(autotune.CACHE_ENV, None)
            else:
                os.environ[autotune.CACHE_ENV] = before
            autotune.clear_cache()
            jax.clear_caches()


__all__ = ["stats_producer"]
