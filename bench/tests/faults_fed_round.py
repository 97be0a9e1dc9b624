"""Faults of the ``fed_round`` traffic, planted in the program underneath a
benchmark run; each one a context manager, registered as ``faults.py``'s
are (``FAULTS``, ``BY_KIND``), so that a caller can add them to those.

* ``no_exchange``: the tree program's ``ppermute`` hands each device back
  its own state, so no device sees another's sites;
* ``no_merge``: ``DAEFEngine.reduce`` hands back site 0's model.

Entering or leaving a fault drops the compiled tree programs, so that the
next reduce traces the program as it then stands.
"""
from __future__ import annotations

import contextlib

from faults import _engine, _patch


def _drop_tree_programs():
    import jax

    from repro.core import fleet_sharded

    fleet_sharded._merge_tree_fn.cache_clear()
    jax.clear_caches()


@contextlib.contextmanager
def no_exchange():
    import jax

    _drop_tree_programs()
    try:
        with _patch(jax.lax, "ppermute", lambda x, axis_name, perm: x):
            yield
    finally:
        _drop_tree_programs()


@contextlib.contextmanager
def no_merge():
    import jax

    cls = _engine()

    def reduce(self, state, group_size):
        return jax.tree.map(lambda leaf: leaf[:1], state)

    with _patch(cls, "reduce", reduce):
        yield


FAULTS = {"no_exchange": no_exchange, "no_merge": no_merge}

#: The faults each traffic kind can have.
BY_KIND = {"fed_round": ("no_exchange", "no_merge")}
