"""Faults planted in the program underneath a benchmark run, each one a
context manager.  ``test_faults.py`` runs the harness over each at a tiny
size; ``calibrate.py --fault-seeds`` reads them on the chip at a cell's own size.

* ``stale``: ``DAEFEngine.fit`` hands back its first result again, the state
  left unchanged;
* ``half``: ``DAEFEngine.fit`` sees only the first half of each data
  matrix's samples, its statistics summed over the rest.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patch(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _engine():
    from repro.engine import DAEFEngine

    return DAEFEngine


@contextlib.contextmanager
def stale():
    cls = _engine()
    original, first = cls.fit, []

    def fit(self, x, **kw):
        out = original(self, x, **kw)
        if not first:
            first.append(out)
        return first[0]

    with _patch(cls, "fit", fit):
        yield


@contextlib.contextmanager
def half():
    cls = _engine()
    original = cls.fit
    with _patch(cls, "fit", lambda self, x, **kw: original(self, x[..., : x.shape[-1] // 2],
                                                            **kw)):
        yield


FAULTS = {"stale": stale, "half": half}

#: The faults each traffic kind can have.
BY_KIND = {"fit": ("stale", "half")}
