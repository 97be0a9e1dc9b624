"""Seconds of the program's fit.dispatch spans in which JAX traced or
compiled: the warm-up fit's trace, lowering and compile (or cache load)."""
import scopes


def read(run):
    return scopes.compiled_s("fit.dispatch")
