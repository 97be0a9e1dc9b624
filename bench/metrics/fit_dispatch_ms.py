"""Host milliseconds per fit in the program's span fit.dispatch (the call of
the compiled fit program), over the spans in which JAX neither traced nor
compiled: the warm-up fit's compile is left out."""
import scopes


def read(run):
    return scopes.span_ms("fit.dispatch")
