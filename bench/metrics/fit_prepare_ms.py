"""Host milliseconds per fit in the program's span fit.prepare (checks,
resolved config, layer keys, per-tenant seeds and lambdas), over the spans
in which JAX neither traced nor compiled."""
import scopes


def read(run):
    return scopes.span_ms("fit.prepare")
