"""Host milliseconds per reduce in the program's span reduce.prepare under
engine.reduce (checks and the host read of the fleet's seeds and lambdas,
which waits for them), over the spans in which JAX neither traced nor
compiled."""
import scopes


def read(run):
    return scopes.span_ms("reduce.prepare")
