"""Host milliseconds per fit in the program's span fit.place (the input put
on the device), over the spans in which JAX neither traced nor compiled."""
import scopes


def read(run):
    return scopes.span_ms("fit.place")
