"""Device milliseconds per fit of the fit program's ops under the named
scope stats (each layer's Gram-statistics fold), outside encoder."""
import scopes


def read(run):
    return scopes.device_ms(run, "stats")
