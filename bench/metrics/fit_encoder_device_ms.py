"""Device milliseconds per fit of the fit program's ops under the named
scope encoder (the Gram, its eigh and the projection)."""
import scopes


def read(run):
    return scopes.device_ms(run, "encoder")
