"""Device milliseconds per fit of the fit program's ops under the named
scope solve (each layer's weight solve), outside encoder."""
import scopes


def read(run):
    return scopes.device_ms(run, "solve")
