"""Device milliseconds per fit of every other op of the fit program: the
named scopes forward and errors, and ops under no stage scope."""
import scopes


def read(run):
    return scopes.device_ms(run, "forward", "errors", "unscoped", "unmapped")
