"""Device milliseconds per round of the tree program's leaf operations and
of _every_nth's, mean over the devices."""
import round_scopes


def read(run):
    return round_scopes.merge_ms(run)
