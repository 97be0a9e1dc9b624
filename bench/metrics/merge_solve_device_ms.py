"""Device milliseconds per round of the tree program's leaf operations
under the named scope merge_solve (the weights solved at the root), mean
over the devices."""
import round_scopes


def read(run):
    return round_scopes.merge_ms(run, "merge_solve")
