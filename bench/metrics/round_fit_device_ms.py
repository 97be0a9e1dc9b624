"""Device milliseconds per round of the per-shard fit program's leaf
operations, mean over the devices."""
import round_scopes


def read(run):
    return round_scopes.fit_ms(run)
