"""Device milliseconds per round of collective operations (the butterfly's
collective-permutes; trace_reduce's collective_s), mean over the devices."""
import round_scopes


def read(run):
    if round_scopes.programs(run) is None:
        return None
    return round_scopes.per_round_ms(run, run["trace"]["collective_s"])
