"""Host milliseconds per fit from the call of DAEFEngine.fit to its return,
before block_until_ready (the benchmark's own clock)."""


def read(run):
    rec = run["record"]
    return 1e3 * rec["host_s"] / rec["fits"]
