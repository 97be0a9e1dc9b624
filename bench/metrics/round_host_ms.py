"""Host milliseconds per federated round inside the benchmark's spans
bench.round.fit and bench.round.reduce (the calls of DAEFEngine.fit and
DAEFEngine.reduce, before block_until_ready)."""


def read(run):
    rec = run["record"]
    return 1e3 * (rec["fit_host_s"] + rec["reduce_host_s"]) / rec["rounds"]
