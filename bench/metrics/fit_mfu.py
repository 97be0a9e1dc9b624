"""Percent of the chips' bf16 peak: the operations one fit needs
(flops.fit_flops) times fits per second of the window."""
import readers


def read(run):
    return readers.peak_share(run, "flops_per_fit", "fits")
