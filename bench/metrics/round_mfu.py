"""Percent of the chips' bf16 peak: the operations one federated round needs
(flops.fit_flops of every site plus the tree merge's, generators/fed_round.py
merge_flops) times rounds per second of the window."""
import readers


def read(run):
    return readers.peak_share(run, "flops_per_round", "rounds")
