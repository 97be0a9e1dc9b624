"""Percent of the traced window in which the devices ran nothing (mean over
the devices)."""
import readers


def read(run):
    return readers.idle_share(run)
