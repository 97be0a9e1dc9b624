"""Percent of the traced window in which the devices ran nothing."""
import readers


def read(run):
    return readers.idle_share(run)
