"""Device busy milliseconds per fit over the traced window."""
import readers


def read(run):
    return readers.device_ms_per(run, "fits")
