"""Arithmetic the per-layer metric readers share (``metrics/<name>.py``).

``run`` is what the harness hands a reader: the generator's ``record``, the
trace reduction (``trace``, see ``trace_reduce.reduce``), the ``device``
facts, the configuration and the chips the cell uses.
"""
from __future__ import annotations

import peaks


def idle_share(run) -> float | None:
    """Percent of the traced window in which no operation ran on the
    devices (mean over the devices), or None without a device trace."""
    t = run["trace"]
    if not t or not t["devices"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def peak_share(run, flops_key: str, count_key: str) -> float | None:
    """Percent of the chips' bf16 peak that the record's operations per item
    times items per second reach, or None off the chip."""
    if run["device"]["platform"] != "tpu":
        return None
    rec = run["record"]
    peak = peaks.peaks(run["device"]["kind"]).flops
    return 100.0 * rec[flops_key] * rec[count_key] / rec["window_s"] / (run["chips"] * peak)


def device_ms_per(run, count_key: str) -> float | None:
    """Device busy milliseconds per item of the record, from the trace."""
    t = run["trace"]
    if not t or not t["devices"] or not run["record"][count_key]:
        return None
    return 1e3 * t["busy_s"] / run["record"][count_key]
