"""A scan class's share of its memory-bound roofline, in percent."""

from __future__ import annotations

from statistics import median


def scan_share(run, cls: str):
    """Least time the chips could take to read the class's bytes (its class
    file's ``bytes_read`` over peak HBM bytes/s, the bytes spread over the
    cell's chips) over the device time one statement of it took."""
    ms = run.device_ms().get(cls, []) if int(run.mix["clients"]) == 1 else []
    if not ms or median(ms) <= 0:
        return None
    peak = run.peaks[run.device_kind]["hbm_bytes_per_s"]
    need = run.classes[cls].bytes_read(run.rows, run.widths)
    least_ms = need / run.cell["chips"] / peak * 1e3
    return 100.0 * least_ms / median(ms)
