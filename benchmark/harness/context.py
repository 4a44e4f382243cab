"""What one run measured, as the metric readers see it.  A reader is a file
``end_to_end/<name>.py`` or ``layer_metrics/<name>.py`` with one function
``read(run, arg)``; it returns a number, or ``None`` where it finds
nothing to read, and the harness then leaves the metric out."""

from __future__ import annotations

from dataclasses import dataclass, field
from statistics import median

from harness import stats, xplane


@dataclass
class Run:
    cell: dict
    config: dict
    mix: dict
    classes: dict                   # name -> class module
    # the load generator's records, one per statement sent
    records: list = field(default_factory=list)
    t0: float = 0.0                 # window, time.monotonic seconds
    t_end: float = 0.0
    setup_s: float = 0.0
    setup_parts: dict = field(default_factory=dict)
    # /sched at the window's start and end (the program's counters)
    sched_before: dict = field(default_factory=dict)
    sched_after: dict = field(default_factory=dict)
    # information_schema.statements_summary at the same two instants:
    # {class: (exec_count, sum_latency_ms)}
    summary_before: dict = field(default_factory=dict)
    summary_after: dict = field(default_factory=dict)
    # flight-recorder span trees of statements inside the window
    trees: list = field(default_factory=list)
    # the profiler's trace of a slice of the window (``--trace 1``)
    trace: dict | None = None
    trace_lo_ns: float = 0.0        # slice bounds, on the trace's clock
    trace_hi_ns: float = 0.0
    clock_offset_ns: float = 0.0    # trace clock minus time.monotonic_ns
    peaks: dict = field(default_factory=dict)
    rows: dict = field(default_factory=dict)        # table -> rows
    widths: dict = field(default_factory=dict)      # table -> col -> bytes
    memory_peak_bytes: int = 0
    device_kind: str = ""
    _device_ms: dict = field(default_factory=dict, repr=False)

    # ---------------------------------------------------------------- #

    def answered(self) -> list:
        """Records of statements that came back with rows."""
        return [r for r in self.records if r["ok"] is not None]

    def ms(self) -> dict[str, dict[int, list[float]]]:
        """Statement time in ms, ``{class: {statement: [...]}}``, from when
        each was due (which in a closed loop is when it was sent) to its
        last row decoded."""
        out: dict = {}
        for r in self.answered():
            out.setdefault(r["class"], {}).setdefault(r["stmt"], []).append(
                (r["done"] - r["due"]) * 1e3)
        return out

    def readings(self) -> list[tuple[float, int, float]]:
        """``(due, statement, ms)`` of every statement answered."""
        return [(r["due"], r["stmt"], (r["done"] - r["due"]) * 1e3)
                for r in self.answered()]

    def ms_by_class(self) -> dict[str, list[float]]:
        return {c: [x for v in by.values() for x in v]
                for c, by in self.ms().items()}

    def completed_in_window(self) -> int:
        return sum(1 for r in self.records
                   if r["ok"] and r["done"] <= self.t_end)

    def sched_delta(self, *path: str) -> float:
        """Growth of a ``/sched`` counter over the window."""
        a, b = self.sched_before, self.sched_after
        for k in path:
            a, b = (a or {}).get(k, 0), b[k]
        return b - (a or 0)

    def span_ms(self, name: str) -> dict[str, list[float]]:
        """Per class, each sampled statement's time in spans of that name
        (ms); statements without such a span are left out.  By class,
        because the flight recorder keeps every slow statement and one in
        sixteen of the rest, so its sample is not the window's mix."""
        out: dict = {}
        for t in self.trees:
            ms = [s["duration_us"] / 1e3 for s in t["spans"]
                  if s["name"] == name]
            if ms:
                out.setdefault(t["class"], []).append(sum(ms))
        return out

    def traced_statements(self) -> list[tuple]:
        """``(class, start_ns, end_ns)`` on the trace's clock."""
        return [(r["class"], r["sent"] * 1e9 + self.clock_offset_ns,
                 r["done"] * 1e9 + self.clock_offset_ns)
                for r in self.answered()]

    def device_ms(self, only=None) -> dict[str, list[float]]:
        """Device ms per statement by class (``xplane.per_statement``),
        worked out once for each ``only``: several readers ask."""
        if self.trace is None:
            return {}
        if only not in self._device_ms:
            self._device_ms[only] = xplane.per_statement(
                self.trace, self.traced_statements(), self.trace_lo_ns,
                self.trace_hi_ns, only)
        return self._device_ms[only]

    def busy(self) -> dict:
        if self.trace is None:
            return {}
        return xplane.busy(self.trace, self.trace_lo_ns, self.trace_hi_ns)


def median_or_none(values: list[float]):
    return median(values) if values else None


def geomean_of_medians(by_class: dict[str, list[float]]):
    """Geometric mean over classes of each class's median, as the
    end-to-end statement time is taken; ``None`` for nothing to read."""
    medians = [median(v) for v in by_class.values() if v]
    if not medians or min(medians) <= 0:
        return None
    return stats.geomean(medians)
