"""The program's own spans, read from the profiler's trace.

The program enters a ``jax.profiler.TraceAnnotation`` for every copscope
span (``tidb_tpu/obs/trace.py``), so a traced run's ``.xplane.pb`` holds
them on the plane ``/host:CPU``, on the line of the thread that made
them and on the clock of the device planes: ``session.ExecuteStmt``,
``session.parse``, ``session.plan``, ``plan.gates``, ``cop.dispatch``,
``sched.admit``, ``sched.launch`` (stat ``program``: the name of the
device program it dispatched, which is also the name of that program's
``XLA Modules`` events after ``jit_``), ``sched.compile``,
``cop.transfer``, ``cop.device_wait``, ``cop.d2h``, ``cop.host_merge``,
``session.resultset``, ``wire.write``; all but ``session.parse`` carry the
statement's ``trace_id``.  ``xplane.read`` keeps only the sync marks of
that plane, so this reads the file again.

A program without the annotations (any commit before PR 23) gives an
empty list, and every reader built on it then returns ``None``.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os

from harness import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPAN_PREFIXES = ("session.", "plan.", "sched.", "cop.", "wire.")
# the host phase a span belongs to, innermost first: what the host was
# doing while the device waited
PHASES = ("wire", "session", "sched", "transfer", "merge")


def phase(name: str) -> str:
    if name == "wire.write":
        return "wire"
    if name in ("cop.host_merge", "session.resultset"):
        return "merge"
    if name in ("cop.transfer", "cop.device_wait", "cop.d2h"):
        return "transfer"
    if name == "cop.dispatch" or name.startswith("sched."):
        return "sched"
    return "session"


def load(path: str) -> list[dict]:
    """``[{"name", "start", "end", "line", "trace_id", "program"}]``, by
    start, times in ns on the trace's clock, from an ``.xplane.pb`` file
    or a gzip of one."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if not e.name.startswith(SPAN_PREFIXES):
                    continue
                stats = dict(e.stats)
                out.append({"name": e.name, "start": e.start_ns,
                            "end": e.start_ns + e.duration_ns, "line": i,
                            "trace_id": stats.get("trace_id"),
                            "program": stats.get("program")})
    return sorted(out, key=lambda s: (s["start"], -s["end"]))


def of(run) -> list[dict]:
    """The traced run's host spans, read once.  The harness keeps no path
    on the run; it writes ``.benchrun/<cell>/trace_meta.json``."""
    if "_hostspans" not in run.__dict__:
        meta = os.path.join(ROOT, ".benchrun", run.cell["name"],
                            "trace_meta.json")
        spans = []
        if run.trace is not None and os.path.isfile(meta):
            with open(meta) as f:
                spans = load(json.load(f)["file"])
        run.__dict__["_hostspans"] = spans
    return run.__dict__["_hostspans"]


def phases(spans: list[dict], lo: float, hi: float) -> dict[str, list]:
    """[lo, hi] cut into sorted disjoint intervals per host phase: at
    each instant the phase of the innermost span in flight, which across
    threads is the one entered last; ``wire`` where none is."""
    edges = sorted({lo, hi} | {t for s in spans
                               for t in (s["start"], s["end"]) if lo < t < hi})
    starting = sorted(spans, key=lambda s: s["start"])
    out = {p: [] for p in PHASES}
    active, k = [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(starting) and starting[k]["start"] <= a:
            active.append(starting[k])
            k += 1
        active = [s for s in active if s["end"] > a]
        name = max(active, key=lambda s: (s["start"], -s["end"]))["name"] \
            if active else ""
        ivs = out[phase(name) if name else "wire"]
        if ivs and ivs[-1][1] == a:
            ivs[-1] = (ivs[-1][0], b)
        else:
            ivs.append((a, b))
    return out


def idle_by_phase(trace: dict, spans: list[dict], lo: float,
                  hi: float) -> dict[str, float]:
    """Idle nanoseconds of the least busy device inside [lo, hi], split
    by the host phase in flight.  The parts sum to the device's idle
    time: ``xplane.busy``'s idle share times the slice."""
    unions = [xplane.union(d["ops"], lo, hi)
              for d in trace["devices"].values()]
    if not unions or not spans:
        return {}
    busy = min(unions, key=lambda u: xplane.covered(u, lo, hi))
    gaps, edge = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    return {p: sum(xplane.covered(ivs, a, b) for a, b in gaps)
            for p, ivs in phases(spans, lo, hi).items()}


def launches(trace: dict, spans: list[dict], lo: float, hi: float) -> list:
    """Every ``sched.launch`` annotation that starts inside [lo, hi] and
    names its program, paired with that program's execution and with the
    wait for it: ``{"program", "trace_id", "launch", "start", "end",
    "ready"}``.  ``launch`` is the annotation's start and ``ready`` the
    end of the statement's first ``cop.device_wait`` after it, both on
    the host's clock; ``start`` (first device) and ``end`` (last device)
    are the module event's, on the device's.  The two clocks of a trace
    differ by up to a millisecond (PERF.md section 6, PR 23), so a launch
    is paired with the execution of its program that starts nearest to
    it, not with the first one after it."""
    waits: dict = {}
    for s in spans:
        if s["name"] == "cop.device_wait":
            waits.setdefault(s["trace_id"], []).append(s)
    per_dev = []                # {module name: its events, by start}
    for dev in trace["devices"].values():
        by_name: dict = {}
        for m in dev["modules"]:
            by_name.setdefault(m[2].split("(")[0], []).append(m)
        per_dev.append(by_name)
    out = []
    for s in spans:
        if s["name"] != "sched.launch" or not s["program"] \
                or not lo <= s["start"] <= hi:
            continue
        ran = []
        for by_name in per_dev:
            mods = by_name.get("jit_" + s["program"], ())
            i = bisect.bisect_left(mods, (s["start"],))
            near = mods[max(i - 1, 0):i + 1]
            if near:
                ran.append(min(near, key=lambda m: abs(m[0] - s["start"])))
        if not ran:
            continue
        wait = next((w for w in waits.get(s["trace_id"], ())
                     if w["end"] >= s["start"]), None)
        out.append({"program": s["program"], "trace_id": s["trace_id"],
                    "launch": s["start"],
                    "start": min(m[0] for m in ran),
                    "end": max(m[1] for m in ran),
                    "ready": wait["end"] if wait else None})
    return out


def module_ms(trace: dict, prefix: str, lo: float, hi: float) -> list[float]:
    """Device milliseconds of each execution inside [lo, hi] of the
    programs whose module name starts with ``prefix``, on the device
    that spent longest in them."""
    per_dev = [[(m[1] - m[0]) / 1e6 for m in dev["modules"]
                if m[2].startswith(prefix) and m[0] >= lo and m[1] <= hi]
               for dev in trace["devices"].values()]
    return max(per_dev, key=sum, default=[])
