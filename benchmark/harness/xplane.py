"""From a profiler trace (``.xplane.pb``) to numbers: the one place that
knows which planes are devices and which lines hold operations, so that
every PR computes busy time, idle share and per-class device time the
same way.  Checked on a recorded trace in ``selftest/test_xplane.py``.

What a v5e trace of this program holds (looked at by hand, PR 22; see
PERF.md section 3):

- a plane ``/device:TPU:<n>`` per chip, with the lines ``XLA Modules``
  (one event per executed program, all named ``jit__device_fn(<hash>)``),
  ``XLA Ops`` (one event per executed HLO operation, named by its whole
  HLO text: ``%sort = (s32[67108864]...``), ``Async XLA Ops`` (the
  ``copy-start``/``slice-start`` DMAs, which overlap the operations) and
  two empty ones (``Scalar Unit``, ``TC Overlay``).  Busy time is the union
  of the ``XLA Ops`` intervals: within 0.01 % of the union of the
  modules, and the asynchronous operations add nothing to it.
- planes ``#Chip0 Host Interface``, ``#Chip0 Misc``, ``/host:metadata``,
  ``/device:CUSTOM:Megascale Trace`` and ``Task Environment``: empty.
- a plane ``/host:CPU`` with a line per host thread (``python3`` for the
  interpreter's threads, ``pjrt-tpu-tasks/<n>``, ``tfrt-...``);
  ``TraceAnnotation`` events land on the line of the thread that made
  them.

Times: ``start_ns`` counts from the start of the profiling session.  The
harness writes ``bench_sync`` annotations and notes ``time.monotonic_ns``
beside each, which gives the offset between the trace's clock and the
clock the load generator stamps statements with.
"""

from __future__ import annotations

import bisect
import gzip
import re
from statistics import median

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SYNC_NAME = "bench_sync"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast", re.I)
BETWEEN = "between statements"
OP_NAME_CHARS = 160


def read(path: str) -> dict:
    """``{"devices": {n: {"ops": [(start_ns, end_ns, name)], "modules":
    [...]}}, "sync": [start_ns, ...]}`` from an ``.xplane.pb`` file, or a
    gzip of one."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, sync = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                sync += [e.start_ns for e in line.events
                         if e.name == SYNC_NAME]
    return {"devices": devices, "sync": sorted(sync)}


def own_name(op: tuple) -> str:
    """An operation's own name, ``%all-reduce.1``: the head of its HLO
    text, without the operands, which name other operations."""
    return op[2].split(" = ", 1)[0]


def clock_offset_ns(trace_sync: list, host_sync: list) -> float:
    """Trace clock minus ``time.monotonic_ns``: the median over the sync
    marks, the k-th annotation against the k-th host reading."""
    if not trace_sync or len(trace_sync) != len(host_sync):
        raise ValueError(f"{len(trace_sync)} sync marks in the trace, "
                         f"{len(host_sync)} written")
    return median(t - h for t, h in zip(trace_sync, host_sync))


def union(intervals, lo=None, hi=None) -> list[tuple]:
    """Sorted, disjoint intervals covering the same points, clipped to
    [lo, hi] where given."""
    out = []
    for a, b in sorted((i[0], i[1]) for i in intervals):
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(disjoint: list[tuple], lo, hi) -> float:
    """Length of the part of [lo, hi] that sorted disjoint intervals
    cover."""
    # the first interval that can reach into [lo, hi]: the one before the
    # first that starts after lo
    i = max(bisect.bisect_right(disjoint, (lo, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(disjoint) and disjoint[i][0] < hi:
        a, b = disjoint[i]
        if b > lo:
            total += min(b, hi) - max(a, lo)
        i += 1
    return total


def busy(trace: dict, lo_ns: float, hi_ns: float) -> dict:
    """Busy seconds per device inside the window, with the window's
    length, the mean over devices and the least busy device's idle
    share."""
    window = (hi_ns - lo_ns) / 1e9
    per_dev = {n: covered(union(d["ops"], lo_ns, hi_ns), lo_ns, hi_ns) / 1e9
               for n, d in trace["devices"].items()}
    if not per_dev or window <= 0:
        return {}
    return {"window_s": window, "per_device_s": per_dev,
            "busy_s": sum(per_dev.values()) / len(per_dev),
            "idle_share": 1.0 - min(per_dev.values()) / window}


def per_statement(trace: dict, statements: list[tuple], lo_ns, hi_ns,
                  only=None) -> dict:
    """Device milliseconds per statement, by class: for each statement
    ``(class, start_ns, end_ns)`` wholly inside the window, the union of
    the device's operation intervals inside it, on the device that was
    busy longest.  ``only`` keeps operations whose name matches."""
    unions = []
    for d in trace["devices"].values():
        ops = d["ops"] if only is None else \
            [o for o in d["ops"] if only.search(own_name(o))]
        unions.append(union(ops, lo_ns, hi_ns))
    out: dict = {}
    for cls, a, b in statements:
        if a < lo_ns or b > hi_ns or not unions:
            continue
        out.setdefault(cls, []).append(
            max(covered(u, a, b) for u in unions) / 1e6)
    return out


def exposed_collective_ms(trace: dict, statements, lo_ns, hi_ns) -> dict:
    """As ``per_statement`` for collectives, counting only the part of
    each collective during which no other operation ran on its device."""
    per_dev = []
    for d in trace["devices"].values():
        coll = union([o for o in d["ops"] if COLLECTIVE.search(own_name(o))],
                     lo_ns, hi_ns)
        rest = union([o for o in d["ops"] if not COLLECTIVE.search(own_name(o))],
                     lo_ns, hi_ns)
        per_dev.append((coll, rest))
    out: dict = {}
    for cls, a, b in statements:
        if a < lo_ns or b > hi_ns or not per_dev:
            continue
        worst = 0.0
        for coll, rest in per_dev:
            alone = 0.0
            for ca, cb in coll:
                ca, cb = max(ca, a), min(cb, b)
                if cb > ca:
                    alone += (cb - ca) - covered(rest, ca, cb)
            worst = max(worst, alone)
        out.setdefault(cls, []).append(worst / 1e6)
    return out


def breakdown(trace: dict, statements, lo_ns, hi_ns, top: int = 10) -> dict:
    """``device_ops``: the operations that took most device time, seconds
    summed over the window and averaged over devices, under XLA's names.
    ``idle_gaps``: the idle seconds of the least busy device by which
    class's statement was in flight (``between statements`` for none,
    ``several in flight`` for more than one class)."""
    devs = trace["devices"]
    if not devs:
        return {}
    by_op: dict = {}
    for d in devs.values():
        for a, b, name in d["ops"]:
            a, b = max(a, lo_ns), min(b, hi_ns)
            if b > a:
                by_op[name] = by_op.get(name, 0.0) + (b - a) / 1e9 / len(devs)
    unions = {n: union(d["ops"], lo_ns, hi_ns) for n, d in devs.items()}
    idlest = min(unions, key=lambda n: covered(unions[n], lo_ns, hi_ns))
    gaps, edge = {}, lo_ns
    flights = sorted((a, b, cls) for cls, a, b in statements)
    for a, b in unions[idlest] + [(hi_ns, hi_ns)]:
        if a > edge:
            mid = (edge + a) / 2
            inflight = {c for sa, sb, c in flights if sa <= mid <= sb}
            name = BETWEEN if not inflight else (
                f"{inflight.pop()} in flight" if len(inflight) == 1
                else "several in flight")
            gaps[name] = gaps.get(name, 0.0) + (a - edge) / 1e9
        edge = max(edge, b)

    def ranked(d):          # an operation's name is its whole HLO text
        return [[k[:OP_NAME_CHARS], v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": ranked(by_op), "idle_gaps": ranked(gaps)}
