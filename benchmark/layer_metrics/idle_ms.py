"""``idle_ms.<phase>``: the idle time of the least busy device inside the
traced slice, split by what the host was doing: each idle gap goes to the
host phase of the innermost program span in flight (``harness/hostspans``:
``session.*``/``plan.*`` -> ``session``; ``cop.dispatch``/``sched.*`` ->
``sched``; ``cop.transfer`` and its children -> ``transfer``;
``cop.host_merge``/``session.resultset`` -> ``merge``; ``wire.write`` or no
span at all -> ``wire``), over the statements answered inside the slice,
in ms.  The five parts sum to ``device_idle_share`` x slice / statements."""

from harness import hostspans


def read(run, arg=None):
    if run.trace is None:
        return None
    lo, hi = run.trace_lo_ns, run.trace_hi_ns
    cached = run.__dict__.get("_idle_by_phase")
    if cached is None:
        cached = run.__dict__["_idle_by_phase"] = hostspans.idle_by_phase(
            run.trace, hostspans.of(run), lo, hi)
    n = sum(1 for _c, _a, b in run.traced_statements() if lo <= b <= hi)
    if arg not in cached or not n:
        return None
    return cached[arg] / n / 1e6
