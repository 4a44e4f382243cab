"""Admission scheduler: ``window_hold_ms``, the time a sampled statement
sat in the micro-batch window's hold (ms): the drain had picked a lead
and waited for stragglers that might share its launch (the program's
``sched.hold`` span, a child of ``sched.queue``, which until PR 48 held
this time unnamed).  Over the statements a hold touched: median per
class, geometric mean over the classes, as ``host_merge_ms`` is taken;
0 where the window held no sampled statement at all.  Nothing to read
where no tree was sampled or where the program has no such span (it
then has no ``hold_ns_total`` on ``/sched`` either)."""

from harness.context import geomean_of_medians


def read(run, arg=None):
    if "hold_ns_total" not in run.sched_after or not run.trees:
        return None
    return geomean_of_medians(run.span_ms("sched.hold")) or 0.0
