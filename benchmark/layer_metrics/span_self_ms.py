"""``span_self_ms.<span>``: per sampled statement, the time inside spans of
that name that no child span covers (ms), from the flight recorder's span
trees: ``session.parse``, ``session.plan``, ``plan.gates``,
``sched.admit``, ``cop.device_wait``, ``cop.d2h``, ``session.resultset``,
``wire.write``.  Statements without such a span are left out (a plan-cache
hit passes no gate).  Median per class, geometric mean over classes, as
``host_merge_ms`` is taken."""

from harness import xplane
from harness.context import geomean_of_medians


def self_us(span: dict, spans: list) -> float:
    lo, hi = span["start_us"], span["start_us"] + span["duration_us"]
    below = xplane.union(
        [(s["start_us"], s["start_us"] + s["duration_us"]) for s in spans
         if s["parent"] == span["id"]], lo, hi)
    return span["duration_us"] - xplane.covered(below, lo, hi)


def read(run, arg=None):
    out: dict = {}
    for tree in run.trees:
        mine = [s for s in tree["spans"] if s["name"] == arg]
        if mine:
            out.setdefault(tree["class"], []).append(
                sum(self_us(s, tree["spans"]) for s in mine) / 1e3)
    return geomean_of_medians(out)
