"""Session, planner, gates: ``server_unnamed_ms``, the part of a served
statement that no named span covers (ms).  Per sampled statement, the
summed self-time of the containers ``wire.stmt``, ``session.ExecuteStmt``,
``cop.dispatch`` and ``cop.transfer``: spans whose time is meant to be
their children's, so what their children leave uncovered has no name
(``span_self_ms.self_us`` does the arithmetic).  Median per class,
geometric mean over classes.  It is the measure of the span tree itself:
a later span that names a stretch takes it out of here.  Only trees rooted
at ``wire.stmt`` count; a program without that span (any commit before
PR 33) gives nothing to read."""

import importlib.util
import os

from harness.context import geomean_of_medians

CONTAINERS = ("wire.stmt", "session.ExecuteStmt", "cop.dispatch",
              "cop.transfer")


def _self_us():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "span_self_ms.py")
    spec = importlib.util.spec_from_file_location("bench_span_self_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.self_us


def read(run, arg=None):
    self_us = _self_us()
    out: dict = {}
    for tree in run.trees:
        spans = tree["spans"]
        if not any(s["name"] == "wire.stmt" and s["duration_us"] > 0
                   for s in spans):
            continue
        out.setdefault(tree["class"], []).append(
            sum(self_us(s, spans) for s in spans
                if s["name"] in CONTAINERS) / 1e3)
    return geomean_of_medians(out)
