"""Session, planner and gates: per sampled statement, the root span
``session.ExecuteStmt`` minus its direct ``cop.*`` children (dispatch with
the scheduler's queue and launch inside it, transfer, host merge), from
the flight recorder's span trees.  What is left is parse, plan, the plan
gates and building the result set.  Median per class, geometric mean over
classes.  Only statements with a ``cop.transfer`` span count: the rows
path (``topn``) has none, so its wait for the device would be read as
planning."""

from harness.context import geomean_of_medians


def read(run, arg=None):
    out: dict = {}
    for tree in run.trees:
        spans = tree["spans"]
        root = next((s for s in spans if s["name"] == "session.ExecuteStmt"),
                    None)
        if root is None or not any(s["name"] == "cop.transfer" for s in spans):
            continue
        below = sum(s["duration_us"] for s in spans
                    if s["parent"] == root["id"]
                    and s["name"].startswith("cop."))
        out.setdefault(tree["class"], []).append(
            (root["duration_us"] - below) / 1e3)
    return geomean_of_medians(out)
