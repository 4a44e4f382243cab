"""Device programs: of the statements answered in the window, the share
answered with exactly the join launches their plan has (each class file
says how many: ``JOIN_LAUNCHES``; ``/sched`` ``join_launches``).  0 where
a repartition join ran (``join_shuffle_launches``), a lookup join fell
back to the host (``join_host_fallbacks``), an expanding join or a group
table was regrown (``join_regrows``, ``hndv_agg_regrows``), a probe
compaction or a rows-returning program overflowed its capacity and was
rerun (``join_compact_overflows``, ``rows_regrows``), or the host engine
answered (``client.degraded``, ``client.oom_recovered``).  Has to read 100
in ``tpch1x1.orderjoin``.  Nothing to read on a program that keeps no
such counters."""

RERUNS = ("join_shuffle_launches", "join_host_fallbacks", "join_regrows",
          "hndv_agg_regrows", "join_compact_overflows", "rows_regrows")


def read(run, arg=None):
    if any(k not in run.sched_after for k in RERUNS + ("join_launches",)):
        return None
    by_class: dict = {}
    for r in run.answered():
        by_class[r["class"]] = by_class.get(r["class"], 0) + 1
    want = sum(n * int(getattr(run.classes[c], "JOIN_LAUNCHES", 0))
               for c, n in by_class.items())
    if not want:
        return None
    if any(run.sched_delta(k) for k in RERUNS) \
            or any(run.sched_delta("client", k)
                   for k in ("degraded", "oom_recovered")):
        return 0.0
    got = run.sched_delta("join_launches")
    return 100.0 * min(got, want) / max(got, want)
