"""Device programs: of the statements answered in the window, the share
answered with exactly the join launches their plan has (each class file
says how many: ``JOIN_LAUNCHES``; ``/sched`` ``join_launches``).  0 where
a lookup join fell back to the host (``join_host_fallbacks``), an
expanding join or a group table was regrown (``join_regrows``,
``hndv_agg_regrows``), a bucket of the exchange, a probe compaction, a
window or a rows-returning program overflowed and the statement was rerun
(``exchange_overflows``, ``join_compact_overflows``,
``join_window_overflows``, ``rows_regrows``), or the host engine answered
(``client.degraded``, ``client.oom_recovered``).  Has to read 100 in
``tpch10x4.shuffle``.  Nothing to read on a program that keeps no such
counters."""

RERUNS = ("join_host_fallbacks", "join_regrows", "exchange_overflows",
          "join_compact_overflows", "join_window_overflows",
          "hndv_agg_regrows", "rows_regrows")


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
