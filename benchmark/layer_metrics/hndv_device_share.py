"""Device programs: of the statements answered in the window, the share
that ONE launch of a device program whose root is a host-merged GROUP BY
answered (``/sched`` ``hndv_agg_launches``).  0 where a statement was
rerun with a larger table or a wider record (``hndv_agg_regrows``), where
the host engine answered an aggregation (``client.degraded``,
``client.oom_recovered``), or where statements and launches are not one
to one.  Has to read 100 in ``tpch1x1.hndv``.  Whether the groups were
ranked by the device or by the host is not folded in: the line says it
beside (``hndv_host_topn_launches``), and ``host_plan_ms`` and
``host_merge_ms`` carry what a host-side rank costs.  Nothing to read on
a program that keeps no such counters."""


def read(run, arg=None):
    if "hndv_agg_launches" not in run.sched_after:
        return None
    answered = len(run.answered())
    if not answered:
        return None
    print("[bench] hndv_host_topn_launches in the window: "
          f"{run.sched_delta('hndv_host_topn_launches')}", flush=True)
    if run.sched_delta("hndv_agg_regrows") \
            or run.sched_delta("launches") != answered \
            or any(run.sched_delta("client", k)
                   for k in ("degraded", "oom_recovered")):
        return 0.0
    return 100.0 * run.sched_delta("hndv_agg_launches") / answered
