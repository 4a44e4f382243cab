"""Device programs: of the launches in the window whose program has a
host-merged GROUP BY at its root (``/sched`` ``hndv_agg_launches``), the
share whose groups the device ranked itself, so that the first groups of
the statement's ORDER BY crossed to the host and not the table
(``group_topn_device_launches``: the launch's ``group_topn`` fact read
"device").  Has to read 100 in ``tpch1x1.orderjoin``, whose one such
statement is Q3.  Nothing to read where no such program launched, or
where the program keeps no such counter."""


def read(run, arg=None):
    if "group_topn_device_launches" not in run.sched_after:
        return None
    n = run.sched_delta("hndv_agg_launches")
    return 100.0 * run.sched_delta("group_topn_device_launches") / n \
        if n else None
