"""Device programs: of the launches in the window whose program holds a
lookup join, the share whose program read a direct-addressed table by
windows, one a block of 128 probe rows, because the probe key is stored
in key order (``/sched`` ``join_window_launches`` over
``join_launches``): such a lookup costs a slice fetch a block and a
select-reduce, not an index a probe row.  Has to read 33.3 in
``tpch1x1.orderjoin``: of the three join launches of a ``q3`` and a
``q12`` statement only ``q3``'s ``lineitem`` launch probes in key order
with rows nothing has compacted (its ``orders`` launch probes with
``o_custkey``; ``q12`` compacts its probe rows first).  Nothing to read
where no such program launched, or where the program keeps no such
counter."""


def read(run, arg=None):
    if "join_window_launches" not in run.sched_after:
        return None
    n = run.sched_delta("join_launches")
    return 100.0 * run.sched_delta("join_window_launches") / n \
        if n else None
