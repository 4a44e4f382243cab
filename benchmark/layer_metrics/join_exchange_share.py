"""Device programs: of the launches in the window whose program holds a
lookup join, the share whose join moved rows between chips (``/sched``
``join_exchange_launches`` over ``join_launches``).  Has to read 66.7 in
``tpch10x4.shuffle``: of the three join launches of a ``q3_x4`` and a
``q12_x4`` statement the two that probe ``orders`` exchange (``lineitem``'s
live rows travel to the chip that owns their order), and ``q3_x4``'s
``orders`` launch, which looks its rows up in the replicated customers,
does not.  Nothing to read where no join launched, or where the program
keeps no such counter."""


def read(run, arg=None):
    if "join_exchange_launches" not in run.sched_after:
        return None
    n = run.sched_delta("join_launches")
    return 100.0 * run.sched_delta("join_exchange_launches") / n \
        if n else None
