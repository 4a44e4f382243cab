"""Device programs: of the launches in the window whose program has a
host-merged GROUP BY at its root (``/sched`` ``hndv_agg_launches``), the
share whose prefix sums over the sorted slots were made as 8-bit limb
lanes summed inside blocks of 128 slots on the MXU, with one small int64
scan over the block totals (``hndv_limb_scan_launches``), and not as an
int64 scan over every slot.  Has to read 100 in ``tpch1x1.hndv``.
Nothing to read where no such program launched, or where the program
keeps no such counter."""


def read(run, arg=None):
    if "hndv_limb_scan_launches" not in run.sched_after:
        return None
    n = run.sched_delta("hndv_agg_launches")
    return 100.0 * run.sched_delta("hndv_limb_scan_launches") / n \
        if n else None
