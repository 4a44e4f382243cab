"""Device programs: ``program_ms.<program>_<root>``, the device time of
one execution of the programs named ``cop_<program>_<root>_<digest>``
(``solo_agg_scalar``, ``solo_agg_dense``, ``solo_topn``, ...), from their
``XLA Modules`` events; median over the executions inside the traced slice
on the device that spent longest in them, ms.  A program is found by its
name, not by which statement was in flight, so the reading holds with any
number of clients."""

from harness import hostspans
from harness.context import median_or_none


def read(run, arg=None):
    if run.trace is None:
        return None
    return median_or_none(hostspans.module_ms(
        run.trace, f"jit_cop_{arg}_", run.trace_lo_ns, run.trace_hi_ns))
