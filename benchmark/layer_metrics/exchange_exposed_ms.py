"""Device, several chips: the part of ``exchange_ms`` during which no
other operation ran on that chip: what the exchange adds to a statement,
the rest being hidden behind other work.  The reduction is
``xplane.exposed_collective_ms``'s, over the operations ``exchange_ms``
counts."""

import re

from harness import xplane
from harness.context import median_or_none

# exchange_ms's pattern
EXCHANGE = re.compile(r"all[-_]to[-_]all|collective[-_]permute", re.I)


def exposed_ms(trace: dict, statements, lo_ns, hi_ns) -> dict:
    """Per class, for each statement wholly inside the slice: the time
    inside exchange operations with nothing else running, on the chip
    where that was longest."""
    per_dev = []
    for d in trace["devices"].values():
        mine = [o for o in d["ops"] if EXCHANGE.search(xplane.own_name(o))]
        rest = [o for o in d["ops"]
                if not EXCHANGE.search(xplane.own_name(o))]
        per_dev.append((xplane.union(mine, lo_ns, hi_ns),
                        xplane.union(rest, lo_ns, hi_ns)))
    out: dict = {}
    for cls, a, b in statements:
        if a < lo_ns or b > hi_ns or not per_dev:
            continue
        worst = 0.0
        for mine, rest in per_dev:
            alone = 0.0
            for ca, cb in mine:
                ca, cb = max(ca, a), min(cb, b)
                if cb > ca:
                    alone += (cb - ca) - xplane.covered(rest, ca, cb)
            worst = max(worst, alone)
        out.setdefault(cls, []).append(worst / 1e6)
    return out


def read(run, arg=None):
    if run.cell["chips"] == 1 or run.trace is None:
        return None
    per_class = exposed_ms(run.trace, run.traced_statements(),
                           run.trace_lo_ns, run.trace_hi_ns)
    return median_or_none([v for vs in per_class.values() for v in vs])
