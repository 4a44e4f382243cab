"""Device, several chips: ms a statement inside the operations that move
a join's rows between chips (``all-to-all`` and ``collective-permute``;
not the ``all-reduce`` that merges an aggregate's states, which
``collective_ms`` counts with them), on the chip that spent longest there;
median over the statements inside the traced slice.  Above zero wherever
a join exchanged.  An operation is told by its own name, and XLA names
one after the call that made it: ``lax.all_to_all`` gives
``%all_to_all.8``, underscores and all (my chip run, PR 35), where a
``psum`` gives ``%all-reduce.1``; both spellings count."""

import re

from harness.context import median_or_none

EXCHANGE = re.compile(r"all[-_]to[-_]all|collective[-_]permute", re.I)


def read(run, arg=None):
    if run.cell["chips"] == 1:
        return None
    return median_or_none(
        [v for vs in run.device_ms(only=EXCHANGE).values() for v in vs])
