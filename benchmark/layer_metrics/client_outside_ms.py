"""Wire: ``client_outside_ms``, what a statement costs its client outside
the server's process (ms).  Per class, the client's median statement time
(send -> last row decoded) less the median duration of that class's
``wire.stmt`` spans (the command's payload read -> the last ``sendall`` of
its result returned); the mean over classes: the kernel's TCP path both
ways and the load generator's own encode and decode, nothing a change to
the server can take.  The arithmetic mean and not the geometric one the
other per-class readings take, and never under 0: on one host the
difference is 0.00-0.2 ms (PR 33), which for a short statement is inside
what a sample of one tree in sixteen resolves, so a class can read a few
microseconds under nothing.  ``wire_ms`` times the same layer from outside,
as a difference of two means, and includes ``session.parse``,
``session.begin``, ``session.finish`` and ``wire.write``, which are the
server's.  A program without ``wire.stmt`` (any commit before PR 33) gives
nothing to read."""

from statistics import median


def read(run, arg=None):
    served: dict = {}
    for tree in run.trees:
        for s in tree["spans"]:
            if s["name"] == "wire.stmt" and s["duration_us"] > 0:
                served.setdefault(tree["class"], []).append(
                    s["duration_us"] / 1e3)
    ms = run.ms_by_class()
    per_class = [median(ms[cls]) - median(inside)
                 for cls, inside in served.items() if ms.get(cls)]
    if not per_class:
        return None
    return max(sum(per_class) / len(per_class), 0.0)
