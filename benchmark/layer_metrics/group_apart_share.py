"""Admission scheduler: of the groups of two or more programs (or
slots) that formed in the window, the share that was served apart
because the group's program was not loaded (``/sched``
``groups_apart_unloaded``), in percent.  A group that forms ends in one
of three ways: it shares a launch (``fused_launches``,
``batched_launches``), it is refused (``fused_refused``,
``batched_refused``), or it is served apart for want of its program;
the three add up to the groups formed.  It is how much of the sharing
the bound on group programs and the literals in a program's digest leave
on the table.  Nothing to read where the program keeps no such counter
(a parent that compiles a group's program where the clients wait), or
where no group formed."""

FORMED = ("fused_launches", "batched_launches", "fused_refused",
          "batched_refused", "groups_apart_unloaded")


def read(run, arg=None):
    if "groups_apart_unloaded" not in run.sched_after:
        return None
    formed = sum(run.sched_delta(k) for k in FORMED)
    return 100.0 * run.sched_delta("groups_apart_unloaded") / formed \
        if formed else None
