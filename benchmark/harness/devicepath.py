"""Did the device, and nothing in its place, answer?  A copy of the counter
check of ``chip_smoke.check_device_path`` (PR 21), held here so that no
later PR can loosen it.  Reads two ``/sched`` documents, one from the
start of the window and one from its end."""

from __future__ import annotations

# each one is a way for a statement to be answered without the device
# doing the work, or for the scheduler to have refused or repeated it
ZERO_COUNTERS = ("quarantined", "bisected_launches", "retried_launches",
                 "warm_failures", "budget_rejects", "fused_refused",
                 "batched_refused", "oom_faults")
ZERO_CLIENT_COUNTERS = ("degraded", "oom_recovered")


def faults(platform: str, before: dict, after: dict, answered: int) -> list:
    """Reasons the device path is not proven; empty when it is."""
    bad = []
    if platform != "tpu":
        bad.append(f"platform is {platform!r}, not 'tpu'")
    if not after.get("started"):
        return bad + ["the admission scheduler never started"]
    for k in ZERO_CLIENT_COUNTERS:
        if after["client"][k]:
            bad.append(f"client.{k} = {after['client'][k]}")
    for k in ZERO_COUNTERS:
        if after[k]:
            bad.append(f"{k} = {after[k]}")
    if after["breaker"]:
        bad.append(f"breaker not empty: {after['breaker']}")
    if after["compile_cache"]["uncacheable"]:
        bad.append("compile_cache.uncacheable = "
                   f"{after['compile_cache']['uncacheable']}")
    done = after["tasks_done"] - before.get("tasks_done", 0)
    if done < answered:
        bad.append(f"{done} scheduler tasks completed in the window for "
                   f"{answered} statements answered")
    return bad
