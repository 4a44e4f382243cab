"""The one general traffic generator.  A mix is a data file,
``traffic/<mix>.json``; this turns it, with the seed and the classes it
names, into the load generator's plan: statements, one sequence per
client, loop parameters.  A new mix is a new file and no code.

A mix's keys:

- ``loop``: ``closed`` (each client sends when its last answer is in) or
  ``open`` (due on a schedule, whatever the server does).
- ``clients``: connections, each on a thread of the load generator.
- ``mix``: ``{class: n}``, how often each class comes in one cycle.
- ``order``: ``shuffled`` (each cycle in an order drawn from the seed,
  each client its own) or ``fixed`` (as listed, class by class).
- ``cycles``: how many cycles are drawn before the sequence repeats.
- ``rate_per_s`` and ``arrivals`` (``poisson`` or ``uniform``): open loop
  only, all clients together.
- ``pool_seed``: what the classes' parameter sets are drawn from.  It is
  the mix's and not the run's ``--seed``: a statement's literals are part
  of its program (Q6's device time is 4.6 or 6.3 ms by its discount
  literal, and a new literal is a new compile), so literals drawn from the
  run's seed made the work, and the set-up, differ from run to run.  The
  run's seed makes the data, each client's order and an open loop's
  arrivals.
- ``pool`` (optional): parameter sets drawn per class, in place of the
  class's own ``POOL``.

Each class draws its pool from ``[pool_seed, crc32(class)]``, and the
members of a pool come round in turn.
"""

from __future__ import annotations

import zlib

import numpy as np

LOOPS = ("closed", "open")


def pools(classes: dict, mix: dict) -> dict[str, list[dict]]:
    """``{class: [parameter set, ...]}``: distinct sets, as many as the
    pool asks for where the class's domain holds that many."""
    out = {}
    for name in mix["mix"]:
        cls = classes[name]
        rng = np.random.default_rng(
            [int(mix["pool_seed"]), zlib.crc32(name.encode())])
        want = int(mix.get("pool", cls.POOL))
        pool: list[dict] = []
        for _ in range(64 * want):
            p = cls.draw(rng)
            if p not in pool:
                pool.append(p)
            if len(pool) == want:
                break
        out[name] = pool
    return out


def streams(mix: dict, pool_sizes: dict[str, int], seed: int) -> list[list]:
    """One sequence of ``(class, pool member)`` per client."""
    if mix["loop"] not in LOOPS:
        raise ValueError(f"loop {mix['loop']!r} is not one of {LOOPS}")
    if mix["order"] not in ("shuffled", "fixed"):
        raise ValueError(f"order {mix['order']!r} is not shuffled or fixed")
    cycle = [c for c, n in mix["mix"].items() for _ in range(int(n))]
    out = []
    # an open loop has one schedule, so one sequence, whoever sends it
    for client in range(1 if mix["loop"] == "open" else int(mix["clients"])):
        rng = np.random.default_rng([seed, 1000 + client])
        turn = {c: client for c in mix["mix"]}     # clients start apart
        seq = []
        for _ in range(int(mix["cycles"])):
            order = rng.permutation(len(cycle)) if mix["order"] == "shuffled" \
                else range(len(cycle))
            for i in order:
                c = cycle[i]
                seq.append((c, turn[c] % pool_sizes[c]))
                turn[c] += 1
        out.append(seq)
    return out
