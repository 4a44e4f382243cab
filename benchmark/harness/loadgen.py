"""The load generator: a process of its own that never imports JAX.

``python loadgen.py <plan.json>``.  The plan holds the server's address,
the statements with the rows the oracle expects, one sequence of
statement numbers per client, and the loop's parameters.  It connects
every client, runs the clients' sequences closed-loop for ``ramp_s``
seconds and throws those readings away (the ramp: the window then starts
on cores, caches and a server that are already at speed, where the first
seconds after an idle wait read 2-4 % slow), prints ``READY``, waits for
``GO`` on standard input, offers the load for ``seconds`` seconds, and
writes one record per statement to the plan's ``out`` file: which statement, when it was due, sent and fully
decoded (this process's ``time.monotonic``, which on Linux is the same
clock in every process), and whether the answer was the expected one.

- ``closed``: each client sends its next statement when the last one's
  rows are decoded; a statement is due when it is sent.
- ``open``: statements are due on a schedule drawn from the plan's seed
  (``poisson`` or ``uniform`` gaps at ``rate_per_s``), whatever the server
  does; a free client takes the next one, and a statement's time runs from
  when it was due, so a stall is charged to everything it delays.

Real clients are another process: client threads inside the server's
process would share its interpreter lock and be read as a slow server.
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.wire import Connection, WireError  # noqa: E402


def _schedule(plan: dict) -> list[float]:
    """Offsets from the window's start at which open-loop statements are
    due: a fixed amount of work drawn from the seed."""
    rng = random.Random(plan["seed"])
    rate, t, out = float(plan["rate_per_s"]), 0.0, []
    while True:
        t += rng.expovariate(rate) if plan["arrivals"] == "poisson" \
            else 1.0 / rate
        if t >= plan["seconds"]:
            return out
        out.append(t)


class _Run:
    def __init__(self, plan: dict):
        self.plan = plan
        self.statements = [
            (s["sql"], [tuple(r) for r in s["rows"]], s["ordered"])
            for s in plan["statements"]]
        self.records: list[list] = []
        self.mu = threading.Lock()
        self.next = 0
        self.t0 = self.t_end = 0.0
        self.due: list[float] = []

    def one(self, conn: Connection, idx: int, due: float | None) -> bool:
        """Send statement ``idx``; False when the connection is gone."""
        sql, want, ordered = self.statements[idx]
        if due is not None:
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
        alive, err = True, None
        sent = time.monotonic()
        try:
            rows = conn.query(sql)
            done = time.monotonic()
            if not ordered:
                rows, want = sorted(rows), sorted(want)
            ok = rows == want
            if not ok:
                err = f"wrong answer: got {rows[:3]} expected {want[:3]}"
        except WireError as e:          # refused or failed: no retry
            done, ok, err = time.monotonic(), None, str(e)
        except OSError as e:
            done, ok, err, alive = time.monotonic(), None, repr(e), False
        with self.mu:
            self.records.append(
                [idx, sent if due is None else due, sent, done, ok, err])
        return alive

    def closed(self, conn: Connection, stream: list[int]) -> None:
        i = 0
        while time.monotonic() < self.t_end:
            if not self.one(conn, stream[i % len(stream)], None):
                return
            i += 1

    def open(self, conn: Connection, stream: list[int]) -> None:
        while True:
            with self.mu:
                k, self.next = self.next, self.next + 1
            if k >= len(self.due):
                return
            if not self.one(conn, stream[k % len(stream)],
                            self.t0 + self.due[k]):
                return


def _together(work: list[tuple]) -> None:
    threads = [threading.Thread(target=fn, args=(c, s)) for fn, c, s in work]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        plan = json.load(f)
    run = _Run(plan)
    streams = plan["streams"]
    conns = [Connection(plan["host"], plan["port"], db=plan["db"])
             for _ in range(plan["clients"])]
    try:
        if plan["loop"] == "open":
            run.due = _schedule(plan)
            # one sequence for the whole schedule, whichever client sends
            work = [(run.open, c, streams[0]) for c in conns]
        elif plan["loop"] == "closed":
            work = [(run.closed, c, streams[i % len(streams)])
                    for i, c in enumerate(conns)]
        else:
            raise ValueError(f"loop {plan['loop']!r} is not closed or open")
        if plan["ramp_s"] > 0:
            run.t_end = time.monotonic() + plan["ramp_s"]
            _together([(run.closed, c, streams[i % len(streams)])
                       for i, c in enumerate(conns)])
            run.records.clear()
        print("READY", flush=True)
        if sys.stdin.readline().strip() != "GO":
            return 3
        run.t0 = time.monotonic()
        run.t_end = run.t0 + plan["seconds"]
        _together(work)
    finally:
        for c in conns:
            c.close()
    with open(plan["out"], "w") as f:
        json.dump({"t0": run.t0, "t_end": run.t_end, "records": run.records,
                   "scheduled": len(run.due)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
