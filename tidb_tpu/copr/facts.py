"""What a device program says of itself, and what that means outside
`copr/`.

A lowering writes what it decided, as a static value under a name, into
the `facts` of the batch it works on (`exec.DeviceBatch.facts`, one dict
a trace); a program adds what its DAG and a launch's inputs say without
a trace (`parallel/spmd.ShardedCopProgram.facts`).  The table below is
all the rest of the system knows of a fact: the scheduler bumps the
`/sched` counters a row names and puts on the `sched.launch` span what
the row lets through, and knows no fact by name.  A kernel that wants a
new counter or span attribute writes its fact and adds a row here.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from . import dag as D


def _present(_value) -> bool:
    return True


def _first(values):
    return values[0]


def _rows_root(root) -> bool:
    """A root whose live rows `exec.compact_root` hands to the host."""
    return not isinstance(root, (D.Aggregation, D.TopN, D.Limit,
                                 D.FusedDag))


def _exchanges(root) -> bool:
    return D.exchanging_join(root) is not None


def _host_merged(root) -> bool:
    return isinstance(root, D.Aggregation) \
        and root.host_merged


@dataclass(frozen=True)
class Fact:
    """counters: (`/sched` counter, test of the value) pairs: a launch of
        a program that has the fact bumps those whose test it passes.
    merge: the values of the members of a fused program that have the
        fact -> the fused launch's.
    on_span: does this value go on the `sched.launch` span?
    root: of a fact a lowering writes in the trace (None: the program
        adds it itself): test of a DAG's root, the programs it counts
        for.  A TopN under an aggregation was never counted; and only
        such a program is traced abstractly for its facts where copforge
        serves its executable untraced."""
    counters: tuple = ()
    merge: Callable = max
    on_span: Callable = bool
    root: Optional[Callable] = None


FACTS = {
    # `exec._exec_topn`: the blocks it viewed its input as (1: it sorted
    # every row)
    "topn_blocks": Fact(
        counters=(("topn_launches", _present),
                  ("topn_pruned_launches", lambda n: n > 1)),
        root=lambda r: isinstance(r, D.TopN)),
    # the DENSE branch of `exec._agg_partial_states`: the int32 lanes a
    # row its SUM and COUNT states were reduced as (0: not the limb form)
    "agg_limbs": Fact(
        counters=(("dense_agg_launches", _present),
                  ("dense_agg_limb_launches", lambda n: n > 0)),
        merge=lambda ns: 0 if 0 in ns else sum(ns),
        root=lambda r: isinstance(r, D.Aggregation)
        and r.strategy == D.GroupStrategy.DENSE),
    # a program with lookup joins (it takes aux inputs, so it is launched
    # alone): "unique" | "multimatch", the slots probed with, the build
    # sides' rows (slots, where direct-addressed)
    "join": Fact(counters=(("join_launches", _present),), on_span=_present),
    "probe_rows": Fact(on_span=_present),
    "build_rows": Fact(on_span=_present),
    # the form each lookup of the program took, in the order of its
    # joins, joined by commas: "direct" | "sorted" | "expanding"
    # (copr/joinbuild.py)
    "join_form": Fact(
        counters=(("join_direct_launches",
                   lambda f: set(f.split(",")) == {"direct"}),),
        merge=",".join, on_span=_present),
    # of a program whose lookup joins are all unique: the slots a device
    # compacts its live probe rows to before the lookup (0: it looks up
    # every slot)
    "probe_capacity": Fact(
        counters=(("join_compact_launches", lambda c: c > 0),)),
    # of the same: the slots a device compacts its matched rows to after
    # the lookup (0: what is above the join runs on every slot)
    "match_capacity": Fact(
        counters=(("join_match_compact_launches", lambda c: c > 0),)),
    # of the same: the widest window a lookup of it reads its table by
    # (`dag.LookupJoin.probe_window`; 0: every lookup is a gather)
    "probe_window": Fact(
        counters=(("join_window_launches", lambda w: w > 0),)),
    # of a program with a lookup whose build side stays sharded where it
    # lives (`dag.LookupJoin.sharded`): how many of its lookups
    "build_sharded": Fact(
        counters=(("join_sharded_build_launches", _present),),
        on_span=_present),
    # of a program whose join moved rows between devices: which side
    # travelled ("probe_to_build": the live probe rows, to the device
    # that owns their key; "both": parallel/shuffle.py re-buckets both
    # sides) and the slots of one bucket a destination.  What a device
    # sent is known once the outputs are fetched: `cop.transfer`
    # {`exchange_rows_sent`}
    "exchange": Fact(counters=(("join_exchange_launches", _present),),
                     merge=_first, on_span=_present),
    "exchange_capacity": Fact(on_span=_present),
    # `exec._sharded_lookup`, written where the exchange is traced: the
    # column sorts over all of a device's slots its buckets are made
    # with (`parallel/exchange.exchange_passes`: 1, or one a device)
    "exchange_passes": Fact(
        counters=(("join_exchange_onepass_launches", lambda p: p == 1),),
        on_span=_present, root=_exchanges),
    # `exec.compact_root`, the root of a rows-returning program: the
    # slots a device hands its live rows to the host in, and whether
    # they got there by the column sort (1) or by the scatter (0)
    "rows_capacity": Fact(on_span=_present, root=_rows_root),
    "rows_compact": Fact(
        counters=(("rows_launches", _present),
                  ("rows_compact_launches", lambda c: c > 0)),
        merge=min, root=_rows_root),
    # copr/runagg, a TPU's lowering of a SORT aggregation root: the
    # strategy's name, the table's slots a device, and where the groups
    # a TopN above it keeps are ranked, "device" | "host" (absent: none
    # is above it).  "host": the table came back whole for its sake;
    # "device": the first groups alone crossed
    "agg_strategy": Fact(counters=(("hndv_agg_launches", _present),),
                         merge=_first, on_span=_present, root=_host_merged),
    "group_capacity": Fact(on_span=_present, root=_host_merged),
    "group_topn": Fact(
        counters=(("hndv_host_topn_launches", lambda w: w == "host"),
                  ("group_topn_device_launches", lambda w: w == "device")),
        merge=_first, on_span=_present, root=_host_merged),
    # `runagg.agg_run_states`: the 8-bit limb lanes its prefix sums were
    # made of, in blocks on the MXU (0: an int64 scan over every slot)
    "scan_limbs": Fact(
        counters=(("hndv_limb_scan_launches", lambda n: n > 0),),
        root=_host_merged),
    # the same: the group keys that rode its sort as payload because
    # the other keys determine them (`dag.Aggregation.dependent`)
    "dependent_keys": Fact(
        counters=(("agg_dependent_key_launches", _present),),
        root=_host_merged),
}

# counted by name (`DeviceScheduler.count`) by whoever sees it happen:
# no launch carries these
# (`join_compact_overflows`: a compacting join found more live rows than
# its capacity and the statement was rerun uncompacted;
# `join_window_overflows`: a lookup read by windows found a live row
# outside its window and the statement was rerun with the gather;
# `hndv_agg_regrows`: a host-merged aggregation was rerun with a larger
# table or a wider record; `rows_regrows`: a rows-returning program's
# live rows did not fit its capacity and it was rerun with more;
# `exchange_overflows`: a bucket of a join's exchange did not hold the
# rows a device had for one destination and the statement was rerun with
# the capacity the devices found)
EVENTS = ("join_shuffle_launches", "join_host_fallbacks", "join_regrows",
          "join_compact_overflows", "join_window_overflows",
          "hndv_agg_regrows", "rows_regrows", "exchange_overflows")


def counter_names() -> tuple:
    """Every counter above: `/sched` shows them from the start, at zero."""
    return tuple(name for fact in FACTS.values()
                 for name, _test in fact.counters) + EVENTS


def says_in_trace(root) -> bool:
    """Can a trace of a program with this root hold a fact of its?"""
    return any(f.root is not None and f.root(root) for f in FACTS.values())


def of_program(traced: dict, root) -> dict:
    """Those of a trace's facts that count as the program's."""
    return {k: v for k, v in traced.items() if FACTS[k].root(root)}


def merged(members: list) -> dict:
    """The facts of a fused launch from its member programs'."""
    return {k: FACTS[k].merge([m[k] for m in members if k in m])
            for k in dict.fromkeys(k for m in members for k in m)}


def counters(facts: dict) -> list:
    """The `/sched` counters one launch with these facts bumps."""
    return [name for k, v in facts.items()
            for name, test in FACTS[k].counters if test(v)]


def span_attrs(facts: dict) -> dict:
    """What the `sched.launch` span says of these facts."""
    return {k: v for k, v in facts.items() if FACTS[k].on_span(v)}
