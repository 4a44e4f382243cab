"""The TPU's lowering of a SORT aggregation (copr/runagg), which no
statement reaches on the CPU mesh (there the host engine answers): traced
for `Evaluator(jnp, platform="tpu")`, its group tables have to hold what a
plain reference (tidb_tpu/testing/groupref: sort, `np.add.reduceat`, Python
ints) finds, in every record form, and keep the contract the host merge
and the regrow loop rely on."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from tidb_tpu.copr import dag as D
from tidb_tpu.copr import exec as X
from tidb_tpu.copr import runagg
from tidb_tpu.copr.aggregate import merge_sorted_states, sum_out_dtype
from tidb_tpu.expr import ColumnRef
from tidb_tpu.expr.compile import Evaluator
from tidb_tpu.parallel import spmd
from tidb_tpu.parallel.mesh import SHARD_AXIS, sharded
from tidb_tpu.testing.groupref import group_by
from tidb_tpu.types import dtypes as dt

I64, I64N = dt.bigint(False), dt.bigint(True)
SUM, COUNT = D.AggFunc.SUM, D.AggFunc.COUNT
FORMS = (1, 2, 0)       # `pack_words`: the exact forms and the wide one


def _agg(n_keys=1, nullable=True, words=2, cap=1024, topn=None, aggs=None):
    t = I64N if nullable else I64
    scan = D.TableScan(tuple(range(n_keys + 1)), (t,) * (n_keys + 1))
    arg = ColumnRef(t, n_keys)
    if aggs is None:
        aggs = (D.AggDesc(SUM, arg, sum_out_dtype(t)),
                D.AggDesc(COUNT, None, I64), D.AggDesc(COUNT, arg, I64))
    return D.Aggregation(
        scan, tuple(ColumnRef(t, j) for j in range(n_keys)), aggs,
        D.GroupStrategy.SORT, group_capacity=cap, pack_words=words,
        topn=topn)


def _states(agg, cols, sel, stacked=1):
    """`_agg_partial_states` traced for a TPU -> (states, facts)."""
    facts = {}

    def fn(cols, sel):
        batch = X.DeviceBatch(
            [(v, True if m is None else m) for v, m in cols], sel,
            stacked=stacked)
        out = X._agg_partial_states(
            agg, batch, Evaluator(jnp, platform="tpu"), {})
        facts.update(batch.facts)
        return out
    out = jax.jit(fn)(cols, sel)
    return jax.tree_util.tree_map(np.asarray, out), facts


def _regrown(agg, cols, sel, stacked=1):
    """As the dispatcher: rerun wider, then larger, until it fits.
    -> (states, the aggregation that fit, reruns)."""
    for reruns in range(8):
        st, _facts = _states(agg, cols, sel, stacked)
        if "__bits__" in st and st["__bits__"] > 32 * agg.pack_words:
            agg = dataclasses.replace(
                agg, pack_words=2 if st["__bits__"] <= 64 else 0)
        elif st["__ngroups__"] > agg.group_capacity:
            agg = dataclasses.replace(agg, group_capacity=1 << int(
                st["__ngroups__"] - 1).bit_length())
        else:
            return st, agg, reruns
    raise AssertionError("did not converge")


def _groups(agg, st, duplicates=False) -> dict:
    """{key tuple: [value an aggregate]} of one table (as the reference's).
    A key in two slots is an error unless `duplicates`, which adds them
    up as the host merge does."""
    out: dict = {}
    for g in np.nonzero(st["__rows__"] > 0)[0]:
        key = tuple(st[f"k{j}"]["val"][g].item()
                    if st[f"k{j}"]["valid"][g] else None
                    for j in range(len(agg.group_by)))
        vals = []
        for i, a in enumerate(agg.aggs):
            s = st[f"a{i}"]
            if a.func == COUNT:
                vals.append(int(s["count"][g]))
            else:
                vals.append((int(s["hi"][g]) << 32) + int(s["lo"][g])
                            if s["cnt"][g] else None)
        if key in out:
            assert duplicates, f"group {key} is in two slots"
            vals = [b if a is None else a if b is None else a + b
                    for a, b in zip(out[key], vals)]
        out[key] = vals
    return out


def _want(agg, cols, sel) -> dict:
    k = len(agg.group_by)
    return group_by(
        cols[:k], sel,
        [(("count", None) if a.arg is None else
          ("count" if a.func == COUNT else "sum", cols[a.arg.index]))
         for a in agg.aggs])


def _table(n, ndv, seed, n_keys=1, nulls=True, live=0.7, spread=10 ** 5):
    rng = np.random.default_rng(seed)
    cols = []
    for j in range(n_keys):
        k = rng.integers(-(ndv // 3), ndv - ndv // 3, n) if j == 0 \
            else rng.integers(0, 3, n)
        cols.append((k, rng.random(n) > 0.05 if nulls else None))
    cols.append((rng.integers(-spread // 100, spread, n),
                 rng.random(n) > 0.2 if nulls else None))
    return cols, rng.random(n) < live


@pytest.mark.parametrize("words", FORMS)
@pytest.mark.parametrize("n,ndv", [(1000, 1), (1000, 7), (5000, 700),
                                   (1 << 17, 40000)])
def test_tables_equal_the_reference(n, ndv, words):
    """NDV from one group to above 32,768, NULL keys (one group) and
    NULL arguments, three in ten rows dead."""
    cols, sel = _table(n, ndv, seed=ndv)
    st, agg, _ = _regrown(_agg(words=words, cap=1 << 16), cols, sel)
    want = _want(agg, cols, sel)
    assert _groups(agg, st) == want
    assert int(st["__ngroups__"]) == len(want)
    assert ndv < 40000 or len(want) > 1 << 15


@pytest.mark.parametrize("words", FORMS)
@pytest.mark.parametrize("live", [0.0, 0.001, 0.5, 1.0])
def test_every_live_dead_mix(live, words):
    cols, sel = _table(3000, 50, seed=3, live=live)
    st, agg, _ = _regrown(_agg(words=words), cols, sel)
    assert _groups(agg, st) == _want(agg, cols, sel)
    if not live:
        assert int(st["__ngroups__"]) == 0 and not st["__rows__"].any()


@pytest.mark.parametrize("words", FORMS)
def test_two_column_keys_one_of_them_null(words):
    cols, sel = _table(4000, 300, seed=5, n_keys=2)
    st, agg, _ = _regrown(_agg(n_keys=2, words=words), cols, sel)
    want = _want(agg, cols, sel)
    assert _groups(agg, st) == want
    assert any(k[0] is None for k in want) and any(k[1] is None for k in want)


@pytest.mark.parametrize("stacked", [1, 8])
def test_stacked_shards_and_a_batch_that_is_no_whole_column(stacked):
    """Eight stacked shards (the sort is fed in the order they lie in a
    TPU's memory) and a row count no multiple of 128 (padded dead)."""
    n = 8 * 1024 if stacked == 8 else 1001
    cols, sel = _table(n, 90, seed=9)
    st, agg, _ = _regrown(_agg(words=2), cols, sel, stacked)
    assert _groups(agg, st) == _want(agg, cols, sel)


def _runs(lengths, seed, spread=10 ** 5, nulls=False):
    """One key a run, the runs in key order as long as `lengths` say:
    the exact forms sort by the key, so a run lies where its length and
    those before it put it (the wide form sorts by a hash of the key: the
    same runs, in another order)."""
    rng = np.random.default_rng(seed)
    n = sum(lengths)
    k = np.repeat(np.arange(len(lengths)), lengths)
    order = rng.permutation(n)
    x = rng.integers(0, spread, n)
    return [(k[order], None),
            (x, rng.random(n) > 0.3 if nulls else None)], np.ones(n, bool)


def _largest(words, n):
    """(least, largest) argument whose distance is the widest a record
    of this form admits: 2^31 - 1 fills a one-word record under the dead
    bit (one group: a key of no bits); in a two-word one what n of them
    leave of int64 (2^40 - 1 at 2^23 slots); wide, both halves 2^32 - 1."""
    if not words:
        return -(2 ** 63), 2 ** 63 - 1
    room = min(32 * words - 1, 63 - (n - 1).bit_length())
    return -5, 2 ** room - 1 - 5


SCAN_CASES = ["block_edges", "one_run", "largest", "all_dead", "null_arg",
              "stacked4"]


@pytest.mark.parametrize("words", FORMS)
@pytest.mark.parametrize("case", SCAN_CASES)
def test_prefix_sums_across_blocks_and_limbs(case, words):
    """What the two-level prefix sum (`ops/limbscan`) has to carry: runs
    that begin and end at the last slot of a block of 128, the first of
    the next and the one after; one run over every slot; every distance
    the largest its form admits, so every limb is full and the int64
    scan over the block totals carries; no live row; NULL arguments (a
    one-limb lane beside the distances'); four stacked shards."""
    nullable, stacked, n = False, 1, 5 * 128
    if case == "block_edges":
        # slots 127, 128 and 129 are runs of their own, then runs that
        # end at 255 and at 256, begin at 257, and end at 383 and 384
        cols, sel = _runs([127, 1, 1, 1, 126, 1, 127, 1, 255], seed=1)
    elif case == "one_run":
        cols, sel = _runs([n], seed=2)
    elif case == "largest":
        lo, hi = _largest(words, n)
        k = np.arange(n) % 2 * (words != 1)
        x = np.where(np.arange(n) == 7, lo, hi)
        cols, sel = [(k, None), (x, None)], np.ones(n, bool)
    elif case == "all_dead":
        cols, sel = _runs([300, 340], seed=4)
        sel = np.zeros(n, bool)
    elif case == "null_arg":
        nullable = True
        cols, sel = _runs([127, 2, 300, 211], seed=5, nulls=True)
    else:
        stacked = 4
        cols, sel = _runs([127, 2, 127, 129, 127], seed=6)
        assert len(sel) == stacked * 128
    agg = _agg(nullable=nullable, words=words)
    st, got_agg, reruns = _regrown(agg, cols, sel, stacked)
    assert (got_agg.pack_words, reruns) == (words, 0), "it fits its form"
    assert _groups(agg, st) == _want(agg, cols, sel)
    assert int(st["__ngroups__"]) == len(_want(agg, cols, sel))
    _st, facts = _states(agg, cols, sel, stacked)
    # the limb lanes: a NULL bit is one, a distance what its bound takes
    bound = {1: 31, 2: 63 - 10, 0: 32}[words]
    lanes = (2 if not words else 1) * -(-bound // 8)
    assert facts["scan_limbs"] == lanes + 2 * nullable


@pytest.mark.parametrize("words", FORMS)
def test_sums_at_the_ends_of_the_limb_fence(words):
    """Values at both ends of int64 in one group: the sum passes int64
    and comes back exact in its two words; the exact forms say the
    record does not fit and the wide form answers."""
    top, bot = 2 ** 63 - 1, -(2 ** 63)
    k = np.array([1, 1, 1, 2, 2, 3, 3, 3] * 16)
    x = np.array([top, top, top, bot, bot, top, bot, 5] * 16)
    cols, sel = [(k, None), (x, None)], np.ones(len(k), bool)
    st, agg, reruns = _regrown(_agg(nullable=False, words=words), cols, sel)
    assert _groups(agg, st) == {(1,): [48 * top, 48, 48],
                                (2,): [32 * bot, 32, 32],
                                (3,): [16 * (top + bot + 5), 48, 48]}
    assert agg.pack_words == 0 and reruns == (1 if words else 0)
    # and a sum whose rows all hold the largest value: distances are 0
    x = np.full(len(k), top)
    st, agg, _ = _regrown(_agg(nullable=False, words=words),
                          [(k, None), (x, None)], sel)
    assert _groups(agg, st)[(1,)] == [48 * top, 48, 48]


def test_a_record_that_does_not_fit_says_so_and_is_rerun_wider():
    """One word holds a dead bit, 10 bits of key and 17 of argument
    here; a key 2^20 wide takes the second word; one 2^40 wide has its
    key part pass the first word, which is all the sort compares."""
    for span, fits in ((1 << 10, 1), (1 << 20, 2), (1 << 40, 0)):
        rng = np.random.default_rng(1)
        k = rng.integers(0, 1 << 10, 4000) * (span >> 10)
        x = rng.integers(0, 1 << 17, 4000)
        cols, sel = [(k, None), (x, None)], np.ones(4000, bool)
        st, agg, reruns = _regrown(_agg(nullable=False, words=1, cap=2048),
                                   cols, sel)
        assert (agg.pack_words, reruns) == (fits, fits != 1)
        assert _groups(agg, st) == _want(agg, cols, sel)


def test_a_capacity_that_overflows_regrows_once():
    """`__ngroups__` passes the capacity where groups are missing, by
    the distinct count or by what the compaction's fullest column takes,
    and the capacity it names holds them all."""
    cols, sel = _table(1 << 14, 3000, seed=11)
    small = _agg(words=2, cap=1024)
    st, _ = _states(small, cols, sel)
    want = _want(small, cols, sel)
    assert int(st["__ngroups__"]) >= len(want) > 1024
    st, agg, reruns = _regrown(small, cols, sel)
    assert reruns == 1 and agg.group_capacity >= len(want)
    assert _groups(agg, st) == want


def test_keys_that_collide_in_the_hash_are_never_merged(monkeypatch):
    """THE CONTRACT: two keys with one hash become partial groups of
    each (their rows interleave in the sort), never one group: the
    table's slots still add up to the reference per true key, and the
    host merge (`merge_sorted_states`) makes each key one group."""
    real = runagg.key_hash
    monkeypatch.setattr(
        runagg, "key_hash",
        lambda keyinfo, n: real(
            [(vz, m, nf, code // 4) for vz, m, nf, code in keyinfo], n))
    cols, sel = _table(2000, 40, seed=13)
    st, agg, _ = _regrown(_agg(words=0, cap=4096), cols, sel)
    want = _want(agg, cols, sel)
    slots = int((st["__rows__"] > 0).sum())
    assert slots > len(want), "no collision split a group: test is void"
    assert int(st["__ngroups__"]) == slots
    assert _groups(agg, st, duplicates=True) == want
    merged = merge_sorted_states(agg, [st])
    assert len(merged["__rows__"]) == len(want)
    assert int(sum(merged["__rows__"])) == int(sel.sum())


@pytest.mark.parametrize("words", (1, 2))
def test_first_groups_ranked_on_the_device(words):
    """`Aggregation.topn`: SUM descending (a NULL sum last), then the key
    ascending (the NULL key first), exactly the reference's first ten;
    the slots past the groups there are hold none."""
    cols, sel = _table(5000, 700, seed=17)
    aggs = _agg().aggs[:2]
    for limit, desc in ((10, True), (10, False), (1000, True)):
        topn = D.GroupTopN((("agg", 0, desc), ("key", 0, False)), limit)
        agg = _agg(words=words, cap=2048, topn=topn, aggs=aggs)
        st, facts = _states(agg, cols, sel)
        want = _want(agg, cols, sel)

        def rank(item, desc=desc):
            (key,), (s, _c) = item
            null_sum = (s is None) if desc else (s is not None)
            return (null_sum, -(s or 0) if desc else (s or 0),
                    key is not None, key or 0)
        first = sorted(want.items(), key=rank)[:limit]
        if limit > D.GROUP_TOPN_MAX:        # the host ranks: whole table
            assert facts["group_topn"] == "host"
            assert _groups(agg, st) == want
            continue
        assert facts["group_topn"] == "device"
        assert len(st["__rows__"]) == limit
        assert list(_groups(agg, st).items()) == [(k, v) for k, v in first]
        assert int(st["__ngroups__"]) == len(want)
    # fewer groups than the limit
    few = [(c[0][:40], c[1][:40]) for c in cols]
    agg = _agg(words=words, cap=128, aggs=aggs, topn=D.GroupTopN(
        (("agg", 1, True), ("key", 0, True)), 64))
    st, _ = _states(agg, few, sel[:40])
    want = _want(agg, few, sel[:40])
    assert _groups(agg, st) == want and (st["__rows__"] > 0).sum() == len(want)


def _with_dependents(n, ndv, seed, live=0.7):
    """A table (k nullable, d1 = a NULL for one k in five else k // 7,
    d2 = -3 * k, x): d1 and d2 are functions of k, as the columns a
    unique left join brings are of its probe key (an unmatched probe
    row: the NULL)."""
    (k, x), sel = _table(n, ndv, seed, live=live)
    d1 = (k[0] // 7, (k[0] % 5 != 0) & k[1])
    d2 = (np.where(k[1], -3 * k[0], 0), None)
    return [k, d1, d2, x], sel


@pytest.mark.parametrize("words", FORMS)
@pytest.mark.parametrize("n,ndv", [(1000, 7), (5000, 700)])
def test_dependent_keys_ride_as_payload(n, ndv, words):
    """`Aggregation.dependent`: the groups are the reference's over all
    three keys, a dependent key NULL where its rows hold a NULL, in
    every record form; the launch says how many keys rode."""
    cols, sel = _with_dependents(n, ndv, seed=41)
    agg = dataclasses.replace(_agg(n_keys=3, words=words, cap=2048),
                              dependent=(1, 2))
    st, facts = _states(agg, cols, sel)
    assert facts["dependent_keys"] == 2
    want = _want(agg, cols, sel)
    assert any(k[0] is not None and k[1] is None for k in want)
    assert _groups(agg, st) == want
    assert int(st["__ngroups__"]) == len(want)
    plain, plain_facts = _states(dataclasses.replace(agg, dependent=()),
                                 cols, sel)
    assert "dependent_keys" not in plain_facts
    if words:       # the keys alone fit no word here: 65 says so
        assert plain["__bits__"] > st["__bits__"]


@pytest.mark.parametrize("words", FORMS)
def test_a_dependent_key_decides_no_group(words):
    """THE CONTRACT of the record: a key marked dependent is no part of
    the key part (the exact record's first-word bits, the wide form's
    hash, a run's boundary).  Shown by breaking the promise: where the
    marked key does change inside a group of the others, the table still
    has ONE slot a determining key, with the rows of all of them."""
    cols, sel = _with_dependents(4000, 60, seed=43, live=1.0)
    cols[2] = (np.arange(4000), None)       # no function of k at all
    agg = dataclasses.replace(_agg(n_keys=3, words=words, cap=2048),
                              dependent=(1, 2))
    st, _ = _states(agg, cols, sel)
    by_k = _want(_agg(n_keys=1), [cols[0], cols[3]], sel)
    assert int(st["__ngroups__"]) == len(by_k) \
        == int((st["__rows__"] > 0).sum())
    got = {key[0]: vals for key, vals in _groups(agg, st).items()}
    assert got == {key[0]: vals for key, vals in by_k.items()}


def test_a_record_too_wide_for_two_words_is_rerun_wide_with_its_dependents_out():
    """A determining key 2^40 wide passes the first word: the dispatcher
    goes to the wide form, whose hash and run boundaries still leave the
    dependent keys out, and the answer is the same."""
    cols, sel = _with_dependents(4000, 300, seed=47)
    cols[0] = (cols[0][0] * (1 << 30), cols[0][1])
    start = dataclasses.replace(_agg(n_keys=3, words=2, cap=2048),
                                dependent=(1, 2))
    st, agg, reruns = _regrown(start, cols, sel)
    assert (agg.pack_words, agg.dependent, reruns) == (0, (1, 2), 1)
    assert _groups(agg, st) == _want(agg, cols, sel)
    _st, facts = _states(agg, cols, sel)
    assert facts["dependent_keys"] == 2


@pytest.mark.parametrize("words", (1, 2))
def test_groups_ranked_on_the_device_by_a_dependent_key(words):
    """A dependent key is a `("key", j, desc)` of `GroupTopN` like any
    other: COUNT descending, then the dependent key ascending (its NULL
    first), then the determining key; a limit past `GROUP_TOPN_MAX` is
    the host's."""
    cols, sel = _with_dependents(5000, 300, seed=53)
    aggs = _agg().aggs[1:2]                  # count(*)
    keys = (("agg", 0, True), ("key", 1, False), ("key", 0, False))
    base = dataclasses.replace(
        _agg(n_keys=3, words=words, cap=2048, aggs=aggs), dependent=(1, 2))
    want = _want(base, cols, sel)

    def rank(item):
        (k, d1, _d2), (c,) = item
        return (-c, d1 is not None, d1 or 0, k is not None, k or 0)
    for limit in (10, D.GROUP_TOPN_MAX + 1):
        agg = dataclasses.replace(base, topn=D.GroupTopN(keys, limit))
        st, facts = _states(agg, cols, sel)
        if limit > D.GROUP_TOPN_MAX:
            assert facts["group_topn"] == "host"
            assert _groups(agg, st) == want
            continue
        assert facts["group_topn"] == "device"
        assert list(_groups(agg, st).items()) \
            == sorted(want.items(), key=rank)[:limit]


@pytest.mark.parametrize("dependents", [False, True])
@pytest.mark.parametrize("n_dev", [1, 4])
def test_through_the_sharded_program_on_one_and_four_devices(monkeypatch,
                                                             n_dev,
                                                             dependents):
    """`ShardedCopProgram` over a mesh traced as for a TPU, two stacked
    shards a device: the per-device tables, merged by the host, equal
    the reference; the launch says its strategy and its capacity, and a
    device that holds every row of its groups ranks them.  With two of
    three keys riding as dependents: the same, and the launch says so."""
    mesh = Mesh(np.array(jax.devices()[:n_dev]), (SHARD_AXIS,))
    monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: "tpu")
    s, cap = 2 * n_dev, 1024
    cols, _sel = _with_dependents(s * cap, 500, seed=19) if dependents \
        else _table(s * cap, 500, seed=19)
    counts = np.array([cap - 7 * i for i in range(s)], np.int64)
    live = (np.arange(cap)[None, :] < counts[:, None]).reshape(-1)

    def put(a):
        return jax.device_put(a.reshape(s, cap), sharded(mesh))
    args = ([(put(v), None if m is None else put(m)) for v, m in cols],
            jax.device_put(counts, sharded(mesh)))
    agg = dataclasses.replace(_agg(n_keys=3, words=2, cap=2048),
                              dependent=(1, 2)) if dependents \
        else _agg(words=2, cap=2048)
    try:
        prog = spmd.ShardedCopProgram(agg, mesh)
        states = jax.tree_util.tree_map(np.asarray, prog(*args))
        assert (states["__ngroups__"] <= 2048).all()
        # two NULL lanes of one limb; distances below 2^52 (2,048 slots)
        assert prog.facts(*args) == dict(
            {"agg_strategy": "sort", "group_capacity": 2048,
             "scan_limbs": 1 + 7 + 1},
            **({"dependent_keys": 2} if dependents else {}))
        per_dev = [jax.tree_util.tree_map(lambda a, d=d: a[d], states)
                   for d in range(n_dev)]
        merged = merge_sorted_states(agg, per_dev)
        merged = {k: v for k, v in merged.items()}
        merged["__ngroups__"] = 0
        assert _groups(agg, merged) == _want(agg, cols, live)
        if n_dev == 1:
            ranked = dataclasses.replace(agg, topn=D.GroupTopN(
                (("agg", 1, True), ("key", 0, False)), 5))
            prog = spmd.ShardedCopProgram(ranked, mesh)
            top = jax.tree_util.tree_map(lambda a: np.asarray(a)[0],
                                         prog(*args))
            assert prog.facts(*args)["group_topn"] == "device"
            want = sorted(_want(agg, cols, live).items(), key=lambda kv: (
                -kv[1][1], kv[0][0] is not None, kv[0][0] or 0))[:5]
            assert list(_groups(ranked, top).items()) == want
    finally:
        spmd._cached.cache_clear()


QTY_SQL = ("select l_partkey, sum(l_quantity) from lineitem "
           "group by l_partkey order by 2 desc, 1 limit 10")
REV_SQL = ("select l_partkey, sum(l_extendedprice * (1 - l_discount)) as "
           "revenue, count(*) from lineitem where l_shipdate >= date "
           "'1994-01-01' and l_shipdate < date '1994-01-01' + interval '1' "
           "year group by l_partkey order by revenue desc, l_partkey "
           "limit 10")


@pytest.mark.parametrize("sql,words", [(QTY_SQL, 1), (REV_SQL, 2)])
@pytest.mark.parametrize("n_dev", [1, 8])
def test_whole_statements_through_the_normal_path(monkeypatch, sql, words,
                                                  n_dev):
    """Both statement classes of `tpch1x1.hndv`, SQL text to rows, over
    a mesh traced as for a TPU: the answer is the host engine's; one
    launch a statement; `/sched` counts it, no regrow, and the host
    ranks only where a group's rows lie on several devices; the span
    says strategy, capacity and where the groups were ranked, and
    `cop.transfer` the groups found; EXPLAIN says what will run."""
    from tidb_tpu.parallel import get_mesh
    from tidb_tpu.sched import scheduler_for
    from tidb_tpu.testing.tpch import tpch_plan_session
    want = tpch_plan_session(0.002).execute(sql).rows

    sess = tpch_plan_session(0.002)
    dom = sess.domain
    mesh = get_mesh(n_dev)
    dom.client.mesh = mesh
    dom.client._platform = lambda: "tpu"    # the device path
    monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: "tpu")
    sess.execute("set global tidb_tpu_trace_sample = 1")
    sess.execute("set global tidb_tpu_result_cache_entries = 0")
    sess.execute("analyze table lineitem")
    spmd._cached.cache_clear()
    sched = scheduler_for(mesh)
    names = ("launches", "hndv_agg_launches", "hndv_agg_regrows",
             "hndv_host_topn_launches", "hndv_limb_scan_launches")
    where = "device" if n_dev == 1 else "host"
    try:
        footer = [r[0] for r in sess.execute("explain " + sql).rows
                  if r[0].startswith("agg strategy")]
        before = sched.stats()
        got = [sess.execute(sql).rows for _ in range(2)]
        after = sched.stats()
    finally:
        spmd._cached.cache_clear()
    assert got == [want, want]
    assert footer == [
        f"agg strategy: sort (capacity 1024; one sort of {words}-word "
        f"records, first 10 groups ranked on the {where})"]
    assert [after[k] - before[k] for k in names] \
        == [2, 2, 0, 2 * (n_dev > 1), 2]
    spans = [sp for ent in dom.flight_recorder.index()
             for sp in dom.flight_recorder.get(ent["trace_id"]).spans]
    launches = [sp.attrs for sp in spans if sp.name == "sched.launch"
                and "_agg_sort_" in sp.attrs.get("program", "")]
    assert len(launches) == 2
    assert all((a["agg_strategy"], a["group_capacity"], a["group_topn"])
               == ("sort", 1024, where) for a in launches)
    # the prefix sums' limb lanes: a distance is below 2^31 in a one-word
    # record; in a two-word one below 2^49 or 2^52 (what 12,032 or 1,536
    # slots a device leave of int64)
    assert [a["scan_limbs"] for a in launches] \
        == [4 if words == 1 else 7] * 2
    found = [sp.attrs["ngroups"] for sp in spans
             if sp.name == "cop.transfer" and "ngroups" in sp.attrs]
    assert len(found) == 2 and found[0] >= 300 * (1 if words == 2 else 1)


def test_the_planner_keeps_its_choice_on_a_cpu_mesh():
    """No program is lowered for a TPU here, so nothing of this changes
    a plan: no record words, SORT with its capacity from the NDV."""
    from tidb_tpu.testing.tpch import tpch_plan_session
    sess = tpch_plan_session(0.002)
    sess.execute("analyze table lineitem")
    footer = [r[0] for r in sess.execute("explain " + QTY_SQL).rows
              if r[0].startswith("agg strategy")]
    assert footer == ["agg strategy: sort (capacity 1024)"]


def test_run_form_is_counts_and_integer_sums():
    arg = ColumnRef(dt.double(), 1)
    assert runagg.run_form(_agg())
    assert not runagg.run_form(_agg(aggs=(
        D.AggDesc(D.AggFunc.MIN, ColumnRef(I64, 1), I64),)))
    assert not runagg.run_form(_agg(aggs=(
        D.AggDesc(SUM, arg, dt.double()),)))


@pytest.mark.parametrize("change,ok", [
    ({}, True),
    ({"pack_words": 3}, False),
    ({"strategy": D.GroupStrategy.DENSE, "domain_sizes": (1024,),
      "group_capacity": 0, "pack_words": 1}, False),
    ({"topn": D.GroupTopN((("agg", 0, True), ("key", 0, False)), 10)}, True),
    ({"topn": D.GroupTopN((("agg", 5, True),), 10)}, False),
    ({"topn": D.GroupTopN((("key", 0, True),), 0)}, False),
])
def test_the_contract_of_the_new_fields(change, ok):
    """`pack_words` is a SORT record's and 0, 1 or 2; `topn` names keys
    and COUNTs or SUMs of its own aggregation.  And a SORT aggregation
    with an exact record has no fusion class: every member sorts its own
    records, so a fused program only compiles them again."""
    from tidb_tpu.analysis.contracts import (PlanContractError,
                                             fusion_signature, verify_dag)
    agg = dataclasses.replace(_agg(words=1, nullable=False), **change)
    if ok:
        verify_dag(agg)
        assert fusion_signature(agg) is None
        assert fusion_signature(dataclasses.replace(agg, pack_words=0)) \
            == ("sort-agg", 1024)
    else:
        with pytest.raises(PlanContractError):
            verify_dag(agg)


def test_programs_that_use_neither_field_keep_their_names():
    """`pack_words` and `topn` came after programs were named by their
    DAG's digest (`dag.DIGEST_IF_SET`): at their defaults they are no
    part of it, so a DENSE or SCALAR program's name, and its place in
    every compile cache, is what the commit before them gave (the
    literals are PR 44's: a field that leaves `Aggregation` renames
    every aggregation once, and two strategies' fields left there)."""
    from tidb_tpu.analysis.compilekey import stable_digest
    scan = D.TableScan((0, 1), (I64, I64))
    aggs = (D.AggDesc(SUM, ColumnRef(I64, 1), sum_out_dtype(I64)),)
    dense = D.Aggregation(scan, (ColumnRef(I64, 0),), aggs,
                          D.GroupStrategy.DENSE, domain_sizes=(6,))
    sort = D.Aggregation(scan, (ColumnRef(I64, 0),), aggs,
                         D.GroupStrategy.SORT, group_capacity=1024)
    assert stable_digest(dense) == "8f1736135998172f"
    assert stable_digest(sort) == "659275d34c858f8c"
    assert len({stable_digest(sort), stable_digest(dataclasses.replace(
        sort, pack_words=1)), stable_digest(dataclasses.replace(
            sort, topn=D.GroupTopN((("key", 0, False),), 3)))}) == 3


# what the statements of the cells that never reach `agg_run_states`
# launch, planned and lowered as for a TPU over `tpch_plan_session(0.002)`
# (the class files' own text; Q14 against a stand-in for `part` with a
# `p_type`): the program names, digest and all, of PR 44 (which renamed
# every aggregation by taking two fields out of `dag.Aggregation`, and
# changed no program's text)
OTHER_CELLS = {
    "q6": ({"year": 1994, "discount": 6, "quantity": 24},
           ["cop_solo_agg_scalar_af993896b29b"]),
    "q1": ({"delta": 90}, ["cop_solo_agg_dense_43ba43a5bbce"]),
    "topn": ({}, ["cop_solo_topn_16ce5d827856"]),
    "part_agg": ({"size": 25}, ["cop_solo_agg_dense_3dc5cdbe0b84"]),
    "kv_agg": ({"grp": 5}, ["cop_solo_agg_scalar_790de0a0b330"]),
    "q14": ({"year": 1995, "month": 9},
            ["cop_solo_join_agg_scalar_8b5d162ad98a",
             "cop_solo_rows_adc818226355"]),
    "q19": ({"quantity": [1, 10, 20],
             "brand": ["Brand#12", "Brand#23", "Brand#34"]},
            ["cop_solo_join_agg_scalar_b390880e3a9e",
             "cop_solo_rows_dc761e59f787"]),
}


def _bench_module(monkeypatch, kind, name):
    """``benchmark/<kind>/<name>.py``, as the harness loads it."""
    import importlib.util
    import os
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    monkeypatch.syspath_prepend(bench)      # the class files' `harness`
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", os.path.join(bench, kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tpu_planned():
    """-> run(sql): the names of the programs one statement builds."""
    from tidb_tpu.parallel import get_mesh
    from tidb_tpu.testing.tpch import tpch_plan_session
    built = []
    real = spmd.ShardedCopProgram.__init__

    def init(self, *args, **kwargs):
        real(self, *args, **kwargs)
        built.append(self.name)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spmd, "mesh_platform", lambda _mesh: "tpu")
        mp.setattr(spmd.ShardedCopProgram, "__init__", init)
        sess = tpch_plan_session(0.002)
        sess.domain.client.mesh = get_mesh(1)
        sess.domain.client._platform = lambda: "tpu"
        sess.execute("set global tidb_tpu_result_cache_entries = 0")
        sess.execute("create table bench_kv (k bigint primary key, "
                     "grp bigint, v bigint)")
        sess.execute("insert into bench_kv values " + ",".join(
            f"({i},{i % 10},{i * 7})" for i in range(200)))
        sess.execute("create table part_t (p_partkey bigint primary key, "
                     "p_type varchar(25))")
        sess.execute("insert into part_t values " + ",".join(
            f"({i},'{'PROMO' if i % 3 else 'STANDARD'} BRUSHED TIN')"
            for i in range(1, 401)))
        spmd._cached.cache_clear()

        def run(sql):
            del built[:]
            sess.execute(sql)
            return sorted(set(built))
        try:
            yield run
        finally:
            spmd._cached.cache_clear()


@pytest.mark.parametrize("cls", list(OTHER_CELLS))
def test_the_other_cells_statements_keep_their_programs(tpu_planned, cls,
                                                        monkeypatch):
    """`tpch10x1.power`, `tpch10x1.small` and `tpch1x1.partjoin` plan
    DENSE or SCALAR (or a TopN, or a lookup join under a scalar
    aggregation): their programs are named as the parent named them, so
    they are the parent's programs, and none says `scan_limbs`."""
    mod = _bench_module(monkeypatch, "classes", cls)
    params, names = OTHER_CELLS[cls]
    sql = mod.sql(params)
    if cls == "q14":
        sql = sql.replace("lineitem, part ", "lineitem, part_t ")
    assert tpu_planned(sql) == names
    assert "_agg_sort_" not in " ".join(names)


# the statement classes of the cells with a GROUP BY above 6M rows or a
# join (`tpch1x1.hndv`, `tpch1x1.orderjoin`; `tpch10x4.shuffle`'s texts
# are `q3`'s and `q12`'s), each with parameters its `draw` could give,
# the tables its text reads, and how its root aggregation is planned
CELL_GROUP_BYS = {
    "hndv_qty": ({}, ("LINEITEM",), D.GroupStrategy.SORT),
    "hndv_rev": ({"year": 1994}, ("LINEITEM",), D.GroupStrategy.SORT),
    "q3": ({"segment": "BUILDING", "day": 15},
           ("CUSTOMER", "ORDERS", "LineItem"), D.GroupStrategy.SORT),
    # one group a ship mode, a dictionary of seven: in-program
    "q12": ({"mode1": "MAIL", "mode2": "SHIP", "year": 1994},
            ("ORDERS", "LineItem"), D.GroupStrategy.DENSE),
}


@pytest.mark.parametrize("cls", list(CELL_GROUP_BYS))
def test_the_cells_group_bys_keep_their_lowering(monkeypatch, cls):
    """A cell's statement that leaves `runagg` fails here, and not only
    at the chip's `devicepath` check: planned as for a TPU over the
    benchmark's own tables, the root aggregation of each class text is
    SORT, of COUNTs and integer or DECIMAL SUMs alone (`run_form`); or,
    for Q12, DENSE, which no host-merged lowering ever sees."""
    from tidb_tpu.session import Domain, Session
    from tidb_tpu.testing.tpch import built_tpch_plans
    params, tables, strategy = CELL_GROUP_BYS[cls]
    run_py = _bench_module(monkeypatch, "", "run")
    dom = Domain()
    for name in tables:
        table = _bench_module(monkeypatch, "tables", name)
        run_py._load_table(dom, None, table, table.generate(
            0.002, 2147483659, list(table.TYPES)))
    sess = Session(dom)
    for name in tables:
        sess.execute(f"analyze table {name}")
    monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: "tpu")
    sql = _bench_module(monkeypatch, "classes", cls).sql(params)
    (_sql, phys), = built_tpch_plans(sess, [sql])
    roots, stack = [], [phys]
    while stack:
        op = stack.pop()
        if isinstance(getattr(op, "dag", None), D.Aggregation):
            roots.append(op.dag)
        stack.extend(c for c in getattr(op, "children", []) or [] if c)
    (root,) = roots
    assert root.strategy is strategy, root
    if strategy is D.GroupStrategy.SORT:
        assert root.host_merged and runagg.run_form(root), root.aggs


def test_the_scan_limbs_fact_is_a_row_of_the_table_and_nothing_of_sched():
    """`agg_run_states` writes `scan_limbs`; `copr/facts.py` alone says
    what it counts (`hndv_limb_scan_launches`, where it is over 0), that
    it goes on the `sched.launch` span, and that it is a host-merged
    aggregation root's.  The scheduler knows neither name
    (`test_whole_statements_through_the_normal_path` sees both arrive)."""
    import os
    from tidb_tpu import sched
    from tidb_tpu.copr import facts as F
    assert "hndv_limb_scan_launches" in F.counter_names()
    assert F.counters({"agg_strategy": "sort", "scan_limbs": 5}) \
        == ["hndv_agg_launches", "hndv_limb_scan_launches"]
    assert F.counters({"agg_strategy": "sort", "scan_limbs": 0}) \
        == ["hndv_agg_launches"]
    assert F.span_attrs({"scan_limbs": 5}) == {"scan_limbs": 5}
    assert F.merged([{"scan_limbs": 4}, {}, {"scan_limbs": 9}]) \
        == {"scan_limbs": 9}
    sort = _agg(words=1)
    dense = dataclasses.replace(sort, strategy=D.GroupStrategy.DENSE,
                                group_capacity=0, pack_words=0,
                                domain_sizes=(6,))
    assert F.of_program({"scan_limbs": 4}, sort) == {"scan_limbs": 4}
    assert F.of_program({"scan_limbs": 4}, dense) == {}
    folder = os.path.dirname(sched.__file__)
    for name in os.listdir(folder):
        if name.endswith(".py"):
            with open(os.path.join(folder, name)) as f:
                text = f.read()
            assert "scan_limbs" not in text and "limb_scan" not in text, name
