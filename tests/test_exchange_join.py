"""The orders-lineitem join across chips (``tpch_sf10_orders_x4``): a build
side past the broadcast cap stays sharded where it lives, each device
holding the direct-addressed table of the keys it owns, and the probe's
live rows travel to the device that owns their key.  TPC-H Q3 and Q12 in
the spec's own text against the benchmark's plain references
(``benchmark/classes/q3.py``, ``q12.py``: numpy on a key -> row map, exact
integer sums, ``np.lexsort``; nothing of the program) at a small scale
with the cap lowered, on the four-device CPU mesh with every program
lowered as for a TPU; the exchange alone against a numpy partition; the
rules (who owns a key, which side moves, a bucket's capacity) as pure
functions with their cases pinned.

The tolerance is equality: the answers are DECIMAL text."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from test_orderjoin import (SCALE, SEED, _bench, _forget_programs, _spans,
                            _text, lowered_for)  # noqa: F401 - a fixture
from tidb_tpu.copr import dag as D
from tidb_tpu.copr import facts as F
from tidb_tpu.copr import joinbuild as JB
from tidb_tpu.executor import plan
from tidb_tpu.parallel import exchange as E
from tidb_tpu.parallel import get_mesh
from tidb_tpu.parallel.mesh import SHARD_AXIS, shard_map
from tidb_tpu.session import Domain, Session
from tidb_tpu.session.catalog import TableInfo

CAP = 4096          # the broadcast cap the statements run under: ORDERS
                    # (30,000 rows) is past it, CUSTOMER (3,000) under it
V5E = 16 << 30


# --------------------------------------------------------------------- #
# the rules, pure functions
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("firsts, devices, top, slots", [
    # one stripe a device, in order
    ([1, 100, 200, 300], [0, 1, 2, 3], 400, [99, 100, 100, 100]),
    # a table's shards dealt round robin: a device owns two stripes and
    # its table holds them one after the other
    ([1, 100, 200, 300], [0, 1, 0, 1], 400, [199, 200]),
    # a stripe that holds nothing takes no slot
    ([1, None, 200, 300], [0, 1, 2, 3], 400, [199, 0, 100, 100]),
    ([1, 100, None, None], [0, 1, 2, 3], 150, [99, 50, 0, 0]),
    ([None, None, None, 7], [0, 1, 2, 3], 9, [0, 0, 0, 2]),
    ([5], [0], 9, [4]),
])
def test_the_key_range_is_cut_into_stripes(firsts, devices, top, slots):
    part, got = JB.key_partition(firsts, devices, len(slots), top)
    assert list(got) == slots and part.shape == (3, len(firsts))
    # the steps add up to each stripe's device
    assert np.cumsum(part[1]).tolist() == devices


def test_stripes_whose_keys_overlap_are_refused():
    with pytest.raises(ValueError, match="overlap"):
        JB.key_partition([1, 300, 200, 400], [0, 1, 2, 3], 4, 500)


@pytest.mark.parametrize("firsts, devices, top, keys, owner, offset", [
    ([0, 100, 200, 300], [0, 1, 2, 3], 400,
     [0, 99, 100, 199, 200, 299, 300, 399],
     [0, 0, 1, 1, 2, 2, 3, 3], [0, 99, 0, 99, 0, 99, 0, 99]),
    # round robin: device 0 holds [0, 100) and then [200, 300)
    ([0, 100, 200, 300], [0, 1, 0, 1], 400,
     [0, 99, 100, 199, 200, 299, 300, 399],
     [0, 0, 1, 1, 0, 0, 1, 1], [0, 99, 0, 99, 100, 199, 100, 199]),
    # stripe 1 holds nothing and owns nothing
    ([0, None, 200, 300], [0, 1, 2, 3], 400, [0, 199, 200, 250],
     [0, 0, 2, 2], [0, 199, 0, 50]),
    ([7], [0], 9, [7, 8], [0, 0], [0, 1]),
])
def test_every_key_has_one_owner_and_one_slot(firsts, devices, top, keys,
                                              owner, offset):
    """`key_places`: on the host and in a program alike."""
    part, _slots = JB.key_partition(firsts, devices, max(devices) + 1, top)
    own, at = E.key_places(np.array(keys, np.int64), part, np)
    assert own.tolist() == owner and at.tolist() == offset
    own, at = E.key_places(jnp.array(keys, jnp.int32),
                           jnp.asarray(part.astype(np.int32)))
    assert np.asarray(own).tolist() == owner \
        and np.asarray(at).tolist() == offset


def test_a_key_outside_every_stripe_lands_outside_its_owner_s_table():
    part, slots = JB.key_partition([10, 100], [0, 1], 2, 200)
    own, at = E.key_places(np.array([9, -5, 200, 10 ** 9]), part, np)
    assert own.tolist() == [0, 0, 1, 1]
    assert (at[:2] < 0).all() and (at[2:] >= slots[1]).all()


@pytest.mark.parametrize("case, want", [
    # under the cap: replicated, nothing moves
    (dict(rows=1_500_000, span=6_000_000, columns=1, n_dev=4, unique=True,
          by_key=True, from_table=True), plan.REPLICATE),
    # TPC-H SF10's ORDERS, a resident table past the cap: it stays
    (dict(rows=15_000_000, span=60_000_000, columns=1, n_dev=4, unique=True,
          by_key=True, from_table=True), plan.PROBE_TO_BUILD),
    # ... whatever order it is stored in: the host deals its rows out
    (dict(rows=15_000_000, span=60_000_000, columns=1, n_dev=4, unique=True,
          by_key=False, from_table=True), plan.PROBE_TO_BUILD),
    # Q3's orders x customer: a join's result lies where it was made;
    # the two columns the statement reads of its six count, not the six
    (dict(rows=16_500_000, span=60_000_000, columns=2, n_dev=4, unique=True,
          by_key=True, from_table=False), plan.PROBE_TO_BUILD),
    (dict(rows=16_500_000, span=60_000_000, columns=5, n_dev=4, unique=True,
          by_key=True, from_table=False), plan.HOST),
    (dict(rows=16_500_000, span=60_000_000, columns=2, n_dev=4, unique=True,
          by_key=False, from_table=False), plan.HOST),
    # one device: the same rule, and nothing to exchange
    (dict(rows=15_000_000, span=60_000_000, columns=1, n_dev=1, unique=True,
          by_key=True, from_table=True), plan.PROBE_TO_BUILD),
    # a key that comes twice, or a range no table spans: both sides move
    (dict(rows=15_000_000, span=60_000_000, columns=1, n_dev=4, unique=False,
          by_key=True, from_table=True), plan.BOTH),
    (dict(rows=15_000_000, span=1 << 40, columns=1, n_dev=4, unique=True,
          by_key=True, from_table=True), plan.BOTH),
    # a device's share of the table has to fit 1/64 of its memory
    (dict(rows=15_000_000, span=60_000_000, columns=8, n_dev=4, unique=True,
          by_key=True, from_table=True), plan.BOTH),
    (dict(rows=15_000_000, span=60_000_000, columns=8, n_dev=4, unique=True,
          by_key=True, from_table=True, device_bytes=8 * V5E),
     plan.PROBE_TO_BUILD),
    (dict(rows=5000, span=20_000, columns=1, n_dev=4, unique=True,
          by_key=True, from_table=True, cap=CAP), plan.PROBE_TO_BUILD),
])
def test_which_side_moves(case, want):
    assert plan.which_side_moves(**case) == want


@pytest.mark.parametrize("est, n_dev, colocated, want", [
    (1_000_000, 1, False, 0),               # one device: no exchange
    (8_000_000, 4, False, 2_621_440),       # a quarter, and a quarter more
    (8_000_000, 4, True, 196_608),          # a sixty-fourth, and room
    (75_000, 4, True, 4096),
    (0, 4, True, 1024),                     # never less than eight rows
])
def test_a_bucket_s_capacity(est, n_dev, colocated, want):
    got = D.exchange_capacity_for(est, n_dev, colocated)
    assert got == want and got % D.COMPACT_COLUMNS == 0


def test_the_sharded_form_asks_a_table_of_every_device():
    assert JB.sharded_form([15_000_000] * 4, 1, V5E)
    assert not JB.sharded_form([15_000_000, 15_000_000, 1 << 31, 0], 1, V5E)
    assert JB.sharded_form([0, 0, 0, 100], 1, V5E)


# --------------------------------------------------------------------- #
# the exchange alone, against a numpy partition
# --------------------------------------------------------------------- #

N_DEV, SLOTS = 4, 2048
# a device's slots and a bucket's capacity under the half-of-n rule (the
# buckets to the other devices take 6,144 of 16,384 slots: two levels,
# ONE sort of all slots) and over it (every bucket as long as the
# slots: a sort of all slots a destination, the form before PR 36)
FORMS = {"two_levels": (16384, 2048), "sort_a_destination": (SLOTS, SLOTS)}
COLS = D.COMPACT_COLUMNS


def _exchange(vals, nullable, live, dest, capacity, stacked=1):
    """Run `exchange_rows` over the four-device mesh: `vals` (devices,
    slots) int64, `nullable` the same shape or None, `live`, `dest`;
    -> what each device received, per device."""
    mesh = get_mesh(N_DEV)

    def fn(v, m, lv, ds):
        cols = [(v[0], True if m is None else m[0]),
                ((v[0] * 3).astype(jnp.int32), True)]
        got, ok, need, sent = E.exchange_rows(
            cols, lv[0], ds[0], N_DEV, capacity, stacked)
        return ([(g[None], jnp.ones(g.shape, bool)[None]
                  if gm is True else gm[None]) for g, gm in got],
                ok[None], need[None], sent[None])
    specs = (P(SHARD_AXIS), None if nullable is None else P(SHARD_AXIS),
             P(SHARD_AXIS), P(SHARD_AXIS))
    out = jax.jit(shard_map(fn, mesh=mesh, in_specs=specs,
                            out_specs=P(SHARD_AXIS)))(
        vals, nullable, live, dest)
    return jax.tree_util.tree_map(np.asarray, out)


def _need(away, dest):
    """What `need` has to say, by numpy: a device's fullest (column,
    destination) count times the columns; a slot's column is its index
    mod COMPACT_COLUMNS, however the slots are stacked."""
    lane = np.arange(away.shape[1]) % COLS
    return [max(int(np.bincount(lane[away[s] & (dest[s] == d)],
                                minlength=COLS).max())
                for d in range(N_DEV)) * COLS for s in range(N_DEV)]


def _arrived(got, ok, vals, nullable, away, dest):
    """Device d holds exactly the rows the others had for it, each in
    one slot (as a multiset: a bucket's rows lie in no order)."""
    for d in range(N_DEV):
        want = sorted(
            (int(vals[s, i]), bool(nullable[s, i]),
             int(np.int32(vals[s, i] * 3)))
            for s in range(N_DEV) for i in np.nonzero(
                away[s] & (dest[s] == d))[0])
        take = np.nonzero(ok[d])[0]
        have = sorted((int(got[0][0][d][i]), bool(got[0][1][d][i]),
                       int(got[1][0][d][i])) for i in take)
        assert have == want, d


@pytest.mark.parametrize("mix, form, stacked", [
    ("some", "sort_a_destination", 1), ("none", "sort_a_destination", 1),
    ("all", "sort_a_destination", 1),
    ("one_takes_all", "sort_a_destination", 1),
    ("all_stay", "sort_a_destination", 1),
    ("some", "sort_a_destination", 2),
    ("few", "two_levels", 1), ("few", "two_levels", 2),
    ("few", "two_levels", 8), ("none", "two_levels", 1),
    ("all_stay", "two_levels", 2), ("one_takes_few", "two_levels", 1)])
def test_the_exchange_equals_a_numpy_partition(mix, form, stacked):
    """Every live/dead mix, in both forms the buckets are made in and
    with the slots in stacked runs: device d receives exactly the live
    rows the other devices hold with destination d, each in exactly one
    slot, NULL masks and both columns with them; rows whose destination
    is their own device do not travel; `need` and `sent` are numpy's
    counts."""
    slots, capacity = FORMS[form]
    assert E.exchange_passes(slots, N_DEV, capacity) \
        == (1 if form == "two_levels" else N_DEV)
    rng = np.random.default_rng(35)
    vals = rng.integers(-2 ** 40, 2 ** 40, (N_DEV, slots))
    nullable = rng.random((N_DEV, slots)) < 0.9
    dest = rng.integers(0, N_DEV, (N_DEV, slots)).astype(np.int32)
    live = {"some": rng.random((N_DEV, slots)) < 0.3,
            "few": rng.random((N_DEV, slots)) < 0.12,
            "one_takes_few": rng.random((N_DEV, slots)) < 0.04,
            "none": np.zeros((N_DEV, slots), bool),
            "all": np.ones((N_DEV, slots), bool),
            "one_takes_all": np.ones((N_DEV, slots), bool),
            "all_stay": np.ones((N_DEV, slots), bool)}[mix]
    if mix in ("one_takes_all", "one_takes_few"):
        dest[:] = 2
    if mix == "all_stay":
        dest[:] = np.arange(N_DEV)[:, None]
    got, ok, need, sent = _exchange(vals, nullable, live, dest, capacity,
                                    stacked)
    away = live & (dest != np.arange(N_DEV)[:, None])
    assert sent.tolist() == away.sum(axis=1).tolist()
    assert need.tolist() == _need(away, dest) and (need <= capacity).all()
    _arrived(got, ok, vals, nullable, away, dest)


def _past(level):
    """(slots, capacity, live, dest): a bucket past its capacity where
    `level` says.  `first`: two levels, a column holds more remote rows
    than the first sort keeps; `second`: two levels, the first sort
    keeps every remote row (20 of a column's 128, 48 are kept) and one
    destination's do not fit its bucket (16 a column); `sort_a_
    destination`: the form over the half-of-n rule."""
    slots, capacity = {"first": (SLOTS, 256), "second": (16384, 2048),
                       "sort_a_destination": (SLOTS, 512)}[level]
    live = np.ones((N_DEV, slots), bool)
    if level == "second":
        live[:, 20 * COLS:] = False
    return slots, capacity, live, np.full((N_DEV, slots), 1, np.int32)


@pytest.mark.parametrize("level", ["first", "second", "sort_a_destination"])
def test_a_bucket_past_its_capacity_says_what_it_takes(level):
    """Rows that do not fit are reported, not silently dropped, at
    either level of the two-level form and in the other: `need` is
    above the capacity and exact, and at the capacity it names every
    row arrives."""
    slots, capacity, live, dest = _past(level)
    assert E.exchange_passes(slots, N_DEV, capacity) \
        == (N_DEV if level == "sort_a_destination" else 1)
    rng = np.random.default_rng(36)
    vals = rng.integers(0, 2 ** 31, (N_DEV, slots))
    rows = int(live[0].sum())
    away = live & (dest != np.arange(N_DEV)[:, None])
    _got, ok, need, sent = _exchange(vals, None, live, dest, capacity)
    assert need.max() > capacity and ok[1].sum() < 3 * rows
    assert need.tolist() == _need(away, dest)
    exact = D.exchange_capacity_round(int(need.max()))
    got, ok, need2, _sent = _exchange(vals, None, live, dest, exact)
    assert need2.tolist() == need.tolist() and need2.max() <= exact \
        and ok[1].sum() == 3 * rows
    assert sent.tolist() == [rows, 0, rows, rows]
    _arrived(got, ok, vals, np.ones(vals.shape, bool), away, dest)


def _live_rows_pr28(sel, capacity, stacked=1):
    """`copr/join.live_rows` as PR 28 wrote it and every compaction
    that does not exchange still has to trace it, word for word."""
    from jax import lax
    from tidb_tpu.copr.join import _tile_order
    n = sel.shape[0]
    cols = COLS
    assert n % cols == 0 and capacity % cols == 0, (n, capacity)
    bit = max(n - 1, 1).bit_length()
    wt = jnp.int32 if bit < 31 else jnp.int64
    if stacked == 1 or n % (stacked * cols):
        stacked = 1
    run, tile, lane = (lax.broadcasted_iota(
        wt, (stacked, n // stacked // cols, cols), d) for d in range(3))
    places = ((tile * stacked + run) * cols + lane).reshape(n)
    words = _tile_order(jnp.where(sel, places, places | (1 << bit)),
                        stacked).reshape(n // cols, cols)
    top = lax.optimization_barrier(lax.sort(
        words, dimension=0, is_stable=False)[:capacity // cols].reshape(-1))
    need = jnp.max(jnp.sum((words >> bit) == 0, axis=0,
                           dtype=jnp.int32)) * cols
    return top & ((1 << bit) - 1), (top >> bit) == 0, need


def _sorts(jaxpr) -> list:
    """The operand shape of every `sort` of a jaxpr, inner ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            out.append(tuple(eqn.invars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _sorts(sub)
    return sorted(out)


@pytest.mark.parametrize("slots, capacity, stacked, share", [
    (16384, 2048, 1, 0.1), (16384, 2048, 2, 0.1), (16384, 2048, 8, 0.02),
    (16384, 1024, 1, 0.2),      # past the capacity at the second level
    (16384, 1024, 2, 0.9),      # ... and at the first
    (1 << 17, 4096, 4, 0.05),
    (SLOTS, SLOTS, 1, 0.5)])    # over the rule: the same code as before
def test_a_bucket_holds_the_same_place_in_the_same_slot_in_both_forms(
        slots, capacity, stacked, share):
    """Bit for bit against a sort of all slots a destination
    (`live_rows` as it was): `need` always; where it is within the
    capacity, which slots hold a row and the place each holds; every
    other slot holds a place in bounds."""
    rng = np.random.default_rng(slots + stacked)
    remote = rng.random(slots) < share
    dest = rng.integers(0, N_DEV, slots).astype(np.int32)
    remote &= dest != 3         # the device's own bucket stays empty
    places, oks, need = jax.jit(
        lambda r, d: E._bucket_places(r, d, N_DEV, capacity, stacked))(
        remote, dest)
    then = [tuple(np.asarray(x) for x in _live_rows_pr28(
        jnp.asarray(remote & (dest == d)), capacity, stacked))
        for d in range(N_DEV)]
    assert int(need) == max(int(need_d) for _at, _ok, need_d in then)
    assert (int(need) > capacity) == (share >= 0.2 and slots == 16384)
    for got, got_ok, (at, ok, _need_d) in zip(places, oks, then):
        got = np.asarray(got)
        assert ((got >= 0) & (got < slots)).all()
        if int(need) <= capacity:
            assert (np.asarray(got_ok) == ok).all()
            assert (got[ok] == at[ok]).all()


def test_a_colocated_exchange_sorts_its_slots_once():
    """What is traced: under the half-of-n rule ONE sort over a device's
    n slots, and one a destination over the slots it kept; over the
    rule one over all slots a destination.  `live_rows`, the compaction
    of every program that does not exchange (Q14's, Q19's and Q12's
    probe rows, the matched rows, a rows-returning root), traces what
    it traced before, word for word."""
    from tidb_tpu.copr.join import live_rows

    def buckets(slots, capacity):
        return _sorts(jax.make_jaxpr(lambda r, d: E._bucket_places(
            r, d, N_DEV, capacity, 2))(
            jnp.zeros(slots, bool), jnp.zeros(slots, jnp.int32)).jaxpr)
    rows = 16384 // COLS
    assert buckets(16384, 2048) == [(3 * 2048 // COLS, COLS)] * N_DEV \
        + [(rows, COLS)]
    # at three quarters of the slots the two forms cost the same: the
    # choice is made at a half
    assert buckets(16384, 16384 // 6 // COLS * COLS) \
        == [(3 * 2688 // COLS, COLS)] * N_DEV + [(rows, COLS)]
    assert buckets(16384, 2816) == [(rows, COLS)] * N_DEV
    for stacked in (1, 8):
        now, then = (str(jax.make_jaxpr(lambda s: fn(s, 1024, stacked))(
            jnp.zeros(8192, bool))) for fn in (live_rows, _live_rows_pr28))
        assert now == then


@pytest.mark.parametrize("n, n_dev, capacity, want", [
    (1 << 24, 4, 196_608, 1),       # `q3_x4`: 589,824 of 2^24 slots kept
    (655_360, 4, 16_384, 1),        # `q12_x4`, after its probe compaction
    (1 << 24, 4, 2_621_440, 1),     # keys that lie anywhere: 47 %
    (1 << 24, 4, 2_796_202, 1), (1 << 24, 4, 2_796_203, 4),
    (3072, 4, 1024, 4),             # the least bucket, a small table
    (1 << 24, 8, 196_608, 1), (1 << 20, 2, 1 << 19, 1),
    (1 << 20, 2, (1 << 19) + 128, 2)])
def test_the_form_is_chosen_from_two_shapes(n, n_dev, capacity, want):
    assert E.exchange_passes(n, n_dev, capacity) == want


# --------------------------------------------------------------------- #
# a sharded build side, made by the host
# --------------------------------------------------------------------- #

def _put(mesh):
    from tidb_tpu.parallel.mesh import sharded
    return lambda a: jax.device_put(a, sharded(mesh))


def test_a_table_s_rows_are_dealt_to_the_devices_that_own_their_keys():
    mesh = get_mesh(N_DEV)
    rng = np.random.default_rng(37)
    keys = np.sort(rng.choice(40_000, 6000, replace=False)).astype(np.int64)
    pay = rng.integers(-50, 50, 6000)
    valid = rng.random(6000) < 0.8
    # eight stripes dealt round robin over four devices, as a table's
    # shards are under its placement
    part, slots = JB.key_partition(
        [int(keys[750 * s]) for s in range(8)], [s % 4 for s in range(8)],
        N_DEV, int(keys.max()) + 1)
    cols = [(keys, np.ones(6000, bool)), (pay, valid)]
    side = JB.sharded_build(keys, cols, part, slots, _put(mesh), key_col=0)
    assert side.sharded and side.dense and side.unique and side.rows == 6000
    meta = np.asarray(side.aux[0][0])
    assert meta.tolist() == [[0, n] for n in slots]
    table = np.asarray(side.aux[2][0])
    assert table.shape[0] == 4 and (table & 1).sum() == 6000
    assert [(t & 1).sum() for t in table] == [1500] * 4
    assert np.asarray(side.aux[-1][0])[2].tolist() == part.tolist()
    # every key is found at its place, with its columns
    own, at = E.key_places(keys, part, np)
    _n, _p, layout = side.packing
    w, shift, bits, vbit, _wide = layout[1]
    word = np.asarray(side.aux[2 + w][0])[own, at]
    mins = np.asarray(side.aux[1][0])[0]
    assert (((word >> vbit) & 1).astype(bool) == valid).all()
    assert ((((word >> shift) & ((1 << bits) - 1)) + mins[1])[valid]
            == pay[valid]).all()
    # a key that comes twice, a table that does not fit: another plan
    twice = np.concatenate([keys, keys[:1]])
    assert JB.sharded_build(
        twice, [(twice, np.ones(6001, bool))], part, slots, _put(mesh),
        key_col=0) is None
    assert JB.sharded_build(keys, cols, part, slots, _put(mesh), key_col=0,
                            device_bytes=1 << 20) is None


# --------------------------------------------------------------------- #
# whole statements: the spec's text against the reference
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def tpch():
    """(domain, {class: (module, oracle state)}): CUSTOMER, ORDERS and
    LineItem from the benchmark's generators at the scale of
    tests/test_orderjoin.py, ANALYZEd as the configuration does, the
    engine pinned to the device path."""
    run_py = _bench("", "run")
    tables = {n: _bench("tables", n)
              for n in ("CUSTOMER", "ORDERS", "LineItem")}
    data = {n: t.generate(SCALE, SEED, list(t.TYPES))
            for n, t in tables.items()}
    dom = Domain()
    for n, t in tables.items():
        valid = np.ones(len(next(run_py._arrays(data[n]))), bool)
        cols = [run_py._column(t.TYPES[c], v, valid)
                for c, v in data[n].items()]
        info = TableInfo(t.NAME, list(data[n]), [c.dtype for c in cols])
        info.register_columns(cols)
        dom.catalog.create_table("test", info)
    sess = Session(dom)
    for n in tables:
        sess.execute(f"analyze table {n}")
    sess.execute("set global tidb_tpu_result_cache_entries = 0")
    sess.execute("set global tidb_tpu_trace_sample = 1")
    dom.client._platform = lambda: "tpu"
    classes = {}
    for name in ("q3", "q12"):
        mod = _bench("classes", name)
        classes[name] = (mod, mod.prepare(data))
    yield dom, classes
    _forget_programs()


@pytest.fixture
def past_the_cap(monkeypatch):
    """ORDERS past the broadcast cap, CUSTOMER under it, as at SF10."""
    monkeypatch.setattr(plan, "BROADCAST_BUILD_MAX_ROWS", CAP)


COUNTERS = ("join_launches", "join_direct_launches", "join_exchange_launches",
            "join_exchange_onepass_launches", "join_sharded_build_launches",
            "exchange_overflows", "join_host_fallbacks",
            "join_shuffle_launches",
            "join_compact_overflows", "join_window_overflows",
            "hndv_agg_regrows", "hndv_agg_launches", "rows_regrows",
            "group_topn_device_launches", "hndv_host_topn_launches")


def _run(dom, devices, fn):
    """fn(session) on a mesh of `devices`; -> (its result, the `/sched`
    counters it moved)."""
    mesh = dom.client.mesh
    dom.client.mesh = get_mesh(devices)
    sched = dom.client._scheduler()
    try:
        before = sched.stats()
        assert set(COUNTERS) <= set(before)
        out = fn(Session(dom))
        after = sched.stats()
    finally:
        dom.client.mesh = mesh
    return out, {k: after[k] - before[k] for k in COUNTERS}


@pytest.mark.parametrize("devices", [4, 1])
@pytest.mark.parametrize("name", ["q3", "q12"])
def test_spec_text_equals_the_reference(tpch, lowered_for, past_the_cap,
                                        name, devices):
    """Both spec texts with `orders` past the cap, on four devices and on
    one, every program lowered as for a TPU: the oracle's rows text for
    text (Q3's ten in its order).  On four devices every join launch
    against `orders` exchanges (form `probe_to_build`) and its build
    stays sharded; on one nothing is exchanged.  Q3's `orders` build is
    a join's result made into tables on the devices, never fetched: no
    host fallback, no repartition join; its groups are whole on a device
    (every row of an order travels to the order's owner), so each device
    ranks its own and the host merges ten rows a device."""
    dom, classes = tpch
    mod, state = classes[name]
    lowered_for("tpu")

    def statements(sess):
        rng = np.random.default_rng(35)
        for p in [mod.draw(rng) for _ in range(3)]:
            want = mod.answer(state, p)
            assert _text(sess.execute(mod.sql(p)).rows) == want, p
            launches = [a for a in _spans(sess, "sched.launch")
                        if a.get("build_sharded")]
            assert len(launches) == 1
            (a,) = launches
            assert a["join_form"] == "direct"
            if devices > 1:
                assert a["exchange"] == "probe_to_build" \
                    and a["exchange_capacity"] % D.COMPACT_COLUMNS == 0
                # Q3 exchanges a device's 32,768 scanned slots in
                # buckets of 2,048: one sort of them; Q12 the 3,072
                # slots it compacted its probe rows to, of which three
                # of the least bucket (1,024) are all: a sort a bucket
                assert a["exchange_passes"] == E.exchange_passes(
                    a.get("probe_capacity") or a["probe_rows"] // devices,
                    devices, a["exchange_capacity"]) \
                    == (1 if name == "q3" else devices)
                sent = [t["exchange_rows_sent"]
                        for t in _spans(sess, "cop.transfer")
                        if "exchange_rows_sent" in t]
                assert sent and sent[-1] <= a["exchange_capacity"] * devices
            else:
                assert "exchange" not in a and "exchange_passes" not in a
            builds = _spans(sess, "cop.join_build")
            assert [b for b in builds if b.get("sharded")] and all(
                b["form"] == "direct" for b in builds)
            assert {b["source"] for b in builds if b.get("sharded")} \
                == {"join" if name == "q3" else "table"}
            if name == "q3":
                assert a["group_topn"] == "device" and a["dependent_keys"] == 2
            said = [r[0] for r in sess.execute("explain " + mod.sql(p)).rows]
            (forms,) = [r for r in said if r.startswith("join forms")]
            assert "ORDERS.o_orderkey direct" in forms   # what ran, above
            if name == "q3":    # once a statement of the digest has run
                (agg,) = [r for r in said if r.startswith("agg strategy")]
                assert "ranked on the device" in agg
            (line,) = [r for r in said if r.startswith("join exchange")]
            assert "ORDERS.o_orderkey" in line and (
                "probe_to_build" in line and "stays sharded over 4" in line
                if devices > 1 else "nothing moves (one device)" in line)
    _none, moved = _run(dom, devices, statements)
    assert moved["join_direct_launches"] == moved["join_launches"] > 0
    assert moved["join_sharded_build_launches"] == 3
    assert moved["join_exchange_launches"] == (3 if devices > 1 else 0)
    assert moved["join_exchange_onepass_launches"] \
        == (3 if devices > 1 and name == "q3" else 0)
    assert not moved["join_host_fallbacks"] + moved["join_shuffle_launches"]
    if name == "q3":
        assert moved["group_topn_device_launches"] == 3 \
            and not moved["hndv_host_topn_launches"]


def test_q3_makes_its_sharded_build_anew_with_every_statement(
        tpch, lowered_for, past_the_cap):
    """A sharded build side that is a join's result carries the
    statement's parameters: different parameters in turn on four
    devices, each answer its own (no stale build), the build never kept
    (`cached` False, source `join`), made by a program that joins
    nothing from rows that never left their devices; Q12's, a resident
    table's rows, is dealt out once and kept with the snapshot."""
    dom, classes = tpch
    lowered_for("tpu")

    def statements(sess):
        mod, state = classes["q3"]
        rng = np.random.default_rng(36)
        seen = set()
        for p in [mod.draw(rng) for _ in range(4)] * 2:
            assert _text(sess.execute(mod.sql(p)).rows) \
                == mod.answer(state, p), p
            seen.add((p["segment"], p["day"]))
            (b,) = [b for b in _spans(sess, "cop.join_build")
                    if b.get("sharded")]
            assert (b["source"], b["cached"], b["form"]) \
                == ("join", False, "direct") and b["rows"] > 0
            names = [a["program"] for a in _spans(sess, "sched.launch")]
            assert any(n.startswith("cop_table_rows_") for n in names)
        assert len(seen) >= 3
        mod, state = classes["q12"]
        kept = []
        for p in [mod.draw(rng) for _ in range(3)]:
            assert _text(sess.execute(mod.sql(p)).rows) \
                == mod.answer(state, p), p
            (b,) = _spans(sess, "cop.join_build")
            assert b["sharded"] and b["source"] == "table"
            kept.append(b["cached"])
        assert kept[1:] == [True, True]
    _none, moved = _run(dom, 4, statements)
    assert not moved["join_host_fallbacks"] + moved["join_shuffle_launches"] \
        + moved["exchange_overflows"]


# --------------------------------------------------------------------- #
# edge cases in small, against numpy: a fact table in no order probing a
# header stored by its key
# --------------------------------------------------------------------- #

ROWS, HEADS = 8 * 8192, 6000


@pytest.fixture(scope="module")
def star():
    """`fact` (k nullable, a, v): keys in no order, some outside every
    header's; `head` (k, g, c) stored by its key, the first 8 keys of
    every 32; `cust` (c, seg); `tiny` (k, g, c), three rows (five of its
    eight shards are empty); `none`, no row.  ANALYZEd."""
    from tidb_tpu.chunk.column import Column
    from tidb_tpu.types import dtypes as dt
    rng = np.random.default_rng(35)
    k = rng.integers(-50, 25_000, ROWS)
    kvalid = rng.random(ROWS) > 0.05
    a = rng.integers(0, 1000, ROWS)
    v = rng.permutation(ROWS) - ROWS // 2
    hk = np.array([32 * (i // 8) + i % 8 for i in range(HEADS)])
    head = np.stack([hk, hk // 32, hk % 7], axis=1)
    dom = Domain()

    def table(name, names, arrays, valid=None):
        cols = [Column(dt.bigint(valid is not None and i == 0),
                       x.astype(np.int64),
                       valid if valid is not None and i == 0
                       else np.ones(len(x), bool))
                for i, x in enumerate(arrays)]
        info = TableInfo(name, names, [c.dtype for c in cols])
        info.register_columns(cols)
        dom.catalog.create_table("test", info)
    table("fact", ["k", "a", "v"], [k, a, v], kvalid)
    table("head", ["k", "g", "c"], list(head.T))
    table("tiny", ["k", "g", "c"], list(head[[5, 900, 4000]].T))
    s = Session(dom)
    s.execute("create table cust (c bigint, seg bigint)")
    s.execute("insert into cust values "
              + ", ".join(str((c, c % 3)) for c in range(7)))
    s.execute("create table none (k bigint, g bigint, c bigint)")
    for t in ("fact", "head", "cust", "tiny"):
        s.execute(f"analyze table {t}")
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    s.execute("set global tidb_tpu_trace_sample = 1")
    dom.client._platform = lambda: "tpu"
    yield dom, (k, kvalid, a, v), head
    _forget_programs()


def _joined(fact, head, keep_head=None, a_min=300):
    """(fact row, head row) pairs of the inner join, by a dict."""
    k, kvalid, a, _v = fact
    rows = {int(h[0]): j for j, h in enumerate(head)
            if keep_head is None or keep_head(h)}
    return [(i, rows[int(k[i])]) for i in np.nonzero(kvalid & (a >= a_min))[0]
            if int(k[i]) in rows]


@pytest.mark.parametrize("cap", [1000, 2])
def test_the_shards_parts_add_up_to_the_one_device_answer(
        star, lowered_for, monkeypatch, cap):
    """A scalar aggregate above the exchanged join: four devices' parts
    (a `psum`) are the one device's answer and numpy's, with NULL probe
    keys, probe keys below, between and above every build key, and (cap
    2) a build of three rows of whose shards five are empty."""
    dom, fact, head = star
    lowered_for("tpu")
    monkeypatch.setattr(plan, "BROADCAST_BUILD_MAX_ROWS", cap)
    build, rows = ("head", head) if cap == 1000 \
        else ("tiny", head[[5, 900, 4000]])
    sql = (f"select count(*), sum(v), sum(g) from fact, {build} "
           f"where fact.k = {build}.k and a >= 300")
    pairs = _joined(fact, rows)
    want = [(len(pairs), sum(int(fact[3][i]) for i, _j in pairs),
             sum(int(rows[j][1]) for _i, j in pairs))]
    assert want[0][0] > 0
    for devices in (1, 4):
        got, moved = _run(dom, devices, lambda s: (
            s.execute(sql).rows, _spans(s, "sched.launch")))
        assert [tuple(int(x) for x in r) for r in got[0]] == want, devices
        (a,) = [a for a in got[1] if a.get("build_sharded")]
        assert ("exchange" in a) == (devices > 1)
        assert moved["join_exchange_launches"] == (devices > 1)
        assert not moved["join_host_fallbacks"] \
            + moved["join_shuffle_launches"]


def test_a_left_join_keeps_null_and_unowned_probe_keys(star, lowered_for,
                                                       monkeypatch):
    """LEFT join across the exchange: a NULL probe key stays where it was
    scanned, a key no build row holds travels to its owner and finds
    nothing there; both come out once, with NULL build columns."""
    dom, fact, head = star
    lowered_for("tpu")
    monkeypatch.setattr(plan, "BROADCAST_BUILD_MAX_ROWS", 1000)
    k, kvalid, a, v = fact
    sql = ("select count(*), count(g), sum(v), sum(g) from fact left join "
           "head on fact.k = head.k where a >= 900")
    keep = a >= 900
    pairs = _joined(fact, head, a_min=900)
    want = (int(keep.sum()), len(pairs), int(v[keep].sum()),
            sum(int(head[j][1]) for _i, j in pairs))
    for devices in (1, 4):
        got, moved = _run(dom, devices, lambda s: s.execute(sql).rows)
        assert tuple(int(x) for x in got[0]) == want, devices
        assert not moved["join_host_fallbacks"]


@pytest.mark.parametrize("seg,below", [(0, 20_000), (1, 9_000), (2, 20_000),
                                       (1, 0)])
def test_the_group_by_and_the_rank_above_the_exchange(
        star, lowered_for, monkeypatch, seg, below):
    """Q3's shape in small on four devices: the build is `head` joined
    to a segment of `cust` on every shard (a sharded build that is a
    join's result, its parameters changing from statement to statement),
    the GROUP BY has the probe key and a key that depends on it: every
    row of a key lies on the key's owner after the exchange, so each
    device ranks its own groups and the host merges four ten-row
    tables.  An empty build (nothing below 0) is the host fallback's and
    is counted."""
    dom, fact, head = star
    lowered_for("tpu")
    monkeypatch.setattr(plan, "BROADCAST_BUILD_MAX_ROWS", 1000)
    sql = ("select fact.k, sum(v), g from cust, head, fact "
           f"where seg = {seg} and cust.c = head.c and fact.k = head.k "
           f"and head.k < {below} and a >= 300 "
           "group by fact.k, g order by 2 desc, 1 limit 10")
    groups: dict = {}
    for i, j in _joined(fact, head,
                        lambda h: (h[2] % 3) == seg and h[0] < below):
        key = (int(fact[0][i]), int(head[j][1]))
        groups[key] = groups.get(key, 0) + int(fact[3][i])
    want = sorted(((k, s, g) for (k, g), s in groups.items()),
                  key=lambda r: (-r[1], r[0]))[:10]

    def statement(sess):
        rows = sess.execute(sql).rows
        return rows, _spans(sess, "sched.launch"), \
            _spans(sess, "cop.join_build")
    (rows, launches, builds), moved = _run(dom, 4, statement)
    assert [tuple(int(x) for x in r) for r in rows] == want
    assert moved["join_host_fallbacks"] == (below == 0)
    if below:
        assert len(want) == 10
        (grouped,) = [a for a in launches if "agg_strategy" in a]
        assert grouped["exchange"] == "probe_to_build" \
            and grouped["group_topn"] == "device" \
            and grouped["dependent_keys"] == 1
        assert [b["source"] for b in builds if b.get("sharded")] == ["join"]
        assert moved["group_topn_device_launches"] == 1 \
            and not moved["hndv_host_topn_launches"]


def test_a_bucket_that_overflows_costs_one_rerun_and_is_remembered(
        star, lowered_for, monkeypatch):
    """The bucket's capacity is a guess: where a device has more rows for
    one destination the statement is rerun with what the devices found
    (`exchange_overflows`), the answer is exact, and the next statement
    of the digest starts from the finding."""
    dom, fact, head = star
    lowered_for("tpu")
    monkeypatch.setattr(plan, "BROADCAST_BUILD_MAX_ROWS", 1000)
    monkeypatch.setattr(D, "exchange_capacity_for",
                        lambda est, n_dev, colocated: 1024 if n_dev > 1
                        else 0)
    sql = ("select count(*), sum(v) from fact, head "
           "where fact.k = head.k and a >= 100")
    pairs = _joined(fact, head, a_min=100)
    want = (len(pairs), sum(int(fact[3][i]) for i, _j in pairs))

    def twice(sess):
        out = []
        for _ in range(2):
            rows = sess.execute(sql).rows
            launches = [a for a in _spans(sess, "sched.launch")
                        if "exchange" in a]
            out.append((tuple(int(x) for x in rows[0]),
                        [a["exchange_capacity"] for a in launches],
                        [a["exchange_passes"] for a in launches]))
        return out
    (first, second), moved = _run(dom, 4, twice)
    assert first[0] == want and second[0] == want
    assert first[1][0] == 1024 and len(first[1]) == 2 \
        and first[1][1] > 1024
    assert second[1] == [first[1][1]]
    assert moved["exchange_overflows"] == 1
    # the guess is under the half-of-n rule (3 x 1,024 of 16,384 slots),
    # what the devices found is over it: each run in its own form
    assert first[2] == [1, 4] and second[2] == [4]
    assert (moved["join_exchange_launches"],
            moved["join_exchange_onepass_launches"]) == (3, 1)


def test_the_exchange_s_buffers_are_what_the_trace_records():
    """`record_exchange`: one record an exchange traced, the bytes of its
    send buffers (a device's buckets: words and a live bit a slot), the
    seam tests/test_shardflow.py pins the old form's prediction on."""
    records = E.record_exchange(True)
    try:
        rng = np.random.default_rng(38)
        vals = rng.integers(0, 2 ** 31, (N_DEV, SLOTS))
        _exchange(vals, None, np.ones((N_DEV, SLOTS), bool),
                  rng.integers(0, N_DEV, (N_DEV, SLOTS)).astype(np.int32),
                  512)
    finally:
        E.record_exchange(False)
    # an int64 and an int32 column: three words a row
    assert (N_DEV, 512, N_DEV * 512 * (4 * 3 + 1)) in records


def test_the_new_facts_are_rows_of_the_table():
    assert {"exchange", "exchange_capacity", "exchange_passes",
            "build_sharded"} <= set(F.FACTS)
    assert {"join_exchange_launches", "join_exchange_onepass_launches",
            "join_sharded_build_launches", "exchange_overflows",
            "join_shuffle_launches"} <= set(F.counter_names())
    said = {"exchange": "probe_to_build", "exchange_capacity": 4096,
            "build_sharded": 1, "exchange_passes": 4}
    assert set(F.counters(said)) == {"join_exchange_launches",
                                     "join_sharded_build_launches"}
    assert F.span_attrs(said) == said
    assert set(F.counters({**said, "exchange_passes": 1})) == {
        "join_exchange_launches", "join_exchange_onepass_launches",
        "join_sharded_build_launches"}
    fields = {f.name: f for f in dataclasses.fields(D.LookupJoin)}
    for name in ("sharded", "exchange"):
        assert fields[name].metadata == D.DIGEST_IF_SET


def test_the_table_is_sized_by_the_columns_the_statement_reads(
        tpch, lowered_for, past_the_cap, monkeypatch):
    """Q3's build comes with six columns (four of `orders`, the two
    customer keys it was joined on) of which the statement reads two: a
    device on which a table of all six would pass 1/64 of its memory and
    a table of the two does not still plans and runs the sharded build
    (TPC-H SF10 on a v5e: 4 x 15M slots x 5 words is 300 MB of the 256
    that are allowed, 2 words 120)."""
    from tidb_tpu.executor import physical
    dom, classes = tpch
    mod, state = classes["q3"]
    lowered_for("tpu")
    lo, hi = dom.catalog.get_table("test", "ORDERS").snapshot().key_range(0)
    share = -(-(hi - lo + 1) // 4)
    memory = 64 * 4 * share * 3         # three words fit, five do not
    monkeypatch.setattr(physical, "_device_bytes", lambda _mesh: memory)
    assert plan.which_side_moves(40_000, hi - lo + 1, 5, 4, True, True,
                                 False, memory, CAP) == plan.HOST

    def statement(sess):
        p = mod.draw(np.random.default_rng(37))
        assert _text(sess.execute(mod.sql(p)).rows) == mod.answer(state, p)
        return [b for b in _spans(sess, "cop.join_build")
                if b.get("sharded")]
    built, moved = _run(dom, 4, statement)
    assert [b["source"] for b in built] == ["join"]
    assert not moved["join_host_fallbacks"]
