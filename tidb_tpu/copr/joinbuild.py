"""Host half of the broadcast lookup join: a build side's keys and columns
-> the aux group the device program takes, in the form the keys allow
(`dag.LookupJoin` describes both forms; `copr/join.py` has the device
half that probes them).

Reference analog: the build phase of the hash join (hash_join_v2.go
build workers).  A hash table is hostile to a TPU; what the build side IS
decides instead: unique keys over a range little longer than their count
(every TPC-H primary key) are addressed directly, `key - base` being the
build row, with every column that fits packed into one int32 word, so
that ONE gather a probe row fetches the whole build row (an XLA gather on
a v5e costs about 7 ns a row whatever it fetches: PERF.md, PR 25); other
keys are sorted and binary-searched; duplicate keys make the join an
expanding one.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

# direct addressing (dag.LookupJoin `dense`): usable bits of one int32
# word (the sign bit stays clear, so an arithmetic shift is a logical
# one), and how sparse a key range may be before the sorted form is the
# smaller table
WORD_BITS = 31
DENSE_MAX_SPAN = 1 << 24
DENSE_MAX_SPREAD = 4
DENSE_MIN_SPAN = 1 << 16
_I32 = np.iinfo(np.int32)
# a build column's `word` in the packing where it rides in no word
APART = -1          # gathered by itself: a float, or wider than a word
KEY_ITSELF = -2     # the build key: a matched row's probe key is it


@dataclass
class BuildSide:
    """One broadcast-join build side as the device program takes it: the
    aux group, and the strategy fields `dag.LookupJoin` has to carry for
    a program traced against that group."""
    aux: tuple
    rows: int
    unique: bool
    n_unique: int
    dense: bool = False
    packing: tuple = ()

    @property
    def avg_dup(self) -> float:
        return self.rows / max(self.n_unique, 1)


def _fits_i32(lo: int, hi: int) -> bool:
    return _I32.min <= lo and hi <= _I32.max


def prepare_build(keys: np.ndarray, cols: list, dense_ok: bool = True,
                  key_col: int = -1) -> BuildSide:
    """Host half of the lookup join: build keys (int64, NULLs already
    dropped, at least one) and the row-aligned build columns [(data,
    validity)] -> the aux group in the form the keys allow.

    Unique keys over a range at most DENSE_MAX_SPREAD times their count
    are addressed directly (`_dense_group`); anything else is sorted and
    binary-searched, and duplicate keys make the join an expanding one
    (`unique` False: the caller switches the DAG with to_multimatch).
    Keys that fit int32 go up as int32: the program compares at that
    width where the probe key is read as narrowly, and widens them where
    it is not (an int64 lane is two emulated 32-bit lanes on a TPU).
    `dense_ok` False keeps the sorted form (semi/anti joins read no
    build column).  `key_col`: which of `cols` the keys were taken from,
    if any: where its values ARE the keys a direct-addressed side does
    not carry it, the probe key of a matched row is the same number."""
    n = len(keys)
    lo, hi = int(keys.min()), int(keys.max())
    span = hi - lo + 1
    if span <= max(DENSE_MAX_SPREAD * n, DENSE_MIN_SPAN) \
            and span <= DENSE_MAX_SPAN:
        pos = (keys - lo).astype(np.intp)
        n_unique = int(np.count_nonzero(np.bincount(pos, minlength=span)))
        if n_unique == n and dense_ok:
            return _dense_group(pos, lo, span, cols, key_col, keys)
    else:
        n_unique = len(np.unique(keys))
    kdt = np.int32 if _fits_i32(lo, hi) else np.int64
    order = np.argsort(keys, kind="stable")
    aux = [(jnp.asarray(keys[order].astype(kdt)), None),
           (jnp.asarray(np.arange(n, dtype=np.int32)), None)]
    for data, valid in cols:
        aux.append((jnp.asarray(data[order]),
                    None if valid.all() else jnp.asarray(valid[order])))
    return BuildSide(tuple(aux), n, n_unique == n, n_unique)


def _dense_group(pos, lo: int, span: int, cols: list, key_col: int,
                 keys) -> BuildSide:
    """Scatter the build columns over the key range and pack those that
    fit into int32 words (dag.LookupJoin has the layout)."""
    n = len(pos)
    room: list = []                 # free bits of each word
    pbit = -1
    if n != span:                   # holes: one presence bit
        room.append(WORD_BITS - 1)
        pbit = 0
    layout, mins, apart = [], [], []
    for j, (data, valid) in enumerate(cols):
        all_valid = bool(valid.all())
        if j == key_col and all_valid and data.dtype.kind in "iu" \
                and np.array_equal(data, keys):
            layout.append((KEY_ITSELF, 0, 0, -1,
                           not _fits_i32(lo, lo + span)))
            mins.append(0)
            continue
        packable = data.dtype.kind in "ib" or (
            data.dtype.kind == "u" and data.dtype.itemsize < 8)
        vmin = vmax = 0
        if packable and valid.any():
            live = data if all_valid else data[valid]
            vmin, vmax = int(live.min()), int(live.max())
        bits = (vmax - vmin).bit_length()
        need = bits + (0 if all_valid else 1)
        if not packable or need > WORD_BITS:
            table = np.zeros(span, data.dtype)
            table[pos] = data
            vtab = None
            if not all_valid:
                vtab = np.zeros(span, bool)
                vtab[pos] = valid
            apart.append((table, vtab))
            layout.append((APART, 0, 0, -1, True))
            mins.append(0)
            continue
        w = next((i for i, r in enumerate(room) if r >= need), len(room))
        if w == len(room):
            room.append(WORD_BITS)
        shift = WORD_BITS - room[w]
        room[w] -= need
        vbit = -1 if all_valid else shift + bits
        layout.append((w, shift, bits, vbit, not _fits_i32(vmin, vmax)))
        mins.append(vmin)
    words = [np.zeros(span, np.int64) for _ in room]
    if pbit >= 0:
        words[0][pos] = 1
    for (w, shift, bits, vbit, _wide), vmin, (data, valid) in zip(
            layout, mins, cols):
        if w < 0:
            continue
        if bits:
            v = data.astype(np.int64) - vmin
            words[w][pos] |= (v if vbit < 0 else np.where(valid, v, 0)) << shift
        if vbit >= 0:
            words[w][pos] |= valid.astype(np.int64) << vbit
    kdt = np.int32 if _fits_i32(lo, lo + span) else np.int64
    aux = [(jnp.asarray(np.array([lo, span], kdt)), None),
           (jnp.asarray(np.array(mins, np.int64).reshape(len(cols))), None)]
    aux += [(jnp.asarray(w.astype(np.int32)), None) for w in words]
    aux += [(jnp.asarray(t), None if v is None else jnp.asarray(v))
            for t, v in apart]
    return BuildSide(tuple(aux), n, True, n, dense=True,
                     packing=(len(words), pbit, tuple(layout)))


def build_rows(node, grp) -> int:
    """Rows (slots, for a direct-addressed side) of the build side a
    launch carries in `grp` for the LookupJoin `node`."""
    if not node.dense:
        return int(grp[0][0].shape[0])
    return int(grp[2][0].shape[0]) if len(grp) > 2 else 0


__all__ = ["BuildSide", "prepare_build", "build_rows", "WORD_BITS", "APART",
           "KEY_ITSELF"]
