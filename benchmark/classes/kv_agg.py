"""Count and sum over the small table that was written over the wire:
wire, KV store, lazy columnarize.  ``chip_smoke.py``'s small statement; an
assumption of this benchmark, listed in the configuration.  GRP is 1..9.

The oracle is given the rows whose INSERTs were acknowledged, so an
acknowledged write that cannot be read back is a wrong answer."""

from __future__ import annotations

from harness import exact

NAME = "kv_agg"
POOL = 4
ORDERED = True
READS = {"bench_kv": ["grp", "v"]}


def draw(rng) -> dict:
    return {"grp": int(rng.integers(1, 10))}


def sql(p: dict) -> str:
    return f"select count(*), sum(v) from bench_kv where grp < {p['grp']}"


def prepare(data: dict):
    return data["bench_kv"]["grp"], data["bench_kv"]["v"]


def answer(state, p: dict) -> list[tuple]:
    grp, v = state
    m = grp < p["grp"]
    # SUM over no rows is NULL
    return [(str(int(m.sum())), str(int(v[m].sum())) if m.any() else None)]


def bytes_read(rows: dict, width: dict) -> int:
    return exact.scan_bytes(READS, rows, width)
