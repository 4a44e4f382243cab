"""copforge key derivation: restart-stable program variant keys.

Reference analog: the digest-keyed persisted-executable pattern of
compiler-first serving engines (PAPERS.md: Flare keeps compilation off
the hot path; the O(1)-caching inference stack keys persisted
executables by a content digest).  ``copr.dag.dag_digest`` is ``hash()``
of a frozen dataclass tree — perfect for the in-process jit cache, but
Python salts string hashes per process, so that digest DIES with the
process.  A compiled executable persisted across restarts needs a key
every field of which is derivable from content alone.

This module lives next to ``lifetime.py`` deliberately: the DonationPlan
is part of the variant key BY CONSTRUCTION (``variant_key`` derives the
donation signature itself from the dag + program shape), so a donating
and a non-donating build of the same plan can never collide in the
persistent cache — jax bakes input aliasing into the executable, and
loading the wrong variant would delete the caller's arrays.

Key anatomy (every part checked again at load time — a stale or
mismatched entry is rejected, never silently deserialized):

- ``digest``        restart-stable sha256 of the canonical dag encoding
- ``family``        same, with regrow capacities (group_capacity /
                    join out_capacity) zeroed — the warm
                    pool's capacity-reuse index
- ``mesh_fp``       axis names + shape + device ids (sched/task
                    fingerprint, hashed)
- ``capacity_sig``  program shape class: builder kind, row capacity,
                    batch slot count
- ``donation_sig``  DonationPlan slot classes + donate_argnums actually
                    baked into the executable
- ``backend_fp``    jax/jaxlib versions + platform + device kind +
                    device count (an XLA upgrade invalidates everything)
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import hashlib
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from ..copr import dag as D

# fields that only size regrow loops: two dags differing ONLY here run
# the same plan family, so the client's paging/regrow re-entry can round
# up to a capacity the warm pool already holds
_CAPACITY_FIELDS = ("group_capacity", "out_capacity")


def _encode(obj, h, skip_capacity: bool) -> None:
    """Feed one canonical byte stream per value into hasher ``h``.
    Deterministic across processes: no ``id()``, no ``hash()``, no
    unsorted dict iteration — the TPU-DIGEST discipline."""
    if obj is None:
        h.update(b"N")
    elif isinstance(obj, bool):
        h.update(b"b1" if obj else b"b0")
    elif isinstance(obj, int):
        h.update(b"i" + str(obj).encode())
    elif isinstance(obj, float):
        h.update(b"f" + repr(obj).encode())
    elif isinstance(obj, str):
        h.update(b"s" + obj.encode("utf-8", "surrogatepass"))
    elif isinstance(obj, bytes):
        h.update(b"y" + obj)
    elif isinstance(obj, enum.Enum):
        h.update(b"e" + type(obj).__name__.encode())
        _encode(obj.value, h, skip_capacity)
    elif isinstance(obj, np.ndarray):
        h.update(b"a" + str(obj.shape).encode() + obj.dtype.str.encode())
        h.update(np.ascontiguousarray(obj).tobytes())
    elif isinstance(obj, np.generic):
        h.update(b"g" + obj.dtype.str.encode() + obj.tobytes())
    elif isinstance(obj, (tuple, list)):
        h.update(b"t" + str(len(obj)).encode())
        for v in obj:
            _encode(v, h, skip_capacity)
    elif isinstance(obj, (set, frozenset)):
        h.update(b"S")
        for v in sorted(repr(x) for x in obj):
            h.update(v.encode())
    elif dataclasses.is_dataclass(obj):
        h.update(b"d" + type(obj).__name__.encode())
        for f in dataclasses.fields(obj):
            if skip_capacity and f.name in _CAPACITY_FIELDS:
                continue
            if f.metadata.get("digest") == "if_set" \
                    and getattr(obj, f.name) == f.default:
                continue    # dag.DIGEST_IF_SET: older programs keep names
            h.update(b"." + f.name.encode())
            _encode(getattr(obj, f.name), h, skip_capacity)
    else:
        # last resort (plain value objects): repr is assumed canonical
        h.update(b"r" + repr(obj).encode())


@functools.lru_cache(maxsize=2048)
def stable_digest(dag: D.CopNode) -> str:
    """Restart-stable content digest of a cop DAG (hex, 16 chars) —
    the persistent twin of ``copr.dag.dag_digest``."""
    h = hashlib.sha256()
    _encode(dag, h, skip_capacity=False)
    return h.hexdigest()[:16]


def _root_tag(dag) -> str:
    """The root node's kind and strategy, as a program name spells it;
    ``join_`` first where the program holds a LookupJoin, so that a
    join-carrying scalar aggregate (TPC-H Q14, Q19) and Q6 are two names
    in a trace."""
    if D.lookup_joins(dag):
        return "join_" + _plain_root_tag(dag)
    return _plain_root_tag(dag)


def _plain_root_tag(dag) -> str:
    if isinstance(dag, D.FusedDag):
        return f"x{len(dag.members)}"
    if isinstance(dag, D.ShuffleJoinSpec):
        return _plain_root_tag(dag.top)
    if isinstance(dag, D.Aggregation):
        return f"agg_{dag.strategy.value}"
    return {D.TopN: "topn", D.Limit: "limit",
            D.WindowShuffleSpec: "window"}.get(type(dag), "rows")


def program_name(program: str, dag) -> str:
    """``cop_<program>_<root>_<d12>`` (``cop_<program>_join_<root>_<d12>``
    with a lookup join inside): the name a device program is jitted under, hence its module's name in a profiler trace
    (``jit_cop_solo_agg_scalar_<d12>``) and part of what JAX's persistent
    cache keys on.  ``program`` is the builder (``solo``, ``fused``,
    ``batched``, ``shuffle``, ...), ``d12`` twelve hex digits of the
    restart-stable digest: the same DAG is named the same in every
    process, which ``hash()``/``dag_digest`` would not give."""
    return (f"cop_{program.replace('-', '_')}_{_root_tag(dag)}_"
            f"{stable_digest(dag)[:12]}")


def named_jit(fn, program: str, dag, **jit_kwargs):
    """``jax.jit`` of a device program under ``program_name`` (read it
    back as ``jitted.__name__``).  Every builder jits through here, so
    no module in a profiler trace is an anonymous ``jit__device_fn``."""
    import jax

    def device_program(*args):
        return fn(*args)
    device_program.__name__ = device_program.__qualname__ = \
        program_name(program, dag)
    return jax.jit(device_program, **jit_kwargs)


@functools.lru_cache(maxsize=2048)
def family_digest(dag: D.CopNode) -> str:
    """Digest with regrow capacities zeroed: every capacity variant of
    one plan shares a family, so the client can prefer a capacity the
    warm pool already compiled over the minimal pow2 regrow step."""
    h = hashlib.sha256()
    _encode(dag, h, skip_capacity=True)
    return h.hexdigest()[:16]


def mesh_fingerprint_hex(mesh) -> str:
    """Hashed form of the sched/task mesh fingerprint (axis names +
    shape + global device ids) — two Mesh objects over the same chips
    fingerprint identically across rebuilds AND restarts."""
    if mesh is None:
        return "nomesh"
    fp = (tuple(mesh.axis_names), tuple(mesh.devices.shape),
          tuple(int(d.id) for d in mesh.devices.reshape(-1)))
    return hashlib.sha256(repr(fp).encode()).hexdigest()[:16]


def backend_fingerprint(mesh=None) -> str:
    """jax/jaxlib versions + platform + device kind + device count: an
    XLA or topology change invalidates every persisted executable."""
    import jax
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", "?")
    except ImportError:       # pragma: no cover - jaxlib rides jax
        jl = "?"
    devs = (mesh.devices.reshape(-1) if mesh is not None
            else np.array(jax.devices()).reshape(-1))
    d0 = devs[0]
    return "/".join((jax.__version__, jl, d0.platform,
                     str(getattr(d0, "device_kind", "")), str(len(devs))))


@dataclass(frozen=True)
class CompileKey:
    """Builder-level variant key of one cacheable device program.  The
    per-call input shapes are appended by the cache (``entry_hex``), so
    one key covers every shape the builder is invoked with."""
    digest: str          # stable dag digest
    family: str          # capacity-stripped digest (warm-capacity index)
    mesh_fp: str
    capacity_sig: str    # program kind / row capacity / slot count
    donation_sig: str    # DonationPlan classes + baked donate_argnums
    backend_fp: str
    capacity: int = 0    # regrow knob value (family capacity index)

    def parts(self) -> dict:
        """Header fields re-verified at load time — the digest +
        mesh-fingerprint + donation-plan triple the TPU-COMPILE-KEY
        gate rule requires every cache write to carry."""
        return {"digest": self.digest, "family": self.family,
                "mesh_fp": self.mesh_fp,
                "capacity_sig": self.capacity_sig,
                "donation_sig": self.donation_sig,
                "backend_fp": self.backend_fp,
                "capacity": self.capacity}

    def entry_hex(self, shape_sig: str) -> str:
        """Identity of ONE compiled executable: the variant key plus the
        concrete call signature (leaf shapes/dtypes + pytree structure)."""
        h = hashlib.sha256()
        for part in (self.digest, self.family, self.mesh_fp,
                     self.capacity_sig, self.donation_sig,
                     self.backend_fp, shape_sig):
            h.update(part.encode())
            h.update(b"|")
        return h.hexdigest()[:32]


def shape_signature(args) -> str:
    """Canonical call signature: pytree structure + per-leaf
    (shape, dtype, weak_type).  Shardings are deliberately excluded —
    a Compiled executable accepts matching avals whatever the arrays'
    placement, and the cache falls back to the jit path on the rare
    backend that refuses."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = [str(treedef)]
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            arr = np.asarray(leaf)
            shape, dt_, weak = arr.shape, arr.dtype.str, True
        else:
            dt_ = str(getattr(leaf, "dtype", ""))
            weak = bool(getattr(leaf, "weak_type", False))
        sig.append(f"{tuple(shape)}:{dt_}:{int(weak)}")
    return ";".join(sig)


def variant_key(dag: D.CopNode, mesh, program: str,
                row_capacity: int = 0, n_slots: int = 0,
                donate_argnums: Tuple[int, ...] = (),
                extra: Tuple = (),
                n_devices: Optional[int] = None) -> CompileKey:
    """Derive the persistent variant key of one spmd builder.  The
    donation signature comes from the DAG's own DonationPlan — callers
    cannot omit it, so the donating variant keys apart by construction.
    ``extra`` carries builder knobs outside the dag (fused-rows member
    capacities)."""
    from .lifetime import donation_plan
    plan = donation_plan(dag, program)
    donation_sig = (f"{plan.describe()}|argnums="
                    f"{tuple(int(a) for a in donate_argnums)}")
    if isinstance(dag, D.Aggregation):
        capacity = dag.group_capacity or 0
    elif isinstance(dag, D.FusedDag):
        capacity = 0
    else:
        capacity = int(row_capacity)
    cap_sig = (f"{program}/rc={int(row_capacity)}/k={int(n_slots)}"
               f"/x={tuple(extra)}")
    mesh_fp = (mesh_fingerprint_hex(mesh) if mesh is not None
               else f"plan/{n_devices or 0}")
    backend = (backend_fingerprint(mesh) if mesh is not None
               else f"plan/{n_devices or 0}")
    return CompileKey(digest=stable_digest(dag), family=family_digest(dag),
                      mesh_fp=mesh_fp, capacity_sig=cap_sig,
                      donation_sig=donation_sig, backend_fp=backend,
                      capacity=capacity)


# ------------------------------------------------------------------ #
# gate report (--cache-report)
# ------------------------------------------------------------------ #

def cache_report(plans, n_devices: int = 8) -> str:
    """Per-corpus-query key/variant/bytes table: what the compile cache
    would key each device program on, from built plans alone (no trace,
    no device).  Rides ``python -m tidb_tpu.analysis --cache-report``."""
    from .copcost import format_bytes, plan_cost
    from .lifetime import _plan_cop_ops
    lines = [f"{'query':<40} {'digest':>16} {'family':>16} "
             f"{'variant':>24} {'bytes':>10}"]
    keyed = 0
    for idx, (sql, phys) in enumerate(plans):
        one_line = " ".join(sql.split())
        label = f"q{idx:02d} {one_line[:35]}"
        ops = _plan_cop_ops(phys)
        cost = plan_cost(phys, n_devices)
        if not ops:
            lines.append(f"{label:<40} {'-':>16} {'-':>16} "
                         f"{'host-only':>24} {'-':>10}")
            continue
        for _op, dag in ops:
            from .lifetime import donation_plan
            plan = donation_plan(dag, "solo")
            key = variant_key(dag, None, "solo", n_devices=n_devices,
                              donate_argnums=plan.donate_argnums)
            keyed += 1
            variant = f"solo cap={key.capacity} don={len(plan.donate_argnums)}"
            lines.append(
                f"{label:<40} {key.digest:>16} {key.family:>16} "
                f"{variant:>24} {format_bytes(cost.peak_hbm_bytes):>10}")
            label = ""
    lines.append(f"compile keys: {keyed} device programs keyed over "
                 f"{len(plans)} corpus plans")
    return "\n".join(lines)


__all__ = ["CompileKey", "stable_digest", "family_digest",
           "mesh_fingerprint_hex", "backend_fingerprint",
           "shape_signature", "variant_key", "cache_report"]
