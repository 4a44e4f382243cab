"""Device mesh management.

Reference analog: the TiKV store topology + region placement that
pkg/store/copr fans cop tasks out over.  On TPU the "cluster" is a
jax.sharding.Mesh; shards (region analogs) are assigned to devices by
position along the 'shard' axis, and the fan-out (copr worker pool) becomes
one SPMD program (SURVEY.md §2.10 P1).

The mesh is 1-D for the data-parallel scan path; MPP-style repartition
joins reuse the same axis with all_to_all (P7).  Multi-host: jax.devices()
spans all hosts under jax.distributed, so the same code scales from one
chip to a pod — DCN only carries control traffic, ICI the collectives.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the canonical axis name lives with the typed-link topology model
# (parallel/topology, jax-free) so the static analyses and the traced
# programs share one symbol — TPU-SHARD-CONST lints string literals
from .topology import SHARD_AXIS

_log = logging.getLogger(__name__)


def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@functools.lru_cache(maxsize=8)
def get_mesh(n_devices: Optional[int] = None) -> Mesh:
    """The mesh over every local device (or the first ``n_devices``).
    Resolution is lazy (first device dispatch) and says what it found:
    serving entry points run with INFO logging on, so a process that
    meant to hold a TPU and resolved CPU devices shows it here."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    _log.info("mesh resolved: platform=%s device_kind=%s devices=%d",
              devs[0].platform, devs[0].device_kind, len(devs))
    return Mesh(np.array(devs), (SHARD_AXIS,))


def mesh_platform(mesh: Mesh) -> str:
    """What a program over `mesh` is lowered for (`Evaluator.platform`):
    a CPU mesh on a TPU host (dryrun_multichip) takes the CPU's forms."""
    return mesh.devices.reshape(-1)[0].platform


def shard_spec() -> P:
    return P(SHARD_AXIS)


def sharded(mesh: Mesh) -> NamedSharding:
    """Sharding for (n_shards, capacity) stacked column arrays: shards are
    split across devices, each shard contiguous in its device's HBM."""
    return NamedSharding(mesh, P(SHARD_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


__all__ = ["SHARD_AXIS", "get_mesh", "mesh_platform", "shard_spec", "sharded",
           "replicated", "shard_map"]
