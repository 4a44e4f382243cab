"""copforge: AOT compile cache + warm program pool (ISSUE 9).

Takes compile latency off the serving path: compiled spmd executables
persist across process restarts keyed by restart-stable variant keys
(analysis/compilekey — dag digest + mesh fingerprint + capacity +
DonationPlan + backend fingerprint), and a boot-time warm pool replays
the hot-program manifest through the admission queue at LOW priority so
a restarted server serves its first corpus-shaped query without
tracing or compiling anything.
"""

from .cache import (GROUP_PROGRAMS_MAX, CachedProgram, CompileCache,
                    cached_call, compile_cache, configure, is_group_key)
from .manifest import WarmManifest
from .warmup import (maybe_warm_start, reset_warmed, simulate_restart,
                     warm_start)

__all__ = ["CompileCache", "CachedProgram", "compile_cache", "configure",
           "cached_call", "is_group_key", "GROUP_PROGRAMS_MAX",
           "WarmManifest", "warm_start",
           "maybe_warm_start", "reset_warmed", "simulate_restart"]
