"""tidb_tpu — a TPU-native distributed SQL framework with TiDB's capabilities.

A MySQL-compatible SQL layer whose coprocessor pushdown path executes as
XLA-compiled kernels on TPU: vectorized expression evaluation and the
Selection/HashAgg/TopN operator pipeline run over Arrow-layout column shards,
region-level cop tasks fan out as SPMD (``shard_map``) programs across a TPU
mesh with partial aggregates merged via ``jax.lax.psum``.

This is an idiomatic JAX/XLA design, not a port of the Go reference
(jebter/tidb).  Layer map (reference analog in parens):

- :mod:`tidb_tpu.types`     — MySQL type system (pkg/types)
- :mod:`tidb_tpu.chunk`     — Arrow-layout columnar data plane (pkg/util/chunk)
- :mod:`tidb_tpu.expr`      — expression IR + JAX compiler (pkg/expression)
- :mod:`tidb_tpu.copr`      — coprocessor DAG execution on device
                              (unistore/cophandler, closure_exec.go)
- :mod:`tidb_tpu.parallel`  — mesh / shard_map SPMD fan-out + collectives
                              (pkg/store/copr fan-out, MPP exchanges)
- :mod:`tidb_tpu.store`     — shard catalog, columnar shards, KV/MVCC/txn
                              (pkg/store, unistore)
- :mod:`tidb_tpu.sql`       — lexer/parser/AST (pkg/parser)
- :mod:`tidb_tpu.planner`   — logical/physical optimizer + pushdown split
                              (pkg/planner)
- :mod:`tidb_tpu.executor`  — host-side root Volcano executors (pkg/executor)
- :mod:`tidb_tpu.session`   — session, catalog, DDL (pkg/session, pkg/meta)
- :mod:`tidb_tpu.utils`     — tracing, metrics, config/sysvars (pkg/util)
"""

import jax

# SQL semantics need 64-bit ints (BIGINT) and doubles end-to-end.  TPU
# emulates i64/f64 with 32-bit pairs; hot kernels downcast internally where
# provably safe (see copr/kernels.py).
jax.config.update("jax_enable_x64", True)

# The platform is JAX's to choose (JAX_PLATFORMS).  Device acquisition is
# lazy: parallel/mesh resolves at first device dispatch (see
# session.Domain), so importing this package or running host-only
# statements never initializes a backend.

__version__ = "0.1.0"
