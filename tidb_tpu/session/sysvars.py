"""System-variable registry: scopes, types, defaults, validation.

Reference analog: pkg/sessionctx/variable (sysvar.go + vardef/tidb_vars.go,
~700 vars).  This registry carries the variables this engine actually
honors plus the widely-set compatibility surface; SET validates and
coerces through it, unknown variables are rejected like MySQL's ERROR
1193 (unless prefixed `@@local.`-style passthrough is added later).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

SCOPE_GLOBAL = "global"
SCOPE_SESSION = "session"
SCOPE_BOTH = "both"
SCOPE_NONE = "noop"       # accepted for compatibility, no effect


@dataclass(frozen=True)
class SysVar:
    name: str
    default: Any
    scope: str = SCOPE_BOTH
    kind: str = "int"         # int | bool | float | str | enum
    min: Optional[int] = None
    max: Optional[int] = None
    options: tuple = ()       # enum values
    validator: Optional[Callable] = None


def _v(*args, **kw) -> SysVar:
    return SysVar(*args, **kw)


_VARS = [
    # engine-honored knobs
    # TPU-engine knobs (this framework's own surface — the reference
    # exposes every perf knob as a sysvar, vardef/tidb_vars.go)
    # -1 = unset: the engine default (module constant / ctor value)
    # stays authoritative until a user explicitly SETs the variable
    _v("tidb_tpu_device_mem_cap", -1, kind="int", min=-1,
       scope=SCOPE_GLOBAL),            # bytes; 0 = resident (no streaming)
    _v("tidb_tpu_broadcast_build_max_rows", -1, kind="int", min=-1,
       scope=SCOPE_GLOBAL),            # broadcast- vs shuffle-join cut
    _v("tidb_tpu_shard_count", 8, kind="int", min=1, max=4096),
    _v("tidb_tpu_dense_broadcast_max_groups", -1, kind="int", min=-1,
       max=1 << 20),
    _v("tidb_tpu_result_cache_entries", -1, kind="int", min=-1,
       max=4096, scope=SCOPE_GLOBAL),
    # device admission scheduler (sched/): bounded queue depth (every
    # launch is admitted through it, so 0 is refused) and the max tasks
    # one launch may coalesce
    _v("tidb_tpu_sched_queue_depth", -1, kind="int", min=-1,
       max=1 << 16, scope=SCOPE_GLOBAL, validator=lambda v: v != 0),
    _v("tidb_tpu_sched_max_coalesce", -1, kind="int", min=-1, max=64,
       scope=SCOPE_GLOBAL),
    # cross-query kernel fusion (one scan, many payloads) and the
    # adaptive micro-batch window: -1 = EWMA-tuned wait-for-stragglers,
    # 0 = never hold a launch, >0 = fixed window in microseconds
    _v("tidb_tpu_sched_fusion", 1, kind="bool", scope=SCOPE_GLOBAL),
    _v("tidb_tpu_sched_window_us", -1, kind="int", min=-1, max=100_000,
       scope=SCOPE_GLOBAL),
    # per-mesh HBM admission budget for the static cost gate
    # (analysis/copcost): -1 = auto from device memory stats (CPU
    # fallback constant), 0 = unlimited, >0 = bytes.  Launches whose
    # LaunchCost.peak_hbm_bytes exceed it are rejected pre-trace.
    _v("tidb_tpu_sched_hbm_budget", -1, kind="int", min=-1,
       scope=SCOPE_GLOBAL),
    # resource control plane (rc/): RU-bucket enforcement at the drain.
    # rc_enable=0 reverts to the legacy post-paid statement charge;
    # overdraft is the bounded RU debt the drain tolerates per group
    # (-1 = engine default, DEFAULT_OVERDRAFT_RU)
    _v("tidb_tpu_rc_enable", 1, kind="bool", scope=SCOPE_GLOBAL),
    _v("tidb_tpu_rc_overdraft_ru", -1, kind="int", min=-1,
       max=1 << 20, scope=SCOPE_GLOBAL),
    # launch supervision (faultline): host-oracle fallback for
    # breaker-quarantined program digests (default on — a broken device
    # kernel degrades to slow-but-correct instead of unavailable), and
    # the fault-injection plane spec (seam:kind[:rate][:match=..]
    # [:times=..] rules, comma-separated, optional seed=N; empty = off)
    _v("tidb_tpu_sched_host_fallback", 1, kind="bool",
       scope=SCOPE_GLOBAL),
    _v("tidb_tpu_faults", "", kind="str", scope=SCOPE_GLOBAL),
    # copforge AOT compile cache (compilecache/): cacheable device
    # programs resolve through a warm executable pool; with a cache dir
    # set, compiled executables persist across restarts (digest + mesh
    # fingerprint + donation-plan keyed) and the boot warm pool replays
    # the hot-program manifest at LOW priority.  warm_pool caps the
    # pool/manifest in BYTES (-1 = engine default, 0 = unbounded).
    _v("tidb_tpu_compile_cache", 1, kind="bool", scope=SCOPE_GLOBAL),
    _v("tidb_tpu_compile_cache_dir", "", kind="str", scope=SCOPE_GLOBAL),
    _v("tidb_tpu_compile_warm_pool", -1, kind="int", min=-1,
       scope=SCOPE_GLOBAL),
    # coplace PD-style coordination plane (pd/): N server processes
    # share one RU budget per resource group (debt-weighted refill
    # shares), one compile-artifact registry (compile-once claims +
    # peer warm-pool adoption + cross-process quarantine), and merged
    # cost calibration.  Default OFF — a single process needs no
    # coordination and stays byte-identical to the pre-pd behavior.
    # pd_dir empty = in-process shared store (N Domains in one
    # interpreter); set = file-backed store shared by real processes
    # (advisory locks + atomic rename, one host).
    _v("tidb_tpu_pd", 0, kind="bool", scope=SCOPE_GLOBAL),
    _v("tidb_tpu_pd_dir", "", kind="str", scope=SCOPE_GLOBAL),
    # copmeter closed-loop cost calibration (analysis/calibrate):
    # measured per-digest launch times correct the static LaunchCost
    # terms feeding RU pricing, HBM-budget admission, fusion caps, the
    # micro-batch window, and deadline-aware early shedding.  Off = the
    # static model untouched, no feedback recorded.
    _v("tidb_tpu_cost_calibration", 1, kind="bool", scope=SCOPE_GLOBAL),
    # shardflow typed-link topology view (parallel/topology): the host
    # factorization analysis assumes when classifying collective bytes
    # as same-host ICI vs cross-host DCI.  -1 = derive from the mesh's
    # device process indices (single-host on one machine); >0 declares
    # a (host=N, device=D/N) view — how tier-1 exercises the DCI tier
    # on the 8-vdev CPU mesh
    _v("tidb_tpu_topology_hosts", -1, kind="int", min=-1, max=4096,
       scope=SCOPE_GLOBAL),
    # copscope (obs/): per-statement span trees with cross-thread trace
    # propagation + the flight-recorder ring.  tidb_tpu_trace off =
    # no tree is built, no span is recorded anywhere (the overhead
    # guard's baseline); tidb_tpu_trace_sample = keep 1-in-N ordinary
    # traces (failed/degraded/quarantined/retried/slow and a digest's
    # outliers always kept; the rest one in tidb_tpu_trace_sample of
    # each digest)
    _v("tidb_tpu_trace", 1, kind="bool"),
    _v("tidb_tpu_trace_sample", 16, kind="int", min=1, max=65536,
       scope=SCOPE_GLOBAL),
    # copgauge (obs/hbm): the live HBM ledger and measured launch
    # watermarks feeding continuous mem_factor calibration.  Off = no
    # ledger accounting, no measured watermarks — the static cost model
    # behaves byte-identically to the pre-copgauge engine (mem_factor
    # moves only on OOM).
    _v("tidb_tpu_hbm_ledger", 1, kind="bool", scope=SCOPE_GLOBAL),
    # on-demand jax.profiler capture gate (/profile?ms=N): off by
    # default — a trace capture writes xplane dirs to disk and costs
    # real overhead, so an operator must opt in
    _v("tidb_tpu_profile", 0, kind="bool", scope=SCOPE_GLOBAL),
    # copsan runtime lock sanitizer (utils/locksan): instrumented lock
    # wrappers verify every observed acquisition edge against the
    # static concurrency model (analysis/concurrency).  Off by default
    # — arming only affects locks allocated AFTER it, so flip it
    # before building the domain (the stress smoke and bench do).
    _v("tidb_tpu_lock_sanitizer", 0, kind="bool", scope=SCOPE_GLOBAL),
    # slow-query log threshold (ms), session -> Domain plumb — replaces
    # the constructor-only threshold in utils/stmtsummary; slow entries
    # carry schedWait/compile/ru/retried/trace-id fields
    _v("tidb_tpu_slow_threshold_ms", 300, kind="int", min=0,
       max=86_400_000),
    _v("tidb_distsql_scan_concurrency", 15, kind="int", min=1, max=256),
    _v("tidb_max_chunk_size", 1024, kind="int", min=32, max=65536),
    _v("tidb_enable_vectorized_expression", 1, kind="bool"),
    _v("tidb_ddl_reorg_worker_cnt", 4, kind="int", min=1, max=128),
    _v("tidb_mdl_wait_timeout", 10.0, kind="float", min=0.0, max=3600.0),
    # MySQL client/ORM handshake compat (accepted, enforced where the
    # engine has the corresponding behavior)
    _v("profiling", 0, kind="bool"),
    _v("innodb_strict_mode", 1, kind="bool"),
    _v("optimizer_switch", "", kind="str"),
    _v("big_tables", 0, kind="bool"),
    _v("sql_buffer_result", 0, kind="bool"),
    _v("lc_time_names", "en_US", kind="str"),
    _v("div_precision_increment", 4, kind="int", min=0, max=30),
    _v("tidb_mem_quota_query", -1, kind="int"),
    _v("tidb_enable_tmp_storage_on_oom", 1, kind="bool"),
    _v("tidb_enable_plan_cache", 1, kind="bool"),
    _v("tidb_enable_cascades_planner", 0, kind="bool"),
    _v("tidb_opt_skew_distinct_agg", 0, kind="bool"),
    _v("tidb_gc_life_time_sec", 600, kind="int", min=1),
    _v("tidb_gc_run_interval_sec", 60, kind="int", min=1),
    _v("tidb_ttl_job_interval_sec", 60, kind="int", min=1),
    _v("tidb_auto_analyze_ratio", 0.5, kind="float"),
    _v("tidb_enable_auto_analyze", 1, kind="bool"),
    _v("tidb_txn_mode", "optimistic", kind="enum",
       options=("optimistic", "pessimistic")),
    _v("tidb_slow_log_threshold", 300, kind="int", min=0),
    _v("tidb_resource_group", "default", kind="str"),
    _v("tidb_enable_telemetry", 0, kind="bool", scope=SCOPE_GLOBAL),
    # MySQL compatibility surface (honored where the engine has the
    # concept; stored + reflected otherwise)
    _v("autocommit", 1, kind="bool"),
    _v("sql_mode", "ONLY_FULL_GROUP_BY,STRICT_TRANS_TABLES", kind="str"),
    _v("time_zone", "SYSTEM", kind="str"),
    _v("max_execution_time", 0, kind="int", min=0),
    _v("max_allowed_packet", 67108864, kind="int", min=1024),
    _v("character_set_client", "utf8mb4", kind="str"),
    _v("character_set_connection", "utf8mb4", kind="str"),
    _v("character_set_results", "utf8mb4", kind="str"),
    _v("collation_connection", "utf8mb4_bin", kind="str"),
    _v("default_collation_for_utf8mb4", "utf8mb4_bin", kind="str"),
    _v("transaction_isolation", "REPEATABLE-READ", kind="enum",
       options=("REPEATABLE-READ", "READ-COMMITTED")),
    # pre-8.0 connector/ORM aliases and connect-time compat vars —
    # clients SET these during handshake; they must not error
    _v("tx_isolation", "REPEATABLE-READ", kind="enum",
       options=("REPEATABLE-READ", "READ-COMMITTED")),
    _v("tx_read_only", 0, kind="bool", scope=SCOPE_NONE),
    _v("transaction_read_only", 0, kind="bool", scope=SCOPE_NONE),
    _v("sql_auto_is_null", 0, kind="bool", scope=SCOPE_NONE),
    _v("sql_safe_updates", 0, kind="bool", scope=SCOPE_NONE),
    _v("sql_notes", 1, kind="bool", scope=SCOPE_NONE),
    _v("sql_warnings", 0, kind="bool", scope=SCOPE_NONE),
    _v("sql_log_bin", 1, kind="bool", scope=SCOPE_NONE),
    _v("sql_quote_show_create", 1, kind="bool", scope=SCOPE_NONE),
    _v("character_set_server", "utf8mb4", kind="str"),
    _v("collation_server", "utf8mb4_bin", kind="str"),
    _v("character_set_database", "utf8mb4", kind="str"),
    _v("collation_database", "utf8mb4_bin", kind="str"),
    _v("default_storage_engine", "tpu-columnar", kind="str",
       scope=SCOPE_NONE),
    _v("net_buffer_length", 16384, kind="int", scope=SCOPE_NONE),
    _v("query_cache_size", 0, kind="int", scope=SCOPE_NONE),
    _v("query_cache_type", 0, kind="int", scope=SCOPE_NONE),
    _v("system_time_zone", "UTC", kind="str", scope=SCOPE_GLOBAL),
    _v("sql_require_primary_key", 0, kind="bool", scope=SCOPE_NONE),
    _v("init_connect", "", kind="str", scope=SCOPE_GLOBAL),
    _v("wait_timeout", 28800, kind="int", min=1),
    _v("interactive_timeout", 28800, kind="int", min=1),
    _v("net_write_timeout", 60, kind="int", min=1),
    _v("net_read_timeout", 30, kind="int", min=1),
    _v("lower_case_table_names", 2, kind="int", scope=SCOPE_GLOBAL),
    _v("version_comment", "tidb-tpu", kind="str", scope=SCOPE_GLOBAL),
    _v("port", 4000, kind="int", scope=SCOPE_GLOBAL),
    _v("socket", "", kind="str", scope=SCOPE_GLOBAL),
    _v("datadir", "", kind="str", scope=SCOPE_GLOBAL),
    _v("last_insert_id", 0, kind="int", scope=SCOPE_SESSION),
    _v("auto_increment_increment", 1, kind="int", min=1, max=65535),
    _v("auto_increment_offset", 1, kind="int", min=1, max=65535),
    _v("group_concat_max_len", 1024, kind="int", min=4),
    _v("sql_select_limit", 2 ** 64 - 1, kind="int", min=0),
    _v("foreign_key_checks", 0, kind="bool"),
    _v("unique_checks", 1, kind="bool"),
    _v("innodb_lock_wait_timeout", 50, kind="int", min=1),
    # TiDB-compat knobs accepted as no-ops (reference defines ~700; the
    # ones users commonly SET must not error)
    _v("tidb_enable_async_commit", 1, kind="bool", scope=SCOPE_NONE),
    _v("tidb_enable_1pc", 1, kind="bool", scope=SCOPE_NONE),
    _v("tidb_enable_clustered_index", "ON", kind="str", scope=SCOPE_NONE),
    _v("tidb_analyze_version", 2, kind="int", scope=SCOPE_NONE),
    _v("tidb_cost_model_version", 2, kind="int", scope=SCOPE_NONE),
    _v("tidb_partition_prune_mode", "dynamic", kind="str",
       scope=SCOPE_NONE),
    _v("tidb_enable_paging", 1, kind="bool", scope=SCOPE_NONE),
    _v("tidb_executor_concurrency", 5, kind="int", min=1, max=256),
    _v("tidb_hash_join_concurrency", 5, kind="int", scope=SCOPE_NONE),
    _v("tidb_index_lookup_concurrency", 4, kind="int", scope=SCOPE_NONE),
    _v("tidb_build_stats_concurrency", 4, kind="int", scope=SCOPE_NONE),
    _v("tidb_enable_rate_limit_action", 0, kind="bool", scope=SCOPE_NONE),
    _v("tidb_replica_read", "leader", kind="str", scope=SCOPE_NONE),
    _v("tidb_isolation_read_engines", "tpu", kind="str",
       scope=SCOPE_NONE),
    _v("tidb_enable_stmt_summary", 1, kind="bool", scope=SCOPE_NONE),
    _v("tidb_stmt_summary_max_stmt_count", 3000, kind="int",
       scope=SCOPE_NONE),
    _v("tidb_enable_collect_execution_info", 1, kind="bool",
       scope=SCOPE_NONE),
    _v("tidb_opt_agg_push_down", 1, kind="bool", scope=SCOPE_NONE),
    _v("tidb_opt_join_reorder_threshold", 12, kind="int",
       scope=SCOPE_NONE),
    _v("tidb_index_join_batch_size", 25000, kind="int", scope=SCOPE_NONE),
    _v("tidb_init_chunk_size", 32, kind="int", scope=SCOPE_NONE),
    _v("tidb_retry_limit", 10, kind="int", scope=SCOPE_NONE),
    _v("tidb_disable_txn_auto_retry", 1, kind="bool", scope=SCOPE_NONE),
    _v("tidb_constraint_check_in_place", 0, kind="bool",
       scope=SCOPE_NONE),
    _v("tidb_skip_utf8_check", 0, kind="bool", scope=SCOPE_NONE),
    _v("tidb_enable_window_function", 1, kind="bool", scope=SCOPE_NONE),
    _v("tidb_enable_table_partition", "ON", kind="str", scope=SCOPE_NONE),
    _v("tidb_scatter_region", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_wait_split_region_finish", 1, kind="bool", scope=SCOPE_NONE),
    _v("tidb_store_batch_size", 4, kind="int", scope=SCOPE_NONE),
    _v("tidb_enable_index_merge", 1, kind="bool", scope=SCOPE_NONE),
    _v("tidb_enable_noop_functions", 0, kind="bool", scope=SCOPE_NONE),
    _v("tidb_row_format_version", 2, kind="int", scope=SCOPE_NONE),
    # widely-set TiDB compatibility surface (noop scope): ORMs and
    # operator tooling SET these freely; they must not error
    _v("tidb_allow_batch_cop", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_allow_fallback_to_tikv", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_allow_mpp", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_auto_analyze_end_time", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_auto_analyze_start_time", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_backoff_lock_fast", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_backoff_weight", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_batch_commit", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_batch_delete", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_batch_insert", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_broadcast_join_threshold_count", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_broadcast_join_threshold_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_capture_plan_baselines", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_check_mb4_value_in_utf8", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_checksum_table_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_committer_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_current_ts", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_ddl_error_count_limit", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_ddl_flashback_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_ddl_reorg_batch_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_ddl_reorg_priority", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_dml_batch_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_amend_pessimistic_txn", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_auto_increment_in_generated", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_cascades_planner", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_chunk_rpc", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_column_tracking", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_ddl", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_enhanced_security", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_exchange_partition", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_extended_stats", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_fast_analyze", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_foreign_key", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_gc_aware_memory_track", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_global_index", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_index_merge_join", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_list_partition", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_local_txn", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_metadata_lock", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_mutation_checker", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_new_cost_interface", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_new_only_full_group_by_check", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_noop_variables", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_null_aware_anti_join", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_ordered_result_mode", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_outer_join_reorder", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_parallel_apply", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_pipelined_window_function", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_prepared_plan_cache", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_pseudo_for_outdated_stats", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_resource_control", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_reuse_chunk", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_slow_log", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_strict_double_type_check", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_tiflash_read_for_write_stmt", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_top_sql", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_enable_tso_follower_proxy", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_evolve_plan_baselines", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_expensive_query_time_threshold", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_force_priority", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_gc_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_gc_enable", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_gc_max_wait_time", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_gc_scan_lock_mode", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_general_log", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_generate_binary_plan", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_guarantee_linearizability", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_hash_exchange_with_new_collation", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_hashagg_final_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_hashagg_partial_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_ignore_prepared_cache_close_stmt", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_index_lookup_join_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_index_lookup_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_index_merge_intersection_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_index_serial_scan_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_last_ddl_info", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_last_query_info", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_last_txn_info", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_log_file_max_days", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_low_resolution_tso", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_max_auto_analyze_time", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_max_delta_schema_count", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_max_paging_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_max_tiflash_threads", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_mem_oom_action", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_mem_quota_analyze", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_mem_quota_apply_cache", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_mem_quota_binding_cache", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_memory_usage_alarm_ratio", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_merge_join_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_metric_query_range_duration", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_metric_query_step", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_min_paging_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_multi_statement_mode", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_nontransactional_ignore_error", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_broadcast_cartesian_join", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_concurrency_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_copcpu_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_correlation_exp_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_correlation_threshold", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_cpu_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_desc_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_disk_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_distinct_agg_push_down", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_enable_correlation_adjustment", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_force_inline_cte", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_insubq_to_join_and_agg", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_limit_push_down_threshold", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_memory_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_mpp_outer_join_fixed_build_side", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_network_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_prefer_range_scan", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_projection_push_down", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_range_max_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_scan_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_seek_factor", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_skew_distinct_agg", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_opt_write_row_id", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_placement_mode", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_pprof_sql_cpu", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_prepared_plan_cache_memory_guard_ratio", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_prepared_plan_cache_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_projection_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_query_log_max_len", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_rc_read_check_ts", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_read_consistency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_read_staleness", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_record_plan_in_slow_log", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_redact_log", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_regard_null_as_point", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_remove_orderby_in_subquery", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_restricted_read_only", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_server_memory_limit", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_shard_allocate_step", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_simplified_metrics", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_skip_ascii_check", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_skip_isolation_level_check", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_slow_query_file", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_snapshot", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_source_id", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_stats_cache_mem_quota", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_stats_load_sync_wait", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_stmt_summary_history_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_stmt_summary_internal_query", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_stmt_summary_max_sql_length", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_stmt_summary_refresh_interval", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_store_limit", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_streamagg_concurrency", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_super_read_only", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_sysdate_is_now", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_table_cache_lease", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_tmp_table_max_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_top_sql_max_meta_count", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_top_sql_max_time_series_count", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_track_aggregate_memory_usage", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_tso_client_batch_max_wait_time", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_txn_assertion_level", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_txn_commit_batch_size", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_wait_split_region_timeout", "", kind="str", scope=SCOPE_NONE),
    _v("tidb_window_concurrency", "", kind="str", scope=SCOPE_NONE),
]

REGISTRY: dict[str, SysVar] = {v.name: v for v in _VARS}


class SysVarError(ValueError):
    pass


def validate_set(name: str, value: Any,
                 scope: Optional[str] = None) -> Any:
    """Coerce + validate a SET value; raises SysVarError on unknown
    variable, wrong scope, or out-of-range value.  Returns the canonical
    value.  `scope` is the statement's scope ('global'/'session')."""
    sv = REGISTRY.get(name)
    if sv is None:
        raise SysVarError(f"Unknown system variable {name!r}")
    if scope == "global" and sv.scope == SCOPE_SESSION:
        raise SysVarError(f"{name} is a SESSION variable")
    if scope == "session" and sv.scope == SCOPE_GLOBAL:
        raise SysVarError(
            f"{name} is a GLOBAL variable; use SET GLOBAL")
    if value is None:
        return sv.default          # SET x = DEFAULT
    if sv.kind == "bool":
        if isinstance(value, str):
            up = value.upper()
            if up in ("ON", "TRUE", "1"):
                return 1
            if up in ("OFF", "FALSE", "0"):
                return 0
            raise SysVarError(f"{name}: bad boolean {value!r}")
        return 1 if value else 0
    if sv.kind == "int":
        try:
            iv = int(value)
        except (TypeError, ValueError):
            raise SysVarError(f"{name}: expected integer, got {value!r}")
        if sv.min is not None and iv < sv.min:
            iv = sv.min           # MySQL clamps with a warning
        if sv.max is not None and iv > sv.max:
            iv = sv.max
        if sv.validator is not None and not sv.validator(iv):
            raise SysVarError(f"{name}: {iv} is not a value it takes")
        return iv
    if sv.kind == "float":
        try:
            return float(value)
        except (TypeError, ValueError):
            raise SysVarError(f"{name}: expected float, got {value!r}")
    if sv.kind == "enum":
        s = str(value).upper().replace("_", "-")
        for opt in sv.options:
            if s == opt.upper() or str(value).lower() == opt.lower():
                return opt
        raise SysVarError(
            f"{name}: must be one of {', '.join(sv.options)}")
    return str(value)


def defaults() -> dict[str, Any]:
    return {v.name: v.default for v in _VARS}


__all__ = ["SysVar", "REGISTRY", "SysVarError", "validate_set", "defaults"]
