"""benchkv-style micro-benchmark of the native MVCC engine
(cmd/benchkv/main.go analog): loads N committed keys, then measures
random point-gets (in-process, ctypes overhead excluded via
kv_bench_gets) and a full snapshot scan — memtable-only vs flushed to an
immutable sorted run (the LSM read path).

Usage: python -m tidb_tpu.testing.bench_kv   [BENCHKV_KEYS=2000000]
"""

import ctypes, time
import os
from ..native import ensure_built
lib = ctypes.CDLL(ensure_built("libtpukv.so", "kvstore.cpp"))
for n,r,a in [("kv_open",ctypes.c_void_p,[]),("kv_alloc_ts",ctypes.c_uint64,[ctypes.c_void_p]),
 ("kv_flush",ctypes.c_int64,[ctypes.c_void_p]),
 ("kv_bench_gets",ctypes.c_int64,[ctypes.c_void_p,ctypes.c_int64,ctypes.c_uint64,ctypes.c_uint64]),
 ("kv_set_flush_threshold",None,[ctypes.c_void_p,ctypes.c_int64])]:
    f=getattr(lib,n); f.restype=r; f.argtypes=a
lib.kv_prewrite.restype=ctypes.c_int32
lib.kv_prewrite.argtypes=[ctypes.c_void_p,ctypes.c_char_p,ctypes.c_int32,ctypes.c_char_p,ctypes.c_int32,ctypes.c_char_p,ctypes.c_int32,ctypes.c_uint64,ctypes.c_uint8]
lib.kv_commit.restype=ctypes.c_int32
lib.kv_commit.argtypes=[ctypes.c_void_p,ctypes.c_char_p,ctypes.c_int32,ctypes.c_uint64,ctypes.c_uint64]
lib.kv_scan.restype=ctypes.c_int32
lib.kv_scan.argtypes=[ctypes.c_void_p,ctypes.c_char_p,ctypes.c_int32,ctypes.c_char_p,ctypes.c_int32,ctypes.c_uint64,ctypes.c_int32,ctypes.c_char_p,ctypes.c_int64,ctypes.POINTER(ctypes.c_int64),ctypes.POINTER(ctypes.c_uint8)]
N = int(os.environ.get("BENCHKV_KEYS", "2000000"))
def bench(flush):
    h = ctypes.c_void_p(lib.kv_open())
    lib.kv_set_flush_threshold(h, 0)
    for i in range(N):
        k = b"%012d" % i; v = b"value-%d" % i
        sts = lib.kv_alloc_ts(h)
        lib.kv_prewrite(h, k, len(k), v, len(v), k, len(k), sts, 0)
        lib.kv_commit(h, k, len(k), sts, lib.kv_alloc_ts(h))
    if flush: lib.kv_flush(h)
    ts = lib.kv_alloc_ts(h)
    ns = lib.kv_bench_gets(h, 1_000_000, 42, ts)
    buf = ctypes.create_string_buffer(64<<20)
    used = ctypes.c_int64(); trunc = ctypes.c_uint8()
    t=time.time()
    n = lib.kv_scan(h, b"", 0, b"", 0, ts, 2_100_000, buf, len(buf), ctypes.byref(used), ctypes.byref(trunc))
    st=time.time()-t
    print(("flushed " if flush else "memtable"), f"get {ns/1e3/1e6:.3f} us/op   scan {N/st/1e6:.1f} M rows/s (n={n})")
bench(False)
bench(True)
