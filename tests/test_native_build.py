"""The native libraries are built from what git holds: source, not
binaries.  A library missing from disk, or recorded as built from another
source, is rebuilt on the next load."""

import os
import subprocess
import sys

from tidb_tpu.native import NATIVE_DIR, ensure_built

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_deleted_library_is_rebuilt_from_source_on_next_load(tmp_path):
    lib = os.path.join(NATIVE_DIR, "libtpuhostops.so")
    ensure_built("libtpuhostops.so", "hostops.cpp")
    keep = tmp_path / "keep.so"
    os.replace(lib, keep)                # gone from disk, hash file stays
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "from tidb_tpu.copr import nativeops\n"
             "import numpy as np\n"
             "assert nativeops.available()\n"
             "print(nativeops.count_keys(np.array([3, 3, 5]), 3, 4)"
             ".tolist())\n"],
            cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=180)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip() == "[2, 0, 1, 0]"
        assert os.path.exists(lib)
    finally:
        if not os.path.exists(lib):
            os.replace(keep, lib)


def test_stale_source_hash_forces_a_rebuild():
    lib = ensure_built("libtpukv.so", "kvstore.cpp")
    with open(lib + ".srchash") as f:
        good = f.read()
    before = os.stat(lib).st_mtime_ns
    with open(lib + ".srchash", "w") as f:
        f.write("0" * 64 + "\n")         # as if built from other source
    assert ensure_built("libtpukv.so", "kvstore.cpp") == lib
    with open(lib + ".srchash") as f:
        assert f.read() == good
    assert os.stat(lib).st_mtime_ns > before


def test_no_binary_is_tracked():
    out = subprocess.run(["git", "ls-files", "tidb_tpu/native"], cwd=REPO,
                         capture_output=True, text=True)
    if out.returncode != 0:
        return                           # not a git checkout: nothing to ask
    assert not [p for p in out.stdout.split() if p.endswith(".so")]
