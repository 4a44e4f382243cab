"""Native runtime components (C++17), built from source on first load.

The shared libraries are not tracked: a checkout or a copy holds only
``kvstore.cpp`` / ``hostops.cpp`` and the Makefile.  ``ensure_built``
compiles a library when it is missing or when the source it was built
from differs from the one on disk.  The comparison is by content hash,
recorded beside the library, because a file time means nothing after a
copy or a checkout.
"""

from __future__ import annotations

import fcntl
import hashlib
import os
import subprocess

NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))


def _sha256(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def ensure_built(lib: str, src: str) -> str:
    """Path of ``lib`` in this directory, rebuilt from ``src`` unless the
    hash recorded at its last build matches the source.  Raises
    ``CalledProcessError`` / ``OSError`` when the toolchain fails; the
    caller decides whether that is fatal."""
    lib_path = os.path.join(NATIVE_DIR, lib)
    hash_path = lib_path + ".srchash"
    want = _sha256(os.path.join(NATIVE_DIR, src))
    # one builder at a time across processes (tests spawn children that
    # load the same libraries)
    with open(os.path.join(NATIVE_DIR, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        have = None
        if os.path.exists(lib_path) and os.path.exists(hash_path):
            with open(hash_path) as f:
                have = f.read().strip()
        if have != want:
            # a process may have the old library mapped: give the new
            # one a new inode instead of rewriting that file in place
            if os.path.exists(lib_path):
                os.unlink(lib_path)
            subprocess.run(["make", "-B", "-C", NATIVE_DIR, lib],
                           check=True, capture_output=True)
            with open(hash_path, "w") as f:
                f.write(want + "\n")
    return lib_path


__all__ = ["ensure_built", "NATIVE_DIR"]
