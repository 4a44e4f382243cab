"""Rehearsals of the benchmark, run by hand and not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/selftest -q

They check answers, arithmetic and control flow.  A CPU run gives no
device number, and the command itself still fails without a chip."""

import importlib.util
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_run_py(bench_dir: str = BENCH):
    """``run.py`` of a benchmark directory as a module."""
    spec = importlib.util.spec_from_file_location(
        "bench_run_" + str(abs(hash(bench_dir))),
        os.path.join(bench_dir, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def run_py():
    return load_run_py()


@pytest.fixture(autouse=True)
def _compile_cache_in_tmp(tmp_path_factory, monkeypatch):
    """A rehearsal's CPU programs do not go into the checkout's cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))
