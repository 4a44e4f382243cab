"""Entry-point contracts, each run as the real file in a fresh subprocess:
  - dryrun_multichip holds itself to virtual CPU devices and finishes fast
  - bench.py runs exactly one child that may touch the chip, last, and
    exits non-zero when that child records no rung on an accelerator
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # simulate driver default env
    env.pop("XLA_FLAGS", None)
    return env


def test_dryrun_multichip_cpu_only_and_fast():
    out = subprocess.run(
        [sys.executable, "-c",
         "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"],
        cwd=REPO, env=_clean_env(), capture_output=True, text=True,
        timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "dryrun_multichip(8)" in out.stdout


def test_entry_compiles_single_chip():
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; jax.config.update('jax_platforms', 'cpu')\n"
         "from __graft_entry__ import entry\n"
         "fn, args = entry()\n"
         "res = jax.jit(fn)(*args)\n"
         "print('compiled', len(res))"],
        cwd=REPO, env=_clean_env(), capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "compiled" in out.stdout


def test_bench_exits_nonzero_when_chip_child_yields_no_rung(tmp_path):
    """No JAX_PLATFORMS=cpu asked for and no accelerator here: JAX falls
    back to the CPU by itself, the chip child refuses to run a ladder on
    it, and the parent prints no result and exits non-zero.  The CPU
    children are stubbed out (the parent's order of work is what is under
    test, not their scenarios); the chip child is the real file."""
    driver = (
        "import json, os, sys\n"
        "import bench\n"
        "real, calls = bench._run_child, []\n"
        "def run(env_extra, timeout_s, tag):\n"
        "    calls.append((tag, env_extra.get('JAX_PLATFORMS')))\n"
        "    if env_extra.get('JAX_PLATFORMS') == 'cpu':\n"
        "        return 0, b''\n"
        "    return real(env_extra, min(timeout_s, 200), tag)\n"
        "bench._run_child = run\n"
        "rc = bench.orchestrate()\n"
        "print('CALLS=' + json.dumps(calls))\n"
        "sys.exit(rc)\n")
    env = _clean_env()
    env.update(BENCH_DATA_DIR=str(tmp_path), BENCH_SF_LADDER="0.1")
    out = subprocess.run([sys.executable, "-c", driver], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=280)
    assert out.returncode != 0, (out.stdout, out.stderr[-2000:])
    lines = out.stdout.splitlines()
    calls = json.loads([l for l in lines if l.startswith("CALLS=")][0][6:])
    # one child without a forced CPU platform, and it runs last
    assert [p for _t, p in calls] == ["cpu", "cpu", None], calls
    assert calls[-1][0] == "chip-bench"
    assert not [l for l in lines if l.lstrip().startswith("{")], out.stdout
    assert "no accelerator" in out.stderr, out.stderr[-2000:]


def test_bench_parent_stays_off_jax():
    """A parent that has touched JAX holds the chip and its child then
    fails or hangs: bench.py may import jax (or tidb_tpu, which does)
    only inside the functions its children run."""
    import ast
    with open(os.path.join(REPO, "bench.py")) as f:
        tree = ast.parse(f.read())
    top = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            top |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            top.add((node.module or "").split(".")[0])
    assert not top & {"jax", "jaxlib", "tidb_tpu", "__graft_entry__"}, top
