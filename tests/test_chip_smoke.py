"""chip_smoke.py contracts that a CPU run can check.

The script itself only runs on a TPU (the chip tool reaches one).  Here:
with no TPU it must fail at once and print no result; outside the repo it
must fail too; and its drive function, at SF0.01 on the CPU mesh, must get
every statement's answer equal to its own numpy/Decimal oracle.  Times it
prints on this mesh are not device times and nothing here reads them.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _has_result_line(stdout: str) -> bool:
    return any(ln.lstrip().startswith("{") and '"ok"' in ln
               for ln in stdout.splitlines())


def test_exits_nonzero_and_names_the_missing_tpu():
    out = _run(REPO, SMOKE)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr, out.stderr
    assert not _has_result_line(out.stdout), out.stdout


def test_fails_in_a_directory_that_holds_nothing_else(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(str(tmp_path), "chip_smoke.py")
    assert out.returncode != 0
    assert not _has_result_line(out.stdout), out.stdout
    # and past the platform check there is no way round the package
    probe = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke.drive(0.01)"],
        cwd=str(tmp_path), env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert probe.returncode != 0
    assert "ModuleNotFoundError" in probe.stderr, probe.stderr[-500:]


def test_drive_answers_equal_the_oracle_at_sf001():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    rep = chip_smoke.drive(0.01, seed=3)     # raises on any wrong answer
    assert set(rep["results"]) == {"q6", "q1", "bcast_join_groupby",
                                   "hndv", "topn", "small"}
    assert all(r["rows"] > 0 for r in rep["results"].values())
    assert rep["results"]["hndv"]["rows"] == 10
    assert chip_smoke.HNDV_SQL == (
        "select l_partkey, sum(l_quantity) from lineitem "
        "group by l_partkey order by 2 desc, 1 limit 10")
    assert all(r["warm_regrows"] == 0 for r in rep["results"].values())
    assert rep["cluster_info"][0][3] == "cpu"
    assert rep["native_hostops"]
    # a CPU mesh answers aggregates from the host engine: the device-path
    # check must say so instead of passing
    with pytest.raises(chip_smoke.SmokeFailure) as ei:
        chip_smoke.check_device_path(rep)
    assert "cluster_info platform 'cpu'" in str(ei.value)
    assert "launches" in str(ei.value)
