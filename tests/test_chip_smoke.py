"""chip_smoke.py on the CPU: the served path at a tiny size, and the
contract that no result line is printed without a TPU."""

import os
import pathlib
import shutil
import subprocess
import sys

import chip_smoke
from janus_tpu.vdaf.registry import VdafInstance

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_run_smoke_collects_the_column_sum():
    rec = chip_smoke.run_smoke(VdafInstance.sum_vec(length=16, bits=16), 40, 10, seed=7)
    assert rec["jobs_created"] == 4
    assert rec["report_count"] == 40
    assert rec["aggregate"] == rec["expected"]
    assert chip_smoke.check_record(rec) == []
    assert set(rec["phases"]) == {"boot", "warmup", "client", "upload", "aggregate", "collect"}


def test_main_without_a_tpu_prints_no_result(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=tmp_path,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
