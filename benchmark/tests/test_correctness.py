"""A whole run at a small size on the CPU, past the harness's look for
a chip: the clean program comes out correct, and each fault planted
under the timed path (`faults.py`), the control among them, makes the
comparison with the reference fail."""

import contextlib
import copy

import pytest

import faults
import run
import spec

SMALL = {
    # 40 backlog reports in jobs of 20 (bucket 32) and 20 uploads in 2 s
    "creator": {"min_aggregation_job_size": 10, "max_aggregation_job_size": 20},
    "traffic": {"backlog_per_s": 20, "upload_rps": 10, "invalid_share": 0.05},
    "seconds": 2.0,
}


def small_cell(name: str, vdaf: dict | None = None):
    cell = spec.load_cell(name)
    cell = copy.deepcopy(cell)
    cell.config["aggregation_job_creator"].update(SMALL["creator"])
    cell.config["task"]["min_batch_size"] = 5
    if vdaf is not None:
        cell.config["vdaf"] = vdaf
        cell.config["name"] = cell.config["name"] + "-small"
    cell.traffic.update(SMALL["traffic"])
    return cell


def run_small(cell, seed: int, fault: str | None):
    import jax

    plant = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    with plant:
        return run.run_cell(cell, seed, SMALL["seconds"], False, jax.devices())[0]


@pytest.mark.parametrize("fault", [None, *faults.FAULTS])
def test_count_run_correct_only_without_fault(fault):
    out = run_small(small_cell("count-drain"), 11, fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(out["metrics"]) == {"aggregated_rps", "setup_s"}


@pytest.mark.parametrize("fault", [None, "skip_verification"])
def test_sumvec_run_correct_only_without_control(fault):
    cell = small_cell("sumvec-drain", {"kind": "sumvec", "length": 8, "bits": 2})
    out = run_small(cell, 12, fault)
    assert out["correct"] is (fault is None), out["checks"]
