"""The readers of the wait metrics (`metrics/datastore_lock_wait_ms_per_report.py`,
`upload_lock_wait_ms.py`, `step_queue_wait_ms.py`,
`helper_hpke_us_per_report.py`) against a `Record` with hand-made
registry snapshots: window deltas over the right label sets, and None
where the program under test records nothing to read."""

import pytest

from record import Record
from spec import load_reader


def _hist(labels, count, total):
    return {"labels": labels, "count": count, "sum": total, "buckets": {}}


def _snapshot(scale):
    """A registry snapshot whose numbers grow with `scale` (0 at the
    window's start, 1 at its end)."""
    return {
        "janus_database_transaction_phase_seconds": {
            "type": "histogram",
            "samples": [
                _hist({"tx": "upload_batch", "phase": "lock_wait"}, 4 * scale, 2.0 * scale),
                _hist({"tx": "upload_batch", "phase": "body"}, 4 * scale, 0.4 * scale),
                _hist({"tx": "step_agg_job_read", "phase": "lock_wait"}, 10 * scale, 3.0 * scale),
            ],
        },
        "janus_step_pipeline_queue_wait_seconds": {
            "type": "histogram",
            "samples": [
                _hist({"stage": "read"}, 10 * scale, 1.0 * scale),
                _hist({"stage": "device"}, 20 * scale, 4.0 * scale),
                _hist({"stage": "staging"}, 10 * scale, 0.5 * scale),
            ],
        },
        "janus_aggregate_init_stage_seconds": {
            "type": "histogram",
            "samples": [
                _hist({"stage": "hpke_stage"}, 2 * scale, 0.25 * scale),
                _hist({"stage": "write_tx"}, 2 * scale, 9.0 * scale),
            ],
        },
        "janus_engine_rows_total": {
            "type": "counter",
            "samples": [
                {"labels": {"op": "helper_init"}, "value": 1000 * scale},
                {"labels": {"op": "leader_init"}, "value": 1000 * scale},
            ],
        },
    }


def _record(start, end):
    return Record(
        setup_s=1.0,
        window_s=10.0,
        t0=0.0,
        t1=10.0,
        uploads=[],
        job_sizes={b"a": 500, b"b": 500},
        job_states={b"a": "finished", b"b": "finished"},
        last_done={b"a": 4.0, b"b": 8.0},
        snapshots={"start": start, "end": end},
    )


@pytest.fixture
def rec():
    return _record(_snapshot(0), _snapshot(1))


@pytest.fixture
def bare():
    """What the run of a program without the wait counters records."""
    return _record({}, {})


@pytest.mark.parametrize(
    "metric, expect",
    [
        # (2.0 + 3.0) s of lock wait over the 1,000 reports aggregated
        ("datastore_lock_wait_ms_per_report", 5.0),
        # 2.0 s over 4 upload group commits
        ("upload_lock_wait_ms", 500.0),
        # mean waits 0.1 + 0.2 + 0.05 s over the stages
        ("step_queue_wait_ms", 350.0),
        # 0.25 s of HPKE open over 1,000 helper-init rows
        ("helper_hpke_us_per_report", 250.0),
    ],
)
def test_reader_takes_window_deltas(rec, metric, expect):
    assert load_reader(metric)(rec) == pytest.approx(expect)


@pytest.mark.parametrize(
    "metric",
    [
        "datastore_lock_wait_ms_per_report",
        "upload_lock_wait_ms",
        "step_queue_wait_ms",
        "helper_hpke_us_per_report",
    ],
)
def test_reader_finds_nothing_without_the_counters(bare, metric):
    assert load_reader(metric)(bare) is None
