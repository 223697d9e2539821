"""The reader of `share_decode_us_per_report` against a `Record` with
hand-made registry snapshots: the histogram's window sum over the
counter's window delta, and None where the program records neither."""

import pytest

from record import Record
from spec import load_reader


def _snapshot(scale):
    return {
        "janus_leader_share_decode_seconds": {
            "type": "histogram",
            "samples": [
                {"labels": {}, "count": 4 * scale, "sum": 0.6 * scale, "buckets": {}},
            ],
        },
        "janus_leader_shares_decoded_total": {
            "type": "counter",
            "samples": [
                {"labels": {"result": "ok"}, "value": 1980 * scale},
                {"labels": {"result": "rejected"}, "value": 20 * scale},
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
        job_sizes={b"a": 500},
        job_states={b"a": "finished"},
        last_done={b"a": 4.0},
        snapshots={"start": start, "end": end},
    )


@pytest.mark.parametrize(
    "start, end, expect",
    [
        # 0.6 s over 4 jobs' 2,000 reports, both results counted
        (_snapshot(0), _snapshot(1), 300.0),
        # a window that starts after the first job: deltas only
        (_snapshot(1), _snapshot(3), 300.0),
        # a program without the pass
        ({}, {}, None),
    ],
    ids=["window", "delta", "absent"],
)
def test_share_decode_reader(start, end, expect):
    got = load_reader("share_decode_us_per_report")(_record(start, end))
    assert got == (pytest.approx(expect) if expect is not None else None)
