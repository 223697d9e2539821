"""Whether the served run is correct, by a plain reference.

The reference is numpy over the seeded measurements: the sum of the
measurements of every valid report that was stored (the backlog, and
each upload the leader acknowledged with 201), and their count. It
takes nothing from the program but the collected result and, from each
aggregator's datastore, the verdict it recorded on each report.

Every comparison is exact, so every limit is 0:

- `aggregate_diff`: elements of the collected aggregate that differ
  from the reference sum;
- `report_count_diff`: how far the collected report count is from the
  number of valid stored reports;
- `verdict_diff`: reports whose recorded verdicts are not exactly one
  `finished` (valid) or one `failed` (invalid) on each aggregator, plus
  recorded reports that no client sent.
"""

from __future__ import annotations

import numpy as np

LIMITS = {"aggregate_diff": 0, "report_count_diff": 0, "verdict_diff": 0}


def reference(measurements: np.ndarray, valid: np.ndarray) -> tuple[np.ndarray, int]:
    """(aggregate, report count) of the valid reports. Measurements are
    below 2**16 and a run holds far fewer than 2**40 reports, so int64
    sums exactly."""
    agg = np.asarray(measurements, dtype=np.int64)[valid].sum(axis=0)
    return np.atleast_1d(agg).astype(object), int(valid.sum())


def verdict_misses(report_ids, stored, invalid, verdicts: dict) -> int:
    misses = 0
    seen = set()
    for rid, ok_stored, bad in zip(report_ids, stored, invalid):
        if not ok_stored:
            continue
        seen.add(rid)
        if verdicts.get(rid) != ["failed" if bad else "finished"]:
            misses += 1
    misses += sum(1 for rid in verdicts if rid not in seen)
    return misses


def compare(corpus, stored: np.ndarray, collected_count: int, collected_agg, leader_verdicts, helper_verdicts) -> dict:
    """{name: [value, limit]} of every comparison."""
    valid = stored & ~corpus.invalid
    agg, count = reference(corpus.measurements, valid)
    got = np.atleast_1d(np.asarray(collected_agg, dtype=object))
    agg_diff = int(len(agg)) if got.shape != agg.shape else int((got != agg).sum())
    values = {
        "aggregate_diff": agg_diff,
        "report_count_diff": abs(int(collected_count) - count),
        "verdict_diff": sum(
            verdict_misses(corpus.report_ids, stored, corpus.invalid, v)
            for v in (leader_verdicts, helper_verdicts)
        ),
    }
    return {k: [v, LIMITS[k]] for k, v in values.items()}


def correct(checks: dict) -> bool:
    return all(v <= limit for v, limit in checks.values())
