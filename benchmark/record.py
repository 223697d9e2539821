"""What one run saw, as the metric readers (`metrics/<name>.py`) read it.

Each reader has one function, `read(record) -> float | None`; None
means it found nothing to read, and the harness leaves the metric out.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field


def _matches(sample_labels: dict, labels: dict) -> bool:
    return all(sample_labels.get(k) == v for k, v in labels.items())


@dataclass
class Record:
    setup_s: float
    window_s: float
    t0: float  # window start, time.monotonic()
    t1: float  # window end
    uploads: list  # (status, latency s from due time, lateness s) per timed upload
    job_sizes: dict  # job id -> reports, every job of the run
    job_states: dict  # job id -> final state
    last_done: dict  # job id -> time its last step finished
    snapshots: dict  # "start" | "end" | "drained" -> metrics registry snapshot
    trace: object = None  # trace_reduce.Reduction of a traced run
    extra: dict = field(default_factory=dict)

    def aggregated(self) -> tuple[int, float]:
        """(reports, seconds): every report of the jobs that finished
        inside the window, and the time from the window's start to the
        last of those finishes. A job still running at the close adds
        neither."""
        done = [
            (jid, t)
            for jid, t in self.last_done.items()
            if t <= self.t1 and self.job_states.get(jid) == "finished"
        ]
        if not done:
            return 0, 0.0
        return sum(self.job_sizes[j] for j, _ in done), max(t for _, t in done) - self.t0

    def upload_stats(self) -> dict:
        """The window's uploads: how many, how many were refused, and the
        latency percentiles of the answered ones from their due times,
        over all and over the first and second half of the schedule. A
        second half slower than the first means the queue grew."""

        def pct_ms(rows, q):
            lat = sorted(r[1] for r in rows if r is not None and r[0] == 201)
            return lat[math.ceil(q * len(lat)) - 1] * 1e3 if lat else None

        half = len(self.uploads) // 2
        first, second = self.uploads[:half], self.uploads[half:]
        return {
            "uploads": len(self.uploads),
            "refused": sum(1 for r in self.uploads if r is None or r[0] != 201),
            "p95_ms": pct_ms(self.uploads, 0.95),
            "p50_first_half_ms": pct_ms(first, 0.5),
            "p50_second_half_ms": pct_ms(second, 0.5),
            "p95_first_half_ms": pct_ms(first, 0.95),
            "p95_second_half_ms": pct_ms(second, 0.95),
        }

    def reports_total(self) -> int:
        return sum(self.job_sizes.values())

    def _samples(self, name: str, snap: str, labels: dict) -> list:
        metric = self.snapshots[snap].get(name)
        if metric is None:
            return []
        return [s for s in metric["samples"] if _matches(s["labels"], labels)]

    def counter(self, name: str, a: str = "start", b: str = "end", **labels) -> float:
        """Change of a counter between two snapshots, summed over the
        label sets that carry `labels`."""
        return sum(s["value"] for s in self._samples(name, b, labels)) - sum(
            s["value"] for s in self._samples(name, a, labels)
        )

    def histogram(self, name: str, a: str = "start", b: str = "end", **labels) -> tuple[int, float]:
        """(observations, sum) a histogram gained between two snapshots."""

        def total(snap):
            ss = self._samples(name, snap, labels)
            return sum(s["count"] for s in ss), sum(s["sum"] for s in ss)

        (n0, s0), (n1, s1) = total(a), total(b)
        return n1 - n0, s1 - s0
