"""Datastore row models.

Equivalent of reference aggregator_core/src/datastore/models.rs
(LeaderStoredReport:78, AggregationJob:220, Lease:434,
ReportAggregation:586 + state:714, BatchAggregation:843 + state:1042,
CollectionJob:1055 + state:1182, AggregateShareJob:1287,
OutstandingBatch:1412, Batch:1473).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field, replace

from ..messages import (
    AggregationJobId,
    BatchId,
    CollectionJobId,
    Duration,
    HpkeCiphertext,
    Interval,
    PrepareError,
    ReportId,
    ReportIdChecksum,
    TaskId,
    Time,
)


class AggregationJobState(str, enum.Enum):
    """reference models.rs:374."""

    IN_PROGRESS = "in_progress"
    FINISHED = "finished"
    ABANDONED = "abandoned"
    DELETED = "deleted"


class ReportAggregationState(str, enum.Enum):
    """reference models.rs:714: Start / WaitingLeader(transition) /
    WaitingHelper(prep state) / Finished / Failed(error)."""

    START = "start"
    WAITING_LEADER = "waiting_leader"
    WAITING_HELPER = "waiting_helper"
    FINISHED = "finished"
    FAILED = "failed"


class BatchAggregationState(str, enum.Enum):
    """reference models.rs:1042."""

    AGGREGATING = "aggregating"
    COLLECTED = "collected"


class CollectionJobState(str, enum.Enum):
    """reference models.rs:1182."""

    START = "start"
    COLLECTABLE = "collectable"
    FINISHED = "finished"
    DELETED = "deleted"
    ABANDONED = "abandoned"


class BatchState(str, enum.Enum):
    """reference models.rs:1456."""

    OPEN = "open"
    CLOSING = "closing"
    CLOSED = "closed"


@dataclass(frozen=True)
class LeaderStoredReport:
    """A decrypted report at rest on the leader (reference models.rs:78)."""

    task_id: TaskId
    report_id: ReportId
    client_time: Time
    public_share: bytes
    leader_input_share: bytes  # decoded leader share, encrypted at rest
    helper_encrypted_input_share: HpkeCiphertext


@dataclass(frozen=True)
class SealedLeaderReport:
    """A stored leader report as the aggregation job read fetches it:
    the leader input share is still sealed under the datastore Crypter,
    so the read transaction does no decryption. `leader_input_share`
    opens it on each access, through the same Crypter call
    `get_client_report` makes."""

    task_id: TaskId
    report_id: ReportId
    client_time: Time
    public_share: bytes
    sealed_leader_input_share: bytes
    helper_encrypted_input_share: HpkeCiphertext
    crypter: object = field(repr=False, compare=False)

    @property
    def leader_input_share(self) -> bytes:
        """The plaintext share; raises ValueError where no key opens it."""
        return self.crypter.decrypt(
            "client_reports",
            self.task_id.data + self.report_id.data,
            "leader_input_share",
            self.sealed_leader_input_share,
        )


@dataclass(frozen=True)
class AggregationJobModel:
    """reference models.rs:220."""

    task_id: TaskId
    job_id: AggregationJobId
    aggregation_parameter: bytes
    partial_batch_identifier: bytes  # encoded PartialBatchSelector body ('' for time-interval)
    client_timestamp_interval: Interval
    state: AggregationJobState
    step: int
    last_request_hash: bytes | None = None
    # W3C traceparent persisted by whoever created the job (the leader's
    # job creator / the helper's init handler); both job drivers adopt it
    # so a step's spans join the creating trace across processes and
    # driver restarts (janus_tpu.trace.use_traceparent)
    trace_context: str | None = None

    def with_state(self, state: AggregationJobState) -> "AggregationJobModel":
        return replace(self, state=state)

    def with_step(self, step: int) -> "AggregationJobModel":
        return replace(self, step=step)

    def with_last_request_hash(self, h: bytes) -> "AggregationJobModel":
        return replace(self, last_request_hash=h)


@dataclass(frozen=True)
class ShardSpec:
    """Fleet shard predicate for the batched lease claims
    (docs/ARCHITECTURE.md "Running a fleet"): a replica owns the jobs
    whose persisted shard_key lands on its (shard_index mod
    shard_count); jobs OUTSIDE the shard become claimable only after
    they have sat eligible for steal_after_s — so a dead replica's
    shard drains instead of starving, while live replicas never
    contend on each other's rows."""

    shard_count: int = 1
    shard_index: int = 0
    steal_after_s: int = 30

    @property
    def active(self) -> bool:
        return self.shard_count > 1


@dataclass(frozen=True)
class Lease:
    """An acquired job lease (reference models.rs:434)."""

    token: bytes
    expiry: Time
    attempts: int


@dataclass(frozen=True)
class AcquiredAggregationJob:
    """reference models.rs:494. shard_key is the row's STORED shard
    hash at claim time (None from legacy constructors; < 0 = the
    affinity was released by a clean hand-back) — the steal classifier
    reads it so a rolling restart's hand-backs never count as
    steals."""

    task_id: TaskId
    job_id: AggregationJobId
    lease: Lease
    shard_key: int | None = None


@dataclass(frozen=True)
class AcquiredCollectionJob:
    """reference models.rs:540 (shard_key: see AcquiredAggregationJob)."""

    task_id: TaskId
    collection_job_id: CollectionJobId
    lease: Lease
    shard_key: int | None = None


@dataclass(frozen=True)
class ReportAggregationModel:
    """reference models.rs:586.

    prep_blob holds the serialized per-report prepare payload for the
    waiting states: the leader's transition (out share + verifier
    context) or the helper's prepare state; opaque at this layer and
    encrypted at rest.
    """

    task_id: TaskId
    job_id: AggregationJobId
    report_id: ReportId
    client_time: Time
    ord: int
    state: ReportAggregationState
    prep_blob: bytes = b""
    prepare_error: PrepareError | None = None

    def finished(self) -> "ReportAggregationModel":
        return replace(self, state=ReportAggregationState.FINISHED, prep_blob=b"")

    def failed(self, err: PrepareError) -> "ReportAggregationModel":
        return replace(
            self, state=ReportAggregationState.FAILED, prep_blob=b"", prepare_error=err
        )


@dataclass(frozen=True)
class BatchAggregation:
    """One shard of a batch's running aggregate (reference models.rs:843).

    Sharding exists to spread row contention (the reference picks a
    random shard 0..shard_count at accumulate time, accumulator.rs:92).
    """

    task_id: TaskId
    batch_identifier: bytes  # encoded Interval or BatchId
    aggregation_parameter: bytes
    ord: int
    state: BatchAggregationState
    aggregate_share: bytes | None  # encoded field vector, None for empty shard
    report_count: int
    client_timestamp_interval: Interval
    checksum: ReportIdChecksum

    def merged_with(self, other: "BatchAggregation") -> "BatchAggregation":
        """Merge another shard-update into this one (same key)."""
        assert self.ord == other.ord and self.batch_identifier == other.batch_identifier
        raise NotImplementedError("merge happens in the aggregator layer with field math")


@dataclass(frozen=True)
class CollectionJobModel:
    """reference models.rs:1055."""

    task_id: TaskId
    collection_job_id: CollectionJobId
    query: bytes  # encoded Query
    aggregation_parameter: bytes
    batch_identifier: bytes
    state: CollectionJobState
    report_count: int | None = None
    client_timestamp_interval: Interval | None = None
    leader_aggregate_share: bytes | None = None  # encrypted at rest
    helper_encrypted_aggregate_share: bytes | None = None
    # W3C traceparent persisted by the collection-create handler; the
    # collection job driver adopts it (see AggregationJobModel)
    trace_context: str | None = None


@dataclass(frozen=True)
class AggregateShareJob:
    """Helper-side record of a served aggregate share (reference models.rs:1287)."""

    task_id: TaskId
    batch_identifier: bytes
    aggregation_parameter: bytes
    helper_aggregate_share: bytes  # encoded field vector, encrypted at rest
    report_count: int
    checksum: ReportIdChecksum


@dataclass(frozen=True)
class Batch:
    """reference models.rs:1473."""

    task_id: TaskId
    batch_identifier: bytes
    aggregation_parameter: bytes
    state: BatchState
    outstanding_aggregation_jobs: int
    client_timestamp_interval: Interval


@dataclass(frozen=True)
class OutstandingBatch:
    """A fixed-size batch being filled (reference models.rs:1412)."""

    task_id: TaskId
    batch_id: BatchId
    time_bucket_start: Time | None
    size: int = 0  # reports assigned so far (incl. in-flight)
