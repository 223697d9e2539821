"""Aggregation job driver (leader stepper) — the hot path.

Equivalent of reference aggregator/src/aggregator/aggregation_job_driver.rs:
49-894: acquire leases, read job + report state, run leader prepare,
PUT the init request to the helper, process its response, accumulate,
write back, release. The reference's three per-report loops
(leader_initialized :329-402, transition evaluation :467-496,
leader_continued + accumulate :530-726) are each one batched device
call here.

For the 1-round Prio3 VDAFs the whole job completes in a single step:
init -> helper responds finish/reject per report -> leader verifies the
prep message (joint-rand seed equality, host-side lane compare) ->
masked accumulate. Crash anywhere before the final write leaves the
job in step 0 with reports in START; the re-acquired lease replays the
init idempotently (helper request-hash dedup).
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from ..core.circuit_breaker import (
    CircuitBreakerConfig,
    CircuitOpenError,
    OutboundCircuitBreakers,
    default_breakers,
    peer_label,
)
from ..core.deadline import (
    DEADLINE_EXCEEDED_STATUS,
    DeadlineExceeded,
    current_deadline,
    deadline_scope,
)
from ..core.retries import Backoff, RequestAborted, retry_http_request
from ..datastore.models import (
    AcquiredAggregationJob,
    AggregationJobState,
    ReportAggregationState,
)
from .. import ledger, metrics
from ..datastore.store import Datastore
from ..messages import (
    AggregationJobContinueReq,
    AggregationJobInitializeReq,
    AggregationJobResp,
    AggregationJobStep,
    Duration,
    PartialBatchSelector,
    PreEncoded,
    PrepareError,
    PrepareInit,
    PrepareStepResult,
    ReportIdChecksum,
    ReportShare,
    ReportMetadata,
    decode_prepare_resps_fast,
    encode_report_share_raw,
)
from ..messages.codec import DecodeError
from ..task import Task
from ..vdaf.registry import circuit_for
from ..vdaf.wire import (
    PP_CONTINUE,
    PP_FINISH,
    PP_INITIALIZE,
    Prio3Wire,
    decode_pingpong,
    encode_field_rows,
    encode_pingpong,
    encode_pingpong_share_column,
    flat_scatter_indices,
    pingpong_finish_frame_matches,
    put_field_row,
    rows_in_range,
    seeds_to_lanes,
)
from .accumulator import (
    Accumulator,
    accumulate_batched,
    bucket_metadata,
    fixed_size_batch_id,
    group_batch_buckets,
)
from .engine_cache import DeviceHangError, EngineCache, bucket_size, engine_cache

log = logging.getLogger(__name__)


def _err_or_default(err) -> PrepareError:
    """PrepareError.BATCH_COLLECTED has enum value 0 (falsy), so the
    `err or DEFAULT` idiom silently rewrites it; compare against None."""
    return err if err is not None else PrepareError.VDAF_PREP_ERROR


# watchdog bound for resident-state fetches issued from threads with no
# ambient lease deadline (background flusher, drain): long enough for a
# busy device to answer, short enough that a wedged one can't park the
# flush pass holding the engine's resident lock
RESIDENT_FLUSH_FETCH_BOUND_S = 30.0


@dataclass
class ResidentConfig:
    """Device-resident accumulator knobs (YAML `resident_accumulators:`
    stanza of the driver binary; docs/ARCHITECTURE.md "Resident
    aggregate state"). Disabled by default: resident mode trades the
    per-job share fetch + write for a bounded durability window (a HARD
    crash — not drain/eviction/quarantine, which all flush — loses the
    unflushed window; see ROBUSTNESS.md fault matrix)."""

    enabled: bool = False
    # flush-to-datastore cadence for dirty resident buffers (also the
    # background flusher's pass interval); the loss window of a hard
    # crash is bounded by roughly this much accumulation
    flush_interval_s: float = 5.0

    @classmethod
    def from_dict(cls, d: dict | None) -> "ResidentConfig":
        d = d or {}
        return cls(
            enabled=bool(d.get("enabled", False)),
            flush_interval_s=float(d.get("flush_interval_secs", 5.0)),
        )


@dataclass
class AggregationJobDriverConfig:
    batch_aggregation_shard_count: int = 1
    maximum_attempts_before_failure: int = 10
    http_backoff: Backoff = Backoff()
    # helper HTTP work is bounded by lease remaining minus this skew
    # (reference job_driver.rs:191-196) so a hung helper can't outlive
    # the lease and run the job concurrently with a re-acquirer
    worker_lease_clock_skew_s: int = 60
    # leader->helper outbound circuit breaker (core/circuit_breaker.py;
    # YAML outbound_circuit_breaker: section)
    circuit_breaker: CircuitBreakerConfig | None = None
    # floor for the breaker-open step-back reacquire delay so a job
    # whose cooldown is nearly over doesn't spin acquire/step-back
    min_step_back_delay_s: int = 1
    # device-resident accumulator state (ISSUE 12)
    resident: ResidentConfig = field(default_factory=ResidentConfig)


@dataclass
class InitStepState:
    """Carrier of one prio3 init step through the stage chain. The
    serial stepper and the step_pipeline schedule the SAME stage
    methods over this state, so the two execution modes cannot drift:
    stage_init fills the staging columns, device_init the device
    outputs, http_init the accept/continue columns, and the commit
    stages consume them."""

    acquired: AcquiredAggregationJob
    task: Task
    job: object
    pending: list
    reports: dict
    wire: Prio3Wire
    engine: object
    multi_round: bool
    # columnar staging (host prefetch stage)
    meas: object = None
    proof: object = None
    nonce_lanes: object = None
    blind_lanes: object = None
    public_parts: object = None
    ok: object = None
    failed: list = field(default_factory=list)
    # device init outputs (device lane)
    out0: object = None
    seed0: object = None
    ver0: object = None
    part0: object = None
    # HTTP leg outputs
    accept: object = None
    continue_msgs: list | None = None
    # accumulate output (device lane)
    accumulator: Accumulator | None = None
    # double-buffered staging handle (engine.prestage_leader, issued by
    # the pipeline's read stage while the lane runs the previous job)
    prestaged: object = None
    # resident-accumulate handles (device PendingDeltas + the per-bucket
    # merge entries), consumed post-commit by commit_finish
    resident_delta: object = None
    resident_entries: list | None = None
    resident_rids: list | None = None
    # block-sparse tasks (ISSUE 17): per-lane PUBLIC block indices from
    # the decoded public shares ([n, max_blocks] int32, -1 padding /
    # failed lanes) — the accumulate stages expand them to flat scatter
    # targets. NOT cleared by the pipeline's device-init stage: the
    # accumulate leg runs after HTTP, long after staging columns drop.
    block_idx: object = None


class AggregationJobDriver:
    """reference aggregation_job_driver.rs:49."""

    def __init__(
        self,
        ds: Datastore,
        http,
        cfg: AggregationJobDriverConfig | None = None,
        breakers: OutboundCircuitBreakers | None = None,
        stopper=None,
        peer_health=None,
    ):
        self.ds = ds
        self.http = http
        self.cfg = cfg or AggregationJobDriverConfig()
        # per-peer circuit breaker shared process-wide by default (the
        # collection driver sees the same helper health)
        self.breakers = (
            breakers if breakers is not None else default_breakers(self.cfg.circuit_breaker)
        )
        # peer-outage parking tracker (peer_health.PeerHealthTracker);
        # None = no parking, per-step breaker step-backs only
        self.peer_health = peer_health
        # shutdown Stopper: in-flight helper retries abort on SIGTERM so
        # the step can step back instead of spending the whole lease
        self.stopper = stopper
        # resident-flush cadence state (ISSUE 12): the last time this
        # driver pushed dirty resident buffers through the write-tx path
        self._resident_flush_lock = threading.Lock()
        # seeded to "now" so the first inline flush waits a full
        # interval (0.0 would flush on the very first commit: monotonic
        # time is process uptime, always past the interval)
        self._resident_last_flush = time.monotonic()

    # --- JobDriver callbacks (reference :840-894) ---
    def acquirer(self, lease_duration_s: int = 600, fleet=None):
        """Batched claim acquirer. `fleet` (config.FleetConfig) adds
        the shard predicate + steal-after fallback and stamps this
        replica's provenance tag into every minted lease token
        (docs/ARCHITECTURE.md "Running a fleet")."""
        from .job_driver import make_claim_acquirer

        shard = fleet.shard_spec() if fleet is not None else None
        holder = fleet.holder_tag() if fleet is not None else None
        return make_claim_acquirer(
            self.ds,
            "aggregation",
            lambda limit: self.ds.run_tx(
                lambda tx: tx.acquire_incomplete_aggregation_jobs(
                    Duration(lease_duration_s), limit, shard=shard, holder=holder
                ),
                "acquire_agg_jobs",
            ),
            shard=shard,
            peer_gate=self.peer_health.park_gate()
            if self.peer_health is not None
            else None,
        )

    def _lease_deadline(self, acquired) -> float:
        from .job_driver import lease_deadline

        return lease_deadline(
            self.ds.clock, acquired.lease, self.cfg.worker_lease_clock_skew_s
        )

    def stepper(self, acquired: AcquiredAggregationJob) -> None:
        if acquired.lease.attempts > self.cfg.maximum_attempts_before_failure:
            self.abandon_job(acquired)
            return
        try:
            self.step_aggregation_job(acquired)
        except Exception as e:
            if self.handle_step_error(acquired, e):
                return
            log.exception(
                "aggregation job %s step failed (attempt %d)",
                acquired.job_id,
                acquired.lease.attempts,
            )
            raise

    def handle_step_error(self, acquired: AcquiredAggregationJob, e: Exception) -> bool:
        """Map a step failure to the step-back / attempt-ledger
        semantics. Returns True when the failure was translated into a
        step-back (lease released early, attempt refunded) — the step
        is NOT the job's fault and must not march it toward
        abandonment. Shared by the serial stepper and every
        step_pipeline stage, so a failure maps identically no matter
        which stage thread it surfaced on."""
        if isinstance(e, CircuitOpenError):
            # the helper's circuit is open: release the lease with the
            # cooldown as backoff instead of failing the step
            self.step_back(
                acquired,
                "circuit_open",
                max(e.retry_in_s, self.cfg.min_step_back_delay_s),
            )
            return True
        if isinstance(e, RequestAborted):
            # shutdown drain: hand the lease back immediately
            self.step_back(acquired, "shutdown_drain", 0.0)
            return True
        if isinstance(e, DeadlineExceeded):
            # the lease budget died (expired lease, retry loop past the
            # bound, or the helper answered the conclusive 408): dead
            # work is dropped here and redone under a fresh lease —
            # never amplified by burning the attempt ledger
            self.step_back(acquired, "deadline_expired", 0.0)
            return True
        if isinstance(e, DeviceHangError):
            # the device dispatch hung and was abandoned; the engine is
            # quarantined (host fallback serves the retry) — not this
            # job's fault, step back with a short reacquire delay
            self.step_back(acquired, "device_hang", self.cfg.min_step_back_delay_s)
            return True
        from .job_driver import datastore_reconnect_delay_s, is_datastore_connection_error

        if is_datastore_connection_error(self.ds, e):
            # datastore outage mid-step: step back with the reconnect
            # cooldown (best effort; if the step-back tx also fails,
            # the lease ages out)
            self.step_back(
                acquired, "datastore_down", datastore_reconnect_delay_s(self.ds)
            )
            return True
        return False

    def step_back(
        self, acquired: AcquiredAggregationJob, reason: str, delay_s: float
    ) -> None:
        """Release the lease early (reacquirable after delay_s, attempt
        refunded) — a breaker-open helper or a draining process must
        neither burn lease TTLs nor march the job toward abandonment."""
        from ..datastore.store import TxConflict

        delay = max(0, int(delay_s))
        log.warning(
            "stepping back aggregation job %s (%s): lease released, reacquirable in %ds",
            acquired.job_id, reason, delay,
        )
        metrics.job_step_back_total.add(reason=reason, **metrics.replica_labels())
        # a shutdown drain is a clean hand-back to the REST of the
        # fleet: backdate the eligible-since so any surviving replica
        # claims it immediately, never waiting out the steal fence
        handback = reason == "shutdown_drain"
        try:
            self.ds.run_tx(
                lambda tx: tx.step_back_aggregation_job(
                    acquired,
                    reacquire_delay_s=delay,
                    count_attempt=False,
                    handback=handback,
                ),
                "step_back_agg_job",
            )
        except TxConflict:
            # lease already lost (expired / re-acquired): nothing to return
            log.info("step-back of %s found the lease already gone", acquired.job_id)
        except Exception:
            # datastore unreachable: the lease ages out on its own TTL —
            # the step-back is an optimization, never a correctness need
            log.warning(
                "step-back of %s could not reach the datastore; lease will age out",
                acquired.job_id,
            )

    def _stage_pending(self, task, wire, engine, pending, reports):
        """Single-pass staging of a job's stored leader shares into
        device-ready arrays + per-report failure marks. Each sealed share
        is decrypted and its u64 words written straight into zeroed limb
        columns of the job's bucket row count; one vectorised range
        check follows. The engine gets the first n rows as views, so
        `pad_args` hands the padded columns to the device without a
        copy."""
        from ..trace import span

        n = len(pending)
        circ = wire.circ
        jf = engine.p3.jf
        # the padded row count the engine dispatches (a job past the
        # engine's cap is chunked from its n rows, so it gets no padding)
        rows = max(n, bucket_size(n, getattr(engine, "bucket_cap", None)))
        meas = tuple(np.zeros((rows, circ.input_len), np.uint64) for _ in range(jf.LIMBS))
        proof = tuple(np.zeros((rows, circ.proof_len), np.uint64) for _ in range(jf.LIMBS))
        jr_at = (circ.input_len + circ.proof_len) * jf.LIMBS  # blind's first word
        blind_lanes = np.zeros((n, 2), np.uint64) if wire.uses_jr else None
        shaped = np.zeros(n, dtype=bool)  # share decrypted at its exact length
        part_rows0: list[bytes | None] = [None] * n
        part_rows1: list[bytes | None] = [None] * n
        failed = [None] * n  # PrepareError or None
        idx_rows: list | None = [None] * n if wire.sparse else None
        with span("driver.share_decode", batch=n):
            for i, ra in enumerate(pending):
                rep = reports.get(ra.report_id.data)
                if rep is None:
                    failed[i] = PrepareError.REPORT_DROPPED
                    continue
                payload = rep.leader_input_share  # decrypts; ValueError fails the step
                if len(payload) != wire.leader_share_len:
                    failed[i] = PrepareError.INVALID_MESSAGE
                    continue
                words = np.frombuffer(payload, dtype="<u8")
                put_field_row(meas, i, words[: circ.input_len * jf.LIMBS])
                put_field_row(proof, i, words[circ.input_len * jf.LIMBS : jr_at])
                shaped[i] = True
                if wire.uses_jr:
                    blind_lanes[i] = words[jr_at:]
                    try:
                        parts = wire.decode_public_share(rep.public_share)
                        part_rows0[i], part_rows1[i] = parts
                        if idx_rows is not None:
                            # validated PUBLIC block indices (the sparse
                            # decode rejects out-of-range / unsorted rows)
                            idx_rows[i] = parts.indices
                    except DecodeError:
                        failed[i] = PrepareError.INVALID_MESSAGE
            meas = tuple(c[:n] for c in meas)
            proof = tuple(c[:n] for c in proof)
            ok_m = shaped & rows_in_range(meas, jf.MODULUS)
            ok_p = shaped & rows_in_range(proof, jf.MODULUS)
            # zero out-of-range rows so device math stays in range
            for cols, col_ok in ((meas, ok_m), (proof, ok_p)):
                if not col_ok.all():
                    for c in cols:
                        c[~col_ok] = 0

        # test-only fake failure injection on the leader init path
        # (the reference's dummy_vdaf prep_init_fn hook)
        if task.vdaf.fails_at("init"):
            for i in range(n):
                if failed[i] is None:
                    failed[i] = PrepareError.VDAF_PREP_ERROR

        nonce_lanes, _ = seeds_to_lanes([ra.report_id.data for ra in pending])
        ok = ok_m & ok_p & np.array([f is None for f in failed], dtype=bool)
        if wire.uses_jr:
            p0, ok_p0 = seeds_to_lanes(part_rows0)
            p1, ok_p1 = seeds_to_lanes(part_rows1)
            ok = ok & ok_p0 & ok_p1
            public_parts = np.stack([p0, p1], axis=1)
        else:
            public_parts = None
        if idx_rows is not None:
            block_idx = np.full((n, circ.max_blocks), -1, dtype=np.int32)
            for i, row in enumerate(idx_rows):
                if row is not None:
                    block_idx[i] = row
        else:
            block_idx = None
        n_ok = int(ok.sum())
        metrics.leader_shares_decoded_total.add(n_ok, result="ok")
        metrics.leader_shares_decoded_total.add(n - n_ok, result="rejected")
        return meas, proof, nonce_lanes, blind_lanes, public_parts, ok, failed, block_idx

    # --- the step (reference :102-726), decomposed into the stage
    # methods the step_pipeline schedules across its executors. The
    # serial path below composes exactly the same stages in order, so
    # the pipelined and classic steppers cannot drift apart. ---
    def read_job(self, acquired: AcquiredAggregationJob):
        """tx1: read everything (reference :144-233). Runs on the
        pipeline's prefetch stage — job k+1's read overlaps job k's
        device/HTTP phases."""

        def read(tx):
            task = tx.get_task(acquired.task_id)
            job = tx.get_aggregation_job(acquired.task_id, acquired.job_id)
            ras = tx.get_report_aggregations_for_job(acquired.task_id, acquired.job_id)
            # the START rows' reports with their leader shares still
            # sealed: staging opens each outside this transaction
            reports = tx.get_sealed_client_reports_for_job(
                acquired.task_id, acquired.job_id
            )
            return task, job, ras, reports

        from ..trace import span

        with span("driver.read_tx"):
            return self.ds.run_tx(read, "step_agg_job_read")

    def release_job(self, acquired: AcquiredAggregationJob) -> None:
        self.ds.run_tx(lambda tx: tx.release_aggregation_job(acquired), "release")

    def step_aggregation_job(self, acquired: AcquiredAggregationJob) -> None:
        task, job, ras, reports = self.read_job(acquired)
        if job is None or task is None:
            raise RuntimeError("job or task vanished while leased")
        if job.state != AggregationJobState.IN_PROGRESS:
            self.release_job(acquired)
            return

        from ..trace import use_traceparent

        # adopt the trace the job's CREATOR persisted in the row: every
        # span below (stage/encode/http/engine/write — and the helper's
        # handler spans, via the propagated traceparent header) joins
        # that trace, no matter which driver process steps the job or
        # how many restarts separate the steps. The lease budget rides
        # the same scope (core/deadline.py): the engine watchdog bounds
        # device dispatches with it and the HTTP client stamps the
        # remainder on outbound helper requests (DAP-Janus-Deadline).
        with use_traceparent(job.trace_context), deadline_scope(
            self._lease_deadline(acquired)
        ):
            self._step_leased_job(acquired, task, job, ras, reports)

    def plan_step(self, acquired, task, job, ras):
        """Classify the leased step -> (kind, payload): 'continue'
        (WaitingLeader rows), 'poplar1', 'empty', or 'init' (the
        pipelined prio3 hot path) with the rows the stage works on."""
        waiting = [
            ra for ra in ras if ra.state == ReportAggregationState.WAITING_LEADER
        ]
        if waiting:
            return "continue", waiting
        pending = [ra for ra in ras if ra.state == ReportAggregationState.START]
        if task.vdaf.kind == "poplar1":
            return "poplar1", pending
        if not pending:
            return "empty", pending
        return "init", pending

    def _step_leased_job(self, acquired, task, job, ras, reports) -> None:
        kind, rows = self.plan_step(acquired, task, job, ras)
        if kind == "continue":
            # multi-round jobs park accepted reports in WaitingLeader
            # after init; a later step sends the continue request
            # (reference :439-514 CONTINUE path)
            self._continue_step(acquired, task, job, rows)
            return
        if kind == "poplar1":
            self._step_poplar1_init(acquired, task, job, rows, reports)
            return
        if kind == "empty":
            self.finish_empty(acquired, job)
            return
        st = self.stage_init(acquired, task, job, rows, reports)
        self.device_init(st)
        self.http_init(st)
        if st.multi_round:
            self.commit_park(st)
        else:
            self.device_accumulate(st)
            self.commit_finish(st)

    def finish_empty(self, acquired, job) -> None:
        # nothing to do; mark job finished
        def finish(tx):
            tx.update_aggregation_job(job.with_state(AggregationJobState.FINISHED))
            tx.release_aggregation_job(acquired)

        self.ds.run_tx(finish, "step_agg_job_finish_empty")

    def stage_init(self, acquired, task, job, pending, reports) -> "InitStepState":
        """Host stage: columnar staging of stored leader shares into
        device-ready arrays (prefetch stage under the pipeline)."""
        from ..trace import span

        wire = Prio3Wire(circuit_for(task.vdaf))
        engine = engine_cache(task.vdaf, task.vdaf_verify_key)
        n = len(pending)
        with span("driver.stage", batch=n):
            (
                meas,
                proof,
                nonce_lanes,
                blind_lanes,
                public_parts,
                ok,
                failed,
                block_idx,
            ) = self._stage_pending(task, wire, engine, pending, reports)
        return InitStepState(
            acquired=acquired,
            task=task,
            job=job,
            pending=pending,
            reports=reports,
            wire=wire,
            engine=engine,
            multi_round=task.vdaf.rounds > 1,
            meas=meas,
            proof=proof,
            nonce_lanes=nonce_lanes,
            blind_lanes=blind_lanes,
            public_parts=public_parts,
            ok=ok,
            failed=failed,
            block_idx=block_idx,
        )

    def device_init(self, st: "InitStepState") -> None:
        """Device stage: batched leader prepare-init (reference hot
        loop :329-402). Owned by the pipeline's device lane. A
        prestaged column set (double-buffered staging: the read stage
        issued the H2D async while the lane ran the previous job) is
        consumed here; leader_init falls back to the host columns when
        it can't use it."""
        prestaged, st.prestaged = st.prestaged, None
        st.out0, st.seed0, st.ver0, st.part0 = st.engine.leader_init(
            st.nonce_lanes, st.public_parts, st.meas, st.proof, st.blind_lanes,
            ok=st.ok, prestaged=prestaged,
        )

    def http_init(self, st: "InitStepState") -> None:
        """HTTP stage: columnar request framing, the helper round trip,
        columnar response decode + host-side verification (reference
        :404-424 build/send, :530-726 response processing)."""
        from ..trace import span

        acquired, task, job, pending, reports = (
            st.acquired, st.task, st.job, st.pending, st.reports,
        )
        wire = st.wire
        n = len(pending)
        failed = st.failed
        # one vectorized framing pass over the whole batch (ISSUE 9):
        # the prep-share column becomes framed ping-pong messages in a
        # single numpy pass, and each PrepareInit body is spliced from
        # pre-encoded rows instead of running the Encoder per report
        with span("driver.encode_init", batch=n):
            frames = encode_pingpong_share_column(
                st.engine.p3.jf, st.ver0, st.part0 if wire.uses_jr else None
            )
            prep_inits = []
            send_idx = []
            for i, ra in enumerate(pending):
                if failed[i] is not None or not st.ok[i]:
                    if failed[i] is None:
                        failed[i] = PrepareError.INVALID_MESSAGE
                    continue
                rep = reports[ra.report_id.data]
                prep_inits.append(
                    PreEncoded(
                        encode_report_share_raw(
                            ra.report_id.data,
                            ra.client_time.seconds,
                            rep.public_share,
                            rep.helper_encrypted_input_share,
                        )
                        + frames.row(i)
                    )
                )
                send_idx.append(i)

        multi_round = st.multi_round
        accept = np.zeros(n, dtype=bool)
        continue_msgs: list[bytes | None] = [None] * n
        if prep_inits:
            req = AggregationJobInitializeReq(
                job.aggregation_parameter,
                PartialBatchSelector.from_bytes(job.partial_batch_identifier),
                tuple(prep_inits),
            )
            with span("driver.http_init", reports=len(prep_inits)):
                body = self._send_init_request_raw(
                    task, acquired.job_id, req, acquired=acquired
                )
            col = decode_prepare_resps_fast(body)
            mapping = self._match_resps(
                [pending[i].report_id.data for i in send_idx], col
            )
            # jr seed rows for the order-aligned verify below, one
            # vectorized conversion for the whole batch
            seed_rows = (
                np.ascontiguousarray(np.asarray(st.seed0, dtype="<u8")).view(np.uint8)
                if wire.uses_jr and not multi_round
                else None
            )
            # process response (reference :530-726), host-side lane checks
            for k, i in enumerate(send_idx):
                j = k if mapping is None else mapping[k]
                if j is None:
                    failed[i] = PrepareError.INVALID_MESSAGE
                    continue
                kind = col.kinds[j]
                if kind == PrepareStepResult.REJECT:
                    failed[i] = _err_or_default(col.errors[j])
                    continue
                msg = col.messages[j]
                if multi_round:
                    # helper answered ping-pong CONTINUE; the leader's
                    # next message (sent on a later step) finishes with
                    # the combined prep message (fake: echo)
                    if msg is None:
                        failed[i] = PrepareError.INVALID_MESSAGE
                        continue
                    try:
                        tag, prep_msg, _share = decode_pingpong(msg)
                    except DecodeError:
                        failed[i] = PrepareError.INVALID_MESSAGE
                        continue
                    if tag != PP_CONTINUE:
                        failed[i] = PrepareError.INVALID_MESSAGE
                        continue
                    continue_msgs[i] = encode_pingpong(PP_FINISH, prep_msg or b"", None)
                    accept[i] = True
                    continue
                if wire.uses_jr:
                    # the helper's answer must be finish(our jr seed):
                    # a two-compare fast path over the raw frame (the
                    # column decoder guarantees msg is exactly one
                    # well-formed self-delimiting frame)
                    verdict = (
                        pingpong_finish_frame_matches(msg, seed_rows[i].tobytes())
                        if msg is not None
                        else None
                    )
                    if verdict is None:
                        failed[i] = PrepareError.INVALID_MESSAGE
                        continue
                    if verdict is False:
                        failed[i] = PrepareError.VDAF_PREP_ERROR
                        continue
                accept[i] = True

        # test-only fake failure at the leader continue/evaluate stage
        # (the reference's dummy_vdaf prep_step_fn hook)
        if task.vdaf.fails_at("step"):
            for i in range(n):
                if accept[i]:
                    accept[i] = False
                    failed[i] = PrepareError.VDAF_PREP_ERROR

        st.accept = accept
        st.continue_msgs = continue_msgs

    def _match_resps(self, sent_ids: list[bytes], col) -> list[int | None] | None:
        """Order-aligned prepare-resp matching: DAP requires the helper
        to answer in request order, so verify alignment cheaply (one
        bytes compare per report, C speed) and skip the O(n) dict build.
        Returns None when aligned (identity mapping); otherwise counts
        the contract violation and falls back to the id->index dict."""
        if len(col.report_ids) == len(sent_ids) and all(
            a == b for a, b in zip(col.report_ids, sent_ids)
        ):
            return None
        metrics.prep_resp_order_mismatch_total.add()
        by_id = {rid: j for j, rid in enumerate(col.report_ids)}
        return [by_id.get(rid) for rid in sent_ids]

    def commit_park(self, st: "InitStepState") -> None:
        """Commit stage, multi-round: park accepted reports as
        WaitingLeader(out_share || msg); job stays in progress — a later
        driver step sends the continue request (reference stores the
        transition the same way, models.rs:714 WaitingLeader)."""
        import dataclasses

        out0_rows = encode_field_rows(st.engine.p3.jf, st.out0)
        new_ras = []
        for i, ra in enumerate(st.pending):
            if st.accept[i]:
                msg = st.continue_msgs[i]
                blob = len(msg).to_bytes(4, "big") + msg + out0_rows[i]
                new_ras.append(
                    dataclasses.replace(
                        ra,
                        state=ReportAggregationState.WAITING_LEADER,
                        prep_blob=blob,
                    )
                )
            else:
                err = _err_or_default(st.failed[i])
                metrics.aggregate_step_failure_counter.add(type=err.name.lower())
                new_ras.append(ra.failed(err))

        acquired = st.acquired

        task_id = st.task.task_id

        def write_waiting(tx):
            for ra in new_ras:
                tx.update_report_aggregation(ra)
            # conservation ledger: FAILED rows reach their terminal here
            # (parked WAITING rows stay in-flight) — booked in the same
            # tx so a run_tx retry can't double-count
            ledger.count_ra_outcomes(
                tx, task_id, new_ras,
                aggregation_parameter=st.job.aggregation_parameter,
            )
            tx.release_aggregation_job(acquired)

        self.ds.run_tx(write_waiting, "step_agg_job_park")

    def device_accumulate(self, st: "InitStepState") -> None:
        """Device stage: masked accumulate (reference
        Accumulator::update :605-627). Owned by the device lane.

        Resident mode (ISSUE 12): instead of one masked reduce + host
        fetch per batch bucket, compute ALL buckets' sums as one device
        PendingDeltas (one [n] int32 upload, zero fetch) and record
        share=None entries in the job's Accumulator — the share bytes
        stay in device memory and merge into the engine's resident
        buffers only after the write tx commits (commit_finish). The
        classic path remains the fallback whenever the engine can't
        serve it (host fallback/quarantine) or the delta dispatch fails
        for a non-hang reason."""
        from ..trace import span

        st.accumulator = Accumulator(st.task, self.cfg.batch_aggregation_shard_count)
        metadatas = [ReportMetadata(ra.report_id, ra.client_time) for ra in st.pending]
        pbs = PartialBatchSelector.from_bytes(st.job.partial_batch_identifier)
        bid_fixed = fixed_size_batch_id(pbs)
        with span("driver.accumulate", batch=len(st.pending)):
            if (
                self.cfg.resident.enabled
                and isinstance(st.engine, EngineCache)
                and st.engine.resident_ready()
                and self._device_accumulate_resident(st, metadatas, bid_fixed)
            ):
                return
            accumulate_batched(
                st.task,
                st.engine,
                st.accumulator,
                st.out0,
                st.accept,
                metadatas,
                batch_identifier=bid_fixed,
                flat_idx=self._flat_idx(st),
            )

    @staticmethod
    def _flat_idx(st: "InitStepState"):
        """[n, compact_len] int32 scatter targets for a sparse job's
        staged block indices; None on dense tasks."""
        if not st.wire.sparse or st.block_idx is None:
            return None
        return flat_scatter_indices(st.block_idx, st.wire.circ)

    def _device_accumulate_resident(self, st, metadatas, bid_fixed) -> bool:
        """Resident accumulate attempt. True = st.accumulator holds
        share=None entries and st.resident_delta carries the device
        sums; False = caller must run the classic path."""
        n = len(metadatas)
        buckets = group_batch_buckets(st.task, metadatas, st.accept, bid_fixed)
        if not buckets:
            return True  # nothing accepted; nothing to merge either
        keys = list(buckets)
        lane_bucket = np.full(n, -1, dtype=np.int32)
        for j, bid in enumerate(keys):
            lane_bucket[buckets[bid]] = j
        try:
            delta = st.engine.aggregate_pending(
                st.out0, lane_bucket, len(keys), flat_idx=self._flat_idx(st)
            )
        except (DeviceHangError, DeadlineExceeded):
            raise  # step-back semantics, identical to the classic path
        except Exception:
            log.warning(
                "resident accumulate failed for job %s; falling back to the "
                "classic per-bucket path",
                st.acquired.job_id,
                exc_info=True,
            )
            return False
        entries = []
        rids0 = []
        for j, bid in enumerate(keys):
            lanes = buckets[bid]
            checksum, interval = bucket_metadata(st.task, metadatas, lanes)
            st.accumulator.update(
                bid,
                None,  # the share bytes live on device until flush
                len(lanes),
                checksum,
                interval,
                [metadatas[i].report_id for i in lanes],
            )
            entries.append(
                (
                    (st.task.task_id.data, st.job.aggregation_parameter, bid),
                    j,
                    len(lanes),
                    interval,
                )
            )
            rids0.append(metadatas[lanes[0]].report_id.data)
        st.resident_delta = delta
        st.resident_entries = entries
        st.resident_rids = rids0
        return True

    def commit_finish(self, st: "InitStepState") -> None:
        """Commit stage: tx2 writes results + releases the lease
        (reference :698-724)."""
        from ..trace import span

        acquired, job = st.acquired, st.job
        new_ras = []
        for i, ra in enumerate(st.pending):
            if st.accept[i]:
                new_ras.append(ra.finished())
            else:
                err = _err_or_default(st.failed[i])
                metrics.aggregate_step_failure_counter.add(type=err.name.lower())
                new_ras.append(ra.failed(err))

        # committing attempt's unmergeable set, carried out of the tx for
        # the post-commit e2e observation (run_tx may retry the closure)
        cell: dict = {}
        accumulator = st.accumulator

        def write(tx):
            # flush first: reports whose batch was collected mid-flight
            # fail individually with BATCH_COLLECTED (reference
            # flush_to_datastore unmergeable set, accumulator.rs:133-215)
            unmerged = accumulator.flush_to_datastore(tx)
            cell["unmerged"] = unmerged
            for ra in new_ras:
                if ra.report_id.data in unmerged:
                    ra = ra.failed(PrepareError.BATCH_COLLECTED)
                tx.update_report_aggregation(ra)
            # conservation ledger: every row is terminal in this tx —
            # FINISHED books aggregated, FINISHED-but-unmerged books
            # rejected:batch_collected, FAILED books rejected:<err>
            # (param-fanout jobs book their own lane: one report
            # finishes once PER parameter)
            ledger.count_ra_outcomes(
                tx, job.task_id, new_ras, unmerged,
                aggregation_parameter=job.aggregation_parameter,
            )
            tx.update_aggregation_job(job.with_state(AggregationJobState.FINISHED))
            tx.release_aggregation_job(acquired)

        with span("driver.write_tx", batch=len(st.pending)):
            self.ds.run_tx(write, "step_agg_job_write")
        # resident mode: the device deltas merge into the engine's
        # resident buffers ONLY now, after the commit landed — a failed
        # write tx (or a step-back anywhere earlier) just drops the
        # PendingDeltas object, so the re-step under a fresh lease can
        # never double-merge. A hard crash in this window loses the
        # delta (the documented resident durability window,
        # ROBUSTNESS.md fault matrix).
        if st.resident_delta is not None:
            self._resident_post_commit(st, cell.get("unmerged", set()))
        # e2e SLO observed only AFTER the write committed: a failed step
        # retried under a fresh lease must not leave phantom samples
        from .accumulator import observe_finished_report_e2e

        observe_finished_report_e2e(self.ds.clock, new_ras, cell.get("unmerged", ()))

    # --- resident aggregate state: merge + flush (ISSUE 12) -----------
    def _resident_post_commit(self, st, unmerged: set) -> None:
        """Merge the job's committed deltas into resident buffers; flush
        LRU-evicted slots immediately; honor the flush cadence."""
        engine = st.engine
        # a bucket whose batch was collected mid-flight had ALL its
        # reports refused by flush_to_datastore (BATCH_COLLECTED) — its
        # delta must not enter the resident share either
        entries = [
            e
            for e, rid0 in zip(st.resident_entries, st.resident_rids)
            if rid0 not in unmerged
        ]
        delta, st.resident_delta = st.resident_delta, None
        if entries:
            try:
                evicted = engine.resident_merge(entries, delta)
            except Exception as merge_exc:
                # the commit LANDED but the merge didn't: the
                # contributions must not vanish — fetch the delta rows
                # directly and push them through the flush path. A
                # mid-loop failure leaves a merged PREFIX safely on
                # device (ResidentMergeError.merged); flushing those
                # again would double-count them when their slot
                # flushes, so only the remainder goes out directly.
                merged_keys = getattr(merge_exc, "merged", frozenset())
                remaining = [e for e in entries if e[0] not in merged_keys]
                log.error(
                    "resident merge failed post-commit for job %s (%d of %d "
                    "buckets merged before the failure); flushing the "
                    "remaining delta rows directly",
                    st.acquired.job_id,
                    len(merged_keys),
                    len(entries),
                    exc_info=True,
                )
                recs = []
                try:
                    recs = engine.fetch_delta_records(remaining, delta)
                except Exception:
                    metrics.engine_resident_flushes_total.add(
                        len(remaining), reason="merge_failed", outcome="lost"
                    )
                    ledger.count_lost(self.ds, st.task.task_id, len(remaining))
                    log.exception(
                        "resident delta fetch also failed; %d bucket "
                        "contribution(s) of job %s are LOST",
                        len(remaining),
                        st.acquired.job_id,
                    )
                    recs = []
                if recs:
                    self.flush_resident_records(engine, recs, reason="merge_failed")
            else:
                if evicted:
                    self.flush_resident_records(engine, evicted, reason="eviction")
        self.maybe_flush_resident(engine)

    def maybe_flush_resident(self, engine) -> None:
        """Honor the flush cadence inline (the background flusher covers
        idle periods; this keeps a busy serial driver bounded too)."""
        interval = self.cfg.resident.flush_interval_s
        now = time.monotonic()
        with self._resident_flush_lock:
            if now - self._resident_last_flush < interval:
                return
            self._resident_last_flush = now
        self.flush_engine_resident(engine, reason="interval")

    def flush_engine_resident(self, engine, reason: str = "interval") -> int:
        """Take every resident slot of `engine` and write the shares
        through the existing batch-aggregation write-tx path. Returns
        the number of buffers flushed. A take failure (wedged device)
        leaves the slots resident — retried on the next pass/drain."""
        if not isinstance(engine, EngineCache):
            return 0
        from .job_driver import datastore_down

        if reason != "drain" and datastore_down(self.ds):
            # flushing into a known-down store would pop the slots and
            # then LOSE the fetched shares when the tx fails (the flush
            # is at-most-once by design — no idempotency key guards a
            # re-flush against double-merging on a commit-ack loss).
            # Leave the state resident; the flusher retries after the
            # supervisor reports the store back up.
            return 0
        try:
            if current_deadline() is None:
                # flusher/drain threads carry no ambient lease deadline,
                # and without one the dispatch watchdog degrades to a
                # direct call — a wedged device would then block this
                # fetch FOREVER while resident_take holds the engine's
                # resident lock, deadlocking every commit worker behind
                # it. Bound the fetch; a timeout restores the slots and
                # the next pass retries.
                with deadline_scope(
                    time.monotonic() + RESIDENT_FLUSH_FETCH_BOUND_S
                ):
                    recs = engine.resident_take()
            else:
                recs = engine.resident_take()
        except Exception:
            log.warning(
                "resident take failed for %s (%s); state stays resident for retry",
                engine.inst.kind,
                reason,
                exc_info=True,
            )
            return 0
        if not recs:
            return 0
        return self.flush_resident_records(engine, recs, reason)

    def flush_resident_state(self, reason: str = "interval") -> int:
        """Flush every live engine's resident buffers (drain hook; also
        the background flusher's pass body)."""
        from .engine_cache import live_engines

        # share the cadence stamp with the inline post-commit check:
        # without this, a busy driver with the background flusher
        # running pays the full take + flush tx TWICE per interval
        with self._resident_flush_lock:
            self._resident_last_flush = time.monotonic()
        flushed = 0
        for eng in live_engines():
            flushed += self.flush_engine_resident(
                eng,
                reason if eng.resident_ready() else "quarantine",
            )
        return flushed

    def flush_resident_records(self, engine, recs: list, reason: str) -> int:
        """Persist fetched resident shares through the existing
        Accumulator write-tx path (share-only merges: count 0, identity
        checksum — counts/checksums were durable at each job's commit).
        A batch collected before its flush arrived is a LOST share
        (counted + ERROR-logged); a deleted task is stale state."""
        from ..messages import TaskId

        by_task: dict[bytes, list] = {}
        for r in recs:
            by_task.setdefault(r["key"][0], []).append(r)
        flushed = 0
        for task_id_bytes, rows in by_task.items():
            outcome_cell: dict = {}

            def write(tx, task_id_bytes=task_id_bytes, rows=rows, cell=outcome_cell):
                cell.clear()
                task = tx.get_task(TaskId(task_id_bytes))
                if task is None:
                    cell["stale"] = len(rows)
                    return
                accs: dict[bytes, Accumulator] = {}
                lost = flushed_n = 0
                for r in rows:
                    _, agg_param, bid = r["key"]
                    if tx.batch_has_collected_shard(task.task_id, bid, agg_param):
                        lost += 1
                        log.error(
                            "resident share for task %s batch %r arrived AFTER "
                            "collection; the share is lost (flush reason=%s)",
                            task.task_id,
                            bid[:16],
                            reason,
                        )
                        continue
                    acc = accs.get(agg_param)
                    if acc is None:
                        acc = accs[agg_param] = Accumulator(
                            task,
                            self.cfg.batch_aggregation_shard_count,
                            aggregation_parameter=agg_param,
                            count_metrics=False,
                        )
                    acc.update(
                        bid,
                        acc.field.encode_vec(r["share"]),
                        0,
                        ReportIdChecksum(),
                        r["interval"],
                        [],
                    )
                    flushed_n += 1
                for acc in accs.values():
                    acc.flush_to_datastore(tx)
                if lost:
                    # first-class ledger terminal for share-mass loss,
                    # booked in the SAME tx that established the loss
                    tx.increment_task_counters(task.task_id, {"lost": lost})
                cell["lost"] = lost
                cell["flushed"] = flushed_n

            try:
                self.ds.run_tx(write, "flush_resident")
            except Exception:
                log.exception(
                    "resident flush tx failed (%d buffer(s), reason=%s); the "
                    "fetched shares are LOST",
                    len(rows),
                    reason,
                )
                metrics.engine_resident_flushes_total.add(
                    len(rows), reason=reason, outcome="lost"
                )
                ledger.count_lost(self.ds, TaskId(task_id_bytes), len(rows))
                continue
            for outcome in ("flushed", "lost", "stale"):
                n = outcome_cell.get(outcome, 0)
                if n:
                    metrics.engine_resident_flushes_total.add(
                        n, reason=reason, outcome=outcome
                    )
            flushed += outcome_cell.get("flushed", 0)
        return flushed

    def _step_poplar1_init(self, acquired, task: Task, job, pending, reports) -> None:
        """Poplar1 leader init (see aggregator.poplar1_ops docstring):
        evaluate IDPF shares at the job's aggregation parameter, send
        sketch shares, verify the helper's combined sketch, park
        WaitingLeader for the continue round."""
        import dataclasses

        from .poplar1_ops import Poplar1Ops

        pop = Poplar1Ops(task.vdaf.bits, task.vdaf_verify_key)
        param = pop.decode_param(job.aggregation_parameter)
        F = pop.field_for(param)

        if not pending:
            def finish_empty(tx):
                tx.update_aggregation_job(job.with_state(AggregationJobState.FINISHED))
                tx.release_aggregation_job(acquired)

            self.ds.run_tx(finish_empty, "step_p1_job_finish_empty")
            return

        n = len(pending)
        failed: list = [None] * n
        evals: dict[int, tuple] = {}  # i -> (prep state, y0, [A0, B0])
        items = []
        item_idx = []
        for i, ra in enumerate(pending):
            rep = reports.get(ra.report_id.data)
            if rep is None:
                failed[i] = PrepareError.REPORT_DROPPED
                continue
            items.append(
                (rep.public_share, rep.leader_input_share, ra.report_id.data)
            )
            item_idx.append(i)
        # one batched device IDPF walk + sketch for the whole job
        for i, res in zip(item_idx, pop.round1_batch(0, items, param)):
            if isinstance(res, ValueError):
                failed[i] = PrepareError.INVALID_MESSAGE
            else:
                evals[i] = res

        prep_inits = []
        send_idx = []
        for i, ra in enumerate(pending):
            if failed[i] is not None:
                continue
            rep = reports[ra.report_id.data]
            _, _, msg1_0 = evals[i]
            prep_inits.append(
                PrepareInit(
                    ReportShare(
                        ReportMetadata(ra.report_id, ra.client_time),
                        rep.public_share,
                        rep.helper_encrypted_input_share,
                    ),
                    encode_pingpong(PP_INITIALIZE, None, pop.encode_vec(param, msg1_0)),
                )
            )
            send_idx.append(i)

        parked: dict[int, bytes] = {}  # i -> WaitingLeader blob
        if prep_inits:
            req = AggregationJobInitializeReq(
                job.aggregation_parameter,
                PartialBatchSelector.from_bytes(job.partial_batch_identifier),
                tuple(prep_inits),
            )
            resp = self._send_init_request(task, acquired.job_id, req, acquired=acquired)
            by_id = {pr.report_id: pr for pr in resp.prepare_resps}
            for i in send_idx:
                ra = pending[i]
                pr = by_id.get(ra.report_id)
                if pr is None or pr.result.kind == PrepareStepResult.REJECT:
                    failed[i] = _err_or_default(
                        pr.result.prepare_error if pr is not None else None
                    )
                    continue
                try:
                    tag, prep_msg, helper_share = decode_pingpong(pr.result.message)
                    if tag != PP_CONTINUE or helper_share is None:
                        raise DecodeError("expected ping-pong continue")
                    es = pop.enc_size(param)
                    # helper share = enc(A1)||enc(B1)||enc(sigma1)
                    msg1_1 = pop.decode_fixed_vec(param, helper_share[: 2 * es], 2)
                    sigma1 = pop.decode_elem(param, helper_share[2 * es :])
                except (DecodeError, ValueError):
                    failed[i] = PrepareError.INVALID_MESSAGE
                    continue
                st0, y0, msg1_0 = evals[i]
                sigma0, combined = pop.round2(st0, msg1_0, msg1_1)
                # the helper's claimed round-1 prep message must equal our
                # own combination, and the quadratic sketch must verify
                # (sigma0 + sigma1 == 0 <=> y one-hot or all-zero)
                if prep_msg != pop.encode_vec(param, combined) or F.add(
                    sigma0, sigma1
                ) != 0:
                    failed[i] = PrepareError.VDAF_PREP_ERROR
                    continue
                msg = encode_pingpong(PP_FINISH, pop.encode_elem(param, sigma0), None)
                parked[i] = (
                    len(msg).to_bytes(4, "big") + msg + pop.encode_vec(param, y0)
                )

        new_ras = []
        for i, ra in enumerate(pending):
            if i in parked:
                new_ras.append(
                    dataclasses.replace(
                        ra,
                        state=ReportAggregationState.WAITING_LEADER,
                        prep_blob=parked[i],
                    )
                )
            else:
                err = _err_or_default(failed[i])
                metrics.aggregate_step_failure_counter.add(type=err.name.lower())
                new_ras.append(ra.failed(err))

        def write_waiting(tx):
            for ra in new_ras:
                tx.update_report_aggregation(ra)
            ledger.count_ra_outcomes(
                tx, task.task_id, new_ras,
                aggregation_parameter=job.aggregation_parameter,
            )
            tx.release_aggregation_job(acquired)

        self.ds.run_tx(write_waiting, "step_p1_job_park")

    def _continue_step(self, acquired, task: Task, job, waiting) -> None:
        """Send the ord-matched continue request for WaitingLeader rows
        and finish the job (reference :439-514 + :530-726)."""
        import dataclasses

        if task.vdaf.kind == "poplar1":
            from .poplar1_ops import Poplar1Ops

            pop = Poplar1Ops(task.vdaf.bits)
            field = pop.field_for(pop.decode_param(job.aggregation_parameter))
        else:
            field = circuit_for(task.vdaf).FIELD
        msgs = []
        outs = []
        for ra in waiting:
            mlen = int.from_bytes(ra.prep_blob[:4], "big")
            msgs.append(ra.prep_blob[4 : 4 + mlen])
            outs.append(ra.prep_blob[4 + mlen :])
        # the stored msgs are already-framed ping-pong messages; splice
        # them raw (PrepareContinue = report_id || message) instead of
        # re-validating each frame through the dataclass codec
        req = AggregationJobContinueReq(
            AggregationJobStep(job.step + 1),
            tuple(
                PreEncoded(ra.report_id.data + msg)
                for ra, msg in zip(waiting, msgs)
            ),
        )
        from ..trace import span

        with span("driver.http_continue", reports=len(waiting)):
            body = self._send_agg_job_request_raw(
                task, acquired.job_id, "POST", req, acquired=acquired
            )
        col = decode_prepare_resps_fast(body)
        mapping = self._match_resps([ra.report_id.data for ra in waiting], col)

        accumulator = Accumulator(
            task,
            self.cfg.batch_aggregation_shard_count,
            field=field,
            aggregation_parameter=job.aggregation_parameter,
        )
        pbs = PartialBatchSelector.from_bytes(job.partial_batch_identifier)
        fixed_bid = fixed_size_batch_id(pbs)
        new_ras = []
        for k, (ra, out_enc) in enumerate(zip(waiting, outs)):
            j = k if mapping is None else mapping[k]
            if j is not None and col.kinds[j] == PrepareStepResult.FINISHED:
                from ..messages import Interval

                bid = fixed_bid or Interval(
                    ra.client_time.to_batch_interval_start(task.time_precision),
                    task.time_precision,
                ).to_bytes()
                accumulator.update_single(
                    bid, field.decode_vec(out_enc), ra.report_id, ra.client_time
                )
                new_ras.append(
                    dataclasses.replace(
                        ra, state=ReportAggregationState.FINISHED, prep_blob=b""
                    )
                )
            else:
                err = _err_or_default(
                    col.errors[j]
                    if j is not None and col.kinds[j] == PrepareStepResult.REJECT
                    else None
                )
                metrics.aggregate_step_failure_counter.add(type=err.name.lower())
                new_ras.append(ra.failed(err))

        new_job = dataclasses.replace(
            job, state=AggregationJobState.FINISHED, step=job.step + 1
        )
        cell: dict = {}

        def write(tx):
            unmerged = accumulator.flush_to_datastore(tx)
            cell["unmerged"] = unmerged
            for ra in new_ras:
                if ra.report_id.data in unmerged:
                    ra = ra.failed(PrepareError.BATCH_COLLECTED)
                tx.update_report_aggregation(ra)
            ledger.count_ra_outcomes(
                tx, task.task_id, new_ras, unmerged,
                aggregation_parameter=job.aggregation_parameter,
            )
            tx.update_aggregation_job(new_job)
            tx.release_aggregation_job(acquired)

        self.ds.run_tx(write, "step_agg_job_continue_write")
        # e2e SLO observed only post-commit (see the init path above)
        from .accumulator import observe_finished_report_e2e

        observe_finished_report_e2e(self.ds.clock, new_ras, cell.get("unmerged", ()))

    def _send_agg_job_request(
        self,
        task: Task,
        job_id,
        method: str,
        req,
        extra_headers: dict | None = None,
        deadline: float | None = None,
        acquired=None,
    ) -> AggregationJobResp:
        return AggregationJobResp.from_bytes(
            self._send_agg_job_request_raw(
                task, job_id, method, req,
                extra_headers=extra_headers, deadline=deadline, acquired=acquired,
            )
        )

    def _send_agg_job_request_raw(
        self,
        task: Task,
        job_id,
        method: str,
        req,
        extra_headers: dict | None = None,
        deadline: float | None = None,
        acquired=None,
    ) -> bytes:
        """Shared PUT(init)/POST(continue) to the helper's
        aggregation_jobs endpoint: URL, auth, deadline-capped timeouts,
        retries; returns the raw response body (the callers' columnar
        decoders parse it)."""
        import base64

        from .job_driver import deadline_request_timeout

        if acquired is not None:
            # recompute the lease budget AT CALL TIME: the staging +
            # device phases (and, pipelined, the stage queues) consumed
            # arbitrary wall time since the step captured its budget —
            # an expired lease raises here and steps back instead of
            # dialing the helper on a dead budget. Clamped to the
            # ambient step scope so a DB-clock-granularity recompute
            # can never EXTEND past the bound the watchdog enforced.
            deadline = self._lease_deadline(acquired)
            ambient = current_deadline()
            if ambient is not None:
                deadline = min(deadline, ambient)

        url = (
            task.helper_aggregator_endpoint.rstrip("/")
            + f"/tasks/{base64.urlsafe_b64encode(task.task_id.data).decode().rstrip('=')}"
            + f"/aggregation_jobs/{base64.urlsafe_b64encode(job_id.data).decode().rstrip('=')}"
        )
        headers = {"Content-Type": req.MEDIA_TYPE, **(extra_headers or {})}
        if task.aggregator_auth_token:
            headers.update(task.aggregator_auth_token.request_headers())
        peer = peer_label(task.helper_aggregator_endpoint)
        if self.peer_health is not None:
            # register the endpoint BEFORE any attempt: the tracker can
            # aim its half-open probes even at a peer that never once
            # answered (first contact during an outage)
            self.peer_health.observe_endpoint(task.helper_aggregator_endpoint)
        payload = req.to_bytes()  # encode once, not once per retry attempt

        def attempt():
            # circuit gate per ATTEMPT: a breaker opened by a concurrent
            # step aborts this retry loop too (CircuitOpenError is not a
            # transport error, so retry_http_request lets it propagate)
            self.breakers.check(peer)
            # go through put/post (not request) so test doubles that
            # wrap those verbs see the traffic; the trailing headers
            # element lets a shedding helper's Retry-After pace retries
            fn = self.http.put if method == "PUT" else self.http.post
            try:
                status, body = fn(
                    url, payload, headers, timeout=deadline_request_timeout(deadline)
                )
            except BaseException:
                # transport failure (or anything else before a response):
                # the breaker must learn of it AND free a half-open probe
                self.breakers.record_failure(peer)
                raise
            # 5xx = the peer is failing; anything conclusive (2xx/4xx,
            # incl. problem documents) or shedding (429) = alive
            if 500 <= status < 600:
                self.breakers.record_failure(peer)
            else:
                self.breakers.record_success(peer)
            return status, body, getattr(self.http, "last_response_headers", {})

        status, body = retry_http_request(
            attempt,
            self.cfg.http_backoff,
            deadline=deadline,
            should_abort=(lambda: self.stopper.stopped) if self.stopper is not None else None,
        )
        if status == DEADLINE_EXCEEDED_STATUS:
            # the helper's conclusive "your budget is dead" answer
            # (docs/ROBUSTNESS.md deadline contract): step back, don't
            # fail the job and don't retry against the same dead budget
            raise DeadlineExceeded(
                "helper reported deadline exceeded", last_status=status
            )
        if status not in (200, 201):
            raise RuntimeError(
                f"helper {method} aggregation job failed: HTTP {status}: {body[:300]!r}"
            )
        return body

    def _send_init_request(
        self, task: Task, job_id, req: AggregationJobInitializeReq, deadline: float | None = None,
        acquired=None,
    ) -> AggregationJobResp:
        return AggregationJobResp.from_bytes(
            self._send_init_request_raw(
                task, job_id, req, deadline=deadline, acquired=acquired
            )
        )

    def _send_init_request_raw(
        self, task: Task, job_id, req: AggregationJobInitializeReq, deadline: float | None = None,
        acquired=None,
    ) -> bytes:
        from .http_handlers import XOF_MODE_HEADER

        return self._send_agg_job_request_raw(
            task,
            job_id,
            "PUT",
            req,
            extra_headers={XOF_MODE_HEADER: task.vdaf.xof_mode},
            deadline=deadline,
            acquired=acquired,
        )

    # --- abandon (reference :728) ---
    def abandon_job(self, acquired: AcquiredAggregationJob) -> None:
        def cancel(tx):
            job = tx.get_aggregation_job(acquired.task_id, acquired.job_id)
            if job is None:
                return
            tx.update_aggregation_job(job.with_state(AggregationJobState.ABANDONED))
            ras = tx.get_report_aggregations_for_job(acquired.task_id, acquired.job_id)
            tx.mark_reports_unaggregated(
                acquired.task_id,
                [ra.report_id for ra in ras if ra.state == ReportAggregationState.START],
            )
            tx.release_aggregation_job(acquired)

        self.ds.run_tx(cancel, "abandon_agg_job")
        metrics.job_cancel_counter.add(kind="aggregation")
        log.warning("abandoned aggregation job %s after max attempts", acquired.job_id)


class ResidentFlusher:
    """Background resident-state flusher (driver binary, resident mode):
    every flush_interval_s it pushes dirty resident buffers of every
    live engine through the driver's write-tx flush path, so an IDLE
    driver's last job doesn't sit unflushed until the next job arrives,
    and a QUARANTINED engine's state flushes within one pass (the
    interim host engine's jobs then see the complete batch rows — the
    quarantine-mid-job contract). stop() + a final flush is the drain
    hook (the binary calls driver.flush_resident_state("drain") after
    the job loop exits)."""

    def __init__(self, driver: AggregationJobDriver, interval_s: float):
        self.driver = driver
        self.interval_s = max(0.1, float(interval_s))
        # quarantine sweep cadence: a quarantined engine's resident
        # state must flush within ~a second, NOT within the interval
        # cadence — the interim host engine's jobs read the batch rows
        self.poll_s = min(1.0, self.interval_s)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="resident-flusher", daemon=True
        )

    def start(self) -> "ResidentFlusher":
        self._thread.start()
        return self

    def _loop(self) -> None:
        from .engine_cache import live_engines

        elapsed = 0.0
        while not self._stop.wait(self.poll_s):
            elapsed += self.poll_s
            try:
                if elapsed >= self.interval_s:
                    elapsed = 0.0
                    self.driver.flush_resident_state(reason="interval")
                else:
                    for eng in live_engines():
                        if not eng.resident_ready():
                            self.driver.flush_engine_resident(
                                eng, reason="quarantine"
                            )
            except Exception:
                log.exception("resident flush pass failed; retrying next pass")

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout_s)
