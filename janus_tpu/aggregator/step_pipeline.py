"""Stage-pipelined leader stepper (ISSUE 9 tentpole).

The serial stepper runs every leased aggregation job as one chain on
one worker thread — read tx -> host staging -> device init -> helper
RTT -> device accumulate -> write tx — so the chip idles behind the
datastore and the helper round trip at exactly the batch sizes the
kernels want (the host-pipeline-starves-the-accelerator failure mode
"Enabling AI ASICs for ZKP" describes for ZKP offload). This module
restructures the step into an explicit staged pipeline:

    read    (prefetch_depth workers): read_tx + columnar staging — job
            k+1 stages while job k occupies the device
    device  (the DEVICE LANE, device_lane_workers=1 by default): EVERY
            device dispatch — leader init and the masked accumulate —
            runs here, so a dispatch is never parked behind a helper
            RTT or a commit. The lane re-enters the job's ambient
            lease-deadline scope per stage, so the PR 7 watchdog /
            quarantine semantics apply unchanged; with lane workers
            > 1, the engine's own coalescing gate merges the
            concurrent dispatches exactly as it does for concurrent
            serial steppers.
    http    (http_inflight workers): columnar request framing, the
            helper round trip, columnar response decode + host-side
            verification
    commit  (commit_inflight workers): the write tx + lease release

Jobs that are not on the prio3 init hot path (multi-round continue
steps, poplar1, empty jobs) run their existing serial step body as one
opaque "classic" stage on the http/commit executors — same code, same
semantics, no device-lane involvement (their device work, if any, is
still watchdog-supervised by the ambient deadline).

Correctness invariants:

  * a job is in EXACTLY ONE stage at a time (the chain enqueues the
    next stage only after the previous returned), so the pipeline can
    never lose or double-step a job; the write tx is byte-for-byte the
    serial stepper's;
  * the lease budget is RE-CHECKED at every stage hand-off
    (deadline.check), and the HTTP leg recomputes it at call time
    (AggregationJobDriver._send_agg_job_request_raw) — a job whose
    budget died waiting in a stage queue steps back instead of dialing;
  * any stage failure maps through the driver's handle_step_error to
    the existing step-back / attempt-ledger semantics (circuit open,
    deadline expired, device hang, datastore down), identical to the
    serial stepper;
  * SIGTERM drain: in-flight chains run to completion (JobDriver.run
    waits on the outer futures before returning); a step that fails
    during drain releases its lease immediately via the releaser, as
    the serial path does.

Observability: janus_step_pipeline_stage_seconds{stage},
janus_step_pipeline_queue_depth{stage}, the time each job waits in a
stage's queue and for a staging-window slot
(janus_step_pipeline_queue_wait_seconds{stage}; the staging wait is
also the `pipeline.staging_wait` span), janus_device_lane_busy_ratio,
janus_step_pipeline_overlap_total, a `step_pipeline` /statusz section,
and a per-job "job.step" flight-recorder digest observation (the bench
served phase reads p50/p95 from it).
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass

from .. import metrics
from ..core import deadline as deadline_mod
from ..datastore.models import AggregationJobState

log = logging.getLogger(__name__)

STAGE_READ = "read"
STAGE_DEVICE = "device"
STAGE_HTTP = "http"
STAGE_COMMIT = "commit"
STAGE_CLASSIC = "classic"  # metric label for non-pipelined step bodies
STAGES = (STAGE_READ, STAGE_DEVICE, STAGE_HTTP, STAGE_COMMIT)


@dataclass
class StepPipelineConfig:
    """YAML `step_pipeline:` stanza of the aggregation job driver
    (docs/samples/aggregation_job_driver.yaml)."""

    enabled: bool = True
    # jobs reading + staging ahead of the device lane (bounded: each
    # prefetched job holds its staged columns in host memory)
    prefetch_depth: int = 2
    # concurrent helper round trips (encode/send/decode/verify legs)
    http_inflight: int = 2
    # concurrent write transactions
    commit_inflight: int = 2
    # device-lane width. 1 (default) = fully serialized dispatches; >1
    # re-enables cross-job coalescing at the engine gate for small jobs
    device_lane_workers: int = 1
    # double-buffered staging (ISSUE 12): the read stage issues job
    # k+1's padded host->device column uploads ASYNC right after
    # staging, so the transfer overlaps job k's dispatch on the lane
    # instead of serializing in front of k+1's own dispatch. Staged
    # device bytes are bounded by the same prefetch_depth window as the
    # host columns.
    double_buffer: bool = True

    @classmethod
    def from_dict(cls, d: dict | None) -> "StepPipelineConfig":
        d = d or {}
        return cls(
            enabled=bool(d.get("enabled", True)),
            prefetch_depth=max(1, int(d.get("prefetch_depth", 2))),
            http_inflight=max(1, int(d.get("http_inflight", 2))),
            commit_inflight=max(1, int(d.get("commit_inflight", 2))),
            device_lane_workers=max(1, int(d.get("device_lane_workers", 1))),
            double_buffer=bool(d.get("double_buffer", True)),
        )


class DeviceLane:
    """Serialized owner of device dispatches: a bounded executor whose
    busy time is accounted, so "is the chip saturated" is one gauge
    (janus_device_lane_busy_ratio, rolling window) plus a counter
    (janus_device_lane_busy_seconds_total) for rate()-based alerts.
    Tracks the concurrency high-water mark so tests can pin the
    serialization contract."""

    # rolling window for the busy-ratio gauge: the ratio reads the last
    # WINDOW..2*WINDOW seconds, never the process lifetime — an
    # overnight-idle driver must not mask a saturated morning (and vice
    # versa). Alerts wanting other widths rate() the counter instead.
    RATIO_WINDOW_S = 60.0

    def __init__(self, workers: int = 1):
        self.workers = workers
        self._pool = ThreadPoolExecutor(workers, thread_name_prefix="device-lane")
        self._lock = threading.Lock()
        t0 = time.monotonic()
        self.busy_s = 0.0
        self.dispatches = 0
        self.concurrent = 0
        self.concurrent_peak = 0
        # two-snapshot rolling window: ratio is computed against the
        # previous snapshot (age WINDOW..2*WINDOW); rolls forward every
        # WINDOW seconds
        self._prev_t, self._prev_busy = t0, 0.0
        self._snap_t, self._snap_busy = t0, 0.0
        # the gauge must DECAY while the lane is idle (dispatch-end is
        # the only other update site, so a saturated burst followed by
        # hours of idle would export ~1.0 forever): a low-cadence
        # refresher keeps the exported window honest between dispatches
        self._stop = threading.Event()
        self._refresher = threading.Thread(
            target=self._refresh_loop, name="device-lane-gauge", daemon=True
        )
        self._refresher.start()

    def _refresh_loop(self) -> None:
        while not self._stop.wait(self.RATIO_WINDOW_S / 4):
            metrics.device_lane_busy_ratio.set(self.busy_ratio())

    def submit(self, fn, *args) -> Future:
        return self._pool.submit(self._run, fn, *args)

    def _run(self, fn, *args):
        with self._lock:
            self.concurrent += 1
            self.concurrent_peak = max(self.concurrent_peak, self.concurrent)
        t0 = time.monotonic()
        try:
            return fn(*args)
        finally:
            dt = time.monotonic() - t0
            with self._lock:
                self.concurrent -= 1
                self.busy_s += dt
                self.dispatches += 1
            metrics.device_lane_busy_seconds.add(dt)
            metrics.device_lane_busy_ratio.set(self.busy_ratio())

    def busy_ratio(self) -> float:
        now = time.monotonic()
        with self._lock:
            if now - self._snap_t >= self.RATIO_WINDOW_S:
                self._prev_t, self._prev_busy = self._snap_t, self._snap_busy
                self._snap_t, self._snap_busy = now, self.busy_s
            base_t, base_busy = self._prev_t, self._prev_busy
            busy = self.busy_s
        wall = now - base_t
        if wall <= 0:
            return 0.0
        return min(1.0, (busy - base_busy) / (wall * self.workers))

    def close(self, wait: bool = True) -> None:
        self._stop.set()
        self._pool.shutdown(wait=wait)


class _PipelinedStep:
    """One leased job moving through the stage chain."""

    __slots__ = ("acquired", "outer", "trace_context", "deadline", "state",
                 "classic", "t_submit", "error", "staging_permit")

    def __init__(self, acquired, outer: Future):
        self.acquired = acquired
        self.outer = outer
        self.trace_context = None  # persisted creator trace, set at read
        self.deadline = None  # lease budget, set at read
        self.state = None  # InitStepState for the hot path
        self.classic = None  # zero-arg step body for non-pipelined kinds
        self.t_submit = time.monotonic()
        self.error = None
        self.staging_permit = False  # holding a slot of the staging window


class StepPipeline:
    """Schedules AggregationJobDriver stage methods across bounded
    stage executors. submit(acquired) returns a Future that resolves
    when the job's step has fully completed (committed, stepped back,
    or failed-and-logged) — JobDriver treats it exactly like a serial
    _step_one future, so discovery, worker accounting and shutdown
    drain are unchanged."""

    def __init__(self, driver, cfg: StepPipelineConfig | None = None,
                 stopper=None, releaser=None):
        self.driver = driver
        self.cfg = cfg or StepPipelineConfig()
        self.stopper = stopper
        self.releaser = releaser
        self.lane = DeviceLane(self.cfg.device_lane_workers)
        self._pools = {
            STAGE_READ: ThreadPoolExecutor(
                self.cfg.prefetch_depth, thread_name_prefix="step-read"
            ),
            STAGE_HTTP: ThreadPoolExecutor(
                self.cfg.http_inflight, thread_name_prefix="step-http"
            ),
            STAGE_COMMIT: ThreadPoolExecutor(
                self.cfg.commit_inflight, thread_name_prefix="step-commit"
            ),
        }
        self._lock = threading.Lock()
        self._http_inflight = 0
        self._queued = {stage: 0 for stage in STAGES}
        self._jobs_done = 0
        # overlap accounting, split by direction so the ratio below is
        # the quantity its name claims: _overlap_device counts device
        # dispatches that STARTED while an HTTP leg was in flight (the
        # numerator of overlap_ratio); _overlap_http counts the reverse
        # interleaving (an HTTP leg starting while the lane is busy),
        # which proves overlap just as well but must not inflate the
        # per-dispatch ratio
        self._overlap_device = 0
        self._overlap_http = 0
        self._closed = False
        # the REAL staged-memory bound: at most prefetch_depth jobs may
        # hold staged columns (InitStepState arrays) that the device
        # has not consumed yet — the read pool only bounds concurrent
        # read transactions, and without this window jobs would pile up
        # staged-but-unconsumed in the device-lane queue, up to the
        # driver's whole worker count
        self._staging_window = threading.Semaphore(self.cfg.prefetch_depth)
        from ..statusz import register_status_provider

        # keep the exact registered object: bound-method accesses make
        # fresh objects, and close()'s guarded unregister is an
        # identity check
        self._status_provider = self.status
        register_status_provider("step_pipeline", self._status_provider)

    # --- submission ----------------------------------------------------
    def submit(self, acquired) -> Future:
        outer: Future = Future()
        job = _PipelinedStep(acquired, outer)
        self._enqueue(STAGE_READ, self._stage_read, job)
        return outer

    def _enqueue(self, stage: str, fn, job: _PipelinedStep, label: str | None = None) -> None:
        with self._lock:
            self._queued[stage] += 1
            metrics.step_pipeline_queue_depth.set(self._queued[stage], stage=stage)
        # the enqueue stamp: the wait ends on the stage's own thread,
        # so it is an observation from here, not a span
        t_enqueued = time.monotonic()
        try:
            if stage == STAGE_DEVICE:
                self.lane.submit(self._run_stage, stage, fn, job, label, t_enqueued)
            else:
                self._pools[stage].submit(
                    self._run_stage, stage, fn, job, label, t_enqueued
                )
        except RuntimeError as e:
            # pool shut down mid-chain (close() raced a straggler):
            # surface instead of silently stranding the lease
            with self._lock:
                self._queued[stage] -= 1
                metrics.step_pipeline_queue_depth.set(self._queued[stage], stage=stage)
            self._fail(job, e)

    # --- stage execution -----------------------------------------------
    def _run_stage(
        self, stage: str, fn, job: _PipelinedStep, label: str | None, t_enqueued: float
    ) -> None:
        from ..trace import use_traceparent

        metrics.step_pipeline_queue_wait_seconds.observe(
            time.monotonic() - t_enqueued, stage=stage
        )

        # only the REAL helper-RTT stage counts as an in-flight HTTP
        # leg for the overlap proof: a "classic" step body on the HTTP
        # pool (continue/poplar1) mixes RTTs with staging and write
        # txs, and counting it would inflate the overlap metric
        is_http = stage == STAGE_HTTP and label is None
        with self._lock:
            self._queued[stage] -= 1
            metrics.step_pipeline_queue_depth.set(self._queued[stage], stage=stage)
            direction = None
            if is_http:
                self._http_inflight += 1
                if self.lane.concurrent > 0:
                    direction = "http_start"
                    self._overlap_http += 1
            elif stage == STAGE_DEVICE and self._http_inflight > 0:
                direction = "device_start"
                self._overlap_device += 1
            if direction is not None:
                # the overlap proof: a device dispatch and a helper RTT
                # are in flight at the same instant — the serial stepper
                # could never be in both at once
                metrics.step_pipeline_overlap_total.add(direction=direction)
        t0 = time.monotonic()
        err: BaseException | None = None
        nxt = None
        try:
            # re-enter the job's trace + lease-budget scopes on THIS
            # stage thread (contextvars do not cross threads), then
            # re-check the budget before doing any stage work: a job
            # whose lease died in the queue steps back here
            with use_traceparent(job.trace_context), deadline_mod.deadline_scope(
                job.deadline
            ):
                deadline_mod.check(f"step_pipeline_{stage}")
                nxt = fn(job)
        except BaseException as e:  # noqa: BLE001 — mapped to step-back below
            err = e
        finally:
            # drop the in-flight mark BEFORE enqueueing the next stage,
            # or a chain's own just-finished HTTP leg would count as
            # overlapping its device_accumulate
            if is_http:
                with self._lock:
                    self._http_inflight -= 1
        self._observe_stage(label or stage, time.monotonic() - t0)
        if err is not None:
            if stage == STAGE_DEVICE:
                # never run the step-back transaction on the device
                # lane: a DeviceHangError with a slow/down datastore
                # would park every queued dispatch (which host fallback
                # could still serve) behind DB I/O
                try:
                    self._pools[STAGE_COMMIT].submit(self._fail, job, err)
                    return
                except RuntimeError:
                    pass  # commit pool already shut down: handle inline
            self._fail(job, err)
        elif nxt is None:
            self._finish(job)
        else:
            nstage, nfn, nlabel = nxt if len(nxt) == 3 else (*nxt, None)
            self._enqueue(nstage, nfn, job, nlabel)

    def _observe_stage(self, stage: str, dur_s: float) -> None:
        metrics.step_pipeline_stage_seconds.observe(dur_s, stage=stage)

    def _finish(self, job: _PipelinedStep) -> None:
        from ..trace import record_operation

        self._release_staging(job)  # no-op unless the chain died staged
        with self._lock:
            self._jobs_done += 1
        args = {"job": type(job.acquired).__name__, "pipelined": True}
        if job.error is not None:
            args["error"] = job.error
        record_operation("job.step", time.monotonic() - job.t_submit, **args)
        job.outer.set_result(None)

    def _fail(self, job: _PipelinedStep, e: BaseException) -> None:
        """Map a stage failure to the serial stepper's semantics
        (AggregationJobDriver.stepper + JobDriver._step_one)."""
        job.error = type(e).__name__
        try:
            if isinstance(e, Exception) and self.driver.handle_step_error(
                job.acquired, e
            ):
                self._finish(job)
                return
        except Exception:
            log.exception(
                "step-back handling itself failed for job %s", job.acquired.job_id
            )
            self._finish(job)
            return
        if (
            self.stopper is not None
            and self.stopper.stopped
            and self.releaser is not None
        ):
            # shutdown drain: this process will not retry — release the
            # lease now so a surviving peer picks the job up immediately
            log.error(
                "pipelined job step failed during shutdown; releasing lease",
                exc_info=e,
            )
            try:
                self.releaser(job.acquired)
            except Exception:
                log.exception("shutdown lease release failed")
        else:
            log.error(
                "pipelined job %s step failed (attempt %d; lease will expire and retry)",
                job.acquired.job_id,
                job.acquired.lease.attempts,
                exc_info=e,
            )
        self._finish(job)

    # --- the stage bodies ----------------------------------------------
    def _stage_read(self, job: _PipelinedStep):
        driver = self.driver
        acquired = job.acquired
        if acquired.lease.attempts > driver.cfg.maximum_attempts_before_failure:
            driver.abandon_job(acquired)
            return None
        task, jobrow, ras, reports = driver.read_job(acquired)
        if jobrow is None or task is None:
            raise RuntimeError("job or task vanished while leased")
        if jobrow.state != AggregationJobState.IN_PROGRESS:
            driver.release_job(acquired)
            return None
        # adopt the persisted creator trace + the lease budget for every
        # later stage (and for the rest of THIS one: staging below runs
        # under the scopes, like the serial stepper's _step_leased_job)
        job.trace_context = jobrow.trace_context
        job.deadline = driver._lease_deadline(acquired)

        from ..trace import span, use_traceparent

        with use_traceparent(job.trace_context), deadline_mod.deadline_scope(
            job.deadline
        ):
            kind, rows = driver.plan_step(acquired, task, jobrow, ras)
            if kind == "continue":
                job.classic = lambda: driver._continue_step(acquired, task, jobrow, rows)
                return (STAGE_HTTP, self._stage_classic, STAGE_CLASSIC)
            if kind == "poplar1":
                job.classic = lambda: driver._step_poplar1_init(
                    acquired, task, jobrow, rows, reports
                )
                return (STAGE_HTTP, self._stage_classic, STAGE_CLASSIC)
            if kind == "empty":
                job.classic = lambda: driver.finish_empty(acquired, jobrow)
                return (STAGE_COMMIT, self._stage_classic, STAGE_CLASSIC)
            # blocks this read worker while prefetch_depth jobs already
            # hold unconsumed staged columns — the staged-memory bound
            with span("pipeline.staging_wait"):
                self._staging_window.acquire()
            job.staging_permit = True
            st = driver.stage_init(acquired, task, jobrow, rows, reports)
            job.state = st
            if self.cfg.double_buffer:
                # double-buffered staging: issue THIS job's padded H2D
                # transfers async now, on the read thread — they overlap
                # whatever dispatch currently occupies the device lane,
                # and device_init consumes them without a host put
                prestage = getattr(st.engine, "prestage_leader", None)
                would_coalesce = getattr(st.engine, "would_coalesce", None)
                if (
                    prestage is not None
                    and self.cfg.device_lane_workers > 1
                    and would_coalesce is not None
                    and would_coalesce(st.nonce_lanes.shape[0])
                ):
                    # a parallel device lane means coalesced rounds can
                    # MERGE, and a merged round discards its entries'
                    # prestages (it re-stages from concatenated host
                    # columns) — don't pay the H2D transfer twice for
                    # exactly the small jobs coalescing targets
                    prestage = None
                if prestage is not None:
                    try:
                        st.prestaged = prestage(
                            st.nonce_lanes, st.public_parts, st.meas,
                            st.proof, st.blind_lanes,
                        )
                    except Exception:
                        log.warning(
                            "prestage failed for job %s; device_init will "
                            "stage from host",
                            acquired.job_id,
                            exc_info=True,
                        )
                        st.prestaged = None
            return (STAGE_DEVICE, self._stage_device_init)

    def _release_staging(self, job: _PipelinedStep) -> None:
        if job.staging_permit:
            job.staging_permit = False
            self._staging_window.release()

    def _stage_classic(self, job: _PipelinedStep):
        job.classic()
        return None

    def _stage_device_init(self, job: _PipelinedStep):
        try:
            self.driver.device_init(job.state)
        finally:
            # the device consumed the staged columns (leader_init's H2D
            # transfers complete before it returns): free the host
            # arrays — and any unconsumed prestaged device buffers —
            # and open the staging window for the next prefetch
            st = job.state
            st.meas = st.proof = st.blind_lanes = st.public_parts = None
            st.nonce_lanes = None
            if st.prestaged is not None:
                st.prestaged.discard()
                st.prestaged = None
            self._release_staging(job)
        return (STAGE_HTTP, self._stage_http_init)

    def _stage_http_init(self, job: _PipelinedStep):
        self.driver.http_init(job.state)
        if job.state.multi_round:
            return (STAGE_COMMIT, self._stage_commit_park)
        return (STAGE_DEVICE, self._stage_device_accumulate)

    def _stage_device_accumulate(self, job: _PipelinedStep):
        self.driver.device_accumulate(job.state)
        return (STAGE_COMMIT, self._stage_commit_finish)

    def _stage_commit_park(self, job: _PipelinedStep):
        self.driver.commit_park(job.state)
        return None

    def _stage_commit_finish(self, job: _PipelinedStep):
        self.driver.commit_finish(job.state)
        return None

    # --- lifecycle / introspection --------------------------------------
    def status(self) -> dict:
        with self._lock:
            queued = dict(self._queued)
            jobs_done = self._jobs_done
            overlap_device = self._overlap_device
            overlap_http = self._overlap_http
            http_inflight = self._http_inflight
        lane = self.lane
        return {
            "jobs_done": jobs_done,
            "queued": queued,
            "http_inflight": http_inflight,
            "device_lane": {
                "workers": lane.workers,
                "dispatches": lane.dispatches,
                "busy_s": round(lane.busy_s, 3),
                "busy_ratio": round(lane.busy_ratio(), 4),
                "concurrent_peak": lane.concurrent_peak,
            },
            # overlap_ratio is exactly what its name claims: the
            # fraction of device dispatches that STARTED while an HTTP
            # leg was in flight. overlap_events additionally counts the
            # reverse interleaving — either direction nonzero proves
            # the pipeline is overlapping
            "overlapped_dispatches": overlap_device,
            "overlap_events": overlap_device + overlap_http,
            "overlap_ratio": min(1.0, round(overlap_device / lane.dispatches, 4))
            if lane.dispatches
            else 0.0,
            "config": {
                "prefetch_depth": self.cfg.prefetch_depth,
                "http_inflight": self.cfg.http_inflight,
                "commit_inflight": self.cfg.commit_inflight,
                "device_lane_workers": self.cfg.device_lane_workers,
            },
        }

    def close(self, wait: bool = True) -> None:
        """Shut the stage executors down. Callers must first drain
        in-flight chains (JobDriver.run waits on the outer futures
        before returning), so this only retires idle workers."""
        if self._closed:
            return
        self._closed = True
        from ..statusz import unregister_status_provider

        # guarded: a newer pipeline's registration must survive
        unregister_status_provider("step_pipeline", self._status_provider)
        for pool in self._pools.values():
            pool.shutdown(wait=wait)
        self.lane.close(wait=wait)
