"""Failure-semantics suite for the stage-pipelined leader stepper
(janus_tpu/aggregator/step_pipeline.py, ISSUE 9): a stage error maps to
the existing step-back/attempt semantics, a lease budget that dies
between stages steps back, shutdown drain flushes in-flight stages and
releases failing leases, the device lane serializes dispatches under
concurrent jobs (the PR 7 watchdog/quarantine contract rides the same
ambient deadline), and the pipelined end-to-end step — single- AND
multi-round — lands exactly the serial stepper's datastore state."""

import time

import pytest

from janus_tpu import metrics
from janus_tpu.aggregator.aggregation_job_creator import (
    AggregationJobCreator,
    AggregationJobCreatorConfig,
)
from janus_tpu.aggregator.aggregation_job_driver import AggregationJobDriver
from janus_tpu.aggregator.engine_cache import DeviceHangError
from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig, Stopper
from janus_tpu.aggregator.step_pipeline import StepPipeline, StepPipelineConfig
from janus_tpu.client import Client, ClientParameters
from janus_tpu.core.circuit_breaker import CircuitOpenError
from janus_tpu.core.deadline import DeadlineExceeded
from janus_tpu.core.http_client import HttpClient
from janus_tpu.datastore.models import AggregationJobState, ReportAggregationState
from janus_tpu.vdaf.registry import VdafInstance

from test_e2e import pair, provision  # noqa: F401  (fixture + helper)


def _upload(pair, leader_task, vdaf, measurements):
    http = HttpClient()
    params = ClientParameters(
        leader_task.task_id,
        pair["leader_srv"].url,
        pair["helper_srv"].url,
        leader_task.time_precision,
    )
    client = Client.with_fetched_configs(params, vdaf, http, clock=pair["clock"])
    for m in measurements:
        client.upload(m)
    return http


def _make_jobs(pair, job_size=100):
    creator = AggregationJobCreator(
        pair["leader_ds"],
        AggregationJobCreatorConfig(
            min_aggregation_job_size=1, max_aggregation_job_size=job_size
        ),
    )
    return creator.run_once()


def _held_agg_leases(ds):
    return [
        e for e in ds.run_tx(lambda tx: tx.get_held_lease_expiries())
        if e[0] == "aggregation"
    ]


def _agg_job_states(ds):
    counts = ds.run_tx(lambda tx: tx.count_jobs_by_state())
    return {state: n for (typ, state), n in counts.items() if typ == "aggregation"}


def _step_back_delta(reason, fn):
    before = metrics.job_step_back_total.get(reason=reason)
    fn()
    return metrics.job_step_back_total.get(reason=reason) - before


def test_pipelined_step_end_to_end(pair):
    """Multiple concurrent jobs through the full stage chain: all
    finish, all report aggregations land FINISHED, the device lane
    stayed serialized, and every stage executed."""
    vdaf = VdafInstance.count()
    leader_task, _, _ = provision(pair, vdaf)
    http = _upload(pair, leader_task, vdaf, [1, 0, 1, 1, 0, 1])
    assert _make_jobs(pair, job_size=2) == 3

    drv = AggregationJobDriver(pair["leader_ds"], http)
    pipe = StepPipeline(drv, StepPipelineConfig())
    try:
        jd = JobDriver(JobDriverConfig(), drv.acquirer(), drv.stepper, pipeline=pipe)
        while jd.run_once():
            pass
        status = pipe.status()
    finally:
        pipe.close()
    assert _agg_job_states(pair["leader_ds"]) == {"finished": 3}
    assert status["jobs_done"] == 3
    assert status["device_lane"]["dispatches"] >= 6  # init + accumulate per job
    assert status["device_lane"]["concurrent_peak"] <= 1  # serialized lane
    assert not _held_agg_leases(pair["leader_ds"])


def test_pipelined_multi_round_parks_and_finishes(pair):
    """The two-round fake VDAF through the pipeline: round 1 parks
    WaitingLeader via commit_park, round 2 runs the classic continue
    stage — identical states to the serial stepper (test_multi_round)."""
    vdaf = VdafInstance.fake_two_round()
    leader_task, _, _ = provision(pair, vdaf)
    http = _upload(pair, leader_task, vdaf, [1, 0, 1])
    assert _make_jobs(pair) == 1

    drv = AggregationJobDriver(pair["leader_ds"], http)
    pipe = StepPipeline(drv, StepPipelineConfig())
    try:
        jd = JobDriver(JobDriverConfig(), drv.acquirer(), drv.stepper, pipeline=pipe)
        assert jd.run_once() == 1  # init round -> WaitingLeader
        job = pair["leader_ds"].run_tx(
            lambda tx: tx.get_aggregation_jobs_for_task(leader_task.task_id)
        )[0]
        ras = pair["leader_ds"].run_tx(
            lambda tx: tx.get_report_aggregations_for_job(
                leader_task.task_id, job.job_id
            )
        )
        assert {ra.state for ra in ras} == {ReportAggregationState.WAITING_LEADER}
        assert jd.run_once() == 1  # continue round (classic stage) -> finished
    finally:
        pipe.close()
    assert _agg_job_states(pair["leader_ds"]) == {"finished": 1}
    ras = pair["leader_ds"].run_tx(
        lambda tx: tx.get_report_aggregations_for_job(leader_task.task_id, job.job_id)
    )
    assert {ra.state for ra in ras} == {ReportAggregationState.FINISHED}


def _one_leased_job(pair, vdaf=None, measurements=(1, 0, 1)):
    vdaf = vdaf or VdafInstance.count()
    leader_task, _, _ = provision(pair, vdaf)
    http = _upload(pair, leader_task, vdaf, list(measurements))
    assert _make_jobs(pair) == 1
    drv = AggregationJobDriver(pair["leader_ds"], http)
    acquired = drv.acquirer()(1)
    assert len(acquired) == 1
    return drv, acquired[0]


def test_stage_error_maps_to_step_back_with_attempt_refunded(pair):
    """A CircuitOpenError out of the HTTP stage steps the job back:
    lease released early, job still IN_PROGRESS (not failed), counted
    under reason=circuit_open — exactly the serial stepper's mapping."""
    drv, acquired = _one_leased_job(pair)
    attempts_at_first_acquire = acquired.lease.attempts

    def open_circuit(st):
        raise CircuitOpenError("helper", 0.0)

    drv.http_init = open_circuit
    pipe = StepPipeline(drv, StepPipelineConfig())
    try:
        delta = _step_back_delta(
            "circuit_open", lambda: pipe.submit(acquired).result(timeout=60)
        )
    finally:
        pipe.close()
    assert delta == 1
    assert _agg_job_states(pair["leader_ds"]) == {"in_progress": 1}
    assert not _held_agg_leases(pair["leader_ds"])  # released, not held to TTL
    # attempt refunded: the step-back released with count_attempt=False,
    # so the next acquire sees the same attempt count (after the 1s
    # reacquire floor delay, advanced on the mock clock)
    from janus_tpu.messages import Duration

    pair["clock"].advance(Duration(2))
    reacquired = drv.acquirer()(1)
    assert len(reacquired) == 1
    assert reacquired[0].lease.attempts == attempts_at_first_acquire


def test_deadline_expiry_between_stages_steps_back(pair):
    """A lease budget that dies AFTER staging but BEFORE the device
    hand-off trips the stage-boundary re-check: step-back with
    reason=deadline_expired, job untouched."""
    drv, acquired = _one_leased_job(pair)
    drv._lease_deadline = lambda a: time.monotonic() + 0.1
    orig_stage = drv.stage_init

    def slow_stage(*a, **kw):
        st = orig_stage(*a, **kw)
        time.sleep(0.3)  # budget dies while the job heads to the lane
        return st

    drv.stage_init = slow_stage
    pipe = StepPipeline(drv, StepPipelineConfig())
    try:
        delta = _step_back_delta(
            "deadline_expired", lambda: pipe.submit(acquired).result(timeout=60)
        )
    finally:
        pipe.close()
    assert delta == 1
    assert _agg_job_states(pair["leader_ds"]) == {"in_progress": 1}
    assert not _held_agg_leases(pair["leader_ds"])


def test_device_hang_in_lane_steps_back(pair):
    """DeviceHangError surfacing on the device lane maps to the PR 7
    contract: step-back reason=device_hang, never a failed attempt."""
    drv, acquired = _one_leased_job(pair)

    def hang(st):
        raise DeviceHangError("leader_init", 0.1)

    drv.device_init = hang
    pipe = StepPipeline(drv, StepPipelineConfig())
    try:
        delta = _step_back_delta(
            "device_hang", lambda: pipe.submit(acquired).result(timeout=60)
        )
    finally:
        pipe.close()
    assert delta == 1
    assert _agg_job_states(pair["leader_ds"]) == {"in_progress": 1}


def test_shutdown_drain_releases_failing_lease(pair):
    """A stage failing while the stopper is set releases the lease via
    the releaser (the serial _step_one contract): the surviving peer
    reacquires immediately instead of waiting out the TTL."""
    drv, acquired = _one_leased_job(pair)

    def boom(st):
        raise RuntimeError("stage exploded mid-drain")

    drv.http_init = boom
    stopper = Stopper()
    stopper.stop()
    released = []
    pipe = StepPipeline(
        drv,
        StepPipelineConfig(),
        stopper=stopper,
        releaser=lambda a: released.append(a) or drv.step_back(a, "shutdown_drain", 0.0),
    )
    try:
        pipe.submit(acquired).result(timeout=60)
    finally:
        pipe.close()
    assert released == [acquired]
    assert not _held_agg_leases(pair["leader_ds"])


def test_unhandled_stage_error_leaves_lease_to_expire(pair):
    """Outside shutdown, an unhandled stage error must NOT release the
    lease (the serial stepper lets it expire and retry) — and the
    outer future still resolves so the driver loop keeps flowing."""
    drv, acquired = _one_leased_job(pair)

    def boom(st):
        raise RuntimeError("unexpected stage failure")

    drv.device_init = boom
    pipe = StepPipeline(drv, StepPipelineConfig())
    try:
        pipe.submit(acquired).result(timeout=60)
    finally:
        pipe.close()
    assert len(_held_agg_leases(pair["leader_ds"])) == 1  # still leased
    assert _agg_job_states(pair["leader_ds"]) == {"in_progress": 1}


def test_device_lane_serializes_under_concurrent_jobs(pair):
    """With many jobs in flight the lane never runs two device stages
    at once (workers=1), while read/HTTP stages of other jobs overlap
    it — the overlap events the metrics record."""
    vdaf = VdafInstance.count()
    leader_task, _, _ = provision(pair, vdaf)
    http = _upload(pair, leader_task, vdaf, [1] * 8)
    assert _make_jobs(pair, job_size=2) == 4

    drv = AggregationJobDriver(pair["leader_ds"], http)
    orig_device_init = drv.device_init

    def slow_device_init(st):
        time.sleep(0.05)  # widen the window a concurrent dispatch would need
        return orig_device_init(st)

    drv.device_init = slow_device_init
    pipe = StepPipeline(drv, StepPipelineConfig(device_lane_workers=1))
    try:
        jd = JobDriver(
            JobDriverConfig(max_concurrent_job_workers=4),
            drv.acquirer(),
            drv.stepper,
            pipeline=pipe,
        )
        while jd.run_once():
            pass
        status = pipe.status()
    finally:
        pipe.close()
    assert _agg_job_states(pair["leader_ds"]) == {"finished": 4}
    assert status["device_lane"]["concurrent_peak"] == 1
    assert status["device_lane"]["dispatches"] == 8


def test_expired_lease_at_read_steps_back(pair):
    """_lease_deadline raising (already-expired lease) inside the read
    stage maps to reason=deadline_expired — same as the serial path."""
    drv, acquired = _one_leased_job(pair)

    def expired(a):
        raise DeadlineExceeded("lease already expired (test)")

    drv._lease_deadline = expired
    pipe = StepPipeline(drv, StepPipelineConfig())
    try:
        delta = _step_back_delta(
            "deadline_expired", lambda: pipe.submit(acquired).result(timeout=60)
        )
    finally:
        pipe.close()
    assert delta == 1


def test_abandon_after_max_attempts_still_applies(pair):
    """The attempts ceiling is enforced in the pipeline's read stage,
    like the serial stepper's entry check."""
    import dataclasses

    drv, acquired = _one_leased_job(pair)
    lease = dataclasses.replace(
        acquired.lease, attempts=drv.cfg.maximum_attempts_before_failure + 1
    )
    over = dataclasses.replace(acquired, lease=lease)
    before = metrics.job_cancel_counter.get(kind="aggregation")
    pipe = StepPipeline(drv, StepPipelineConfig())
    try:
        pipe.submit(over).result(timeout=60)
    finally:
        pipe.close()
    assert metrics.job_cancel_counter.get(kind="aggregation") == before + 1
    assert _agg_job_states(pair["leader_ds"]) == {"abandoned": 1}


def _queue_wait(stage):
    """(count, sum) of janus_step_pipeline_queue_wait_seconds{stage}."""
    doc = metrics.REGISTRY.snapshot()["janus_step_pipeline_queue_wait_seconds"]
    for sample in doc["samples"]:
        if sample["labels"] == {"stage": stage}:
            return sample["count"], sample["sum"]
    return 0, 0.0


class _StubDriver:
    """The stage methods StepPipeline calls, with a device_init that
    blocks the lane until `gate` opens."""

    def __init__(self, gate):
        from types import SimpleNamespace

        self.gate = gate
        self.cfg = SimpleNamespace(maximum_attempts_before_failure=10)

    def read_job(self, acquired):
        from types import SimpleNamespace

        jobrow = SimpleNamespace(state=AggregationJobState.IN_PROGRESS, trace_context=None)
        return "task", jobrow, [], []

    def _lease_deadline(self, acquired):
        return None

    def plan_step(self, acquired, task, jobrow, ras):
        return "init", []

    def stage_init(self, acquired, task, jobrow, rows, reports):
        from types import SimpleNamespace

        return SimpleNamespace(engine=None, prestaged=None, multi_round=False)

    def device_init(self, st):
        assert self.gate.wait(10)

    def http_init(self, st):
        pass

    def device_accumulate(self, st):
        pass

    def commit_finish(self, st):
        pass

    def handle_step_error(self, acquired, e):
        return False


def test_queue_wait_observed_for_blocked_device_lane_and_staging():
    """Job 1 holds the one-worker device lane; job 2, staged, waits in
    the lane's queue; job 3 waits for a staging-window slot. Both waits
    are observed from their own stamps once the lane opens."""
    import threading
    from types import SimpleNamespace

    gate = threading.Event()
    pipe = StepPipeline(_StubDriver(gate), StepPipelineConfig(prefetch_depth=2))
    device0, staging0 = _queue_wait("device"), _queue_wait("staging")
    try:
        futs = [
            pipe.submit(SimpleNamespace(job_id=i, lease=SimpleNamespace(attempts=1)))
            for i in range(3)
        ]
        time.sleep(0.25)
        gate.set()
        for f in futs:
            f.result(timeout=30)
    finally:
        pipe.close()
    n_dev, s_dev = (a - b for a, b in zip(_queue_wait("device"), device0))
    n_stg, s_stg = (a - b for a, b in zip(_queue_wait("staging"), staging0))
    assert n_dev == 6  # init + accumulate per job
    assert s_dev >= 0.2  # job 2 queued behind the blocked lane
    assert n_stg == 3  # every hot-path job passes the staging window
    assert s_stg >= 0.2  # job 3 waited for a slot


def test_pipelined_job_observes_queue_waits_and_helper_init_stages(pair):
    """A real pipelined job observes a queue wait at each of its
    stages, and its helper's init spans feed the init-stage histogram."""
    vdaf = VdafInstance.count()
    leader_task, _, _ = provision(pair, vdaf)
    http = _upload(pair, leader_task, vdaf, [1, 0, 1])
    assert _make_jobs(pair) == 1

    def init_stages():
        doc = metrics.REGISTRY.snapshot()["janus_aggregate_init_stage_seconds"]
        return {s["labels"]["stage"]: s["count"] for s in doc["samples"]}

    waits0 = {s: _queue_wait(s)[0] for s in ("read", "device", "http", "commit", "staging")}
    stages0 = init_stages()
    drv = AggregationJobDriver(pair["leader_ds"], http)
    pipe = StepPipeline(drv, StepPipelineConfig())
    try:
        jd = JobDriver(JobDriverConfig(), drv.acquirer(), drv.stepper, pipeline=pipe)
        assert jd.run_once() == 1
    finally:
        pipe.close()
    assert _agg_job_states(pair["leader_ds"]) == {"finished": 1}
    waits = {s: _queue_wait(s)[0] - n for s, n in waits0.items()}
    assert waits == {"read": 1, "device": 2, "http": 1, "commit": 1, "staging": 1}
    stages = init_stages()
    for stage in ("hpke_stage", "replay_tx", "columnar", "accumulate", "write_tx"):
        assert stages.get(stage, 0) > stages0.get(stage, 0), stage
