"""Datastore tests against an ephemeral SQLite store.

Mirrors the strategy of reference aggregator_core/src/datastore/tests.rs
(44 tests against ephemeral postgres; SURVEY.md section 4.2): every op
exercised through the transactional facade, including lease semantics,
replay detection, crypter round-trips and GC deletes.
"""

import threading

import pytest

from janus_tpu.core.time_util import MockClock
from janus_tpu.datastore import (
    AggregateShareJob,
    AggregationJobModel,
    AggregationJobState,
    Batch,
    BatchAggregation,
    BatchAggregationState,
    BatchState,
    CollectionJobModel,
    CollectionJobState,
    LeaderStoredReport,
    OutstandingBatch,
    ReportAggregationModel,
    ReportAggregationState,
)
from janus_tpu.datastore.store import Crypter, EphemeralDatastore, TxConflict
from janus_tpu.core.hpke import generate_hpke_config_and_private_key
from janus_tpu.messages import (
    AggregationJobId,
    BatchId,
    CollectionJobId,
    Duration,
    HpkeCiphertext,
    HpkeConfigId,
    Interval,
    PrepareError,
    ReportId,
    ReportIdChecksum,
    Role,
    TaskId,
    Time,
)
from janus_tpu.task import QueryTypeConfig, TaskBuilder
from janus_tpu.vdaf.registry import VdafInstance


from conftest import DATASTORE_ENGINES


@pytest.fixture(params=DATASTORE_ENGINES)
def eph(request):
    e = EphemeralDatastore(engine=request.param)
    yield e
    e.cleanup()


def mktask(role=Role.LEADER):
    return TaskBuilder(QueryTypeConfig.time_interval(), VdafInstance.count(), role).build()


def test_task_round_trip(eph):
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    got = ds.run_tx(lambda tx: tx.get_task(task.task_id))
    assert got == task
    assert ds.run_tx(lambda tx: tx.get_task_ids()) == [task.task_id]
    ds.run_tx(lambda tx: tx.delete_task(task.task_id))
    assert ds.run_tx(lambda tx: tx.get_task(task.task_id)) is None


def _report(task, i=0, t=1000):
    return LeaderStoredReport(
        task.task_id,
        ReportId(bytes([i] * 16)),
        Time(t),
        b"pub",
        b"leader-share-secret",
        HpkeCiphertext(HpkeConfigId(0), b"ek", b"payload"),
    )


def test_client_reports(eph):
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    rep = _report(task)
    assert ds.run_tx(lambda tx: tx.put_client_report(rep))
    # replay
    assert not ds.run_tx(lambda tx: tx.put_client_report(rep))
    got = ds.run_tx(lambda tx: tx.get_client_report(task.task_id, rep.report_id))
    assert got == rep  # crypter round-trip
    assert ds.run_tx(lambda tx: tx.check_report_replayed(task.task_id, rep.report_id))

    for i in range(1, 5):
        ds.run_tx(lambda tx, i=i: tx.put_client_report(_report(task, i, 1000 + i)))
    claimed = ds.run_tx(lambda tx: tx.get_unaggregated_client_reports_for_task(task.task_id, 3))
    assert len(claimed) == 3
    # claims are exclusive
    claimed2 = ds.run_tx(lambda tx: tx.get_unaggregated_client_reports_for_task(task.task_id, 10))
    assert len(claimed2) == 2
    assert not set(r for r, _ in claimed) & set(r for r, _ in claimed2)
    # release back
    ds.run_tx(lambda tx: tx.mark_reports_unaggregated(task.task_id, [claimed[0][0]]))
    claimed3 = ds.run_tx(lambda tx: tx.get_unaggregated_client_reports_for_task(task.task_id, 10))
    assert [r for r, _ in claimed3] == [claimed[0][0]]

    n = ds.run_tx(
        lambda tx: tx.count_client_reports_for_interval(
            task.task_id, Interval(Time(1000), Duration(3))
        )
    )
    assert n == 3
    total, started = ds.run_tx(lambda tx: tx.count_client_reports_for_task(task.task_id))
    assert total == 5 and started == 5
    # (never-claimed, claimed) split for ledger expiry attribution; both
    # expired rows here were claimed above, so they count as reclaimed.
    deleted = ds.run_tx(lambda tx: tx.delete_expired_client_reports(task.task_id, Time(1002), 10))
    assert deleted == (0, 2)


def test_sealed_client_reports_for_job(eph):
    """One statement fetches a job's START reports with their leader
    shares still sealed; each opens to what was stored. Reports past
    START, and a START row whose report is gone, are absent."""
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    job = _aggjob(task)
    ds.run_tx(lambda tx: tx.put_aggregation_job(job))
    reps = [_report(task, i, 1000 + i) for i in range(4)]
    for rep in reps[:3]:  # report 3 is not stored
        ds.run_tx(lambda tx, rep=rep: tx.put_client_report(rep))
    states = [
        ReportAggregationState.START,
        ReportAggregationState.START,
        ReportAggregationState.FINISHED,
        ReportAggregationState.START,
    ]
    ds.run_tx(
        lambda tx: [
            tx.put_report_aggregation(
                ReportAggregationModel(
                    task.task_id, job.job_id, rep.report_id, rep.client_time, i, state
                )
            )
            for i, (rep, state) in enumerate(zip(reps, states))
        ]
    )
    got = ds.run_tx(
        lambda tx: tx.get_sealed_client_reports_for_job(task.task_id, job.job_id)
    )
    assert sorted(got) == sorted(r.report_id.data for r in reps[:2])
    for rep in reps[:2]:
        sealed = got[rep.report_id.data]
        assert sealed.sealed_leader_input_share != rep.leader_input_share
        assert sealed.leader_input_share == rep.leader_input_share
        assert (
            sealed.report_id,
            sealed.client_time,
            sealed.public_share,
            sealed.helper_encrypted_input_share,
        ) == (
            rep.report_id,
            rep.client_time,
            rep.public_share,
            rep.helper_encrypted_input_share,
        )


def _aggjob(task, jid=1):
    return AggregationJobModel(
        task.task_id,
        AggregationJobId(bytes([jid] * 16)),
        b"",
        b"",
        Interval(Time(1000), Duration(100)),
        AggregationJobState.IN_PROGRESS,
        0,
    )


def test_aggregation_job_lease_cycle(eph):
    ds = eph.datastore
    clock = eph.clock
    task = mktask()
    job = _aggjob(task)
    ds.run_tx(lambda tx: tx.put_task(task))
    ds.run_tx(lambda tx: tx.put_aggregation_job(job))
    got = ds.run_tx(lambda tx: tx.get_aggregation_job(task.task_id, job.job_id))
    assert got == job

    acq = ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 10))
    assert len(acq) == 1 and acq[0].lease.attempts == 1
    # second acquire sees nothing (lease held)
    assert ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 10)) == []
    # lease expiry makes it reacquirable with attempts bumped
    clock.advance(Duration(601))
    acq2 = ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 10))
    assert len(acq2) == 1 and acq2[0].lease.attempts == 2
    # stale lease release must conflict
    with pytest.raises(TxConflict):
        ds.run_tx(lambda tx: tx.release_aggregation_job(acq[0]))
    # good release
    ds.run_tx(lambda tx: tx.release_aggregation_job(acq2[0]))
    acq3 = ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 10))
    assert len(acq3) == 1 and acq3[0].lease.attempts == 1

    # finished jobs aren't acquirable
    ds.run_tx(lambda tx: tx.release_aggregation_job(acq3[0]))
    ds.run_tx(lambda tx: tx.update_aggregation_job(job.with_state(AggregationJobState.FINISHED)))
    assert ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 10)) == []


def test_report_aggregations(eph):
    ds = eph.datastore
    task = mktask()
    job = _aggjob(task)
    ds.run_tx(lambda tx: tx.put_task(task))
    ds.run_tx(lambda tx: tx.put_aggregation_job(job))
    ras = [
        ReportAggregationModel(
            task.task_id,
            job.job_id,
            ReportId(bytes([i] * 16)),
            Time(1000 + i),
            i,
            ReportAggregationState.WAITING_LEADER,
            prep_blob=b"secret-prep-" + bytes([i]),
        )
        for i in range(3)
    ]
    ds.run_tx(lambda tx: [tx.put_report_aggregation(ra) for ra in ras])
    got = ds.run_tx(lambda tx: tx.get_report_aggregations_for_job(task.task_id, job.job_id))
    assert got == ras  # order + crypter round trip
    upd = ras[1].failed(PrepareError.VDAF_PREP_ERROR)
    ds.run_tx(lambda tx: tx.update_report_aggregation(upd))
    got = ds.run_tx(lambda tx: tx.get_report_aggregations_for_job(task.task_id, job.job_id))
    assert got[1] == upd and got[1].prepare_error == PrepareError.VDAF_PREP_ERROR
    # helper replay check: one set query over the whole id list
    from janus_tpu.messages import ReportId as _RID

    unknown = _RID(bytes(16))
    replayed = ds.run_tx(
        lambda tx: tx.get_aggregated_report_ids(
            task.task_id, [ras[0].report_id, unknown]
        )
    )
    assert replayed == {ras[0].report_id.data}


def test_batch_aggregations_and_conflict(eph):
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    iv = Interval(Time(1000), Duration(100))
    ba = BatchAggregation(
        task.task_id,
        iv.to_bytes(),
        b"",
        0,
        BatchAggregationState.AGGREGATING,
        b"share-bytes",
        5,
        iv,
        ReportIdChecksum(b"\x01" * 32),
    )
    ds.run_tx(lambda tx: tx.put_batch_aggregation(ba))
    # unique violation -> TxConflict -> retried by run_tx; do it raw
    with pytest.raises(Exception):
        ds.run_tx(lambda tx: (_ for _ in ()).throw(TxConflict("x")))
    got = ds.run_tx(lambda tx: tx.get_batch_aggregation(task.task_id, iv.to_bytes(), b"", 0))
    assert got == ba
    ds.run_tx(lambda tx: tx.mark_batch_aggregations_collected(task.task_id, iv.to_bytes(), b""))
    got = ds.run_tx(lambda tx: tx.get_batch_aggregation(task.task_id, iv.to_bytes(), b"", 0))
    assert got.state == BatchAggregationState.COLLECTED

    big = Interval(Time(900), Duration(400))
    found = ds.run_tx(lambda tx: tx.get_batch_aggregations_intersecting_interval(task.task_id, big))
    assert [b.ord for b in found] == [0]
    none = ds.run_tx(
        lambda tx: tx.get_batch_aggregations_intersecting_interval(
            task.task_id, Interval(Time(0), Duration(100))
        )
    )
    assert none == []


def test_collection_jobs(eph):
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    iv = Interval(Time(1000), Duration(100))
    cj = CollectionJobModel(
        task.task_id,
        CollectionJobId(bytes(16)),
        b"query-bytes",
        b"",
        iv.to_bytes(),
        CollectionJobState.START,
    )
    ds.run_tx(lambda tx: tx.put_collection_job(cj))
    assert ds.run_tx(lambda tx: tx.find_collection_job_by_query(task.task_id, b"query-bytes")) == cj
    assert ds.run_tx(lambda tx: tx.find_collection_job_by_query(task.task_id, b"other")) is None

    # START jobs are acquirable (the driver checks readiness itself)
    acq0 = ds.run_tx(lambda tx: tx.acquire_incomplete_collection_jobs(Duration(600), 10))
    assert len(acq0) == 1
    ds.run_tx(lambda tx: tx.release_collection_job(acq0[0]))
    import dataclasses

    cj2 = dataclasses.replace(
        cj,
        state=CollectionJobState.COLLECTABLE,
        report_count=5,
        client_timestamp_interval=iv,
        leader_aggregate_share=b"leader-share",
        helper_encrypted_aggregate_share=b"enc-helper",
    )
    ds.run_tx(lambda tx: tx.update_collection_job(cj2))
    got = ds.run_tx(lambda tx: tx.get_collection_job(task.task_id, cj.collection_job_id))
    assert got == cj2  # crypter round trip on leader share
    acq = ds.run_tx(lambda tx: tx.acquire_incomplete_collection_jobs(Duration(600), 10))
    assert len(acq) == 1
    ds.run_tx(lambda tx: tx.release_collection_job(acq[0]))


def test_aggregate_share_jobs(eph):
    ds = eph.datastore
    task = mktask(Role.HELPER)
    ds.run_tx(lambda tx: tx.put_task(task))
    iv = Interval(Time(1000), Duration(100))
    job = AggregateShareJob(
        task.task_id, iv.to_bytes(), b"", b"helper-share-secret", 7, ReportIdChecksum(b"\x02" * 32)
    )
    ds.run_tx(lambda tx: tx.put_aggregate_share_job(job))
    got = ds.run_tx(lambda tx: tx.get_aggregate_share_job(task.task_id, iv.to_bytes(), b""))
    assert got == job
    assert (
        ds.run_tx(lambda tx: tx.count_aggregate_share_jobs_for_batch(task.task_id, iv.to_bytes()))
        == 1
    )


def test_batches_and_outstanding(eph):
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    iv = Interval(Time(1000), Duration(100))
    b = Batch(task.task_id, iv.to_bytes(), b"", BatchState.OPEN, 1, iv)
    ds.run_tx(lambda tx: tx.put_batch(b))
    got = ds.run_tx(lambda tx: tx.get_batch(task.task_id, iv.to_bytes(), b""))
    assert got == b
    import dataclasses

    b2 = dataclasses.replace(b, state=BatchState.CLOSED, outstanding_aggregation_jobs=0)
    ds.run_tx(lambda tx: tx.update_batch(b2))
    assert ds.run_tx(lambda tx: tx.get_batch(task.task_id, iv.to_bytes(), b"")) == b2

    ob = OutstandingBatch(task.task_id, BatchId(b"\x07" * 32), Time(1000))
    ds.run_tx(lambda tx: tx.put_outstanding_batch(ob))
    assert ds.run_tx(lambda tx: tx.get_outstanding_batches(task.task_id)) == [ob]
    assert ds.run_tx(lambda tx: tx.get_outstanding_batches(task.task_id, Time(1000))) == [ob]
    assert ds.run_tx(lambda tx: tx.get_outstanding_batches(task.task_id, Time(2000))) == []
    ds.run_tx(lambda tx: tx.mark_outstanding_batch_filled(task.task_id, ob.batch_id))
    assert ds.run_tx(lambda tx: tx.get_outstanding_batches(task.task_id)) == []


def test_global_hpke_keys(eph):
    ds = eph.datastore
    kp = generate_hpke_config_and_private_key(config_id=42)
    ds.run_tx(lambda tx: tx.put_global_hpke_keypair(kp))
    got = ds.run_tx(lambda tx: tx.get_global_hpke_keypairs())
    assert got == [(kp, "pending")]
    ds.run_tx(lambda tx: tx.set_global_hpke_keypair_state(42, "active"))
    assert ds.run_tx(lambda tx: tx.get_global_hpke_keypairs())[0][1] == "active"
    ds.run_tx(lambda tx: tx.delete_global_hpke_keypair(42))
    assert ds.run_tx(lambda tx: tx.get_global_hpke_keypairs()) == []


def test_gc_deletes(eph):
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    job = _aggjob(task)
    ds.run_tx(lambda tx: tx.put_aggregation_job(job))
    ds.run_tx(
        lambda tx: tx.put_report_aggregation(
            ReportAggregationModel(
                task.task_id,
                job.job_id,
                ReportId(bytes(16)),
                Time(1000),
                0,
                ReportAggregationState.START,
            )
        )
    )
    # cutoff before end: nothing deleted
    assert ds.run_tx(lambda tx: tx.delete_expired_aggregation_artifacts(task.task_id, Time(1050), 10)) == (0, 0, 0)
    # (jobs deleted, non-terminal canonical rows, non-terminal param
    # rows): the START row dies with its job, so the GC books one
    # in-flight expiry in the canonical lane.
    assert ds.run_tx(lambda tx: tx.delete_expired_aggregation_artifacts(task.task_id, Time(1200), 10)) == (1, 1, 0)
    assert ds.run_tx(lambda tx: tx.get_aggregation_job(task.task_id, job.job_id)) is None
    assert ds.run_tx(lambda tx: tx.get_report_aggregations_for_job(task.task_id, job.job_id)) == []


def test_crypter_key_rotation():
    k1, k2 = b"\x01" * 16, b"\x02" * 16
    old = Crypter([k1])
    ct = old.encrypt("t", b"r", "c", b"secret")
    rotated = Crypter([k2, k1])
    assert rotated.decrypt("t", b"r", "c", ct) == b"secret"
    with pytest.raises(ValueError):
        Crypter([k2]).decrypt("t", b"r", "c", ct)
    with pytest.raises(ValueError):
        rotated.decrypt("t", b"wrong-row", "c", ct)


def test_concurrent_lease_acquire(eph):
    """Two threads racing acquires must never double-claim a job."""
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    for i in range(8):
        ds.run_tx(lambda tx, i=i: tx.put_aggregation_job(_aggjob(task, i + 1)))
    results = [[], []]

    def worker(slot):
        got = ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 8))
        results[slot] = [a.job_id for a in got]

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not set(results[0]) & set(results[1])
    assert len(results[0]) + len(results[1]) == 8


# ---------------------------------------------------------------------------
# failpoint-driven crash recovery (janus_tpu.failpoints; the unit-scale
# companion of scripts/chaos_run.py): run_tx seams at tx begin /
# pre-commit / post-commit, and the invariant that a crash AFTER commit
# but BEFORE ack cannot double anything when the work is retried.
# ---------------------------------------------------------------------------


@pytest.fixture
def _failpoints():
    from janus_tpu import failpoints

    failpoints.clear()
    yield failpoints
    failpoints.clear()


def test_run_tx_pre_commit_fault_is_retried_once_committed(eph, _failpoints):
    """Injected pre-commit conflicts are absorbed by run_tx's own retry
    loop: the closure re-runs, the datastore commits exactly once."""
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    _failpoints.configure("datastore.commit.flaky_write=error:1,count=2")
    runs = {"n": 0}

    def fn(tx):
        runs["n"] += 1
        return tx.put_client_report(_report(task))

    assert ds.run_tx(fn, "flaky_write") is True  # fresh on the attempt that lands
    assert runs["n"] == 3  # two injected conflicts + the committing run
    assert ds.run_tx(lambda tx: tx.check_report_replayed(task.task_id, _report(task).report_id))


def test_run_tx_post_commit_crash_does_not_double_store(eph, _failpoints):
    """Crash after COMMIT, before the caller saw the result (the
    upload-ack window): the retry replays the closure against committed
    state — put_client_report reports a replay, exactly one row exists,
    and the caller's observed result is the idempotent one."""
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    _failpoints.configure("datastore.post_commit.upload_batch=error:1,count=1")
    fresh = ds.run_tx(lambda tx: tx.put_client_report(_report(task)), "upload_batch")
    # the first attempt COMMITTED, then 'crashed' pre-ack; the retry's
    # answer (replay) is what the caller observes
    assert fresh is False
    rows, _ = ds.run_tx(lambda tx: tx.count_client_reports_for_task(task.task_id))
    assert rows == 1


def test_run_tx_post_commit_crash_does_not_double_aggregate(eph, _failpoints):
    """The exactly-once core, in the driver's REAL transaction shape:
    the accumulator flush shares its transaction with the token-guarded
    lease release (step_agg_job_write). A flush alone is idempotent
    only under rollback-retry; when the commit LANDED and the worker
    dies pre-ack, it is the lease release that refuses the replay — the
    retry's release sees a cleared token, raises TxConflict, and the
    whole replayed transaction rolls back. The ambiguous commit
    surfaces as a loud failure; the batch aggregation is never silently
    doubled."""
    import secrets as _secrets

    from janus_tpu.aggregator.accumulator import Accumulator

    ds = eph.datastore
    task = TaskBuilder(
        QueryTypeConfig.time_interval(), VdafInstance.count(), Role.LEADER
    ).with_(min_batch_size=1).build()
    ds.run_tx(lambda tx: tx.put_task(task))
    ds.run_tx(lambda tx: tx.put_aggregation_job(_aggjob(task, 1)))
    (acquired,) = ds.run_tx(
        lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 1)
    )
    acc = Accumulator(task, shard_count=1)
    rid = ReportId(_secrets.token_bytes(16))
    acc.update_single(b"batch-fp", [7], rid, Time(1_600_000_000))

    def write(tx):
        acc.flush_to_datastore(tx)
        tx.release_aggregation_job(acquired)

    _failpoints.configure("datastore.post_commit.step_agg_job_write=error:1,count=1")
    with pytest.raises(TxConflict):
        ds.run_tx(write, "step_agg_job_write")
    rows = ds.run_tx(
        lambda tx: tx.get_batch_aggregations_for_batch(task.task_id, b"batch-fp", b"")
    )
    assert len(rows) == 1 and rows[0].report_count == 1  # committed exactly once
    # and the committed attempt DID release the lease: reacquirable now
    (re,) = ds.run_tx(
        lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 1)
    )
    assert re.lease.attempts == 1


def test_run_tx_tx_begin_fault_never_half_commits(eph, _failpoints):
    """A fault at BEGIN leaves nothing behind: the retry starts from a
    clean transaction."""
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    _failpoints.configure("datastore.tx_begin.begin_fault=error:1,count=1")
    assert ds.run_tx(lambda tx: tx.put_client_report(_report(task, 9)), "begin_fault")
    rows, _ = ds.run_tx(lambda tx: tx.count_client_reports_for_task(task.task_id))
    assert rows == 1


def test_step_back_lease_semantics(eph):
    """step_back_aggregation_job: token cleared, reacquire delayed,
    attempts refunded (count_attempt=False) or preserved (True); stale
    tokens conflict."""
    ds = eph.datastore
    clock = eph.clock
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))
    ds.run_tx(lambda tx: tx.put_aggregation_job(_aggjob(task, 1)))
    (a1,) = ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 1))
    assert a1.lease.attempts == 1
    ds.run_tx(lambda tx: tx.step_back_aggregation_job(a1, reacquire_delay_s=30))
    assert (
        ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 1)) == []
    )
    clock.advance(Duration(31))
    (a2,) = ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 1))
    assert a2.lease.attempts == 1  # refunded, then re-incremented
    # count_attempt=True keeps the ledger
    ds.run_tx(
        lambda tx: tx.step_back_aggregation_job(a2, reacquire_delay_s=0, count_attempt=True)
    )
    (a3,) = ds.run_tx(lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 1))
    assert a3.lease.attempts == 2
    # a stale holder cannot step back the new holder's lease
    with pytest.raises(TxConflict):
        with ds.tx() as tx:
            tx.step_back_aggregation_job(a1)


def test_trace_context_round_trip(eph):
    """ISSUE 6: the persisted causality link — a W3C traceparent stored
    on aggregation and collection job rows survives the round trip (and
    the absence of one reads back as None), on every engine."""
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))

    tp = "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"
    import dataclasses

    traced = dataclasses.replace(_aggjob(task, jid=1), trace_context=tp)
    bare = _aggjob(task, jid=2)
    ds.run_tx(lambda tx: tx.put_aggregation_job(traced))
    ds.run_tx(lambda tx: tx.put_aggregation_job(bare))
    got = ds.run_tx(lambda tx: tx.get_aggregation_job(task.task_id, traced.job_id))
    assert got.trace_context == tp
    assert got == traced
    assert ds.run_tx(
        lambda tx: tx.get_aggregation_job(task.task_id, bare.job_id)
    ).trace_context is None
    # state updates do not disturb the persisted context
    ds.run_tx(
        lambda tx: tx.update_aggregation_job(
            got.with_state(AggregationJobState.FINISHED)
        )
    )
    assert (
        ds.run_tx(
            lambda tx: tx.get_aggregation_job(task.task_id, traced.job_id)
        ).trace_context
        == tp
    )

    # the collection-link query finds jobs whose client interval
    # INTERSECTS the collection (same semantics as the batch gather:
    # a job straddling the boundary still contributed) — and only
    # those with a context
    links = ds.run_tx(
        lambda tx: tx.get_aggregation_job_trace_contexts(
            task.task_id, interval=Interval(Time(900), Duration(300))
        )
    )
    assert links == [tp]
    # straddle: job covers [1000, 1100), collection [1050, 1150)
    assert ds.run_tx(
        lambda tx: tx.get_aggregation_job_trace_contexts(
            task.task_id, interval=Interval(Time(1050), Duration(100))
        )
    ) == [tp]
    assert (
        ds.run_tx(
            lambda tx: tx.get_aggregation_job_trace_contexts(
                task.task_id, interval=Interval(Time(0), Duration(10))
            )
        )
        == []
    )

    cj = CollectionJobModel(
        task.task_id,
        CollectionJobId(b"\x07" * 16),
        b"query",
        b"",
        Interval(Time(1000), Duration(100)).to_bytes(),
        CollectionJobState.START,
        trace_context=tp,
    )
    ds.run_tx(lambda tx: tx.put_collection_job(cj))
    got_cj = ds.run_tx(
        lambda tx: tx.get_collection_job(task.task_id, cj.collection_job_id)
    )
    assert got_cj.trace_context == tp


def test_unaggregated_report_time_quantiles(eph):
    """The freshness-distribution query behind the sampler's p50/p95/p99
    gauges: quantile client_times over unaggregated reports only."""
    ds = eph.datastore
    task = mktask()
    ds.run_tx(lambda tx: tx.put_task(task))

    def put(tx):
        for i in range(10):
            tx.put_client_report(_report(task, i=i, t=1000 + i))

    ds.run_tx(put)
    # bucket_s=1: exact rank semantics (each bucket holds one second)
    rows = ds.run_tx(
        lambda tx: tx.unaggregated_report_time_quantiles_by_task(bucket_s=1)
    )
    assert len(rows) == 1
    task_id, n, oldest, vals = rows[0]
    assert bytes(task_id) == task.task_id.data and n == 10
    # the same scan carries the EXACT oldest time (the sampler's
    # oldest-age gauge rides it instead of a second index walk)
    assert oldest == 1000
    # ages ascending == client_time descending: p50 is the median time,
    # p95/p99 the oldest (rank/edge choices bias toward the older report)
    assert vals[0.5] == 1004
    assert vals[0.95] == 1000
    assert vals[0.99] == 1000
    # the default minute-wide buckets floor to the bucket's older edge:
    # one DB-side histogram scan, conservative within bucket_s
    coarse = ds.run_tx(lambda tx: tx.unaggregated_report_time_quantiles_by_task())
    assert coarse[0][1] == 10 and coarse[0][2] == 1000
    assert all(v == (1000 // 60) * 60 for v in coarse[0][3].values())
    # claimed (aggregation_started) reports leave the distribution
    claimed = ds.run_tx(
        lambda tx: tx.get_unaggregated_client_reports_for_task(task.task_id, 9)
    )
    assert len(claimed) == 9
    rows = ds.run_tx(
        lambda tx: tx.unaggregated_report_time_quantiles_by_task(bucket_s=1)
    )
    assert rows[0][1] == 1 and rows[0][2] == 1009 and rows[0][3][0.5] == 1009
