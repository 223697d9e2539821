"""The leader driver's single-pass staging of stored shares.

`AggregationJobDriver._stage_pending` decrypts each sealed leader share
straight into bucket-padded limb columns and range-checks them in one
vectorised pass. The differential test pins its columns, masks and
marks bit for bit against the per-row decode it replaced
(`decode_field_rows` + `seeds_to_lanes` + `pad_args`, reproduced in
`_staged_by_rows`). The read-path tests pin that the job's read
transaction leaves the shares sealed and that a share's decryption
still behaves as it did inside the transaction.
"""

import secrets
from types import SimpleNamespace

import numpy as np
import pytest

from janus_tpu.aggregator.aggregation_job_driver import AggregationJobDriver
from janus_tpu.aggregator.aggregation_job_creator import (
    AggregationJobCreator,
    AggregationJobCreatorConfig,
)
from janus_tpu.aggregator.engine_cache import bucket_size, pad_args
from janus_tpu.aggregator.step_pipeline import StepPipeline, StepPipelineConfig
from janus_tpu.core.time_util import MockClock
from janus_tpu.datastore import (
    AggregationJobModel,
    AggregationJobState,
    LeaderStoredReport,
    ReportAggregationModel,
    ReportAggregationState,
)
from janus_tpu.datastore.models import SealedLeaderReport
from janus_tpu.datastore.store import Crypter, EphemeralDatastore
from janus_tpu.messages import (
    AggregationJobId,
    Duration,
    HpkeCiphertext,
    HpkeConfigId,
    Interval,
    PrepareError,
    ReportId,
    Role,
    Time,
)
from janus_tpu.task import QueryTypeConfig, TaskBuilder
from janus_tpu.vdaf.engine import jf_for
from janus_tpu.vdaf.registry import VdafInstance, circuit_for, prio3_host
from janus_tpu.vdaf.wire import Prio3Wire, decode_field_rows, seeds_to_lanes
from janus_tpu.messages.codec import DecodeError

T0 = 1_600_000_000


def _task(vdaf, role=Role.LEADER):
    return (
        TaskBuilder(QueryTypeConfig.time_interval(), vdaf, role)
        .with_(min_batch_size=1)
        .build()
    )


def _measurement(vdaf, i):
    if vdaf.kind == "count":
        return i % 2
    if vdaf.kind == "sumvec":
        return [(i + j) % (1 << vdaf.bits) for j in range(vdaf.length)]
    if vdaf.kind == "sparse_sumvec":
        blocks = [(1, [i % 2] * vdaf.block_size), (4, [1] * vdaf.block_size)]
        return blocks[: 1 + i % 2]
    raise AssertionError(vdaf.kind)


def _shares(vdaf, n):
    """n honest (report id, public share, leader share) triples."""
    host = prio3_host(vdaf)
    wire = Prio3Wire(circuit_for(vdaf))
    out = []
    for i in range(n):
        rid = secrets.token_bytes(16)
        public, (leader, _helper) = host.shard(_measurement(vdaf, i), rid)
        out.append(
            (
                rid,
                wire.encode_public_share(public),
                wire.encode_leader_share(
                    leader.measurement_share, leader.proof_share, leader.joint_rand_blind
                ),
            )
        )
    return out


def _set_word(share: bytes, word: int, value: int) -> bytes:
    b = bytearray(share)
    b[8 * word : 8 * word + 8] = value.to_bytes(8, "little")
    return bytes(b)


def _staged_by_rows(task, wire, jf, pending, plain, b):
    """The per-row staging the single pass replaced, padded to b rows
    as the engine padded it."""
    n = len(pending)
    circ = wire.circ
    mlen = circ.input_len * wire.enc_size
    plen = circ.proof_len * wire.enc_size
    meas_rows, proof_rows, blind_rows = [None] * n, [None] * n, [None] * n
    part_rows0, part_rows1 = [None] * n, [None] * n
    idx_rows = [None] * n
    failed = [None] * n
    for i, ra in enumerate(pending):
        rep = plain.get(ra.report_id.data)
        if rep is None:
            failed[i] = PrepareError.REPORT_DROPPED
            continue
        payload = rep.leader_input_share
        if len(payload) != wire.leader_share_len:
            failed[i] = PrepareError.INVALID_MESSAGE
            continue
        meas_rows[i] = payload[:mlen]
        proof_rows[i] = payload[mlen : mlen + plen]
        if wire.uses_jr:
            blind_rows[i] = payload[mlen + plen :]
            try:
                parts = wire.decode_public_share(rep.public_share)
                part_rows0[i], part_rows1[i] = parts
                if wire.sparse:
                    idx_rows[i] = parts.indices
            except DecodeError:
                failed[i] = PrepareError.INVALID_MESSAGE
    meas, ok_m = decode_field_rows(jf, meas_rows, circ.input_len)
    proof, ok_p = decode_field_rows(jf, proof_rows, circ.proof_len)
    nonce, _ = seeds_to_lanes([ra.report_id.data for ra in pending])
    ok = ok_m & ok_p & np.array([f is None for f in failed])
    blind = parts_ = block_idx = None
    if wire.uses_jr:
        blind, ok_b = seeds_to_lanes(blind_rows)
        p0, ok_p0 = seeds_to_lanes(part_rows0)
        p1, ok_p1 = seeds_to_lanes(part_rows1)
        ok = ok & ok_b & ok_p0 & ok_p1
        parts_ = np.stack([p0, p1], axis=1)
    if wire.sparse:
        block_idx = np.full((n, circ.max_blocks), -1, dtype=np.int32)
        for i, row in enumerate(idx_rows):
            if row is not None:
                block_idx[i] = row
    padded = pad_args(b, meas, proof)
    return padded, nonce, blind, parts_, ok, failed, block_idx


# (vdaf, reports, bucket cap, what to break: (report, how))
STAGING_CASES = {
    "count": (VdafInstance.count(), 5, None, ()),
    "count_full_bucket": (VdafInstance.count(), 32, None, ()),
    "sumvec_jr": (VdafInstance.sum_vec(length=6, bits=3), 7, None, ()),
    "sparse_block_indices": (
        VdafInstance.sparse_sumvec(bits=2, length=24, block_size=4, max_blocks=2),
        6,
        None,
        ((4, "bad_public_share"),),
    ),
    "wrong_length": (
        VdafInstance.sum_vec(length=6, bits=3),
        5,
        None,
        ((1, "short"), (3, "long")),
    ),
    "count_at_p": (VdafInstance.count(), 4, None, ((2, "meas_at_p"),)),
    "high_limb_above_p": (
        VdafInstance.sum_vec(length=6, bits=3),
        6,
        None,
        ((0, "proof_hi_above"), (5, "meas_hi_above")),
    ),
    "high_limb_at_p_hi_low_at_p_lo": (
        VdafInstance.sum_vec(length=6, bits=3),
        6,
        None,
        ((2, "proof_hi_eq_lo_ge"), (3, "meas_hi_eq_lo_below")),
    ),
    "missing_report": (VdafInstance.count(), 4, None, ((1, "dropped"),)),
    "past_bucket_cap": (VdafInstance.sum_vec(length=6, bits=3), 40, 32, ((7, "dropped"),)),
}


def _break(vdaf, jf, share, how):
    """Share bytes broken as `how` says (None: the report is dropped)."""
    circ = circuit_for(vdaf)
    limbs = jf.LIMBS
    p = jf.MODULUS
    p_lo, p_hi = p & (2**64 - 1), p >> 64
    proof0 = circ.input_len * limbs  # word of the proof's first element
    if how == "dropped":
        return None
    if how == "short":
        return share[:-8]
    if how == "long":
        return share + bytes(8)
    if how == "meas_at_p":
        return _set_word(share, 0, p)
    if how == "meas_hi_above":
        return _set_word(share, 3 * limbs + 1, p_hi + 1)
    if how == "proof_hi_above":
        return _set_word(share, proof0 + 1, p_hi + 1)
    if how == "proof_hi_eq_lo_ge":
        share = _set_word(share, proof0 + 2, p_lo)
        return _set_word(share, proof0 + 3, p_hi)
    if how == "meas_hi_eq_lo_below":  # p - 1: in range
        share = _set_word(share, 0, p_lo - 1)
        return _set_word(share, 1, p_hi)
    raise AssertionError(how)


@pytest.mark.parametrize("case", sorted(STAGING_CASES))
def test_single_pass_staging_matches_row_decode(case):
    vdaf, n, cap, breaks = STAGING_CASES[case]
    task = _task(vdaf)
    wire = Prio3Wire(circuit_for(vdaf))
    jf = jf_for(wire.circ)
    crypter = Crypter()
    triples = _shares(vdaf, n)
    pending = [SimpleNamespace(report_id=ReportId(rid)) for rid, _, _ in triples]
    how = dict(breaks)
    plain, sealed = {}, {}
    for i, (rid, public, share) in enumerate(triples):
        if how.get(i) == "bad_public_share":
            public = public[:-1]
        elif i in how:
            share = _break(vdaf, jf, share, how[i])
            if share is None:
                continue
        rep = LeaderStoredReport(
            task.task_id, ReportId(rid), Time(T0), public, share,
            HpkeCiphertext(HpkeConfigId(0), b"", b""),
        )
        plain[rid] = rep
        sealed[rid] = SealedLeaderReport(
            task.task_id, ReportId(rid), Time(T0), public,
            crypter.encrypt(
                "client_reports", task.task_id.data + rid, "leader_input_share", share
            ),
            rep.helper_encrypted_input_share,
            crypter,
        )

    engine = SimpleNamespace(p3=SimpleNamespace(jf=jf), bucket_cap=cap)
    drv = AggregationJobDriver(None, None)
    meas, proof, nonce, blind, parts, ok, failed, block_idx = drv._stage_pending(
        task, wire, engine, pending, sealed
    )
    # a job past the engine's cap is dispatched in cap-sized chunks of
    # its n rows, so it is staged unpadded
    b = max(n, bucket_size(n, cap))
    (want_meas, want_proof), want_nonce, want_blind, want_parts, want_ok, want_failed, want_idx = (
        _staged_by_rows(task, wire, jf, pending, plain, b)
    )

    # the engine sees [n, ...] columns with the old dtypes
    for got, want in ((meas, want_meas), (proof, want_proof)):
        assert len(got) == len(want) == jf.LIMBS
        for g in got:
            assert g.shape[0] == n and g.dtype == np.uint64
    got_meas, got_proof = pad_args(b, meas, proof)
    for got, want in ((got_meas, want_meas), (got_proof, want_proof)):
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            assert np.array_equal(g, w)
            if b > n:
                assert not g[n:].any()  # padding rows are zero
    if b > n:
        # the padded base is handed over as it is, not copied again
        assert got_meas[0] is meas[0].base
        assert got_proof[-1] is proof[-1].base
    assert np.array_equal(nonce, want_nonce)
    assert np.array_equal(ok, want_ok) and ok.dtype == bool
    assert failed == want_failed
    for got, want in ((blind, want_blind), (parts, want_parts), (block_idx, want_idx)):
        assert (got is None) == (want is None)
        if got is not None:
            assert got.dtype == want.dtype and np.array_equal(got, want)
    # the broken reports, and only they, are rejected
    assert sorted(np.flatnonzero(~ok)) == sorted(
        i for i, h in how.items() if h != "meas_hi_eq_lo_below"
    )


# --- the read path -------------------------------------------------------


def _seed_job(ds, task, triples, share_crypter=None):
    """Store `triples` (report id, public share, leader share) as the
    task's client reports, the shares sealed under `share_crypter` when
    given, and cut and lease one job."""
    ds.run_tx(lambda tx: tx.put_task(task))
    crypter = ds._crypter
    if share_crypter is not None:
        ds._crypter = share_crypter
    try:
        for i, (rid, public, share) in enumerate(triples):
            rep = LeaderStoredReport(
                task.task_id, ReportId(rid), Time(T0 - 3600 + i), public, share,
                HpkeCiphertext(HpkeConfigId(0), b"", b""),
            )
            ds.run_tx(lambda tx, rep=rep: tx.put_client_report(rep))
    finally:
        ds._crypter = crypter
    AggregationJobCreator(ds, AggregationJobCreatorConfig(min_aggregation_job_size=1)).run_once()
    (acquired,) = ds.run_tx(
        lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 10)
    )
    return acquired


@pytest.fixture
def clock():
    return MockClock(Time(T0))


def test_read_transaction_leaves_the_shares_sealed(clock, monkeypatch):
    eph = EphemeralDatastore(clock=clock)
    try:
        ds = eph.datastore
        task = _task(VdafInstance.count())
        triples = _shares(task.vdaf, 4)
        acquired = _seed_job(ds, task, triples)
        calls = {"in_tx": 0, "outside": 0}
        depth = [0]
        orig_decrypt = Crypter.decrypt
        orig_run_tx = ds.run_tx

        def decrypt(self, table, row, column, data):
            if table == "client_reports":
                calls["in_tx" if depth[0] else "outside"] += 1
            return orig_decrypt(self, table, row, column, data)

        def run_tx(fn, name="tx"):
            depth[0] += 1
            try:
                return orig_run_tx(fn, name)
            finally:
                depth[0] -= 1

        monkeypatch.setattr(Crypter, "decrypt", decrypt)
        monkeypatch.setattr(ds, "run_tx", run_tx)
        drv = AggregationJobDriver(ds, None)
        t, job, ras, reports = drv.read_job(acquired)
        assert calls == {"in_tx": 0, "outside": 0}
        assert len(reports) == 4
        assert all(isinstance(r, SealedLeaderReport) for r in reports.values())
        st = drv.stage_init(acquired, t, job, ras, reports)
        assert calls == {"in_tx": 0, "outside": 4}
        assert st.ok.all() and st.meas[0].shape[0] == 4
        assert sorted(r.leader_input_share for r in reports.values()) == sorted(
            s for _, _, s in triples
        )
    finally:
        eph.cleanup()


def test_share_under_rotated_key_stages(clock):
    old, new = secrets.token_bytes(16), secrets.token_bytes(16)
    eph = EphemeralDatastore(clock=clock, crypter=Crypter([old]))
    try:
        ds = eph.datastore
        task = _task(VdafInstance.sum_vec(length=6, bits=3))
        triples = _shares(task.vdaf, 3)
        acquired = _seed_job(ds, task, triples)
        # the keys rotate: new writes are sealed under `new`, and the
        # shares stored under `old` must still open
        ds._crypter = Crypter([new, old])
        drv = AggregationJobDriver(ds, None)
        t, job, ras, reports = drv.read_job(acquired)
        pending = [ra for ra in ras if ra.state == ReportAggregationState.START]
        st = drv.stage_init(acquired, t, job, pending, reports)
        assert st.ok.all() and st.failed == [None] * 3
        by_id = {rid: share for rid, _, share in triples}
        circ = circuit_for(task.vdaf)
        jf = jf_for(circ)
        want, ok = decode_field_rows(
            jf,
            [by_id[ra.report_id.data][: circ.input_len * 16] for ra in pending],
            circ.input_len,
        )
        assert ok.all()
        for g, w in zip(st.meas, want):
            assert np.array_equal(g, w)
    finally:
        eph.cleanup()


@pytest.mark.parametrize("stepper", ["serial", "pipelined"])
def test_share_under_unknown_key_fails_the_step(clock, monkeypatch, stepper):
    """The share no key opens raises the Crypter's ValueError into
    handle_step_error, which does not take it as a step-back: the step
    fails and the reports stay in START. Under the pipeline the failed
    job also gives its staging-window slot back."""
    eph = EphemeralDatastore(clock=clock)
    try:
        ds = eph.datastore
        task = _task(VdafInstance.count())
        unknown = Crypter([secrets.token_bytes(16)])
        acquired = _seed_job(ds, task, _shares(task.vdaf, 2), share_crypter=unknown)
        drv = AggregationJobDriver(ds, None)
        seen = []
        orig = drv.handle_step_error

        def handle(acq, e):
            handled = orig(acq, e)
            seen.append((type(e), handled))
            return handled

        monkeypatch.setattr(drv, "handle_step_error", handle)
        if stepper == "serial":
            with pytest.raises(ValueError, match="datastore decryption failed"):
                drv.stepper(acquired)
            assert seen == [(ValueError, False)]
        else:
            pipe = StepPipeline(drv, StepPipelineConfig(prefetch_depth=1))
            try:
                pipe.submit(acquired).result(timeout=60)
                # the window's one slot is free again (taken without
                # blocking, so a leaked slot fails here and hangs nothing)
                assert pipe._staging_window.acquire(blocking=False)
                pipe._staging_window.release()
            finally:
                pipe.close()
            assert seen == [(ValueError, False)]
        # no verdict was written: the reports wait in START for a retry
        ras = ds.run_tx(
            lambda tx: tx.get_report_aggregations_for_job(task.task_id, acquired.job_id)
        )
        assert {ra.state for ra in ras} == {ReportAggregationState.START}
    finally:
        eph.cleanup()


def test_poplar1_job_reads_its_plaintext_share(clock):
    eph = EphemeralDatastore(clock=clock)
    try:
        ds = eph.datastore
        task = _task(VdafInstance.poplar1(bits=4))
        ds.run_tx(lambda tx: tx.put_task(task))
        secret = b"poplar1-leader-share-" + secrets.token_bytes(8)
        rid = ReportId(secrets.token_bytes(16))
        ds.run_tx(
            lambda tx: tx.put_client_report(
                LeaderStoredReport(
                    task.task_id, rid, Time(T0 - 60), b"pub", secret,
                    HpkeCiphertext(HpkeConfigId(0), b"ek", b"ct"),
                )
            )
        )
        job = AggregationJobModel(
            task.task_id,
            AggregationJobId(secrets.token_bytes(16)),
            b"\x00\x00",
            b"",
            Interval(Time(T0 - 3600), Duration(3600)),
            AggregationJobState.IN_PROGRESS,
            0,
        )
        ds.run_tx(lambda tx: tx.put_aggregation_job(job))
        ds.run_tx(
            lambda tx: tx.put_report_aggregation(
                ReportAggregationModel(
                    task.task_id, job.job_id, rid, Time(T0 - 60), 0,
                    ReportAggregationState.START,
                )
            )
        )
        (acquired,) = ds.run_tx(
            lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), 10)
        )
        drv = AggregationJobDriver(ds, None)
        t, jobrow, ras, reports = drv.read_job(acquired)
        kind, pending = drv.plan_step(acquired, t, jobrow, ras)
        assert kind == "poplar1" and len(pending) == 1
        rep = reports[rid.data]
        # what _step_poplar1_init reads: the plaintext, and the rest as stored
        assert rep.leader_input_share == secret
        assert rep.public_share == b"pub"
        assert rep.helper_encrypted_input_share == HpkeCiphertext(HpkeConfigId(0), b"ek", b"ct")
    finally:
        eph.cleanup()


def _prefix_of_zero_padded():
    base = np.zeros((32, 3), np.uint64)
    base[:5] = 7
    return base[:5], base


def _prefix_with_data_below():
    base = np.zeros((32, 3), np.uint64)
    base[:5] = 7
    base[20] = 1
    return base[:5], None


def _rows_from_the_middle():
    base = np.zeros((32, 3), np.uint64)
    base[2:7] = 7
    return base[2:7], None


def _base_of_another_bucket():
    base = np.zeros((64, 3), np.uint64)
    base[:5] = 7
    return base[:5], None


def _plain_array():
    return np.full((5, 3), 7, np.uint64), None


@pytest.mark.parametrize(
    "make",
    [
        _prefix_of_zero_padded,
        _prefix_with_data_below,
        _rows_from_the_middle,
        _base_of_another_bucket,
        _plain_array,
    ],
    ids=lambda f: f.__name__.strip("_"),
)
def test_pad_reuses_only_a_zero_padded_base(make):
    """`pad_args` hands over the base an array is the leading rows of
    only where that is exactly the zero padding; otherwise it pads a
    copy, as it always did."""
    arr, reused = make()
    (out,) = pad_args(32, arr)
    want = np.pad(arr, [(0, 32 - arr.shape[0]), (0, 0)])
    assert out.shape == want.shape and out.dtype == want.dtype
    assert np.array_equal(out, want)
    if reused is not None:
        assert out is reused
    else:
        assert not np.shares_memory(out, arr)
