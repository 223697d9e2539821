"""Report-batch generation utilities (tests, benchmarks, load drivers).

The analog of the reference's transcript generator
(core/src/test_util/mod.rs:50 run_vdaf) adapted to column batches:
produce every array the two-party device step consumes, via the
batched device shard (so generating 1M reports is itself a device op).
"""

from __future__ import annotations

import numpy as np

from .registry import VdafInstance, prio3_batched


def random_measurements(inst: VdafInstance, batch: int, rng: np.random.Generator):
    if inst.kind == "count":
        return rng.integers(0, 2, size=batch)
    if inst.kind == "sum":
        hi = min(inst.bits, 62)
        return rng.integers(0, 1 << hi, size=batch)
    if inst.kind == "sumvec":
        hi = min(inst.bits, 62)
        return rng.integers(0, 1 << hi, size=(batch, inst.length))
    if inst.kind == "sparse_sumvec":
        # per-report list of (block_index, dense block) pairs, sorted by
        # index — the sparse measurement currency (vdaf.reference)
        hi = min(inst.bits, 62)
        n_blocks = inst.length // inst.block_size
        out = []
        for _ in range(batch):
            nb = int(rng.integers(1, inst.max_blocks + 1))
            idxs = sorted(rng.choice(n_blocks, size=nb, replace=False).tolist())
            out.append(
                [
                    (int(b), [int(v) for v in rng.integers(0, 1 << hi, size=inst.block_size)])
                    for b in idxs
                ]
            )
        return out
    if inst.kind == "histogram":
        return rng.integers(0, inst.length, size=batch)
    if inst.kind == "countvec":
        return rng.integers(0, 2, size=(batch, inst.length))
    if inst.kind == "fixedpoint":
        # signed raw values kept small enough that any vector's L2 norm < 1
        offset = 1 << (inst.bits - 1)
        hi = max(1, int(offset / (inst.length**0.5)) // 2)
        return rng.integers(-hi, hi, size=(batch, inst.length))
    raise ValueError(inst.kind)


def sparse_compact_batch(inst: VdafInstance, measurements):
    """Convert sparse pair-measurements to the device currency:
    ([batch, compact_len] uint64 compact value rows, [batch, max_blocks]
    int32 block indices, -1 padding). The value rows feed the batched
    engine exactly like dense SumVec rows; the indices ride the public
    share / scatter path."""
    from .registry import circuit_for

    circ = circuit_for(inst)
    vals, idxs = [], []
    for m in measurements:
        v, ix = circ.compact_values(m)
        vals.append(v)
        idxs.append(list(ix))
    return (
        np.asarray(vals, dtype=np.uint64),
        np.asarray(idxs, dtype=np.int32),
    )


def make_wire_reports(
    inst: VdafInstance,
    measurements,
    task_id,
    leader_hpke_config,
    helper_hpke_config,
    time,
    seed: int = 0,
    batch_args=None,
):
    """Device-shard a batch and assemble full DAP Report messages.

    A batched client: sharding runs on device (one traced computation
    for the whole batch), then each report is HPKE-sealed and framed
    exactly as client.Client.prepare_report does per report
    (reference client/src/lib.rs:212-260). Used by load generators and
    the served-mode bench. `batch_args` passes an already sharded batch
    (make_report_batch's step args for these measurements).
    """
    from ..core.hpke import HpkeApplicationInfo, Label, hpke_seal
    from ..messages import (
        InputShareAad,
        PlaintextInputShare,
        Report,
        ReportId,
        ReportMetadata,
        Role,
    )
    from .registry import circuit_for
    from .wire import Prio3Wire, encode_field_rows

    p3 = prio3_batched(inst)
    wire = Prio3Wire(circuit_for(inst))
    sparse = inst.kind == "sparse_sumvec"
    if sparse:
        from .reference import SparsePublicShare

        _, block_idx = sparse_compact_batch(inst, measurements)
    args = batch_args
    if args is None:
        args, _ = make_report_batch(inst, measurements, seed=seed)
    nonce_lanes, public_parts, leader_meas, leader_proof, blind0, helper_seed, blind1 = args
    n = nonce_lanes.shape[0]
    meas_rows = encode_field_rows(p3.jf, leader_meas)
    proof_rows = encode_field_rows(p3.jf, leader_proof)
    seed_rows = [r.tobytes() for r in np.asarray(helper_seed, dtype="<u8")]
    if p3.uses_joint_rand:
        blind0_rows = [r.tobytes() for r in np.asarray(blind0, dtype="<u8")]
        blind1_rows = [r.tobytes() for r in np.asarray(blind1, dtype="<u8")]
        pp = np.asarray(public_parts, dtype="<u8")
        part_rows = [(pp[i, 0].tobytes(), pp[i, 1].tobytes()) for i in range(n)]
    reports = []
    for i in range(n):
        report_id = ReportId(nonce_lanes[i].astype("<u8").tobytes())
        metadata = ReportMetadata(report_id, time)
        if p3.uses_joint_rand:
            parts = list(part_rows[i])
            leader_payload = wire.encode_leader_share_raw(
                meas_rows[i] + proof_rows[i], blind0_rows[i]
            )
            helper_payload = wire.encode_helper_share(seed_rows[i], blind1_rows[i])
        else:
            parts = []
            leader_payload = meas_rows[i] + proof_rows[i]
            helper_payload = wire.encode_helper_share(seed_rows[i], None)
        if sparse:
            public_share = wire.encode_public_share(SparsePublicShare(parts, block_idx[i]))
        elif p3.uses_joint_rand:
            public_share = wire.encode_public_share(parts)
        else:
            public_share = b""
        aad = InputShareAad(task_id, metadata, public_share).to_bytes()
        leader_ct = hpke_seal(
            leader_hpke_config,
            HpkeApplicationInfo(Label.INPUT_SHARE, Role.CLIENT, Role.LEADER),
            PlaintextInputShare((), leader_payload).to_bytes(),
            aad,
        )
        helper_ct = hpke_seal(
            helper_hpke_config,
            HpkeApplicationInfo(Label.INPUT_SHARE, Role.CLIENT, Role.HELPER),
            PlaintextInputShare((), helper_payload).to_bytes(),
            aad,
        )
        reports.append(Report(metadata, public_share, leader_ct, helper_ct))
    return reports


def zero_report_batch(inst: VdafInstance, batch: int):
    """All-zero step args with the shapes and dtypes make_report_batch
    returns, built on the host: the shapes come from an abstract trace
    of the shard (jax.eval_shape), so nothing is compiled or run on a
    device. The reports do not verify; engine warm-up only needs the
    programs they compile."""
    import jax

    p3 = prio3_batched(inst)
    n_seeds = 4 if p3.uses_joint_rand else 2
    inp = tuple(
        jax.ShapeDtypeStruct((batch, p3.circ.input_len), np.uint64) for _ in range(p3.jf.LIMBS)
    )
    sh = jax.eval_shape(
        p3.shard,
        inp,
        jax.ShapeDtypeStruct((batch, 2), np.uint64),
        jax.ShapeDtypeStruct((batch, n_seeds, 2), np.uint64),
    )
    zeros = lambda t: jax.tree_util.tree_map(lambda a: np.zeros(a.shape, a.dtype), t)  # noqa: E731
    return (
        np.zeros((batch, 2), np.uint64),
        zeros(sh["public_parts"]),
        zeros(sh["leader_meas"]),
        zeros(sh["leader_proof"]),
        zeros(sh["blind0"]),
        zeros(sh["helper_seed"]),
        zeros(sh["blind1"]),
    )


def make_report_batch(inst: VdafInstance, measurements, seed: int = 0, shard_chunk: int = 0):
    """Shard a batch of measurements on device.

    Returns (step_args, measurements) where step_args is the positional
    tuple for parallel.api.two_party_step: (nonce_lanes, public_parts,
    leader_meas, leader_proof, blind0, helper_seed, blind1).

    shard_chunk > 0 shards in sub-batches of that size and concatenates
    on host: the FLP *prove* graph peaks at [chunk, arity, n2] per
    sub-batch, so long-vector configs (SumVec len=100k) can stage a
    batch far larger than the prove path could hold at once. The
    prepare step's own memory is unaffected (query needs no wire-poly
    coefficient arrays).
    """
    p3 = prio3_batched(inst)
    rng = np.random.default_rng(seed)
    batch = len(measurements)
    nonce_lanes = rng.integers(0, 1 << 63, size=(batch, 2), dtype=np.uint64)
    n_seeds = 4 if p3.uses_joint_rand else 2
    rand_lanes = rng.integers(0, 1 << 63, size=(batch, n_seeds, 2), dtype=np.uint64)

    def shard_slice(lo: int, hi: int):
        if inst.kind == "sparse_sumvec":
            # the device engine runs the COMPACT encoding: convert pair
            # measurements to compact value rows (the engine never sees
            # the logical length; indices ride the public share)
            vals, _ = sparse_compact_batch(inst, measurements[lo:hi])
            inp_np = p3.bc.encode_batch(vals)
        else:
            inp_np = p3.bc.encode_batch(measurements[lo:hi])
        inp = p3.jf.from_ints(inp_np.astype(object))
        return p3.shard_jit(inp, nonce_lanes[lo:hi], rand_lanes[lo:hi])

    if not shard_chunk or shard_chunk >= batch:
        sh = shard_slice(0, batch)
    else:
        parts = []
        for lo in range(0, batch, shard_chunk):
            s = shard_slice(lo, min(lo + shard_chunk, batch))
            # pull to host so device frees the sub-batch before the next
            parts.append(
                {
                    k: (
                        None
                        if v is None
                        else tuple(np.asarray(x) for x in v)
                        if isinstance(v, tuple)
                        else np.asarray(v)
                    )
                    for k, v in s.items()
                }
            )
        sh = {}
        for k in parts[0]:
            if parts[0][k] is None:
                sh[k] = None
            elif isinstance(parts[0][k], tuple):
                sh[k] = tuple(
                    np.concatenate([p[k][i] for p in parts])
                    for i in range(len(parts[0][k]))
                )
            else:
                sh[k] = np.concatenate([p[k] for p in parts])
    args = (
        nonce_lanes,
        sh["public_parts"],
        sh["leader_meas"],
        sh["leader_proof"],
        sh["blind0"],
        sh["helper_seed"],
        sh["blind1"],
    )
    return args, measurements
