"""Batched FLP prove/query/decide on device — the TPU heart.

The reference runs the FLP per report, serially, on CPU inside the
external `prio` crate (invoked from
aggregator/src/aggregator/aggregation_job_driver.rs:329-402 and
aggregator/src/aggregator.rs:1775-1797). Here one traced computation
processes a whole report batch: every value is a limb-tuple field array
with a leading [batch] axis, wire/gadget polynomial interpolation is
the batched NTT of janus_tpu.ops.ntt, and gadget evaluation is
elementwise — so XLA sees large fused elementwise graphs it can tile
onto the VPU, with throughput scaling in the batch dimension.

Semantics are byte/element-identical to the host oracle
(janus_tpu.vdaf.reference), enforced by differential tests. All four
Prio3 circuits (Count/Sum/SumVec/Histogram) have exactly one gadget
use of degree 2; the adapters below encode each circuit's gadget-call
schedule as static reshapes over the batch.

Per-report validity never branches: invalid reports yield a False lane
in the decision mask and are dropped at accumulation time (masked
aggregate), which is the static-shape answer to the reference's
per-report error handling (SURVEY.md section 7, "Ragged/failure-laden
batches").
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from ..fields.jfield import (
    JF64,
    JF128,
    fconst,
    fmap,
    fmul_pow2,
    fpad_axis,
    fpow_const,
    freshape,
    fsum,
    ftile,
    fwhere,
    is_zero,
    anti_recompute_barrier,
)

# FLP query via MXU limb contraction (ops/limbmm.py) for the chunked
# circuits. Read once at import (participates in tracing, like
# JANUS_NO_BARRIERS): JANUS_QUERY_MM=0 falls back to the VPU fold path.
_QUERY_MM = os.environ.get("JANUS_QUERY_MM", "1") != "0"
from ..ops.ntt import (
    intt_batched,
    lagrange_eval_weights,
    ntt_batched,
    poly_eval_powers,
    powers,
)
from .reference import (
    EVAL_POINT_CANDIDATES,
    Circuit,
    Count,
    FixedPointVec,
    Histogram,
    SparseSumVec,
    Sum,
    SumVec,
    next_pow2,
)


def jf_for(circuit: Circuit):
    return {8: JF64, 16: JF128}[circuit.FIELD.ENCODED_SIZE]


# ---------------------------------------------------------------------------
# Per-circuit batched adapters
# ---------------------------------------------------------------------------


class BatchedCircuit:
    """Vectorized gadget schedule for one validity circuit.

    All methods take/return limb-tuple field values with a leading
    [batch] axis. `calls_inputs` returns [batch, calls, arity];
    `gadget_eval` consumes wires with arity on axis 1 ([batch, arity,
    ...]) and returns the gadget output with that axis dropped.
    """

    def __init__(self, circ: Circuit):
        self.circ = circ
        self.jf = jf_for(circ)
        use = circ.gadget_uses[0]
        assert len(circ.gadget_uses) == 1, "Prio3 circuits have one gadget use"
        self.arity = use.gadget.arity
        self.calls = use.calls
        self.m = use.wire_poly_len
        self.gp_len = use.gadget_poly_len
        self.n2 = next_pow2(self.gp_len)

    # --- measurement plumbing (host-side, numpy-vectorized) ---
    def encode_batch(self, measurements) -> np.ndarray:
        """[batch] measurements -> [batch, input_len] uint64 (< p)."""
        raise NotImplementedError

    # --- schedule ---
    def calls_inputs(self, inp, joint_rand, shares_inv: int):
        raise NotImplementedError

    def gadget_eval(self, wires):
        raise NotImplementedError

    def finish(self, inp, joint_rand, gadget_outs, shares_inv: int):
        raise NotImplementedError

    def truncate(self, inp):
        raise NotImplementedError

    # --- helpers ---
    def _sic(self, shares_inv: int, shape=()):
        return fconst(self.jf, shares_inv, shape)


class BCount(BatchedCircuit):
    def encode_batch(self, measurements):
        a = np.asarray(measurements, dtype=np.uint64)
        assert ((a == 0) | (a == 1)).all()
        return a[:, None]

    def calls_inputs(self, inp, joint_rand, shares_inv):
        # [[x, x]]: one call, arity 2
        return fmap(lambda x: x[:, :, None].repeat(2, axis=2), inp)

    def gadget_eval(self, wires):
        jf = self.jf
        w0 = fmap(lambda x: x[:, 0], wires)
        w1 = fmap(lambda x: x[:, 1], wires)
        return jf.mul(w0, w1)

    def finish(self, inp, joint_rand, gadget_outs, shares_inv):
        jf = self.jf
        return jf.sub(fmap(lambda x: x[:, 0], gadget_outs), fmap(lambda x: x[:, 0], inp))

    def truncate(self, inp):
        return inp


class BSum(BatchedCircuit):
    def encode_batch(self, measurements):
        a = np.asarray(measurements, dtype=np.uint64)
        bits = self.circ.bits
        if bits < 64:
            assert (a < (np.uint64(1) << np.uint64(bits))).all()
        return (a[:, None] >> np.arange(bits, dtype=np.uint64)[None, :]) & np.uint64(1)

    def calls_inputs(self, inp, joint_rand, shares_inv):
        return fmap(lambda x: x[:, :, None], inp)  # [batch, bits, 1]

    def gadget_eval(self, wires):
        jf = self.jf
        x = fmap(lambda w: w[:, 0], wires)
        return jf.sub(jf.mul(x, x), x)  # x^2 - x

    def finish(self, inp, joint_rand, gadget_outs, shares_inv):
        jf = self.jf
        r = fmap(lambda x: x[:, 0], joint_rand)
        pw = powers(jf, r, self.calls + 1)  # [batch, calls+1]
        rp = fmap(lambda x: x[..., 1:], pw)  # r^1..r^calls
        return fsum(jf, jf.mul(rp, gadget_outs), axis=-1)

    def truncate(self, inp):
        jf = self.jf
        return fmap(
            lambda x: x[:, None],
            _pow2_weighted_sum(jf, inp, self.circ.bits, axis=-1),
        )


class _BChunked(BatchedCircuit):
    """Shared ParallelSum(Mul, chunk) schedule of SumVec and Histogram."""

    def _pair_inputs(self, inp, joint_rand, shares_inv):
        """(r^{i+1} x_i, x_i - shares_inv) pairs -> [batch, calls, 2*chunk]."""
        jf = self.jf
        n = self.circ.input_len
        ch = self.circ.chunk_length
        r = fmap(lambda x: x[:, 0], joint_rand)
        pw = powers(jf, r, n + 1)
        rp = fmap(lambda x: x[..., 1:], pw)  # [batch, n]: r^1..r^n
        a = jf.mul(rp, inp)
        b = jf.sub(inp, self._sic(shares_inv))
        # interleave (a_i, b_i) then pad to calls*chunk pairs
        pairs = fmap(
            lambda x, y: jnp.stack([x, y], axis=-1).reshape(x.shape[0], -1), a, b
        )
        total = self.calls * ch * 2
        pad = total - pairs[0].shape[-1]
        if pad:
            pairs = fmap(lambda x: jnp.pad(x, ((0, 0), (0, pad))), pairs)
        return fmap(lambda x: x.reshape(x.shape[0], self.calls, 2 * ch), pairs)

    def calls_inputs(self, inp, joint_rand, shares_inv):
        return self._pair_inputs(inp, joint_rand, shares_inv)

    def gadget_eval(self, wires):
        # wires [batch, 2*chunk, ...] -> sum_c w[2c]*w[2c+1]
        jf = self.jf
        ch = self.circ.chunk_length
        shaped = fmap(
            lambda w: w.reshape((w.shape[0], ch, 2) + w.shape[2:]), wires
        )
        x = fmap(lambda w: w[:, :, 0], shaped)
        y = fmap(lambda w: w[:, :, 1], shaped)
        return fsum(jf, jf.mul(x, y), axis=1)


class BSumVec(_BChunked):
    def encode_batch(self, measurements):
        a = np.asarray(measurements, dtype=np.uint64)  # [batch, length]
        bits = self.circ.bits
        out = (a[:, :, None] >> np.arange(bits, dtype=np.uint64)[None, None, :]) & np.uint64(1)
        return out.reshape(a.shape[0], -1)

    def finish(self, inp, joint_rand, gadget_outs, shares_inv):
        return fsum(self.jf, gadget_outs, axis=-1)

    def truncate(self, inp):
        # bits-major [batch, bits, length] layout: a trailing dim of
        # `bits` (16) pads 8x against the TPU's (8, 128) tile — at
        # len=100k batch=16 that one layout choice cost 683 MB of HBM
        # padding per limb temp (measured via compiled.memory_analysis)
        jf = self.jf
        bits = self.circ.bits
        length = self.circ.length
        v = fmap(
            lambda x: jnp.swapaxes(x.reshape(x.shape[0], length, bits), 1, 2), inp
        )
        return _pow2_weighted_sum(jf, v, bits)


class BHistogram(_BChunked):
    def encode_batch(self, measurements):
        a = np.asarray(measurements, dtype=np.int64)
        assert ((0 <= a) & (a < self.circ.length)).all()
        out = np.zeros((a.shape[0], self.circ.length), dtype=np.uint64)
        out[np.arange(a.shape[0]), a] = 1
        return out

    def finish(self, inp, joint_rand, gadget_outs, shares_inv):
        jf = self.jf
        bit_check = fsum(jf, gadget_outs, axis=-1)
        sum_check = jf.sub(fsum(jf, inp, axis=-1), self._sic(shares_inv))
        jr1 = fmap(lambda x: x[:, 1], joint_rand)
        return jf.add(bit_check, jf.mul(jr1, sum_check))

    def truncate(self, inp):
        return inp


class BFixedPointVec(_BChunked):
    """Device twin of reference.FixedPointVec: bit-check calls followed by
    squared-entry norm calls through the same ParallelSum(Mul) gadget."""

    def encode_batch(self, measurements):
        circ = self.circ
        a = np.asarray(measurements, dtype=np.int64)  # [batch, length] signed
        assert a.ndim == 2 and a.shape[1] == circ.length
        assert ((-circ.offset <= a) & (a < circ.offset)).all()
        u = a.astype(np.uint64) + np.uint64(circ.offset)  # offset binary, mod 2^64
        bits = np.arange(circ.bits, dtype=np.uint64)
        entry_bits = ((u[:, :, None] >> bits[None, None, :]) & np.uint64(1)).reshape(
            a.shape[0], -1
        )
        norms = (a.astype(object) ** 2).sum(axis=1)  # exact ints (b=64 > u64)
        assert all(int(n) < (1 << circ.norm_bits) for n in norms), "L2 norm must be < 1"
        norm_bits = np.array(
            [[(int(n) >> j) & 1 for j in range(circ.norm_bits)] for n in norms],
            dtype=np.uint64,
        )
        return np.concatenate([entry_bits, norm_bits], axis=1)

    def _interleaved_pairs(self, a, b, n_calls):
        """(a_i, b_i) pairs padded/reshaped to [batch, n_calls, 2*chunk]."""
        ch = self.circ.chunk_length
        pairs = fmap(
            lambda x, y: jnp.stack([x, y], axis=-1).reshape(x.shape[0], -1), a, b
        )
        pad = n_calls * ch * 2 - pairs[0].shape[-1]
        if pad:
            pairs = fmap(lambda x: jnp.pad(x, ((0, 0), (0, pad))), pairs)
        return fmap(lambda x: x.reshape(x.shape[0], n_calls, 2 * ch), pairs)

    def _entry_values(self, inp, shares_inv):
        """[batch, length] shares of v_e (offset split per share)."""
        jf = self.jf
        circ = self.circ
        # bits-major layout, same tiling rationale as BSumVec.truncate
        v = fmap(
            lambda x: jnp.swapaxes(
                x[:, : circ.length * circ.bits].reshape(
                    x.shape[0], circ.length, circ.bits
                ),
                1,
                2,
            ),
            inp,
        )
        two_pows = fmap(lambda w: w[:, None], _two_power_consts(jf, circ.bits))
        u = fsum(jf, jf.mul(v, two_pows), axis=1)
        off = fconst(jf, (circ.offset * shares_inv) % jf.MODULUS)
        return jf.sub(u, off)

    def calls_inputs(self, inp, joint_rand, shares_inv):
        jf = self.jf
        circ = self.circ
        r = fmap(lambda x: x[:, 0], joint_rand)
        pw = powers(jf, r, circ.n_bits + 1)
        rp = fmap(lambda x: x[..., 1:], pw)
        a = jf.mul(rp, inp)
        b = jf.sub(inp, self._sic(shares_inv))
        bit_calls = self._interleaved_pairs(a, b, circ.calls_bits)
        y = self._entry_values(inp, shares_inv)
        sq_calls = self._interleaved_pairs(y, y, circ.calls_sq)
        return fmap(lambda p, q: jnp.concatenate([p, q], axis=1), bit_calls, sq_calls)

    def finish(self, inp, joint_rand, gadget_outs, shares_inv):
        jf = self.jf
        circ = self.circ
        bit_check = fsum(
            jf, fmap(lambda x: x[:, : circ.calls_bits], gadget_outs), axis=-1
        )
        norm = fsum(jf, fmap(lambda x: x[:, circ.calls_bits :], gadget_outs), axis=-1)
        nb = fmap(lambda x: x[:, circ.length * circ.bits :], inp)
        claimed = fsum(jf, jf.mul(nb, _two_power_consts(jf, circ.norm_bits)), axis=-1)
        r1 = fmap(lambda x: x[:, 1], joint_rand)
        return jf.add(bit_check, jf.mul(r1, jf.sub(norm, claimed)))

    def truncate(self, inp):
        jf = self.jf
        circ = self.circ
        v = fmap(
            lambda x: x[:, : circ.length * circ.bits].reshape(
                x.shape[0], circ.length, circ.bits
            ),
            inp,
        )
        return fsum(jf, jf.mul(v, _two_power_consts(jf, circ.bits)), axis=-1)


_ADAPTERS = {
    Count: BCount,
    Sum: BSum,
    SumVec: BSumVec,
    # the sparse FLP is SumVec over the COMPACT encoding — the device
    # prepare/verify legs reuse BSumVec verbatim; only aggregation
    # differs (the scatter-merge kernel in aggregator.engine_cache)
    SparseSumVec: BSumVec,
    Histogram: BHistogram,
    FixedPointVec: BFixedPointVec,
}


def _two_power_consts(jf, bits: int):
    """[2^0, ..., 2^{bits-1}] mod p as a device field constant."""
    tp = np.array([pow(2, j, jf.MODULUS) for j in range(bits)], dtype=object)
    return tuple(
        jnp.asarray(((tp >> (64 * i)) & ((1 << 64) - 1)).astype(np.uint64))
        for i in range(jf.LIMBS)
    )


def _pow2_weighted_sum(jf, v, bits: int, axis: int = 1):
    """sum_b 2^b * v[:, b, ...] over a bits-major axis via shift-based
    const-muls (fmul_pow2) — replaces the generic jf.mul by
    _two_power_consts in the truncate paths (~5x fewer VPU ops; exact
    same field elements)."""
    acc = fmap(lambda x: jnp.take(x, 0, axis=axis), v)
    for b in range(1, bits):
        acc = jf.add(
            acc, fmul_pow2(jf, fmap(lambda x: jnp.take(x, b, axis=axis), v), b)
        )
    return acc


def batched_circuit(circ: Circuit) -> BatchedCircuit:
    return _ADAPTERS[type(circ)](circ)


# ---------------------------------------------------------------------------
# FLP prove / query / decide (batched)
# ---------------------------------------------------------------------------


def _wire_polys(bc: BatchedCircuit, seeds, ci):
    """Interpolate wire polynomials: [batch, arity, m] coefficients.

    seeds: [batch, arity] (prove rand or proof-share head); ci: calls
    inputs [batch, calls, arity]. Wire j's values on the NTT domain are
    [seed_j, ci[0][j], ..., ci[calls-1][j], 0...].
    """
    jf = bc.jf
    ci_t = fmap(lambda x: jnp.swapaxes(x, 1, 2), ci)  # [batch, arity, calls]
    evals = fmap(
        lambda s, c: jnp.concatenate([s[:, :, None], c], axis=-1), seeds, ci_t
    )
    if 1 + bc.calls < bc.m:
        pad = bc.m - (1 + bc.calls)
        evals = fmap(lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad))), evals)
    return intt_batched(jf, evals)


def flp_prove_batched(bc: BatchedCircuit, inp, prove_rand, joint_rand):
    """proof [batch, proof_len] matching reference.flp_prove element-wise."""
    jf = bc.jf
    ci = anti_recompute_barrier(bc.calls_inputs(inp, joint_rand, 1))
    wp = _wire_polys(bc, prove_rand, ci)
    wire_evals = ntt_batched(jf, wp, bc.n2)  # [batch, arity, n2]
    gadget_evals = bc.gadget_eval(wire_evals)  # [batch, n2]
    gpoly = intt_batched(jf, gadget_evals)
    gpoly = fmap(lambda x: x[..., : bc.gp_len], gpoly)
    return fmap(lambda s, g: jnp.concatenate([s, g], axis=-1), prove_rand, gpoly)


def _pick_eval_point(jf, cands, m: int):
    """First candidate t (of EVAL_POINT_CANDIDATES) with t^m != 1
    (branch-free draw; bound analysis SECURITY-NOTES.md #4)."""
    tm = fpow_const(jf, cands, m)  # [batch, 4]
    ok = ~is_zero(jf.sub(tm, fconst(jf, 1, tm[0].shape)))
    idx = jnp.argmax(ok, axis=-1)  # first True (0 if none; prob ~2^-128)
    return fmap(lambda x: jnp.take_along_axis(x, idx[:, None], axis=-1)[:, 0], cands)


def _query_proof_side(bc: BatchedCircuit, proof_share, query_rand):
    """Shared proof-share setup of every query variant: split
    seeds/gadget coefficients, pick the eval point t, evaluate the
    gadget polynomial at the call points, and compute t's Lagrange
    weights. Returns (seeds, gcoeffs, t, outs, pw, L0, Lc) — a single
    copy keeps the MM/fold/streamed paths bit-identical by
    construction."""
    jf = bc.jf
    seeds = fmap(lambda x: x[..., : bc.arity], proof_share)
    gcoeffs = fmap(lambda x: x[..., bc.arity : bc.arity + bc.gp_len], proof_share)
    assert query_rand[0].shape[-1] == EVAL_POINT_CANDIDATES
    t = anti_recompute_barrier(_pick_eval_point(jf, query_rand, bc.m))
    # gadget outputs at call points alpha^{k+1}: fold mod x^m - 1, NTT_m
    folds = -(-bc.gp_len // bc.m)
    padded = fmap(lambda x: jnp.pad(x, ((0, 0), (0, folds * bc.m - bc.gp_len))), gcoeffs)
    gfold = fsum(jf, fmap(lambda x: x.reshape(x.shape[0], folds, bc.m), padded), axis=1)
    gevals = ntt_batched(jf, gfold, bc.m)  # values at alpha^0..alpha^{m-1}
    outs = fmap(lambda x: x[..., 1 : bc.calls + 1], gevals)
    pw = anti_recompute_barrier(powers(jf, t, bc.gp_len))
    L = anti_recompute_barrier(lagrange_eval_weights(jf, pw, bc.m))
    L0 = fmap(lambda x: x[:, 0], L)
    Lc = fmap(lambda x: x[:, 1 : 1 + bc.calls], L)
    return seeds, gcoeffs, t, outs, pw, L0, Lc


def _chunked_wire_weights(bc: BatchedCircuit, Lc, r):
    """Per-call weight rows for the MXU wire fold of the
    ParallelSum(Mul, chunk) schedule.

    wire_t[2i]   = r^{i+1} * sum_call (L_call * r^{call*ch}) * X[call, i]
    wire_t[2i+1] =           sum_call  L_call               * X[call, i]
                   - shares_inv * (sum of L over calls whose position i
                                   is a real input element)

    Returns (w [batch, 2, n_calls], rc1 [batch, ch]) where n_calls is
    Lc's call axis (>= bc.calls when the streamed plan pads) and rc1 is
    r^1..r^ch. The decomposition r^{k+1} = r^{call*ch} * r^{i+1}
    replaces the O(input_len) power ladder of the fold path with
    O(calls + ch) muls.
    """
    jf = bc.jf
    ch = bc.circ.chunk_length
    n_calls = Lc[0].shape[-1]
    rc = anti_recompute_barrier(powers(jf, r, ch + 1))  # [batch, ch+1]
    rc1 = fmap(lambda x: x[:, 1:], rc)  # r^1..r^ch
    rch = fmap(lambda x: x[:, ch], rc)  # r^ch
    rpow_ch = anti_recompute_barrier(powers(jf, rch, n_calls))  # r^{call*ch}
    u0 = jf.mul(Lc, rpow_ch)
    w = fmap(lambda a, b: jnp.stack([a, b], axis=1), u0, Lc)
    return w, rc1


def _chunked_b_correction(bc: BatchedCircuit, Lc, shares_inv):
    """shares_inv * SL_i (see _chunked_wire_weights): SL for positions
    covered by every call, minus the last call's weight at padded
    positions (input_len is not a multiple of chunk)."""
    jf = bc.jf
    ch = bc.circ.chunk_length
    SL = fsum(jf, Lc, axis=-1)  # [batch]
    rem = bc.circ.input_len - (bc.calls - 1) * ch
    SLvec = fmap(lambda x: jnp.broadcast_to(x[:, None], (x.shape[0], ch)), SL)
    if rem < ch:
        L_last = fmap(lambda x: x[:, bc.calls - 1], Lc)
        SLpad = jf.sub(SL, L_last)
        mask = jnp.arange(ch) < rem
        SLvec = fwhere(
            mask[None, :],
            SLvec,
            fmap(lambda x: jnp.broadcast_to(x[:, None], (x.shape[0], ch)), SLpad),
        )
    return jf.mul(SLvec, fconst(jf, shares_inv))


def _chunked_X(bc: BatchedCircuit, inp_share):
    """[batch, input_len] share -> zero-padded [batch, calls, ch]."""
    ch = bc.circ.chunk_length
    pad = bc.calls * ch - bc.circ.input_len
    x = inp_share
    if pad:
        x = fmap(lambda v: jnp.pad(v, ((0, 0), (0, pad))), x)
    return fmap(lambda v: v.reshape(v.shape[0], bc.calls, ch), x)


def flp_query_batched(bc: BatchedCircuit, inp_share, proof_share, query_rand, joint_rand, num_shares: int):
    """verifier share [batch, verifier_len] matching reference.flp_query."""
    if _QUERY_MM and type(bc.circ) in (SumVec, SparseSumVec, Histogram):
        return _flp_query_batched_mm(
            bc, inp_share, proof_share, query_rand, joint_rand, num_shares
        )
    jf = bc.jf
    F = bc.circ.FIELD
    shares_inv = F.inv(num_shares)
    # the calls-inputs tensor is reused by the wire interpolation AND the
    # evaluation-at-t path; barrier so XLA shares it instead of
    # recomputing the (r-powers x input) products per consumer
    ci = anti_recompute_barrier(bc.calls_inputs(inp_share, joint_rand, shares_inv))
    seeds, gcoeffs, t, outs, pw, L0, Lc = _query_proof_side(bc, proof_share, query_rand)

    # Wire polys evaluated at t WITHOUT interpolating coefficients:
    # wire j's domain values are [seed_j, ci[*, j], 0...], so
    # wire_j(t) = seed_j*L_0(t) + sum_i ci[i, j]*L_{i+1}(t) with L the
    # Lagrange basis at t (= iNTT of t's powers, ops/ntt.py). Skips the
    # [batch, arity, m] wire-poly iNTT the host oracle does
    # (reference.flp_query:694-699); same field elements, and the peak
    # tensor drops from [batch, arity, m] to the [batch, calls, arity]
    # inputs — the len=100k memory win.
    prod = jf.mul(ci, fmap(lambda x: x[:, :, None], Lc))  # [batch, calls, arity]
    wire_t = jf.add(
        fsum(jf, prod, axis=1),
        jf.mul(seeds, fmap(lambda x: x[:, None], L0)),
    )  # [batch, arity]
    proof_t = poly_eval_powers(jf, gcoeffs, pw)  # [batch]

    v = bc.finish(inp_share, joint_rand, outs, shares_inv)  # [batch]
    return fmap(
        lambda a, b, c: jnp.concatenate([a[:, None], b, c[:, None]], axis=-1),
        v,
        wire_t,
        proof_t,
    )


def _flp_query_batched_mm(
    bc: BatchedCircuit, inp_share, proof_share, query_rand, joint_rand, num_shares: int
):
    """MXU twin of flp_query_batched for the ParallelSum(Mul, chunk)
    circuits (SumVec/Histogram): field-element identical (differential
    tested vs the fold path and the host oracle), but the O(input_len)
    wire fold runs as one limb-decomposed int8 matmul
    (ops/limbmm.fold_contract) instead of u64-emulated VPU multiplies.
    This is the round-5 answer to the instruction-mix headroom
    (unverified link-era figure): the contraction over gadget calls is where
    ~all the query's multiplies live, and the MXU does it at ~40x the
    VPU's integer rate. Replaces the reference's per-report CPU query
    (aggregation_job_driver.rs:329-402) at every chunked length.
    """
    from ..ops.limbmm import fold_contract

    jf = bc.jf
    F = bc.circ.FIELD
    shares_inv = F.inv(num_shares)
    batch = inp_share[0].shape[0]

    seeds, gcoeffs, t, outs, pw, L0, Lc = _query_proof_side(bc, proof_share, query_rand)

    r = fmap(lambda x: x[:, 0], joint_rand)
    w, rc1 = _chunked_wire_weights(bc, Lc, r)
    X = _chunked_X(bc, inp_share)
    Fw = fold_contract(jf, w, X)  # [batch, 2, ch]
    A = jf.mul(fmap(lambda x: x[:, 0], Fw), rc1)
    B = jf.sub(
        fmap(lambda x: x[:, 1], Fw), _chunked_b_correction(bc, Lc, shares_inv)
    )
    wire_t = fmap(lambda a, b: jnp.stack([a, b], axis=-1).reshape(batch, -1), A, B)
    wire_t = jf.add(wire_t, jf.mul(seeds, fmap(lambda x: x[:, None], L0)))
    proof_t = poly_eval_powers(jf, gcoeffs, pw)

    v = bc.finish(inp_share, joint_rand, outs, shares_inv)
    return fmap(
        lambda a, b, c: jnp.concatenate([a[:, None], b, c[:, None]], axis=-1),
        v,
        wire_t,
        proof_t,
    )


# ---------------------------------------------------------------------------
# Streamed FLP query + truncate (large-input circuits)
# ---------------------------------------------------------------------------

# Stream the query once the expanded share would dominate HBM: below
# this the whole-share path is faster (no scan sequentialization).
STREAM_MIN_INPUT_LEN = 1 << 17
# Fewer, larger scan steps since the MM query shrank the per-step
# working set: 8 steps halves the sequential scan overhead that was
# ~40% of helper_init at len=100k (r5 profile) at ~2x the transient
# per-step memory (still O(group)).
_STREAM_TARGET_STEPS = 8
# Hard cap on the per-step tile, in input-share ELEMENTS. The r5 plan
# sized the group as input_len/_STREAM_TARGET_STEPS — memory
# PROPORTIONAL, which is why len=100k (input_len 1.6M) could not reach
# the batch>=256 amortization knee inside the 15.75 GB v5e budget
# (ISSUE r6). With the cap the tile is FIXED at north-star lengths, so
# the scan's working set scales with batch x TILE no matter how long
# the measurement vector grows; extra length only adds scan steps
# (the nested-scan sponge already made long chains linear, r5).
# Floor: the tile must stay a multiple of the lcm(7, bits) x chunk
# alignment quantum (XOF block + truncate-grid alignment, stream_plan),
# so a chunk length coprime with the alignment floors the tile at
# a*ch elements even when that exceeds this clamp.
STREAM_TILE_ELEMS = int(os.environ.get("JANUS_STREAM_TILE", str(1 << 16)))


class StreamPlan:
    """Group geometry for the streamed query: the input is processed in
    `n_steps` scan steps of `gcalls` gadget calls (= `group` input
    elements) each. `group` is aligned to both the XOF block quantum
    (7 Field128 elements per 168-byte counter block) and `bits` (so
    SumVec truncate tiles never straddle a group)."""

    __slots__ = ("gcalls", "n_steps", "group", "bits")

    def __init__(self, gcalls: int, n_steps: int, group: int, bits: int):
        self.gcalls = gcalls
        self.n_steps = n_steps
        self.group = group
        self.bits = bits


def stream_plan(
    bc: BatchedCircuit,
    min_input_len: int | None = None,
    tile_elems: int | None = None,
) -> StreamPlan | None:
    """A StreamPlan for circuits worth streaming, else None.

    SumVec and Histogram only: their query consumes the expanded share
    as per-call folds, so it streams. (FixedPointVec's two-pass entry
    values could stream too but its deployed lengths don't need it;
    Count/Sum inputs are tiny.)

    The group (tile) size is min(input_len/_STREAM_TARGET_STEPS,
    tile_elems), alignment-rounded: short streams keep the measured
    8-step optimum, long streams clamp to the fixed tile so peak memory
    is length-independent (STREAM_TILE_ELEMS rationale above).
    """
    import math

    circ = bc.circ
    if type(circ) not in (SumVec, SparseSumVec, Histogram):
        return None
    if bc.jf.LIMBS != 2:
        return None  # block alignment below assumes 7 F128 elements/block
    if circ.input_len < (STREAM_MIN_INPUT_LEN if min_input_len is None else min_input_len):
        return None
    ch = circ.chunk_length
    bits = getattr(circ, "bits", 1)
    align = math.lcm(7, bits)
    a = align // math.gcd(align, ch)  # smallest gcalls with align | gcalls*ch
    tile = STREAM_TILE_ELEMS if tile_elems is None else tile_elems
    desired_calls = min(bc.calls / _STREAM_TARGET_STEPS, max(1.0, tile / ch))
    gcalls = a * max(1, round(desired_calls / a))
    n_steps = -(-bc.calls // gcalls)
    return StreamPlan(gcalls, n_steps, gcalls * ch, bits)


def describe_engine_geometry(bc: BatchedCircuit) -> dict:
    """Static geometry of a batched circuit for introspection
    (/statusz engine-cache section, bench riders): the tensor shapes
    that drive the HBM feasibility bound and the streamed-query plan,
    in one JSON-shaped dict."""
    circ = bc.circ
    plan = stream_plan(bc)
    return {
        "circuit": type(circ).__name__,
        "input_len": getattr(circ, "input_len", None),
        "output_len": getattr(circ, "output_len", None),
        "verifier_len": getattr(circ, "verifier_len", None),
        "gadget_calls": getattr(bc, "calls", None),
        "field_limbs": bc.jf.LIMBS,
        "stream_plan": (
            {
                "tile_elems": plan.group,
                "gcalls": plan.gcalls,
                "n_steps": plan.n_steps,
            }
            if plan is not None
            else None
        ),
    }


def sliced_meas_source(bc: BatchedCircuit, plan: StreamPlan, meas):
    """meas_source over a device-resident [batch, input_len] share
    (leader side): pad to the group grid once, dynamic-slice per step."""
    total = plan.n_steps * plan.group
    n = bc.circ.input_len
    meas = fpad_axis(meas, total - n) if total > n else meas

    def src(step):
        return ftile(meas, step, plan.group, axis=1)

    return src


def flp_query_streamed(
    bc: BatchedCircuit, plan: StreamPlan, meas_source, proof_share, query_rand, joint_rand, num_shares: int
):
    """Streamed twin of flp_query_batched, fused with truncate.

    meas_source(step) -> input-share elements [batch, group] for
    element range [step*group, (step+1)*group) (values beyond input_len
    are masked here). Returns (verifier, out_share) — field-element
    identical to (flp_query_batched(...), bc.truncate(meas)) (the fold
    order differs but field addition is exact mod p), with peak memory
    O(group) instead of O(input_len): the expanded share never fully
    materializes. This is what lifts the SumVec len=100k single-chip
    batch cap (the limiter was HBM capacity; unverified link-era figure).
    Replaces the reference's per-report query loop
    (aggregation_job_driver.rs:329-402) at north-star lengths.
    """
    jf = bc.jf
    circ = bc.circ
    F = circ.FIELD
    shares_inv = F.inv(num_shares)
    n = circ.input_len
    G = plan.group
    ch = circ.chunk_length
    gcalls = plan.gcalls
    batch = query_rand[0].shape[0]
    is_sumvec = isinstance(circ, SumVec)

    # --- proof-share side (small; shared with flp_query_batched) ---
    seeds, gcoeffs, t, outs, pw, L0, Lc = _query_proof_side(bc, proof_share, query_rand)
    # call weights zero-padded so tail calls beyond `calls` contribute 0
    padc = plan.n_steps * gcalls - bc.calls
    if padc:
        Lc = fpad_axis(Lc, padc)

    # --- streamed input-share folds ---
    r = fmap(lambda x: x[:, 0], joint_rand)
    s_const = fconst(jf, shares_inv)

    from ..fields.jfield import fput_tile, fzeros

    # truncate-output width of one step's tile: the scan accumulates
    # each step's contribution into a carried [batch, n_steps * gp]
    # buffer (fput_tile) instead of scan-stacked ys — the ys path emits
    # an s64-indexed dynamic_update_slice under x64 that the SPMD
    # partitioner rejects on a (dp, sp) mesh (fput_tile rationale).
    gp = G // plan.bits if is_sumvec else G

    if _QUERY_MM:
        # MXU form (see _flp_query_batched_mm): each step's fold is one
        # limb matmul over its gcalls; r-powers and the shares_inv
        # correction are applied once after the scan.
        from ..ops.limbmm import fold_contract

        w_full, rc1 = _chunked_wire_weights(bc, Lc, r)  # Lc is step-padded

        def body(carry, step):
            F0, F1, S, P = carry
            x = meas_source(step)  # [batch, G]
            mask = (step * G + jnp.arange(G)) < n  # [G]
            x = fmap(lambda v: jnp.where(mask[None, :], v, jnp.zeros_like(v)), x)
            Xg = freshape(x, (batch, gcalls, ch))
            wg = ftile(w_full, step, gcalls, axis=2)
            Fg = fold_contract(jf, wg, Xg)  # [batch, 2, ch]
            F0 = jf.add(F0, fmap(lambda v: v[:, 0], Fg))
            F1 = jf.add(F1, fmap(lambda v: v[:, 1], Fg))
            S = jf.add(S, fsum(jf, x, axis=-1))
            if is_sumvec:  # bits-major fold: out[e] = sum_b 2^b x_{e*bits+b}
                v = fmap(
                    lambda w: jnp.swapaxes(
                        w.reshape(batch, G // plan.bits, plan.bits), 1, 2
                    ),
                    x,
                )
                part = _pow2_weighted_sum(jf, v, plan.bits)
            else:  # histogram truncate is the identity
                part = x
            P = fput_tile(P, part, step)
            return (F0, F1, S, P), None

        init = (
            fzeros(jf, (batch, ch)),
            fzeros(jf, (batch, ch)),
            fzeros(jf, (batch,)),
            fzeros(jf, (batch, plan.n_steps * gp)),
        )
        carry, _ = jax.lax.scan(
            body, init, jnp.arange(plan.n_steps, dtype=jnp.int32)
        )
        F0, F1, S, parts = carry
        W0 = jf.mul(F0, rc1)
        W1 = jf.sub(F1, _chunked_b_correction(bc, Lc, shares_inv))
    else:
        rt = anti_recompute_barrier(powers(jf, r, G))  # [batch, G]: r^0..r^{G-1}
        rstep = fpow_const(jf, r, G)  # r^G
        two_pows = _two_power_consts(jf, plan.bits) if is_sumvec else None

        def body(carry, step):
            base, W0, W1, S, P = carry  # base = r^{step*G + 1}
            x = meas_source(step)  # [batch, G]
            mask = (step * G + jnp.arange(G)) < n  # [G]
            x = fmap(lambda v: jnp.where(mask[None, :], v, jnp.zeros_like(v)), x)
            # gadget wire pair (a, b) per element k: (r^{k+1} x_k, x_k - 1/shares)
            a = jf.mul(jf.mul(fmap(lambda v: v[:, None], base), rt), x)
            b = fmap(
                lambda v, z: jnp.where(mask[None, :], v, z),
                jf.sub(x, s_const),
                fzeros(jf, (batch, G)),
            )
            a_r = freshape(a, (batch, gcalls, ch))
            b_r = freshape(b, (batch, gcalls, ch))
            Lg = ftile(Lc, step, gcalls, axis=1)
            Lg3 = fmap(lambda v: v[:, :, None], Lg)
            W0 = jf.add(W0, fsum(jf, jf.mul(a_r, Lg3), axis=1))
            W1 = jf.add(W1, fsum(jf, jf.mul(b_r, Lg3), axis=1))
            S = jf.add(S, fsum(jf, x, axis=-1))
            if is_sumvec:  # bits-major fold: out[e] = sum_b 2^b x_{e*bits+b}
                v = fmap(
                    lambda w: jnp.swapaxes(w.reshape(batch, G // plan.bits, plan.bits), 1, 2), x
                )
                part = fsum(jf, jf.mul(v, fmap(lambda w: w[:, None], two_pows)), axis=1)
            else:  # histogram truncate is the identity
                part = x
            base = jf.mul(base, rstep)
            P = fput_tile(P, part, step)
            return (base, W0, W1, S, P), None

        init = (
            r,
            fzeros(jf, (batch, ch)),
            fzeros(jf, (batch, ch)),
            fzeros(jf, (batch,)),
            fzeros(jf, (batch, plan.n_steps * gp)),
        )
        carry, _ = jax.lax.scan(
            body, init, jnp.arange(plan.n_steps, dtype=jnp.int32)
        )
        _, W0, W1, S, parts = carry

    out_share = fmap(lambda v: v[:, : circ.output_len], parts)

    # wire_t interleaves (a, b) per chunk position: index 2c from W0[c]
    wire_t = fmap(lambda p, q: jnp.stack([p, q], axis=-1).reshape(batch, -1), W0, W1)
    wire_t = jf.add(wire_t, jf.mul(seeds, fmap(lambda x: x[:, None], L0)))
    proof_t = poly_eval_powers(jf, gcoeffs, pw)

    # circuit output v = bc.finish(...) without the full input tensor
    if is_sumvec:
        v = fsum(jf, outs, axis=-1)
    else:
        bit_check = fsum(jf, outs, axis=-1)
        sum_check = jf.sub(S, s_const)
        jr1 = fmap(lambda x: x[:, 1], joint_rand)
        v = jf.add(bit_check, jf.mul(jr1, sum_check))

    verifier = fmap(
        lambda a, b, c: jnp.concatenate([a[:, None], b, c[:, None]], axis=-1),
        v,
        wire_t,
        proof_t,
    )
    return verifier, out_share


def flp_decide_batched(bc: BatchedCircuit, verifier):
    """Boolean accept mask [batch] over combined verifier messages."""
    jf = bc.jf
    v0 = fmap(lambda x: x[:, 0], verifier)
    wires = fmap(lambda x: x[:, 1 : 1 + bc.arity], verifier)
    y = fmap(lambda x: x[:, 1 + bc.arity], verifier)
    circuit_ok = is_zero(v0)
    g = bc.gadget_eval(wires)
    gadget_ok = is_zero(jf.sub(g, y))
    return circuit_ok & gadget_ok
