"""Exact field contractions on the MXU via 7-bit limb decomposition.

The FLP query's hot loop is a contraction over gadget calls:
wire_t[j] = sum_call w[call] * X[call, j] in Field64/Field128 — per
report a [W x calls] @ [calls x chunk] product. The reference computes
the equivalent per report on CPU inside `prio`
(aggregator/src/aggregator/aggregation_job_driver.rs:329-402); round-4
ran it on the VPU as u64-emulated limb multiplies, which the roofline
pinned at ~14% of envelope (unverified link-era figure) — the admitted instruction-mix
headroom. This module moves those multiplies to the MXU, the unit with
~40x the integer throughput, by decomposing field elements into 7-bit
limbs and contracting with int8 x int8 -> int32 `dot_general`s:

  a = sum_l1 A_l1 2^(7 l1),  b = sum_l2 B_l2 2^(7 l2)   (A,B < 2^7)
  sum_call a b = sum_{l1,l2} 2^(7(l1+l2)) sum_call A_l1 B_l2
                              ^^^^^^^^^^^ one i32 matmul per (l1,l2)

Every step is exact: products < 2^14, i32 column sums safe for
calls <= 2^17, the diagonal-group recombination runs in u64 with full
carries, and the final value reduces mod p by the same sparse-moduli
folds as janus_tpu.fields.jfield. The result is the bit-identical
field element the sequential path produces (fuzzed in
tests/test_limbmm.py; the engine differential tests pin the query).

Field64 uses 10 limbs (70 bits), Field128 uses 19 (133 bits); the
(l1, l2) grid rides as extra rows/columns of one batched matmul:
[batch, W*19, calls] @ [batch, calls, 19*C].

`JANUS_LIMBMM_DTYPE=f32` switches the matmul operand dtype for
backends without an int8 MXU path; f32 accumulation is exact while
products * calls < 2^24, so the contraction is segmented at 1024
calls (int8/i32 allows 2^17 before segmenting).
"""

from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..fields.jfield import (
    _f64_reduce_wide,
    _f128_fold,
    _f128_reduce256,
    add_limbs,
)

_NLIMB = {1: 10, 2: 19}  # 7-bit limbs per element, by u64 limb count
_MASK7 = np.uint64(0x7F)

# int8 path: column sums bounded by calls * 127^2 < 2^31 -> 2^17 calls.
# f32 path: exact while bounded by 2^24 -> 1024 calls.
_SEG = {"int8": 1 << 17, "f32": 1 << 10}


def _dtype() -> str:
    d = os.environ.get("JANUS_LIMBMM_DTYPE", "int8")
    assert d in ("int8", "f32"), d
    return d


def decompose7(jf, v):
    """Field value (limb tuple, any shape S) -> u8-in-int8 array
    [*S, nlimbs] of 7-bit limbs, little-endian."""
    nl = _NLIMB[jf.LIMBS]
    dt = jnp.int8 if _dtype() == "int8" else jnp.float32
    pieces = []
    for j in range(nl):
        bit = 7 * j
        w, off = divmod(bit, 64)
        if w >= jf.LIMBS:
            pieces.append(jnp.zeros_like(v[0], dtype=dt))
            continue
        piece = v[w] >> np.uint64(off)
        if off > 57 and w + 1 < jf.LIMBS:
            piece = piece | (v[w + 1] << np.uint64(64 - off))
        pieces.append((piece & _MASK7).astype(dt))
    return jnp.stack(pieces, axis=-1)


def _reduce_limbs(jf, limbs):
    """u64 limb list (value < 2^292 for F128 / 2^166 for F64) -> field."""
    if jf.LIMBS == 1:
        l0, l1, l2 = limbs
        m = _f64_reduce_wide(l1, l2)
        return (_f64_reduce_wide(l0, m),)
    # F128: 5 limbs < 2^292. One fold (H = limbs[2:5] < 2^164) lands
    # under 7H*2^66 + L < 2^234 < 2^256, then the 256-bit reduction.
    r = _f128_fold(list(limbs), 3)[:4]
    return _f128_reduce256(*r)


def fold_contract(jf, w, X):
    """Exact field contraction: out[b, i, c] = sum_p w[b, i, p] * X[b, p, c].

    w: field value [batch, W, calls] (weight rows; W small).
    X: field value [batch, calls, C].
    Returns a reduced field value [batch, W, C], bit-identical to
    fsum(jf, jf.mul(w[..., None], X[:, None]), axis=2).
    """
    nl = _NLIMB[jf.LIMBS]
    dt = _dtype()
    b, W, calls = w[0].shape
    _, _, C = X[0].shape
    dl = decompose7(jf, w)  # [b, W, calls, nl]
    dr = decompose7(jf, X)  # [b, calls, C, nl]
    dl = jnp.transpose(dl, (0, 1, 3, 2)).reshape(b, W * nl, calls)
    dr = jnp.transpose(dr, (0, 1, 3, 2)).reshape(b, calls, nl * C)

    seg = _SEG[dt]
    acc = None  # u64 [b, W, nl, nl, C]
    for s0 in range(0, calls, seg):
        s1 = min(calls, s0 + seg)
        out = lax.dot_general(
            dl[:, :, s0:s1],
            dr[:, s0:s1, :],
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32 if dt == "int8" else jnp.float32,
        )
        part = (
            out.astype(jnp.uint64)
            if dt == "int8"
            # f32 accumulation is exact under the segment bound; values
            # are non-negative integers < 2^24
            else out.astype(jnp.int32).astype(jnp.uint64)
        ).reshape(b, W, nl, nl, C)
        acc = part if acc is None else acc + part  # < calls*127^2*segs: no wrap

    # diagonal groups: value = sum_s 2^(7s) colsum[s], s = l1 + l2 —
    # one scatter-add (an nl^2 python loop traced ~361 adds; trace time
    # is first-job latency, binary_utils warmup docstring)
    n_s = 2 * nl - 1
    s_idx = jnp.asarray(
        np.add.outer(np.arange(nl), np.arange(nl)).reshape(-1), dtype=jnp.int32
    )
    grouped = (
        jnp.zeros((b, W, n_s, C), dtype=jnp.uint64)
        .at[:, :, s_idx, :]
        .add(acc.reshape(b, W, nl * nl, C))
    )
    colsum = [grouped[:, :, s, :] for s in range(n_s)]

    # assemble u64 limbs with carries: each colsum (< 2^40: <= nl
    # segment-partials of < 2^31/2^24 each) contributes at bit offset
    # 7s, straddling at most two limbs
    n_limbs = 5 if jf.LIMBS == 2 else 3
    limbs = [jnp.zeros_like(colsum[0]) for _ in range(n_limbs)]
    for s in range(n_s):
        wd, off = divmod(7 * s, 64)
        lo = colsum[s] << np.uint64(off)
        add = [jnp.zeros_like(lo) for _ in range(n_limbs)]
        add[wd] = lo
        if off > 24 and wd + 1 < n_limbs:  # 2^40 << off crosses the limb
            add[wd + 1] = colsum[s] >> np.uint64(64 - off)
        limbs, _ = add_limbs(limbs, add)
        # total value < 2^292 (F128) / 2^166 (F64): top limb never wraps
    return _reduce_limbs(jf, limbs)
