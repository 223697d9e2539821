"""Fused XOF-expansion Pallas kernel: Keccak + mod-p sampling in VMEM.

The unfused device path (janus_tpu.vdaf.keccak_jax.expand_field_vec)
materializes the counter-mode SHAKE128 stream in HBM — 168 bytes per
block in, 168+ out of the permutation kernel, re-read by the sampler —
~24 raw stream bytes per Field128 element that exist only to be reduced
mod p and thrown away. At the north-star SumVec len=100k that stream is
38.4 MB per report and is what capped the single-chip batch at 8
(the limiter is HBM *capacity*; unverified link-era figure).

This kernel fuses the whole expansion: each grid cell covers 8 reports
x 128 counter blocks; the single-block counter-mode Keccak state is
built in VMEM from a per-report prefix row (dst||seed||binder', <=160
bytes, broadcast along lanes) plus a lane-index counter, permuted for
all 24 rounds (janus_tpu.ops.keccak_pallas.permute_pairs), and each
168-byte rate block is reduced to 7 Field128 elements in-kernel. Only
the 112 bytes/block of element words ever reach HBM; the raw stream
never exists.

The mod-p reduction mirrors janus_tpu.fields.jfield._f128_reduce256 on
32-bit words (TPU VPU native): p = 2^128 - 7*2^66 + 1, so folding
H*2^128 ≡ H*(7*2^66 - 1) is shift/add/borrow only — no multiplies.
The sampled value here is 192 bits (three u64 stream lanes per element,
oversample-and-reduce, xof.py), so two folds + a top-bit correction +
one conditional subtract reach canonical form:

  X < 2^192:  fold H=X>>128 (< 2^64)  -> X1 < 2^133
              fold H=X1>>128 (< 2^6)  -> X2 < 2^128 + 2^75  (carry c4)
  c4 set:     X2 - p = (X2 - 2^128) + 7*2^66 - 1  (< 2^76)
  finally:    one conditional subtract of p.

Field64 (21 lanes/block, 2 lanes/element) straddles block boundaries
and its expansions are tiny (Count/Sum); it stays on the unfused path.

Gating and interpret-mode plumbing follow keccak_pallas (JANUS_PALLAS
env, cached at first use).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..fields.field import Field128
from . import keccak_pallas
from .keccak_pallas import permute_pairs


def _mode() -> str:
    # via the module so tests patching keccak_pallas._mode take effect
    return keccak_pallas._mode()

U32 = jnp.uint32
U64 = jnp.uint64

_TILE_REPORTS = 8
_TILE_BLOCKS = 128

_P = Field128.MODULUS
_P_WORDS = tuple(np.uint32((_P >> (32 * k)) & 0xFFFFFFFF) for k in range(4))
# 7*2^66 - 1 = 27*2^64 + (2^64 - 1), the p-complement added when the
# top (2^128) bit is folded away.
_E_WORDS = (
    np.uint32(0xFFFFFFFF),
    np.uint32(0xFFFFFFFF),
    np.uint32(0x0000001B),
    np.uint32(0),
)

# Minimum counter blocks per report to dispatch here: the tile quantum
# is 128 blocks, so short expansions (query/joint randomness) would pay
# mostly-padding tiles; they stay on the unfused path.
MIN_BLOCKS = 64


def enabled(jf, out_blocks: int) -> bool:
    if jf.LIMBS != 2 or _mode() == "off":
        return False
    if out_blocks < MIN_BLOCKS:
        return False
    # bound padded-tile waste: below one full tile the pad can dominate
    padded = -(-out_blocks // _TILE_BLOCKS) * _TILE_BLOCKS
    return padded <= 2 * out_blocks


def _addc(x, y, c):
    """x + y + c on u32 words; c in {0,1}. Returns (sum, carry)."""
    s = x + y
    c1 = (s < x).astype(U32)
    s2 = s + c
    c2 = (s2 < s).astype(U32)
    return s2, c1 | c2


def _subb(x, y, b):
    """x - y - b on u32 words; b in {0,1}. Returns (diff, borrow)."""
    d = x - y
    b1 = (x < y).astype(U32)
    d2 = d - b
    b2 = (d < b).astype(U32)
    return d2, b1 | b2


def _reduce_f128_words(w, zero):
    """Reduce a 192-bit little-endian 6-word value mod p -> 4 words."""
    h_lo, h_hi = w[4], w[5]
    # h7 = 7*H = (H << 3) - H, 3 words
    s0 = h_lo << np.uint32(3)
    s1 = (h_hi << np.uint32(3)) | (h_lo >> np.uint32(29))
    s2 = h_hi >> np.uint32(29)
    t0, b = _subb(s0, h_lo, zero)
    t1, b = _subb(s1, h_hi, b)
    t2 = s2 - b  # exact: 7H >= 0 fits 3 words
    # g = h7 << 2  (7H*2^66 = g*2^64), 3 words (7H < 2^67)
    g0 = t0 << np.uint32(2)
    g1 = (t1 << np.uint32(2)) | (t0 >> np.uint32(30))
    g2 = (t2 << np.uint32(2)) | (t1 >> np.uint32(30))
    # X1 = L + g*2^64 - H, 5 words
    x0, x1 = w[0], w[1]
    x2, c = _addc(w[2], g0, zero)
    x3, c = _addc(w[3], g1, c)
    x4 = g2 + c
    x0, b = _subb(x0, h_lo, zero)
    x1, b = _subb(x1, h_hi, b)
    x2, b = _subb(x2, zero, b)
    x3, b = _subb(x3, zero, b)
    x4 = x4 - b  # X1 >= 0 guarantees no wrap (see module docstring)
    # fold2: H2 = x4 < 2^6; D = 7*H2*2^66 - H2 as 3 words
    h2 = x4
    c2w = ((h2 << np.uint32(3)) - h2) << np.uint32(2)  # 28*H2, fits a word
    nz = (h2 > zero).astype(U32)
    d0 = zero - h2
    d1 = zero - nz
    d2 = c2w - nz  # c2w >= 28 when nz, no borrow
    y0, c = _addc(x0, d0, zero)
    y1, c = _addc(x1, d1, c)
    y2, c = _addc(x2, d2, c)
    y3, c4 = _addc(x3, zero, c)
    # top-bit correction: if c4, value = 2^128 + Y; Y + (7*2^66 - 1) < 2^76
    z0, c = _addc(y0, jnp.full_like(zero, _E_WORDS[0]), zero)
    z1, c = _addc(y1, jnp.full_like(zero, _E_WORDS[1]), c)
    z2, c = _addc(y2, jnp.full_like(zero, _E_WORDS[2]), c)
    z3 = y3 + c
    top = c4 != zero
    y0 = jnp.where(top, z0, y0)
    y1 = jnp.where(top, z1, y1)
    y2 = jnp.where(top, z2, y2)
    y3 = jnp.where(top, z3, y3)
    # final conditional subtract of p
    s0, b = _subb(y0, jnp.full_like(zero, _P_WORDS[0]), zero)
    s1, b = _subb(y1, jnp.full_like(zero, _P_WORDS[1]), b)
    s2_, b = _subb(y2, jnp.full_like(zero, _P_WORDS[2]), b)
    s3, b = _subb(y3, jnp.full_like(zero, _P_WORDS[3]), b)
    ge = b == zero
    return (
        jnp.where(ge, s0, y0),
        jnp.where(ge, s1, y1),
        jnp.where(ge, s2_, y2),
        jnp.where(ge, s3, y3),
    )


def _expand_kernel(p_lanes: int, tile_blocks: int = _TILE_BLOCKS, rounds: int = 24):
    """Kernel factory: prefix occupies lanes [0, p_lanes), counter at
    lane p_lanes, SHAKE padding at p_lanes+1 and lane 20 (the
    ctr_stream_lanes single-block framing, keccak_jax.py). off_ref is a
    [1] SMEM scalar: the stream-block counter offset (0 for whole-share
    expansion; step*blocks_per_step for the streamed query path)."""

    def kern(off_ref, pref_ref, o_ref):
        shape = (_TILE_REPORTS, tile_blocks)
        zero = jnp.zeros(shape, U32)
        lane_i = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        ctr_lo = (lane_i + pl_program_id(1) * tile_blocks + off_ref[0]).astype(U32)
        a = []
        for lane in range(25):
            if lane < p_lanes:
                lo = jnp.broadcast_to(pref_ref[:, 2 * lane : 2 * lane + 1], shape)
                hi = jnp.broadcast_to(pref_ref[:, 2 * lane + 1 : 2 * lane + 2], shape)
                a.append((lo, hi))
            elif lane == p_lanes:
                a.append((ctr_lo, zero))
            else:
                lo = zero
                hi = zero
                if lane == p_lanes + 1:
                    lo = jnp.full(shape, np.uint32(0x1F))
                if lane == 20:  # RATE_LANES - 1: 0x80 in the last byte
                    hi = jnp.full(shape, np.uint32(0x80000000))
                a.append((lo, hi))
        a = permute_pairs(a, rounds)
        for t in range(7):
            w = (
                a[3 * t][0],
                a[3 * t][1],
                a[3 * t + 1][0],
                a[3 * t + 1][1],
                a[3 * t + 2][0],
                a[3 * t + 2][1],
            )
            words = _reduce_f128_words(w, zero)
            for k in range(4):
                o_ref[:, 0, 4 * t + k, :] = words[k]

    return kern


def pl_program_id(axis: int):
    from jax.experimental import pallas as pl

    return pl.program_id(axis)


@lru_cache(maxsize=None)
def _call(p_lanes: int, b8: int, nb: int, tile_blocks: int, interpret: bool, rounds: int = 24):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = (b8 // _TILE_REPORTS, nb)
    # index maps derived from grid indices only (monomorphic i32 — see
    # keccak_pallas._call for the Mosaic constraint this dodges)
    # explicit monomorphic index map (literal 0s lower to i64 constants,
    # which this Mosaic build refuses to mix in func.return — see
    # keccak_pallas._call)
    off_spec = pl.BlockSpec((1,), lambda b, j: (j * 0,), memory_space=pltpu.SMEM)
    in_spec = pl.BlockSpec(
        (_TILE_REPORTS, 128), lambda b, j: (b, j * 0), memory_space=pltpu.VMEM
    )
    # block tail dims must be divisible by (8, 128) or equal the array
    # dims — hence (..., nb, 28, tile) with a full (28, tile) tail block
    out_spec = pl.BlockSpec(
        (_TILE_REPORTS, 1, 28, tile_blocks),
        lambda b, j: (b, j, j * 0, j * 0),
        memory_space=pltpu.VMEM,
    )
    return pl.pallas_call(
        _expand_kernel(p_lanes, tile_blocks, rounds),
        out_shape=jax.ShapeDtypeStruct((b8, nb, 28, tile_blocks), jnp.uint32),
        grid=grid,
        in_specs=[off_spec, in_spec],
        out_specs=out_spec,
        interpret=interpret,
        name="expand_f128",
    )


def expand_f128(prefix_lanes, out_blocks: int, length: int, block_offset=0, rounds: int = 24):
    """Expand per-report counter-mode prefixes straight to Field128
    limb arrays, fused on device.

    prefix_lanes: [batch, p] u64 (dst||seed||binder', lane-aligned);
    returns a (lo, hi) limb tuple of shape [batch, length] — the same
    value keccak_jax.sample_field_vec produces from the unfused stream
    (differential-tested in tests/test_expand_pallas.py). block_offset
    (python int or traced scalar) starts the stream counter at that
    block.
    """
    prefix_lanes = jnp.asarray(prefix_lanes, U64)
    batch, p = prefix_lanes.shape
    assert p + 1 <= 20, "prefix + counter must fit one rate block"
    assert 7 * out_blocks >= length
    b8 = -(-batch // _TILE_REPORTS) * _TILE_REPORTS
    nb = -(-out_blocks // _TILE_BLOCKS)
    lo32 = prefix_lanes.astype(U32)
    hi32 = (prefix_lanes >> np.uint64(32)).astype(U32)
    inter = jnp.stack([lo32, hi32], axis=-1).reshape(batch, 2 * p)
    inter = jnp.pad(inter, ((0, b8 - batch), (0, 128 - 2 * p)))
    off = jnp.asarray(block_offset, jnp.int32).reshape(1)
    out = _call(p, b8, nb, _TILE_BLOCKS, _mode() != "tpu", rounds)(off, inter)
    # out[b, nbi, t*4+k, lane] = word k of element t of block
    # nbi*128+lane; element index is block*7 + t
    o = out.reshape(b8, nb, 7, 4, _TILE_BLOCKS)
    o = jnp.transpose(o, (0, 1, 4, 2, 3)).reshape(b8, nb * _TILE_BLOCKS * 7, 4)
    lo = o[:batch, :length, 0].astype(U64) | (o[:batch, :length, 1].astype(U64) << np.uint64(32))
    hi = o[:batch, :length, 2].astype(U64) | (o[:batch, :length, 3].astype(U64) << np.uint64(32))
    return (lo, hi)
