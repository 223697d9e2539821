"""Keccak-f[1600] as a Pallas TPU kernel: 24 rounds in VMEM, u32-native.

The XLA path (janus_tpu.vdaf.keccak_jax.keccak_f1600) runs the rounds
as a lax.scan: every round reads and writes the whole 25-lane state
from HBM — ~24 x 2 x state-size of traffic — and each u64 bit-op
lowers to a u32 pair anyway. This kernel keeps the state of a row tile
resident in VMEM for all 24 rounds and works on the u32 halves
directly: one HBM read + one write per element total. Profiled on the
SumVec two-party step the scan-based permutations were ~50% of device
time.

Layout: callers hold the state as 25 u64 arrays of identical shape S
(one array per Keccak lane, batch shape S). Here that becomes one
[50, R, 128] u32 array — row 2k = lane k's low half, row 2k+1 = high
half, with prod(S) flattened and zero-padded to R*128 columns — tiled
over a grid on R. Zero columns permute to garbage and are sliced away.

Enabled on single-device TPU processes by default (JANUS_PALLAS=0
disables; =1 forces interpret mode on CPU for differential tests;
multi-device TPU is always off — see _mode — and JANUS_PALLAS=1 does
NOT override that); everything else falls back to the scan path. The flag and backend are
read once at the first XOF call and cached (jitted graphs embed the
dispatch decision, so mid-process toggles could not take effect
anyway); tests that need a different mode patch `_mode` directly.
"""

from __future__ import annotations

import logging
import os
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

# Round constants / rotation offsets shared with the scan path — one
# authoritative copy (keccak_jax imports this module only lazily inside
# keccak_f1600, so there is no import cycle).
from ..vdaf.keccak_jax import _RC as _RC_U64, _ROT

log = logging.getLogger(__name__)

_RC = [int(x) for x in _RC_U64]

_TILE_ROWS = 8  # u32 min tile is (8, 128)


def _xor2(a, b):
    return (a[0] ^ b[0], a[1] ^ b[1])


def _rot64(a, r: int):
    """Rotate-left a u64 held as (lo32, hi32) by r."""
    lo, hi = a
    r %= 64
    if r == 0:
        return a
    if r >= 32:
        lo, hi = hi, lo
        r -= 32
        if r == 0:
            return (lo, hi)
    s = np.uint32(r)
    t = np.uint32(32 - r)
    return ((lo << s) | (hi >> t), (hi << s) | (lo >> t))


def permute_pairs(a, rounds: int = 24):
    """Keccak-f[1600] rounds on a 25-list of (lo32, hi32) pairs.

    Shared between the plain-permutation kernel below and the fused
    expansion kernel (janus_tpu.ops.expand_pallas). `rounds < 24` is a
    test-only reduction (same round function, first `rounds` round
    constants) so the full kernel framing runs in interpret mode in
    default CI without the 24-round unrolled-body compile cost; both
    sides of every differential use the same count."""
    for rnd in range(rounds):
        # theta
        c = [
            _xor2(_xor2(_xor2(a[i], a[i + 5]), _xor2(a[i + 10], a[i + 15])), a[i + 20])
            for i in range(5)
        ]
        d = [_xor2(c[(i - 1) % 5], _rot64(c[(i + 1) % 5], 1)) for i in range(5)]
        a = [_xor2(a[i], d[i % 5]) for i in range(25)]
        # rho + pi
        b = [None] * 25
        for xx in range(5):
            for yy in range(5):
                b[yy + 5 * ((2 * xx + 3 * yy) % 5)] = _rot64(a[xx + 5 * yy], _ROT[xx][yy])
        # chi
        a = [
            _xor2(
                b[xx + 5 * yy],
                (
                    (~b[(xx + 1) % 5 + 5 * yy][0]) & b[(xx + 2) % 5 + 5 * yy][0],
                    (~b[(xx + 1) % 5 + 5 * yy][1]) & b[(xx + 2) % 5 + 5 * yy][1],
                ),
            )
            for yy in range(5)
            for xx in range(5)
        ]
        # iota
        rc = _RC[rnd]
        a[0] = (
            a[0][0] ^ np.uint32(rc & 0xFFFFFFFF),
            a[0][1] ^ np.uint32(rc >> 32),
        )
    return a


def _kernel_for(rounds: int):
    def _kernel(x_ref, o_ref):
        x = x_ref[:]  # [50, TR, 128] u32
        a = permute_pairs([(x[2 * i], x[2 * i + 1]) for i in range(25)], rounds)
        o_ref[:] = jnp.stack([h for pair in a for h in pair], axis=0)

    return _kernel


@lru_cache(maxsize=1)
def _mode() -> str:
    """'tpu' (real kernel), 'interpret' (forced on non-TPU), or 'off'.

    Multi-device TPU processes run with kernels off: engine_cache binds
    jitted steps to a dp mesh there, and pallas_call has no SPMD
    partitioning rule — sharding it needs shard_map plumbing around
    every call site (future work). That decision is logged once and
    shown in the /statusz engine_cache section (status())."""
    flag = os.environ.get("JANUS_PALLAS")
    if flag == "0":
        return "off"
    if jax.default_backend() == "tpu":
        n = len(jax.devices())
        if n == 1:
            return "tpu"
        log.warning(
            "Pallas kernels OFF: this process sees %d TPU devices and the mesh "
            "engines cannot partition a pallas_call; the scan path runs instead",
            n,
        )
        return "off"
    return "interpret" if flag == "1" else "off"


def status() -> dict:
    """Kernel mode and why, for /statusz and chip_smoke.py."""
    mode = _mode()
    if os.environ.get("JANUS_PALLAS") == "0":
        reason = "JANUS_PALLAS=0"
    elif jax.default_backend() != "tpu":
        reason = f"{jax.default_backend()} backend"
        if mode == "interpret":
            reason += " (interpret)"
    elif mode == "off":
        reason = f"{len(jax.devices())} TPU devices: no SPMD rule for pallas_call"
    else:
        reason = "one TPU device"
    return {"mode": mode, "reason": reason}


# Below this many state columns the relayout into [50, R, 128] u32
# costs more than the kernel saves (measured: Count at batch 8192 ran
# ~10% slower through the kernel; SumVec's 1.2M-column states gain 41%).
MIN_COLUMNS = 32768


def enabled(n_columns: int | None = None) -> bool:
    if _mode() == "off":
        return False
    return n_columns is None or n_columns >= MIN_COLUMNS


def on_tpu(kernel, fallback):
    """kernel() in a program lowered for a TPU, fallback() in one
    lowered for another platform: a TPU process's clients shard on the
    host CPU, where a Mosaic kernel cannot run. Interpret mode runs the
    kernel everywhere."""
    if _mode() == "interpret":
        return kernel()
    return jax.lax.platform_dependent(tpu=kernel, default=fallback)


@lru_cache(maxsize=None)
def _call(rows: int, interpret: bool, rounds: int = 24):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = (rows // _TILE_ROWS,)
    # all three block indices derived from the grid index so the index
    # map is monomorphic i32 (literal 0s lower to i64 constants, which
    # this Mosaic build refuses to mix in func.return)
    spec = pl.BlockSpec(
        (50, _TILE_ROWS, 128), lambda i: (i * 0, i, i * 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        _kernel_for(rounds),
        out_shape=jax.ShapeDtypeStruct((50, rows, 128), jnp.uint32),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        interpret=interpret,
        name="keccak_f1600",
    )


def keccak_f1600_pallas(state, rounds: int = 24):
    """Permute 25 u64 arrays of identical shape; returns the same tuple
    structure. Caller guarantees enabled() is True."""
    shape = state[0].shape
    n = int(np.prod(shape)) if shape else 1
    cols = -(-n // (_TILE_ROWS * 128)) * (_TILE_ROWS * 128)
    rows = cols // 128
    flat = [jnp.ravel(x) for x in state]
    halves = []
    for x in flat:
        halves.append(x.astype(jnp.uint32))          # low 32 bits
        halves.append((x >> np.uint64(32)).astype(jnp.uint32))
    stacked = jnp.stack(halves, axis=0)  # [50, n]
    if cols != n:
        stacked = jnp.pad(stacked, ((0, 0), (0, cols - n)))
    out = _call(rows, _mode() != "tpu", rounds)(stacked.reshape(50, rows, 128))
    out = out.reshape(50, cols)[:, :n]
    res = []
    for i in range(25):
        lo = out[2 * i].astype(jnp.uint64)
        hi = out[2 * i + 1].astype(jnp.uint64)
        res.append((lo | (hi << np.uint64(32))).reshape(shape))
    return tuple(res)


# ---------------------------------------------------------------------------
# Single-block variant: rate lanes in, first `out_lanes` lanes out.
# ---------------------------------------------------------------------------


def _kernel_single(out_lanes: int, rounds: int):
    def _kernel(x_ref, o_ref):
        x = x_ref[:]  # [42, TR, 128] u32 — 21 rate lanes as lo/hi pairs
        zeros = jnp.zeros_like(x[0])
        a = [(x[2 * i], x[2 * i + 1]) for i in range(21)] + [(zeros, zeros)] * 4
        a = permute_pairs(a, rounds)
        o_ref[:] = jnp.stack(
            [h for i in range(out_lanes) for h in a[i]], axis=0
        )

    return _kernel


@lru_cache(maxsize=None)
def _call_single(rows: int, interpret: bool, out_lanes: int, rounds: int = 24):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = (rows // _TILE_ROWS,)
    in_spec = pl.BlockSpec(
        (42, _TILE_ROWS, 128), lambda i: (i * 0, i, i * 0), memory_space=pltpu.VMEM
    )
    out_spec = pl.BlockSpec(
        (2 * out_lanes, _TILE_ROWS, 128),
        lambda i: (i * 0, i, i * 0),
        memory_space=pltpu.VMEM,
    )
    return pl.pallas_call(
        _kernel_single(out_lanes, rounds),
        out_shape=jax.ShapeDtypeStruct((2 * out_lanes, rows, 128), jnp.uint32),
        grid=grid,
        in_specs=[in_spec],
        out_specs=out_spec,
        interpret=interpret,
        name="keccak_single_block",
    )


def keccak_single_block_pallas(lane_cols, out_lanes: int, rounds: int = 24):
    """Permute single-block messages given as 21 rate-lane u64 arrays of
    identical shape; return the first `out_lanes` output lanes (same
    tuple-of-arrays structure). vs keccak_f1600_pallas this moves 42
    u32 rows in and 2*out_lanes out instead of 50/50 — the tree-digest
    levels (out_lanes=2) were paying ~3x their necessary HBM traffic
    through the general kernel, the dominant cost of the leader
    joint-rand binder at SumVec len=100k (profiled r5)."""
    shape = lane_cols[0].shape
    n = int(np.prod(shape)) if shape else 1
    cols_pad = -(-n // (_TILE_ROWS * 128)) * (_TILE_ROWS * 128)
    rows = cols_pad // 128
    halves = []
    for x in lane_cols:
        flat = jnp.ravel(x)
        halves.append(flat.astype(jnp.uint32))
        halves.append((flat >> np.uint64(32)).astype(jnp.uint32))
    stacked = jnp.stack(halves, axis=0)  # [42, n]
    if cols_pad != n:
        stacked = jnp.pad(stacked, ((0, 0), (0, cols_pad - n)))
    out = _call_single(rows, _mode() != "tpu", out_lanes, rounds)(
        stacked.reshape(42, rows, 128)
    )
    out = out.reshape(2 * out_lanes, cols_pad)[:, :n]
    res = []
    for i in range(out_lanes):
        lo = out[2 * i].astype(jnp.uint64)
        hi = out[2 * i + 1].astype(jnp.uint64)
        res.append((lo | (hi << np.uint64(32))).reshape(shape))
    return tuple(res)
