"""Batched Keccak-f[1600] / SHAKE128 in JAX for on-device XOF expansion.

The VDAF hot path needs, per report, hundreds of KB of XOF output to
expand helper measurement/proof shares from 16-byte seeds (the
reference does this on CPU inside `prio`'s Xof, one report at a time,
invoked from aggregator/src/aggregator.rs:1775-1797). Keccak is pure
64-bit bitwise logic, which vectorizes perfectly: the state is 25 u64
lanes per message, and every round is elementwise XOR/rotate/and-not.
On TPU the u64 ops lower to u32 pairs on the VPU.

The XOF stream framing is **counter mode** (janus_tpu.vdaf.xof, which
is the host oracle — see its docstring for the design): every 168-byte
output block is an independent single-block SHAKE128 message
(dst||seed||binder'||le64(i)), so one `keccak_f1600` call over
[batch, n_blocks]-shaped lanes produces the *entire* stream of every
report in a batch — sequential depth 24 rounds regardless of stream
length. Long binders are bound via an arity-7 Merkle digest whose
levels are each one batched permutation (`tree_digest_lanes`). All
messages are u64-lane-aligned by construction; host and device produce
byte-identical streams — tested in tests/test_keccak.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

U64 = jnp.uint64

RATE_BYTES = 168  # SHAKE128
RATE_LANES = RATE_BYTES // 8  # 21

_RC = np.array(
    [
        0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
        0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
        0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
        0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
        0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
        0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
        0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
        0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
    ],
    dtype=np.uint64,
)

# rotation offsets indexed [x][y]
_ROT = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]


def _rotl(x, r: int):
    if r == 0:
        return x
    return (x << np.uint64(r)) | (x >> np.uint64(64 - r))


def _keccak_round(a, rc):
    """One Keccak round; a: tuple of 25 u64 arrays, rc: scalar constant."""
    # theta
    c = [a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20] for x in range(5)]
    d = [c[(x - 1) % 5] ^ _rotl(c[(x + 1) % 5], 1) for x in range(5)]
    a = [a[i] ^ d[i % 5] for i in range(25)]
    # rho + pi: B[y, 2x+3y] = rot(A[x, y])
    b = [None] * 25
    for x in range(5):
        for y in range(5):
            b[y + 5 * ((2 * x + 3 * y) % 5)] = _rotl(a[x + 5 * y], _ROT[x][y])
    # chi
    a = [
        b[x + 5 * y] ^ ((~b[(x + 1) % 5 + 5 * y]) & b[(x + 2) % 5 + 5 * y])
        for y in range(5)
        for x in range(5)
    ]
    # iota
    a[0] = a[0] ^ rc
    return tuple(a)


# Round count for every permutation in this module (the kernel and the
# scan path). 24 always in production; tests monkeypatch it to run the
# full kernel plumbing at a reduced count in interpret mode
# (tests/test_keccak_pallas.py) — patching here covers every dispatch
# site, including the single-block kernel below.
KECCAK_ROUNDS = 24


def keccak_f1600(state, rounds: int | None = None):
    """One permutation. state: 25 u64 arrays (lane (x,y) at index x + 5*y).

    On TPU this dispatches to the Pallas kernel (janus_tpu.ops.
    keccak_pallas): all 24 rounds stay in VMEM on native u32 halves,
    one HBM read+write per element. Elsewhere the rounds run under
    lax.scan so the round body is traced and compiled once — an
    unrolled permutation inflates the XLA graph by ~2k ops per call
    site, which multiplies out to minutes of compile time across the
    expansion pipeline. `rounds < 24` exists for the reduced-round CI
    differentials only (tests/test_keccak_pallas.py).
    """
    from ..ops import keccak_pallas

    if rounds is None:
        rounds = KECCAK_ROUNDS
    state = tuple(jnp.asarray(x, dtype=U64) for x in state)
    n = int(np.prod(state[0].shape)) if state[0].shape else 1
    if keccak_pallas.enabled(n):
        return keccak_pallas.on_tpu(
            lambda: keccak_pallas.keccak_f1600_pallas(state, rounds),
            lambda: _keccak_scan(state, rounds),
        )
    return _keccak_scan(state, rounds)


def _keccak_scan(state, rounds: int):
    def body(a, rc):
        return _keccak_round(a, rc), None

    out, _ = jax.lax.scan(body, state, jnp.asarray(_RC[:rounds]))
    return out


def _absorb_block(state, block_lanes):
    """XOR one rate block ([batch, 21]) into the state and permute."""
    state = list(state)
    for lane in range(RATE_LANES):
        state[lane] = state[lane] ^ block_lanes[:, lane]
    return keccak_f1600(state)


_UNROLL_BLOCKS = 4  # small messages stay unrolled; long ones lax.scan


def shake128_squeeze_lanes(msg_lanes, out_blocks: int):
    """SHAKE128 over pre-padded messages; returns raw squeezed lanes.

    msg_lanes: [batch, n_blocks, 21] u64 — the message already padded to
    whole rate blocks (use pad_message_lanes). Returns
    [batch, out_blocks, 21] u64 of output stream lanes.

    Absorb/squeeze are lax.scan loops over blocks (the permutation is
    inherently sequential per report), so the traced graph stays O(1)
    in stream length — a SumVec-100k share expansion is ~1.5k blocks
    and must not unroll.
    """
    batch = msg_lanes.shape[0]
    n_blocks = msg_lanes.shape[1]
    state = tuple(jnp.zeros((batch,), dtype=U64) for _ in range(25))
    if n_blocks <= _UNROLL_BLOCKS:
        for blk in range(n_blocks):
            state = _absorb_block(state, msg_lanes[:, blk])
    else:
        state = _absorb_scan(state, msg_lanes)
    if out_blocks <= _UNROLL_BLOCKS:
        outs = []
        for blk in range(out_blocks):
            if blk > 0:
                state = keccak_f1600(state)
            outs.append(jnp.stack(state[:RATE_LANES], axis=-1))
        return jnp.stack(outs, axis=1)
    return _squeeze_scan(state, out_blocks)


# Sponge chains past this many blocks run as NESTED scans (an outer
# scan of _SCAN_CHUNK-length inner scans): a single flat lax.scan goes
# wildly superlinear past ~32k trip counts on the TPU runtime
# (1.9 s at 32k blocks vs 209 s at 152k — unverified link-era figure),
# which round 4 mistook for an inherent cost and capped the draft device gate on. The chunking is value-neutral: the same
# sequential permutation chain, same output blocks.
_SCAN_CHUNK = 4096


def _absorb_scan(state, msg_lanes):
    n_blocks = msg_lanes.shape[1]

    def absorb(st, blk):
        return _absorb_block(st, blk), None

    n_full = (n_blocks // _SCAN_CHUNK) if n_blocks > 2 * _SCAN_CHUNK else 0
    if n_full:
        head = jnp.moveaxis(
            msg_lanes[:, : n_full * _SCAN_CHUNK].reshape(
                msg_lanes.shape[0], n_full, _SCAN_CHUNK, RATE_LANES
            ),
            0,
            2,
        )  # [n_full, chunk, batch, 21]

        def outer(st, chunk_blocks):
            st2, _ = jax.lax.scan(absorb, st, chunk_blocks)
            return st2, None

        state, _ = jax.lax.scan(outer, state, head)
        msg_lanes = msg_lanes[:, n_full * _SCAN_CHUNK :]
    if msg_lanes.shape[1]:
        xs = jnp.moveaxis(msg_lanes, 1, 0)
        state, _ = jax.lax.scan(absorb, state, xs)
    return state


def _squeeze_scan(state, out_blocks: int):
    def squeeze(st, _):
        ys = jnp.stack(st[:RATE_LANES], axis=-1)
        return keccak_f1600(st), ys

    if out_blocks <= 2 * _SCAN_CHUNK:
        _, ys = jax.lax.scan(squeeze, state, None, length=out_blocks)
        return jnp.moveaxis(ys, 0, 1)
    # full chunks via the nested scan + a flat remainder scan (mirrors
    # _absorb_scan; rounding the squeeze up would waste up to a whole
    # chunk of permutations over the batch)
    n_full = out_blocks // _SCAN_CHUNK
    rem = out_blocks - n_full * _SCAN_CHUNK

    def outer(st, _):
        st2, ys = jax.lax.scan(squeeze, st, None, length=_SCAN_CHUNK)
        return st2, ys

    state, yss = jax.lax.scan(outer, state, None, length=n_full)
    ys = yss.reshape(n_full * _SCAN_CHUNK, yss.shape[2], RATE_LANES)
    if rem:
        state, tail = jax.lax.scan(squeeze, state, None, length=rem)
        ys = jnp.concatenate([ys, tail], axis=0)
    return jnp.moveaxis(ys, 0, 1)


def pad_message_lanes(parts, msg_len_bytes: int, batch: int):
    """Assemble a padded SHAKE128 message as [batch, n_blocks, 21] lanes.

    parts: list of (lane_offset, lanes) where lanes is a [batch, k] u64
    array (dynamic content) or a host bytes object of length 8*k (static
    content), in ascending offset order (gaps are zero-filled).
    msg_len_bytes must be a multiple of 8 (guaranteed by the
    lane-aligned stream framing in janus_tpu.vdaf.xof). Assembly is
    whole-array concatenation so the traced graph stays O(#parts), not
    O(message length).
    """
    assert msg_len_bytes % 8 == 0
    msg_lanes_n = msg_len_bytes // 8
    n_blocks = msg_lanes_n // RATE_LANES + 1  # always room for padding
    total = n_blocks * RATE_LANES
    lanes = _assemble_segments(parts, msg_lanes_n, batch)
    # SHAKE padding: 0x1F at msg end, 0x80 at the last byte of the last
    # block (may share a lane).
    tail = np.zeros(total - msg_lanes_n, dtype=np.uint64)
    tail[0] ^= np.uint64(0x1F)
    tail[-1] ^= np.uint64(0x80) << np.uint64(56)
    lanes = jnp.concatenate(
        [lanes, jnp.broadcast_to(jnp.asarray(tail), (batch, tail.size))], axis=1
    )
    return lanes.reshape(batch, n_blocks, RATE_LANES)


def bytes_to_lanes(data: bytes) -> np.ndarray:
    assert len(data) % 8 == 0
    return np.frombuffer(data, dtype="<u8").astype(np.uint64)


def _assemble_segments(parts, total_lanes: int, batch: int):
    """Concatenate (lane_offset, lanes|bytes) parts into [batch, total_lanes].

    Gaps are zero-filled; host bytes are broadcast across the batch.
    """
    segs = []
    pos = 0
    for off, content in sorted(parts, key=lambda p: p[0]):
        assert off >= pos, "overlapping message parts"
        if off > pos:
            segs.append(jnp.zeros((batch, off - pos), dtype=U64))
            pos = off
        if isinstance(content, (bytes, bytearray)):
            assert len(content) % 8 == 0
            lanes = np.frombuffer(bytes(content), dtype="<u8").astype(np.uint64)
            segs.append(jnp.broadcast_to(jnp.asarray(lanes), (batch, lanes.size)))
            pos += lanes.size
        else:
            segs.append(content.astype(U64))
            pos += content.shape[-1]
    assert pos <= total_lanes
    if pos < total_lanes:
        segs.append(jnp.zeros((batch, total_lanes - pos), dtype=U64))
    return jnp.concatenate(segs, axis=1)


# ---------------------------------------------------------------------------
# Counter-mode stream + tree digest (the janus_tpu.vdaf.xof framing)
# ---------------------------------------------------------------------------

PAD_START = np.uint64(0x1F)
PAD_END = np.uint64(0x80) << np.uint64(56)


def _single_block_keccak(lane_cols, out_lanes: int = 25):
    """Permute single-block messages given as a list of 21 lane arrays.

    lane_cols: 21 arrays of identical shape [...] (the rate lanes of the
    already-padded message). Returns (at least) the first `out_lanes`
    output lanes; callers that only need a digest or a rate block pass
    a smaller out_lanes so the Pallas path can skip moving the rest
    (keccak_single_block_pallas: 42 u32 rows in, 2*out_lanes out,
    instead of the general kernel's 50/50).
    """
    from ..ops import keccak_pallas

    shape = lane_cols[0].shape
    n = int(np.prod(shape)) if shape else 1
    zeros = jnp.zeros_like(lane_cols[0])
    state = tuple(lane_cols) + (zeros,) * 4
    if out_lanes < 25 and keccak_pallas.enabled(n):
        return keccak_pallas.on_tpu(
            lambda: keccak_pallas.keccak_single_block_pallas(
                lane_cols, out_lanes, rounds=KECCAK_ROUNDS
            ),
            lambda: _keccak_scan(state, KECCAK_ROUNDS)[:out_lanes],
        )
    return keccak_f1600(state)


def ctr_stream_lanes(prefix_parts, prefix_len_bytes: int, batch: int, out_blocks: int, ctr_offset=0):
    """Counter-mode SHAKE128 stream: [batch, out_blocks, 21] u64 lanes.

    prefix_parts: (lane_offset, content) segments of the prefix
    dst16 || seed || binder' (binder' already inline-size). Every output
    block is the independent single-block message prefix || le64(i), so
    the whole stream is ONE batched permutation — this is the load-bearing
    TPU restructuring over sequential sponge squeezing.

    ctr_offset (python int or traced scalar) starts the counter at block
    `ctr_offset` instead of 0 — the streamed-expansion path (engine.py
    flp_query_streamed) generates the stream a slice at a time.
    """
    assert prefix_len_bytes % 8 == 0
    p = prefix_len_bytes // 8
    assert p + 1 <= RATE_LANES - 1, "prefix + counter must fit one rate block"
    prefix = _assemble_segments(prefix_parts, p, batch)  # [batch, p]
    shape = (batch, out_blocks)
    cols = []
    for lane in range(RATE_LANES):
        if lane < p:
            cols.append(jnp.broadcast_to(prefix[:, lane : lane + 1], shape))
        elif lane == p:
            ctr = jnp.arange(out_blocks, dtype=U64)[None, :] + jnp.asarray(ctr_offset, U64)
            cols.append(jnp.broadcast_to(ctr, shape))
        else:
            v = np.uint64(0)
            if lane == p + 1:
                v |= PAD_START
            if lane == RATE_LANES - 1:
                v |= PAD_END
            cols.append(jnp.broadcast_to(jnp.asarray(v), shape))
    state = _single_block_keccak(cols, out_lanes=RATE_LANES)
    return jnp.stack(state[:RATE_LANES], axis=-1)  # [batch, out_blocks, 21]


TREE_MAGIC_LANE = np.frombuffer(b"JanusTr1", dtype="<u8")[0]
TREE_CHUNK_LANES = 14  # 112 bytes
TREE_ARITY = 7
TREE_DIGEST_LANES = 2


def _tree_level_planar(planes, level: int, total_lanes_bytes: int):
    """Hash one tree level from plane-major input: planes
    [batch, 14, n] -> digests [batch, n, 2]. Node k's payload lane j is
    planes[:, j, k] — a contiguous row slice."""
    batch, _, n = planes.shape
    shape = (batch, n)
    idx = jnp.broadcast_to(jnp.arange(n, dtype=U64)[None, :], shape)
    consts = {
        0: np.uint64(TREE_MAGIC_LANE),
        1: np.uint64(level),
        3: np.uint64(total_lanes_bytes),
        18: PAD_START,
        20: PAD_END,
    }
    cols = []
    for lane in range(RATE_LANES):
        if lane == 2:
            cols.append(idx)
        elif 4 <= lane < 4 + TREE_CHUNK_LANES:
            cols.append(planes[:, lane - 4, :])
        else:
            cols.append(
                jnp.broadcast_to(jnp.asarray(consts.get(lane, np.uint64(0))), shape)
            )
    state = _single_block_keccak(cols, out_lanes=TREE_DIGEST_LANES)
    return jnp.stack(state[:TREE_DIGEST_LANES], axis=-1)


def _tree_level(chunks, level: int, total_lanes_bytes: int):
    """Hash one tree level: chunks [batch, n, 14] -> digests [batch, n, 2]."""
    batch, n, _ = chunks.shape
    shape = (batch, n)
    idx = jnp.broadcast_to(jnp.arange(n, dtype=U64)[None, :], shape)
    consts = {
        0: np.uint64(TREE_MAGIC_LANE),
        1: np.uint64(level),
        3: np.uint64(total_lanes_bytes),
        18: PAD_START,  # message = 4 + 14 lanes; 0x1f right after
        20: PAD_END,
    }
    cols = []
    for lane in range(RATE_LANES):
        if lane == 2:
            cols.append(idx)
        elif 4 <= lane < 4 + TREE_CHUNK_LANES:
            cols.append(chunks[:, :, lane - 4])
        else:
            cols.append(
                jnp.broadcast_to(jnp.asarray(consts.get(lane, np.uint64(0))), shape)
            )
    state = _single_block_keccak(cols, out_lanes=TREE_DIGEST_LANES)
    return jnp.stack(state[:TREE_DIGEST_LANES], axis=-1)  # [batch, n, 2]


def tree_digest_lanes(data_parts, data_len_bytes: int, batch: int):
    """Arity-7 Merkle digest of lane-aligned data: [batch, 2] u64.

    Byte-identical to janus_tpu.vdaf.xof.tree_digest. Each level is one
    batched permutation over all of that level's nodes. Level 0 uses
    the PLANAR leaf mapping (lane j of leaf k = data lane j*n+k, see
    tree_digest): each leaf lane column is one contiguous slice of the
    binder instead of a stride-14 gather over all of it.
    """
    assert data_len_bytes % 8 == 0
    lanes_n = data_len_bytes // 8
    data = _assemble_segments(data_parts, lanes_n, batch)  # [batch, L]
    n = max(1, -(-lanes_n // TREE_CHUNK_LANES))
    pad = n * TREE_CHUNK_LANES - lanes_n
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    planes = data.reshape(batch, TREE_CHUNK_LANES, n)
    digs = _tree_level_planar(planes, 0, data_len_bytes)  # [batch, n, 2]
    level = 0
    while n > 1:
        level += 1
        groups = -(-n // TREE_ARITY)
        gpad = groups * TREE_ARITY - n
        if gpad:
            digs = jnp.pad(digs, ((0, 0), (0, gpad), (0, 0)))
        chunks = digs.reshape(batch, groups, TREE_CHUNK_LANES)
        digs = _tree_level(chunks, level, data_len_bytes)
        n = groups
    return digs[:, 0, :]  # [batch, 2]


# ---------------------------------------------------------------------------
# Field-element sampling (oversample-and-reduce; janus_tpu.vdaf.xof)
# ---------------------------------------------------------------------------


def sample_count_blocks(jf, length: int) -> int:
    """Number of SHAKE output blocks needed to sample `length` elements."""
    lanes_needed = length * (jf.LIMBS + 1)
    return (lanes_needed + RATE_LANES - 1) // RATE_LANES


def sample_field_vec(jf, stream_lanes, length: int):
    """Sample `length` field elements by reducing (LIMBS+1)-lane
    little-endian chunks mod p (bias <= 2^-64 per element; see
    janus_tpu.vdaf.xof). Pure elementwise limb math — rejection
    sampling's data-dependent compaction lowered to row-wise gathers
    and sort-based scatters that were 78% of the two-party SumVec step
    on chip. stream_lanes: [batch, out_blocks, 21] u64; returns a field
    value of shape [batch, length].
    """
    from ..fields.jfield import _f64_reduce_wide, _f128_reduce256

    batch = stream_lanes.shape[0]
    g = jf.LIMBS + 1
    flat = stream_lanes.reshape(batch, -1)
    assert flat.shape[1] >= length * g
    lanes = tuple(flat[:, i : length * g : g] for i in range(g))
    if jf.LIMBS == 1:
        return (_f64_reduce_wide(lanes[0], lanes[1]),)
    zero = jnp.zeros_like(lanes[0])
    return _f128_reduce256(lanes[0], lanes[1], lanes[2], zero)


def expand_field_vec(jf, prefix_parts, prefix_len_bytes: int, batch: int, length: int, block_offset=0):
    """XOF-expand per-report prefixes straight to field vectors on device.

    prefix_parts lay out dst16 || seed || binder' (counter-mode framing,
    janus_tpu.vdaf.xof); the binder must already be inline-size.

    Long Field128 expansions dispatch to the fused Pallas kernel
    (janus_tpu.ops.expand_pallas): permutation + mod-p sampling in
    VMEM, so the raw stream (24 bytes/element) never reaches HBM.

    block_offset (python int or traced scalar) starts the counter at
    that stream block; the caller is responsible for block-aligning the
    element range (Field128: 7 elements per block).
    """
    from ..ops import expand_pallas, keccak_pallas

    assert prefix_len_bytes % 8 == 0  # lane-aligned framing (xof.py)
    blocks = sample_count_blocks(jf, length)

    def unfused():
        out = ctr_stream_lanes(
            prefix_parts, prefix_len_bytes, batch, blocks, ctr_offset=block_offset
        )
        return sample_field_vec(jf, out, length)

    if expand_pallas.enabled(jf, blocks):

        def fused():
            prefix = _assemble_segments(prefix_parts, prefix_len_bytes // 8, batch)
            return expand_pallas.expand_f128(prefix, blocks, length, block_offset=block_offset)

        return keccak_pallas.on_tpu(fused, unfused)
    return unfused()
