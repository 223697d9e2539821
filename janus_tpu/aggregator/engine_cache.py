"""Jitted device-step cache with HBM-aware batch-size bucketing.

One compiled executable serves many request sizes: batches are padded
up to the next power-of-two bucket (padding lanes carry mask=False and
are sliced off), so each (task VDAF, step kind) compiles O(log max
batch) times total. This is the TPU answer to the reference's
per-report loop — XLA sees static shapes, reports ride the batch axis.

Bucketing is no longer blind (ISSUE r6): at construction each
EngineCache asks the HBM feasibility model (vdaf.feasibility) for the
largest bucket the device budget supports given the circuit geometry
and the streamed-query tile, and batches beyond that cap are chunked
into serial cap-sized dispatches instead of padded into one doomed
one. When the model is still optimistic and the device raises
RESOURCE_EXHAUSTED anyway, the engine halves its cap and retries; at
the bucket floor it falls back to the scalar HostEngineCache for the
rest of the process, so a serving aggregation job degrades to host
speed instead of dying.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from collections import OrderedDict, deque

import jax
import jax.numpy as jnp
import numpy as np

from .. import failpoints
from ..core.deadline import current_deadline
from . import aot_cache, shape_manifest
from ..vdaf.engine import STREAM_MIN_INPUT_LEN, stream_plan
from ..vdaf.feasibility import device_memory_budget, feasible_bucket
from ..vdaf.reference import SparseSumVec
from ..vdaf.registry import VdafInstance, prio3_batched
from . import device_watchdog
from .device_watchdog import DeviceHangError  # noqa: F401 - re-export: the
# job drivers catch it at the step boundary (step_back, not job failure)

log = logging.getLogger(__name__)

MIN_BUCKET = 32


def bucket_size(n: int, cap: int | None = None) -> int:
    """Power-of-two jit bucket for n rows, floored at MIN_BUCKET.

    `cap` (the engine's HBM feasibility bound) clamps the result; a
    capped bucket may be smaller than n, in which case the caller is
    responsible for chunking the batch into cap-sized dispatches
    (EngineCache does)."""
    b = MIN_BUCKET
    while b < n:
        b *= 2
    if cap is not None and cap < b:
        b = cap
    return b


# Substrings identifying a device memory exhaustion across the ways it
# surfaces (XlaRuntimeError RESOURCE_EXHAUSTED, allocator messages).
_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "Out of memory",
    "OOM",
    "Allocation failure",
)


def is_oom_error(e: BaseException) -> bool:
    s = str(e)
    return any(m in s for m in _OOM_MARKERS)


def _annotate_dispatch_bucket(e: BaseException, b: int, fixed: bool = False) -> None:
    """Record the bucket of the DISPATCH that raised. OOM recovery must
    halve from the failed dispatch size — a coalesced round dispatches
    many submitters' rows at once, and halving from one submitter's own
    (much smaller) n would collapse the cap far below what actually
    overflowed. `fixed` marks dispatches whose bucket cannot follow a
    halved cap (aggregates over an already-resident device buffer), so
    the handler knows retrying cannot make progress. Best-effort:
    extension exception types without a __dict__ simply keep the
    caller-n fallback."""
    try:
        if not hasattr(e, "_janus_dispatch_bucket"):
            e._janus_dispatch_bucket = b
            e._janus_fixed_bucket = fixed
    except Exception:
        pass


def _cut_rows(a, s: int, e: int):
    """Row-slice an arg that may be None, bytes, a field limb tuple, or
    a plain array (the per-call arg vocabulary of pad_args)."""
    if a is None or isinstance(a, (bytes, int)):
        return a
    if isinstance(a, tuple):
        return tuple(x[s:e] for x in a)
    return a[s:e]


def _padded_base(arr, b: int):
    """The array of b rows that `arr` is the leading rows of, where that
    array's other rows are all zero: then it is exactly what padding
    `arr` to b rows makes, and no copy is needed. The driver stages its
    columns so (`_stage_pending`); anything else gets None."""
    base = getattr(arr, "base", None)
    if (
        not isinstance(base, np.ndarray)
        or base.ndim != arr.ndim
        or base.shape[0] != b
        or base.shape[1:] != arr.shape[1:]
        or base.dtype != arr.dtype
        or not base.flags.c_contiguous
        or not arr.flags.c_contiguous
        or arr.ctypes.data != base.ctypes.data
        or base[arr.shape[0] :].any()
    ):
        return None
    return base


def _pad(arr, b: int):
    if arr is None:
        return None
    pad = b - arr.shape[0]
    if pad == 0:
        return arr
    base = _padded_base(arr, b)
    if base is not None:
        return base
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(np.asarray(arr), widths)


def _tree_nbytes(tree) -> int:
    """Host bytes of an arg pytree (arrays / field-limb tuples / None /
    scalars) — the h2d/d2h accounting unit of janus_engine_hd_bytes_total."""
    if tree is None or isinstance(tree, (bytes, int, float, bool)):
        return 0
    if isinstance(tree, (tuple, list)):
        return sum(_tree_nbytes(x) for x in tree)
    nb = getattr(tree, "nbytes", None)
    return int(nb) if nb is not None else 0


def count_h2d(tree_or_bytes) -> None:
    """Account host->device bytes (staged uploads, masks, bucket ids)."""
    from .. import metrics

    n = tree_or_bytes if isinstance(tree_or_bytes, int) else _tree_nbytes(tree_or_bytes)
    if n:
        metrics.engine_hd_bytes_total.add(n, direction="h2d")


def count_d2h(tree_or_bytes) -> None:
    """Account device->host bytes (fetches of masks, seeds, aggregates)."""
    from .. import metrics

    n = tree_or_bytes if isinstance(tree_or_bytes, int) else _tree_nbytes(tree_or_bytes)
    if n:
        metrics.engine_hd_bytes_total.add(n, direction="d2h")


def put_args(args, block: bool = False, shardings=None):
    """Explicitly dispatch every staged host array to the device, all
    puts in flight at once (async), before invoking the jit — one slow
    serialized arg upload must not gate the whole call.

    block=True waits for the transfers to land before returning.

    shardings: optional pytree (matching args) of NamedShardings so
    multi-device placement happens in the transfer itself instead of a
    resharding copy at dispatch."""
    count_h2d(args)
    if shardings is not None:
        out = jax.device_put(args, shardings)
    else:
        out = jax.device_put(args)  # maps over the arg pytree, puts async
    if block:
        jax.block_until_ready(out)
    return out


def pad_args(b: int, *args):
    out = []
    for a in args:
        if a is None or isinstance(a, (bytes, int)):
            out.append(a)
        elif isinstance(a, tuple):  # field value limbs
            out.append(tuple(_pad(x, b) for x in a))
        else:
            out.append(_pad(a, b))
    return tuple(out)


class DeviceRows:
    """Out-share field value living ON DEVICE, padded to its bucket.

    The serving path used to fetch out shares to numpy after init and
    re-upload them for the masked aggregate — ~2x the out-share bytes
    across the host<->device link per job for nothing. Callers that
    truly need host rows (multi-round park paths) go through
    `to_numpy()`; `EngineCache.aggregate` consumes the device value
    directly.

    `offset` supports coalesced dispatches: several jobs' rows share
    one device buffer, each job holding a [offset, offset+n) view."""

    __slots__ = ("value", "n", "offset")

    def __init__(self, value, n: int, offset: int = 0):
        self.value = value  # tuple of [bucket, len] device limb arrays
        self.n = n  # true batch size (rows beyond n are padding)
        self.offset = offset

    def to_numpy(self):
        rows = tuple(
            np.asarray(x)[self.offset : self.offset + self.n] for x in self.value
        )
        count_d2h(rows)
        return rows


class DeviceRowsChunks:
    """Out shares of a pipelined (chunked) leader init: an ordered list
    of DeviceRows covering consecutive row ranges. Quacks like
    DeviceRows for the two consumers (to_numpy; EngineCache.aggregate
    special-cases it)."""

    __slots__ = ("chunks",)

    def __init__(self, chunks: list[DeviceRows]):
        self.chunks = chunks

    @property
    def n(self) -> int:
        return sum(c.n for c in self.chunks)

    def to_numpy(self):
        parts = [c.to_numpy() for c in self.chunks]
        return tuple(np.concatenate([p[i] for p in parts]) for i in range(len(parts[0])))


class PrestagedInit:
    """Async-uploaded leader-init columns (double-buffered staging,
    ISSUE 12): pad_args + device_put issued while the device lane runs
    the PREVIOUS job's dispatch, consumed by leader_init when the
    direct path applies at the same bucket. Holds only the device
    pytree — discard() drops the references so a fallback (coalesced
    multi-job round, bucket cap moved under OOM recovery, host
    fallback) frees the transfer's buffers immediately."""

    __slots__ = ("b", "_staged", "meshed")

    def __init__(self, b: int, staged, meshed: bool):
        self.b = b
        self._staged = staged
        self.meshed = meshed

    def usable(self, b: int, meshed: bool) -> bool:
        return self._staged is not None and self.b == b and self.meshed == meshed

    def take(self):
        staged, self._staged = self._staged, None
        return staged

    def discard(self) -> None:
        self._staged = None


class ResidentMergeError(RuntimeError):
    """resident_merge died partway through its entry loop. `merged`
    holds the keys whose delta DID land in a resident slot before the
    failure — those contributions are safe on device and flush with the
    slot; the caller must directly flush only the REMAINING entries'
    delta rows (re-flushing a merged one double-counts it)."""

    def __init__(self, merged: frozenset, cause: BaseException):
        super().__init__(
            f"resident merge failed after {len(merged)} bucket(s): {cause!r}"
        )
        self.merged = merged


class ResidentSlot:
    """One per-(task, batch bucket) aggregate buffer living in device
    memory across job steps: `value` is a [output_len] field limb tuple
    of device arrays. Host-side metadata rides along so a flush can
    write through the existing batch-aggregation path (the interval is
    the union of every merged contribution's; counts/checksums are
    already durable — the per-job write tx records them at commit time,
    only the share bytes live here)."""

    __slots__ = ("key", "value", "interval", "rows", "nbytes", "last_used")

    def __init__(self, key: tuple, value, interval, rows: int, nbytes: int):
        self.key = key  # (task_id bytes, agg_param bytes, batch_identifier bytes)
        self.value = value
        self.interval = interval
        self.rows = rows
        self.nbytes = nbytes
        self.last_used = time.monotonic()


class PendingDeltas:
    """Per-bucket masked sums of ONE job step, still on device
    ([k, output_len] field limb tuple): computed by aggregate_pending
    on the device lane, merged into resident slots only AFTER the job's
    write transaction committed (resident_merge). A failed commit just
    drops the object — no rollback, no double-merge on the re-step."""

    __slots__ = ("value", "k", "row_nbytes")

    def __init__(self, value, k: int, row_nbytes: int):
        self.value = value
        self.k = k
        self.row_nbytes = row_nbytes

    def row(self, j: int):
        """Row j as a device field value (lazy jnp slice — no fetch)."""
        return tuple(x[j] for x in self.value)


class SparsePendingDeltas:
    """Sparse-job pending state (ISSUE 17). Unlike PendingDeltas the
    per-bucket reduction CANNOT run at dispatch time: two reports of
    the same batch bucket carry different block indices, so a
    compact-width pre-sum would add values living at unrelated logical
    coordinates. Instead the job's raw out shares (device rows) ride to
    merge time together with each report's flat scatter indices, and
    resident_merge scatter-adds report blocks straight into the dense
    logical slot — HBM holds ONE [logical_len] accumulator per slot
    while per-report device work stays O(nonzero lanes). Same commit
    discipline as PendingDeltas: dropped uncommitted, merged after.

    flat_idx: [n, compact_len] host int32 scatter targets, sentinel =
    logical_len for padding lanes (the scatter drops them);
    bucket_idx: [n] host int32 bucket per report, -1 = rejected.
    row_nbytes is the DENSE logical row size (what a slot occupies)."""

    __slots__ = ("out_shares", "flat_idx", "bucket_idx", "k", "row_nbytes", "logical_len")

    def __init__(self, out_shares, flat_idx, bucket_idx, k: int, row_nbytes: int, logical_len: int):
        self.out_shares = out_shares
        self.flat_idx = flat_idx
        self.bucket_idx = bucket_idx
        self.k = k
        self.row_nbytes = row_nbytes
        self.logical_len = logical_len


# process-wide resident accounting (the HBM the resident layer holds
# across every engine; the eviction cap reads the byte total). The
# per-kind buffer counts live here too: several engines share a vdaf
# kind (one per task verify key), so a per-engine gauge set would have
# them overwrite each other's value instead of summing.
_resident_bytes_lock = threading.Lock()
_resident_bytes_total = 0
_resident_buffer_counts: dict[str, int] = {}


def _resident_bytes_add(delta: int, kind: str, nbuf: int) -> int:
    """Account one slot insert/remove: `delta` device bytes and `nbuf`
    (+1/-1) buffers of vdaf `kind`. Publishes both gauges."""
    global _resident_bytes_total
    from .. import metrics

    with _resident_bytes_lock:
        _resident_bytes_total += delta
        total = _resident_bytes_total
        n = _resident_buffer_counts.get(kind, 0) + nbuf
        _resident_buffer_counts[kind] = n
    metrics.engine_resident_bytes.set(float(total))
    metrics.engine_resident_buffers.set(float(n), vdaf=kind)
    return total


def resident_bytes_total() -> int:
    with _resident_bytes_lock:
        return _resident_bytes_total


class _Coalescer:
    """Round-based dispatch coalescing across concurrent callers.

    The driver steps jobs concurrently but each job used to dispatch
    its own device call: a 10k-report Count job got 86,813 r/s from a
    chip that does 287,619 at batch 32768 (unverified link-era figure)
    — the dispatch floor cannot amortize. Here concurrent calls to the same engine step merge into one padded
    device call: an arrival with no dispatch in flight goes out
    immediately (zero added latency when unloaded); arrivals during an
    in-flight dispatch queue and ride the next round together. The
    reference's analog is rayon parallelism inside one job
    (aggregation_job_driver.rs:329) — it has no cross-job batching at
    all.

    Lease/abandon semantics are untouched: coalescing sits strictly
    below the job layer (one device call serving several jobs' rows;
    each job still writes and releases its own lease).
    """

    __slots__ = ("_run", "_max_rows", "_lock", "_cv", "_queue", "_active", "rounds")

    def __init__(self, run, max_rows: int):
        import collections

        self._run = run  # ([args...], [n...]) -> [per-call results]
        self._max_rows = max_rows
        self._lock = threading.Lock()
        # signaled when the dispatcher role frees up with work queued
        self._cv = threading.Condition(self._lock)
        self._queue: list[list] = []  # entries: [args, n, Event, result, error]
        self._active = False
        # calls per dispatched round, recent window only (stats/tests;
        # unbounded growth would be a slow RSS leak on long-lived
        # aggregators)
        self.rounds = collections.deque(maxlen=1024)

    def submit(self, args, n: int):
        ent = [args, n, threading.Event(), None, None]
        with self._lock:
            self._queue.append(ent)
            dispatcher = not self._active
            if dispatcher:
                self._active = True
        if dispatcher:
            self._dispatch_until_done(ent)
        else:
            while not ent[2].is_set():
                # the previous dispatcher may exit with entries still
                # queued (its own round finished first): a waiter is
                # notified via the condition and adopts the role (the
                # short timeout is only a lost-wakeup backstop)
                with self._lock:
                    adopt = not self._active and not ent[2].is_set() and bool(self._queue)
                    if adopt:
                        self._active = True
                    elif not ent[2].is_set():
                        self._cv.wait(0.05)
                        continue
                if adopt:
                    self._dispatch_until_done(ent)
                    break
        if ent[4] is not None:
            raise ent[4]
        return ent[3]

    def _dispatch_until_done(self, own):
        """Dispatch rounds until our own entry completes AND the queue
        is drained or another thread adopts the role."""
        try:
            while True:
                with self._lock:
                    batch: list[list] = []
                    rows = 0
                    while self._queue and (
                        not batch or rows + self._queue[0][1] <= self._max_rows
                    ):
                        e = self._queue.pop(0)
                        batch.append(e)
                        rows += e[1]
                    if not batch:
                        return
                self.rounds.append(len(batch))
                try:
                    results = self._run([e[0] for e in batch], [e[1] for e in batch])
                    for e, r in zip(batch, results):
                        e[3] = r
                except BaseException as ex:  # noqa: BLE001 - even
                    # KeyboardInterrupt/SystemExit must release the
                    # co-batched waiters (their entries were already
                    # popped; nobody else will ever set their events)
                    for e in batch:
                        e[4] = ex
                    if not isinstance(ex, Exception):
                        for e in batch:
                            e[2].set()
                        with self._lock:
                            self._cv.notify_all()
                        raise
                for e in batch:
                    e[2].set()
                # wake cv-parked waiters so completed entries return
                # immediately instead of on the 50 ms backstop
                with self._lock:
                    self._cv.notify_all()
                if own[2].is_set():
                    # our caller has work to do with its result; hand
                    # the role to a waiter (notified in finally)
                    return
        finally:
            with self._lock:
                self._active = False
                if self._queue:
                    self._cv.notify()


def _concat_args(args_list):
    """Concatenate per-call arg tuples along the batch axis. None args
    must be None in every call (same engine => same schedule)."""
    out = []
    for parts in zip(*args_list):
        if parts[0] is None:
            assert all(p is None for p in parts)
            out.append(None)
        elif isinstance(parts[0], tuple):  # field limbs
            out.append(
                tuple(
                    np.concatenate([np.asarray(p[k]) for p in parts])
                    for k in range(len(parts[0]))
                )
            )
        else:
            assert all(p is not None for p in parts)
            out.append(np.concatenate([np.asarray(p) for p in parts]))
    return tuple(out)


def _split_rows(value, offsets):
    """Slice a host array / field tuple / None back into per-call rows."""
    if value is None:
        return [None] * (len(offsets) - 1)
    if isinstance(value, tuple):
        return [
            tuple(x[s:e] for x in value) for s, e in zip(offsets, offsets[1:])
        ]
    return [value[s:e] for s, e in zip(offsets, offsets[1:])]


# ---------------------------------------------------------------------------
# Cross-TASK dispatch coalescing (ISSUE 12). The PR 7 coalescer merged
# concurrent small jobs of ONE engine (one task's vdaf+verify_key) into
# shared dispatches; here engines of the SAME VdafInstance — identical
# circuit geometry, identical compiled steps, differing only in the
# 16-byte verify key — share one round-based coalescer per (inst,
# side), and a mixed round dispatches ONE device call whose verify key
# is a per-LANE input (the XOF already consumes per-lane seed segments,
# so the kernel change is just the key's segment becoming an array).
# The cross-job mask-leak invariant is unchanged by construction: each
# job still holds an [offset, offset+n) view of the shared buffer and
# aggregates under its own mask (re-pinned cross-task in
# tests/test_engine_coalesce.py).
# ---------------------------------------------------------------------------

# default ON: single-engine rounds take byte-identical code paths (the
# scalar-key jit), so behavior only changes when two tasks' small jobs
# genuinely overlap — exactly the fleet shape ROADMAP item 2 adds.
XTASK_COALESCE = os.environ.get("JANUS_XTASK_COALESCE", "1") != "0"

class _MeshDispatch:
    """One queued mesh enqueue: the wrapped jit, its args, and the
    rendezvous the submitting thread blocks on."""

    __slots__ = (
        "fn", "args", "kwargs", "vdaf", "program", "t_submit",
        "done", "result", "error",
    )

    def __init__(self, fn, args, kwargs, vdaf, program):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.vdaf = vdaf
        self.program = program
        self.t_submit = time.monotonic()
        self.done = threading.Event()
        self.result = None
        self.error = None


class MeshDispatchQueue:
    """Single-controller dispatch lane for EVERY mesh program in the
    process (replaces the PR 14 process-global _MESH_DISPATCH_LOCK).

    Single-controller multi-device programs deadlock when two threads
    interleave their per-device enqueues: each device ends up parked on
    the other program's collective. That happens between ANY two mesh
    programs sharing the process's devices — two different tasks'
    engines dispatching concurrently (the cross-task fleet/coalesce
    shape) deadlocked exactly like two threads on one engine did
    (observed as a rare tier-1 stall in
    test_cross_task_coalesced_round_matches_solo_...). The lock fixed
    correctness but became the throughput ceiling: it woke waiters in
    arbitrary order (starvation under contention) and hid the
    cross-engine serialization cost inside each caller's dispatch wall
    time.

    The queue keeps the invariant — exactly ONE thread (the
    "mesh-dispatch" lane, profiled under the device_lane role) performs
    every mesh enqueue — and adds what a lock cannot: FIFO fairness,
    and janus_mesh_dispatch_* queue-depth/wait-time metrics. Only the ENQUEUE is serialized;
    execution stays async on the devices, so concurrent jobs keep
    coalescing and pipelining safely. Exceptions (OOM recovery depends
    on them) re-raise in the submitting thread, original object intact
    — _handle_engine_error's type checks and the _janus_oom_handled
    dedup marker keep working."""

    def __init__(self):
        self._q: "queue.SimpleQueue[_MeshDispatch]" = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._pid: int | None = None
        self._depth = 0
        self._seen: set[tuple[str, str]] = set()
        self._stats = {
            "submitted": 0,
            "completed": 0,
            "errors": 0,
            "max_depth": 0,
            "max_wait_s": 0.0,
            "busy_s": 0.0,
        }

    def submit(self, fn, args, kwargs, vdaf: str = "", program: str = ""):
        """Run fn(*args, **kwargs) on the dispatch lane; block until the
        enqueue returns; re-raise its exception in the caller."""
        from .. import metrics

        self._ensure_thread()
        item = _MeshDispatch(fn, args, kwargs, vdaf, program)
        with self._lock:
            self._depth += 1
            depth = self._depth
            self._stats["submitted"] += 1
            if depth > self._stats["max_depth"]:
                self._stats["max_depth"] = depth
        metrics.mesh_dispatch_queue_depth.set(float(depth))
        self._q.put(item)
        item.done.wait()
        if item.error is not None:
            raise item.error
        return item.result

    def _ensure_thread(self) -> None:
        pid = os.getpid()
        t = self._thread
        if t is not None and t.is_alive() and self._pid == pid:
            return
        with self._lock:
            t = self._thread
            if t is not None and t.is_alive() and self._pid == pid:
                return
            if self._pid is not None and self._pid != pid:
                # forked child: the parent's lane thread didn't survive
                # the fork and its queue may hold the parent's items —
                # start clean (submitters in the child re-enqueue)
                self._q = queue.SimpleQueue()
                self._depth = 0
            self._pid = pid
            q = self._q
            t = threading.Thread(
                target=self._run, args=(q,), name="mesh-dispatch", daemon=True
            )
            self._thread = t
            t.start()

    def _run(self, q) -> None:
        from .. import metrics

        while True:
            item = q.get()
            wait = time.monotonic() - item.t_submit
            with self._lock:
                self._depth -= 1
                depth = self._depth
                if wait > self._stats["max_wait_s"]:
                    self._stats["max_wait_s"] = wait
                self._seen.add((item.vdaf, item.program))
            metrics.mesh_dispatch_queue_depth.set(float(depth))
            metrics.mesh_dispatch_wait_seconds.observe(wait)
            t0 = time.monotonic()
            try:
                item.result = item.fn(*item.args, **item.kwargs)
            except BaseException as e:  # noqa: BLE001 - belongs to the caller
                item.error = e
            finally:
                dt = time.monotonic() - t0
                with self._lock:
                    self._stats["busy_s"] += dt
                    self._stats["completed"] += 1
                    if item.error is not None:
                        self._stats["errors"] += 1
                metrics.mesh_dispatch_busy_seconds.add(dt)
                metrics.mesh_dispatch_total.add(program=item.program or "unknown")
                item.done.set()

    def status(self) -> dict:
        with self._lock:
            t = self._thread
            return {
                "depth": self._depth,
                "lane_alive": bool(t is not None and t.is_alive()),
                "programs": len(self._seen),
                **dict(self._stats),
            }

    def reset_for_tests(self) -> None:
        """Zero counters between test modules; the lane thread (if any)
        keeps running — it is stateless outside these counters."""
        with self._lock:
            self._seen.clear()
            self._stats.update(
                submitted=0, completed=0, errors=0,
                max_depth=0, max_wait_s=0.0, busy_s=0.0,
            )


# the process-wide lane: one queue for every engine's mesh programs,
# mirroring the lock it replaced (the interleaved-enqueue deadlock is a
# process-level hazard, not a per-engine one)
_MESH_QUEUE = MeshDispatchQueue()

_xtask_lock = threading.Lock()
_xtask_coalescers: dict[tuple, "_Coalescer"] = {}


def _shared_coalescer(inst, side: str, max_rows: int) -> "_Coalescer":
    key = (inst, side)
    with _xtask_lock:
        co = _xtask_coalescers.get(key)
        if co is None:
            run = _run_leader_round if side == "leader" else _run_helper_round
            co = _Coalescer(run, max_rows)
            _xtask_coalescers[key] = co
        return co


def _clear_shared_coalescers() -> None:
    with _xtask_lock:
        _xtask_coalescers.clear()


def _verify_key_lanes(engines, ns) -> np.ndarray:
    """[sum(ns), 2] u64 lane array carrying each entry's task verify
    key across its rows (the per-lane key input of a cross-task round)."""
    rows = [
        np.broadcast_to(
            np.frombuffer(e.verify_key, dtype="<u8").astype(np.uint64), (n, 2)
        )
        for e, n in zip(engines, ns)
    ]
    return np.ascontiguousarray(np.concatenate(rows, axis=0))


def _round_prestage_fallback(prestaged_list) -> None:
    from .. import metrics

    for p in prestaged_list:
        if p is not None:
            p.discard()  # a merged round re-stages from host columns
            metrics.engine_prestage_total.add(outcome="fallback")


def _run_leader_round(args_list, ns):
    """Coalescer round callback (leader init). Entries carry their
    submitting engine: a single-engine round is exactly the PR 7 path
    (scalar verify key, same jit); a mixed round merges across tasks
    with per-lane verify keys, executed by the first entry's engine
    (same VdafInstance => same Prio3Batched object => same geometry)."""
    engines = [a[0] for a in args_list]
    if len(args_list) == 1:
        eng, prestaged, *rest = args_list[0]
        return [eng._leader_init_inner(*rest, prestaged=prestaged)]
    from .. import metrics

    exec_eng = engines[0]
    cross = any(e is not exec_eng for e in engines)
    offsets = list(np.cumsum([0] + ns))
    metrics.engine_coalesced_rounds_total.add()
    metrics.engine_coalesced_rows_total.add(int(sum(ns)))
    _round_prestage_fallback([a[1] for a in args_list])
    merged = _concat_args([a[2:] for a in args_list])
    vk = _verify_key_lanes(engines, ns) if cross else None
    # one padded dispatch for the whole round (no intra-call
    # pipelining: round-to-round overlap already covers H2D)
    out0, seed0, ver0, part0 = exec_eng._leader_init_inner(
        *merged, coalesced=len(ns), allow_pipeline=False, vk_lanes=vk
    )
    if isinstance(out0, DeviceRowsChunks):
        # cap halved mid-round (concurrent OOM recovery): split on
        # host rows instead of device-buffer views
        rows = out0.to_numpy()
        outs = [
            tuple(x[s:e] for x in rows) for s, e in zip(offsets, offsets[1:])
        ]
    else:
        outs = [
            DeviceRows(out0.value, e - s, offset=s)
            for s, e in zip(offsets, offsets[1:])
        ]
    seeds = _split_rows(seed0, offsets)
    vers = _split_rows(ver0, offsets)
    parts = _split_rows(part0, offsets)
    return list(zip(outs, seeds, vers, parts))


def _run_helper_round(args_list, ns):
    """Coalescer round callback (helper init); see _run_leader_round."""
    engines = [a[0] for a in args_list]
    offsets = list(np.cumsum([0] + ns))
    if len(args_list) == 1:
        eng, *rest = args_list[0]
        out1, mask, prep_msg = eng._helper_init_inner(*rest)
        return [(out1, mask, prep_msg)]
    from .. import metrics

    exec_eng = engines[0]
    cross = any(e is not exec_eng for e in engines)
    metrics.engine_coalesced_rounds_total.add()
    metrics.engine_coalesced_rows_total.add(int(sum(ns)))
    merged = _concat_args([a[1:] for a in args_list])
    vk = _verify_key_lanes(engines, ns) if cross else None
    out1, mask, prep_msg = exec_eng._helper_init_inner(
        *merged, coalesced=len(ns), vk_lanes=vk
    )
    if isinstance(out1, DeviceRowsChunks):
        # the bucket cap halved between round admission and dispatch
        # (concurrent OOM recovery) and the merged round chunked:
        # split on host rows — plain limb tuples are valid out-share
        # currency (HostEngineCache returns them)
        rows = out1.to_numpy()
        return [
            (tuple(x[s:e] for x in rows), mask[s:e], prep_msg[s:e])
            for s, e in zip(offsets, offsets[1:])
        ]
    return [
        (DeviceRows(out1.value, e - s, offset=s), mask[s:e], prep_msg[s:e])
        for s, e in zip(offsets, offsets[1:])
    ]


def _engine_dispatch_failpoint() -> None:
    """`engine.dispatch` failpoint INSIDE every watchdog-supervised
    device region: the oom action raises a RESOURCE_EXHAUSTED-shaped
    error so the injected fault rides the REAL recovery path
    (_handle_engine_error's halved-bucket retry / host fallback),
    exactly like a device OOM; the hang action parks the supervised
    worker exactly like a wedged XLA dispatch, so the watchdog's
    abandon/quarantine path is what recovers it."""
    failpoints.hit(
        "engine.dispatch",
        error_factory=lambda: RuntimeError(
            "RESOURCE_EXHAUSTED: injected failpoint engine.dispatch"
        ),
    )


def _helper_init_body(p3, vkey, nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask):
    """Helper prepare init plus the joint verification against the
    leader's shares: (out1, accept mask, prep_msg lanes)."""
    out1, seed1, ver1, part1 = p3.prepare_init_helper(
        vkey, nonce_lanes, public_parts, helper_seeds, blinds
    )
    mask, prep_msg = p3.prep_shares_to_prep(ver0, ver1, part0, part1)
    mask = p3.prepare_finish(seed1, prep_msg, mask)
    mask = mask & ok_mask
    if prep_msg is None:
        prep_msg = jnp.zeros((nonce_lanes.shape[0], 2), dtype=jnp.uint64)
    return out1, mask, prep_msg


class EngineCache:
    """Per (vdaf, verify_key) jitted steps, keyed by batch bucket.

    Multi-device serving: when the process sees more than one JAX
    device, every jitted step is bound to a dp (report-batch) mesh over
    the largest power-of-two device count, so helper init and the
    leader driver — the production traffic paths, not just bench.py —
    shard across chips (SURVEY §2.10 P2/P4; the reference scales the
    same work with DB replicas + rayon). Single-device behavior is
    unchanged."""

    # input_len at which the vector axis gets a slice of the mesh (sp):
    # the streamed-query activation point — the lengths where per-report
    # tensors, not report count, dominate
    SP_MIN_INPUT_LEN = STREAM_MIN_INPUT_LEN

    # mesh geometry overrides (the `engine: mesh: {dp, sp}` config
    # stanza; None = auto-select from device count + circuit shape).
    # Class attributes so janus_main applies the YAML once; the
    # JANUS_MESH_DP / JANUS_MESH_SP env vars win over both (operator
    # override, read per-engine so subprocess benches can force shapes).
    MESH_DP: int | None = None
    MESH_SP: int | None = None

    @classmethod
    def _configured_geometry(cls) -> tuple[int | None, int | None]:
        def pick(env: str, fallback: int | None) -> int | None:
            v = os.environ.get(env)
            if v is None or not v.strip():
                return fallback
            try:
                return int(v)
            except ValueError:
                log.warning("ignoring non-integer %s=%r", env, v)
                return fallback

        return pick("JANUS_MESH_DP", cls.MESH_DP), pick("JANUS_MESH_SP", cls.MESH_SP)

    def __init__(self, inst: VdafInstance, verify_key: bytes):
        self.inst = inst
        self.verify_key = verify_key
        self.p3 = prio3_batched(inst)
        self._jits: dict[str, object] = {}
        ndev = len(jax.devices())
        self._ndev = ndev
        # geometry: auto-selected from device count and circuit shape
        # (dp = report batch axis, sp = measurement/out-share column
        # axis for long-vector tasks — SURVEY §2.10 P4 / §5
        # long-context analog), or pinned by the `engine: mesh:` config
        # stanza / JANUS_MESH_DP/SP overrides. One device (or an
        # override pinning 1x1) = the single-device path, no mesh.
        from ..parallel.api import choose_mesh_geometry, make_mesh

        cfg_dp, cfg_sp = self._configured_geometry()
        circ = self.p3.circ
        dp, sp = choose_mesh_geometry(
            ndev,
            getattr(circ, "input_len", 0),
            getattr(circ, "output_len", 0),
            self.SP_MIN_INPUT_LEN,
            MIN_BUCKET,  # every bucket must divide by dp
            dp=cfg_dp,
            sp=cfg_sp,
        )
        # block-sparse tasks (ISSUE 17) force the single-device path:
        # the scatter-merge kernel writes one donated logical
        # accumulator per slot, and sharding its write axis over 'sp'
        # is future work. The reason is explicit in /statusz mesh.
        self.sparse = isinstance(self.p3.circ, SparseSumVec)
        self.mesh_fallback_reason: str | None = None
        if self.sparse and dp * sp > 1:
            dp, sp = 1, 1
            self.mesh_fallback_reason = "sparse_scatter_single_device"
        self.mesh = make_mesh(dp, sp) if dp * sp > 1 else None
        self.dp = dp
        self.sp = sp
        # HBM feasibility bound (ISSUE r6): the largest power-of-two
        # bucket the device budget supports for this circuit, from the
        # bytes model in vdaf.feasibility (staged share + proofs +
        # outputs + the streamed-query tile working set). The CPU
        # backend has no budget and stays uncapped; an accelerator
        # without one is an error (device_memory_budget).
        # JANUS_BUCKET_CAP overrides for tests/tuning ("0" = uncapped).
        circ = self.p3.circ
        plan = stream_plan(self.p3.bc)
        self.tile_elems = plan.group if plan is not None else None
        env_cap = os.environ.get("JANUS_BUCKET_CAP")
        if env_cap is not None:
            cap = int(env_cap)
            # buckets are powers of two (bucket_size) and mesh shards
            # need dp | bucket — round a stray override down so e.g.
            # "20" can't produce a 20-row axis dp can't partition
            self.bucket_cap = (1 << (cap.bit_length() - 1)) if cap > 0 else None
        else:
            self.bucket_cap = feasible_bucket(
                circ,
                device_memory_budget(),
                tile_elems=self.tile_elems,
                draft=inst.xof_mode != "fast",
            )
        if self.bucket_cap is not None:
            # mesh dispatches shard the report axis over dp devices;
            # every bucket (hence the cap) must stay divisible by dp
            self.bucket_cap = max(self.bucket_cap, self.dp)
        # runtime OOM recovery state: halve-the-bucket retries mutate
        # bucket_cap under the lock; at the floor the engine installs a
        # HostEngineCache and serves from it for the process lifetime.
        self._oom_lock = threading.Lock()
        self._host_fallback: "HostEngineCache | None" = None
        self._initial_bucket_cap = self.bucket_cap
        # multi-device program dispatch rides the process-wide
        # single-controller lane (_MESH_QUEUE — see MeshDispatchQueue
        # for the interleaved-enqueue deadlock it prevents and the
        # queue-depth/wait metrics it adds over the lock it replaced)
        # cross-job dispatch coalescing (VERDICT r4 item 3): calls at or
        # below COALESCE_MAX_JOB rows ride shared device dispatches;
        # bigger jobs fill a dispatch on their own and go direct. The
        # per-round row cap scales inversely with the instance's
        # per-row size: a global 32768 tuned on Count would merge
        # concurrent SumVec jobs past the measured single-dispatch HBM
        # limit (len=1000 OOMed at batch 4096, unverified link-era
        # figure) and fail every co-batched job at once — and never past the HBM
        # feasibility cap.
        self._coalesce = os.environ.get("JANUS_COALESCE", "1") != "0"
        in_len = max(1, getattr(self.p3.circ, "input_len", 1))
        round_rows = max(
            MIN_BUCKET, min(self.COALESCE_ROUND_ROWS, self.COALESCE_ROUND_ELEMS // in_len)
        )
        if self.bucket_cap is not None:
            round_rows = min(round_rows, self.bucket_cap)
        self._initial_round_rows = round_rows
        # round-based coalescers. With cross-task coalescing (the
        # default) engines of the same VdafInstance SHARE one coalescer
        # per side, so small jobs of different tasks ride one dispatch
        # (per-lane verify keys); disabled, each engine keeps its own
        # (the PR 7 shape). Entries always carry their engine.
        if XTASK_COALESCE:
            self._co_leader = _shared_coalescer(inst, "leader", round_rows)
            self._co_helper = _shared_coalescer(inst, "helper", round_rows)
        else:
            self._co_leader = _Coalescer(_run_leader_round, round_rows)
            self._co_helper = _Coalescer(_run_helper_round, round_rows)
        # device-resident aggregate state (ISSUE 12): per-(task, batch
        # bucket) accumulator buffers living in device memory across job
        # steps. The ENGINE owns the buffers and the device ops
        # (delta/merge/fetch); the DRIVER owns the flush policy (the
        # write-tx path) — see aggregation_job_driver.ResidentConfig.
        self._resident: "OrderedDict[tuple, ResidentSlot]" = OrderedDict()
        self._resident_lock = threading.Lock()
        self._resident_stats = {
            "merged_rows": 0,
            "merges": 0,
            "evictions": 0,
            "eviction_deferred": 0,
            "takes": 0,
        }
        # sparse scatter accounting (ISSUE 17): total reports scattered
        # into dense logical accumulators + the last dispatch's mean
        # block occupancy — surfaced on the statusz `sparse` line and
        # the janus_engine_scatter_rows_total / _sparse_block_occupancy
        # metrics
        self._scatter_rows = 0
        self._sparse_last_occupancy: float | None = None
        # device-circuit quarantine (ISSUE 8; docs/ROBUSTNESS.md "Device
        # hangs & deadlines"): a watchdog-abandoned dispatch opens the
        # circuit — serving moves to the host engine immediately (the
        # interim work must land), and a background canary thread
        # recompiles + probe-dispatches until the device answers again,
        # then restores the device path with the initial caps — an
        # EVENT-driven exit (proof the device responds).
        self._quarantined = False
        # set by stop_canary() (process teardown): wakes the canary's
        # cool-down wait so the loop exits instead of launching a probe
        # whose native device work would race interpreter finalization
        self._canary_wakeup = threading.Event()
        self._canary_stop = False
        self._canary_thread: threading.Thread | None = None
        # observability (docs/OBSERVABILITY.md "Engine metrics"): first
        # dispatch per (op, bucket) is the compile; OOM events feed the
        # /statusz engine-cache section
        self._dispatched_buckets: set[tuple[str, int]] = set()
        # finer first-dispatch tracking for the persisted shape
        # manifest: keyed by the jit specialization (variant name +
        # bucket) the call site reports, so a classic-aggregate compile
        # after the resident path warmed the same row bucket — or a new
        # agg_buckets_{kk} program at an already-seen bucket — is still
        # recorded as a specialization of its own
        self._specializations_dispatched: set[tuple] = set()
        self._dispatch_track_lock = threading.Lock()
        self.oom_history: deque = deque(maxlen=16)
        self._publish_state()

    # every state the janus_engine_backend gauge manages; exactly one
    # is 1 per VDAF kind at any time (docs/OBSERVABILITY.md)
    BACKEND_STATES = ("device", "host_fallback", "quarantined", "host")

    def _backend_state(self) -> str:
        if self._quarantined:
            return "quarantined"
        return "device" if self._host_fallback is None else "host_fallback"

    def _publish_state(self) -> None:
        """Refresh the janus_engine_backend / janus_engine_bucket_cap
        gauges for this engine's VDAF kind (callers hold _oom_lock when
        mutating fallback state; the gauges take their own locks).
        All states are managed — including "host", which only
        _build_engine sets to 1 — so exactly one state is 1 per kind
        and a draft-mode host engine followed by a fast-mode device
        engine of the same kind can't leave both at 1. Same-kind
        engines (different params) share the label and last-writer
        wins; the gauge is per VDAF kind, not per task."""
        from ..metrics import engine_backend_state, engine_bucket_cap

        state = self._backend_state()
        for s in self.BACKEND_STATES:
            engine_backend_state.set(1.0 if s == state else 0.0, vdaf=self.inst.kind, state=s)
        engine_bucket_cap.set(float(self.bucket_cap or 0), vdaf=self.inst.kind)

    def _record_dispatch(
        self,
        op: str,
        n: int,
        b: int,
        elapsed_s: float,
        manifest_op: str | None = None,
        compile_key: tuple | None = None,
    ) -> None:
        """Per-dispatch accounting: throughput counters, padding-waste
        gauge, and the first-call-per-(op, bucket) compile histogram —
        jax.jit compiles synchronously on the first call of a shape
        bucket, so that call's wall time IS the cold-start cost
        OBSERVABILITY.md used to describe only in prose. `manifest_op`
        names the shape-manifest entry finer than the engine counters
        (the resident aggregate_pending path shares op="aggregate" in
        janus_engine_dispatches_total but one dispatch covers k
        buckets) and `compile_key` carries the variant name the call
        site jitted, so first-dispatch tracking follows the real
        specialization, not the engine-metric (op, bucket)
        approximation."""
        from .. import metrics

        metrics.engine_dispatches_total.add(op=op)
        metrics.engine_rows_total.add(n, op=op)
        if b > 0:
            metrics.engine_batch_fill_ratio.set(n / b, op=op)
        lkey = compile_key if compile_key is not None else (manifest_op or op, b)
        if self.mesh is not None:
            # mesh specializations are keyed by geometry too: the shape
            # manifest must never hand a (dp, sp) program to a boot with
            # a different device topology (prewarm checks this suffix),
            # and the AOT digest carries the same triple
            lkey = tuple(lkey) + ("mesh", self.dp, self.sp, self._ndev)
        with self._dispatch_track_lock:
            first = (op, b) not in self._dispatched_buckets
            if first:
                self._dispatched_buckets.add((op, b))
            new_specialization = lkey not in self._specializations_dispatched
            if new_specialization:
                self._specializations_dispatched.add(lkey)
        if first:
            metrics.engine_compile_seconds.observe(elapsed_s, op=op, bucket=str(b))
        if new_specialization:
            # persisted shape manifest (ISSUE 14): the first dispatch
            # of a specialization IS the cold-start cost a restarted
            # process would pay again — record it so the boot prewarm
            # can compile exactly this set before /readyz flips ready
            shape_manifest.record_dispatch(
                self.inst, manifest_op or op, b, lkey, elapsed_s, rows=n
            )

    # Per-call row cap for joining a shared round; absolute round row
    # cap; and the rows x input_len budget one coalesced round may
    # stage (2^25 elements = half the len=1000 OOM point at 4096 rows).
    COALESCE_MAX_JOB = 4096
    COALESCE_ROUND_ROWS = 32768
    COALESCE_ROUND_ELEMS = 1 << 25

    def _shard(self, *batch_ndims):
        """NamedShardings splitting the leading (report) axis over 'dp';
        one entry per arg, each an int ndim or a tuple (field limbs) or
        None (absent arg). The string marker "vec2" is a 2-d field limb
        whose trailing (vector) axis additionally shards over 'sp'."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        def one(nd):
            if nd is None:
                return None
            if nd == "vec2":
                return NamedSharding(self.mesh, P("dp", "sp"))
            if isinstance(nd, tuple):
                return tuple(one(x) for x in nd)
            return NamedSharding(self.mesh, P(*(("dp",) + (None,) * (nd - 1))))

        return tuple(one(nd) for nd in batch_ndims)

    def init_step(self, name: str):
        """The single-task program `name` ("leader_init" or
        "helper_init") as the function _jit compiles, the verify key a
        trace constant. tests/test_chip_compile.py and chip_smoke.py
        lower it without dispatching."""
        p3 = self.p3
        vk = self.verify_key
        if name == "leader_init":

            def step(*a):
                return p3.prepare_init_leader(vk, *a)

        else:

            def step(*a):
                return _helper_init_body(p3, vk, *a)

        return step

    def _jit(self, name: str, fn, in_shardings=None, out_shardings=None):
        if name not in self._jits:
            # the program's name: the lowered module, and the trace's
            # `XLA Modules` line, read jit_<name>
            fn.__name__ = fn.__qualname__ = name
            kwargs = {}
            if self.mesh is not None:
                if in_shardings is not None:
                    kwargs["in_shardings"] = in_shardings
                if out_shardings is not None:
                    kwargs["out_shardings"] = out_shardings
            jitted = jax.jit(fn, **kwargs)
            # every program — single-device AND mesh — rides the
            # serialized-executable AOT cache (aot_cache.py): a
            # restarted process, or a canary rebuild that just dropped
            # _jits, deserializes the compiled executable instead of
            # re-tracing. Mesh digests carry (dp, sp, device count) so
            # a blob only ever loads on its own topology; a passthrough
            # while the cache is disarmed.
            wrapped = aot_cache.wrap(
                jitted,
                aot_cache.engine_base(
                    self.inst.to_dict(),
                    self.verify_key,
                    name,
                    mesh=(self.dp, self.sp, self._ndev)
                    if self.mesh is not None
                    else None,
                ),
            )
            if self.mesh is not None:
                # multi-device enqueues are owned by the process-wide
                # single-controller lane (MeshDispatchQueue): submit
                # blocks this thread until the lane ran the enqueue,
                # execution stays async on the devices
                vdaf = self.inst.kind

                def queued(*a, _fn=wrapped, _name=name, _vdaf=vdaf, **k):
                    return _MESH_QUEUE.submit(_fn, a, k, vdaf=_vdaf, program=_name)

                self._jits[name] = queued
            else:
                self._jits[name] = wrapped
        return self._jits[name]

    # --- OOM recovery (shared by every public step) ---
    def _handle_engine_error(self, e: BaseException, n: int) -> None:
        """Called from an except block. Re-raises non-OOM errors;
        otherwise halves the bucket cap (so the caller's retry chunks
        smaller) and, at the bucket floor, installs the permanent
        HostEngineCache fallback. Never lets the OOM escape — the
        aggregation job driver sees a slow success, not a dead job."""
        if not is_oom_error(e):
            raise
        with self._oom_lock:
            if self._host_fallback is not None:
                return
            # A coalesced round hands the SAME exception object to every
            # co-batched submitter's retry loop; only the first may act,
            # or one transient OOM would halve once per submitter and
            # walk the cap straight to the host-fallback floor.
            if getattr(e, "_janus_oom_handled", False):
                return
            try:
                e._janus_oom_handled = True
            except Exception:
                pass
            floor = max(1, self.dp)
            observed = getattr(e, "_janus_dispatch_bucket", None)
            if observed is None:
                observed = bucket_size(n, self.bucket_cap)
            # halving only helps dispatches whose bucket tracks the cap.
            # An aggregate over an ALREADY-RESIDENT device buffer re-runs
            # at the buffer's fixed bucket no matter the cap, so a
            # persistent OOM there would loop forever at new_cap ==
            # bucket_cap — treat "no progress possible" as the floor.
            stuck = (
                getattr(e, "_janus_fixed_bucket", False)
                and self.bucket_cap is not None
                and observed // 2 >= self.bucket_cap
            )
            if observed <= floor or stuck:
                # an HBM overflow at the bucket floor can never fit, so
                # the fallback is final
                log.warning(
                    "device OOM at bucket floor %d for %s; falling back to "
                    "the host engine permanently: %s",
                    floor,
                    self.inst.kind,
                    e,
                )
                from ..metrics import engine_host_fallback_counter

                engine_host_fallback_counter.add()
                self._host_fallback = HostEngineCache(self.inst, self.verify_key)
                self.oom_history.append(
                    {
                        "at": time.time(),
                        "bucket": observed,
                        "action": "host_fallback",
                        "error": str(e)[:200],
                    }
                )
                self._publish_state()
                return
            new_cap = observed // 2
            self.bucket_cap = new_cap if self.bucket_cap is None else min(self.bucket_cap, new_cap)
            self._co_leader._max_rows = min(self._co_leader._max_rows, self.bucket_cap)
            self._co_helper._max_rows = min(self._co_helper._max_rows, self.bucket_cap)
            log.warning(
                "device OOM at bucket %d for %s; retrying with bucket cap %d: %s",
                observed, self.inst.kind, self.bucket_cap, e,
            )
            from ..metrics import engine_oom_retry_counter

            engine_oom_retry_counter.add()
            self.oom_history.append(
                {
                    "at": time.time(),
                    "bucket": observed,
                    "action": f"halved_to_{self.bucket_cap}",
                    "error": str(e)[:200],
                }
            )
            self._publish_state()

    def _host(self) -> "HostEngineCache | None":
        """Active host engine, or None to dispatch on the device. A
        definite RESOURCE_EXHAUSTED at the bucket floor pins the host
        engine for the process lifetime. Process-wide HOST-ONLY mode
        (the watchdog's abandoned-thread cap tripped — the device has
        eaten too many threads to trust again this process) installs
        one, and the per-engine hang QUARANTINE serves from one until
        its canary probe proves the device answers again."""
        if device_watchdog.WATCHDOG.host_only():
            host = self._host_fallback
            if host is None:
                with self._oom_lock:
                    if self._host_fallback is None:
                        self._host_fallback = HostEngineCache(self.inst, self.verify_key)
                        self._publish_state()
                    host = self._host_fallback
            return host
        return self._host_fallback

    # --- hang quarantine + canary rebuild (ISSUE 8) ---
    # Env defaults let harnesses (chaos_run device_hang) shrink the
    # cycle; janus_main applies the YAML `device_watchdog:` values to
    # these class attributes at boot.
    QUARANTINE_CANARY_DELAY_SECS = float(os.environ.get("JANUS_CANARY_DELAY_S", "5.0"))
    QUARANTINE_CANARY_TIMEOUT_SECS = float(os.environ.get("JANUS_CANARY_TIMEOUT_S", "30.0"))
    QUARANTINE_CANARY_MAX_DELAY_SECS = 60.0

    def _supervised(self, label: str, fn):
        """Route a device-touching closure through the process dispatch
        watchdog under the AMBIENT deadline (job drivers: lease bound;
        helper handlers: propagated request budget — core/deadline.py).
        No ambient deadline = direct call: one contextvar read, the
        bench --dry-run `watchdog_overhead` record keeps it honest."""
        return device_watchdog.WATCHDOG.run(
            fn,
            deadline=current_deadline(),
            label=label,
            vdaf=self.inst.kind,
            on_hang=self._quarantine_on_hang,
        )

    def _quarantine_on_hang(self, label: str) -> None:
        """Watchdog hang hook: open the device circuit. Serving moves
        to the host engine NOW (the step that hung steps back; its
        retry and every other job must land through host fallback), and
        the canary thread owns the way back."""
        from .. import metrics

        with self._oom_lock:
            if self._quarantined:
                return
            # order matters for the lock-free readers in _host(): the
            # fallback must exist BEFORE the flag flips, or a racing
            # caller sees quarantined-with-no-host and dispatches to
            # the known-wedged device
            if self._host_fallback is None:
                self._host_fallback = HostEngineCache(self.inst, self.verify_key)
            self._quarantined = True
            self.oom_history.append(
                {
                    "at": time.time(),
                    "bucket": None,
                    "action": "quarantined",
                    "error": f"hung dispatch {label}",
                }
            )
            self._publish_state()
            start_canary = not device_watchdog.WATCHDOG.host_only()
        metrics.engine_quarantines_total.add(vdaf=self.inst.kind, event="open")
        log.error(
            "engine %s QUARANTINED after hung %s dispatch; serving from the host "
            "engine while the canary probes the device",
            self.inst.kind,
            label,
        )
        if start_canary:
            t = threading.Thread(
                target=self._canary_loop,
                name=f"engine-canary-{self.inst.kind}",
                daemon=True,
            )
            self._canary_thread = t
            t.start()

    def _canary_loop(self) -> None:
        """Background canary: after a cool-down, recompile + probe the
        device; on success restore the device path with the initial
        caps, on failure back off and try again (a still-wedged device
        keeps quarantine open; repeated hung probes walk the abandoned
        cap toward host-only mode, which ends the loop)."""
        from .. import metrics

        delay = self.QUARANTINE_CANARY_DELAY_SECS
        while True:
            self._canary_wakeup.wait(delay)
            self._canary_wakeup.clear()
            if (
                self._canary_stop
                or not self._quarantined
                or device_watchdog.WATCHDOG.host_only()
            ):
                return
            metrics.engine_quarantines_total.add(vdaf=self.inst.kind, event="canary_probe")
            try:
                self._canary_probe()
            except BaseException as e:  # noqa: BLE001 - incl. DeviceHangError
                metrics.engine_quarantines_total.add(
                    vdaf=self.inst.kind, event="canary_failed"
                )
                log.warning(
                    "canary probe for %s failed (%s: %s); next probe in %.1fs",
                    self.inst.kind, type(e).__name__, e, delay,
                )
                delay = min(delay * 2, self.QUARANTINE_CANARY_MAX_DELAY_SECS)
                continue
            with self._oom_lock:
                self._quarantined = False
                self._host_fallback = None
                self.bucket_cap = self._initial_bucket_cap
                self._co_leader._max_rows = self._initial_round_rows
                self._co_helper._max_rows = self._initial_round_rows
                self.oom_history.append(
                    {"at": time.time(), "bucket": None, "action": "restored", "error": ""}
                )
                self._publish_state()
            metrics.engine_quarantines_total.add(vdaf=self.inst.kind, event="restored")
            log.warning(
                "engine %s restored to the device path (canary probe succeeded)",
                self.inst.kind,
            )
            # warm canary restore (ISSUE 14): the probe dropped every
            # compiled executable, so re-warm this engine's recorded
            # specializations from the shape manifest HERE, in the
            # canary thread — with the persistent compile cache these
            # are disk loads, and the serving path never pays a
            # post-restore re-trace. Best-effort: serving is already
            # restored; a failed warm just means lazier compiles.
            if not self._canary_stop:
                try:
                    from .prewarm import warm_engine_from_manifest

                    # stop-aware between entries: stop_canary's bounded
                    # join must not leave this loop dispatching native
                    # work into interpreter finalization
                    warmed = warm_engine_from_manifest(
                        self, should_stop=lambda: self._canary_stop
                    )
                    if warmed:
                        log.info(
                            "canary re-warmed %d recorded specialization(s) for %s",
                            warmed, self.inst.kind,
                        )
                except Exception:
                    log.warning("post-restore manifest warm failed", exc_info=True)
            return

    def stop_canary(self, timeout_s: float = 2.0) -> None:
        """Process-teardown hook (shutdown_engines): stop the canary
        loop and give an in-flight probe a bounded window to finish —
        a daemon worker mid-probe re-entering native device code while
        the interpreter finalizes crashes the runtime (the same hazard
        as woken hang workers; ROBUSTNESS.md)."""
        self._canary_stop = True
        self._canary_wakeup.set()
        t = self._canary_thread
        if t is not None and t.is_alive():
            t.join(timeout_s)

    def _canary_probe(self) -> None:
        """Recompile + probe dispatch: drop the cached executables (the
        hung program may be wedged inside the runtime) and run a small
        REAL masked aggregate — device put, fresh trace+compile,
        dispatch, fetch — under the watchdog with its own bounded
        deadline. Success means the device answers end to end. The
        `engine.canary` failpoint lets tests hold the quarantine open."""
        p3 = self.p3
        self._jits = {}  # atomic swap; abandoned threads keep old refs
        b = max(MIN_BUCKET, self.dp)
        value = tuple(
            np.zeros((b, p3.circ.output_len), dtype=np.uint64)
            for _ in range(p3.jf.LIMBS)
        )
        mask = np.zeros(b, dtype=bool)

        def step(v, m):
            return p3.aggregate(v, m)

        fn = self._jit("aggregate", step)
        deadline = time.monotonic() + self.QUARANTINE_CANARY_TIMEOUT_SECS

        def probe():
            failpoints.hit("engine.canary")
            staged = put_args((value, mask), block=True)
            agg = fn(*staged)
            return [int(x) for x in p3.jf.to_ints(agg)]

        result = device_watchdog.WATCHDOG.run(
            probe, deadline=deadline, label="canary", vdaf=self.inst.kind
        )
        if any(result):
            raise RuntimeError(f"canary probe returned garbage: {result[:4]}")

    # --- helper side: init + combine + decide in one traced step ---
    def helper_init(self, nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask):
        """Returns (out1 field value, accept mask, prep_msg lanes) sliced
        to the true batch size. Small batches coalesce with concurrent
        callers into one device dispatch (_Coalescer). Device OOM is
        absorbed: halved-bucket retry, then host fallback."""
        args = (nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask)
        while True:
            host = self._host()
            if host is not None:
                return host.helper_init(*args)
            try:
                return self._helper_init_entry(*args)
            except Exception as e:  # noqa: BLE001 - OOM filter inside
                self._handle_engine_error(e, nonce_lanes.shape[0])

    def _helper_init_entry(self, nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask):
        n = nonce_lanes.shape[0]
        cap = self.bucket_cap
        if self._coalesce and n <= self.COALESCE_MAX_JOB and (cap is None or n <= cap):
            return self._co_helper.submit(
                (self, nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask),
                n,
            )
        if cap is not None and n > cap:
            return self._helper_init_chunked(
                nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask, cap
            )
        return self._helper_init_inner(
            nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask
        )

    def _helper_init_chunked(
        self, nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask, cap: int,
        vk_lanes=None,
    ):
        """Serial cap-sized dispatches for a batch past the HBM bound —
        each chunk's working set fits the budget; out shares stay
        device-resident as DeviceRowsChunks."""
        n = nonce_lanes.shape[0]
        outs, masks, preps = [], [], []
        for s in range(0, n, cap):
            e = min(s + cap, n)
            out1, mask, prep = self._helper_init_inner(
                _cut_rows(nonce_lanes, s, e),
                _cut_rows(public_parts, s, e),
                _cut_rows(helper_seeds, s, e),
                _cut_rows(blinds, s, e),
                _cut_rows(ver0, s, e),
                _cut_rows(part0, s, e),
                _cut_rows(ok_mask, s, e),
                vk_lanes=_cut_rows(vk_lanes, s, e),
            )
            outs.append(out1)
            masks.append(mask)
            preps.append(prep)
        return DeviceRowsChunks(outs), np.concatenate(masks), np.concatenate(preps)

    def _helper_init_inner(
        self, nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask,
        coalesced: int = 0, vk_lanes=None,
    ):
        p3 = self.p3
        n = nonce_lanes.shape[0]
        cap = self.bucket_cap  # read once — concurrent OOM recovery may
        # halve it between the entry/coalescer gate and here; a stale
        # smaller cap with n > cap must chunk, never pad negative
        if cap is not None and n > cap:
            return self._helper_init_chunked(
                nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask, cap,
                vk_lanes=vk_lanes,
            )
        b = bucket_size(n, cap)

        from ..trace import span

        L = len(ver0)
        arg_nds = (
            2,
            None if public_parts is None else 3,
            2,
            None if blinds is None else 2,
            (2,) * L,
            2,
            1,
        )
        if vk_lanes is None:
            # single-task round: the verify key stays a trace constant —
            # byte-identical compiled steps to the pre-cross-task engine
            step = self.init_step("helper_init")
            name = "helper_init"
        else:
            # cross-task round: the key is a per-lane input
            def step(vk, *a):
                return _helper_init_body(p3, vk, *a)

            name = "helper_init_vk"
            arg_nds = (2,) + arg_nds
        shardings = None
        if self.mesh is not None:
            shardings = self._shard(*arg_nds)
        fn = self._jit(name, step, in_shardings=shardings)
        raw_args = (nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask)
        if vk_lanes is not None:
            raw_args = (vk_lanes,) + raw_args
        args = pad_args(b, *raw_args)

        # the np.asarray conversions block on device execution — they
        # must sit inside the span or it measures only async dispatch.
        # out1 stays ON DEVICE (DeviceRows): the aggregate step reads it
        # there; only the small mask/prep_msg come back. The whole
        # device-touching region (put/dispatch/fetch — every point a
        # wedged device can park the thread, failpoint included so the
        # hang action models exactly that) runs under the dispatch
        # watchdog (_supervised).
        def device_call():
            _engine_dispatch_failpoint()
            with span(
                "engine.helper_init",
                vdaf=self.inst.kind,
                batch=n,
                bucket=b,
                coalesced=coalesced,
            ):
                with span("engine.helper_init.put", vdaf=self.inst.kind, bucket=b):
                    staged = put_args(args, block=True, shardings=shardings)
                t_disp = time.monotonic()
                with span("engine.helper_init.dispatch", vdaf=self.inst.kind):
                    out1, mask, prep_msg = fn(*staged)
                self._record_dispatch(
                    "helper_init", n, b, time.monotonic() - t_disp,
                    compile_key=(name, b),
                )
                with span("engine.helper_init.fetch", vdaf=self.inst.kind, bucket=b):
                    mask = np.asarray(mask)[:n]
                    prep_msg = np.asarray(prep_msg)[:n]
                    count_d2h((mask, prep_msg))
            return out1, mask, prep_msg

        try:
            out1, mask, prep_msg = self._supervised("helper_init", device_call)
        except Exception as e:
            _annotate_dispatch_bucket(e, b)
            raise
        return DeviceRows(out1, n), mask, prep_msg

    # Pipelined leader init: jobs past 2x this size split into chunks
    # whose host->device transfers are ALL issued up front; each chunk's
    # dispatch then overlaps the later chunks' transfers (VERDICT r3
    # item 8 — the driver used to stage-then-dispatch serially, leaving
    # the device idle for the whole staging transfer).
    PIPELINE_CHUNK = 256

    # --- leader side: init only (network round trip follows) ---
    def leader_init(self, nonce_lanes, public_parts, meas, proof, blind0, ok=None, prestaged=None):
        # ok is accepted for interface parity with HostEngineCache; the
        # batched device step costs nothing extra for failed lanes
        # (their rows are zeroed and masked downstream).
        while True:
            host = self._host()
            if host is not None:
                if prestaged is not None:
                    prestaged.discard()
                    prestaged = None
                return host.leader_init(nonce_lanes, public_parts, meas, proof, blind0, ok)
            try:
                return self._leader_init_entry(
                    nonce_lanes, public_parts, meas, proof, blind0, prestaged
                )
            except Exception as e:  # noqa: BLE001 - OOM filter inside
                if prestaged is not None:
                    prestaged.discard()
                    prestaged = None  # the retry re-stages from host
                self._handle_engine_error(e, nonce_lanes.shape[0])

    def _leader_init_entry(self, nonce_lanes, public_parts, meas, proof, blind0, prestaged=None):
        n = nonce_lanes.shape[0]
        cap = self.bucket_cap
        if self._coalesce and n <= self.COALESCE_MAX_JOB and (cap is None or n <= cap):
            return self._co_leader.submit(
                (self, prestaged, nonce_lanes, public_parts, meas, proof, blind0), n
            )
        return self._leader_init_inner(
            nonce_lanes, public_parts, meas, proof, blind0, prestaged=prestaged
        )

    def _leader_init_inner(
        self,
        nonce_lanes,
        public_parts,
        meas,
        proof,
        blind0,
        coalesced: int = 0,
        allow_pipeline: bool = True,
        vk_lanes=None,
        prestaged=None,
    ):
        p3 = self.p3
        n = nonce_lanes.shape[0]
        cap = self.bucket_cap
        if cap is not None and n > cap:
            # past the HBM bound: serial cap-sized dispatches (staging
            # everything up front, as the pipelined path does, would
            # resident-stage exactly the bytes the cap exists to avoid)
            if prestaged is not None:
                prestaged.discard()
            return self._leader_init_chunked(
                nonce_lanes, public_parts, meas, proof, blind0, cap, vk_lanes=vk_lanes
            )
        if (
            allow_pipeline
            and vk_lanes is None
            and self.mesh is None
            and n >= 2 * self.PIPELINE_CHUNK
        ):
            if prestaged is not None:
                prestaged.discard()
            return self._leader_init_pipelined(
                nonce_lanes, public_parts, meas, proof, blind0
            )
        b = bucket_size(n, cap)

        from ..trace import span

        L = len(meas)
        meas_nd = "vec2" if self.sp > 1 else 2
        arg_nds = (
            2,
            None if public_parts is None else 3,
            (meas_nd,) * L,
            (2,) * L,
            None if blind0 is None else 2,
        )
        if vk_lanes is None:
            step = self.init_step("leader_init")
            name = "leader_init"
        else:
            # cross-task round: per-lane verify keys ride the dispatch
            def step(vk, *a):
                return p3.prepare_init_leader(vk, *a)

            name = "leader_init_vk"
            arg_nds = (2,) + arg_nds
        shardings = None
        if self.mesh is not None:
            shardings = self._shard(*arg_nds)
        fn = self._jit(name, step, in_shardings=shardings)
        # double-buffered staging (ISSUE 12): a usable prestaged column
        # set (same bucket, issued while the PREVIOUS job occupied the
        # device lane) skips the host put entirely — its transfers are
        # already in flight or landed
        use_prestaged = (
            prestaged is not None
            and vk_lanes is None
            and prestaged.usable(b, self.mesh is not None)
        )
        if prestaged is not None:
            from .. import metrics

            metrics.engine_prestage_total.add(
                outcome="hit" if use_prestaged else "fallback"
            )
            if not use_prestaged:
                prestaged.discard()
        if not use_prestaged:
            raw_args = (nonce_lanes, public_parts, meas, proof, blind0)
            if vk_lanes is not None:
                raw_args = (vk_lanes,) + raw_args
            args = pad_args(b, *raw_args)

        # conversions block on device execution — keep inside the span.
        # out0 stays ON DEVICE (DeviceRows) for the later aggregate;
        # seed0/ver0/part0 are needed host-side for the wire round trip.
        # Whole device region watchdog-supervised (see _helper_init_inner).
        def device_call():
            _engine_dispatch_failpoint()
            with span(
                "engine.leader_init",
                vdaf=self.inst.kind,
                batch=n,
                bucket=b,
                coalesced=coalesced,
                prestaged=bool(use_prestaged),
            ):
                with span("engine.leader_init.put", vdaf=self.inst.kind, bucket=b):
                    if use_prestaged:
                        staged = prestaged.take()  # transfers already in flight
                        jax.block_until_ready(staged)
                    else:
                        staged = put_args(args, block=True, shardings=shardings)
                t_disp = time.monotonic()
                with span("engine.leader_init.dispatch", vdaf=self.inst.kind):
                    out0, seed0, ver0, part0 = fn(*staged)
                self._record_dispatch(
                    "leader_init", n, b, time.monotonic() - t_disp,
                    compile_key=(name, b),
                )
                with span("engine.leader_init.fetch_seed", vdaf=self.inst.kind, bucket=b):
                    seed0 = np.asarray(seed0)[:n] if seed0 is not None else None
                with span("engine.leader_init.fetch_ver", vdaf=self.inst.kind, bucket=b):
                    ver0 = tuple(np.asarray(x)[:n] for x in ver0)
                with span("engine.leader_init.fetch_part", vdaf=self.inst.kind, bucket=b):
                    part0 = np.asarray(part0)[:n] if part0 is not None else None
                count_d2h((seed0, ver0, part0))
            return out0, seed0, ver0, part0

        try:
            out0, seed0, ver0, part0 = self._supervised("leader_init", device_call)
        except Exception as e:
            _annotate_dispatch_bucket(e, b)
            raise
        return DeviceRows(out0, n), seed0, ver0, part0

    def prestage_leader(self, nonce_lanes, public_parts, meas, proof, blind0):
        """Double-buffered host->device staging: issue the padded column
        uploads ASYNC now (typically from the pipeline's read stage,
        while the device lane runs the previous job's dispatch) and hand
        back a PrestagedInit for leader_init to consume. Returns None
        when the direct-dispatch path can't use it (host fallback /
        quarantine, chunked past the HBM cap, or the big-batch pipelined
        path which stages its own chunks)."""
        if self._host() is not None:
            return None
        n = nonce_lanes.shape[0]
        cap = self.bucket_cap
        if cap is not None and n > cap:
            return None
        if self.mesh is None and n >= 2 * self.PIPELINE_CHUNK:
            return None
        b = bucket_size(n, cap)
        L = len(meas)
        shardings = None
        if self.mesh is not None:
            meas_nd = "vec2" if self.sp > 1 else 2
            shardings = self._shard(
                2,
                None if public_parts is None else 3,
                (meas_nd,) * L,
                (2,) * L,
                None if blind0 is None else 2,
            )
        args = pad_args(b, nonce_lanes, public_parts, meas, proof, blind0)
        staged = put_args(args, block=False, shardings=shardings)
        return PrestagedInit(b, staged, self.mesh is not None)

    def _leader_init_chunked(
        self, nonce_lanes, public_parts, meas, proof, blind0, cap: int, vk_lanes=None
    ):
        """Serial cap-sized leader inits for a batch past the HBM bound.
        Unlike _leader_init_pipelined, chunk k+1's transfer is NOT
        staged while chunk k computes — bounding resident bytes is the
        whole point. Outputs merge exactly like the pipelined path."""
        n = nonce_lanes.shape[0]
        outs, seeds, vers, parts = [], [], [], []
        for s in range(0, n, cap):
            e = min(s + cap, n)
            out0, seed0, ver0, part0 = self._leader_init_inner(
                _cut_rows(nonce_lanes, s, e),
                _cut_rows(public_parts, s, e),
                _cut_rows(meas, s, e),
                _cut_rows(proof, s, e),
                _cut_rows(blind0, s, e),
                allow_pipeline=False,
                vk_lanes=_cut_rows(vk_lanes, s, e),
            )
            outs.append(out0)
            seeds.append(seed0)
            vers.append(ver0)
            parts.append(part0)
        seed = np.concatenate(seeds) if seeds[0] is not None else None
        ver = tuple(
            np.concatenate([v[i] for v in vers]) for i in range(len(vers[0]))
        )
        part = np.concatenate(parts) if parts[0] is not None else None
        return DeviceRowsChunks(outs), seed, ver, part

    def _leader_init_pipelined(self, nonce_lanes, public_parts, meas, proof, blind0):
        """Chunked leader init: every chunk's device transfer is issued
        immediately (async, all in flight), then chunks dispatch in
        order — chunk k's compute overlaps chunk k+1..'s H2D. Outputs
        are host-concatenated; out shares stay device-resident as
        DeviceRowsChunks."""
        import jax

        from ..trace import span

        p3 = self.p3
        n = nonce_lanes.shape[0]
        C = self.PIPELINE_CHUNK

        def step(nonce_lanes, public_parts, meas, proof, blind0):
            return p3.prepare_init_leader(
                self.verify_key, nonce_lanes, public_parts, meas, proof, blind0
            )

        fn = self._jit("leader_init", step)

        spans_ = [(s, min(s + C, n)) for s in range(0, n, C)]

        # one supervised region for the whole pipeline: every chunk's
        # block_until_ready/dispatch/fetch can park on a wedged device
        # the dominant chunk bucket labels the put/fetch spans of the
        # whole pipelined pass (the tail chunk may pad to a smaller
        # bucket; its share of the one put/fetch span can't be split
        # out)
        chunk_b = bucket_size(min(n, C))

        def device_call():
            _engine_dispatch_failpoint()
            with span("engine.leader_init", vdaf=self.inst.kind, batch=n, pipelined=len(spans_)):
                staged = []
                with span(
                    "engine.leader_init.put_all_async", vdaf=self.inst.kind, bucket=chunk_b
                ):
                    for s, e in spans_:
                        args = pad_args(
                            bucket_size(e - s),
                            _cut_rows(nonce_lanes, s, e),
                            _cut_rows(public_parts, s, e),
                            _cut_rows(meas, s, e),
                            _cut_rows(proof, s, e),
                            _cut_rows(blind0, s, e),
                        )
                        staged.append(put_args(args, block=False))
                outs = []
                for k, ((s, e), args) in enumerate(zip(spans_, staged)):
                    with span("engine.leader_init.chunk", k=k, rows=e - s, vdaf=self.inst.kind):
                        jax.block_until_ready(args)  # this chunk's H2D only
                        t_disp = time.monotonic()
                        outs.append(fn(*args))
                        self._record_dispatch(
                            "leader_init", e - s, bucket_size(e - s),
                            time.monotonic() - t_disp,
                        )
                with span("engine.leader_init.fetch", vdaf=self.inst.kind, bucket=chunk_b):
                    out_chunks = [
                        DeviceRows(o[0], e - s) for (s, e), o in zip(spans_, outs)
                    ]
                    seed0 = (
                        np.concatenate(
                            [np.asarray(o[1])[: e - s] for (s, e), o in zip(spans_, outs)]
                        )
                        if outs[0][1] is not None
                        else None
                    )
                    L = len(outs[0][2])
                    ver0 = tuple(
                        np.concatenate(
                            [np.asarray(o[2][i])[: e - s] for (s, e), o in zip(spans_, outs)]
                        )
                        for i in range(L)
                    )
                    part0 = (
                        np.concatenate(
                            [np.asarray(o[3])[: e - s] for (s, e), o in zip(spans_, outs)]
                        )
                        if outs[0][3] is not None
                        else None
                    )
            return DeviceRowsChunks(out_chunks), seed0, ver0, part0

        try:
            return self._supervised("leader_init", device_call)
        except Exception as exc:
            _annotate_dispatch_bucket(exc, bucket_size(min(n, C)))
            raise

    # --- masked aggregate over the batch axis ---
    def aggregate(self, out_shares, mask):
        """Masked aggregate with the same OOM recovery as the init
        steps. After a host fallback, rows produced by the host engine
        (plain limb tuples) aggregate on host; device-resident rows
        from before the fallback are fetched and aggregated on host."""
        while True:
            host = self._host()
            if host is not None:
                if isinstance(out_shares, (DeviceRows, DeviceRowsChunks)):
                    # fetching a buffer resident on a possibly-wedged
                    # device is itself a device wait: supervise it, so
                    # a hung fetch steps the job back instead of
                    # parking the host path unbounded
                    rows = self._supervised("fetch_resident", out_shares.to_numpy)
                    return host.aggregate(rows, np.asarray(mask))
                return host.aggregate(out_shares, mask)
            try:
                return self._aggregate_inner(out_shares, mask)
            except Exception as e:  # noqa: BLE001 - OOM filter inside
                if (
                    is_oom_error(e)
                    and getattr(e, "_janus_fixed_bucket", False)
                    and isinstance(out_shares, (DeviceRows, DeviceRowsChunks))
                ):
                    # A resident buffer re-dispatches at its own fixed
                    # bucket no matter the cap, so halving can't help —
                    # fetch and reduce THIS buffer on host instead of
                    # abandoning the device path engine-wide for an OOM
                    # specific to one oversized buffer.
                    log.warning(
                        "device OOM aggregating a fixed-bucket resident "
                        "buffer for %s; reducing it on host: %s",
                        self.inst.kind, e,
                    )
                    host = HostEngineCache(self.inst, self.verify_key)
                    return host.aggregate(out_shares.to_numpy(), np.asarray(mask))
                n = getattr(out_shares, "n", None) or np.asarray(mask).shape[0]
                self._handle_engine_error(e, n)

    def _aggregate_inner(self, out_shares, mask):
        p3 = self.p3

        if isinstance(out_shares, DeviceRowsChunks):
            # chunked out shares: per-chunk masked reduce, host merge
            p = p3.jf.MODULUS
            total = None
            off = 0
            for chunk in out_shares.chunks:
                part = self._aggregate_inner(chunk, np.asarray(mask)[off : off + chunk.n])
                off += chunk.n
                total = part if total is None else [
                    (a + b) % p for a, b in zip(total, part)
                ]
            return total

        def step(out_shares, mask):
            return p3.aggregate(out_shares, mask)

        fn = self._jit("aggregate", step)
        if isinstance(out_shares, DeviceRows):
            # device-resident path: the out shares are already on device
            # padded to their bucket — only the (tiny) mask moves.
            n = out_shares.n
            value = out_shares.value
            b = value[0].shape[0]
            vb = bucket_size(n)
            s = out_shares.offset
            if (s or vb < b) and s + vb <= b:
                # coalesced view: one jitted dynamic-slice + masked
                # reduce over the job's own bucket — reducing the whole
                # merged buffer once per co-batched job would multiply
                # the aggregate work by the round size. (Views whose
                # bucket would run past the buffer keep the full-width
                # mask path below: dynamic_slice clamps out-of-bounds
                # starts, which would silently shift rows.)
                def step_view(value, start, mask, _vb=vb):
                    v = tuple(
                        jax.lax.dynamic_slice_in_dim(x, start, _vb, axis=0)
                        for x in value
                    )
                    return p3.aggregate(v, mask)

                jit_name = f"aggregate_view_{vb}"
                fnv = self._jit(jit_name, step_view)
                mask_vb = np.zeros(vb, dtype=bool)
                mask_vb[:n] = np.asarray(mask, dtype=bool)
                count_h2d(int(mask_vb.nbytes))
                dispatch_b, dispatch_fixed = vb, True
                dispatch = lambda: fnv(value, np.int32(s), mask_vb)  # noqa: E731
            else:
                jit_name = "aggregate"
                full = np.zeros(b, dtype=bool)
                full[s : s + n] = np.asarray(mask, dtype=bool)
                count_h2d(int(full.nbytes))
                dispatch_b, dispatch_fixed = b, True
                dispatch = lambda: fn(value, full)  # noqa: E731
        else:
            n = mask.shape[0]
            cap = self.bucket_cap
            if cap is not None and n > cap:
                # host-staged rows past the HBM cap: cap-sized partial
                # reduces merged mod p on host
                p = p3.jf.MODULUS
                total = None
                for s in range(0, n, cap):
                    e = min(s + cap, n)
                    part = self._aggregate_inner(
                        _cut_rows(out_shares, s, e), np.asarray(mask)[s:e]
                    )
                    total = part if total is None else [
                        (a + b) % p for a, b in zip(total, part)
                    ]
                return total
            b = bucket_size(n, cap)
            jit_name = "aggregate"
            dispatch_b, dispatch_fixed = b, False
            host_args = pad_args(b, out_shares, mask)
            count_h2d(host_args)
            dispatch = lambda: fn(*host_args)  # noqa: E731
        from ..trace import span

        # PJRT raises allocation failures synchronously from the
        # dispatch; other device errors realize async at the fetch.
        # Both need the bucket annotation, so both live in this try.
        # to_ints forces the fetch, so the span bounds true device
        # wall time, not async dispatch. Watchdog-supervised: the fetch
        # is exactly where a wedged device parks the thread.
        def device_call():
            _engine_dispatch_failpoint()
            t_disp = time.monotonic()
            with span(
                "engine.aggregate.dispatch",
                vdaf=self.inst.kind,
                batch=n,
                bucket=dispatch_b,
            ):
                agg = dispatch()
                result = [int(x) for x in p3.jf.to_ints(agg)]
                count_d2h(len(result) * p3.jf.LIMBS * 8)
            self._record_dispatch(
                "aggregate", n, dispatch_b, time.monotonic() - t_disp,
                compile_key=(jit_name, dispatch_b),
            )
            return result

        try:
            return self._supervised("aggregate", device_call)
        except Exception as e:
            _annotate_dispatch_bucket(e, dispatch_b, fixed=dispatch_fixed)
            raise

    def aggregate_sparse(self, out_shares, mask, flat_idx):
        """Masked sparse aggregate: scatter-add every accepted report's
        blocks into a dense logical accumulator and fetch it — the
        classic-path analogue of the resident scatter-merge (helper
        accumulate and the resident-disabled leader land here). An OOM
        degrades to a host scatter over fetched rows instead of failing
        the job; other errors propagate like aggregate's."""
        from .. import metrics

        host = self._host()
        if host is not None:
            if isinstance(out_shares, (DeviceRows, DeviceRowsChunks)):
                rows = self._supervised("fetch_resident", out_shares.to_numpy)
                return host.aggregate_sparse(rows, np.asarray(mask), flat_idx)
            return host.aggregate_sparse(out_shares, mask, flat_idx)
        p3 = self.p3
        L = p3.circ.agg_output_len
        accept = np.asarray(mask, bool)
        idx = np.where(
            accept[:, None], np.asarray(flat_idx, np.int32), np.int32(L)
        ).astype(np.int32)
        n = idx.shape[0]
        n_rows = int(accept.sum())
        live = int((idx < L).sum())

        def device_call():
            _engine_dispatch_failpoint()
            t_disp = time.monotonic()
            acc = self._scatter_dispatch(self._zeros_row(L), out_shares, idx)
            result = [int(x) for x in p3.jf.to_ints(acc)]
            count_d2h(len(result) * p3.jf.LIMBS * 8)
            self._record_dispatch(
                "aggregate",
                n,
                bucket_size(n),
                time.monotonic() - t_disp,
                manifest_op="scatter_merge",
                compile_key=("scatter_merge", bucket_size(n)),
            )
            metrics.engine_scatter_rows_total.add(n_rows, vdaf=self.inst.kind)
            self._scatter_rows += n_rows
            if n_rows:
                occ = live / (n_rows * idx.shape[1])
                self._sparse_last_occupancy = occ
                metrics.engine_sparse_block_occupancy.set(occ, vdaf=self.inst.kind)
            return result

        try:
            return self._supervised("aggregate_sparse", device_call)
        except Exception as e:
            if not is_oom_error(e):
                _annotate_dispatch_bucket(e, bucket_size(n), fixed=True)
                raise
            log.warning(
                "sparse aggregate OOM at bucket %d; scattering on host",
                bucket_size(n),
                exc_info=True,
            )
            rows = (
                self._supervised("fetch_resident", out_shares.to_numpy)
                if isinstance(out_shares, (DeviceRows, DeviceRowsChunks))
                else out_shares
            )
            return _host_scatter_rows(p3.jf, rows, idx, L)

    # --- device-resident aggregate state (ISSUE 12; docs/ARCHITECTURE.md
    # "Resident aggregate state"). The engine owns the per-(task, batch
    # bucket) buffers and the device ops; the DRIVER owns flush policy
    # (interval / eviction / quarantine / drain all go through its
    # write-tx path — aggregation_job_driver.flush_resident_state). ---

    # process-wide device-byte bound on resident buffers; overflow
    # evicts this engine's LRU slots through the flush path. Env is the
    # operator override; janus_main applies the YAML `engine:` value.
    RESIDENT_MAX_BYTES = int(os.environ.get("JANUS_RESIDENT_MAX_BYTES", str(256 << 20)))

    def resident_ready(self) -> bool:
        """True while the device path serves. Resident accumulation is
        a device feature: under host fallback / quarantine the driver
        uses the classic per-job flush, so interim work is durable
        immediately (the quarantine-mid-job contract)."""
        return self._host() is None

    def _delta_shardings(self, ndim: int = 2):
        """out_shardings for pending-delta values ([kk, output_len], or
        [output_len] rows when ndim=1): the out-share COLUMNS shard over
        'sp' when the engine has a vector axis, so the resident
        accumulator lives sharded per device — scatter merges stay
        sharded and the gather happens only at the flush/take fetch
        (the parallel/api.py design note, now on the serving path).
        Engines without a vector axis keep the delta replicated; None on
        the single-device path."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        out_len = getattr(self.p3.circ, "output_len", 0)
        col = "sp" if (self.sp > 1 and out_len % self.sp == 0) else None
        spec = P(*((None,) * (ndim - 1) + (col,)))
        sh = NamedSharding(self.mesh, spec)
        return tuple(sh for _ in range(self.p3.jf.LIMBS))

    def aggregate_pending(self, out_shares, bucket_idx, k: int, flat_idx=None):
        """Per-bucket masked sums of one job's out shares as a DEVICE
        [k, output_len] value — ONE dispatch, one [n] int32 upload,
        nothing fetched (the classic path uploads a full n-bool mask
        and fetches the aggregate per bucket). k pads to the next power
        of two so the traced program specializes O(log k) times.
        Errors propagate: the driver falls back to the classic
        accumulate for OOM-class failures and steps back on hangs.

        `flat_idx` ([n, compact_len] int32 scatter targets) marks a
        block-sparse job: no device work happens here — the per-bucket
        scatter into the dense logical accumulator runs at merge time
        (SparsePendingDeltas explains why a compact pre-sum is wrong)."""
        p3 = self.p3
        if flat_idx is not None:
            L = p3.circ.agg_output_len
            return SparsePendingDeltas(
                out_shares,
                np.asarray(flat_idx, np.int32),
                np.asarray(bucket_idx, np.int32),
                k,
                L * p3.jf.LIMBS * 8,
                L,
            )
        kk = 1 << max(0, int(k - 1).bit_length())
        row_nbytes = p3.circ.output_len * p3.jf.LIMBS * 8

        n_rows = len(bucket_idx)

        def device_call():
            _engine_dispatch_failpoint()
            t_disp = time.monotonic()
            value = self._pending_dispatch(out_shares, np.asarray(bucket_idx, np.int32), kk)
            self._record_dispatch(
                "aggregate",
                n_rows,
                bucket_size(n_rows),
                time.monotonic() - t_disp,
                manifest_op="aggregate_pending",
                # the traced program specializes on the padded bucket
                # COUNT kk (agg_buckets_{kk}), not just the row bucket
                compile_key=("aggregate_pending", kk, bucket_size(n_rows)),
            )
            return value

        try:
            value = self._supervised("aggregate_pending", device_call)
        except Exception as e:
            _annotate_dispatch_bucket(e, kk, fixed=True)
            raise
        return PendingDeltas(value, k, row_nbytes)

    def _pending_dispatch(self, out_shares, bucket_idx, kk: int):
        p3 = self.p3
        if isinstance(out_shares, DeviceRowsChunks):
            total = None
            off = 0
            for chunk in out_shares.chunks:
                part = self._pending_dispatch(
                    chunk, bucket_idx[off : off + chunk.n], kk
                )
                off += chunk.n
                total = part if total is None else p3.jf.add(total, part)
            return total
        if isinstance(out_shares, DeviceRows):
            n = out_shares.n
            value = out_shares.value
            b = value[0].shape[0]
            vb = bucket_size(n)
            s = out_shares.offset
            if (s or vb < b) and s + vb <= b:
                # coalesced view: dynamic-slice the job's own bucket
                # (same window discipline as the aggregate view path)
                idx = np.full(vb, -1, np.int32)
                idx[:n] = bucket_idx

                def step_view(value, start, idx, _vb=vb, _kk=kk):
                    v = tuple(
                        jax.lax.dynamic_slice_in_dim(x, start, _vb, axis=0)
                        for x in value
                    )
                    return p3.aggregate_buckets(v, idx, _kk)

                fn = self._jit(
                    f"agg_buckets_view_{kk}_{vb}",
                    step_view,
                    out_shardings=self._delta_shardings(),
                )
                count_h2d(int(idx.nbytes))
                return fn(value, np.int32(s), idx)
            idx = np.full(b, -1, np.int32)
            idx[s : s + n] = bucket_idx

            def step_full(value, idx, _kk=kk):
                return p3.aggregate_buckets(value, idx, _kk)

            fn = self._jit(
                f"agg_buckets_{kk}", step_full, out_shardings=self._delta_shardings()
            )
            count_h2d(int(idx.nbytes))
            return fn(value, idx)
        # host limb rows (a round that degraded to host currency):
        # stage them — rare, and still one dispatch for all buckets
        n = bucket_idx.shape[0]
        bb = bucket_size(n)
        idx = np.full(bb, -1, np.int32)
        idx[:n] = bucket_idx
        (padded,) = pad_args(bb, out_shares)
        count_h2d((padded, idx))

        def step_host(value, idx, _kk=kk):
            return p3.aggregate_buckets(value, idx, _kk)

        fn = self._jit(
            f"agg_buckets_{kk}", step_host, out_shardings=self._delta_shardings()
        )
        return fn(padded, idx)

    def _resident_add(self, acc, row):
        """acc + row on device. Single-device: the accumulator buffer
        is DONATED so the merge is in place (no HBM growth per merge);
        CPU ignores donation, mesh dispatches ride the single-controller
        lane via _jit and keep the slot's column sharding — the merged
        accumulator never gathers until flush."""
        if self.mesh is not None:
            fn = self._jit(
                "resident_add",
                lambda a, r: self.p3.jf.add(a, r),
                out_shardings=self._delta_shardings(ndim=1),
            )
            return fn(acc, row)
        name = "resident_add"
        if name not in self._jits:
            p3 = self.p3
            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._jits[name] = jax.jit(
                lambda a, r: p3.jf.add(a, r), donate_argnums=donate
            )
        return self._jits[name](acc, row)

    # --- block-sparse scatter-merge (ISSUE 17; docs/ARCHITECTURE.md
    # "Block-sparse aggregation"): verified reports' compact blocks
    # scatter-add into the dense logical accumulator by their PUBLIC
    # block indices. Sparse engines are single-device (see __init__). ---

    def _zeros_row(self, length: int):
        """Fresh dense logical accumulator: a zero field row on device."""
        return tuple(
            jnp.zeros(length, dtype=jnp.uint64) for _ in range(self.p3.jf.LIMBS)
        )

    def _scatter_fn(self):
        """Jitted scatter-add of per-report compact blocks into a dense
        [logical_len] accumulator (the ISSUE 17 headline kernel —
        vdaf.prio3_jax.scatter_rows). The accumulator is DONATED on
        real devices so repeated merges into one slot stay in place;
        jax.jit respecializes per (bucket, compact_len, logical_len)
        shape on its own."""
        name = "scatter_merge"
        if name not in self._jits:
            p3 = self.p3
            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._jits[name] = jax.jit(
                lambda acc, values, idx: p3.scatter_rows(acc, values, idx),
                donate_argnums=donate,
            )
        return self._jits[name]

    def _scatter_dispatch(self, acc, out_shares, idx):
        """Scatter-add every report row of `out_shares` whose idx row is
        live into acc. idx: [n, compact_len] host int32, sentinel =
        logical_len drops a lane. Handles the three out-share
        currencies like _pending_dispatch; padding rows inside a device
        bucket get all-sentinel idx rows so their garbage never lands."""
        L = acc[0].shape[0]
        fn = self._scatter_fn()
        if isinstance(out_shares, DeviceRowsChunks):
            off = 0
            for chunk in out_shares.chunks:
                acc = self._scatter_dispatch(acc, chunk, idx[off : off + chunk.n])
                off += chunk.n
            return acc
        if isinstance(out_shares, DeviceRows):
            n = out_shares.n
            value = out_shares.value
            b = value[0].shape[0]
            s = out_shares.offset
            full = np.full((b, idx.shape[1]), np.int32(L), np.int32)
            full[s : s + n] = idx
            count_h2d(int(full.nbytes))
            return fn(acc, value, full)
        # host limb rows (a round that degraded to host currency)
        n = idx.shape[0]
        bb = bucket_size(n)
        (padded,) = pad_args(bb, out_shares)
        full = np.full((bb, idx.shape[1]), np.int32(L), np.int32)
        full[:n] = idx
        count_h2d((padded, full))
        return fn(acc, padded, full)

    def _sparse_slot_value(self, slot, deltas: "SparsePendingDeltas", j: int):
        """Scatter-add bucket j's report blocks into the slot's dense
        logical accumulator (zeros for a fresh slot / a raw delta
        fetch). One device dispatch, recorded as a scatter_merge
        specialization; feeds the scatter metrics."""
        from .. import metrics

        L = deltas.logical_len
        sel = deltas.bucket_idx == j
        idx = np.where(sel[:, None], deltas.flat_idx, np.int32(L)).astype(np.int32)
        acc = self._zeros_row(L) if slot is None else slot.value
        n_rows = int(sel.sum())
        live = int((idx < L).sum())
        t_disp = time.monotonic()
        value = self._scatter_dispatch(acc, deltas.out_shares, idx)
        self._record_dispatch(
            "aggregate",
            n_rows,
            bucket_size(len(sel)),
            time.monotonic() - t_disp,
            manifest_op="scatter_merge",
            compile_key=("scatter_merge", bucket_size(len(sel))),
        )
        metrics.engine_scatter_rows_total.add(n_rows, vdaf=self.inst.kind)
        self._scatter_rows += n_rows
        if n_rows:
            occ = live / (n_rows * deltas.flat_idx.shape[1])
            self._sparse_last_occupancy = occ
            metrics.engine_sparse_block_occupancy.set(occ, vdaf=self.inst.kind)
        return value

    def resident_merge(self, entries, deltas: PendingDeltas) -> list[dict]:
        """Merge one job's committed deltas into the resident slots.

        entries: [(key, delta_row, report_count, interval)] — call only
        AFTER the job's write transaction committed (the post-commit
        discipline that makes a failed/retried step unable to
        double-merge: an uncommitted PendingDeltas is simply dropped).
        Returns flush records for slots LRU-evicted past
        RESIDENT_MAX_BYTES — already fetched and removed from device
        state; the caller MUST persist them through the write-tx path.
        """
        from ..messages import Interval

        sparse = isinstance(deltas, SparsePendingDeltas)
        evicted: list[ResidentSlot] = []
        merged: set = set()
        with self._resident_lock:
            try:
                for key, j, rows, interval in entries:
                    slot = self._resident.get(key)
                    if sparse:
                        # scatter-merge: blocks land straight in the
                        # (fresh or existing) dense logical accumulator
                        value = self._sparse_slot_value(slot, deltas, j)
                    if slot is None:
                        slot = ResidentSlot(
                            key,
                            value if sparse else deltas.row(j),
                            interval,
                            rows,
                            deltas.row_nbytes,
                        )
                        self._resident[key] = slot
                        _resident_bytes_add(slot.nbytes, self.inst.kind, +1)
                    else:
                        slot.value = (
                            value
                            if sparse
                            else self._resident_add(slot.value, deltas.row(j))
                        )
                        slot.interval = Interval.merged(slot.interval, interval)
                        slot.rows += rows
                        self._resident.move_to_end(key)
                    slot.last_used = time.monotonic()
                    self._resident_stats["merged_rows"] += rows
                    merged.add(key)
            except BaseException as e:
                # a mid-loop failure leaves a merged PREFIX on device —
                # report exactly which keys landed so the caller flushes
                # only the remainder (re-flushing a merged entry's delta
                # would double-count it when the slot later flushes)
                raise ResidentMergeError(frozenset(merged), e) from e
            self._resident_stats["merges"] += 1
            while resident_bytes_total() > self.RESIDENT_MAX_BYTES and self._resident:
                _, slot = self._resident.popitem(last=False)
                _resident_bytes_add(-slot.nbytes, self.inst.kind, -1)
                evicted.append(slot)
                self._resident_stats["evictions"] += 1
            if not evicted:
                return []
            try:
                return self._fetch_slots_locked(evicted)
            except BaseException:
                for slot in evicted:  # restore: eviction must not LOSE state
                    self._resident[slot.key] = slot
                    _resident_bytes_add(slot.nbytes, self.inst.kind, +1)
                # the DELTAS all merged — raising here would make the
                # caller's merge-failed recovery re-flush them (double
                # count). The eviction is merely DEFERRED: bytes stay
                # over the cap, the next merge/flusher pass retries.
                self._resident_stats["eviction_deferred"] += 1
                log.warning(
                    "resident eviction fetch failed for %s; eviction deferred "
                    "(state restored, retried next pass)",
                    self.inst.kind,
                    exc_info=True,
                )
                return []

    def resident_take(self, keys=None) -> list[dict]:
        """Pop (all, or `keys`) resident slots and fetch their encoded
        shares for a flush. On a fetch failure every popped slot is
        RESTORED and the error propagates — resident state is never
        dropped because the device was slow once; the flusher retries
        after the canary restores the path."""
        with self._resident_lock:
            take = (
                list(self._resident.keys())
                if keys is None
                else [k for k in keys if k in self._resident]
            )
            slots = [self._resident.pop(k) for k in take]
            for slot in slots:
                _resident_bytes_add(-slot.nbytes, self.inst.kind, -1)
            if not slots:
                return []
            try:
                recs = self._fetch_slots_locked(slots)
            except BaseException:
                for slot in slots:
                    self._resident[slot.key] = slot
                    _resident_bytes_add(slot.nbytes, self.inst.kind, +1)
                raise
            self._resident_stats["takes"] += len(slots)
            return recs

    def fetch_delta_records(self, entries, deltas) -> list[dict]:
        """Supervised d2h fetch of a job's raw delta rows — the driver's
        merge-failed recovery path. Bounded like every other resident
        fetch: a raw to_ints() here would park the commit worker in
        native code forever on exactly the wedged device that likely
        just failed the merge. Sparse deltas scatter into a zero dense
        logical row first (the flush currency is always dense)."""
        p3 = self.p3
        sparse = isinstance(deltas, SparsePendingDeltas)

        def fetch():
            out = []
            for key, j, rows, interval in entries:
                value = (
                    self._sparse_slot_value(None, deltas, j)
                    if sparse
                    else deltas.row(j)
                )
                out.append(
                    {
                        "key": key,
                        "share": [int(x) for x in p3.jf.to_ints(value)],
                        "rows": rows,
                        "interval": interval,
                    }
                )
            return out

        recs = self._supervised("resident_delta_fetch", fetch)
        count_d2h(deltas.row_nbytes * len(entries))
        return recs

    def _fetch_slots_locked(self, slots: list) -> list[dict]:
        """Supervised d2h fetch of popped slots (callers hold
        _resident_lock; a watchdog-abandoned fetch raises back to them
        with the lock released by their unwind)."""
        p3 = self.p3

        def fetch():
            out = []
            for slot in slots:
                out.append(
                    {
                        "key": slot.key,
                        "share": [int(x) for x in p3.jf.to_ints(slot.value)],
                        "rows": slot.rows,
                        "interval": slot.interval,
                    }
                )
            return out

        recs = self._supervised("resident_fetch", fetch)
        count_d2h(sum(slot.nbytes for slot in slots))
        return recs

    def has_resident(self) -> bool:
        """True while unflushed resident slots live on this engine —
        the process engine-cache LRU must not evict such an engine (the
        flusher only walks CACHED engines; dropping one silently loses
        the share bytes and leaks the resident-bytes ledger)."""
        with self._resident_lock:
            return bool(self._resident)

    def would_coalesce(self, n: int) -> bool:
        """True when a leader init of n rows would enter a coalesced
        round (the _leader_init_entry routing predicate). A prestage
        for such a job is wasted whenever the round MERGES — the merged
        round re-stages from concatenated host columns — so a parallel
        device lane declines prestaging exactly these jobs."""
        cap = self.bucket_cap
        return bool(
            self._coalesce
            and n <= self.COALESCE_MAX_JOB
            and (cap is None or n <= cap)
        )

    def resident_status(self) -> dict:
        with self._resident_lock:
            out = {
                "vdaf": self.inst.kind,
                "buffers": len(self._resident),
                "bytes": sum(s.nbytes for s in self._resident.values()),
                **dict(self._resident_stats),
            }
            if self.sparse:
                circ = self.p3.circ
                out["sparse"] = {
                    "logical_length": circ.logical_length,
                    "block_size": circ.block_size,
                    "max_blocks": circ.max_blocks,
                    "scatter_rows": self._scatter_rows,
                    "block_occupancy": self._sparse_last_occupancy,
                }
            return out


def _host_scatter_rows(jf, rows, idx, L: int) -> list[int]:
    """Host scatter-add over fetched [n, compact_len] limb rows — the
    OOM degrade for EngineCache.aggregate_sparse. idx carries the same
    sentinel convention as the device kernel (>= L drops the lane)."""
    vals = jf.to_ints(tuple(np.asarray(r) for r in rows))
    p = jf.MODULUS
    agg = [0] * L
    n, cm = idx.shape
    for i in range(n):
        for c in range(cm):
            fx = int(idx[i, c])
            if 0 <= fx < L:
                agg[fx] = (agg[fx] + int(vals[i, c])) % p
    return agg


class _HostP3:
    """Duck-typed `.p3` for HostEngineCache (callers use engine.p3.jf
    for the columnar codecs)."""

    def __init__(self, jf):
        self.jf = jf


class HostEngineCache:
    """Per-report host engine for draft-mode (spec-framing) tasks.

    Same surface as EngineCache but loops reports through the scalar
    host Prio3 — mirroring the reference's own per-report CPU loop
    (aggregation_job_driver.rs:329-402, aggregator.rs:1775-1826). The
    TPU engine only implements the fast framing; conformant tasks trade
    throughput for cross-implementation compatibility.
    """

    def __init__(self, inst: VdafInstance, verify_key: bytes):
        from ..vdaf.engine import jf_for
        from ..vdaf.registry import circuit_for, prio3_host

        self.inst = inst
        self.verify_key = verify_key
        self.host = prio3_host(inst)
        self.circ = circuit_for(inst)
        self.jf = jf_for(self.circ)
        self.p3 = _HostP3(self.jf)

    # --- lane <-> host-int conversions ---
    def _row_ints(self, limbs, i) -> list[int]:
        if len(limbs) == 1:
            return [int(x) for x in np.asarray(limbs[0])[i]]
        lo = np.asarray(limbs[0])[i]
        hi = np.asarray(limbs[1])[i]
        return [int(l) | (int(h) << 64) for l, h in zip(lo, hi)]

    def _ints_to_limbs(self, rows: list[list[int] | None], n: int):
        batch = len(rows)
        out = tuple(np.zeros((batch, n), dtype=np.uint64) for _ in range(self.jf.LIMBS))
        for i, r in enumerate(rows):
            if r is None:
                continue
            for j, v in enumerate(r):
                out[0][i, j] = np.uint64(v & 0xFFFFFFFFFFFFFFFF)
                if self.jf.LIMBS == 2:
                    out[1][i, j] = np.uint64(v >> 64)
        return out

    @staticmethod
    def _row_bytes(lanes, i) -> bytes:
        return np.asarray(lanes, dtype="<u8")[i].tobytes()

    def helper_init(self, nonce_lanes, public_parts, helper_seeds, blinds, ver0, part0, ok_mask):
        from ..vdaf.reference import HelperShare, PrepShare, VdafError

        n = nonce_lanes.shape[0]
        uses_jr = self.host.uses_joint_rand
        out_rows: list[list[int] | None] = [None] * n
        accept = np.zeros(n, dtype=bool)
        prep_msg = np.zeros((n, 2), dtype=np.uint64)
        for i in range(n):
            if not ok_mask[i]:
                continue
            nonce = self._row_bytes(nonce_lanes, i)
            share = HelperShare(
                self._row_bytes(helper_seeds, i),
                self._row_bytes(blinds, i) if uses_jr else None,
            )
            parts = (
                [self._row_bytes(public_parts[:, 0], i), self._row_bytes(public_parts[:, 1], i)]
                if uses_jr
                else []
            )
            try:
                state1, ps1 = self.host.prepare_init(
                    self.verify_key, 1, nonce, parts, share
                )
                ps0 = PrepShare(
                    self._row_ints(ver0, i),
                    self._row_bytes(part0, i) if uses_jr else None,
                )
                msg = self.host.prepare_shares_to_prep([ps0, ps1])
                self.host.prepare_next(state1, msg)
            except VdafError:
                continue
            out_rows[i] = state1.out_share
            accept[i] = True
            if uses_jr:
                prep_msg[i] = np.frombuffer(msg, dtype="<u8")
        out1 = self._ints_to_limbs(out_rows, self.circ.output_len)
        return out1, accept, prep_msg

    def leader_init(
        self, nonce_lanes, public_parts, meas, proof, blind0, ok=None, prestaged=None
    ):
        from ..vdaf.reference import LeaderShare

        if prestaged is not None:
            # signature parity with EngineCache: the pipeline's
            # device_init passes prestaged= unconditionally; a host
            # engine has no device path, so free the transfer's buffers
            prestaged.discard()

        n = nonce_lanes.shape[0]
        uses_jr = self.host.uses_joint_rand
        out_rows: list[list[int] | None] = [None] * n
        ver_rows: list[list[int] | None] = [None] * n
        seed0 = np.zeros((n, 2), dtype=np.uint64) if uses_jr else None
        part0 = np.zeros((n, 2), dtype=np.uint64) if uses_jr else None
        for i in range(n):
            if ok is not None and not ok[i]:
                continue  # don't pay scalar FLP prepare for failed lanes
            nonce = self._row_bytes(nonce_lanes, i)
            share = LeaderShare(
                self._row_ints(meas, i),
                self._row_ints(proof, i),
                self._row_bytes(blind0, i) if uses_jr else None,
            )
            parts = (
                [self._row_bytes(public_parts[:, 0], i), self._row_bytes(public_parts[:, 1], i)]
                if uses_jr
                else []
            )
            state, ps = self.host.prepare_init(self.verify_key, 0, nonce, parts, share)
            out_rows[i] = state.out_share
            ver_rows[i] = ps.verifier_share
            if uses_jr:
                seed0[i] = np.frombuffer(state.corrected_joint_rand_seed, dtype="<u8")
                part0[i] = np.frombuffer(ps.joint_rand_part, dtype="<u8")
        out0 = self._ints_to_limbs(out_rows, self.circ.output_len)
        ver0 = self._ints_to_limbs(ver_rows, self.circ.verifier_len)
        return out0, seed0, ver0, part0

    def aggregate(self, out_shares, mask):
        p = self.circ.FIELD.MODULUS
        agg = [0] * self.circ.output_len
        for i in range(mask.shape[0]):
            if not mask[i]:
                continue
            row = self._row_ints(out_shares, i)
            agg = [(a + b) % p for a, b in zip(agg, row)]
        return agg

    def aggregate_sparse(self, out_shares, mask, flat_idx):
        """Host scatter-add of accepted reports' compact rows into a
        dense logical aggregate (same contract as the device
        EngineCache.aggregate_sparse)."""
        p = self.circ.FIELD.MODULUS
        L = getattr(self.circ, "agg_output_len", self.circ.output_len)
        agg = [0] * L
        idx = np.asarray(flat_idx)
        for i in range(mask.shape[0]):
            if not mask[i]:
                continue
            row = self._row_ints(out_shares, i)
            for v, fx in zip(row, idx[i]):
                fx = int(fx)
                if 0 <= fx < L:
                    agg[fx] = (agg[fx] + int(v)) % p
        return agg


def _build_engine(inst: VdafInstance, verify_key: bytes):
    if inst.xof_mode != "fast":
        # draft (VDAF-07) framing: device engine for every circuit
        # whose sponge streams fit vdaf.draft_jax MAX_STREAM_BLOCKS
        # (160k since r5 — covers the north-star len=100k; the r4
        # "latency knee" was a flat-scan pathology); truly huge streams keep the scalar host loop
        try:
            prio3_batched(inst)
        except ValueError:
            from ..metrics import engine_backend_state

            for s in EngineCache.BACKEND_STATES:
                engine_backend_state.set(
                    1.0 if s == "host" else 0.0, vdaf=inst.kind, state=s
                )
            return HostEngineCache(inst, verify_key)
    return EngineCache(inst, verify_key)


# LRU over live engines. Formerly a bare functools.lru_cache; the
# hand-rolled variant exists so hit/miss/entry counts export as
# metrics and /statusz can walk the live engines (bucket caps, backend
# state, OOM history) — lru_cache hides its table.
_ENGINE_CACHE_MAX = 256
_engine_cache_lock = threading.Lock()
_engine_cache: "OrderedDict[tuple, object]" = OrderedDict()


def engine_cache(inst: VdafInstance, verify_key: bytes):
    from .. import metrics

    key = (inst, verify_key)
    with _engine_cache_lock:
        eng = _engine_cache.get(key)
        if eng is not None:
            _engine_cache.move_to_end(key)
            metrics.engine_cache_hits.add()
            return eng
    metrics.engine_cache_misses.add()
    # build outside the lock: construction touches jax (mesh setup) and
    # must not serialize against lookups; a concurrent double-build
    # resolves first-insert-wins below
    eng = _build_engine(inst, verify_key)
    with _engine_cache_lock:
        cur = _engine_cache.get(key)
        if cur is not None:
            return cur
        _engine_cache[key] = eng
        while len(_engine_cache) > _ENGINE_CACHE_MAX:
            # evict the oldest entry that holds NO resident aggregate
            # state: the flusher only walks cached engines, so dropping
            # one with live slots silently loses the share bytes and
            # leaks its bytes in the resident ledger forever
            victim = None
            for k, e in _engine_cache.items():
                if not (isinstance(e, EngineCache) and e.has_resident()):
                    victim = k
                    break
            if victim is None:
                # every entry holds unflushed state (bounded by
                # RESIDENT_MAX_BYTES): keep them all until a flush
                # pass drains one, then the next insert evicts
                break
            _engine_cache.pop(victim)
        metrics.engine_cache_entries.set(float(len(_engine_cache)))
    return eng


def _engine_cache_clear() -> None:
    from .. import metrics

    global _resident_bytes_total
    with _engine_cache_lock:
        _engine_cache.clear()
    # shared cross-task coalescers, the mesh dispatch lane's counters
    # and the resident byte ledger follow the cache lifetime (tests
    # clear between modules for isolation)
    _clear_shared_coalescers()
    _MESH_QUEUE.reset_for_tests()
    with _resident_bytes_lock:
        _resident_bytes_total = 0
        kinds = list(_resident_buffer_counts)
        _resident_buffer_counts.clear()
    metrics.engine_resident_bytes.set(0.0)
    for kind in kinds:
        metrics.engine_resident_buffers.set(0.0, vdaf=kind)
    metrics.engine_cache_entries.set(0.0)


def live_engines() -> list["EngineCache"]:
    """Live DEVICE engines in the process cache (host engines hold no
    resident state) — the resident flusher/drain walk this."""
    with _engine_cache_lock:
        return [e for e in _engine_cache.values() if isinstance(e, EngineCache)]


def shutdown_engines(timeout_s: float = 2.0) -> None:
    """Process-teardown: stop every live engine's canary loop (bounded)
    so no probe's native device work races interpreter finalization.
    Called from janus_main's finally, before the watchdog drain."""
    with _engine_cache_lock:
        engines = list(_engine_cache.values())
    for eng in engines:
        stop = getattr(eng, "stop_canary", None)
        if stop is not None:
            try:
                stop(timeout_s)
            except Exception:
                log.exception("stopping canary for %s failed", eng.inst.kind)


# lru_cache-compatible surface (tests/conftest.py calls cache_clear
# between modules to drop compiled callables)
engine_cache.cache_clear = _engine_cache_clear


def engine_cache_status() -> dict:
    """Live engine-cache snapshot for /statusz: per-engine bucket cap,
    backend state, geometry, and recent OOM history."""
    with _engine_cache_lock:
        engines = list(_engine_cache.values())
    out = []
    for eng in engines:
        if isinstance(eng, HostEngineCache):
            out.append(
                {
                    "vdaf": eng.inst.kind,
                    "xof_mode": eng.inst.xof_mode,
                    "backend": "host",
                }
            )
            continue
        ent = {
            "vdaf": eng.inst.kind,
            "xof_mode": eng.inst.xof_mode,
            "backend": eng._backend_state(),
            "quarantined": eng._quarantined,
            "bucket_cap": eng.bucket_cap,
            "initial_bucket_cap": eng._initial_bucket_cap,
            "dp": eng.dp,
            "sp": eng.sp,
            "tile_elems": eng.tile_elems,
            "coalesce_round_rows": eng._co_leader._max_rows,
            "cross_task_coalesce": XTASK_COALESCE,
            "resident": eng.resident_status(),
            "oom_history": list(eng.oom_history),
        }
        try:
            from ..vdaf.engine import describe_engine_geometry

            ent["geometry"] = describe_engine_geometry(eng.p3.bc)
        except Exception:
            pass
        out.append(ent)
    doc = {"entries": len(engines), "max_entries": _ENGINE_CACHE_MAX, "engines": out}
    if engines:
        # an engine has initialized the backend, so reading the kernel
        # gate triggers no device discovery
        from ..ops import keccak_pallas

        doc["pallas"] = keccak_pallas.status()
    return doc


def resident_accumulators_status() -> dict:
    """/statusz `resident_accumulators` section: process-wide resident
    aggregate state (bytes, per-engine buffer counts, merge/eviction/
    flush-take counters)."""
    with _engine_cache_lock:
        engines = list(_engine_cache.values())
    device_engines = [e for e in engines if not isinstance(e, HostEngineCache)]
    return {
        "total_bytes": resident_bytes_total(),
        "max_bytes": EngineCache.RESIDENT_MAX_BYTES,
        "cross_task_coalesce": XTASK_COALESCE,
        # block-sparse rollup (ISSUE 17): scatter-merge activity across
        # every sparse engine — scrape_check pins this line's presence
        "sparse": {
            "engines": sum(1 for e in device_engines if getattr(e, "sparse", False)),
            "scatter_rows": sum(getattr(e, "_scatter_rows", 0) for e in device_engines),
        },
        "engines": [eng.resident_status() for eng in device_engines],
    }


def mesh_status() -> dict:
    """/statusz `mesh` section: device topology, per-engine (dp, sp)
    geometry, and the single-controller dispatch lane's live counters
    (scripts/scrape_check.py pins the shape)."""
    with _engine_cache_lock:
        engines = [e for e in _engine_cache.values() if isinstance(e, EngineCache)]
    # the topology an engine saw when it was built — a bare statusz
    # probe must not pay (or trigger) device discovery
    devices = engines[0]._ndev if engines else None
    return {
        "devices": devices,
        "queue": _MESH_QUEUE.status(),
        "engines": [
            {
                "vdaf": e.inst.kind,
                "dp": e.dp,
                "sp": e.sp,
                "mesh": e.mesh is not None,
                "sharded_resident": e.sp > 1,
                "fallback_reason": e.mesh_fallback_reason,
            }
            for e in engines
        ],
    }


from ..statusz import register_status_provider as _register_status_provider

_register_status_provider("engine_cache", engine_cache_status)
_register_status_provider("resident_accumulators", resident_accumulators_status)
_register_status_provider("mesh", mesh_status)
