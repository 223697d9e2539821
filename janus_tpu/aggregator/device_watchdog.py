"""Watchdog-supervised device dispatch (docs/ROBUSTNESS.md "Device
hangs & deadlines").

`jax.block_until_ready` / a device fetch has no timeout: a wedged XLA
dispatch (device hang) parks the calling thread forever,
silently holding a job lease until TTL while the work it was doing is
already dead. The accelerator must be treated as a failable peer —
exactly like the helper behind the outbound circuit breaker.

`DispatchWatchdog.run(fn, deadline=...)` executes the device-touching
closure on a reusable worker thread and waits at most until the
caller's deadline (the ambient `core.deadline` budget: a job driver's
lease bound, a helper handler's propagated request deadline). On
expiry the dispatch is **abandoned**: the worker thread stays parked on
the hung device call (it cannot be interrupted — that is the point),
is counted in `janus_hung_dispatches_total` and the
`janus_abandoned_dispatch_threads` gauge, shows up in the /statusz
`device_watchdog` section WITH its current stack, and the caller gets
`DeviceHangError` — which the engine turns into a quarantine and the
job drivers turn into a step-back.

Abandoned threads are a leak by design (each pins a stack and whatever
device buffers its call staged), so they are capped: at
`abandoned_thread_cap` parked threads the watchdog trips **host-only
mode** — every EngineCache serves from the scalar host engine and no
further device dispatches are attempted — because a device that has
eaten that many threads is not coming back on its own.

Disarmed cost (no ambient deadline — tests, bench, uploads): one
contextvar read and a None check, measured by the bench --dry-run
`watchdog_overhead` record (≤ 1 µs/dispatch acceptance bound).
"""

from __future__ import annotations

import contextvars
import logging
import os
import sys
import threading
import time

from ..core.deadline import DeadlineExceeded, current_deadline

log = logging.getLogger(__name__)


class DeviceHangError(RuntimeError):
    """A supervised device dispatch exceeded its deadline and was
    abandoned. NOT an OOM: the engine's OOM ladder must not absorb it —
    it quarantines the engine and the job steps back instead."""

    def __init__(self, label: str, waited_s: float):
        super().__init__(
            f"device dispatch {label!r} abandoned after {waited_s:.3f}s "
            "(deadline exceeded; thread parked and counted)"
        )
        self.label = label
        self.waited_s = waited_s


# marks code already running ON a watchdog worker so nested supervised
# regions (chunked dispatch recursion) don't stack a second worker
_in_watchdog: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "janus_in_watchdog", default=False
)


class _Job:
    __slots__ = ("fn", "ctx", "done", "result", "exc", "lock", "abandoned", "label", "started_at")

    def __init__(self, fn, ctx, label: str):
        self.fn = fn
        self.ctx = ctx
        self.done = threading.Event()
        self.result = None
        self.exc: BaseException | None = None
        self.lock = threading.Lock()
        self.abandoned = False
        self.label = label
        self.started_at = time.monotonic()


class DispatchWatchdog:
    """One per process (module-level WATCHDOG below); engines call
    through `run`."""

    def __init__(self, abandoned_thread_cap: int = 8):
        self.abandoned_thread_cap = max(1, abandoned_thread_cap)
        self._lock = threading.Lock()
        self._idle: list = []  # idle (thread, job queue) pairs
        self._stalled: dict[int, dict] = {}  # thread ident -> info
        self._host_only = False
        self._hung_total = 0
        self._seq = 0

    # ------------------------------------------------------------------
    def host_only(self) -> bool:
        """True once the abandoned-thread cap tripped: no further
        device dispatches; engines serve from the host engine."""
        return self._host_only

    def reset_for_tests(self) -> None:
        """Drop host-only mode and forget stalled bookkeeping (parked
        threads themselves are daemons and unwind on their own)."""
        from .. import metrics

        with self._lock:
            self._host_only = False
            self._stalled.clear()
            self._idle.clear()
        metrics.abandoned_dispatch_threads.set(0.0)

    # ------------------------------------------------------------------
    def _worker_loop(self, q) -> None:
        from .. import metrics

        while True:
            job: _Job = q.get()
            try:
                result = job.ctx.run(job.fn)
                exc = None
            except BaseException as e:  # noqa: BLE001 - crosses threads
                result, exc = None, e
            ident = threading.get_ident()
            with job.lock:
                job.result, job.exc = result, exc
                abandoned = job.abandoned
                job.done.set()
            if abandoned:
                # the hung call finally returned (device recovered or
                # process unwinding): result discarded, thread retires
                with self._lock:
                    self._stalled.pop(ident, None)
                    n = len(self._stalled)
                metrics.abandoned_dispatch_threads.set(float(n))
                log.warning(
                    "abandoned dispatch %s completed after %.1fs; worker retiring",
                    job.label, time.monotonic() - job.started_at,
                )
                return
            with self._lock:
                self._idle.append((threading.current_thread(), q))

    def _checkout_worker(self):
        import queue

        with self._lock:
            if self._idle:
                return self._idle.pop()
            self._seq += 1
            seq = self._seq
        q: queue.Queue = queue.Queue(maxsize=1)
        t = threading.Thread(
            target=self._worker_loop, args=(q,), name=f"device-watchdog-{seq}", daemon=True
        )
        t.start()
        return t, q

    # ------------------------------------------------------------------
    def run(self, fn, *, deadline: float | None = None, label: str = "dispatch",
            vdaf: str = "", on_hang=None):
        """Execute `fn` under supervision.

        deadline None (or already inside a watchdog worker) = direct
        call: the disarmed path must cost nothing. Otherwise `fn` runs
        on a worker with the caller's context (trace/deadline
        contextvars propagate); past the deadline the worker is
        abandoned, `on_hang(label)` fires (the engine's quarantine
        hook) and DeviceHangError raises."""
        if deadline is None or _in_watchdog.get():
            return fn()
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise DeadlineExceeded(f"no budget left before dispatch {label!r}")
        if self._host_only:
            # engines check host_only() before dispatching; this is the
            # backstop for races around the trip
            raise DeviceHangError(label, 0.0)
        ctx = contextvars.copy_context()
        ctx.run(_in_watchdog.set, True)
        job = _Job(fn, ctx, label)
        thread, q = self._checkout_worker()
        q.put(job)
        if job.done.wait(remaining):
            if job.exc is not None:
                raise job.exc
            return job.result
        with job.lock:
            if job.done.is_set():
                # completed in the race window: not a hang
                if job.exc is not None:
                    raise job.exc
                return job.result
            job.abandoned = True
        waited = time.monotonic() - job.started_at
        self._record_hang(thread, job, vdaf, waited)
        if on_hang is not None:
            try:
                on_hang(label)
            except Exception:
                log.exception("watchdog on_hang hook failed for %s", label)
        raise DeviceHangError(label, waited)

    def _record_hang(self, thread: threading.Thread, job: _Job, vdaf: str, waited: float) -> None:
        from .. import metrics

        metrics.hung_dispatches_total.add(vdaf=vdaf, op=job.label)
        with self._lock:
            self._stalled[thread.ident] = {
                "label": job.label,
                "vdaf": vdaf,
                "thread": thread.name,
                "since": time.time(),
                "started_monotonic": job.started_at,
            }
            n = len(self._stalled)
            tripped = n >= self.abandoned_thread_cap and not self._host_only
            if tripped:
                self._host_only = True
        metrics.abandoned_dispatch_threads.set(float(n))
        self._hung_total += 1
        log.error(
            "device dispatch %s HUNG (%.3fs past its budget window); thread %s "
            "abandoned (%d/%d parked)",
            job.label, waited, thread.name, n, self.abandoned_thread_cap,
        )
        if tripped:
            log.error(
                "abandoned-dispatch cap %d reached: tripping HOST-ONLY mode — "
                "no further device dispatches this process",
                self.abandoned_thread_cap,
            )

    def drain(self, timeout_s: float = 2.0) -> bool:
        """Wait (bounded) for abandoned workers to retire — the process
        shutdown hook, called AFTER failpoints.release_hangs(): a
        daemon worker re-entering native device code while the
        interpreter finalizes segfaults the runtime, so give the woken
        workers a moment to unwind first. True when none remain."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if not self._stalled:
                    return True
            time.sleep(0.01)
        with self._lock:
            return not self._stalled

    # ------------------------------------------------------------------
    def status(self) -> dict:
        """/statusz `device_watchdog` section: counts, host-only flag,
        and a live STACK DUMP of every parked (stalled) thread — the
        first thing an operator wants when a dispatch wedges. The dump
        uses the continuous profiler's shared frame formatter
        (profiler.format_stack), so this rendering and the
        /debug/profile folded stacks cannot diverge."""
        from ..profiler import format_stack

        with self._lock:
            stalled = {ident: dict(info) for ident, info in self._stalled.items()}
            host_only = self._host_only
            hung_total = self._hung_total
        frames = sys._current_frames()
        out_stalled = []
        now = time.monotonic()
        for ident, info in sorted(stalled.items()):
            ent = {
                "label": info["label"],
                "vdaf": info["vdaf"],
                "thread": info["thread"],
                "age_s": round(now - info["started_monotonic"], 3),
            }
            frame = frames.get(ident)
            if frame is not None:
                ent["stack"] = format_stack(frame, limit=12, lineno=True)
            out_stalled.append(ent)
        return {
            "abandoned_threads": len(stalled),
            "abandoned_thread_cap": self.abandoned_thread_cap,
            "host_only": host_only,
            "hung_dispatches_total": hung_total,
            "stalled": out_stalled,
        }


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


WATCHDOG = DispatchWatchdog(
    abandoned_thread_cap=_env_int("JANUS_WATCHDOG_ABANDONED_CAP", 8)
)


def configure(abandoned_thread_cap: int | None = None) -> None:
    """Apply the YAML `device_watchdog:` knobs (janus_main); the
    JANUS_WATCHDOG_ABANDONED_CAP env var set the boot default."""
    if abandoned_thread_cap is not None:
        WATCHDOG.abandoned_thread_cap = max(1, int(abandoned_thread_cap))


def supervised(fn, *, label: str, vdaf: str = "", on_hang=None):
    """Module-level convenience: run `fn` under the process watchdog
    with the AMBIENT deadline (core.deadline contextvar). No deadline
    = direct call."""
    return WATCHDOG.run(
        fn, deadline=current_deadline(), label=label, vdaf=vdaf, on_hang=on_hang
    )


from ..statusz import register_status_provider as _register_status_provider

_register_status_provider("device_watchdog", WATCHDOG.status)
