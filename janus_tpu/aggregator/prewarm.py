"""AOT engine prewarm from the persisted shape manifest (ISSUE 14;
docs/ARCHITECTURE.md "Cold-start and prewarm").

`prewarm_engines` replays the shape manifest (shape_manifest.py)
against the provisioned tasks at boot: every recorded dispatch
specialization — (vdaf, op, bucket, jit variant) — is re-dispatched
with synthetic data of exactly that geometry, so the trace happens and
the persistent XLA compile cache is loaded BEFORE /readyz reports
ready. Entries are warmed highest-recorded-cost first and bounded by a
boot budget; the remainder continues on a background thread (role
`engine_warm` in the profiler catalog), so one pathological manifest
can delay readiness by at most the budget, never forever.

The same warmer serves the quarantine canary (engine_cache._canary_loop):
a restored engine's dropped executables are re-warmed from the
manifest in the canary thread, so restore means restored-to-full-speed,
not restored-to-recompile-per-dispatch.

Observability: `janus_engine_prewarm_total{outcome}` +
`janus_engine_prewarm_seconds` and the /statusz `engine_prewarm`
section (compile cache dir + file counts, manifest inventory, hit/miss
split — a "hit" is a warm whose compile landed without growing the
cache dir, i.e. a persistent-cache load).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time

from ..statusz import register_status_provider
from . import shape_manifest

log = logging.getLogger(__name__)

DEFAULT_BOOT_BUDGET_S = 30.0

# module state behind the /statusz `engine_prewarm` section; always
# well-formed, even in a process that never prewarms
_state_lock = threading.Lock()
_STATE: dict = {
    "state": "idle",  # idle | running | ready | done | disabled
    "warmed": 0,
    "cache_hits": 0,
    "cache_misses": 0,
    "failed": 0,
    "unsupported": 0,
    "no_task": 0,
    "deferred": 0,
    "geometry_mismatch": 0,
    "boot_budget_s": None,
    "priority_elapsed_s": None,
}
_COMPILE_CACHE: dict = {"enabled": False, "dir": None}


def note_compile_cache(cache_dir: str | None) -> None:
    """Record the live persistent-compile-cache directory for the
    statusz section (binary_utils.enable_compile_cache calls this)."""
    with _state_lock:
        _COMPILE_CACHE["enabled"] = cache_dir is not None
        _COMPILE_CACHE["dir"] = cache_dir


def _cache_dir_stats() -> tuple[int, int]:
    """(files, bytes) in the compile cache dir (0, 0 when unknown)."""
    d = _COMPILE_CACHE.get("dir")
    if not d:
        return 0, 0
    files = total = 0
    try:
        with os.scandir(os.path.expanduser(d)) as it:
            for ent in it:
                try:
                    if ent.is_file():
                        files += 1
                        total += ent.stat().st_size
                except OSError:
                    continue
    except OSError:
        return 0, 0
    return files, total


def _bump(outcome: str, n: int = 1) -> None:
    from .. import metrics

    metrics.engine_prewarm_total.add(n, outcome=outcome)
    with _state_lock:
        if outcome in (
            "warmed",
            "failed",
            "unsupported",
            "no_task",
            "deferred",
            "geometry_mismatch",
        ):
            key = outcome
            _STATE[key] = _STATE.get(key, 0) + n


def engine_prewarm_status() -> dict:
    """The /statusz `engine_prewarm` section: compile cache state,
    manifest inventory and the prewarm outcome counts."""
    files, nbytes = _cache_dir_stats()
    with _state_lock:
        state = dict(_STATE)
        cache = dict(_COMPILE_CACHE)
    cache["files"] = files
    cache["bytes"] = nbytes
    from . import aot_cache

    return {
        "compile_cache": cache,
        "aot": aot_cache.status(),
        "manifest": shape_manifest.manifest_status(),
        "prewarm": state,
    }


register_status_provider("engine_prewarm", engine_prewarm_status)


def reset_for_tests() -> None:
    with _state_lock:
        _STATE.update(
            state="idle",
            warmed=0,
            cache_hits=0,
            cache_misses=0,
            failed=0,
            unsupported=0,
            no_task=0,
            deferred=0,
            boot_budget_s=None,
            priority_elapsed_s=None,
        )


# ---------------------------------------------------------------------------
# Warming one recorded specialization. The warmer re-dispatches through
# the ENGINE's own entry points (never raw jax.jit), so the compiled
# program is byte-for-byte the one serving traffic will use — warm
# results are bit-identical to cold ones by construction, and the
# dispatch feeds the same cost ledger / manifest choke points.
# ---------------------------------------------------------------------------


def _tile_rows(a, n: int):
    """Broadcast a 1-row staged arg (array / field-limb tuple / None /
    bytes) to n rows along the leading (report) axis."""
    import numpy as np

    if a is None or isinstance(a, (bytes, int)):
        return a
    if isinstance(a, tuple):
        return tuple(_tile_rows(x, n) for x in a)
    a = np.asarray(a)
    return np.repeat(a, n, axis=0)


class _Warmer:
    """Per-run context: generates ONE synthetic report per engine and
    TILES it to each target row count. Compiled programs depend only on
    shapes, never values, so a duplicated row is as good as n distinct
    reports — and it skips the per-report host share generation that
    would otherwise dominate a warm boot (measured: the difference
    between a ~30 s and a <10 s warm restart at 20 manifest entries).
    Leader-init outputs are cached per (engine, rows) so helper/
    aggregate entries reuse the leader leg instead of re-dispatching
    it."""

    def __init__(self):
        self._base: dict[int, tuple] = {}
        self._batches: dict[tuple, tuple] = {}

    def _rows_for_bucket(self, bucket: int) -> int:
        # smallest n whose jit bucket is `bucket` — minimal staged
        # bytes for the same compiled program
        return bucket // 2 + 1

    def _batch(self, eng, n: int):
        from ..vdaf.testing import zero_report_batch

        key = (id(eng), n)
        got = self._batches.get(key)
        if got is None:
            base = self._base.get(id(eng))
            if base is None:
                # host-built zero reports: no client shard compiles here
                base = zero_report_batch(eng.inst, 1)
                self._base[id(eng)] = base
            args = tuple(_tile_rows(a, n) for a in base)
            got = self._batches[key] = (args, {})
        return got

    def _leader_out(self, eng, n: int):
        """leader_init outputs at rows n (cached per engine+n)."""
        args, cache = self._batch(eng, n)
        if "leader" not in cache:
            nonce, parts, meas, proof, blind0, _, _ = args
            cache["leader"] = eng._leader_init_inner(
                nonce, parts, meas, proof, blind0, allow_pipeline=False
            )
        return args, cache["leader"]

    def warm(self, eng, entry: dict) -> str:
        """Warm one manifest entry on `eng`; returns the outcome."""
        import numpy as np

        from .engine_cache import MIN_BUCKET, DeviceRows, HostEngineCache, bucket_size

        if isinstance(eng, HostEngineCache) or eng._host() is not None:
            return "unsupported"  # nothing to compile on the host path
        key = [str(k) if not isinstance(k, (int, float)) else k for k in entry.get("key") or ()]
        # a specialization recorded under a different mesh topology is
        # a DIFFERENT program: replaying it here would trace something
        # serving never dispatches and burn the boot budget on it
        # (e.g. a single-device boot reading a (dp=4, sp=1) manifest,
        # or a pod reading a laptop's) — skip, distinctly counted
        from .shape_manifest import entry_geometry

        recorded = entry_geometry(key)
        current = (
            (eng.dp, eng.sp, eng._ndev) if eng.mesh is not None else None
        )
        if recorded != current:
            return "geometry_mismatch"
        variant = str(key[0]) if key else str(entry.get("op", ""))
        bucket = int(entry.get("bucket", 0))
        if bucket < max(MIN_BUCKET, eng.dp) or (
            eng.bucket_cap is not None and bucket > eng.bucket_cap
        ):
            return "unsupported"
        n = self._rows_for_bucket(bucket)
        vk_lanes = None
        if variant.endswith("_vk"):
            vk_lanes = np.ascontiguousarray(
                np.broadcast_to(
                    np.frombuffer(eng.verify_key, dtype="<u8").astype(np.uint64),
                    (n, 2),
                )
            )
        if variant in ("leader_init", "leader_init_vk"):
            args, _ = self._batch(eng, n)
            nonce, parts, meas, proof, blind0, _, _ = args
            eng._leader_init_inner(
                nonce, parts, meas, proof, blind0,
                allow_pipeline=False, vk_lanes=vk_lanes,
            )
            return "warmed"
        if variant in ("helper_init", "helper_init_vk"):
            args, (out0, seed0, ver0, part0) = self._leader_out(eng, n)
            nonce, parts, _, _, _, hseed, blind1 = args
            ok = np.ones(n, dtype=bool)
            part0_l = (
                part0 if part0 is not None else np.zeros((n, 2), dtype=np.uint64)
            )
            eng._helper_init_inner(
                nonce, parts, hseed, blind1, ver0, part0_l, ok, vk_lanes=vk_lanes
            )
            return "warmed"
        if variant == "aggregate":
            _, (out0, _, _, _) = self._leader_out(eng, n)
            eng.aggregate(out0, np.ones(n, dtype=bool))
            return "warmed"
        if variant.startswith("aggregate_view_"):
            try:
                vb = int(variant.rsplit("_", 1)[1])
            except ValueError:
                return "unsupported"
            if vb < MIN_BUCKET or bucket_size(vb) != vb:
                return "unsupported"
            # a view needs a buffer WIDER than its own bucket: stage a
            # leader init at 2*vb rows, aggregate a vb-row view of it
            n2 = self._rows_for_bucket(2 * vb)
            _, (out_big, _, _, _) = self._leader_out(eng, n2)
            if not isinstance(out_big, DeviceRows):
                return "unsupported"
            view = DeviceRows(out_big.value, min(vb, out_big.n), offset=0)
            eng.aggregate(view, np.ones(view.n, dtype=bool))
            return "warmed"
        if variant == "aggregate_pending":
            kk = int(key[1]) if len(key) > 1 else 1
            _, (out0, _, _, _) = self._leader_out(eng, n)
            idx = (np.arange(n, dtype=np.int32) % max(1, kk)).astype(np.int32)
            eng.aggregate_pending(out0, idx, max(1, kk))
            return "warmed"
        if variant == "scatter_merge":
            # block-sparse scatter-add into a dense logical accumulator
            # (ISSUE 17): the program specializes on (row bucket,
            # compact_len, logical_len) — replay with every compact
            # lane live, which traces the same shapes serving uses
            if not getattr(eng, "sparse", False):
                return "unsupported"
            cm = eng.p3.circ.output_len
            _, (out0, _, _, _) = self._leader_out(eng, n)
            flat = np.tile(np.arange(cm, dtype=np.int32), (n, 1))
            eng.aggregate_sparse(out0, np.ones(n, dtype=bool), flat)
            return "warmed"
        return "unsupported"


def _vdaf_key(d: dict) -> str:
    return json.dumps(dict(d), sort_keys=True, separators=(",", ":"))


def _warm_one(warmer: _Warmer, eng, entry: dict) -> str:
    from .. import metrics
    from . import aot_cache

    aot0 = aot_cache.stats()  # O(1) counters, no directory scan
    t0 = time.monotonic()
    try:
        outcome = warmer.warm(eng, entry)
    except Exception:
        log.warning(
            "prewarm of %s failed", entry.get("key"), exc_info=True
        )
        outcome = "failed"
    elapsed = time.monotonic() - t0
    if outcome == "warmed":
        metrics.engine_prewarm_seconds.observe(elapsed)
        # hit/miss: an AOT executable load is the canonical warm hit,
        # an AOT save the canonical cold miss; without AOT activity
        # (disarmed, or a specialization already live in _jits) call a
        # sub-second warm a hit and anything slower a miss — the only
        # signal left once neither cache moved
        aot1 = aot_cache.stats()
        with _state_lock:
            if aot1["loads"] > aot0["loads"]:
                _STATE["cache_hits"] += 1
            elif aot1["saves"] > aot0["saves"] or elapsed >= 1.0:
                _STATE["cache_misses"] += 1
            else:
                _STATE["cache_hits"] += 1
    _bump(outcome)
    return outcome


def prewarm_engines(
    ds,
    manifest: "shape_manifest.ShapeManifest | None" = None,
    boot_budget_s: float = DEFAULT_BOOT_BUDGET_S,
    ready_event: "threading.Event | None" = None,
    background_remainder: bool = True,
) -> dict:
    """Replay the shape manifest against the provisioned tasks.

    Warms entries highest-recorded-cost first until `boot_budget_s` of
    wall time is spent; the remainder (counted `deferred`) continues on
    a daemon thread so readiness is never hostage to a long tail.
    Returns a summary dict (also reflected in the /statusz
    `engine_prewarm` section). `ready_event`, when given, is set the
    moment the priority (in-budget) set is warm — the `engine_prewarm`
    readiness check keys off it."""
    from .engine_cache import engine_cache

    manifest = manifest if manifest is not None else shape_manifest.installed()
    t0 = time.monotonic()
    entries = manifest.entries() if manifest is not None else []
    summary = {"entries": len(entries), "warmed": 0, "deferred": 0}
    with _state_lock:
        _STATE["state"] = "running" if entries else "done"
        _STATE["boot_budget_s"] = boot_budget_s
    if not entries:
        if ready_event is not None:
            ready_event.set()
        with _state_lock:
            _STATE["priority_elapsed_s"] = 0.0
        summary["priority_elapsed_s"] = 0.0
        return summary

    tasks = ds.run_tx(lambda tx: tx.get_tasks(), "prewarm_list_tasks")
    by_vdaf: dict[str, list] = {}
    for task in tasks:
        if task.vdaf.kind.startswith("fake") or task.vdaf.kind == "poplar1":
            continue
        by_vdaf.setdefault(_vdaf_key(task.vdaf.to_dict()), []).append(task)

    jobs: list[tuple[dict, object]] = []
    for entry in entries:
        matched = by_vdaf.get(_vdaf_key(entry.get("vdaf") or {}))
        if not matched:
            _bump("no_task")
            continue
        for task in matched:
            jobs.append((entry, task))

    warmer = _Warmer()
    remainder: list[tuple[dict, object]] = []
    deferred_oversize: list[tuple[dict, object]] = []
    for i, (entry, task) in enumerate(jobs):
        elapsed = time.monotonic() - t0
        if elapsed > boot_budget_s:
            remainder = jobs[i:]
            break
        # an entry whose RECORDED cold cost alone dwarfs the whole
        # budget defers immediately: a compile cannot be preempted, so
        # starting it would hold readiness far past the documented
        # bound (worst case it is a cheap cache hit we warm a little
        # later in background; worst case avoided is a 170 s compile
        # behind a 30 s budget). Budget overshoot is otherwise bounded
        # by ONE specialization's warm time.
        if float(entry.get("cost_s", 0.0)) > 2.0 * boot_budget_s:
            deferred_oversize.append((entry, task))
            continue
        eng = engine_cache(task.vdaf, task.vdaf_verify_key)
        if _warm_one(warmer, eng, entry) == "warmed":
            summary["warmed"] += 1
    remainder = deferred_oversize + remainder
    elapsed = time.monotonic() - t0
    summary["priority_elapsed_s"] = round(elapsed, 3)
    summary["deferred"] = len(remainder)
    with _state_lock:
        _STATE["state"] = "ready"
        _STATE["priority_elapsed_s"] = round(elapsed, 3)
    if ready_event is not None:
        ready_event.set()
    if remainder:
        _bump("deferred", len(remainder))
        log.info(
            "engine prewarm: %d specialization(s) warmed in %.1fs; %d deferred "
            "past the %.1fs boot budget to the background warmer",
            summary["warmed"], elapsed, len(remainder), boot_budget_s,
        )
        if background_remainder:

            def _drain():
                w = _Warmer()
                for entry, task in remainder:
                    try:
                        eng = engine_cache(task.vdaf, task.vdaf_verify_key)
                        _warm_one(w, eng, entry)
                    except Exception:
                        log.warning("background prewarm failed", exc_info=True)
                with _state_lock:
                    _STATE["state"] = "done"

            threading.Thread(
                target=_drain, name="engine-warmup-bg", daemon=True
            ).start()
    else:
        with _state_lock:
            _STATE["state"] = "done"
        log.info(
            "engine prewarm: %d specialization(s) warmed in %.1fs (budget %.1fs)",
            summary["warmed"], elapsed, boot_budget_s,
        )
    return summary


def warm_engine_from_manifest(eng, budget_s: float = 60.0, should_stop=None) -> int:
    """Re-warm ONE engine's recorded specializations (the quarantine
    canary's restore path: `_canary_probe` dropped the compiled
    executables, so without this every post-restore dispatch pays a
    re-trace — from-disk-cheap with the persistent cache, but still
    worth doing off the serving path). `should_stop` is checked
    between entries so process teardown can end the loop — a daemon
    thread dispatching native device work while the interpreter
    finalizes crashes the runtime (the stop_canary hazard). Returns
    the warmed count."""
    manifest = shape_manifest.installed()
    if manifest is None:
        return 0
    want = _vdaf_key(eng.inst.to_dict())
    warmer = _Warmer()
    warmed = 0
    t0 = time.monotonic()
    for entry in manifest.entries():
        if should_stop is not None and should_stop():
            break
        if _vdaf_key(entry.get("vdaf") or {}) != want:
            continue
        if time.monotonic() - t0 > budget_s:
            break
        if _warm_one(warmer, eng, entry) == "warmed":
            warmed += 1
    return warmed
