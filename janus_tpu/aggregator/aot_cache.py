"""Serialized-executable AOT cache (ISSUE 14; docs/ARCHITECTURE.md
"Cold-start and prewarm").

The persistent XLA compile cache only skips the *XLA compile*; a
restarted process still pays Python tracing + lowering per jit
specialization, which measures 3-6 s per histogram-class program on
the CPU bench — most of a warm boot. This layer closes that gap: the
first cold dispatch of a specialization compiles through jax's AOT
path (`jit.lower(args).compile()`), SERIALIZES the compiled executable
(`jax.experimental.serialize_executable`) to disk, and every later
process — the boot prewarm, a restarted driver, a canary rebuild —
deserializes it in ~tens-to-hundreds of milliseconds with no trace at
all. Deserialized executables are the same compiled bytes, so results
are bit-identical by construction (pinned by test).

Keying: blobs are named by a digest over (jax version, backend
platform + device count, the HOST target-machine fingerprint — CPU
feature flags, see below — the engine identity — vdaf config + a
verify key digest, since single-task programs close over the key as a
trace constant — the jit variant name and the module name lowered
from it, the mesh geometry
`(dp, sp, device count)` for mesh programs, and the argument avals
(shape + dtype tree)). Anything the digest misses — a jax upgrade
changing the wire format, a corrupted blob — surfaces as a
deserialization error: the blob is deleted and the call falls back to
the plain jit, so the cache can only ever cost a cold compile, never
correctness.

Cross-machine poison (MULTICHIP_r05, rc 124): XLA:CPU AOT executables
embed the COMPILE machine's CPU features ("Target machine feature
+prefer-no-gather is not supported on the host machine"), and a blob
compiled elsewhere could stall the loader rather than raise cleanly.
Two defenses: the host fingerprint in the digest means a foreign blob
is never even looked up, and each blob carries the writer's
fingerprint, checked BEFORE the native deserialize — a mismatch
deletes the blob and falls back to the jit without ever entering the
loader.

Scope: single-device AND mesh jits (mesh digests carry their
(dp, sp, device count) geometry, so a blob only loads on its own
topology), and only while ARMED (janus_main arms it next to the
compile cache; bare tests/bench processes see byte-identical behavior
to before).
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading

log = logging.getLogger(__name__)

_lock = threading.Lock()
# serializes every AOT serialization compile: the XLA-compilation-cache
# disable below mutates process-global jax config
_compile_flag_lock = threading.Lock()
_ARMED: dict = {"dir": None}
_STATS = {"loads": 0, "saves": 0, "errors": 0, "bytes_saved": 0}

BLOB_SUFFIX = ".jaxexe"
# disk bound: a production deployment's distinct specializations are
# few (O(ops x buckets x tasks) with STABLE verify keys), but test/
# chaos harnesses mint random keys per run, so a shared cache dir
# accumulates dead blobs — at the cap, saves trim the oldest-mtime
# blobs first (dead keys age out, live ones stay warm)
MAX_BLOBS = 256


def arm(directory: str) -> None:
    """Enable the AOT executable cache at `directory` (created
    lazily). janus_main calls this beside enable_compile_cache."""
    with _lock:
        _ARMED["dir"] = os.path.expanduser(directory)


def disarm() -> None:
    with _lock:
        _ARMED["dir"] = None


def armed_dir() -> str | None:
    return _ARMED["dir"]


def stats() -> dict:
    """O(1) counter snapshot (no directory scan) — the prewarm loop
    diffs this per warmed entry."""
    with _lock:
        return dict(_STATS)


def status() -> dict:
    """The `aot` slice of the /statusz engine_prewarm section."""
    d = _ARMED["dir"]
    blobs = blob_bytes = 0
    if d:
        try:
            with os.scandir(d) as it:
                for ent in it:
                    if ent.name.endswith(BLOB_SUFFIX):
                        blobs += 1
                        try:
                            blob_bytes += ent.stat().st_size
                        except OSError:
                            pass
        except OSError:
            pass
    with _lock:
        stats = dict(_STATS)
    return {"enabled": d is not None, "dir": d, "blobs": blobs, "blob_bytes": blob_bytes, **stats}


def reset_for_tests() -> None:
    with _lock:
        _ARMED["dir"] = None
        _STATS.update(loads=0, saves=0, errors=0, bytes_saved=0)


def _bump(key: str, n: int = 1) -> None:
    with _lock:
        _STATS[key] = _STATS.get(key, 0) + n


def _leaf_sig(x) -> str:
    if x is None:
        return "N"
    if isinstance(x, (bytes, bool, int, float)):
        return repr(x)[:64]
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        raise TypeError(f"unsupported AOT arg leaf {type(x).__name__}")
    return f"{tuple(shape)}:{dtype}"


def _args_sig(args) -> str:
    parts = []
    for a in args:
        if isinstance(a, (tuple, list)):
            parts.append("(" + ",".join(_args_sig((x,)) for x in a) + ")")
        else:
            parts.append(_leaf_sig(a))
    return "|".join(parts)


_HOST_FP: str | None = None


def host_fingerprint() -> str:
    """Digest of the host's target-machine identity: architecture plus
    the CPU feature flags XLA:CPU bakes into AOT executables. Part of
    every blob digest AND stored inside each blob (checked before the
    native deserialize) — the MULTICHIP_r05 cross-machine poison fix."""
    global _HOST_FP
    if _HOST_FP is None:
        import platform

        parts = [platform.system(), platform.machine()]
        flags = ""
        try:
            with open("/proc/cpuinfo", encoding="utf-8", errors="replace") as f:
                for line in f:
                    # x86 "flags", arm64 "Features" — first hit is the
                    # boot CPU; features are uniform across cores on
                    # the machines we serve from
                    if line.lower().startswith(("flags", "features")):
                        flags = " ".join(sorted(line.split(":", 1)[1].split()))
                        break
        except OSError:
            flags = platform.processor() or ""
        parts.append(flags)
        _HOST_FP = hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]
    return _HOST_FP


def engine_base(
    inst_dict: dict,
    verify_key: bytes,
    name: str,
    mesh: tuple[int, int, int] | None = None,
) -> str:
    """Digest base identifying one engine's jit variant across
    processes (see the module docstring for what it must cover).
    `mesh` is the (dp, sp, device count) geometry for mesh programs —
    a blob must only ever load on its own topology."""
    import json

    import jax

    return "|".join(
        (
            jax.__version__,
            jax.default_backend(),
            str(len(jax.local_devices())),
            host_fingerprint(),
            json.dumps(inst_dict, sort_keys=True, separators=(",", ":")),
            hashlib.sha256(verify_key).hexdigest()[:16],
            name,
            # the lowered module's name (EngineCache._jit): blobs from
            # before programs were named (all `jit_step`) never load
            "module:jit_" + name,
            "mesh:%dx%d/%d" % mesh if mesh is not None else "single",
        )
    )


class AotJit:
    """Wraps one engine jit: per argument-aval specialization, load a
    serialized executable if one exists, else compile via the AOT path
    and serialize it for the next process. Falls back to the wrapped
    jit on ANY cache trouble — including a blob that deserializes but
    faults on its first execution."""

    __slots__ = ("_jitted", "_base", "_loaded", "_lock", "_sig_locks")

    def __init__(self, jitted, base: str):
        self._jitted = jitted
        self._base = base
        self._loaded: dict[str, object] = {}
        self._lock = threading.Lock()
        # per-signature first-call locks: concurrent first callers of
        # the SAME specialization must not duplicate a multi-second
        # compile, but a different specialization's ~tens-of-ms blob
        # load must never queue behind one either
        self._sig_locks: dict[str, threading.Lock] = {}

    def _blob_path(self, d: str, sig: str) -> str:
        h = hashlib.sha256(f"{self._base}||{sig}".encode()).hexdigest()
        return os.path.join(d, h + BLOB_SUFFIX)

    def _drop_and_fall_back(self, sig: str, path: str | None, args):
        _bump("errors")
        self._loaded.pop(sig, None)
        if path is not None:
            try:
                os.unlink(path)
            except OSError:
                pass
        return self._jitted(*args)

    def __call__(self, *args):
        d = _ARMED["dir"]
        if d is None:
            return self._jitted(*args)
        try:
            sig = _args_sig(args)
        except TypeError:
            return self._jitted(*args)
        comp = self._loaded.get(sig)
        if comp is not None:
            try:
                return comp(*args)
            except Exception:
                # aval drift / runtime rejection: drop to the jit,
                # which re-specializes freely
                return self._drop_and_fall_back(sig, None, args)
        with self._lock:
            sig_lock = self._sig_locks.setdefault(sig, threading.Lock())
        path = self._blob_path(d, sig)
        loaded_from_disk = False
        with sig_lock:
            comp = self._loaded.get(sig)
            if comp is None:
                comp = self._try_load(path)
                loaded_from_disk = comp is not None
                if comp is None:
                    comp = self._compile_and_save(path, args)
                if comp is None:
                    return self._jitted(*args)
                self._loaded[sig] = comp
        if not loaded_from_disk:
            return comp(*args)
        try:
            return comp(*args)
        except Exception:
            # the first execution of a DESERIALIZED executable is the
            # last place a bad blob can surface (the digest + envelope
            # fingerprint catch cross-machine blobs up front, but a
            # same-machine blob can still be stale or corrupt): it
            # must cost a recompile, never a failed serving dispatch
            log.warning(
                "AOT blob %s loaded but faulted on first execution; "
                "deleting and falling back to the jit", path, exc_info=True,
            )
            return self._drop_and_fall_back(sig, path, args)

    def _try_load(self, path: str):
        from jax.experimental import serialize_executable

        try:
            with open(path, "rb") as f:
                blob = pickle.loads(f.read())
            # v2 blob envelope: the writer's host fingerprint rides
            # along and is checked BEFORE the native deserialize — a
            # foreign-machine executable must fall back here, not
            # stall inside the XLA:CPU loader (MULTICHIP_r05). A
            # legacy 3-tuple blob has no fingerprint: treat it as
            # foreign (its digest scheme is gone anyway).
            if not (isinstance(blob, dict) and blob.get("v") == 2):
                raise ValueError("legacy AOT blob envelope (no fingerprint)")
            if blob.get("fp") != host_fingerprint():
                raise ValueError(
                    f"AOT blob compiled on another machine "
                    f"(fp {blob.get('fp')!r} != host {host_fingerprint()!r})"
                )
            serialized, in_tree, out_tree = blob["payload"]
        except FileNotFoundError:
            return None
        except Exception:
            _bump("errors")
            log.warning("AOT blob %s unreadable; deleting", path, exc_info=True)
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        try:
            comp = serialize_executable.deserialize_and_load(
                serialized, in_tree, out_tree
            )
        except Exception:
            # bad blob: jax/XLA version skew, or a blob serialized
            # from an XLA-persistent-cache-HIT executable ("Symbols
            # not found" — such executables carry no JIT object code;
            # _compile_and_save forces a real compile to prevent this,
            # but blobs written before that fix may linger). Delete and
            # recompile — the cache can only cost a compile, never
            # correctness.
            _bump("errors")
            log.warning(
                "AOT blob %s failed to deserialize; deleting and recompiling",
                path, exc_info=True,
            )
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        _bump("loads")
        return comp

    def _compile_and_save(self, path: str, args):
        import jax
        from jax.experimental import serialize_executable

        try:
            # the serialization compile must be a REAL compile: an
            # executable loaded from the XLA persistent cache carries
            # no JIT object code, and serializing one yields a blob
            # that fails every later deserialize with "Symbols not
            # found" (pinned by test). The AOT blob supersedes the XLA
            # cache for this program anyway. The flag is process-GLOBAL
            # jax config — the module lock keeps a concurrent wrapper's
            # compile from racing the disable/restore window and
            # serializing a cache-hit (poisoned) executable. Accepted
            # tradeoff: an UNRELATED first compile on another thread
            # that lands inside the window skips the persistent cache
            # once and recompiles on the next restart — rare
            # (concurrent first-compiles only; mesh programs all
            # compile on the single dispatch lane, so they can't race
            # each other), self-limited, and never a correctness issue.
            with _compile_flag_lock:
                cache_was_on = bool(jax.config.jax_enable_compilation_cache)
                if cache_was_on:
                    jax.config.update("jax_enable_compilation_cache", False)
                try:
                    comp = self._jitted.lower(*args).compile()
                finally:
                    if cache_was_on:
                        jax.config.update("jax_enable_compilation_cache", True)
        except Exception:
            _bump("errors")
            return None  # caller falls back to the jit call path
        try:
            d = os.path.dirname(path)
            os.makedirs(d, exist_ok=True)
            if os.path.exists(path):
                # an in-process load fallback kept a valid blob for
                # the next restart; don't churn it
                return comp
            with os.scandir(d) as it:
                blobs = [
                    (e.stat().st_mtime, e.path)
                    for e in it
                    if e.name.endswith(BLOB_SUFFIX)
                ]
            # at the disk bound, age out the oldest blobs (dead test
            # keys) instead of refusing to cache the live one
            for _, old in sorted(blobs)[: max(0, len(blobs) - (MAX_BLOBS - 1))]:
                try:
                    os.unlink(old)
                except OSError:
                    pass
            blob = pickle.dumps(
                {
                    "v": 2,
                    "fp": host_fingerprint(),
                    "payload": serialize_executable.serialize(comp),
                }
            )
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
            _bump("saves")
            _bump("bytes_saved", len(blob))
        except Exception:
            _bump("errors")
            log.warning("AOT blob save to %s failed", path, exc_info=True)
        return comp


def wrap(jitted, base: str):
    """Wrap a plain jax.jit callable for the AOT cache. Always wraps —
    the wrapper is a no-op passthrough while disarmed — so an engine
    built before janus_main arms the cache still benefits."""
    return AotJit(jitted, base)
