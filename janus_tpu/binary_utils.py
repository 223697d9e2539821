"""Binary harness shared by the five processes.

Equivalent of reference aggregator/src/binary_utils.rs: `janus_main`
(config parse -> trace subscriber -> metrics -> datastore -> run),
the /healthz listener (also serving /metrics Prometheus text), and
SIGTERM -> Stopper graceful shutdown (binary_utils.rs:40-120,
docs/DEPLOYING.md:33-39).

Datastore keys come from --datastore-keys or the DATASTORE_KEYS env
var (comma-separated base64, first key is primary), matching the
reference's k8s-secret pathway.
"""

from __future__ import annotations

import argparse
import base64
import logging
import os
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from .aggregator import prewarm as prewarm_mod
from .aggregator import shape_manifest as shape_manifest_mod
from .aggregator.job_driver import Stopper
from .config import CommonConfig, load_config
from .core.time_util import RealClock
from .datastore.store import Crypter, open_datastore
from .metrics import REGISTRY
from .statusz import register_status_provider, render_statusz_html, status_snapshot
from .trace import install_trace_subscriber

log = logging.getLogger(__name__)

# Prometheus text exposition content type (version 0.0.4); the charset
# matters — label values may carry escaped non-ASCII task ids/errors.
METRICS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"
# The OpenMetrics exposition mode (?openmetrics=1 or Accept-negotiated):
# same families plus histogram exemplars and the # EOF terminator.
OPENMETRICS_CONTENT_TYPE = "application/openmetrics-text; version=1.0.0; charset=utf-8"

# GET / on the health listener: a tiny discovery page so an operator
# pointed at a port can find every endpoint from a browser (previously
# a bare 404).
_INDEX_ENDPOINTS = (
    ("/healthz", "liveness (always 200 while the process runs)"),
    ("/readyz", "readiness (503 + JSON reasons while degraded)"),
    ("/metrics", "Prometheus text exposition"),
    ("/metrics?openmetrics=1", "OpenMetrics mode with trace exemplars"),
    ("/statusz", "process status snapshot (JSON; ?format=html)"),
    ("/alertz", "SLO burn-rate engine: alert state, budgets, evidence"),
    ("/debug/vars", "raw metrics-registry JSON dump"),
    ("/debug/traces", "flight recorder: recent spans, slow traces, digests"),
    ("/debug/profile", "continuous profiler: collapsed wall-clock stacks (flamegraph.pl)"),
    ("/debug/profile?format=json", "continuous profiler: per-role self/total shares"),
    ("/debug/boot", "boot-phase timeline (process start to /readyz ready)"),
    ("/debug/flight", "telemetry flight recorder: resource history, trend slopes, leak verdicts"),
    ("/debug/ledger", "report-flow conservation ledger: per-task balance, imbalance, breaches"),
)


def _render_index() -> bytes:
    import html as _html

    rows = "".join(
        f'<li><a href="{path}"><code>{_html.escape(path)}</code></a>'
        f" — {_html.escape(desc)}</li>"
        for path, desc in _INDEX_ENDPOINTS
    )
    return (
        "<!doctype html><html><head><meta charset='utf-8'>"
        "<title>janus_tpu health listener</title>"
        "<style>body{font-family:monospace;margin:2em;}li{margin:0.3em 0;}</style>"
        "</head><body><h1>janus_tpu health listener</h1>"
        f"<ul>{rows}</ul>"
        "<p>POST /debug/profile?seconds=N opens an on-demand profiler "
        "capture window.</p></body></html>"
    ).encode()


# ---------------------------------------------------------------------------
# Readiness registry: /healthz is LIVENESS (the process is running —
# restarting it would not help), /readyz is READINESS (this replica can
# currently do useful work — take it out of rotation, don't kill it).
# A datastore outage fails readiness, never liveness: killing the pod
# would also kill the upload spill journal's replayer.
# ---------------------------------------------------------------------------

_readiness_lock = threading.Lock()
_readiness_checks: dict[str, object] = {}


def register_readiness_check(name: str, fn) -> None:
    """Register (or replace) a readiness check: `fn()` returns None
    when ready, or a human-readable reason string when not. A check
    that raises counts as not ready (with the exception as reason)."""
    with _readiness_lock:
        _readiness_checks[name] = fn


def unregister_readiness_check(name: str) -> None:
    with _readiness_lock:
        _readiness_checks.pop(name, None)


def readiness_snapshot() -> tuple[bool, dict]:
    """(ready, {check: reason}) across every registered check. No
    checks registered = ready (a binary without a datastore supervisor
    keeps its old semantics)."""
    with _readiness_lock:
        checks = dict(_readiness_checks)
    reasons: dict = {}
    for name, fn in sorted(checks.items()):
        try:
            reason = fn()
        except Exception as e:
            reason = f"readiness check failed: {type(e).__name__}: {e}"
        if reason:
            reasons[name] = str(reason)
    return not reasons, reasons


def parse_datastore_keys(raw: str) -> list[bytes]:
    keys = []
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        pad = "=" * (-len(part) % 4)
        keys.append(base64.urlsafe_b64decode(part + pad))
    if not keys:
        raise ValueError("at least one datastore key is required")
    for k in keys:
        if len(k) != 16:
            raise ValueError("datastore keys must be 16 bytes (AES-128-GCM)")
    return keys


def _split_hostport(addr: str) -> tuple[str, int]:
    host, _, port = addr.rpartition(":")
    return host or "0.0.0.0", int(port)


class BoundedThreadingHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a fixed handler pool instead of a
    thread per connection (docs/INGEST.md "Bounded serving"): accepted
    connections are served by at most `max_handler_threads` workers;
    excess connections wait in the accept backlog / pool queue rather
    than growing threads without limit. Both the DAP listener and the
    health/metrics listener use it."""

    # deep listen backlog: bursts of short-lived connections (load
    # generators, proxies that do not keep alive) otherwise overflow
    # the default 5-entry accept queue into client-visible resets
    request_queue_size = 128

    def __init__(self, addr, handler_cls, max_handler_threads: int = 32):
        import weakref
        from concurrent.futures import ThreadPoolExecutor

        super().__init__(addr, handler_cls)
        self._max_handler_threads = max(1, max_handler_threads)
        self._active_connections = 0
        self._active_lock = threading.Lock()
        # accept-time per connection (weak: entries vanish with the
        # socket) — socket objects define __slots__, so the stamp
        # cannot ride the object itself
        self._accept_times = weakref.WeakKeyDictionary()
        self._pool = ThreadPoolExecutor(
            max_workers=self._max_handler_threads, thread_name_prefix="dap-handler"
        )

    def queue_age_s(self, request) -> float | None:
        """Seconds `request` (a connection socket) waited between
        accept and the handler picking it up, once: the entry is
        consumed, so later keep-alive requests on the same connection —
        whose wait is the CLIENT's idle time, not ours — read None.
        Handlers charge this against a request's propagated deadline
        (docs/ROBUSTNESS.md deadline contract)."""
        t = self._accept_times.pop(request, None)
        return None if t is None else time.monotonic() - t

    @property
    def saturated(self) -> bool:
        """Every pool worker is occupied by a connection. Handlers use
        this to drop HTTP keep-alive (`Connection: close` after the
        in-flight response): a persistent connection pins its worker
        for the connection's lifetime, so at saturation idle-but-open
        clients would otherwise starve every later connection without
        even a 429 reaching them."""
        return self._active_connections >= self._max_handler_threads

    def process_request(self, request, client_address):
        # queue-entry stamp (docs/ROBUSTNESS.md deadline contract):
        # handlers charge the pool-queue wait against a request's
        # propagated deadline — a request that expired while queued is
        # shed before any crypto
        try:
            self._accept_times[request] = time.monotonic()
        except TypeError:  # exotic non-weakref-able socket impls
            pass
        try:
            self._pool.submit(self._process_in_pool, request, client_address)
        except RuntimeError:  # pool already shut down (server closing)
            self.shutdown_request(request)

    def _process_in_pool(self, request, client_address):
        with self._active_lock:
            self._active_connections += 1
        try:
            self.finish_request(request, client_address)
        except Exception:
            self.handle_error(request, client_address)
        finally:
            with self._active_lock:
                self._active_connections -= 1
            self.shutdown_request(request)

    def server_close(self):
        super().server_close()
        self._pool.shutdown(wait=False)


# ---------------------------------------------------------------------------
# On-demand profiler capture (POST /debug/profile?seconds=N): one
# window runs jax.profiler.trace (loadable in Perfetto/TensorBoard;
# the program's spans ride it on the device ops' clock, trace.span)
# plus a temporary host Chrome-trace writer, and answers with the
# artifact paths. Guarded: concurrent captures 409,
# the window is clamped.
# ---------------------------------------------------------------------------

PROFILE_MIN_SECONDS = 0.1
PROFILE_MAX_SECONDS = 60.0
_profile_lock = threading.Lock()


class ProfileBusy(RuntimeError):
    """A capture window is already open."""


def capture_profile(seconds: float, out_dir: str | None = None) -> dict:
    """Open a capture window of `seconds` (clamped to
    [PROFILE_MIN_SECONDS, PROFILE_MAX_SECONDS]); raises ProfileBusy if
    one is already open. Returns the artifact paths: the jax.profiler
    trace dir when the profiler starts (absent on backends without
    one), which holds the host spans and the device ops on one clock;
    the host Chrome-trace JSON always, on this process's own clock, for
    backends without a profiler."""
    import tempfile
    import time as _time

    from .trace import scoped_chrome_trace

    if not _profile_lock.acquire(blocking=False):
        raise ProfileBusy("a profile capture is already in progress")
    try:
        seconds = min(max(float(seconds), PROFILE_MIN_SECONDS), PROFILE_MAX_SECONDS)
        out_dir = out_dir or tempfile.mkdtemp(prefix="janus-profile-")
        os.makedirs(out_dir, exist_ok=True)
        host_trace = os.path.join(out_dir, "host-trace.json")
        device_dir = os.path.join(out_dir, "device")
        device_started = False
        device_error = None
        try:
            import jax

            jax.profiler.start_trace(device_dir)
            device_started = True
        except Exception as e:  # no profiler on this backend — host-only
            device_error = f"{type(e).__name__}: {e}"
        try:
            with scoped_chrome_trace(host_trace):
                _time.sleep(seconds)
        finally:
            if device_started:
                try:
                    import jax

                    jax.profiler.stop_trace()
                except Exception as e:
                    device_started = False
                    device_error = f"{type(e).__name__}: {e}"
        out = {"seconds": seconds, "host_chrome_trace": host_trace}
        if device_started:
            out["device_trace_dir"] = device_dir
        if device_error is not None:
            out["device_profiler_error"] = device_error
        return out
    finally:
        _profile_lock.release()


class HealthServer:
    """The per-process introspection listener:

      GET  /healthz                  -> 200 (liveness: always, while
                                        the process runs)
      GET  /readyz                   -> 200 when every registered
                                        readiness check passes; 503
                                        with a JSON reason map when
                                        degraded (datastore down,
                                        upload journal full)
      GET  /metrics                  -> Prometheus text exposition
      GET  /statusz                  -> JSON status snapshot (HTML with
                                        ?format=html or Accept: text/html)
      GET  /debug/vars               -> JSON dump of the metrics registry
      POST /debug/profile?seconds=N  -> on-demand profiler capture

    (reference serves /healthz from binary_utils.rs and metrics via the
    OTel Prometheus exporter, metrics.rs:53-80; statusz/debug follow
    the usual *z-page convention)."""

    def __init__(self, addr: str):
        host, port = _split_hostport(addr)

        class Handler(BaseHTTPRequestHandler):
            def _send(self, status: int, ctype: str, body: bytes) -> None:
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                import json as _json
                from urllib.parse import parse_qsl, urlsplit

                parts = urlsplit(self.path)
                query = dict(parse_qsl(parts.query))
                if parts.path == "/healthz":
                    self._send(200, "text/plain", b"")
                elif parts.path in ("/", "/index.html"):
                    self._send(200, "text/html; charset=utf-8", _render_index())
                elif parts.path == "/alertz":
                    # in-process SLO burn-rate engine state (installed
                    # by janus_main from the YAML `slo:` stanza; a
                    # process without one answers a well-formed
                    # disabled document)
                    from .slo import alertz_snapshot

                    self._send(
                        200,
                        "application/json",
                        _json.dumps(alertz_snapshot(), default=str).encode(),
                    )
                elif parts.path == "/readyz":
                    ready, reasons = readiness_snapshot()
                    body = {"ready": ready}
                    if reasons:
                        body["reasons"] = reasons
                    self._send(
                        200 if ready else 503,
                        "application/json",
                        _json.dumps(body).encode(),
                    )
                elif parts.path == "/metrics":
                    # OpenMetrics mode (exemplar syntax + # EOF) via
                    # ?openmetrics=1 or Accept negotiation; the default
                    # scrape's bytes are unaffected by stored exemplars
                    openmetrics = query.get("openmetrics") == "1" or (
                        "application/openmetrics-text"
                        in (self.headers.get("Accept") or "")
                    )
                    self._send(
                        200,
                        OPENMETRICS_CONTENT_TYPE if openmetrics else METRICS_CONTENT_TYPE,
                        REGISTRY.render(openmetrics=openmetrics).encode(),
                    )
                elif parts.path == "/statusz":
                    snap = status_snapshot()
                    wants_html = query.get("format") == "html" or "text/html" in (
                        self.headers.get("Accept") or ""
                    )
                    if wants_html:
                        self._send(
                            200,
                            "text/html; charset=utf-8",
                            render_statusz_html(snap).encode(),
                        )
                    else:
                        self._send(
                            200,
                            "application/json",
                            _json.dumps(snap, indent=2, default=str).encode(),
                        )
                elif parts.path == "/debug/vars":
                    self._send(
                        200, "application/json", _json.dumps(REGISTRY.snapshot()).encode()
                    )
                elif parts.path == "/debug/profile":
                    # always-on sampling profiler: collapsed-stack
                    # (flamegraph.pl) folded format by default, JSON
                    # role/frame shares with ?format=json (the POST
                    # form of this path remains the on-demand
                    # jax.profiler capture window)
                    from .profiler import profile_collapsed, profile_json

                    wants_json = query.get("format") == "json" or (
                        "application/json" in (self.headers.get("Accept") or "")
                    )
                    if wants_json:
                        self._send(
                            200,
                            "application/json",
                            _json.dumps(profile_json(), default=str).encode(),
                        )
                    else:
                        self._send(
                            200,
                            "text/plain; charset=utf-8",
                            profile_collapsed().encode(),
                        )
                elif parts.path == "/debug/boot":
                    # one-shot boot-phase timeline (janus_main records
                    # the phases; sums to process-start -> ready)
                    from .profiler import boot_snapshot

                    self._send(
                        200,
                        "application/json",
                        _json.dumps(boot_snapshot(), default=str).encode(),
                    )
                elif parts.path == "/debug/traces":
                    # always-on flight recorder: recent completed spans,
                    # captured slow traces, per-name latency digests
                    # (?limit=N bounds the recent list)
                    from .trace import flight_recorder

                    try:
                        limit = max(1, min(int(query.get("limit", "100")), 10_000))
                    except ValueError:
                        limit = 100
                    self._send(
                        200,
                        "application/json",
                        _json.dumps(
                            flight_recorder().snapshot(recent_limit=limit),
                            default=str,
                        ).encode(),
                    )
                elif parts.path == "/debug/flight":
                    # telemetry flight recorder: recent resource/metric
                    # history + live trend analysis (?window_secs=N
                    # narrows the judged window, ?max_points=N bounds
                    # the snapshot list)
                    from .flight_recorder import flight_document

                    try:
                        window_s = float(query["window_secs"])
                    except (KeyError, ValueError):
                        window_s = None
                    try:
                        max_points = max(1, min(int(query.get("max_points", "500")), 10_000))
                    except ValueError:
                        max_points = 500
                    self._send(
                        200,
                        "application/json",
                        _json.dumps(
                            flight_document(window_s=window_s, max_points=max_points),
                            default=str,
                        ).encode(),
                    )
                elif parts.path == "/debug/ledger":
                    # report-flow conservation ledger: latest complete
                    # per-task balance document (torn-read tolerant —
                    # the evaluator hands out the last COMPLETE doc)
                    from .ledger import ledger_document

                    self._send(
                        200,
                        "application/json",
                        _json.dumps(ledger_document(), default=str).encode(),
                    )
                else:
                    self._send(404, "text/plain", b"not found")

            def do_POST(self):  # noqa: N802
                import json as _json
                from urllib.parse import parse_qsl, urlsplit

                parts = urlsplit(self.path)
                if parts.path != "/debug/profile":
                    self._send(404, "text/plain", b"not found")
                    return
                query = dict(parse_qsl(parts.query))
                try:
                    seconds = float(query.get("seconds", "2"))
                except ValueError:
                    self._send(400, "text/plain", b"seconds must be a number")
                    return
                try:
                    result = capture_profile(seconds)
                except ProfileBusy as e:
                    self._send(
                        409,
                        "application/json",
                        _json.dumps({"error": str(e)}).encode(),
                    )
                    return
                except Exception:
                    log.exception("profile capture failed")
                    self._send(500, "text/plain", b"profile capture failed")
                    return
                self._send(200, "application/json", _json.dumps(result).encode())

            def log_message(self, fmt, *args):
                pass

        # small fixed pool: scrapes and probes are cheap, and the
        # listener must never be a thread-growth vector either
        self._srv = BoundedThreadingHTTPServer((host, port), Handler, max_handler_threads=4)
        self._thread = threading.Thread(
            target=self._srv.serve_forever, name="health-listener", daemon=True
        )

    @property
    def port(self) -> int:
        return self._srv.server_address[1]

    def start(self) -> "HealthServer":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()


def setup_signal_handler(stopper: Stopper) -> None:
    """SIGTERM/SIGINT -> cooperative stop (binary_utils.rs
    setup_signal_handler). Only callable from the main thread."""

    def handle(signum, frame):
        log.info("received signal %s, shutting down", signum)
        stopper.stop()
        # release threads parked by hang failpoints (a modeled device
        # wedge must not outlive the process's intent to exit)
        from . import failpoints

        failpoints.release_hangs()

    signal.signal(signal.SIGTERM, handle)
    signal.signal(signal.SIGINT, handle)


def enable_compile_cache(cache_dir: str | None = None) -> tuple[str | None, str]:
    """Turn on the persistent XLA compilation cache via jax.config.
    One shared helper for bench.py, chip_smoke.py, the measurement
    scripts, the dryrun entry, the CLI precompile and the serving
    binaries (CommonConfig.compilation_cache_dir). The directory comes
    from config.resolve_compile_cache_dir: JAX_COMPILATION_CACHE_DIR
    when set, else `cache_dir`, else the checkout's fixed `.jax_cache`.
    Returns (directory, where it came from)."""
    import jax

    from .config import DEFAULT_COMPILE_CACHE_DIR, resolve_compile_cache_dir

    resolved, source = resolve_compile_cache_dir(cache_dir or DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", resolved)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # statusz `engine_prewarm` section + the prewarm hit/miss split
    # read the live cache dir from here
    prewarm_mod.note_compile_cache(resolved)
    return resolved, source


def warmup_engines_background(ds, buckets=None, manifest=None) -> "threading.Thread":
    """Ahead-of-time bucket compilation OFF the boot path (VERDICT r3
    weak #8: a fresh deployment's first job on a new batch bucket still
    stalled minutes). Serving starts immediately; a daemon thread warms
    each configured bucket in ascending order, so the small buckets
    (interactive traffic) compile first and big job buckets follow.
    `manifest` has warmup_engines' semantics — janus_main passes
    _NO_DEDUPE when the manifest prewarm did not run."""
    import threading

    buckets = sorted(buckets or (None,), key=lambda b: b or 0)

    def work():
        for b in buckets:
            warmup_engines(ds, batch=b, manifest=manifest)

    t = threading.Thread(target=work, name="engine-warmup", daemon=True)
    t.start()
    return t


_NO_DEDUPE = object()  # warmup sentinel: skip NO geometry (the manifest
# prewarm did not run, so nothing "owns" the covered ones)


def warmup_engines(ds, batch: int | None = None, manifest=None) -> dict:
    """Compile the device engine steps for every provisioned task before
    serving traffic (cold-start mitigation: a cold aggregator otherwise
    stalls for minutes on first request per task). With the persistent
    compilation cache, restarts reduce this to disk loads.

    `batch` selects the batch size to warm (engines compile per
    power-of-two jit bucket). Without it, each task warms the sizes of
    its PENDING aggregation jobs — the geometry the next driver pass
    will actually dispatch — falling back to MIN_BUCKET only when
    there is no pending work to learn from. Geometries the shape
    manifest already covers are SKIPPED (counted
    `outcome="skipped_covered"`): the manifest-driven prewarm owns
    them, so warm-up work is never duplicated — pass
    `manifest=_NO_DEDUPE` when the prewarm did NOT run (disabled /
    failed), so a covered-but-unwarmed geometry still warms. Returns a
    summary dict ({"warmed": [(task_id, bucket)], "skipped_covered": n})."""
    import numpy as np

    from . import metrics
    from .aggregator import shape_manifest
    from .aggregator.engine_cache import (
        MIN_BUCKET,
        HostEngineCache,
        bucket_size,
        engine_cache,
    )
    from .vdaf.testing import random_measurements, zero_report_batch

    if manifest is _NO_DEDUPE:
        manifest = None
    elif manifest is None:
        manifest = shape_manifest.installed()
    tasks = ds.run_tx(lambda tx: tx.get_tasks(), "warmup_list_tasks")
    pending: dict[bytes, list[int]] = {}
    if batch is None:
        try:
            pending = ds.run_tx(
                lambda tx: tx.get_pending_aggregation_job_sizes(), "warmup_job_sizes"
            )
        except Exception:
            log.warning(
                "pending aggregation job sizes unavailable; warming the "
                "minimum bucket",
                exc_info=True,
            )
    # ops a task-bucket warm compiles; a bucket is skipped only when the
    # manifest covers ALL of them (a partial warm would still pay the
    # leader leg the aggregate warm needs)
    warm_ops = ("leader_init", "helper_init", "aggregate")
    result: dict = {"warmed": [], "skipped_covered": 0}
    # warm dispatches are infrastructure, not the serving path a chaos
    # schedule drills: keep armed failpoints inert so `after=K` anchors
    # stay pinned to SERVING dispatch counts (failpoints.suppressed)
    from . import failpoints

    with failpoints.suppressed():
        for task in tasks:
            if task.vdaf.kind.startswith("fake") or task.vdaf.kind == "poplar1":
                continue  # fakes and host-side Poplar1 have no device engine
            if batch is not None:
                sizes = [int(batch)]
            else:
                # dedupe pending job sizes by their jit bucket (the compile
                # unit), keep ascending so interactive sizes warm first,
                # and bound the set — one warm per bucket is enough
                by_bucket: dict[int, int] = {}
                for n in sorted(pending.get(task.task_id.data, [])):
                    by_bucket.setdefault(bucket_size(n), n)
                sizes = [by_bucket[b] for b in sorted(by_bucket)][:4] or [MIN_BUCKET]
            for warm_batch in sizes:
                b = bucket_size(warm_batch)
                inst_dict = task.vdaf.to_dict()
                try:
                    eng = engine_cache(task.vdaf, task.vdaf_verify_key)
                    if isinstance(eng, HostEngineCache):
                        continue  # host engines need no compile
                    # coverage is per mesh topology: a manifest recorded
                    # under a different (dp, sp, ndev) — another machine
                    # class, or a single-device run — names programs this
                    # process never dispatches, so it doesn't cover these
                    geometry = (
                        (eng.dp, eng.sp, eng._ndev) if eng.mesh is not None else None
                    )
                    if manifest is not None and all(
                        manifest.covers(inst_dict, op, b, geometry=geometry)
                        for op in warm_ops
                    ):
                        result["skipped_covered"] += 1
                        metrics.engine_prewarm_total.add(outcome="skipped_covered")
                        continue
                    # zero-valued reports of the real shapes: the warm
                    # compiles the aggregator's programs only, never the
                    # client's shard graph
                    rng = np.random.default_rng(0)
                    args = zero_report_batch(task.vdaf, warm_batch)
                    nonce, parts, meas, proof, blind0, hseed, blind1 = args
                    out0, seed0, ver0, part0 = eng.leader_init(
                        nonce, parts, meas, proof, blind0
                    )
                    ok = np.ones(warm_batch, dtype=bool)
                    part0_l = (
                        part0
                        if part0 is not None
                        else np.zeros((warm_batch, 2), dtype=np.uint64)
                    )
                    eng.helper_init(nonce, parts, hseed, blind1, ver0, part0_l, ok)
                    if task.vdaf.kind == "sparse_sumvec":
                        # block-sparse tasks never dispatch the dense
                        # aggregate: warm the gather/scatter program the
                        # resident merge and the classic sparse path share
                        # (compile_key ("scatter_merge", bucket)) —
                        # aggregate_sparse is stateless, so no resident
                        # slot is polluted (docs/ARCHITECTURE.md
                        # "Block-sparse aggregation")
                        from .vdaf.registry import circuit_for
                        from .vdaf.testing import sparse_compact_batch
                        from .vdaf.wire import flat_scatter_indices

                        meas_pairs = random_measurements(task.vdaf, warm_batch, rng)
                        _, block_idx = sparse_compact_batch(task.vdaf, meas_pairs)
                        flat_idx = flat_scatter_indices(
                            block_idx, circuit_for(task.vdaf)
                        )
                        eng.aggregate_sparse(out0, ok, flat_idx)
                    else:
                        eng.aggregate(out0, ok)
                    result["warmed"].append((task.task_id, b))
                    log.info(
                        "warmed engines for task %s (%s) at bucket %d",
                        task.task_id, task.vdaf.kind, b,
                    )
                except Exception:
                    log.exception("engine warmup failed for task %s", task.task_id)
    return result


def janus_main(description: str, config_cls, run, argv=None, install_signals: bool = True):
    """Shared entry point (reference binary_utils.rs janus_main).

    `run(cfg, ds, stopper)` is the binary body; this harness owns config
    parsing, logging, the health/metrics listener, the datastore and
    signal handling.
    """
    parser = argparse.ArgumentParser(description=description)
    parser.add_argument("--config-file", required=True, help="YAML configuration file")
    parser.add_argument(
        "--datastore-keys",
        default=os.environ.get("DATASTORE_KEYS", ""),
        help="comma-separated base64url AES-128 keys (or DATASTORE_KEYS env)",
    )
    args = parser.parse_args(argv)

    # boot-phase timeline (docs/OBSERVABILITY.md "Continuous
    # profiling"): everything before this call — interpreter start,
    # janus_tpu/jax imports — is the "imports" phase; each later
    # phase_done closes the phase running since the previous mark, so
    # the phases tile process-start -> ready exactly
    from . import profiler as profiler_mod
    from .profiler import BOOT

    BOOT.phase_done("imports")

    cfg = load_config(args.config_file, config_cls)
    common: CommonConfig = cfg.common
    install_trace_subscriber(common.logging_config)

    # refresh janus_build_info with the YAML-configured backend (the
    # import-time registration guessed from the environment)
    from .metrics import register_build_info, set_replica_identity

    register_build_info(
        backend=common.jax_platform or os.environ.get("JAX_PLATFORMS")
    )

    # fleet replica identity (docs/ARCHITECTURE.md "Running a fleet"):
    # janus_replica_info carries it on every scrape; an EXPLICITLY
    # configured replica_id (YAML fleet: / JANUS_REPLICA_ID) also turns
    # on the per-replica labels of the job-driver/health-sampler/SLO
    # families and rides every trace as a resource attribute, so N
    # processes over one datastore stay attributable end to end.
    fleet = common.fleet
    replica_id = fleet.resolved_replica_id()
    set_replica_identity(
        replica_id=fleet.replica_id,
        shard_index=fleet.shard_index,
        shard_count=fleet.shard_count,
    )
    from .trace import set_resource_attributes

    set_resource_attributes(
        replica=replica_id,
        shard=f"{fleet.shard_index % max(1, fleet.shard_count)}/{fleet.shard_count}",
    )
    register_status_provider(
        "fleet",
        lambda: {
            "replica_id": replica_id,
            "configured": fleet.replica_id is not None,
            "shard_index": fleet.shard_index % max(1, fleet.shard_count),
            "shard_count": fleet.shard_count,
            "steal_after_secs": fleet.steal_after_secs,
        },
    )

    # fault injection: JANUS_FAILPOINTS env wins over the YAML
    # `failpoints:` key; unset/empty compiles every site to a no-op.
    # Always on /statusz so an operator can see at a glance whether a
    # process is running with injected faults (docs/ROBUSTNESS.md).
    from . import failpoints

    failpoints.configure_from_env(default=common.failpoints)
    register_status_provider("failpoints", failpoints.status)

    # device-path watchdog + quarantine knobs (registers the /statusz
    # `device_watchdog` section — abandoned-thread count + live stack
    # dumps of stalled dispatches — as an import side effect)
    from .aggregator import device_watchdog
    from .aggregator.engine_cache import EngineCache, shutdown_engines

    if "JANUS_WATCHDOG_ABANDONED_CAP" not in os.environ:
        # like the canary knobs below: the env var is the operator
        # override — applying the YAML/default over it would silently
        # kill the documented knob in every binary
        device_watchdog.configure(
            abandoned_thread_cap=common.watchdog_abandoned_thread_cap
        )
    if "JANUS_CANARY_DELAY_S" not in os.environ:
        EngineCache.QUARANTINE_CANARY_DELAY_SECS = common.quarantine_canary_delay_secs
    if "JANUS_CANARY_TIMEOUT_S" not in os.environ:
        EngineCache.QUARANTINE_CANARY_TIMEOUT_SECS = (
            common.quarantine_canary_timeout_secs
        )
    BOOT.phase_done("config")

    if common.jax_platform:
        os.environ["JAX_PLATFORMS"] = common.jax_platform
        try:
            import jax

            jax.config.update("jax_platforms", common.jax_platform)
        except Exception:
            log.exception("could not pin JAX platform %r", common.jax_platform)

    # persistent XLA compile cache: restart cold-start drops from
    # minutes (first jit of each engine step) to seconds. The `engine:`
    # stanza's compile_cache_dir overrides the top-level knob; a set
    # JAX_COMPILATION_CACHE_DIR overrides both.
    from .config import resolve_compile_cache_dir

    compile_cache_dir, _ = resolve_compile_cache_dir(
        common.engine.compile_cache_dir or common.compilation_cache_dir
    )
    if compile_cache_dir:
        try:
            enable_compile_cache(compile_cache_dir)
        except Exception:
            log.exception("could not enable the persistent compilation cache")
    # serialized-executable AOT cache rides beside the XLA cache, in its
    # `aot` subdirectory: the XLA cache skips recompiles, this skips the
    # re-TRACE — the larger half of a warm restart (docs/ARCHITECTURE.md
    # "Cold-start and prewarm"). JANUS_AOT_CACHE=0 turns it off.
    if os.environ.get("JANUS_AOT_CACHE") != "0" and common.engine.aot_cache and compile_cache_dir:
        from .aggregator import aot_cache

        aot_cache.arm(os.path.join(compile_cache_dir, "aot"))

    # engine-layer knobs (YAML `engine:` stanza). Envs are the operator
    # override, same discipline as the watchdog knobs above.
    if common.engine.resident_max_bytes and "JANUS_RESIDENT_MAX_BYTES" not in os.environ:
        EngineCache.RESIDENT_MAX_BYTES = int(common.engine.resident_max_bytes)
    if (
        common.engine.cross_task_coalesce is not None
        and "JANUS_XTASK_COALESCE" not in os.environ
    ):
        from .aggregator import engine_cache as engine_cache_mod

        engine_cache_mod.XTASK_COALESCE = bool(common.engine.cross_task_coalesce)
    # mesh serving geometry (`engine: mesh: {dp, sp}`): pins the
    # (dp, sp) axes engines build instead of auto-selecting from the
    # device count; validated per-engine (single-device processes fall
    # back to the unsharded path regardless). JANUS_MESH_DP/SP envs win.
    if common.engine.mesh_dp is not None and "JANUS_MESH_DP" not in os.environ:
        EngineCache.MESH_DP = int(common.engine.mesh_dp)
    if common.engine.mesh_sp is not None and "JANUS_MESH_SP" not in os.environ:
        EngineCache.MESH_SP = int(common.engine.mesh_sp)
    BOOT.phase_done("backend_init")

    keys = parse_datastore_keys(args.datastore_keys)
    ds = open_datastore(common.database.url, Crypter(keys), RealClock())
    if "JANUS_SLOW_TX_WARN_S" not in os.environ:
        # the env var is the operator override; only the YAML value is
        # applied when it's absent (else it would be silently dead in
        # every binary — the class default already read it)
        ds.slow_tx_warn_s = common.database.slow_tx_warn_secs
    ds.retry_max_interval_s = common.database.retry_max_interval_secs

    # datastore connection supervision: background health probe driving
    # the up/degraded/down/recovering state machine, /statusz section
    # and the /readyz readiness split (liveness /healthz stays up — a
    # DB outage is a reason to stop routing, never to kill the process)
    if common.database.health_probe_interval_secs > 0:
        supervisor = ds.start_supervision(
            probe_interval_s=common.database.health_probe_interval_secs,
            down_threshold=common.database.down_after_failures,
            reconnect_max_interval_s=common.database.reconnect_max_interval_secs,
        )
        register_status_provider("datastore", supervisor.status)
        register_readiness_check("datastore", supervisor.readiness)

    # /statusz base sections: build/process info and the provisioned
    # tasks (subsystems — engine cache, ingest, health sampler — add
    # their own sections as they come up)
    def _process_status():
        from . import __version__

        info = {
            "version": __version__,
            "role": description,
            "pid": os.getpid(),
            "config_file": args.config_file,
            "database_url": common.database.url,
            "jax_platform": common.jax_platform or os.environ.get("JAX_PLATFORMS"),
            "health_sampler_interval_s": common.health_sampler_interval_s,
        }
        try:
            import jax

            info["jax_version"] = jax.__version__
        except Exception:
            pass
        return info

    def _tasks_status():
        from .metrics import task_id_label

        tasks = ds.run_tx(lambda tx: tx.get_tasks(), "statusz_tasks")
        return [
            {
                "task_id": task_id_label(t.task_id.data),
                "role": t.role.name,
                "vdaf": t.vdaf.kind,
                "xof_mode": t.vdaf.xof_mode,
                "query_type": t.query_type.code,
            }
            for t in tasks
        ]

    register_status_provider("process", _process_status)
    register_status_provider("tasks", _tasks_status)
    BOOT.phase_done("datastore")

    # --- persisted shape manifest + AOT prewarm (ISSUE 14; docs/
    # ARCHITECTURE.md "Cold-start and prewarm"): load the manifest of
    # observed dispatch specializations and compile the recorded set —
    # highest recorded cost first, bounded by the boot budget — BEFORE
    # the health listener is up, so /readyz never reports a replica
    # ready that would stall its first jobs on cold compiles. The
    # JANUS_SHAPE_MANIFEST env var is the operator override; an empty
    # path ("" in YAML or env) disables recording and prewarm, and a
    # manifest-less boot degrades to the legacy warmup below.
    manifest = None
    manifest_path = os.environ.get("JANUS_SHAPE_MANIFEST")
    if manifest_path is None:
        manifest_path = common.engine.shape_manifest_path
    if manifest_path is None and compile_cache_dir:
        manifest_path = os.path.join(compile_cache_dir, shape_manifest_mod.DEFAULT_FILENAME)
    if manifest_path:
        try:
            manifest = shape_manifest_mod.install_manifest(
                manifest_path,
                max_entries=common.engine.shape_manifest_max_entries,
            )
        except Exception:
            log.exception("could not install the shape manifest at %s", manifest_path)
    BOOT.phase_done("engine_warm_manifest")

    prewarm_ready = threading.Event()
    register_readiness_check(
        "engine_prewarm",
        lambda: None
        if prewarm_ready.is_set()
        else "boot-budget engine prewarm still compiling",
    )
    prewarm_ran = False
    if common.engine.prewarm and manifest is not None:
        try:
            prewarm_mod.prewarm_engines(
                ds,
                manifest,
                boot_budget_s=common.engine.prewarm_boot_budget_secs,
                ready_event=prewarm_ready,
            )
            prewarm_ran = True
        except Exception:
            log.exception("manifest prewarm failed; serving cold")
    prewarm_ready.set()  # idempotent (prewarm_engines sets it after the
    # priority set); a disabled/failed prewarm must never wedge /readyz
    if common.warmup_engines_at_boot:
        # dedupe against the manifest ONLY when the prewarm really
        # warmed it — with prewarm disabled/failed, a covered geometry
        # would otherwise be skipped by BOTH paths and serve its first
        # job cold
        dedupe = manifest if prewarm_ran else _NO_DEDUPE
        if common.warmup_buckets:
            # non-blocking: serve immediately, compile buckets behind
            warmup_engines_background(ds, common.warmup_buckets, manifest=dedupe)
        else:
            warmup_engines(ds, manifest=dedupe)
    BOOT.phase_done("engine_warm")

    # in-process SLO burn-rate engine (YAML `slo:` stanza; ISSUE 10):
    # evaluates the burn-rate ladder over the live registry and serves
    # GET /alertz + the `slo` statusz section on the health listener
    from . import slo as slo_mod

    slo_engine = None
    if common.slo.enabled:
        slo_engine = slo_mod.install_slo_engine(common.slo)

    # always-on sampling profiler (YAML `profiler:` stanza; ISSUE 13):
    # wall-clock stacks behind GET /debug/profile on the listener below
    profiler_mod.install_profiler(common.profiler)

    # telemetry flight recorder (YAML `flight:` stanza; ISSUE 18):
    # low-cadence resource/metric history + trend/leak verdicts behind
    # GET /debug/flight, feeding the `trend` SLO signal above
    from . import flight_recorder as flight_mod

    flight_mod.install_flight_recorder(common.flight)

    stopper = Stopper()
    if install_signals:
        setup_signal_handler(stopper)
    health = HealthServer(common.health_check_listen_address).start()
    log.info("health/metrics listener on port %d", health.port)
    # the listener is up and every registered readiness check is live:
    # this is the moment /readyz starts answering — seal the boot record
    BOOT.phase_done("listener_up")
    BOOT.mark_ready()
    try:
        return run(cfg, ds, stopper)
    finally:
        health.stop()
        flight_mod.uninstall_flight_recorder()
        profiler_mod.uninstall_profiler()
        if slo_engine is not None:
            slo_mod.uninstall_slo_engine()
        # teardown ordering against interpreter finalization — a daemon
        # thread running REAL device work while the interpreter
        # finalizes crashes inside native XLA: (1) stop engine canary
        # loops (bounded join of an in-flight probe), (2) unpark
        # hang-failpoint wedges (they raise at the site), (3) let
        # abandoned watchdog workers retire
        shutdown_engines(2.0)
        failpoints.release_hangs()
        device_watchdog.WATCHDOG.drain(2.0)
        unregister_readiness_check("engine_prewarm")
        shape_manifest_mod.uninstall_manifest()
        from .aggregator import aot_cache

        aot_cache.disarm()
        ds.close()
