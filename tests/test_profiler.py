"""Continuous profiling subsystem (ISSUE 13; janus_tpu/profiler.py):
the sampling wall-clock profiler (role tagging, window ring, collapsed
format under hostile names, measured overhead), the boot-phase
timeline, the health listener endpoints, and the shared stack
formatter the device watchdog's stalled dumps reuse.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import pytest

from janus_tpu import profiler as prof
from janus_tpu.profiler import (
    BootTimeline,
    ProfilerConfig,
    SamplingProfiler,
    fold_component,
    format_stack,
    frame_label,
    thread_role,
    validate_collapsed,
)


# ---------------------------------------------------------------------------
# role tagging
# ---------------------------------------------------------------------------


def test_thread_role_covers_every_named_thread_family():
    """Every thread family the codebase creates maps to its documented
    role — a rename at a creation site without a catalog update is a
    test failure, not a silent 'other'."""
    expected = {
        # step pipeline (ThreadPoolExecutor appends -0, -1, ...)
        "device-lane-0": "device_lane",
        "device-watchdog-3": "device_lane",  # supervised dispatches run here
        "step-read-1": "prefetch",
        "step-commit-0": "commit",
        "step-http-2": "http_client",
        "dap-handler-5": "http_handler",
        # ingest
        "ingest-decrypt-0": "decrypt_pool",
        "ingest-decode-1": "decode_pool",
        # flushers
        "report-writer": "flusher",
        "resident-flusher": "flusher",
        "upload-journal-replay": "flusher",
        "chrome-trace-flush": "flusher",
        "device-lane-gauge": "flusher",
        # background engines/samplers
        "slo-engine": "slo_engine",
        "health-sampler": "sampler",
        "datastore-supervisor": "supervisor",
        "engine-canary-count": "engine_warm",
        "engine-warmup": "engine_warm",
        # listeners (normalized in this PR — they were unnamed)
        "dap-listener": "listener",
        "health-listener": "listener",
        "api-listener": "listener",
        "interop-listener": "listener",
        # steps real jobs — must NOT fold into the accept-loop role
        "interop-runner": "other",
        "gc-loop": "gc",
        "janus-profiler": "profiler",
        "MainThread": "main",
        # unknown names degrade to 'other', never crash
        "Thread-17 (run)": "other",
        'evil;name\n"x"': "other",
    }
    for name, role in expected.items():
        assert thread_role(name) == role, (name, thread_role(name), role)


# ---------------------------------------------------------------------------
# sampling, folding, hostile names
# ---------------------------------------------------------------------------


def _spin_marker_loop(stop: threading.Event):
    # distinctive frame the sampler must catch (busy, not a wait leaf)
    while not stop.is_set():
        sum(range(256))


def test_sampler_catches_live_thread_with_role_and_frames():
    stop = threading.Event()
    t = threading.Thread(target=_spin_marker_loop, args=(stop,), name="device-lane-9")
    t.start()
    p = SamplingProfiler(ProfilerConfig(hz=200.0, window_secs=60.0))
    p.start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            doc = p.profile_json()
            if doc["roles"].get("device_lane", {}).get("self_samples", 0) > 0:
                break
            time.sleep(0.02)
    finally:
        p.stop()
        stop.set()
        t.join()
    doc = p.profile_json()
    lane = doc["roles"]["device_lane"]
    assert lane["samples"] > 0 and lane["self_samples"] > 0
    assert 0 < lane["self_pct"] <= lane["total_pct"] <= 100.0
    collapsed = p.collapsed()
    assert "_spin_marker_loop" in collapsed
    # the role tags the folded stack's root
    assert any(
        line.startswith("device_lane;") and "_spin_marker_loop" in line
        for line in collapsed.splitlines()
    )
    # the sampler excludes its own thread
    assert "profiler;" not in collapsed
    assert validate_collapsed(collapsed) == []


def test_collapsed_roundtrip_with_hostile_thread_name():
    """A thread named with semicolons/newlines/quotes/spaces — the
    folded-format separators — must not corrupt the document: every
    line still splits into a stack and an integer count."""
    stop = threading.Event()
    t = threading.Thread(
        target=_spin_marker_loop,
        args=(stop,),
        name='evil;stack\ncorruptor "x" 42 ',
    )
    t.start()
    p = SamplingProfiler(ProfilerConfig(hz=500.0, window_secs=60.0))
    p.start()
    try:
        for _ in range(200):
            if p.profile_json()["samples"] > 10:
                break
            time.sleep(0.01)
    finally:
        p.stop()
        stop.set()
        t.join()
    collapsed = p.collapsed()
    assert collapsed
    assert validate_collapsed(collapsed) == []
    for line in collapsed.splitlines():
        stack, _, count = line.rpartition(" ")
        assert count.isdigit()
        assert all(comp and ";" not in comp for comp in stack.split(";"))


def test_fold_component_sanitizes_separators():
    assert fold_component("a;b c\nd\te") == "a_b_c_d_e"
    assert fold_component("") == "_"
    assert fold_component("clean.frame") == "clean.frame"


def test_window_rotation_and_ring_bounds():
    p = SamplingProfiler(ProfilerConfig(hz=50.0, window_secs=0.0, windows=3))
    # drive sampling synchronously (no thread): window_secs=0 rotates
    # on every pass, so the ring must hold at most `windows` windows
    # and aggregation must still sum samples across ring + current
    p._current = prof._Window(time.time())
    for _ in range(10):
        p.sample_once()
    assert len(p._ring) == 3
    stacks, samples, passes = p._aggregate_locked()
    # only ring + current survive: 3 retained + the fresh current
    assert passes <= 4
    assert samples >= 0 and isinstance(stacks, dict)


def test_sampler_overhead_zero_off_and_sane_on():
    from janus_tpu import metrics as m

    p = SamplingProfiler(ProfilerConfig(hz=100.0, window_secs=30.0))
    # off: never started -> ratio 0 via the gauge default and the doc
    assert p.profile_json()["overhead_ratio"] == 0.0
    before = m.profiler_samples_total.get()
    p.start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and p.profile_json()["passes"] < 5:
            time.sleep(0.01)
    finally:
        p.stop()
    doc = p.profile_json()
    assert doc["passes"] >= 5
    # measured: strictly positive, far under the 2% budget even at
    # 100 Hz (the bound is loose for loaded CI hosts)
    assert 0.0 < doc["overhead_ratio"] < 0.2
    assert m.profiler_samples_total.get() > before
    assert m.profiler_overhead_ratio.get() >= 0.0


def test_start_stop_idempotent_and_install_uninstall():
    p = SamplingProfiler(ProfilerConfig(hz=100.0))
    p.start()
    p.start()  # second start is a no-op, not a second thread
    assert sum(1 for t in threading.enumerate() if t.name == "janus-profiler") == 1
    p.stop()
    assert not p.running
    p.stop()  # idempotent

    old = prof.PROFILER
    try:
        inst = prof.install_profiler(ProfilerConfig(hz=100.0, enabled=True))
        assert inst.running and prof.PROFILER is inst
        # the module-level statusz provider follows the installed
        # instance (it reads the module global at call time)
        from janus_tpu.statusz import status_snapshot

        snap = status_snapshot()
        assert snap["profile"]["enabled"] is True
        prof.uninstall_profiler()
        assert not inst.running
        assert status_snapshot()["profile"]["enabled"] is False
        # enabled: false installs but never starts
        inst2 = prof.install_profiler(ProfilerConfig(enabled=False))
        assert not inst2.running
    finally:
        prof.uninstall_profiler()
        prof.PROFILER = old


# ---------------------------------------------------------------------------
# boot timeline
# ---------------------------------------------------------------------------


def test_boot_timeline_phases_monotone_and_complete():
    b = BootTimeline(start_unix=time.time() - 0.5)
    b.phase_done("imports")
    time.sleep(0.02)
    b.phase_done("config")
    b.phase_done("backend_init")
    b.mark_ready()
    snap = b.snapshot()
    assert snap["ready"] is True
    names = [p["phase"] for p in snap["phases"]]
    assert names == ["imports", "config", "backend_init"]
    # contiguous + monotone: each phase starts where the previous ended
    last_end = 0.0
    for p in snap["phases"]:
        assert p["start_s"] == pytest.approx(last_end, abs=1e-6)
        assert p["end_s"] >= p["start_s"]
        # seconds and the start/end offsets are rounded independently
        # to 6 decimals, so they can disagree by up to ~2 µs
        assert p["seconds"] == pytest.approx(p["end_s"] - p["start_s"], abs=5e-6)
        last_end = p["end_s"]
    # phases tile process start -> the last mark; ready is moments after
    assert snap["boot_phases_sum_s"] == pytest.approx(snap["total_s"], rel=0.01)
    assert snap["phases"][0]["seconds"] >= 0.5  # the pre-main imports span
    # gauge exported per phase
    from janus_tpu import metrics as m

    assert m.boot_phase_seconds.get(phase="config") > 0

    # a phase reported after ready appends flagged late and does not
    # disturb the sealed sum
    b.phase_done("journal_scan")
    snap2 = b.snapshot()
    assert snap2["phases"][-1]["phase"] == "journal_scan"
    assert snap2["phases"][-1].get("late") is True
    assert snap2["boot_phases_sum_s"] == snap["boot_phases_sum_s"]
    assert snap2["total_s"] == snap["total_s"]
    # mark_ready is idempotent: first call wins
    ready0 = b.ready_unix
    b.mark_ready()
    assert b.ready_unix == ready0


# ---------------------------------------------------------------------------
# endpoints (content types + payload shape over live HTTP)
# ---------------------------------------------------------------------------


def test_health_listener_profile_and_boot_endpoints():
    from janus_tpu.binary_utils import HealthServer

    old = prof.PROFILER
    prof.install_profiler(ProfilerConfig(hz=100.0, window_secs=10.0))
    srv = HealthServer("127.0.0.1:0").start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and prof.PROFILER.profile_json()["passes"] < 3:
            time.sleep(0.01)

        with urllib.request.urlopen(base + "/debug/profile", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            collapsed = resp.read().decode()
        assert collapsed and validate_collapsed(collapsed) == []

        with urllib.request.urlopen(
            base + "/debug/profile?format=json", timeout=10
        ) as resp:
            assert resp.headers["Content-Type"].startswith("application/json")
            doc = json.loads(resp.read())
        assert doc["enabled"] is True and doc["samples"] > 0
        assert "roles" in doc and "top_frames" in doc

        # Accept negotiation picks JSON too
        req = urllib.request.Request(
            base + "/debug/profile", headers={"Accept": "application/json"}
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("application/json")

        with urllib.request.urlopen(base + "/debug/boot", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("application/json")
            boot = json.loads(resp.read())
        assert {"started_unix", "ready", "phases", "boot_phases_sum_s"} <= set(boot)

        # the index page advertises the new endpoints
        with urllib.request.urlopen(base + "/", timeout=10) as resp:
            index = resp.read().decode()
        assert "/debug/profile" in index and "/debug/boot" in index
    finally:
        srv.stop()
        prof.uninstall_profiler()
        prof.PROFILER = old


# ---------------------------------------------------------------------------
# shared stack formatter (watchdog consolidation)
# ---------------------------------------------------------------------------


def test_format_stack_and_frame_label_shared_with_watchdog():
    import sys as _sys

    frame = _sys._getframe()
    label = frame_label(frame)
    assert label.endswith(".test_format_stack_and_frame_label_shared_with_watchdog")
    assert frame_label(frame, lineno=True).rsplit(":", 1)[1].isdigit()
    stack = format_stack(frame, limit=12, lineno=True)
    assert 0 < len(stack) <= 12
    # outermost-first: this test's frame is the LAST entry
    assert "test_format_stack_and_frame_label" in stack[-1]


def test_watchdog_stalled_dump_uses_shared_formatter():
    """A hung supervised dispatch's /statusz stack dump renders through
    profiler.format_stack — the same frame labels as the folded
    profile, so the two renderings cannot diverge."""
    from janus_tpu.aggregator.device_watchdog import DeviceHangError, DispatchWatchdog

    wd = DispatchWatchdog(abandoned_thread_cap=99)
    release = threading.Event()

    def wedge():
        release.wait(20)

    with pytest.raises(DeviceHangError):
        wd.run(wedge, deadline=time.monotonic() + 0.2, label="test_wedge")
    try:
        status = wd.status()
        assert status["abandoned_threads"] == 1
        ent = status["stalled"][0]
        assert ent["label"] == "test_wedge"
        stack = ent.get("stack")
        assert stack, status
        # shared formatter shape: module.func:lineno, innermost last —
        # the parked thread is inside wedge -> Event.wait
        assert all(s.rsplit(":", 1)[1].isdigit() for s in stack)
        assert any("threading" in s and ".wait" in s for s in stack)
    finally:
        release.set()
        wd.drain(2.0)
        wd.reset_for_tests()


def test_validate_collapsed_rejects_malformed_documents():
    assert validate_collapsed("a;b 3\n") == []
    assert validate_collapsed("") == []
    assert validate_collapsed("no_count_here") != []
    assert validate_collapsed("a;b notanint") != []
    assert validate_collapsed("a;;b 3") != []
    assert validate_collapsed("a; b 3") != []
    assert validate_collapsed(" 3") != []
