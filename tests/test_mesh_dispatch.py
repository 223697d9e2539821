"""Multi-chip serving (ISSUE 16): the single-controller mesh dispatch
queue (FIFO fairness, exception propagation, lane accounting — and the
process-global _MESH_DISPATCH_LOCK it replaced being GONE), mesh-vs-
single-device bit-identity through the SERVING EngineCache path (count
+ sumvec, rejected lanes, sharded resident accumulate) both in-process
and in a subprocess forced to a different device topology, geometry
selection, and the prewarm geometry-mismatch skip."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from janus_tpu.aggregator import engine_cache as ec
from janus_tpu.aggregator.engine_cache import (
    EngineCache,
    MeshDispatchQueue,
    mesh_status,
)
from janus_tpu.messages import Duration, Interval, Time
from janus_tpu.vdaf.registry import VdafInstance
from janus_tpu.vdaf.testing import make_report_batch, random_measurements

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COUNT = VdafInstance.count()
SUMVEC = VdafInstance.sum_vec(length=4, bits=2)


# ---------------------------------------------------------------------------
# the dispatch queue itself (no device work)
# ---------------------------------------------------------------------------


def test_mesh_dispatch_lock_is_gone():
    # the PR 14 process-global lock is replaced by the queue; anything
    # still importing it should fail loudly, not silently double-lock
    assert not hasattr(ec, "_MESH_DISPATCH_LOCK")
    assert isinstance(ec._MESH_QUEUE, MeshDispatchQueue)


def test_mesh_dispatch_queue_single_lane_no_overlap_no_starvation():
    q = MeshDispatchQueue()
    lanes = set()
    executed = []
    busy = threading.Event()
    overlaps = []

    def work(tag):
        if busy.is_set():
            overlaps.append(tag)
        busy.set()
        try:
            lanes.add(threading.current_thread().name)
            executed.append(tag)
            time.sleep(0.001)
        finally:
            busy.clear()
        return tag * 2

    results = {}
    errors = []

    def submitter(base):
        # several sequential submits per thread: a starved submitter
        # would wedge here and trip the join timeout below
        try:
            for j in range(5):
                tag = base * 100 + j
                results[tag] = q.submit(work, (tag,), {}, program="t")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=submitter, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads), "submitter starved"
    assert not errors
    assert not overlaps, f"dispatch lane overlapped: {overlaps}"
    assert lanes == {"mesh-dispatch"}
    assert len(executed) == 20
    assert results == {t: t * 2 for t in executed}
    st = q.status()
    assert st["submitted"] == 20
    assert st["completed"] == 20
    assert st["errors"] == 0
    assert st["depth"] == 0
    assert st["lane_alive"] is True
    assert st["busy_s"] > 0


def test_mesh_dispatch_queue_exception_propagates_and_lane_survives():
    q = MeshDispatchQueue()

    class Boom(RuntimeError):
        pass

    boom = Boom("injected")

    def bad():
        raise boom

    with pytest.raises(Boom) as ei:
        q.submit(bad, (), {}, vdaf="count", program="bad")
    # the ORIGINAL exception object: OOM recovery tags the instance
    # (_janus_oom_handled) and type-checks it, so a re-wrap would break
    # the engine's error handling
    assert ei.value is boom
    st = q.status()
    assert st["errors"] == 1
    # the lane survives a failed enqueue and keeps serving
    assert q.submit(lambda: 7, (), {}, program="ok") == 7
    assert q.status()["completed"] == 2


def test_mesh_dispatch_queue_fifo_order_when_backlogged():
    q = MeshDispatchQueue()
    order = []
    gate = threading.Event()

    def blocker():
        gate.wait(30)
        order.append("blocker")

    def tagged(i):
        order.append(i)

    # park the lane on the blocker, then pile up a backlog in a known
    # submit order; the single lane must drain it FIFO
    t0 = threading.Thread(target=q.submit, args=(blocker, (), {}))
    t0.start()
    for _ in range(200):
        if q.status()["depth"] == 0 and q.status()["submitted"] == 1:
            break
        time.sleep(0.005)
    backlog = []
    started = threading.Event()

    def enqueue(i):
        # stagger the racers: each waits for the previous one to be
        # COUNTED as submitted before enqueuing, making submit order
        # deterministic while the lane stays parked
        q.submit(tagged, (i,), {})

    for i in range(6):
        want = 2 + i  # blocker + i prior + this one
        th = threading.Thread(target=enqueue, args=(i,))
        th.start()
        backlog.append(th)
        for _ in range(400):
            if q.status()["submitted"] >= want:
                break
            time.sleep(0.005)
    gate.set()
    t0.join(timeout=30)
    for th in backlog:
        th.join(timeout=30)
    assert order == ["blocker", 0, 1, 2, 3, 4, 5]
    st = q.status()
    assert st["max_depth"] >= 6  # the backlog was really queued


# ---------------------------------------------------------------------------
# geometry selection + mesh status
# ---------------------------------------------------------------------------


def test_choose_mesh_geometry_contract():
    from janus_tpu.parallel.api import choose_mesh_geometry

    # single device: always (1, 1)
    assert choose_mesh_geometry(1, 2, 1, 4096, 32) == (1, 1)
    # auto: largest power of two <= ndev
    assert choose_mesh_geometry(4, 2, 1, 4096, 32) == (4, 1)
    assert choose_mesh_geometry(6, 2, 1, 4096, 32) == (4, 1)
    # long vectors carve an sp=2 axis (input and output divisible)
    dp, sp = choose_mesh_geometry(8, 8192, 8192, 4096, 32)
    assert sp == 2 and dp * sp <= 8
    # explicit overrides validated: non-pow2 dp rounds down, dp*sp
    # clamped to the device count
    assert choose_mesh_geometry(8, 2, 1, 4096, 32, dp=3) == (2, 1)
    assert choose_mesh_geometry(4, 8, 8, 4096, 32, dp=4, sp=2) == (2, 2)
    # sp that doesn't divide the vector falls back to 1
    assert choose_mesh_geometry(8, 7, 7, 0, 32, sp=2)[1] == 1


def test_mesh_statusz_section_shape():
    import jax

    # the statusz section lists engines registered in the process-wide
    # factory cache (direct EngineCache constructions are invisible)
    ec.engine_cache(COUNT, b"\x21" * 16)
    snap = mesh_status()
    assert snap["devices"] == len(jax.devices())
    for key in ("depth", "lane_alive", "submitted", "completed", "errors"):
        assert key in snap["queue"]
    assert any(
        e["vdaf"] == "count" and e["dp"] * e["sp"] >= 1 and "mesh" in e
        for e in snap["engines"]
    )


# ---------------------------------------------------------------------------
# serving-path bit-identity: mesh vs forced-single geometry
# ---------------------------------------------------------------------------


def _serve(eng, inst, n=32, seed=0x51, k=2):
    """One serving round through the REAL EngineCache entry points:
    leader + helper init, masked aggregate with rejected lanes, then
    the sharded resident accumulate + flush. Returns stringified field
    elements so results compare across processes via JSON."""
    rng = np.random.default_rng(seed)
    args, _ = make_report_batch(inst, random_measurements(inst, n, rng), seed=seed)
    nonce, parts, meas, proof, blind0, hseed, blind1 = args
    ok = np.ones(n, dtype=bool)
    ok[::5] = False  # rejected lanes stay in the batch
    out0, _s, ver0, part0 = eng.leader_init(nonce, parts, meas, proof, blind0)
    p0 = part0 if part0 is not None else np.zeros((n, 2), dtype=np.uint64)
    out1, _mask, _pm = eng.helper_init(nonce, parts, hseed, blind1, ver0, p0, ok)
    agg0 = [str(x) for x in eng.aggregate(out0, ok)]
    agg1 = [str(x) for x in eng.aggregate(out1, ok)]
    deltas = eng.aggregate_pending(out0, (np.arange(n) % k).astype(np.int32), k)
    iv = Interval(Time(0), Duration(3600))
    eng.resident_merge([(("g", j), j, n // k, iv) for j in range(k)], deltas)
    res = sorted(
        [str(r["key"]), [str(x) for x in r["share"]]] for r in eng.resident_take()
    )
    return {"agg0": agg0, "agg1": agg1, "resident": res}


@pytest.mark.slow  # the tier-1 bit-identity proof is the subprocess smoke below; this in-process variant adds the 8-device geometry + live queue-counter assertions
def test_mesh_vs_single_device_bit_identical_in_process(monkeypatch):
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device conftest mesh")
    mesh_eng = EngineCache(SUMVEC, b"\x11" * 16)
    assert mesh_eng.mesh is not None
    monkeypatch.setenv("JANUS_MESH_DP", "1")
    monkeypatch.setenv("JANUS_MESH_SP", "1")
    single_eng = EngineCache(SUMVEC, b"\x11" * 16)
    assert single_eng.mesh is None
    assert _serve(mesh_eng, SUMVEC) == _serve(single_eng, SUMVEC)
    # the mesh engine's work went through the single-controller lane
    st = mesh_status()["queue"]
    assert st["submitted"] > 0 and st["errors"] == 0 and st["lane_alive"]


_SUBPROC_CHILD = """
import json
import numpy as np
import jax; jax.config.update('jax_platforms', 'cpu')
import test_mesh_dispatch as t

out = {"devices": len(jax.devices())}
for name, inst in (("count", t.COUNT), ("sumvec", t.SUMVEC)):
    eng = t.EngineCache(inst, b"\\x11" * 16)
    rec = t._serve(eng, inst)
    rec["dp"], rec["sp"] = eng.dp, eng.sp
    out[name] = rec
print("MESH_BITID:" + json.dumps(out), flush=True)
"""


def test_mesh_subprocess_bit_identity_forced_4dev(monkeypatch):
    """The ISSUE 16 tier-1 smoke: a subprocess forced to a 4-device
    topology (XLA_FLAGS=--xla_force_host_platform_device_count=4)
    serves count + sumvec through the mesh EngineCache; this process
    serves the SAME batches with geometry forced to single-device.
    Every aggregate and resident share must be bit-identical."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count=4".strip()
    env.pop("JANUS_MESH_DP", None)
    env.pop("JANUS_MESH_SP", None)
    script = (
        "import sys; sys.path.insert(0, %r); sys.path.insert(0, %r)\n"
        % (REPO, os.path.join(REPO, "tests"))
    ) + _SUBPROC_CHILD
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=420,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = None
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("MESH_BITID:"):
            rec = json.loads(line[len("MESH_BITID:"):])
            break
    assert rec is not None, proc.stdout[-2000:]
    assert rec["devices"] == 4
    assert rec["count"]["dp"] * rec["count"]["sp"] > 1
    monkeypatch.setenv("JANUS_MESH_DP", "1")
    monkeypatch.setenv("JANUS_MESH_SP", "1")
    for name, inst in (("count", COUNT), ("sumvec", SUMVEC)):
        eng = EngineCache(inst, b"\x11" * 16)
        assert eng.mesh is None
        ref = _serve(eng, inst)
        assert rec[name]["agg0"] == ref["agg0"], name
        assert rec[name]["agg1"] == ref["agg1"], name
        assert rec[name]["resident"] == ref["resident"], name


# ---------------------------------------------------------------------------
# prewarm skips manifest entries recorded under a different topology
# ---------------------------------------------------------------------------


def test_prewarm_skips_geometry_mismatch(tmp_path, monkeypatch):
    import jax

    from janus_tpu.aggregator import prewarm, shape_manifest

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device conftest mesh")
    prewarm.reset_for_tests()
    man = shape_manifest.install_manifest(str(tmp_path / "m.jsonl"))
    try:
        eng = EngineCache(COUNT, bytes(range(16)))
        assert eng.mesh is not None
        # ONE dispatch records mesh-geometry-keyed manifest entries
        # (leader_init only: the skip logic is per-entry, one suffices)
        rng = np.random.default_rng(1)
        args, _ = make_report_batch(
            COUNT, random_measurements(COUNT, 8, rng), seed=1
        )
        nonce, parts, meas, proof, blind0, _h, _b1 = args
        eng.leader_init(nonce, parts, meas, proof, blind0)
        geoms = {shape_manifest.entry_geometry(e["key"]) for e in man.entries()}
        assert geoms == {(eng.dp, eng.sp, eng._ndev)}
        # a single-device boot replaying this manifest must skip every
        # entry, distinctly counted — not trace programs it never runs
        monkeypatch.setenv("JANUS_MESH_DP", "1")
        monkeypatch.setenv("JANUS_MESH_SP", "1")
        eng2 = EngineCache(COUNT, bytes(range(16)))
        assert eng2.mesh is None
        w = prewarm._Warmer()
        outcomes = [w.warm(eng2, e) for e in man.entries()]
        assert outcomes and all(o == "geometry_mismatch" for o in outcomes)
        # covers() is geometry-aware the same way: the warmup would
        # still owe these compiles on the new topology
        assert not man.covers({"kind": "count"}, "leader_init", 32, geometry=None)
        assert man.covers(
            {"kind": "count"},
            "leader_init",
            32,
            geometry=(eng.dp, eng.sp, eng._ndev),
        )
    finally:
        shape_manifest.uninstall_manifest()
        prewarm.reset_for_tests()
