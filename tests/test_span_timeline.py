"""The program's spans on the profiler's timeline and the wait counters
behind them: `trace.span` inside a `jax.profiler` session lands in the
captured `.xplane.pb` by name and nesting; `run_tx` splits each
transaction into lock_wait / body / commit / retry_wait
(janus_database_transaction_phase_seconds); the helper's init spans
feed janus_aggregate_init_stage_seconds; `EngineCache._jit` names the
lowered module after the program."""

from __future__ import annotations

import glob
import os
import threading
import time

import numpy as np
import pytest

from janus_tpu import metrics, trace
from janus_tpu.datastore.store import EphemeralDatastore, TxConflict
from janus_tpu.trace import span


def _hist(name: str, **labels) -> tuple[int, float]:
    """(count, sum) of a registry histogram over the label sets that
    carry `labels`."""
    doc = metrics.REGISTRY.snapshot().get(name, {"samples": []})
    n = s = 0
    for sample in doc["samples"]:
        if all(sample["labels"].get(k) == v for k, v in labels.items()):
            n += sample["count"]
            s += sample["sum"]
    return n, s


def _delta(before, after):
    return after[0] - before[0], after[1] - before[1]


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------


def _xplane_events(trace_dir: str) -> list:
    """(name, (plane, line), start ns, end ns) of every host-plane
    event; a host line is one thread."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, (plane.name, line.name), ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_span_lands_in_xplane_nested_under_parent(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test.window"):
            with span("test.parent_span", tx="x"):
                time.sleep(0.002)
                with span("test.child_span"):
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    events = {name: (line, s, e) for name, line, s, e in _xplane_events(str(tmp_path))}
    assert {"test.window", "test.parent_span", "test.child_span"} <= set(events)
    w_line, w_s, w_e = events["test.window"]
    p_line, p_s, p_e = events["test.parent_span"]
    c_line, c_s, c_e = events["test.child_span"]
    # one thread, one line: the spans nest in time inside the window
    assert w_line == p_line == c_line
    assert w_s <= p_s <= c_s < c_e <= p_e <= w_e
    assert c_e - c_s >= 1_000_000  # the child's 2 ms body, in ns


def test_span_opens_no_annotation_without_profiler_session(monkeypatch):
    from jax.profiler import TraceAnnotation

    opened = []

    class Counting(TraceAnnotation):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(trace, "TraceAnnotation", Counting)
    assert not TraceAnnotation.is_enabled()
    with span("test.no_session"):
        pass
    assert opened == []


def test_span_opens_annotation_only_while_profiler_records(tmp_path, monkeypatch):
    import jax
    from jax.profiler import TraceAnnotation

    opened = []

    class Counting(TraceAnnotation):
        def __init__(self, name, **kw):
            opened.append((name, set(kw)))
            super().__init__(name, **kw)

    monkeypatch.setattr(trace, "TraceAnnotation", Counting)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("test.in_session", vdaf="count"):
            pass
    finally:
        jax.profiler.stop_trace()
    with span("test.after_session"):
        pass
    # the name and the trace id only: span args stay off the hot path
    assert opened == [("test.in_session", {"trace_id"})]


# ---------------------------------------------------------------------------
# run_tx phases
# ---------------------------------------------------------------------------


@pytest.fixture
def ds():
    eph = EphemeralDatastore()
    yield eph.datastore
    eph.cleanup()


def _phases(tx_name: str) -> dict:
    return {
        p: _hist("janus_database_transaction_phase_seconds", tx=tx_name, phase=p)
        for p in ("lock_wait", "body", "commit", "retry_wait")
    }


def test_run_tx_phases_sum_to_duration_observation(ds):
    name = "phase_sum_probe"
    before = _phases(name)
    dur0 = _hist("janus_database_transaction_duration_seconds", tx=name)

    def body(tx):
        time.sleep(0.02)
        return tx.get_tasks()

    ds.run_tx(body, name)
    after = _phases(name)
    n_dur, s_dur = _delta(dur0, _hist("janus_database_transaction_duration_seconds", tx=name))
    assert n_dur == 1
    deltas = {p: _delta(before[p], after[p]) for p in before}
    assert {p: n for p, (n, _) in deltas.items()} == {
        "lock_wait": 1, "body": 1, "commit": 1, "retry_wait": 0,
    }
    assert deltas["body"][1] >= 0.02
    assert sum(s for _, s in deltas.values()) == pytest.approx(s_dur, abs=1e-3)


def test_run_tx_lock_wait_behind_held_begin_immediate(ds):
    held = threading.Event()
    release = threading.Event()

    def hold(tx):
        held.set()
        release.wait(10)

    holder = threading.Thread(target=ds.run_tx, args=(hold, "lock_holder"))
    holder.start()
    assert held.wait(10)
    name = "lock_waiter_probe"
    before = _phases(name)
    threading.Timer(0.25, release.set).start()
    ds.run_tx(lambda tx: tx.get_tasks(), name)
    holder.join(10)
    after = _phases(name)
    n_lock, s_lock = _delta(before["lock_wait"], after["lock_wait"])
    n_body, s_body = _delta(before["body"], after["body"])
    assert n_lock == 1 and s_lock >= 0.2
    # the wait is told apart from the work
    assert n_body == 1 and s_body < 0.1


def test_run_tx_retry_wait_under_injected_conflict(ds):
    name = "retry_probe"
    before = _phases(name)
    dur0 = _hist("janus_database_transaction_duration_seconds", tx=name)
    calls = []

    def body(tx):
        calls.append(1)
        if len(calls) == 1:
            raise TxConflict("injected for the test")
        return tx.get_tasks()

    ds.run_tx(body, name)
    after = _phases(name)
    deltas = {p: _delta(before[p], after[p]) for p in before}
    assert len(calls) == 2
    # two attempts: two lock waits and bodies, one commit, one backoff
    assert {p: n for p, (n, _) in deltas.items()} == {
        "lock_wait": 2, "body": 2, "commit": 1, "retry_wait": 1,
    }
    _, s_dur = _delta(dur0, _hist("janus_database_transaction_duration_seconds", tx=name))
    assert sum(s for _, s in deltas.values()) == pytest.approx(s_dur, abs=1e-3)


# ---------------------------------------------------------------------------
# helper init stages, program names
# ---------------------------------------------------------------------------


def test_helper_spans_feed_init_stage_histogram():
    stages = ("hpke_stage", "replay_tx", "columnar", "accumulate", "write_tx")
    before = {s: _hist("janus_aggregate_init_stage_seconds", stage=s) for s in stages}
    for s in stages:
        with span(f"helper.{s}", batch=3):
            time.sleep(0.001)
    for s in stages:
        n, total = _delta(before[s], _hist("janus_aggregate_init_stage_seconds", stage=s))
        assert n == 1 and total >= 0.001, s


def test_engine_jit_lowers_module_named_after_program(monkeypatch):
    from janus_tpu.aggregator import aot_cache
    from janus_tpu.aggregator.engine_cache import EngineCache
    from janus_tpu.vdaf.registry import VdafInstance

    # the single-device path: _jit hands back the AOT wrapper itself
    monkeypatch.setenv("JANUS_MESH_DP", "1")
    monkeypatch.setenv("JANUS_MESH_SP", "1")
    eng = EngineCache(VdafInstance.count(), bytes(range(16)))
    assert eng.mesh is None

    def step(x):
        return x + 1

    fn = eng._jit("probe_program", step)
    text = fn._jitted.lower(np.zeros(4, np.uint32)).as_text()
    assert "module @jit_probe_program" in text
    assert "jit_step" not in text
    # the AOT key names the module, so no blob of an unnamed
    # (`jit_step`) module is looked up under the new name
    base = aot_cache.engine_base(eng.inst.to_dict(), eng.verify_key, "probe_program")
    assert "module:jit_probe_program" in base.split("|")


@pytest.mark.parametrize("name", ["keccak_f1600", "keccak_single_block", "expand_f128"])
def test_pallas_kernel_carries_its_name_when_lowered_for_tpu(name):
    """Lowered for the TPU (no chip needed), each Mosaic kernel carries
    its name, so the trace tells the kernels apart by name."""
    import jax
    import jax.numpy as jnp

    from janus_tpu.ops import expand_pallas, keccak_pallas

    rows = keccak_pallas._TILE_ROWS
    call, args = {
        "keccak_f1600": (
            keccak_pallas._call(rows, False),
            (jax.ShapeDtypeStruct((50, rows, 128), jnp.uint32),),
        ),
        "keccak_single_block": (
            keccak_pallas._call_single(rows, False, 2),
            (jax.ShapeDtypeStruct((42, rows, 128), jnp.uint32),),
        ),
        "expand_f128": (
            expand_pallas._call(5, 8, 1, 128, False),
            (
                jax.ShapeDtypeStruct((1,), jnp.int32),
                jax.ShapeDtypeStruct((8, 128), jnp.uint32),
            ),
        ),
    }[name]
    text = jax.jit(call).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    assert name in text
