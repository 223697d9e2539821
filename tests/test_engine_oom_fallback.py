"""EngineCache OOM robustness: halve-the-bucket retry + host fallback.

Before r6 a device RESOURCE_EXHAUSTED in a serving round killed the
aggregation job (only bench.py had recovery). Now EngineCache absorbs
it: the bucket cap halves and the round retries in smaller chunks; at
the bucket floor the engine installs a permanent HostEngineCache and
the job completes at host speed. No exception may escape to the job
driver, and recovered results must be identical to a healthy engine's.
"""

import numpy as np
import pytest

from janus_tpu.aggregator.engine_cache import (
    DeviceRows,
    DeviceRowsChunks,
    EngineCache,
    HostEngineCache,
    bucket_size,
    is_oom_error,
)
from janus_tpu.vdaf.registry import VdafInstance
from janus_tpu.vdaf.testing import make_report_batch, random_measurements

VK = bytes(range(16))

# One instance + one module-scoped healthy reference engine: every test
# that needs an uncapped reference round reuses its compiled functions
# (three per-test EngineCaches used to recompile the identical bucket-32
# program set, ~19s each on the CPU tier-1 runner). Count keeps the
# trace/compile cost minimal — the subject here is the engine's OOM
# handling, which is circuit-independent; multi-element aggregation and
# window masking are covered by test_engine_coalesce.
INST = VdafInstance.count()


@pytest.fixture(scope="module")
def healthy():
    return EngineCache(INST, VK)

try:
    from jaxlib.xla_extension import XlaRuntimeError
except ImportError:  # pragma: no cover
    XlaRuntimeError = RuntimeError


def _oom():
    return XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying to allocate")


def _job(inst, n=4, seed=1):
    rng = np.random.default_rng(seed)
    meas = random_measurements(inst, n, rng)
    args, m = make_report_batch(inst, meas, seed=seed)
    return args, m


def _full_round(eng, args, n=4):
    """Leader init + helper init + both masked aggregates through the
    public engine surface (what the job drivers call)."""
    nonce, public, meas, proof, blind0, seeds, blind1 = args
    out0, seed0, ver0, part0 = eng.leader_init(nonce, public, meas, proof, blind0)
    out1, mask, _ = eng.helper_init(
        nonce, public, seeds, blind1, ver0, part0, np.ones(n, dtype=bool)
    )
    assert np.asarray(mask).all(), "honest reports must verify"
    agg0 = eng.aggregate(out0, mask)
    agg1 = eng.aggregate(out1, mask)
    p = eng.p3.jf.MODULUS
    return [(a + b) % p for a, b in zip(agg0, agg1)]


def _failing_jit(eng, n_failures: int, exc_factory=_oom):
    """Monkeypatch the engine's jit-call seam: the first n_failures
    compiled-step invocations raise (the acceptance's 'monkeypatched
    jit call'). Thread-safe — concurrent submitters must not over-fire
    the injection budget."""
    import threading

    orig = eng._jit
    lock = threading.Lock()
    state = {"left": n_failures, "raised": 0}

    def patched(name, fn, in_shardings=None):
        real = orig(name, fn, in_shardings=in_shardings)

        def wrapper(*a, **k):
            with lock:
                fire = state["left"] > 0
                if fire:
                    state["left"] -= 1
                    state["raised"] += 1
            if fire:
                raise exc_factory()
            return real(*a, **k)

        return wrapper

    eng._jit = patched
    return state


def test_is_oom_error_classifier():
    assert is_oom_error(_oom())
    assert is_oom_error(RuntimeError("XLA:TPU ran Out of memory"))
    assert not is_oom_error(ValueError("shape mismatch"))
    # an opaque compile-service 500 is an error, not an OOM
    assert not is_oom_error(RuntimeError("remote_compile: HTTP 500"))


def test_bucket_size_cap():
    assert bucket_size(40) == 64
    assert bucket_size(40, cap=16) == 16  # caller chunks to <= 16
    assert bucket_size(10, cap=16) == 16
    assert bucket_size(1, cap=1) == 1
    assert bucket_size(5) == 32  # MIN_BUCKET floor unchanged


def test_injected_oom_halves_bucket_and_succeeds(healthy):
    """One RESOURCE_EXHAUSTED from the jitted step: the round retries
    with a halved cap and completes with correct results."""
    inst = INST
    args, meas = _job(inst)
    want = _full_round(healthy, args)

    eng = EngineCache(inst, VK)
    # observed bucket for n=4 is MIN_BUCKET (32) — above the bucket
    # floor even on the conftest 8-virtual-device mesh (floor = dp)
    eng.bucket_cap = 32
    state = _failing_jit(eng, 1)
    got = _full_round(eng, args)
    assert got == want
    assert state["raised"] == 1
    assert eng.bucket_cap == 16  # halved from the observed bucket 32
    assert eng._host_fallback is None
    want_sum = np.atleast_1d(np.asarray(meas).sum(axis=0))
    assert got[: len(want_sum)] == [int(x) for x in want_sum]


def test_persistent_oom_falls_back_to_host_engine(healthy):
    """Every jit call raising RESOURCE_EXHAUSTED: the cap walks down to
    the bucket floor (1), the engine installs HostEngineCache, and the
    round still completes correctly — nothing escapes to the driver."""
    inst = INST
    args, meas = _job(inst)
    want = _full_round(healthy, args)

    eng = EngineCache(inst, VK)
    _failing_jit(eng, 10**9)
    got = _full_round(eng, args)
    assert got == want
    assert isinstance(eng._host_fallback, HostEngineCache)
    # subsequent rounds go straight to the host engine (no device call)
    got2 = _full_round(eng, _job(inst, seed=2)[0])
    healthy2 = _full_round(healthy, _job(inst, seed=2)[0])
    assert got2 == healthy2


def test_non_oom_errors_still_raise():
    inst = VdafInstance.count()
    args, _ = _job(inst)
    eng = EngineCache(inst, VK)
    _failing_jit(eng, 10**9, exc_factory=lambda: ValueError("bad trace"))
    nonce, public, meas, proof, blind0, seeds, blind1 = args
    with pytest.raises(ValueError, match="bad trace"):
        eng.leader_init(nonce, public, meas, proof, blind0)
    assert eng._host_fallback is None


def test_capped_batch_chunks_and_matches_uncapped(healthy):
    """A batch larger than the cap splits into serial cap-sized
    dispatches (DeviceRowsChunks) with results identical to the
    uncapped engine."""
    inst = INST
    ref = healthy
    # cap and batch scale with dp so each chunk stays mesh-dispatchable
    # (the conftest runs an 8-virtual-device mesh; dp divides buckets);
    # n stays inside the shared healthy engine's bucket so the uncapped
    # reference round reuses its compiled functions
    cap = max(8, ref.dp)
    n = 3 * cap
    assert bucket_size(n) == bucket_size(4), "reference must reuse the healthy bucket"
    args, meas = _job(inst, n=n, seed=3)
    want = _full_round(ref, args, n=n)

    eng = EngineCache(inst, VK)
    eng.bucket_cap = cap
    eng._coalesce = False  # force the direct (chunked) path
    nonce, public, meas_v, proof, blind0, seeds, blind1 = args
    out0, seed0, ver0, part0 = eng.leader_init(nonce, public, meas_v, proof, blind0)
    assert isinstance(out0, DeviceRowsChunks)
    assert [c.n for c in out0.chunks] == [cap, cap, cap]
    out1, mask, _ = eng.helper_init(
        nonce, public, seeds, blind1, ver0, part0, np.ones(n, dtype=bool)
    )
    assert isinstance(out1, DeviceRowsChunks)
    assert np.asarray(mask).all()
    agg0 = eng.aggregate(out0, mask)
    agg1 = eng.aggregate(out1, mask)
    p = eng.p3.jf.MODULUS
    got = [(a + b) % p for a, b in zip(agg0, agg1)]
    assert got == want


def test_coalesced_round_oom_halves_cap_once(healthy):
    """One OOM in a COALESCED round must halve the cap exactly once,
    from the dispatched round's bucket — not once per co-batched
    submitter from each submitter's own small n (which walked the cap
    to the floor and permanently installed the host fallback)."""
    from concurrent.futures import ThreadPoolExecutor

    inst = INST
    eng = EngineCache(inst, VK)
    eng.bucket_cap = 32
    state = _failing_jit(eng, 1)
    jobs = [_job(inst, seed=20 + j) for j in range(6)]
    wants = [_full_round(healthy, a) for a, _ in jobs]

    def run(args):
        return _full_round(eng, args)

    with ThreadPoolExecutor(max_workers=6) as pool:
        got = list(pool.map(run, [a for a, _ in jobs]))
    assert got == wants
    assert state["raised"] == 1
    # halved once from the failed dispatch's bucket (<= 32), never to
    # the floor: the device engine must survive one transient OOM
    assert eng.bucket_cap == 16
    assert eng._host_fallback is None


def test_stale_cap_gate_chunks_instead_of_negative_pad(healthy):
    """A call that passed the entry gate before a concurrent OOM halved
    the cap reaches the inner dispatch with n > cap; it must chunk
    (DeviceRowsChunks), not die in np.pad with a negative width."""
    inst = INST
    eng = EngineCache(inst, VK)
    cap = max(1, eng.dp)  # mesh dispatches need dp | bucket
    n = 2 * cap
    args, meas = _job(inst, n=n, seed=5)
    nonce, public, meas_v, proof, blind0, seeds, blind1 = args
    _, _, ver0, part0 = healthy.leader_init(nonce, public, meas_v, proof, blind0)
    eng.bucket_cap = cap  # as if halved after the caller's gate check
    # call the inner dispatch directly — the deterministic equivalent of
    # losing the entry-gate race
    out1, mask, _ = eng._helper_init_inner(
        nonce, public, seeds, blind1, ver0, part0, np.ones(n, dtype=bool)
    )
    assert isinstance(out1, DeviceRowsChunks)
    assert np.asarray(mask).all()


def test_persistent_aggregate_oom_on_resident_rows_terminates(healthy):
    """A DeviceRows aggregate re-dispatches at the BUFFER's fixed bucket
    no matter how far the cap halves, so a persistent OOM there can
    never reach the bucket floor. The engine must fetch and reduce THAT
    buffer on host — not spin forever in aggregate()'s retry loop, and
    not install the engine-wide host fallback for an OOM specific to
    one oversized resident buffer (init dispatches at smaller buckets
    would still work on device)."""
    inst = INST
    n = 4
    args, meas = _job(inst, n=n)
    nonce, public, meas_v, proof, blind0, seeds, blind1 = args
    out0, _, _, _ = healthy.leader_init(nonce, public, meas_v, proof, blind0)
    want = healthy.aggregate(out0, np.ones(n, dtype=bool))

    eng = EngineCache(inst, VK)

    # pre-annotated exceptions model the async case where the OOM
    # surfaces at the fetch and carries the fixed buffer-bucket mark —
    # without the host-side reduce this loops forever (cap pinned at
    # observed//2, floor unreachable) and the test would hang
    def _oom_fixed():
        e = _oom()
        e._janus_dispatch_bucket = out0.value[0].shape[0]
        e._janus_fixed_bucket = True
        return e

    _failing_jit(eng, 10**9, exc_factory=_oom_fixed)
    got = eng.aggregate(out0, np.ones(n, dtype=bool))
    assert got == want
    # the device path survives: no engine-wide fallback installed
    assert eng._host_fallback is None


def test_feasibility_cap_applied_at_construction(monkeypatch):
    """A pinned JANUS_HBM_BUDGET must produce a finite bucket cap from
    the model at construction time."""
    monkeypatch.setenv("JANUS_HBM_BUDGET", str(1 << 30))  # 1 GiB
    inst = VdafInstance.sum_vec(length=1000, bits=16)
    eng = EngineCache(inst, VK)
    assert eng.bucket_cap is not None
    assert eng.bucket_cap & (eng.bucket_cap - 1) == 0
    # coalescer rounds may never exceed the cap
    assert eng._co_leader._max_rows <= eng.bucket_cap
    assert eng._co_helper._max_rows <= eng.bucket_cap


def test_env_bucket_cap_override(monkeypatch):
    monkeypatch.setenv("JANUS_BUCKET_CAP", "16")
    inst = VdafInstance.count()
    eng = EngineCache(inst, VK)
    assert eng.bucket_cap == 16
    monkeypatch.setenv("JANUS_BUCKET_CAP", "0")
    eng2 = EngineCache(inst, VK)
    assert eng2.bucket_cap is None
