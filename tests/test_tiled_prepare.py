"""Tiled (fixed-size length-tile) FLP prepare: memory bound + identity.

The r6 tentpole makes device prepare memory-BOUNDED instead of
memory-proportional: the streamed query's scan tile is clamped to
STREAM_TILE_ELEMS, so peak live bytes scale with batch x TILE rather
than batch x input_len. These tests prove:

- the tile geometry is length-independent past the clamp (the O(TILE)
  claim, host math only);
- the jit-compiled memory analysis of the helper prepare at the
  north-star config (SumVec len=100k, batch 256) fits the 15.75 GB
  v5e HBM budget — the configuration round 5 measured at 20.68 GB
  with batch 128 under the proportional plan;
- forcing tiny multi-step tiles produces BIT-IDENTICAL prepare outputs
  to the untiled whole-share engine across Count/Sum/SumVec/Histogram
  (Count/Sum take the untiled path by design — the equality asserts
  the dispatch as well as the math).
"""

import numpy as np
import pytest

from janus_tpu.vdaf import engine
from janus_tpu.vdaf.prio3_jax import Prio3Batched
from janus_tpu.vdaf.reference import Count, Histogram, Sum, SumVec
from janus_tpu.vdaf.registry import VdafInstance

VK = bytes(range(16))

V5E_HBM_BYTES = int(15.75 * (1 << 30))


def test_tile_size_length_independent():
    """Past the clamp the tile stops growing with input_len: the scan's
    per-step working set is O(batch x TILE) by construction. Pinned to
    an alignment-friendly chunk (2520 = 56*45) — with the sqrt-default
    chunk the tile floors at the lcm(7,bits)-alignment quantum instead
    (asserted separately below)."""
    plans = {
        n: engine.stream_plan(engine.batched_circuit(SumVec(n, 16, chunk_length=2520)))
        for n in (100_000, 200_000, 400_000)
    }
    groups = {n: p.group for n, p in plans.items()}
    assert all(p is not None for p in plans.values())
    # identical tile at every length: 4x the length = 4x the steps,
    # NOT 4x the per-step working set (the proportional r5 plan)
    assert groups[100_000] == groups[200_000] == groups[400_000], groups
    assert groups[100_000] <= engine.STREAM_TILE_ELEMS
    assert plans[400_000].n_steps > 2 * plans[100_000].n_steps


def test_tile_bounded_for_default_chunks():
    """Default (sqrt-heuristic) chunks may be coprime with the
    lcm(7,bits) alignment, flooring the tile at one alignment quantum
    a*ch — bounded by max(clamp, quantum) + rounding, never
    input_len-proportional."""
    for n in (100_000, 400_000):
        circ = SumVec(n, 16)
        plan = engine.stream_plan(engine.batched_circuit(circ))
        ch = circ.chunk_length
        import math

        align = math.lcm(7, 16)
        a = align // math.gcd(align, ch)
        bound = max(engine.STREAM_TILE_ELEMS + a * ch // 2, a * ch)
        assert plan.group <= bound, (n, plan.group, bound)
        assert plan.group < circ.input_len  # strictly sub-proportional


def test_short_streams_keep_target_step_plan():
    """Below the clamp the r5 8-step optimum is unchanged."""
    bc = engine.batched_circuit(SumVec(10_000, 16))
    plan = engine.stream_plan(bc)
    assert plan is not None
    assert plan.n_steps <= engine._STREAM_TARGET_STEPS + 1


def test_len100k_batch256_fits_v5e_hbm():
    """North-star acceptance: jit-compiled memory analysis of the
    helper prepare (share expansion + tiled query + truncate) at
    SumVec len=100k batch=256 stays under the 15.75 GB v5e budget."""
    import jax
    import jax.numpy as jnp

    from janus_tpu.parallel.api import helper_init_step

    inst = VdafInstance.sum_vec(length=100_000, bits=16)
    step = helper_init_step(inst, VK)
    B = 256
    u64 = jnp.uint64
    args = (
        jax.ShapeDtypeStruct((B, 2), u64),  # nonce lanes
        jax.ShapeDtypeStruct((B, 2, 2), u64),  # public parts
        jax.ShapeDtypeStruct((B, 2), u64),  # helper seed
        jax.ShapeDtypeStruct((B, 2), u64),  # blind
    )
    compiled = jax.jit(step).lower(*args).compile()
    ma = compiled.memory_analysis()
    total = (
        ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
    )
    assert total < V5E_HBM_BYTES, f"{total / 2**30:.2f} GiB exceeds the v5e budget"
    # and the feasibility model agrees this batch is admissible
    from janus_tpu.vdaf.feasibility import feasible_bucket

    plan = engine.stream_plan(engine.batched_circuit(SumVec(100_000, 16)))
    assert feasible_bucket(
        SumVec(100_000, 16), V5E_HBM_BYTES, tile_elems=plan.group
    ) >= 256


def _rand_lanes(rng, batch, n):
    return rng.integers(0, 1 << 63, size=(batch, n), dtype=np.uint64)


TILED_CIRCUITS = [
    Count(),
    Sum(bits=8),
    SumVec(40, 16, chunk_length=5),
    Histogram(200, chunk_length=9),
]


@pytest.mark.parametrize(
    "circ", TILED_CIRCUITS, ids=["count", "sum", "sumvec", "histogram"]
)
def test_tiled_prepare_bit_identical(circ, monkeypatch):
    """Forced tiny tiles (multi-step scan) == untiled whole-share
    prepare, bit for bit, for both aggregators. Count/Sum never tile
    (stream_plan returns None) — the equality also locks that in."""
    p3 = Prio3Batched(circ)
    rng = np.random.default_rng(17)
    batch = 3
    nonce = _rand_lanes(rng, batch, 2)
    helper_seed = _rand_lanes(rng, batch, 2)
    blind = _rand_lanes(rng, batch, 2) if p3.uses_joint_rand else None
    public_parts = (
        np.stack([_rand_lanes(rng, batch, 2), _rand_lanes(rng, batch, 2)], axis=1)
        if p3.uses_joint_rand
        else None
    )
    jf = p3.jf
    meas = tuple(
        rng.integers(0, 1 << 62, size=(batch, circ.input_len), dtype=np.uint64)
        for _ in range(jf.LIMBS)
    )
    proof = tuple(
        rng.integers(0, 1 << 62, size=(batch, circ.proof_len), dtype=np.uint64)
        for _ in range(jf.LIMBS)
    )

    def both():
        h = p3.prepare_init_helper(VK, nonce, public_parts, helper_seed, blind)
        l = p3.prepare_init_leader(VK, nonce, public_parts, meas, proof, blind)
        return h, l

    # tiled: activation threshold 1, tile clamped to a few gadget-call
    # alignment quanta so every circuit that CAN tile takes >1 step
    ch = getattr(circ, "chunk_length", 0)
    monkeypatch.setattr(engine, "STREAM_MIN_INPUT_LEN", 1)
    monkeypatch.setattr(engine, "STREAM_TILE_ELEMS", 8 * ch if ch else 8)
    plan = engine.stream_plan(p3.bc)
    if type(circ) in (SumVec, Histogram):
        assert plan is not None and plan.n_steps > 1, "tiling must engage"
    else:
        assert plan is None
    tiled_h, tiled_l = both()

    # untiled reference engine
    monkeypatch.setattr(engine, "STREAM_MIN_INPUT_LEN", 1 << 60)
    flat_h, flat_l = both()

    for tiled, flat in ((tiled_h, flat_h), (tiled_l, flat_l)):
        for t, f in zip(tiled, flat):
            if t is None:
                assert f is None
                continue
            if isinstance(t, tuple):
                for a, b in zip(t, f):
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            else:
                np.testing.assert_array_equal(np.asarray(t), np.asarray(f))


def test_tiled_two_party_step_end_to_end(monkeypatch):
    """Shard + tiled prepare + decide + aggregate: every report
    accepted, aggregate equals the true sum (SumVec on the multi-step
    tile plan)."""
    import jax

    from janus_tpu.parallel.api import two_party_step
    from janus_tpu.vdaf.registry import prio3_batched
    from janus_tpu.vdaf.testing import make_report_batch, random_measurements

    monkeypatch.setattr(engine, "STREAM_MIN_INPUT_LEN", 1)
    monkeypatch.setattr(engine, "STREAM_TILE_ELEMS", 250)
    inst = VdafInstance.sum_vec(length=21, bits=4)
    rng = np.random.default_rng(7)
    meas = random_measurements(inst, 4, rng)
    step_args, _ = make_report_batch(inst, meas, seed=3)
    agg0, agg1, count = jax.jit(two_party_step(inst, VK))(*step_args)
    assert int(count) == 4
    p3 = prio3_batched(inst)
    vals = p3.jf.to_ints(p3.merge_agg_shares(agg0, agg1))
    np.testing.assert_array_equal(
        np.asarray([int(v) for v in vals]), np.asarray(meas).sum(axis=0)
    )


def test_feasibility_model_basics(monkeypatch):
    from janus_tpu.vdaf import feasibility as fz

    circ = SumVec(100_000, 16)
    plan = engine.stream_plan(engine.batched_circuit(circ))
    # unbounded when the budget is unknown
    assert fz.feasible_bucket(circ, None, tile_elems=plan.group) is None
    # power-of-two, monotone in budget
    b1 = fz.feasible_bucket(circ, V5E_HBM_BYTES, tile_elems=plan.group)
    b2 = fz.feasible_bucket(circ, 2 * V5E_HBM_BYTES, tile_elems=plan.group)
    assert b1 & (b1 - 1) == 0 and b2 >= b1
    # tiled rows dominate untiled rows at long lengths
    assert fz.prepare_row_bytes(circ, tile_elems=plan.group) < fz.prepare_row_bytes(circ)
    # draft pays the materialized share regardless of tiling
    assert fz.prepare_row_bytes(circ, tile_elems=plan.group, draft=True) > fz.prepare_row_bytes(
        circ, tile_elems=plan.group
    )
    # env override wins
    monkeypatch.setenv("JANUS_HBM_BUDGET", "12345")
    assert fz.device_memory_budget() == 12345


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform = platform
        self.device_kind = f"fake {platform}"
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize(
    "platform,stats,want",
    [
        ("cpu", None, None),  # the CPU has no budget: uncapped
        ("tpu", {"bytes_limit": 16 << 30, "peak_bytes_in_use": 0}, 16 << 30),
        ("tpu", {}, RuntimeError),  # never plan against an assumed size
    ],
    ids=["cpu-uncapped", "tpu-memory-stats", "tpu-no-stats-error"],
)
def test_device_memory_budget_by_platform(monkeypatch, platform, stats, want):
    from janus_tpu.vdaf import feasibility as fz

    monkeypatch.delenv("JANUS_HBM_BUDGET", raising=False)
    dev = _FakeDevice(platform, stats)
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="no memory budget"):
            fz.device_memory_budget(dev)
    else:
        assert fz.device_memory_budget(dev) == want


def test_draft_device_gate_consults_budget():
    """vdaf.draft_jax device support is gated on the feasibility bound,
    not just MAX_STREAM_BLOCKS (r6 tentpole)."""
    from janus_tpu.vdaf.draft_jax import Prio3BatchedDraft

    circ = Sum(bits=8)
    # stream-length-eligible circuit: budget-unknown keeps legacy yes
    assert Prio3BatchedDraft.supports_circuit(circ, budget_bytes=None)
    # a budget too small for MIN_DEVICE_ROWS materialized shares: no
    assert not Prio3BatchedDraft.supports_circuit(circ, budget_bytes=1024)
    # ample budget: yes
    assert Prio3BatchedDraft.supports_circuit(circ, budget_bytes=1 << 34)
