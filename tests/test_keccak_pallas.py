"""Pallas Keccak kernel vs the scan-based XLA path.

Always-on in default CI: the kernels are round-parameterized, so on
CPU the differentials run the full kernel plumbing (u32-pair relayout,
tiling, padding, grid, dispatch threshold) at ROUNDS=2 in interpret
mode — the 24-round unrolled body is the only thing too slow for a
single-core interpret compile, and the round function is identical at
any count. On TPU (or with JANUS_PALLAS_TESTS=1 on a many-core host)
the same tests run at the full 24 rounds; the scan path they compare
against is pinned to hashlib at 24 rounds by tests/test_keccak.py,
which always runs.
"""

import os

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from janus_tpu.vdaf import keccak_jax as kj
from janus_tpu.ops import keccak_pallas as kp

FULL = os.environ.get("JANUS_PALLAS_TESTS") == "1" or jax.default_backend() == "tpu"
ROUNDS = 24 if FULL else 2


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    if jax.default_backend() != "tpu":
        monkeypatch.setattr(kp, "_mode", lambda: "interpret")
    yield


@pytest.mark.parametrize("shape", [(4, 129)])  # pads 516 -> 1024 columns
def test_pallas_permutation_matches_scan(shape):
    rng = np.random.default_rng(sum(shape))
    state = tuple(
        jnp.asarray(rng.integers(0, 1 << 63, size=shape, dtype=np.uint64))
        for _ in range(25)
    )

    def scan_path(st):
        out, _ = jax.lax.scan(
            lambda a, rc: (kj._keccak_round(a, rc), None),
            st,
            jnp.asarray(kj._RC[:ROUNDS]),
        )
        return out

    want = scan_path(state)
    got = kp.keccak_f1600_pallas(state, rounds=ROUNDS)
    for lane, (w, g) in enumerate(zip(want, got)):
        assert (np.asarray(w) == np.asarray(g)).all(), lane


def test_pallas_stream_matches_oracle(monkeypatch):
    """Full ctr stream through the kernel path. At 24 rounds the oracle
    is hashlib (XofCtr128); at reduced rounds it is the scan path at
    the same count — either way the kernel's relayout, MIN_COLUMNS
    dispatch, and counter framing are exercised end to end."""
    from janus_tpu.vdaf.xof import XofCtr128, dst

    monkeypatch.setattr(kp, "MIN_COLUMNS", 0)
    d = dst(0x42, 2)
    seed = bytes(range(16))
    seed_lanes = jnp.asarray(kj.bytes_to_lanes(seed)[None, :])
    parts = [(0, d), (2, seed_lanes)]

    if FULL:
        got = np.asarray(kj.ctr_stream_lanes(parts, 32, 1, 3))
        want = XofCtr128(seed, d).next(3 * 168)
        assert got[0].reshape(-1).astype("<u8").tobytes() == want
        return

    # reduced rounds through BOTH paths: kernel (interpret) vs scan —
    # KECCAK_ROUNDS governs every dispatch site incl. the single-block
    # kernel the ctr path now uses
    monkeypatch.setattr(kj, "KECCAK_ROUNDS", ROUNDS)
    got = np.asarray(kj.ctr_stream_lanes(parts, 32, 1, 3))
    monkeypatch.setattr(kp, "_mode", lambda: "off")
    want = np.asarray(kj.ctr_stream_lanes(parts, 32, 1, 3))
    assert (got == want).all()


def test_single_block_kernel_matches_general(monkeypatch):
    """The 42-in/2N-out single-block kernel equals the general 50/50
    kernel's first lanes on the same messages (interpret mode, ROUNDS)."""
    rng = np.random.default_rng(9)
    shape = (3, 200)  # pads 600 -> 1024 columns
    rate = tuple(
        jnp.asarray(rng.integers(0, 1 << 63, size=shape, dtype=np.uint64))
        for _ in range(21)
    )
    state = rate + tuple(jnp.zeros(shape, jnp.uint64) for _ in range(4))
    want = kp.keccak_f1600_pallas(state, rounds=ROUNDS)
    for out_lanes in (2, 21):
        got = kp.keccak_single_block_pallas(rate, out_lanes, rounds=ROUNDS)
        assert len(got) == out_lanes
        for i in range(out_lanes):
            assert (np.asarray(got[i]) == np.asarray(want[i])).all(), i


def _kernel_site(name: str):
    """One kernel call site of keccak_jax, past its size threshold."""
    from janus_tpu.fields.jfield import JF128

    rng = np.random.default_rng(11)

    def lanes(n):
        return tuple(
            jnp.asarray(rng.integers(0, 1 << 63, size=(256, 128), dtype=np.uint64))
            for _ in range(n)
        )

    if name == "permutation":
        state = lanes(25)
        return lambda: kj.keccak_f1600(state)
    if name == "single_block":
        rate = lanes(21)
        return lambda: kj._single_block_keccak(rate, out_lanes=2)
    seeds = jnp.asarray(rng.integers(0, 1 << 63, size=(2, 2), dtype=np.uint64))
    parts = [(0, bytes(range(16))), (2, seeds)]
    return lambda: kj.expand_field_vec(JF128, parts, 32, 2, 500)


@pytest.mark.parametrize("site", ["permutation", "single_block", "expand"])
def test_kernels_give_way_in_programs_lowered_for_the_host(monkeypatch, site):
    """A TPU process's clients shard under jax.default_device(<cpu>):
    what they trace is lowered for the CPU, where a Mosaic kernel cannot
    run. With the kernels on, such a program runs the scan path
    (lax.platform_dependent) and agrees with the kernels-off program."""
    fn = _kernel_site(site)
    monkeypatch.setattr(kp, "_mode", lambda: "off")
    want = jax.tree_util.tree_leaves(jax.jit(fn)())
    monkeypatch.setattr(kp, "_mode", lambda: "tpu")
    with jax.default_device(jax.devices("cpu")[0]):
        got = jax.tree_util.tree_leaves(jax.jit(fn)())
    assert len(got) <= len(want)
    for w, g in zip(want, got):
        assert (np.asarray(w) == np.asarray(g)).all()


def test_multi_device_tpu_turns_kernels_off_visibly(monkeypatch, caplog):
    monkeypatch.undo()  # the real gate, not this module's interpret-mode patch
    monkeypatch.delenv("JANUS_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [None] * 4)
    kp._mode.cache_clear()
    try:
        with caplog.at_level("WARNING", logger=kp.__name__):
            assert kp._mode() == "off"
        assert "Pallas kernels OFF" in caplog.text
        assert kp.status() == {
            "mode": "off",
            "reason": "4 TPU devices: no SPMD rule for pallas_call",
        }
    finally:
        kp._mode.cache_clear()
