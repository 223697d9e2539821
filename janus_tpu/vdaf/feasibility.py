"""HBM feasibility model for device prepare dispatches.

The round-5 measurements (ISSUE r5; link-era figure, not re-measured on the chip) showed the
device path at north-star lengths was capped not by compute but by HBM
capacity: batch 128 at SumVec len=100k wants 20.68 GB of a 15.75 GB v5e
budget, and the only knob — batch size — was picked blind (power-of-two
bucketing in aggregator.engine_cache) with a hard `XlaRuntimeError`
when the guess was wrong. This module is the shared answer:

- `device_memory_budget()` reads the accelerator's own accounting
  (`jax.local_devices()[0].memory_stats()`), or the `JANUS_HBM_BUDGET`
  env override (bytes). The CPU backend has none: it returns None and
  callers treat that as "unbounded". An accelerator without one is
  an error.
- `prepare_row_bytes()` estimates resident bytes per report row of a
  two-party prepare from the circuit geometry (input/proof/output/
  verifier lengths, limb width) plus the tiled working set (the
  streamed query's per-step tensors scale with the TILE, not
  input_len — vdaf.engine.stream_plan).
- `feasible_rows()` / `feasible_bucket()` turn that into the largest
  safe batch (power-of-two for the jit bucket cache).

The model is deliberately a first-order estimate with headroom, not a
buffer-assignment oracle: it picks the *starting* bucket; the runtime
halve-on-OOM retry in `aggregator.engine_cache.EngineCache` is the
backstop when the estimate is optimistic.
"""

from __future__ import annotations

import os

# Fraction of the reported budget the model is allowed to plan into.
# XLA needs slack for fusion temporaries, the compiler's own scratch,
# and donation gaps; 0.85 matches the measured len=100k fit (batch 256
# modeled at ~11.3 GB inside 15.75 GB).
DEFAULT_HEADROOM = 0.85

# Copies of a tile-sized tensor live at once inside one scan step of the
# streamed query (masked share, wire pair a/b or the MM fold operands,
# the XOF candidate stream, and XLA double-buffering of the carry).
TILE_WORKING_COPIES = 6

# Whole-share working copies for the untiled (short-circuit) query path:
# calls-inputs tensor, its r-power product, and the interleaved pairs.
UNTILED_WORKING_COPIES = 4


def device_memory_budget(device=None) -> int | None:
    """Usable accelerator memory in bytes; None on the CPU backend,
    which has no budget and stays uncapped.

    `JANUS_HBM_BUDGET` (bytes) overrides, so tests can pin the budget
    to exercise the model. An accelerator that reports no budget is an
    error: planning against an assumed size would hide it.
    """
    env = os.environ.get("JANUS_HBM_BUDGET")
    if env:
        return int(env)
    import jax

    if device is None:
        device = jax.local_devices()[0]
    if device.platform == "cpu":
        return None
    stats = device.memory_stats() or {}
    budget = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not budget:
        raise RuntimeError(
            f"{device.platform} device {device.device_kind} reports no memory "
            f"budget in memory_stats() ({sorted(stats)}); set JANUS_HBM_BUDGET"
        )
    return int(budget)


def _elem_bytes(circ) -> int:
    # one field element = LIMBS u64 lanes = ENCODED_SIZE bytes resident
    return circ.FIELD.ENCODED_SIZE


def prepare_row_bytes(circ, tile_elems: int | None = None, draft: bool = False) -> int:
    """Modeled resident bytes per report row of a two-party prepare.

    tile_elems: the streamed query's tile (group) size in input
    elements, or None when the whole-share path runs (short circuits).
    draft: the VDAF-07 framing materializes the full helper share (the
    sequential sponge has no random-access counter) plus its rejection
    candidate stream, so it pays O(input_len) regardless of tiling.
    """
    per = _elem_bytes(circ)
    n = circ.input_len
    # staged leader measurement share is device-resident for the whole
    # step; both proof shares, both verifier shares, both out shares.
    resident = n * per
    resident += 2 * circ.proof_len * per
    resident += 2 * circ.verifier_len * per
    resident += 2 * circ.output_len * per
    if tile_elems is not None and tile_elems < n:
        resident += TILE_WORKING_COPIES * tile_elems * per
    else:
        resident += UNTILED_WORKING_COPIES * n * per
    if draft:
        # materialized helper share + the ~1.5x candidate stream the
        # rejection sampler reads it from (24 raw bytes per F128 lane
        # pair amortizes to ~1.5 resident copies)
        resident += int(2.5 * n * per)
    return resident


def feasible_rows(
    circ,
    budget_bytes: int | None,
    tile_elems: int | None = None,
    draft: bool = False,
    headroom: float = DEFAULT_HEADROOM,
) -> int | None:
    """Largest report-row count the budget supports, or None (unbounded)
    when the budget is unknown. Always at least 1: a budget too small
    for one row still returns 1 and lets the runtime OOM retry make the
    final call (host fallback)."""
    if budget_bytes is None:
        return None
    row = prepare_row_bytes(circ, tile_elems=tile_elems, draft=draft)
    return max(1, int(budget_bytes * headroom) // max(1, row))


def feasible_bucket(
    circ,
    budget_bytes: int | None,
    tile_elems: int | None = None,
    draft: bool = False,
    headroom: float = DEFAULT_HEADROOM,
) -> int | None:
    """Largest power-of-two batch bucket within the budget (None =
    unbounded). This is the adaptive replacement for the blind
    `bucket_size(n)` growth in aggregator.engine_cache."""
    rows = feasible_rows(circ, budget_bytes, tile_elems=tile_elems, draft=draft, headroom=headroom)
    if rows is None:
        return None
    b = 1
    while b * 2 <= rows:
        b *= 2
    return b


def describe(circ, tile_elems: int | None = None, draft: bool = False, budget_bytes=None) -> dict:
    """One JSON-able snapshot of the model for a circuit — used by
    `bench.py --dry-run` and surfaced in the bench JSON so every run
    records the bucket the model chose and why."""
    if budget_bytes is None:
        budget_bytes = device_memory_budget()
    row = prepare_row_bytes(circ, tile_elems=tile_elems, draft=draft)
    return {
        "input_len": circ.input_len,
        "proof_len": circ.proof_len,
        "verifier_len": circ.verifier_len,
        "output_len": circ.output_len,
        "elem_bytes": _elem_bytes(circ),
        "tile_elems": tile_elems,
        "row_bytes": row,
        "budget_bytes": budget_bytes,
        "headroom": DEFAULT_HEADROOM,
        "feasible_rows": feasible_rows(circ, budget_bytes, tile_elems=tile_elems, draft=draft),
        "feasible_bucket": feasible_bucket(circ, budget_bytes, tile_elems=tile_elems, draft=draft),
    }
