"""Chip measurement: draft-mode sequential sponge under nested scans.

Round 4 measured a single flat lax.scan squeeze going superlinear past
~32k blocks (1.9 s @ 32k vs 209 s @ 152k, batch 8) and capped the
draft device gate there. keccak_jax now chunks long chains into nested
scans (_SCAN_CHUNK); this script re-measures the knee and the batch
amortization the r4 verdict asked for (item 2): per-report cost at
batch 8 vs 64 vs 512, and a full draft SumVec len=100k prepare if the
squeeze proves linear.

Usage (the only process on the chip):
    python scripts/measure_draft_sponge.py
    python scripts/measure_draft_sponge.py --full-prepare --batch 64
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="8192,32768,152382")
    ap.add_argument("--batches", default="8,64,256")
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--full-prepare", action="store_true")
    ap.add_argument("--batch", type=int, default=64, help="for --full-prepare")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from janus_tpu.binary_utils import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    import janus_tpu.vdaf.keccak_jax as kj

    print(f"[sponge] backend={jax.default_backend()} chunk={kj._SCAN_CHUNK}", flush=True)

    def checksum_squeeze(batch, blocks):
        @jax.jit
        def f(msg):
            out = kj.shake128_squeeze_lanes(msg, blocks)
            return jnp.sum(out)

        return f

    rng = np.random.default_rng(1)
    for blocks in [int(b) for b in args.blocks.split(",")]:
        for batch in [int(b) for b in args.batches.split(",")]:
            msg = jnp.asarray(
                rng.integers(0, 1 << 63, size=(batch, 2, kj.RATE_LANES), dtype=np.uint64)
            )
            f = checksum_squeeze(batch, blocks)
            t0 = time.time()
            v = int(f(msg))
            compile_s = time.time() - t0
            t0 = time.time()
            for _ in range(args.iters):
                v = int(f(msg))
            per = (time.time() - t0) / args.iters
            print(
                json.dumps(
                    {
                        "squeeze_blocks": blocks,
                        "batch": batch,
                        "s_per_chain": round(per, 3),
                        "us_per_block": round(per / blocks * 1e6, 2),
                        "chain_per_report_s": round(per, 3),
                        "amortized_r_per_s": round(batch / per, 2),
                        "compile_s": round(compile_s, 1),
                    }
                ),
                flush=True,
            )

    if args.full_prepare:
        import dataclasses

        from janus_tpu.vdaf import draft_jax
        from janus_tpu.vdaf.registry import VdafInstance
        from janus_tpu.parallel.api import two_party_step
        from janus_tpu.vdaf.testing import make_report_batch, random_measurements

        draft_jax.Prio3BatchedDraft.MAX_STREAM_BLOCKS = 1 << 20  # lift the gate
        inst = VdafInstance.sum_vec(length=100_000, bits=16, chunk_length=0)
        inst = dataclasses.replace(inst, xof_mode="draft")
        batch = args.batch
        t0 = time.time()
        meas = random_measurements(inst, batch, rng)
        step_args, _ = make_report_batch(inst, meas, seed=1, shard_chunk=8)
        step_args = jax.device_put(step_args)
        jax.block_until_ready(step_args)
        print(f"[sponge] staging: {time.time()-t0:.1f}s", flush=True)
        step = jax.jit(two_party_step(inst, bytes(range(16))))
        t0 = time.time()
        out = step(*step_args)
        assert int(out[2]) == batch, int(out[2])
        print(f"[sponge] compile+first: {time.time()-t0:.1f}s", flush=True)
        t0 = time.time()
        iters = max(1, args.iters)
        for _ in range(iters):
            out = step(*step_args)
            assert int(out[2]) == batch
        per = (time.time() - t0) / iters
        print(
            json.dumps(
                {
                    "metric": "draft_sumvec_len100k_two_party",
                    "batch": batch,
                    "s_per_step": round(per, 2),
                    "r_per_s": round(batch / per, 2),
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
