"""Component-level timing of the two-party SumVec step on the chip.

Times each stage of the prepare pipeline separately under fetch-forced
timing (a value fetch proves completion). Every component is wrapped
in a jit that reduces its outputs to one u64 checksum so the fetch is
O(1) bytes.

Usage (the only process on the chip):
    python scripts/profile_components.py --batch 2048 --length 1000
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
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--length", type=int, default=1000)
    ap.add_argument("--bits", type=int, default=16)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--only", default="", help="comma list of component names")
    ap.add_argument("--cpu", action="store_true", help="pin the CPU backend")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from janus_tpu.binary_utils import enable_compile_cache

    enable_compile_cache()

    backend = jax.default_backend()
    print(f"[profile] backend={backend}", flush=True)

    from janus_tpu.vdaf.registry import VdafInstance, prio3_batched
    from janus_tpu.vdaf.engine import flp_query_batched, flp_decide_batched
    from janus_tpu.vdaf.xof import USAGE_MEASUREMENT_SHARE, USAGE_PROOF_SHARE
    from janus_tpu.parallel.api import two_party_step
    from janus_tpu.vdaf.testing import make_report_batch, random_measurements

    inst = VdafInstance.sum_vec(length=args.length, bits=args.bits)
    p3 = prio3_batched(inst)
    bc = p3.bc
    jf = p3.jf
    circ = p3.circ
    B = args.batch
    print(
        f"[profile] input_len={circ.input_len} proof_len={circ.proof_len} "
        f"chunk={circ.chunk_length} calls={bc.calls} m={bc.m} gp_len={bc.gp_len}",
        flush=True,
    )

    rng = np.random.default_rng(0x50F11E)
    verify_key = bytes(range(16))

    def rand_field(shape):
        lo = jnp.asarray(rng.integers(0, 1 << 63, size=shape, dtype=np.uint64))
        if jf.LIMBS == 1:
            return (lo,)
        hi = jnp.asarray(rng.integers(0, 1 << 62, size=shape, dtype=np.uint64))
        return (lo, hi)

    def rand_lanes(shape):
        return jnp.asarray(rng.integers(0, 1 << 63, size=shape, dtype=np.uint64))

    def checksum(tree):
        leaves = jax.tree_util.tree_leaves(tree)
        acc = jnp.uint64(0)
        for x in leaves:
            acc = acc + jnp.sum(x.astype(jnp.uint64))
        return acc

    timings = {}

    def timeit(name, fn, *a):
        if args.only and name not in args.only.split(","):
            return
        f = jax.jit(lambda *xs: checksum(fn(*xs)))
        t0 = time.time()
        v = int(f(*a))
        compile_s = time.time() - t0
        t0 = time.time()
        for _ in range(args.iters):
            v = int(f(*a))
        per = (time.time() - t0) / args.iters
        timings[name] = per
        print(
            json.dumps(
                {
                    "component": name,
                    "s_per_call": round(per, 4),
                    "us_per_report": round(per / B * 1e6, 2),
                    "rps": round(B / per, 1),
                    "compile_s": round(compile_s, 1),
                }
            ),
            flush=True,
        )
        return v

    # --- staged inputs (device-resident before timing) ---
    helper_seed = rand_lanes((B, 2))
    nonce = rand_lanes((B, 2))
    blind = rand_lanes((B, 2))
    meas = rand_field((B, circ.input_len))
    proof = rand_field((B, circ.proof_len))
    qr = rand_field((B, circ.query_rand_len))
    jr = rand_field((B, circ.joint_rand_len))
    (helper_seed, nonce, blind, meas, proof, qr, jr) = jax.device_put(
        (helper_seed, nonce, blind, meas, proof, qr, jr)
    )
    jax.block_until_ready((helper_seed, nonce, blind, meas, proof, qr, jr))

    # 1. XOF expansion of the helper measurement share (the dominant
    #    op count)
    timeit(
        "expand_meas",
        lambda s: p3._expand_share(s, USAGE_MEASUREMENT_SHARE, circ.input_len),
        helper_seed,
    )
    # 2. proof-share expansion
    timeit(
        "expand_proof",
        lambda s: p3._expand_share(s, USAGE_PROOF_SHARE, circ.proof_len),
        helper_seed,
    )
    # 3. FLP query on staged shares (leader-shaped: no expansion)
    timeit(
        "flp_query",
        lambda m, p, q, j: flp_query_batched(bc, m, p, q, j, 2),
        meas,
        proof,
        qr,
        jr,
    )
    # 4. truncate + masked aggregate
    def trunc_agg(m):
        out = bc.truncate(m)
        mask = jnp.ones((B,), bool)
        return p3.aggregate(out, mask)

    timeit("truncate_aggregate", trunc_agg, meas)
    # 5. joint-rand derivation chain (leader binder = full share enc)
    timeit(
        "joint_rand_chain",
        lambda b, n, m: p3._joint_rand_part(0, b, n, p3._part_binder(0, m, None)),
        blind,
        nonce,
        meas,
    )
    # 6. helper init (expansion + query fused by XLA)
    from janus_tpu.parallel.api import helper_init_step

    hi_step = helper_init_step(inst, verify_key)
    public_parts = rand_lanes((B, 2, 2))
    timeit("helper_init", hi_step, nonce, public_parts, helper_seed, blind)

    # 7. full two-party step with real staged reports
    t0 = time.time()
    ms = random_measurements(inst, B, rng)
    step_args, _ = make_report_batch(inst, ms, seed=1, shard_chunk=8 if circ.input_len * 16 > (1 << 22) else 0)
    step_args = jax.device_put(step_args)
    jax.block_until_ready(step_args)
    print(f"[profile] staging: {time.time()-t0:.1f}s", flush=True)
    step = two_party_step(inst, verify_key)
    timeit("two_party_step", step, *step_args)

    total = sum(v for k, v in timings.items() if k not in ("two_party_step", "helper_init"))
    if "two_party_step" in timings:
        print(
            f"[profile] component sum (1x expand_meas/proof/query/trunc/jr) = "
            f"{total:.3f}s vs full step {timings['two_party_step']:.3f}s",
            flush=True,
        )


if __name__ == "__main__":
    main()
