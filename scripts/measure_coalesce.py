"""Chip measurement: coalesced small-job throughput vs one big batch.

VERDICT r4 item 3's done-bar: small aggregation jobs within ~20% of
the large-batch device capability. This drives the REAL engine surface
(EngineCache.helper_init + aggregate — the helper serving hot path)
from N concurrent driver-shaped threads submitting small jobs, against
the same total rows as one monolithic dispatch.

Usage (the only process on the chip):
    python scripts/measure_coalesce.py --job-rows 1024 --jobs 16 --threads 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="count", choices=["count", "sumvec"])
    ap.add_argument("--job-rows", type=int, default=1024)
    ap.add_argument("--jobs", type=int, default=16)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from janus_tpu.binary_utils import enable_compile_cache

    enable_compile_cache()

    import numpy as np

    from janus_tpu.aggregator.engine_cache import EngineCache
    from janus_tpu.vdaf.registry import VdafInstance
    from janus_tpu.vdaf.testing import make_report_batch, random_measurements

    inst = (
        VdafInstance.count()
        if args.config == "count"
        else VdafInstance.sum_vec(length=1000, bits=16)
    )
    engine = EngineCache(inst, bytes(range(16)))
    rng = np.random.default_rng(5)
    total = args.job_rows * args.jobs
    print(
        f"[coalesce] backend={jax.default_backend()} config={args.config} "
        f"job_rows={args.job_rows} jobs={args.jobs} threads={args.threads}",
        flush=True,
    )

    meas = random_measurements(inst, total, rng)
    t0 = time.time()
    big_args, _ = make_report_batch(inst, meas, seed=3)
    print(f"[coalesce] staging: {time.time()-t0:.1f}s", flush=True)

    def cut(a, s, e):
        if a is None:
            return None
        if isinstance(a, tuple):
            return tuple(x[s:e] for x in a)
        return np.asarray(a)[s:e]

    job_args = [
        tuple(cut(a, j * args.job_rows, (j + 1) * args.job_rows) for a in big_args)
        for j in range(args.jobs)
    ]

    def run_job(a):
        nonce, public, meas_c, proof, blind0, hseed, blind1 = a
        n = nonce.shape[0]
        out0, seed0, ver0, part0 = engine.leader_init(nonce, public, meas_c, proof, blind0)
        out1, mask, _ = engine.helper_init(
            nonce, public, hseed, blind1, ver0, part0, np.ones(n, bool)
        )
        agg1 = engine.aggregate(out1, mask)
        return int(mask.sum())

    def small_jobs_concurrent():
        with ThreadPoolExecutor(max_workers=args.threads) as pool:
            done = sum(pool.map(run_job, job_args))
        assert done == total, done
        return done

    def one_big_job():
        return run_job(big_args)

    for name, fn in (("big_single_dispatch", one_big_job), ("small_jobs_coalesced", small_jobs_concurrent)):
        fn()  # compile
        t0 = time.time()
        for _ in range(args.iters):
            fn()
        per = (time.time() - t0) / args.iters
        print(
            json.dumps(
                {
                    "variant": name,
                    "rows": total,
                    "s": round(per, 3),
                    "rps": round(total / per, 1),
                    "coalesce_rounds": list(engine._co_leader.rounds)[-8:],
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    main()
