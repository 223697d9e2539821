"""Chip microbench: tree-digest leaf layout — contiguous vs planar.

Hypothesis (r5): the leader joint-rand binder at SumVec len=100k costs
~5 ms/report not in Keccak but in the stride-14 gather that turns
contiguous 112-byte leaf chunks into per-lane columns ([batch, n, 14]
minor-dim slices = an 819 MB strided transpose at ~10% bandwidth).
The planar variant maps leaf k's lane l to data[l*n + k] — every lane
column is then a contiguous slice, no transpose — at the price of a
(self-consistent, internal) derivation change.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    from janus_tpu.binary_utils import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    import janus_tpu.vdaf.keccak_jax as kj

    print(f"[tree] backend={jax.default_backend()}", flush=True)
    batch, lanes_n = 32, 3_200_000  # the len=100k leader share binder
    rng = np.random.default_rng(3)
    data = jnp.asarray(
        rng.integers(0, 1 << 63, size=(batch, lanes_n), dtype=np.uint64)
    )
    jax.block_until_ready(data)

    def timeit(name, fn):
        f = jax.jit(fn)
        t0 = time.time()
        v = np.asarray(f(data)).sum()
        compile_s = time.time() - t0
        ts = []
        for _ in range(3):
            t0 = time.time()
            v = np.asarray(f(data)).sum()
            ts.append(time.time() - t0)
        print(
            json.dumps(
                {"variant": name, "s": round(min(ts), 4), "compile_s": round(compile_s, 1)}
            ),
            flush=True,
        )

    def current(d):
        return kj.tree_digest_lanes([(0, d)], lanes_n * 8, batch)

    CH = kj.TREE_CHUNK_LANES

    def planar_level0(d):
        # planar leaves: lane l of node k = data[l*n + k]; every lane
        # column is one contiguous slice
        n = -(-lanes_n // CH)
        pad = n * CH - lanes_n
        if pad:
            d = jnp.pad(d, ((0, 0), (0, pad)))
        planes = d.reshape(batch, CH, n)
        idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.uint64)[None, :], (batch, n))
        consts = {
            0: np.uint64(kj.TREE_MAGIC_LANE),
            1: np.uint64(0),
            3: np.uint64(lanes_n * 8),
            18: kj.PAD_START,
            20: kj.PAD_END,
        }
        cols = []
        for lane in range(kj.RATE_LANES):
            if lane == 2:
                cols.append(idx)
            elif 4 <= lane < 4 + CH:
                cols.append(planes[:, lane - 4, :])
            else:
                cols.append(
                    jnp.broadcast_to(
                        jnp.asarray(consts.get(lane, np.uint64(0))), (batch, n)
                    )
                )
        state = kj._single_block_keccak(cols, out_lanes=2)
        digs = jnp.stack(state[:2], axis=-1)
        # upper levels on the (small) digest array, current layout
        level, nn = 0, n
        while nn > 1:
            level += 1
            groups = -(-nn // kj.TREE_ARITY)
            gpad = groups * kj.TREE_ARITY - nn
            if gpad:
                digs = jnp.pad(digs, ((0, 0), (0, gpad), (0, 0)))
            chunks = digs.reshape(batch, groups, CH)
            digs = kj._tree_level(chunks, level, lanes_n * 8)
            nn = groups
        return digs[:, 0, :]

    def level0_only_current(d):
        n = -(-lanes_n // CH)
        pad = n * CH - lanes_n
        if pad:
            d = jnp.pad(d, ((0, 0), (0, pad)))
        chunks = d.reshape(batch, n, CH)
        return kj._tree_level(chunks, 0, lanes_n * 8)

    # NOTE post-r5: the library digest IS the planar layout now, so
    # "library_full" ~= "planar_full"; "contiguous_level0" preserves
    # the pre-r5 contiguous-leaf baseline this change was measured
    # against (245 ms library vs 176 ms planar on this config,
    # 2026-08-01; unverified link-era figure).
    timeit("library_full", current)
    timeit("contiguous_level0", level0_only_current)
    timeit("planar_full", planar_level0)


if __name__ == "__main__":
    main()
