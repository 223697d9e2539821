"""Device time of the Pallas Keccak kernels per report: inside each
leader-init and helper-init program run of the traced stretch, the
device time of the kernels' ops; per program the mean per dispatch
over the reports a dispatch carried, summed over the two. None where
no kernel op ran (the scan path).

The kernels carry no name of their own in the trace: they are the
`tpu_custom_call` ops whose state operand is laid out as
`ops/keccak_pallas.py` lays it out, u32[50, rows, 128] for the
permutation and u32[42, rows, 128] for single-block messages."""

import bisect
import re

KERNEL = re.compile(r'custom-call\(u32\[(42|50),\d+,128\].*custom_call_target="tpu_custom_call"')
PROGRAMS = ("leader_init", "helper_init")


def read(rec):
    if rec.trace is None:
        return None
    ops = sorted((s, e) for name, s, e in rec.trace.ops if KERNEL.search(name))
    if not ops:
        return None
    starts = [s for s, _ in ops]
    total_ns = 0.0
    for prog in PROGRAMS:
        runs = [(s, e) for name, s, e in rec.trace.modules if name == prog]
        dispatches = rec.counter("janus_engine_dispatches_total", op=prog)
        if not runs or not dispatches:
            return None
        kernel_ns = 0.0
        for s, e in runs:
            for o_s, o_e in ops[bisect.bisect_left(starts, s) : bisect.bisect_right(starts, e)]:
                kernel_ns += min(o_e, e) - o_s
        rows_per_dispatch = rec.counter("janus_engine_rows_total", op=prog) / dispatches
        total_ns += kernel_ns / len(runs) / rows_per_dispatch
    return total_ns * 1e-3
