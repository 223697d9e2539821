"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark
reports: device busy time as the union of device-op intervals, the
device time of each op name, and the idle gaps between device ops,
each labelled by the benchmark's own host annotation that overlapped
it most.

The window is the span of one host annotation (`WINDOW`) that the
benchmark holds open for as long as the profiler records.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench.trace_window"
ANNOTATION_PREFIX = "bench."
# where a TPU's executed HLO ops, and its executed programs, are in
# the trace
TPU_PLANE_PREFIX = "/device:TPU:"
TPU_OPS_LINE = "XLA Ops"
TPU_MODULES_LINE = "XLA Modules"


@dataclass
class Reduction:
    window_s: float
    busy_s: float  # mean over the device planes
    op_s: dict = field(default_factory=dict)  # op name -> device seconds, all planes
    op_n: dict = field(default_factory=dict)  # op name -> event count
    gaps: list = field(default_factory=list)  # (label, seconds), longest first
    devices: int = 1
    ops: list = field(default_factory=list)  # (op name, start, end) of the first device
    modules: list = field(default_factory=list)  # (program, start, end) inside the window

    def top_ops(self, k: int = 10) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _clipped(intervals, lo: float, hi: float) -> list:
    """Intervals of non-zero length inside [lo, hi], clipped, sorted."""
    return sorted((max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo))


def union_length(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in _clipped(intervals, lo, hi):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) of each stretch of [lo, hi] that no interval covers."""
    gaps = []
    t = lo
    for s, e in _clipped(intervals, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def label_gap(gap, annotations) -> str:
    """The names of the annotations that cover at least half of `gap`
    (several threads work at once), joined by "+" with the longest
    overlap first, or "none"."""
    s, e = gap
    overlap: dict = {}
    for name, a_s, a_e in annotations:
        o = min(e, a_e) - max(s, a_s)
        if o > 0:
            overlap[name] = overlap.get(name, 0.0) + o
    names = sorted((n for n, o in overlap.items() if o >= (e - s) / 2), key=lambda n: -overlap[n])
    return "+".join(names) or "none"


def reduce_events(device_planes, annotations, modules=(), programs=None, k_gaps: int = 10) -> Reduction:
    """`device_planes`: per device, a list of (op name, start ns, end ns);
    `annotations`: (name, start ns, end ns) of the host annotations,
    among them one `WINDOW`; `modules`: (program name, start, end) of
    the programs executed, renamed by `programs` where it knows them."""
    windows = [(s, e) for n, s, e in annotations if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    lo, hi = windows[0]
    others = [a for a in annotations if a[0] != WINDOW]
    op_s: dict = {}
    op_n: dict = {}
    busy = []
    gaps = []
    for ops in device_planes:
        spans = []
        for name, s, e in ops:
            clipped = min(e, hi) - max(s, lo)
            if clipped <= 0:
                continue
            spans.append((s, e))
            op_s[name] = op_s.get(name, 0.0) + clipped * 1e-9
            op_n[name] = op_n.get(name, 0) + 1
        busy.append(union_length(spans, lo, hi) * 1e-9)
        gaps += idle_gaps(spans, lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(label_gap(g, others), (g[1] - g[0]) * 1e-9) for g in gaps[:k_gaps]]
    n = max(1, len(device_planes))
    programs = programs or {}
    inside = [(programs.get(m, m), s, e) for m, s, e in modules if s >= lo and e <= hi]
    ops = device_planes[0] if device_planes else []
    return Reduction((hi - lo) * 1e-9, sum(busy) / n, op_s, op_n, labelled, n, ops, inside)


def program_order(modules, prefix: str) -> list:
    """Names of the programs starting with `prefix`, in the order they
    first ran."""
    seen: list = []
    for name, _, _ in sorted(modules, key=lambda m: m[1]):
        if name.startswith(prefix) and name not in seen:
            seen.append(name)
    return seen


def events_of(path: str, device_plane_prefix: str = TPU_PLANE_PREFIX, ops_line: str = TPU_OPS_LINE):
    """(device planes, host annotations, executed programs) read from an
    `.xplane.pb`."""
    from jax.profiler import ProfileData

    def spans(line):
        return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]

    data = ProfileData.from_file(path)
    device_planes, annotations, modules = [], [], []
    for plane in data.planes:
        for line in plane.lines:
            on_device = plane.name.startswith(device_plane_prefix)
            if on_device and line.name.startswith(ops_line):
                device_planes.append(spans(line))
            elif on_device and line.name == TPU_MODULES_LINE:
                modules += spans(line)
            elif not plane.name.startswith("/device:"):
                annotations += [a for a in spans(line) if a[0].startswith(ANNOTATION_PREFIX)]
    return device_planes, annotations, modules


def reduce_trace(trace_dir: str, programs=None, **kw) -> Reduction:
    return reduce_events(*events_of(find_xplane(trace_dir), **kw), programs=programs)
