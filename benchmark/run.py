"""Runs one cell of the benchmark: a served Prio3 DAP pair on one chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up boots the pair (`pair.py`), makes the cell's seeded client
reports (`corpus.py`; a small corpus is read back from an earlier run
of the same seed), warms the programs of the one job size the cell
uses, stores the backlog in the leader's datastore and cuts its
aggregation jobs. The window then runs for `--seconds`: the leader's
aggregation job driver drains the backlog against the helper while an
open-loop client uploads more reports at the cell's `upload_rps`.
After the window the run stores the rest of the corpus's last job,
drains what is left, untimed, collects the batch over HTTP and
compares it with a plain reference (`check.py`).

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or
with `--trace 1` its per-layer metrics), `device`, with `--trace 1`
`breakdown`, and last `checks`, each compared number beside its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no such line.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import spec  # noqa: E402

if spec.ROOT not in sys.path:
    sys.path.insert(1, spec.ROOT)  # the program under test

# length of the traced stretch, centred in the window
TRACE_SECONDS = 5.0
# how long the untimed drain after the window may take
DRAIN_TIMEOUT_S = 150.0
# libtpu's visible-chip variables: one process, one chip
ONE_CHIP_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


class CompileCount:
    """XLA compiles and persistent-cache misses seen by this process."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.misses = 0

        def on_duration(event: str, _duration, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple[int, int]:
        return self.compiles, self.misses


def chip_devices(chips: int):
    """The TPU devices this run may use, or None with the reason logged."""
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms:
        names = platforms.split(",")
        if "tpu" not in names:
            log(f"JAX_PLATFORMS={platforms!r} leaves no TPU")
            return None
        if "cpu" not in names:
            os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    if chips == 1:
        for k, v in ONE_CHIP_ENV.items():
            os.environ.setdefault(k, v)
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"JAX found no accelerator: {e}")
        return None
    if devices[0].platform != "tpu":
        log(f"JAX found no TPU (platform {devices[0].platform})")
        return None
    if len(devices) < chips:
        log(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
        return None
    return devices[:chips]


def enable_caches() -> None:
    """JAX's persistent compile cache and the program's serialized
    executables, both under the checkout's fixed cache directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = spec.CACHE_DIR
    from janus_tpu.aggregator import aot_cache
    from janus_tpu.binary_utils import enable_compile_cache

    enable_compile_cache()
    aot_cache.arm(os.path.join(spec.CACHE_DIR, "aot"))


def _sleep_until(t: float) -> None:
    while (left := t - time.monotonic()) > 0:
        time.sleep(min(left, 0.5))


def _trace(trace_dir: str, start: float, seconds: float) -> None:
    import jax
    from jax.profiler import TraceAnnotation

    from trace_reduce import WINDOW

    _sleep_until(start)
    jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    try:
        with TraceAnnotation(WINDOW):
            time.sleep(seconds)
    finally:
        jax.profiler.stop_trace()


def _warm_up(pair, trace_dir: str | None) -> dict:
    """Warms the pair's programs. With `trace_dir`, records the warm-up
    in a trace and returns {program name in the trace: its role}, from
    the order the warm-up runs them (`Pair.WARM_PROGRAMS`)."""
    if trace_dir is None:
        pair.warm_up()
        return {}
    import jax
    from jax.profiler import TraceAnnotation

    from trace_reduce import WINDOW, events_of, find_xplane, program_order

    jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    try:
        with TraceAnnotation(WINDOW):
            pair.warm_up()
    finally:
        jax.profiler.stop_trace()
    order = program_order(events_of(find_xplane(trace_dir))[2], "jit_")
    log(f"programs the warm-up ran, in order: {order}")
    return dict(zip(order, pair.WARM_PROGRAMS)) if len(order) == len(pair.WARM_PROGRAMS) else {}


def _profile_options():
    from jax.profiler import ProfileOptions

    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def _pct(values: list, q: float) -> float:
    """The q-quantile of `values` by nearest rank."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))]


def run_cell(cell, seed: int, seconds: float, trace: bool, devices):
    """One run of `cell`; returns (the result object, the `Record`)."""
    import numpy as np

    import check
    import corpus as corpus_mod
    from janus_tpu import metrics
    from janus_tpu.aggregator.engine_cache import shutdown_engines
    from janus_tpu.aggregator import aot_cache
    from pair import Pair, Uploader, Window
    from record import Record

    counts = CompileCount()
    traffic = cell.traffic
    with tempfile.TemporaryDirectory(prefix="janus-bench-") as workdir:
        pair = Pair(cell.config, seed, workdir)
        try:
            n_backlog, n_upload = corpus_mod.sizes(traffic, pair.job_size, seconds)
            t = time.monotonic()
            corpus, cached = corpus_mod.cached_corpus(
                spec.CORPUS_DIR,
                cell.config["vdaf"],
                pair.inst,
                seed,
                n_backlog,
                n_upload,
                traffic["invalid_share"],
                corpus_mod.client_chunk(pair.inst, pair.job_size),
                pair.client_view(),
            )
            log(
                f"corpus: {n_backlog} backlog + {n_upload} upload reports, "
                f"{int(corpus.invalid.sum())} invalid, {'read from the cache' if cached else 'made'} "
                f"in {time.monotonic() - t:.1f}s"
            )
            t = time.monotonic()
            programs = _warm_up(pair, os.path.join(workdir, "warm_trace") if trace else None)
            log(
                f"warm-up {time.monotonic() - t:.1f}s: compiles {counts.compiles}, "
                f"cache misses {counts.misses}, executables {aot_cache.stats()}"
            )
            t = time.monotonic()
            decrypt_s, write_s = pair.load_backlog(corpus.reports[:n_backlog])
            n_jobs = pair.create_jobs()
            sizes = {j: n for j, (_, n) in pair.job_states().items()}
            log(
                f"backlog stored ({decrypt_s:.1f}s decrypting, {write_s:.1f}s writing), "
                f"{n_jobs} jobs created, in {time.monotonic() - t:.1f}s"
            )

            window = Window(pair)
            # the uploads due inside the window; the rest of the corpus
            # (it holds whole jobs) is stored directly once it has closed
            n_timed = min(n_upload, math.ceil(traffic["upload_rps"] * seconds))
            uploader = Uploader(
                pair.upload_url, corpus.reports[n_backlog : n_backlog + n_timed], traffic["upload_rps"]
            )
            setup_s = time.monotonic() - _T0
            snaps = {"start": metrics.REGISTRY.snapshot()}
            c0 = counts.mark()
            t0 = time.monotonic()
            t1 = t0 + seconds
            window.start()
            uploader.start(t0)
            tracer = None
            trace_dir = os.path.join(workdir, "trace")
            if trace:
                trace_s = min(TRACE_SECONDS, seconds / 2)
                tracer = threading.Thread(
                    target=_trace, args=(trace_dir, t0 + (seconds - trace_s) / 2, trace_s)
                )
                tracer.start()
            _sleep_until(t1)
            snaps["end"] = metrics.REGISTRY.snapshot()
            c1 = counts.mark()
            stats = devices[0].memory_stats() or {}
            peak = stats.get("peak_bytes_in_use")
            acquired_in_window = {j for j, t in window.acquired if t <= t1}
            log(
                f"window closed: compiles inside it {c1[0] - c0[0]}, cache misses "
                f"{c1[1] - c0[1]}; peak_bytes_in_use {peak}"
            )

            # the untimed drain: the rest of the uploads, their jobs, and
            # whatever of the backlog is left
            if tracer is not None:
                tracer.join()
            uploader.finish()
            pair.load_backlog(corpus.reports[n_backlog + n_timed :])
            pair.create_jobs()
            deadline = time.monotonic() + DRAIN_TIMEOUT_S
            while time.monotonic() < deadline:
                states = pair.job_states()
                if all(s != "in_progress" for s, _ in states.values()):
                    break
                time.sleep(0.5)
            window.stop()
            snaps["drained"] = metrics.REGISTRY.snapshot()
            states = pair.job_states()
            sizes = {j: n for j, (_, n) in states.items()}
            log(f"drained: {len(states)} jobs, {sum(sizes.values())} reports")

            result = pair.collect()
            leader_v = pair.verdicts(pair.leader_ds)
            helper_v = pair.verdicts(pair.helper_ds)
        finally:
            pair.close()
            shutdown_engines()

        stored = np.ones(len(corpus), dtype=bool)
        stored[n_backlog : n_backlog + n_timed] = np.asarray(uploader.final) == 201
        checks = check.compare(
            corpus, stored, result.report_count, result.aggregate_result, leader_v, helper_v
        )
        last_done: dict = {}
        for jid, t in window.done:
            last_done[jid] = max(t, last_done.get(jid, t))
        rec = Record(
            setup_s=setup_s,
            window_s=seconds,
            t0=t0,
            t1=t1,
            uploads=uploader.results[:n_timed],
            job_sizes=sizes,
            job_states={j: s for j, (s, _) in states.items()},
            last_done=last_done,
            snapshots=snaps,
        )
        if trace:
            from trace_reduce import reduce_trace

            rec.trace = reduce_trace(trace_dir, programs=programs)

    timed = rec.uploads
    status_counts: dict = {}
    for r in timed:
        status_counts[r[0]] = status_counts.get(r[0], 0) + 1
    log(f"upload answers in the window: {status_counts}")
    up = rec.upload_stats()
    log(
        f"upload p95 of the answered: {up['p95_ms']} ms; first and second half of the "
        f"schedule: p50 {up['p50_first_half_ms']} and {up['p50_second_half_ms']} ms, "
        f"p95 {up['p95_first_half_ms']} and {up['p95_second_half_ms']} ms"
    )
    lateness = [r[2] for r in timed if r is not None]
    if lateness:
        log(
            f"upload generator lateness over {len(lateness)} uploads: p50 "
            f"{_pct(lateness, 0.5) * 1e3:.2f} ms, p99 {_pct(lateness, 0.99) * 1e3:.2f} ms, "
            f"max {max(lateness) * 1e3:.2f} ms"
        )
    valid_rejected = sum(
        1
        for rid, ok, bad in zip(corpus.report_ids, stored, corpus.invalid)
        if ok and not bad and leader_v.get(rid) != ["finished"]
    )
    step_faults = rec.counter("janus_job_step_back_total", "start", "drained") + rec.counter(
        "janus_job_cancellations", "start", "drained"
    )
    attempted = n_timed + sum(sizes.get(j, 0) for j in acquired_in_window)
    failed = sum(1 for r in timed if r is None or r[0] != 201) + valid_rejected + int(step_faults)

    out_metrics = {}
    for m in cell.metrics(trace):
        value = m.read(rec)
        if value is not None:
            out_metrics[m.name] = {"value": value, "unit": m.unit}
    dev = devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    out = {
        "correct": check.correct(checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": out_metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = {
            # an op's name is its HLO text: the instruction and its shapes
            "device_ops": [[n[:120], s] for n, s in rec.trace.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in rec.trace.gaps[:10]],
        }
    out["checks"] = checks
    return out, rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell named in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import janus_tpu  # noqa: F401
    except ImportError as e:
        log(f"the program under test is not beside the benchmark: {e}")
        return 2
    cell = spec.load_cell(args.workload)
    devices = chip_devices(cell.chips)
    if devices is None:
        return 2
    enable_caches()
    log(f"{args.workload}: {devices[0].device_kind} x{len(devices)}, seed {args.seed}")
    out, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, (value, limit) in out["checks"].items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
