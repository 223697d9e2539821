"""Benchmark: batched two-party Prio3 prepare+accumulate throughput.

Measures report-shares/sec/chip
for the full two-party prepare + accumulate step (leader init + helper
init + combine/decide + masked aggregate — everything the reference
does per report in aggregation_job_driver.rs:329-402,530-726 and
aggregator.rs:1775-1826), on whatever accelerator JAX exposes.

CPU baseline: the host oracle (janus_tpu.vdaf.reference) timed on a few
reports and extrapolated. The reference's own prio-rs CPU path cannot
run in this image (no Rust toolchain); the host oracle stands in as
the measured-CPU baseline. vs_baseline is
device_throughput / host_throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def run_served(inst, n_reports: int, job_size: int) -> dict:
    """End-to-end served throughput: reports through the real helper +
    leader HTTP handlers (HPKE opens, wire decode, SQLite writes, the
    device engine) on an in-process loopback pair.

    Measures what the device-step bench deliberately excludes — the
    serving shell around the engine (VERDICT Weak #4; the reference's
    hot path aggregator.rs:1561-1890 includes all of it).
    """
    import time as _time

    import dataclasses as _dc

    import numpy as np

    from janus_tpu import metrics as _m
    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.aggregation_job_driver import AggregationJobDriver
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.client import ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import MockClock
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.testing import make_wire_reports, random_measurements

    clock = MockClock(Time(1_600_000_000))
    leader_eph = EphemeralDatastore(clock=clock)
    helper_eph = EphemeralDatastore(clock=clock)
    # supervise the serving store like the real binaries do, so the
    # record's datastore_up/janus_datastore_up series carry the real
    # outage-survival signal (unsupervised, the gauge would read a
    # misleading default 0)
    leader_eph.datastore.start_supervision(probe_interval_s=2.0)
    leader_agg = Aggregator(leader_eph.datastore, clock, Config())
    helper_agg = Aggregator(helper_eph.datastore, clock, Config())
    leader_srv = DapServer(DapHttpApp(leader_agg)).start()
    helper_srv = DapServer(DapHttpApp(helper_agg)).start()
    # the SLO engine runs through the served phase like in the real
    # binaries (default definitions, fast cadence so the windows hold
    # real samples by scrape time) — the record's alertz_ok and the
    # exemplar round-trip come from the live /alertz + OpenMetrics
    # scrape at the end
    from janus_tpu import slo as _slo

    _slo.install_slo_engine(_slo.SloEngineConfig(evaluation_interval_s=0.5))
    # the continuous profiler runs through the served phase like in the
    # real binaries (janus_main installs it by default): the record's
    # profiler rider reads the per-role shares and the device cost
    # ledger's µs/report attribution at the end
    from janus_tpu import profiler as _prof

    # 97 Hz (vs the production 19): the served aggregate phase is a
    # fraction of a second on CPU, and the rider's device-lane self
    # share needs real samples inside it; still well under the 2%
    # overhead budget (the rider records the measured ratio)
    _prof.install_profiler(_prof.ProfilerConfig(hz=97.0, window_secs=15.0))
    try:
        collector_kp = generate_hpke_config_and_private_key(config_id=200)
        leader_task = (
            TaskBuilder(QueryTypeConfig.time_interval(), inst, Role.LEADER)
            .with_(
                leader_aggregator_endpoint=leader_srv.url,
                helper_aggregator_endpoint=helper_srv.url,
                collector_hpke_config=collector_kp.config,
                aggregator_auth_token=AuthenticationToken.random_bearer(),
                collector_auth_token=AuthenticationToken.random_bearer(),
                min_batch_size=1,
            )
            .build()
        )
        helper_task = _dc.replace(
            leader_task,
            role=Role.HELPER,
            hpke_keys=(generate_hpke_config_and_private_key(config_id=1),),
        )
        leader_eph.datastore.run_tx(lambda tx: tx.put_task(leader_task))
        helper_eph.datastore.run_tx(lambda tx: tx.put_task(helper_task))

        # warm both aggregators' engines before timing (production boots
        # with warmup_engines_at_boot; first-compile must not pollute
        # the steady-state serving numbers)
        from janus_tpu.binary_utils import warmup_engines

        # warm every batch bucket the run will actually use: full jobs
        # of job_size and the remainder job (bucketed separately)
        warm_sizes = {min(job_size, n_reports)}
        if n_reports % job_size:
            warm_sizes.add(n_reports % job_size)
        t0 = _time.time()
        for ws in sorted(warm_sizes):
            warmup_engines(leader_eph.datastore, batch=ws)
            warmup_engines(helper_eph.datastore, batch=ws)
        warmup_s = _time.time() - t0

        rng = np.random.default_rng(0x5E12)
        meas = random_measurements(inst, n_reports, rng)
        t0 = _time.time()
        when = clock.now().to_batch_interval_start(leader_task.time_precision)
        reports = make_wire_reports(
            inst,
            meas,
            leader_task.task_id,
            leader_task.hpke_keys[0].config,
            helper_task.hpke_keys[0].config,
            when,
            seed=2,
        )
        stage_s = _time.time() - t0

        http = HttpClient()
        params = ClientParameters(
            leader_task.task_id, leader_srv.url, helper_srv.url, leader_task.time_precision
        )
        # concurrent upload clients: the write batcher amortizes the
        # datastore tx across in-flight uploads (reference
        # ReportWriteBatcher semantics) — a serial client only measures
        # the flush delay, not throughput
        from concurrent.futures import ThreadPoolExecutor

        def _upload(r):
            for attempt in (0, 1):
                try:
                    status, body = http.put(
                        params.upload_uri(),
                        r.to_bytes(),
                        {"Content-Type": "application/dap-report"},
                    )
                except (ConnectionError, OSError):
                    if attempt:
                        raise
                    continue
                if status == 201:
                    return
                if attempt and status in (400, 409) and (
                    b"reportRejected" in body or b"replay" in body
                ):
                    # the first PUT landed but its 201 was lost on the
                    # wire; the server's duplicate-report answer on the
                    # retry is success, not a bench failure
                    return
                break
            raise AssertionError(f"upload failed: {status} {body!r}")

        # ingest phase (docs/INGEST.md): serial baseline first — one
        # report in flight, so the decrypt pool cannot overlap work —
        # then the 16-way burst the staged pipeline was built for; the
        # ratio is the pipelining win on this host. Shed accounting
        # rides along (0 unless admission buckets are configured).
        from janus_tpu import metrics as _metrics

        shed0 = _metrics.upload_shed_counter.total()
        n_serial = max(2, min(32, n_reports // 4))
        t0 = _time.time()
        for r in reports[:n_serial]:
            _upload(r)
        serial_s = _time.time() - t0
        serial_rps = n_serial / serial_s if serial_s > 0 else float("inf")
        t0 = _time.time()
        with ThreadPoolExecutor(max_workers=16) as pool:
            list(pool.map(_upload, reports[n_serial:]))
        upload_s = _time.time() - t0
        ingest_rps = (n_reports - n_serial) / upload_s if upload_s > 0 else float("inf")
        shed_total = _metrics.upload_shed_counter.total() - shed0

        # server-side ingest capacity, isolated from the loopback
        # client's own Python cost (which shares the GIL with the
        # server above): the OLD upload architecture — one thread, one
        # transaction per report — vs the staged pipeline fed directly,
        # on fresh stores so every commit is a real insert
        from janus_tpu.aggregator.core import TaskAggregator
        from janus_tpu.aggregator.report_writer import ReportWriteBatcher
        from janus_tpu.ingest import IngestPipeline

        sample = reports[: min(96, n_reports)]
        eph_a = EphemeralDatastore(clock=clock)
        eph_b = EphemeralDatastore(clock=clock)
        try:
            eph_a.datastore.run_tx(lambda tx: tx.put_task(leader_task))
            eph_b.datastore.run_tx(lambda tx: tx.put_task(leader_task))
            ta = TaskAggregator(leader_task, Config())
            t0 = _time.time()
            for r in sample:
                ta.handle_upload(eph_a.datastore, clock, r, None)
            serial_path_s = _time.time() - t0
            writer = ReportWriteBatcher(eph_b.datastore, 100, 0)
            pipe = IngestPipeline(writer, queue_depth=len(sample))
            try:
                t0 = _time.time()
                tickets = [pipe.submit(ta, clock, r.to_bytes()) for r in sample]
                assert all(t.result(timeout_s=60) for t in tickets)
                pipeline_s = _time.time() - t0
            finally:
                pipe.close()
                writer.close()
        finally:
            eph_a.cleanup()
            eph_b.cleanup()

        creator = AggregationJobCreator(
            leader_eph.datastore,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=job_size
            ),
        )
        # resident accumulators on (ISSUE 12): the masked accumulate
        # merges into device-resident per-bucket buffers (no per-job
        # share fetch); the drain flush below writes them out before
        # collection — the production resident-mode shape
        from janus_tpu.aggregator.aggregation_job_driver import (
            AggregationJobDriverConfig,
            ResidentConfig,
        )

        driver = AggregationJobDriver(
            leader_eph.datastore,
            http,
            AggregationJobDriverConfig(
                resident=ResidentConfig(enabled=True, flush_interval_s=3600.0)
            ),
        )
        # the production stepper: the stage pipeline (ISSUE 9) — job
        # B's read+staging and HTTP legs overlap job A's device phases
        # behind the serialized device lane (double-buffered staging on
        # by default: job k+1's H2D overlaps job k's dispatch)
        from janus_tpu.aggregator.step_pipeline import StepPipeline, StepPipelineConfig

        pipeline = StepPipeline(driver, StepPipelineConfig())
        jd = JobDriver(
            JobDriverConfig(max_concurrent_job_workers=4),
            driver.acquirer(),
            driver.stepper,
            pipeline=pipeline,
        )
        hd_h2d0 = _m.engine_hd_bytes_total.get(direction="h2d")
        hd_d2h0 = _m.engine_hd_bytes_total.get(direction="d2h")
        prestage0 = {
            o: _m.engine_prestage_total.get(outcome=o) for o in ("hit", "fallback")
        }
        t0 = _time.time()
        creator.run_once()
        while jd.run_once():
            pass
        resident_flushed = driver.flush_resident_state(reason="drain")
        aggregate_s = _time.time() - t0
        resident_rider = {
            "enabled": True,
            "flushed_buffers": resident_flushed,
            "hd_bytes_h2d": _m.engine_hd_bytes_total.get(direction="h2d") - hd_h2d0,
            "hd_bytes_d2h": _m.engine_hd_bytes_total.get(direction="d2h") - hd_d2h0,
            "prestage_hits": _m.engine_prestage_total.get(outcome="hit")
            - prestage0["hit"],
            "prestage_fallbacks": _m.engine_prestage_total.get(outcome="fallback")
            - prestage0["fallback"],
        }
        resident_rider["hd_bytes_per_report"] = round(
            (resident_rider["hd_bytes_h2d"] + resident_rider["hd_bytes_d2h"])
            / max(1, n_reports),
            1,
        )
        # p50/p95 aggregation-job step latency from the flight-recorder
        # digest (PR 5) — BASELINE's second metric, read BEFORE the
        # collection driver adds its own job.step observations
        from janus_tpu import trace as _tr

        _step_digest = (
            _tr.flight_recorder().snapshot(recent_limit=0)["digests"].get("job.step")
        )
        step_pipeline_status = pipeline.status()

        collector = Collector(
            CollectorParameters(
                leader_task.task_id,
                leader_srv.url,
                leader_task.collector_auth_token,
                collector_kp,
            ),
            inst,
            http,
        )
        query = Query.time_interval(
            Interval(Time(when.seconds - 3600), Duration(3600 * 4))
        )
        t0 = _time.time()
        job_id = collector.start_collection(query)
        cdriver = CollectionJobDriver(leader_eph.datastore, http)
        cjd = JobDriver(
            JobDriverConfig(max_concurrent_job_workers=1),
            cdriver.acquirer(),
            cdriver.stepper,
        )
        cjd.run_once()
        result = collector.poll_once(job_id, query)
        collect_s = _time.time() - t0
        assert result.report_count == n_reports, result.report_count

        # scrape the real health listener after the serving run: one
        # sampling pass against the leader store, then /metrics +
        # /statusz over HTTP, validated with the shared exposition
        # parser — so every BENCH json carries the engine/job metric
        # snapshot even when the accelerator phases stall
        scrape_ok = False
        scrape_errors: list = []
        alertz_ok = False
        alertz_firing: list = []
        exemplar_roundtrip: dict = {}
        try:
            scrape = _scrape_health_listener(ds=leader_eph.datastore)
            scrape["server"].stop()
            scrape_ok = not scrape["errors"]
            scrape_errors = scrape["errors"][:5]
            alertz = scrape["alertz"]
            alertz_ok = (
                alertz.get("enabled") is True
                and {"firing", "alerts", "slos"} <= set(alertz)
                and len(alertz["slos"]) >= 5
                and all("burn_rates" in s for s in alertz["slos"])
                and not scrape["openmetrics_errors"]
            )
            alertz_firing = alertz.get("firing", [])
            # exemplar resolution over live HTTP: a latency exemplar in
            # the OpenMetrics scrape links to a /debug/traces capture
            exemplar_roundtrip = _exemplar_roundtrip(scrape)
        except Exception as e:  # the bench record must survive
            scrape_errors = [f"scrape failed: {e}"]
        # profiler rider (ISSUE 13): top roles by wall-clock share over
        # the served run, the cost ledger's live µs/report table (the
        # accumulate row is the acceptance cross-check against the
        # served device time) and the boot timeline (None in-process —
        # janus_main owns the boot record in the real binaries)
        prof_doc = _prof.PROFILER.profile_json()
        profiler_rider = {
            "enabled": prof_doc["enabled"],
            "samples": prof_doc["samples"],
            "overhead_ratio": prof_doc["overhead_ratio"],
            "top_roles": [
                {"role": r, "total_pct": v["total_pct"], "self_pct": v["self_pct"]}
                for r, v in sorted(
                    prof_doc["roles"].items(), key=lambda kv: -kv[1]["total_pct"]
                )[:3]
            ],
            "device_lane_self_pct": prof_doc["roles"]
            .get("device_lane", {})
            .get("self_pct", 0.0),
            "boot_total_s": _prof.BOOT.snapshot().get("total_s"),
        }
        return {
            "n_reports": n_reports,
            "warmup_s": round(warmup_s, 2),
            "stage_s": round(stage_s, 2),
            "upload_serial_rps": round(serial_rps, 2),
            "ingest_rps": round(ingest_rps, 2),
            "upload_rps": round(ingest_rps, 2),  # legacy name
            "ingest_vs_serial": round(ingest_rps / serial_rps, 2),
            "upload_shed_total": shed_total,
            # old architecture (one thread, one tx per report) vs the
            # staged pipeline, pure server-side
            "single_thread_upload_rps": round(len(sample) / serial_path_s, 2),
            "ingest_pipeline_rps": round(len(sample) / pipeline_s, 2),
            "ingest_pipeline_speedup": round(serial_path_s / pipeline_s, 2),
            "served_aggregate_rps": round(n_reports / aggregate_s, 2),
            # BASELINE's second metric: aggregation-job step latency
            # quantiles, sourced from the flight-recorder digests
            "agg_job_step_latency": (
                {
                    "p50_s": _step_digest["p50_s"],
                    "p95_s": _step_digest["p95_s"],
                    "mean_s": _step_digest["mean_s"],
                    "count": _step_digest["count"],
                }
                if _step_digest
                else None
            ),
            # stage-pipeline overlap proof for the measured form of the
            # step_pipeline record (the dry-run form rides pipeline_smoke)
            "step_pipeline": {
                "overlap_ratio": step_pipeline_status["overlap_ratio"],
                "overlapped_dispatches": step_pipeline_status["overlapped_dispatches"],
                "device_lane_busy_ratio": step_pipeline_status["device_lane"]["busy_ratio"],
                "device_lane_dispatches": step_pipeline_status["device_lane"]["dispatches"],
            },
            # resident accumulators + double-buffered staging over the
            # served run (ISSUE 12): drain-flushed buffer count, the
            # engine layer's host<->device bytes/report, and the
            # prestage hit/fallback split
            "resident": resident_rider,
            "collect_s": round(collect_s, 2),
            "metrics_scrape_valid": scrape_ok,
            # SLO engine + exemplar surface over the served run (ISSUE
            # 10): /alertz well-formed with burn rates for every
            # default SLO, and an OpenMetrics exemplar resolving to a
            # live /debug/traces span
            "alertz_ok": alertz_ok,
            "alertz_firing": alertz_firing,
            "exemplar_roundtrip": exemplar_roundtrip,
            **({"metrics_scrape_errors": scrape_errors} if scrape_errors else {}),
            # datastore/journal state at the end of the served run (the
            # outage-survival dashboard series; full samples ride the
            # snapshot below via the janus_datastore_/janus_upload_
            # journal_ prefixes)
            "datastore_up": _m.datastore_up.get(),
            "upload_journal_depth": _m.upload_journal_depth.get(),
            # continuous profiler over the served run (ISSUE 13)
            "profiler": profiler_rider,
            "metrics_snapshot": _metrics_snapshot_rider(),
        }
    finally:
        _prof.uninstall_profiler()
        _slo.uninstall_slo_engine()
        try:
            pipeline.close()
        except NameError:
            pass  # failed before the aggregate phase built it
        leader_srv.stop()
        helper_srv.stop()
        leader_eph.cleanup()
        helper_eph.cleanup()


def run_poplar1(args) -> None:
    """Poplar1 two-party prepare throughput: batched device IDPF eval +
    quadratic sketch (vdaf.poplar1_jax) at the declared parity config
    (Poplar1<XofShake128,16>, reference aggregator.rs:1096), leaf level,
    256 queried prefixes. Host baseline: the per-report host walk
    (vdaf.poplar1.Poplar1.prepare_init), extrapolated."""
    import secrets
    import time as _time

    import numpy as np

    from janus_tpu.vdaf.poplar1 import Poplar1, Poplar1AggParam
    from janus_tpu.vdaf.poplar1_jax import prepare_init_batched

    bits = 16
    level = bits - 1
    n_prefixes = 256
    batch = args.batch or 512
    verify_key = bytes(range(16))
    poplar = Poplar1(bits)
    rng = np.random.default_rng(0xB0B)

    t0 = _time.time()
    alphas = [int(rng.integers(0, 1 << bits)) for _ in range(batch)]
    keys0, keys1 = [], []
    for a in alphas:
        _, (k0, k1) = poplar.shard(a)
        keys0.append(k0)
        keys1.append(k1)
    prefixes = tuple(sorted(rng.choice(1 << bits, size=n_prefixes, replace=False).tolist()))
    param = Poplar1AggParam(level, prefixes)
    nonces = [secrets.token_bytes(16) for _ in alphas]
    print(f"[bench] poplar1 shard(batch={batch}): {_time.time()-t0:.1f}s", file=sys.stderr, flush=True)

    def both_parties():
        # two-party prepare: both aggregators' round-1 (device), sketch
        # combine on host ints (tiny). Return value forces the fetch.
        y0, A0, B0, a0, c0 = prepare_init_batched(bits, 0, keys0, param, verify_key, nonces)
        y1, A1, B1, a1, c1 = prepare_init_batched(bits, 1, keys1, param, verify_key, nonces)
        F = poplar.idpf.field_at(level)
        ok = 0
        for i in range(batch):
            A = F.add(A0[i], A1[i])
            B = F.add(B0[i], B1[i])
            s0 = F.neg(F.sub(F.mul(2 % F.MODULUS, F.mul(A, a0[i])), c0[i]))
            s0 = F.add(s0, F.sub(F.mul(A, A), B))
            s1 = F.neg(F.sub(F.mul(2 % F.MODULUS, F.mul(A, a1[i])), c1[i]))
            ok += int(F.add(s0, s1) == 0)
        assert ok == batch, f"sketch failed: {ok}/{batch}"
        return ok

    t0 = _time.time()
    both_parties()
    compile_s = _time.time() - t0
    t0 = _time.time()
    iters = max(2, args.iters)
    for _ in range(iters):
        both_parties()
    device_rps = batch * iters / (_time.time() - t0)

    # host baseline: the scalar walk on a few reports
    hr = min(args.host_reports, batch)
    t0 = _time.time()
    for i in range(hr):
        poplar.prepare_init(0, keys0[i], param, verify_key, nonces[i])
        poplar.prepare_init(1, keys1[i], param, verify_key, nonces[i])
    host_rps = hr / (_time.time() - t0)

    print(
        json.dumps(
            {
                "metric": "poplar1_two_party_prepare",
                "value": round(device_rps, 2),
                "unit": "reports_per_sec_per_chip",
                "vs_baseline": round(device_rps / host_rps, 2),
                "backend": backend,
                "batch": batch,
                "bits": bits,
                "level": level,
                "prefixes": n_prefixes,
                "iters": iters,
                "compile_s": round(compile_s, 1),
                "host_walk_rps": round(host_rps, 3),
            }
        )
    )


def _make_inst(args, ap):
    """The BASELINE.json measurement config for the parsed args (shared
    by the measured run and --dry-run)."""
    import dataclasses

    from janus_tpu.vdaf.registry import VdafInstance

    if args.length and args.config in ("count", "sum"):
        ap.error(f"--length has no meaning for --config {args.config}")
    L = args.length
    inst = {
        "count": VdafInstance.count(),
        "sum": VdafInstance.sum(bits=32),
        "sumvec": VdafInstance.sum_vec(length=L or 1000, bits=16),
        "histogram": VdafInstance.histogram(length=L or 10000),
        "fixedpoint": VdafInstance.fixed_point_vec(length=L or 1000, bits=16),
        # block-sparse north star (ISSUE 17): logical len-1M accumulator,
        # each report carries <= 16 live blocks of 64 — device work rides
        # the COMPACT encoding (1024 lanes), the scatter-merge owns the
        # logical length
        "sparse": VdafInstance.sparse_sumvec(
            bits=16, length=L or 1_000_000, block_size=64, max_blocks=16
        ),
    }[args.config]
    if args.xof_mode != "fast":
        inst = dataclasses.replace(inst, xof_mode=args.xof_mode)
    return inst


def _oom_fallback_smoke() -> dict:
    """Exercise the EngineCache OOM machinery on a toy circuit with an
    injected RESOURCE_EXHAUSTED: one flaky round must survive via the
    halved-bucket retry, a persistently failing device must end in the
    HostEngineCache fallback — with correct results both times and no
    exception escaping. Runs anywhere (CPU backend); CI's --dry-run
    smoke covers the serving path's new failure handling."""
    import numpy as np

    from janus_tpu.aggregator import engine_cache as ec
    from janus_tpu.vdaf.registry import VdafInstance
    from janus_tpu.vdaf.testing import make_report_batch, random_measurements

    inst = VdafInstance.sum_vec(length=4, bits=2)
    vk = bytes(range(16))
    rng = np.random.default_rng(5)
    meas = random_measurements(inst, 4, rng)
    (nonce, public, meas_v, proof, blind0, seeds, blind1), _ = make_report_batch(
        inst, meas, seed=1
    )
    ok = np.ones(4, dtype=bool)

    # one injected OOM -> halved-bucket retry succeeds (observed bucket
    # MIN_BUCKET=32 stays above the floor even on an 8-device mesh)
    eng = ec.EngineCache(inst, vk)
    eng.bucket_cap = 32
    inner = eng._helper_init_inner
    fails = {"n": 0}

    def flaky(*a, **k):
        if fails["n"] < 1:
            fails["n"] += 1
            raise RuntimeError("RESOURCE_EXHAUSTED: injected (dry-run smoke)")
        return inner(*a, **k)

    eng._helper_init_inner = flaky
    _, seed0, ver0, part0 = eng.leader_init(nonce, public, meas_v, proof, blind0)
    _, mask, _ = eng.helper_init(nonce, public, seeds, blind1, ver0, part0, ok)
    retry_ok = bool(mask.all()) and fails["n"] == 1 and eng._host_fallback is None

    # persistent OOM -> bucket floor -> host fallback, still correct
    eng2 = ec.EngineCache(inst, vk)

    def always_oom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: injected (dry-run smoke)")

    eng2._helper_init_inner = always_oom
    out1, mask2, _ = eng2.helper_init(nonce, public, seeds, blind1, ver0, part0, ok)
    fallback_ok = bool(mask2.all()) and eng2._host_fallback is not None
    return {
        "halved_retry_ok": retry_ok,
        "bucket_cap_after_retry": eng.bucket_cap,
        "host_fallback_ok": fallback_ok,
    }


def _sparse_scatter_smoke() -> dict:
    """Block-sparse scatter-merge end to end on a toy geometry (CPU
    backend): two-party prepare over sparse reports, then scatter-add of
    each verified report's blocks into the dense logical accumulator via
    BOTH device paths — the classic per-bucket aggregate_sparse reduce
    and the pending-delta resident_merge — asserting the released
    aggregate is bit-identical to the dense oracle computed by expanding
    the plaintext measurements on host. Also proves the scatter path
    actually ran (the engine's scatter rows and
    janus_engine_scatter_rows_total both nonzero)."""
    import numpy as np

    from janus_tpu import metrics
    from janus_tpu.aggregator.engine_cache import EngineCache
    from janus_tpu.messages import Duration, Interval, Time
    from janus_tpu.vdaf.registry import VdafInstance, circuit_for
    from janus_tpu.vdaf.testing import (
        make_report_batch,
        random_measurements,
        sparse_compact_batch,
    )
    from janus_tpu.vdaf.wire import flat_scatter_indices

    inst = VdafInstance.sparse_sumvec(bits=3, length=48, block_size=4, max_blocks=3)
    circ = circuit_for(inst)
    rng = np.random.default_rng(11)
    n = 8
    meas = random_measurements(inst, n, rng)
    (nonce, public, mv, proof, blind0, seeds, blind1), _ = make_report_batch(
        inst, meas, seed=3
    )
    _, block_idx = sparse_compact_batch(inst, meas)
    flat_idx = flat_scatter_indices(block_idx, circ)
    ok = np.ones(n, dtype=bool)

    eng = EngineCache(inst, bytes(range(16)))
    out0, _, ver0, part0 = eng.leader_init(nonce, public, mv, proof, blind0)
    out1, accept, _ = eng.helper_init(nonce, public, seeds, blind1, ver0, part0, ok)
    share0 = eng.aggregate_sparse(out0, accept, flat_idx)
    share1 = eng.aggregate_sparse(out1, accept, flat_idx)
    p = circ.FIELD.MODULUS
    got = [(int(x) + int(y)) % p for x, y in zip(share0, share1)]
    # dense oracle: expand each plaintext pair-measurement and sum mod p
    want = [0] * circ.logical_length
    for m in meas:
        for bi, block in m:
            for off, v in enumerate(block):
                k = bi * circ.block_size + off
                want[k] = (want[k] + v) % p
    classic_identical = got == want and bool(accept.all())

    # resident path: the deltas defer the scatter to merge time, then a
    # take releases the logical-length share
    deltas = eng.aggregate_pending(out0, np.zeros(n, dtype=np.int32), 1, flat_idx=flat_idx)
    iv = Interval(Time(0), Duration(3600))
    eng.resident_merge([((b"task", b"", b"bid"), 0, n, iv)], deltas)
    recs = eng.resident_take()
    deltas1 = eng.aggregate_pending(out1, np.zeros(n, dtype=np.int32), 1, flat_idx=flat_idx)
    recs1 = eng.fetch_delta_records([((b"task", b"", b"bid"), 0, n, iv)], deltas1)
    resident = [
        (int(x) + int(y)) % p
        for x, y in zip(recs[0]["share"], recs1[0]["share"])
    ]
    resident_identical = resident == want
    scatter_rows = metrics.engine_scatter_rows_total.get(vdaf=inst.kind)
    return {
        "classic_identical": classic_identical,
        "resident_identical": resident_identical,
        "scatter_path_observed": eng._scatter_rows > 0 and scatter_rows > 0,
        "scatter_rows": eng._scatter_rows,
        "block_occupancy": eng._sparse_last_occupancy,
        "mesh_fallback_reason": eng.mesh_fallback_reason,
    }


def _ingest_shed_smoke() -> dict:
    """Drive a burst of real uploads through the admission-controlled
    ingest pipeline over loopback HTTP with a deliberately tiny token
    bucket: the first `burst` uploads must commit (exactly once), the
    rest must shed `429 + Retry-After`, and `janus_upload_shed_total`
    must account for every rejection. CPU-only, no accelerator — CI's
    --dry-run smoke covers the serving shed path on every test run."""
    from janus_tpu import metrics as _m
    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import MockClock
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.messages import Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    clock = MockClock(Time(1_600_000_000))
    eph = EphemeralDatastore(clock=clock)
    # burst of 3 then a ~glacial refill: uploads 4..8 shed deterministically
    cfg = Config(
        upload_bucket_rate=0.001,
        upload_bucket_burst=3,
        ingest_decrypt_workers=2,
        ingest_queue_depth=8,
    )
    agg = Aggregator(eph.datastore, clock, cfg)
    srv = DapServer(DapHttpApp(agg), max_handler_threads=4).start()
    try:
        vdaf = VdafInstance.count()
        leader_kp = generate_hpke_config_and_private_key(config_id=0)
        helper_kp = generate_hpke_config_and_private_key(config_id=1)
        task = (
            TaskBuilder(QueryTypeConfig.time_interval(), vdaf, Role.LEADER)
            .with_(
                leader_aggregator_endpoint=srv.url,
                helper_aggregator_endpoint=srv.url,
                hpke_keys=(leader_kp,),
                min_batch_size=1,
            )
            .build()
        )
        eph.datastore.run_tx(lambda tx: tx.put_task(task))
        params = ClientParameters(task.task_id, srv.url, srv.url, task.time_precision)
        client = Client(params, vdaf, leader_kp.config, helper_kp.config, clock=clock)
        http = HttpClient()
        shed0 = _m.upload_shed_counter.total()
        results = []
        for _ in range(8):
            report = client.prepare_report(1)
            status, _body = http.put(
                params.upload_uri(),
                report.to_bytes(),
                {"Content-Type": "application/dap-report"},
            )
            retry_after = next(
                (
                    v
                    for k, v in http.last_response_headers.items()
                    if k.lower() == "retry-after"
                ),
                None,
            )
            results.append((status, retry_after))
        accepted = sum(1 for s, _ in results if s == 201)
        shed = [r for r in results if r[0] == 429]
        stored, _ = eph.datastore.run_tx(
            lambda tx: tx.count_client_reports_for_task(task.task_id)
        )
        return {
            "accepted": accepted,
            "shed": len(shed),
            "shed_counter_delta": _m.upload_shed_counter.total() - shed0,
            "retry_after_present": bool(shed)
            and all(ra is not None and float(ra) >= 1 for _, ra in shed),
            "stored_reports": int(stored),
            "committed_exactly_once": int(stored) == accepted,
        }
    finally:
        srv.stop()
        eph.cleanup()


def _tracing_overhead(iters: int = 1000) -> dict:
    """Measure the span() hot path instead of assuming it: a synthetic
    per-report workload wrapped in the engine's span shape (one outer +
    three phase spans, the same names the span->metric bridge observes)
    timed with tracing disabled, with the Chrome-trace writer, and with
    the OTLP exporter recording spans (export posts go to an
    unroutable endpoint and fail in the background thread — the hot
    path cost is record_span, not the network). Also reports the bare
    cost of one span() enter/exit per mode."""
    import tempfile
    import time as _time

    import numpy as np

    from janus_tpu import trace as trace_mod
    from janus_tpu.trace import span

    a = np.random.default_rng(7).random((64, 64))
    b = a.T.copy()

    def workload_plain():
        a @ b
        a @ b
        a @ b

    def workload_traced():
        with span("bench.prepare", vdaf="bench", batch=64):
            with span("bench.prepare.put", vdaf="bench"):
                a @ b
            with span("bench.prepare.dispatch", vdaf="bench"):
                a @ b
            with span("bench.prepare.fetch", vdaf="bench"):
                a @ b

    def measure(fn=None) -> tuple[float, float]:
        """(workload iters/s, bare span cost ns)."""
        fn = fn or workload_traced
        t0 = _time.perf_counter()
        for _ in range(iters):
            fn()
        rps = iters / (_time.perf_counter() - t0)
        n_bare = 10_000
        t0 = _time.perf_counter()
        for _ in range(n_bare):
            with span("bench.overhead.noop"):
                pass
        span_ns = (_time.perf_counter() - t0) / n_bare * 1e9
        return rps, span_ns

    # save/restore the process-global exporters so the phase cannot
    # leak a writer into the rest of the run
    saved_writer = trace_mod._chrome_writer
    saved_otlp = trace_mod._otlp_exporter
    saved_recorder = trace_mod._flight_recorder
    tmp = tempfile.mkdtemp(prefix="janus-bench-trace-")

    class _NullRecorder:  # flight-recorder-off baseline (it is
        def record(self, *a, **k):  # always armed in production)
            pass

    try:
        trace_mod._chrome_writer = None
        trace_mod._otlp_exporter = None
        # warm numpy/BLAS and the span machinery before ANY measurement:
        # on a loaded 2-core host, thread-pool spin-up landing inside
        # the first timed mode skews the ratios
        for _ in range(200):
            workload_plain()
            workload_traced()
        # no-span baseline: disabled_vs_baseline isolates the cost of
        # the span machinery itself (contextvar + PRNG + the
        # span->metric bridge lookup + the always-armed flight
        # recorder) with no exporter configured
        baseline_rps, _ = measure(workload_plain)
        # recorder-off vs recorder-armed: the marginal cost of the
        # always-on flight recorder itself (ISSUE 6 "near-free" claim)
        trace_mod._flight_recorder = _NullRecorder()
        recorder_off_rps, recorder_off_ns = measure()
        trace_mod._flight_recorder = saved_recorder
        disabled_rps, disabled_ns = measure()

        trace_mod.install_chrome_trace(os.path.join(tmp, "overhead.json"))
        chrome_rps, chrome_ns = measure()
        trace_mod._chrome_writer.close()
        trace_mod._chrome_writer = None

        # long flush interval: no mid-measurement flush; shutdown's
        # final flush fails fast (connection refused on loopback)
        exporter = trace_mod.OtlpExporter(
            "http://127.0.0.1:9", flush_interval_s=3600.0
        )
        trace_mod._otlp_exporter = exporter
        otlp_rps, otlp_ns = measure()
        trace_mod._otlp_exporter = None
        exporter.shutdown()
    finally:
        trace_mod._chrome_writer = saved_writer
        trace_mod._otlp_exporter = saved_otlp
        trace_mod._flight_recorder = saved_recorder
    return {
        "iters": iters,
        "spans_per_iter": 4,
        "baseline_rps": round(baseline_rps, 1),
        "disabled_vs_baseline": round(disabled_rps / baseline_rps, 3),
        "disabled_rps": round(disabled_rps, 1),
        "recorder_off_rps": round(recorder_off_rps, 1),
        "chrome_rps": round(chrome_rps, 1),
        "otlp_rps": round(otlp_rps, 1),
        "chrome_vs_disabled": round(chrome_rps / disabled_rps, 3),
        "otlp_vs_disabled": round(otlp_rps / disabled_rps, 3),
        "span_ns_recorder_off": round(recorder_off_ns),
        "span_ns_disabled": round(disabled_ns),
        "span_ns_chrome": round(chrome_ns),
        "span_ns_otlp": round(otlp_ns),
    }


# /metrics families the BENCH json rider carries (the full snapshot
# would bloat the record; these are the device-path and job-health
# series this PR exists to expose).
_SNAPSHOT_PREFIXES = (
    "janus_engine_",
    "janus_jobs",
    "janus_job_",
    "janus_oldest_",
    "janus_unaggregated_",
    "janus_batches_",
    "janus_task_reports_",
    "janus_report_",
    "janus_span_",
    "janus_ingest_",
    "janus_upload_shed",
    "janus_upload_journal_",
    "janus_database_",
    "janus_datastore_",
    "janus_tx_retries",
    # continuous profiler + boot timeline (ISSUE 13)
    "janus_profiler_",
    "janus_boot_",
)


def _metrics_snapshot_rider() -> dict:
    """Compact {metric: samples} dict of the engine/job families for
    embedding in the BENCH json."""
    from janus_tpu.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    out = {}
    for name, fam in snap.items():
        if not name.startswith(_SNAPSHOT_PREFIXES):
            continue
        if fam["type"] == "histogram":
            out[name] = [
                {"labels": s["labels"], "sum": round(s["sum"], 6), "count": s["count"]}
                for s in fam["samples"]
            ]
        else:
            out[name] = [
                {"labels": s["labels"], "value": s["value"]} for s in fam["samples"]
            ]
    return out


def _scrape_health_listener(ds=None) -> dict:
    """Boot the real health listener, (optionally) run one health
    sampling pass against `ds`, and scrape /metrics + /statusz over
    HTTP, validating the scrape with the shared exposition parser."""
    import urllib.request

    from janus_tpu.binary_utils import HealthServer
    from janus_tpu.exposition import parse_exposition, validate_exposition

    if ds is not None:
        from janus_tpu.aggregator.health_sampler import HealthSampler

        HealthSampler(ds).run_once()
    srv = HealthServer("127.0.0.1:0").start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            text = resp.read().decode()
        errors = validate_exposition(text)
        families, _ = parse_exposition(text)
        with urllib.request.urlopen(base + "/statusz", timeout=10) as resp:
            statusz = json.loads(resp.read())
        # the SLO engine state and the OpenMetrics exemplar mode ride
        # every scrape record (the served phase distils alertz_ok and
        # the exemplar round-trip from these)
        with urllib.request.urlopen(base + "/alertz", timeout=10) as resp:
            alertz = json.loads(resp.read())
        with urllib.request.urlopen(base + "/metrics?openmetrics=1", timeout=10) as resp:
            om_text = resp.read().decode()
        om_errors = validate_exposition(om_text, openmetrics=True)
        with urllib.request.urlopen(base + "/debug/traces?limit=10000", timeout=10) as resp:
            debug_traces = json.loads(resp.read())
        return {
            "base": base,
            "text": text,
            "families": families,
            "errors": errors,
            "statusz": statusz,
            "alertz": alertz,
            "openmetrics_text": om_text,
            "openmetrics_errors": om_errors,
            "debug_traces": debug_traces,
            "server": srv,
        }
    except BaseException:
        srv.stop()
        raise


def _live_trace_ids(traces_doc: dict) -> set:
    """Trace ids currently resolvable on a /debug/traces snapshot."""
    return {s["trace_id"] for s in traces_doc.get("recent", ())} | {
        t["trace_id"] for t in traces_doc.get("slow_traces", ())
    }


def _freshest_resolving_exemplar(exemplars, live_ids) -> tuple:
    """(trace_id, resolved) over parser exemplar dicts, NEWEST first:
    a stale exemplar (a slow request from an earlier phase)
    legitimately outlives the bounded span ring — the claim under test
    is always that a FRESH exemplar resolves. Shared by the served
    phase's roundtrip record and the slo_alert smoke."""
    chosen = None
    for ex in sorted(exemplars, key=lambda e: e.get("ts") or 0, reverse=True):
        tid = ex["labels"].get("trace_id")
        if tid is None:
            continue
        chosen = chosen or tid
        if tid in live_ids:
            return tid, True
    return chosen, False


def _exemplar_roundtrip(scrape: dict) -> dict:
    """Resolve the freshest exemplar of each histogram family in the
    scrape's OpenMetrics text against the same listener's
    /debug/traces snapshot: {checked, resolved, example_trace_id}."""
    from janus_tpu.exposition import parse_exposition

    fams, _ = parse_exposition(scrape["openmetrics_text"], openmetrics=True)
    live_ids = _live_trace_ids(scrape["debug_traces"])
    checked = resolved = 0
    example = None
    for fam in fams.values():
        exemplars = [ex for _, _, ex in fam.exemplars]
        if not any(ex["labels"].get("trace_id") for ex in exemplars):
            continue
        checked += 1
        tid, ok = _freshest_resolving_exemplar(exemplars, live_ids)
        if ok:
            resolved += 1
            example = example or tid
    return {
        "checked": checked,
        "resolved": resolved,
        "example_trace_id": example,
        # at least one exemplar must exist AND resolve once real spans
        # have flowed; a ring-evicted older exemplar is not a failure
        "ok": checked > 0 and resolved > 0,
    }


def _trace_lifecycle_smoke() -> dict:
    """Prove the report-lifecycle tracing tentpole (ISSUE 6) on a live
    loopback leader+helper pair with the two-round fake VDAF: the
    creator persists a trace context in the aggregation job row; a
    driver instance runs the init round; a SECOND, fresh driver
    instance (the in-process analog of a driver restart — no shared
    state beyond the datastore) runs the continue round; a collection
    is created, persisted with its own trace context, and driven to a
    released aggregate. The flight recorder must then show leader
    driver spans and helper handler spans from BOTH rounds sharing the
    persisted job trace id, the collect-finish span linking back to
    it, and non-empty janus_report_e2e_seconds for both stages."""
    import dataclasses

    from janus_tpu import metrics as _m
    from janus_tpu import trace as _tr
    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.aggregation_job_driver import AggregationJobDriver
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import MockClock
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    def _e2e_counts() -> dict:
        fam = _m.REGISTRY.snapshot().get("janus_report_e2e_seconds", {})
        return {
            s["labels"].get("stage"): s["count"] for s in fam.get("samples", ())
        }

    e2e_before = _e2e_counts()
    clock = MockClock(Time(1_600_000_000))
    leader_eph = EphemeralDatastore(clock=clock)
    helper_eph = EphemeralDatastore(clock=clock)
    leader_ds, helper_ds = leader_eph.datastore, helper_eph.datastore
    leader_srv = DapServer(DapHttpApp(Aggregator(leader_ds, clock, Config()))).start()
    helper_srv = DapServer(DapHttpApp(Aggregator(helper_ds, clock, Config()))).start()
    try:
        vdaf = VdafInstance.fake_two_round()
        collector_kp = generate_hpke_config_and_private_key(config_id=200)
        leader_task = (
            TaskBuilder(QueryTypeConfig.time_interval(), vdaf, Role.LEADER)
            .with_(
                leader_aggregator_endpoint=leader_srv.url,
                helper_aggregator_endpoint=helper_srv.url,
                collector_hpke_config=collector_kp.config,
                aggregator_auth_token=AuthenticationToken.random_bearer(),
                collector_auth_token=AuthenticationToken.random_bearer(),
                min_batch_size=1,
            )
            .build()
        )
        helper_task = dataclasses.replace(
            leader_task,
            role=Role.HELPER,
            hpke_keys=(generate_hpke_config_and_private_key(config_id=1),),
        )
        leader_ds.run_tx(lambda tx: tx.put_task(leader_task))
        helper_ds.run_tx(lambda tx: tx.put_task(helper_task))

        http = HttpClient()
        params = ClientParameters(
            leader_task.task_id, leader_srv.url, helper_srv.url, leader_task.time_precision
        )
        client = Client.with_fetched_configs(params, vdaf, http, clock=clock)
        measurements = [1, 0, 1]
        for m in measurements:
            client.upload(m)

        creator = AggregationJobCreator(
            leader_ds, AggregationJobCreatorConfig(min_aggregation_job_size=1)
        )
        assert creator.run_once() == 1
        job = leader_ds.run_tx(
            lambda tx: tx.get_aggregation_jobs_for_task(leader_task.task_id)
        )[0]
        job_tc = job.trace_context
        job_trace_id = _tr.trace_id_of(job_tc) or ""
        helper_job_tc = None

        # round 1 (init) with one driver instance, round 2 (continue)
        # with a FRESH one: the only way the second can join the first's
        # trace is through the persisted row — the restart story
        jd_cfg = JobDriverConfig(max_concurrent_job_workers=1)
        driver_a = AggregationJobDriver(leader_ds, http)
        assert JobDriver(jd_cfg, driver_a.acquirer(), driver_a.stepper).run_once() == 1
        helper_job = helper_ds.run_tx(
            lambda tx: tx.get_aggregation_job(helper_task.task_id, job.job_id)
        )
        helper_job_tc = helper_job.trace_context if helper_job else None
        driver_b = AggregationJobDriver(leader_ds, http)
        assert JobDriver(jd_cfg, driver_b.acquirer(), driver_b.stepper).run_once() == 1

        # collect end-to-end through the real collector + driver
        start = Time(clock.now().seconds).to_batch_interval_start(
            leader_task.time_precision
        )
        query = Query.time_interval(
            Interval(Time(start.seconds - 3600), Duration(2 * 3600))
        )
        collector = Collector(
            CollectorParameters(
                leader_task.task_id,
                leader_srv.url,
                leader_task.collector_auth_token,
                collector_kp,
            ),
            vdaf,
            http,
        )
        cj_id = collector.start_collection(query)
        cjob = leader_ds.run_tx(
            lambda tx: tx.get_collection_job(leader_task.task_id, cj_id)
        )
        collection_tc = cjob.trace_context if cjob else None
        cdriver = CollectionJobDriver(leader_ds, http)
        assert JobDriver(jd_cfg, cdriver.acquirer(), cdriver.stepper).run_once() == 1
        result = collector.poll_once(cj_id, query)

        # the flight recorder (always armed — nothing was installed)
        rec = _tr.flight_recorder()
        spans = rec.snapshot(recent_limit=rec.capacity)["recent"]
        in_job_trace = {s["name"] for s in spans if s["trace_id"] == job_trace_id}
        finish = next(
            (s for s in reversed(spans) if s["name"] == "driver.collect_finish"), None
        )
        linked = (finish or {}).get("args", {}).get("linked_traces", "")
        e2e_after = _e2e_counts()
        return {
            "collected": result.report_count,
            "aggregate": result.aggregate_result,
            "job_trace_context_persisted": bool(job_tc),
            # the helper's row carries the SAME trace id, adopted off
            # the leader's wire request
            "helper_row_same_trace": bool(
                helper_job_tc and job_trace_id and job_trace_id in helper_job_tc
            ),
            "trace_span_names": sorted(in_job_trace),
            "leader_init_span_in_trace": "driver.http_init" in in_job_trace,
            "leader_continue_span_in_trace": "driver.http_continue" in in_job_trace,
            "helper_init_span_in_trace": "dap.aggregate_init" in in_job_trace,
            "helper_continue_span_in_trace": "dap.aggregate_continue" in in_job_trace,
            "collection_trace_context_persisted": bool(collection_tc),
            "collect_finish_span_in_collection_trace": bool(
                finish
                and collection_tc
                and finish["trace_id"] == _tr.trace_id_of(collection_tc)
            ),
            "collect_links_include_job_trace": bool(job_trace_id) and job_trace_id in linked,
            "e2e_aggregate_delta": e2e_after.get("aggregate", 0)
            - e2e_before.get("aggregate", 0),
            "e2e_collect_delta": e2e_after.get("collect", 0)
            - e2e_before.get("collect", 0),
        }
    finally:
        leader_srv.stop()
        helper_srv.stop()
        leader_eph.cleanup()
        helper_eph.cleanup()


def _slo_alert_smoke() -> dict:
    """Live proof of the SLO burn-rate engine (ISSUE 10) over loopback
    HTTP against real listeners: a failpoint-driven 5xx storm on real
    uploads flips the default upload_availability alert to firing on
    /alertz (burn rates over threshold, firing_since set,
    janus_alert_active=1 in /metrics), a latency exemplar from the
    OpenMetrics scrape resolves against a live /debug/traces capture,
    recovery clears the alert, scripts/debug_bundle.py produces a tar
    whose MANIFEST inventories every captured endpoint, and the default
    scrape stays exemplar-free (bit-compatible)."""
    import pathlib
    import subprocess
    import tarfile
    import tempfile
    import urllib.request

    from janus_tpu import failpoints
    from janus_tpu import metrics as _m
    from janus_tpu import slo as _slo
    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.binary_utils import HealthServer
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import MockClock
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.exposition import parse_exposition, validate_exposition
    from janus_tpu.messages import Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    clock = MockClock(Time(1_600_000_000))
    eph = EphemeralDatastore(clock=clock)
    agg = Aggregator(eph.datastore, clock, Config(ingest_decrypt_workers=2))
    srv = DapServer(DapHttpApp(agg), max_handler_threads=4).start()
    health = HealthServer("127.0.0.1:0").start()
    # the production ladder with every window shrunk 900x: the 1h/5m
    # page rung becomes 4s/0.33s — observable in a CI smoke without
    # forking the shipped definitions
    engine = _slo.install_slo_engine(
        _slo.SloEngineConfig(
            evaluation_interval_s=0.05, window_scale=1.0 / 900, budget_window_s=30.0
        )
    )
    base = f"http://127.0.0.1:{health.port}"
    out: dict = {}
    try:
        vdaf = VdafInstance.count()
        leader_kp = generate_hpke_config_and_private_key(config_id=0)
        helper_kp = generate_hpke_config_and_private_key(config_id=1)
        task = (
            TaskBuilder(QueryTypeConfig.time_interval(), vdaf, Role.LEADER)
            .with_(
                leader_aggregator_endpoint=srv.url,
                helper_aggregator_endpoint=srv.url,
                hpke_keys=(leader_kp,),
                min_batch_size=1,
            )
            .build()
        )
        eph.datastore.run_tx(lambda tx: tx.put_task(task))
        params = ClientParameters(task.task_id, srv.url, srv.url, task.time_precision)
        client = Client(params, vdaf, leader_kp.config, helper_kp.config, clock=clock)
        http = HttpClient()

        def upload_once() -> int:
            report = client.prepare_report(1)
            status, _ = http.put(
                params.upload_uri(),
                report.to_bytes(),
                {"Content-Type": "application/dap-report"},
            )
            return status

        def get_json(path: str) -> dict:
            with urllib.request.urlopen(base + path, timeout=10) as resp:
                return json.loads(resp.read())

        def upload_alerts(doc: dict) -> dict:
            return {
                a["severity"]: a
                for a in doc["alerts"]
                if a["alert"] == "upload_availability"
            }

        # --- healthy baseline: real 201s, no alert ---
        good_statuses = [upload_once() for _ in range(3)]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if upload_alerts(get_json("/alertz")):
                break
            time.sleep(0.05)
        baseline = upload_alerts(get_json("/alertz"))
        out["baseline_statuses"] = good_statuses
        out["baseline_firing"] = sorted(
            s for s, a in baseline.items() if a["state"] == "firing"
        )

        # --- failpoint-driven 5xx storm: the report-write flush fails,
        # so REAL uploads (admitted, decrypted) answer 500 ---
        failpoints.configure("report_writer.flush=error")
        storm_statuses = []
        try:
            deadline = time.monotonic() + 20
            fired = None
            while time.monotonic() < deadline:
                storm_statuses.append(upload_once())
                doc = get_json("/alertz")
                page = upload_alerts(doc).get("page")
                if page and page["state"] == "firing":
                    fired = (doc, page)
                    break
                time.sleep(0.05)
        finally:
            failpoints.clear()
        out["storm_statuses_5xx"] = sum(1 for s in storm_statuses if 500 <= s < 600)
        out["alert_fired"] = fired is not None
        if fired:
            doc, page = fired
            out["burn_rate_long"] = page["burn_rate_long"]
            out["burn_rate_short"] = page["burn_rate_short"]
            out["burn_rate_threshold"] = page["burn_rate_threshold"]
            out["burn_over_threshold"] = (
                page["burn_rate_long"] >= page["burn_rate_threshold"]
                and page["burn_rate_short"] >= page["burn_rate_threshold"]
            )
            out["firing_since_set"] = page["firing_since_unix"] is not None
            out["alertz_firing_list"] = doc["firing"]
            slo_doc = next(
                s for s in doc["slos"] if s["name"] == "upload_availability"
            )
            out["budget_remaining_while_firing"] = slo_doc[
                "error_budget_remaining_ratio"
            ]
            out["evidence_present"] = bool(slo_doc["evidence"])

        # --- janus_alert_active visible in the default /metrics scrape
        # (and the default scrape stays exemplar-free) ---
        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            default_text = resp.read().decode()
        fams, _ = parse_exposition(default_text)
        active = fams.get("janus_alert_active")
        out["alert_active_in_metrics"] = any(
            labels.get("alert") == "upload_availability"
            and labels.get("severity") == "page"
            and v == 1.0
            for _, labels, v in (active.samples if active else [])
        )
        # re-reading the default scrape WITH exemplar parsing must find
        # none (a substring test would false-positive on a legal label
        # value containing ' # {')
        leak_fams, _ = parse_exposition(default_text, openmetrics=True)
        out["default_scrape_exemplar_free"] = not any(
            f.exemplars for f in leak_fams.values()
        )
        out["default_scrape_valid"] = not validate_exposition(default_text)

        # --- exemplar round-trip: an upload-route latency exemplar from
        # the OpenMetrics scrape resolves to a live /debug/traces span ---
        with urllib.request.urlopen(
            base + "/metrics?openmetrics=1", timeout=10
        ) as resp:
            om_text = resp.read().decode()
            om_ctype = resp.headers.get("Content-Type", "")
        out["openmetrics_content_type_ok"] = om_ctype.startswith(
            "application/openmetrics-text"
        )
        om_errors = validate_exposition(om_text, openmetrics=True)
        out["openmetrics_scrape_valid"] = not om_errors
        out["openmetrics_errors"] = om_errors[:3]
        om_fams, _ = parse_exposition(om_text, openmetrics=True)
        dur = om_fams.get("janus_http_request_duration_seconds")
        upload_exemplars = [
            ex
            for _, labels, ex in (dur.exemplars if dur else [])
            if labels.get("route") == "upload"
        ]
        out["upload_exemplar_count"] = len(upload_exemplars)
        resolved = False
        exemplar_trace = None
        if upload_exemplars:
            exemplar_trace, resolved = _freshest_resolving_exemplar(
                upload_exemplars,
                _live_trace_ids(get_json("/debug/traces?limit=10000")),
            )
        out["exemplar_trace_id"] = exemplar_trace
        out["exemplar_resolves_in_debug_traces"] = resolved

        # --- recovery: healthy uploads, the windows slide past the
        # storm, the alert clears and the gauge drops to 0 ---
        deadline = time.monotonic() + 20
        cleared = False
        while time.monotonic() < deadline:
            upload_once()
            doc = get_json("/alertz")
            if not any(
                a["state"] == "firing" for a in upload_alerts(doc).values()
            ):
                cleared = True
                break
            time.sleep(0.2)
        out["alert_cleared_after_recovery"] = cleared
        out["alert_active_gauge_after_recovery"] = _m.alert_active.get(
            alert="upload_availability", severity="page"
        )

        # --- one-command incident debug bundle against the live
        # listener: every endpoint captured, MANIFEST inventories them ---
        repo = pathlib.Path(__file__).resolve().parent
        with tempfile.TemporaryDirectory() as td:
            bundle_path = os.path.join(td, "bundle.tar.gz")
            proc = subprocess.run(
                [
                    sys.executable,
                    str(repo / "scripts" / "debug_bundle.py"),
                    "--url",
                    base,
                    "--out",
                    bundle_path,
                ],
                capture_output=True,
                text=True,
                timeout=120,
            )
            out["bundle_rc"] = proc.returncode
            out["bundle_err"] = proc.stderr[-300:] if proc.returncode else ""
            if proc.returncode == 0:
                from janus_tpu.tools.debug_bundle import ENDPOINTS

                with tarfile.open(bundle_path) as tar:
                    names = tar.getnames()
                    manifest_name = next(
                        n for n in names if n.endswith("MANIFEST.json")
                    )
                    manifest = json.loads(
                        tar.extractfile(manifest_name).read()
                    )
                target = next(iter(manifest["targets"].values()))
                captured = target["endpoints"]
                out["bundle_endpoints_captured"] = sorted(captured)
                out["bundle_manifest_complete"] = all(
                    name in captured and captured[name].get("status") is not None
                    for name, _ in ENDPOINTS
                )
                out["bundle_files"] = len(manifest["files"])
        return out
    finally:
        _slo.uninstall_slo_engine()
        health.stop()
        srv.stop()
        eph.cleanup()


def _observability_smoke() -> dict:
    """Drive the full observability surface on CPU and prove the
    acceptance criteria end-to-end: the live health listener's /metrics
    scrape is exposition-valid (including a hostile label value
    containing a double quote and a newline), janus_engine_dispatch_seconds
    and janus_jobs carry non-zero samples, /statusz renders task +
    engine-cache state, POST /debug/profile yields a loadable host
    Chrome trace while a concurrent capture 409s, and
    scripts/scrape_check.py passes against the same listener."""
    import pathlib
    import subprocess
    import threading
    import urllib.error
    import urllib.request

    from janus_tpu import metrics as _m
    from janus_tpu.aggregator.engine_cache import engine_cache
    from janus_tpu.datastore.models import (
        AggregationJobModel,
        AggregationJobState,
        LeaderStoredReport,
    )
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.messages import (
        AggregationJobId,
        Duration,
        HpkeCiphertext,
        HpkeConfigId,
        Interval,
        ReportId,
        Role,
        Time,
    )
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    # the continuous profiler runs through the whole smoke like in the
    # real binaries (janus_main installs it by default) — scrape_check
    # below validates /debug/profile live, which requires the sampler
    # running; a fast-ish rate so the short smoke accumulates samples
    from janus_tpu import profiler as _prof

    _prof.install_profiler(_prof.ProfilerConfig(hz=47.0, window_secs=10.0))

    # the telemetry flight recorder likewise runs like in the real
    # binaries — scrape_check validates the /statusz flight section and
    # its last-snapshot freshness against this listener
    from janus_tpu import flight_recorder as _flight

    _flight.install_flight_recorder(
        _flight.FlightRecorderConfig(interval_s=0.5)
    ).snapshot_once()

    # the report-lifecycle tracing smoke runs FIRST so its e2e series
    # and flight-recorder state are live in the scrape below
    trace_lifecycle = _trace_lifecycle_smoke()

    # the SLO burn-rate engine's live proof (ISSUE 10): 5xx storm ->
    # /alertz firing -> exemplar round-trip -> recovery -> debug bundle
    slo_alert = _slo_alert_smoke()

    # a label value that would corrupt an unescaped scrape
    _m.aggregate_step_failure_counter.add(type='hostile"label\nvalue\\end')

    eph = EphemeralDatastore()
    clock = eph.clock
    task = (
        TaskBuilder(QueryTypeConfig.time_interval(), VdafInstance.count(), Role.LEADER)
        .with_(min_batch_size=1)
        .build()
    )

    def provision(tx):
        tx.put_task(task)
        # one in-progress job and one unaggregated report so the
        # sampler has a real backlog to export
        tx.put_aggregation_job(
            AggregationJobModel(
                task.task_id,
                AggregationJobId(b"\x01" * 16),
                b"",
                b"",
                Interval(Time(clock.now().seconds - 120), Duration(60)),
                AggregationJobState.IN_PROGRESS,
                0,
                None,
            )
        )
        tx.put_client_report(
            LeaderStoredReport(
                task.task_id,
                ReportId(b"\x02" * 16),
                Time(clock.now().seconds - 300),
                b"",
                b"share",
                HpkeCiphertext(HpkeConfigId(0), b"enc", b"payload"),
            )
        )
        # book the hand-provisioned report so the conservation ledger's
        # books balance (the real admission path does this in-tx)
        from janus_tpu import ledger as _lg

        _lg.count_admitted(tx, task.task_id, 1)

    eph.datastore.run_tx(provision)
    # engine-cache state for /statusz (hit + miss counters ride along);
    # the dispatch histograms were already fed by the OOM smoke's real
    # engine calls through the span->metric bridge
    inst = VdafInstance.sum_vec(length=4, bits=2)
    engine_cache(inst, bytes(range(16)))
    engine_cache(inst, bytes(range(16)))

    # the task list section janus_main registers in the real binaries
    from janus_tpu.metrics import task_id_label
    from janus_tpu.statusz import register_status_provider

    register_status_provider(
        "tasks",
        lambda: [
            {
                "task_id": task_id_label(t.task_id.data),
                "role": t.role.name,
                "vdaf": t.vdaf.kind,
            }
            for t in eph.datastore.run_tx(lambda tx: tx.get_tasks(), "statusz_tasks")
        ],
    )

    # the report-flow conservation ledger runs like in the real binaries
    # (every datastore-owning binary installs it) — scrape_check below
    # validates the `ledger` statusz section and /debug/ledger live; one
    # evaluation before the scrape so the balance document is populated
    from janus_tpu import ledger as _ledger

    ledger_ev = _ledger.install_ledger(eph.datastore, _ledger.LedgerConfig())
    ledger_ev.evaluate_once()

    scrape = _scrape_health_listener(ds=eph.datastore)
    srv = scrape["server"]
    try:
        base = scrape["base"]
        families = scrape["families"]
        dispatch = families.get("janus_engine_dispatch_seconds")
        dispatch_count = sum(
            v
            for name, labels, v in (dispatch.samples if dispatch else [])
            if name.endswith("_count")
        )
        jobs = families.get("janus_jobs")
        jobs_in_progress = next(
            (
                v
                for name, labels, v in (jobs.samples if jobs else [])
                if labels.get("type") == "aggregation"
                and labels.get("state") == "in_progress"
            ),
            0.0,
        )
        hostile = families["janus_aggregate_step_failures"]
        hostile_ok = any(
            labels.get("type") == 'hostile"label\nvalue\\end'
            for _, labels, _ in hostile.samples
        )
        statusz = scrape["statusz"]

        # concurrent profile captures: exactly one wins, one 409s. The
        # listener is in-process, so the second POST fires only once
        # the first's capture window is provably open (the guard lock
        # is held) — deterministic, not a sleep race.
        import janus_tpu.binary_utils as _bu

        codes = []

        def post(seconds):
            req = urllib.request.Request(
                base + f"/debug/profile?seconds={seconds}", method="POST"
            )
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    codes.append((resp.status, resp.read()))
            except urllib.error.HTTPError as e:
                codes.append((e.code, e.read()))
            except Exception as e:  # record, never drop silently
                codes.append((f"error: {type(e).__name__}: {e}", b""))

        t1 = threading.Thread(target=post, args=(2,))
        t1.start()
        deadline = time.monotonic() + 60
        while not _bu._profile_lock.locked() and time.monotonic() < deadline:
            time.sleep(0.02)
        t2 = threading.Thread(target=post, args=(1,))
        t2.start()
        t1.join()
        t2.join()
        status_codes = sorted((c for c, _ in codes), key=str)
        host_trace_loadable = False
        for code, body in codes:
            if code == 200:
                artifacts = json.loads(body)
                raw = open(artifacts["host_chrome_trace"]).read().rstrip()
                json.loads(raw if raw.endswith("]") else raw + "{}]")
                host_trace_loadable = True

        # the always-on flight recorder over live HTTP: /debug/traces
        # must be valid JSON with the lifecycle smoke's spans in it
        with urllib.request.urlopen(base + "/debug/traces?limit=50", timeout=10) as resp:
            traces_doc = json.loads(resp.read())
        debug_traces_ok = (
            {"recent", "slow_traces", "digests", "recorded_total"} <= set(traces_doc)
            and traces_doc["recorded_total"] > 0
            and len(traces_doc["recent"]) > 0
        )

        # continuous profiler over live HTTP (ISSUE 13): the collapsed
        # document folds clean (shared validator) and the JSON mode
        # carries per-role shares with the sampler enabled
        with urllib.request.urlopen(base + "/debug/profile", timeout=10) as resp:
            collapsed_text = resp.read().decode()
        profile_collapsed_ok = (
            not _prof.validate_collapsed(collapsed_text) and bool(collapsed_text)
        )
        with urllib.request.urlopen(
            base + "/debug/profile?format=json", timeout=10
        ) as resp:
            profile_doc = json.loads(resp.read())
        profile_roles = sorted(profile_doc.get("roles", {}))
        with urllib.request.urlopen(base + "/debug/boot", timeout=10) as resp:
            boot_doc = json.loads(resp.read())
        debug_boot_ok = {"started_unix", "ready", "phases"} <= set(boot_doc)

        # conservation ledger over live HTTP (ISSUE 20): /debug/ledger
        # must answer the full balance document with the smoke's one
        # admitted-but-unaggregated report attributably in flight
        with urllib.request.urlopen(base + "/debug/ledger", timeout=10) as resp:
            ledger_doc = json.loads(resp.read())
        debug_ledger_ok = (
            ledger_doc.get("enabled") is True
            and {"evaluations", "tasks", "breaches"} <= set(ledger_doc)
            and ledger_doc["evaluations"] >= 1
        )

        repo = pathlib.Path(__file__).resolve().parent
        check = subprocess.run(
            [
                sys.executable,
                str(repo / "scripts" / "scrape_check.py"),
                "--url",
                base,
                "--statusz",
            ],
            capture_output=True,
            text=True,
            timeout=60,
        )
        return {
            "scrape_valid": not scrape["errors"],
            "scrape_errors": scrape["errors"][:5],
            "engine_dispatch_samples": int(dispatch_count),
            "jobs_in_progress": jobs_in_progress,
            "hostile_label_roundtrip": hostile_ok,
            "statusz_tasks": len(statusz.get("tasks", [])),
            "statusz_engine_cache_entries": statusz.get("engine_cache", {}).get(
                "entries", 0
            ),
            "statusz_job_health_present": "job_health" in statusz,
            "oldest_unaggregated_age_s": statusz.get("job_health", {})
            .get("oldest_unaggregated_report_age_seconds", {}),
            "profile_status_codes": status_codes,
            "profile_host_trace_loadable": host_trace_loadable,
            "debug_traces_ok": debug_traces_ok,
            "statusz_flight_recorder_present": "flight_recorder" in statusz,
            "statusz_flight_present": "flight" in statusz,
            "scrape_check_rc": check.returncode,
            "scrape_check_err": check.stderr[-500:] if check.returncode else "",
            # continuous profiler over live HTTP (ISSUE 13): collapsed
            # format well-formed, JSON roles present, statusz sections
            "profile_collapsed_ok": profile_collapsed_ok,
            "profile_roles": profile_roles,
            "debug_boot_ok": debug_boot_ok,
            "statusz_profile_present": "profile" in statusz,
            # conservation ledger (ISSUE 20): statusz section + live
            # /debug/ledger document, books balanced on the smoke task
            "statusz_ledger_present": "ledger" in statusz,
            "debug_ledger_ok": debug_ledger_ok,
            "ledger_breaches": ledger_doc.get("breaches", []),
            "trace_lifecycle": trace_lifecycle,
            "slo_alert": slo_alert,
        }
    finally:
        srv.stop()
        eph.cleanup()
        _ledger.uninstall_ledger()
        _flight.uninstall_flight_recorder()
        _prof.uninstall_profiler()


def _ledger_smoke() -> dict:
    """Smoke-level proof of the report-flow conservation ledger (ISSUE
    20): reports admitted through the REAL group-commit admission path
    leave the books balanced (ingest imbalance 0); then the
    `ledger.drop_report` failpoint silently deletes one admitted report
    AFTER its admission tx counted it — no rate metric moves, but the
    very next ledger evaluation books a +1 ingest imbalance, the breach
    fires immediately (grace 0), and the `conservation` SLO signal goes
    bad on the same tick."""
    from janus_tpu import failpoints as _fp
    from janus_tpu import ledger as _ledger
    from janus_tpu.aggregator.report_writer import ReportWriteBatcher
    from janus_tpu.datastore.models import LeaderStoredReport
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.messages import (
        HpkeCiphertext,
        HpkeConfigId,
        ReportId,
        Role,
        Time,
    )
    from janus_tpu.slo import ConservationSignal
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    eph = EphemeralDatastore()
    try:
        ds = eph.datastore
        clock = eph.clock
        task = (
            TaskBuilder(
                QueryTypeConfig.time_interval(), VdafInstance.count(), Role.LEADER
            )
            .with_(min_batch_size=1)
            .build()
        )
        ds.run_tx(lambda tx: tx.put_task(task))
        batcher = ReportWriteBatcher(ds)

        def mk(i: int) -> LeaderStoredReport:
            return LeaderStoredReport(
                task.task_id,
                ReportId(bytes([i]) * 16),
                Time(clock.now().seconds - 60),
                b"",
                b"share",
                HpkeCiphertext(HpkeConfigId(0), b"enc", b"payload"),
            )

        batcher.flush_direct([mk(i) for i in range(1, 4)])
        # grace 0: a nonzero imbalance breaches on the evaluation that
        # first sees it — "within one sampler interval" by construction
        ev = _ledger.LedgerEvaluator(ds, _ledger.LedgerConfig(grace_s=0.0))
        ev.evaluate_once()
        doc = ev.document()
        balanced_ok = bool(doc["tasks"]) and all(
            t["imbalance"].get("ingest") == 0 and t["imbalance"].get("collect") == 0
            for t in doc["tasks"].values()
        )
        balanced_breaches = list(doc.get("breaches", []))

        # fresh SLO tick state for the conservation signal (the real
        # engine holds this per-signal dict; a stub suffices here)
        class _Eng:
            _condition_state: dict = {}

        eng = _Eng()
        sig = ConservationSignal()
        bad0, total0, _ = sig.read(eng)

        # injected-loss lane: the admission tx counts the report, the
        # failpoint deletes the row before commit — a silent loss
        _fp.configure("ledger.drop_report=error:1.0,count=1")
        try:
            batcher.flush_direct([mk(9)])
        finally:
            _fp.clear()
        ev.evaluate_once()
        doc2 = ev.document()
        loss_imbalances = {
            label: t["imbalance"].get("ingest")
            for label, t in doc2["tasks"].items()
        }
        bad1, total1, _ = sig.read(eng)
        return {
            "balanced_ok": balanced_ok,
            "balanced_breaches": balanced_breaches,
            "loss_imbalance_total": sum(v or 0 for v in loss_imbalances.values()),
            "loss_detected_in_one_evaluation": any(
                v == 1 for v in loss_imbalances.values()
            ),
            "breach_fired": bool(doc2.get("breaches")),
            "slo_bad_before": bad0,
            "slo_bad_after": bad1,
            "slo_fired": bad1 > bad0 and total1 > total0,
            "evaluations": doc2.get("evaluations", 0),
        }
    finally:
        eph.cleanup()


def _failpoint_overhead(iters: int = 200_000) -> dict:
    """Measure — not assume — the cost of an instrumented failpoint
    site on the hot path: ns per `failpoints.hit()` with the registry
    disarmed (the production state: one module-flag check) and with
    OTHER failpoints armed (one dict miss under the registry lock),
    against an empty-loop baseline. The upload/commit/dispatch paths
    each carry one or two of these per operation, so disarmed cost must
    be unmeasurable against any real work."""
    import time as _time

    from janus_tpu import failpoints

    was = failpoints.status()
    failpoints.clear()

    def measure(fn) -> float:
        t0 = _time.perf_counter()
        for _ in range(iters):
            fn()
        return (_time.perf_counter() - t0) / iters * 1e9

    try:
        baseline_ns = measure(lambda: None)
        disabled_ns = measure(lambda: failpoints.hit("bench.hot_path"))
        failpoints.configure("bench.other_site=delay:0.0,count=0")
        armed_other_ns = measure(lambda: failpoints.hit("bench.hot_path"))
    finally:
        failpoints.clear()
        if was.get("enabled"):  # restore a caller's armed schedule
            failpoints.configure(
                {
                    n: f"{fp['action']}:{fp['arg']},prob={fp['prob']}"
                    + (f",count={fp['count']}" if fp["count"] is not None else "")
                    for n, fp in was["failpoints"].items()
                }
            )
    return {
        "iters": iters,
        "baseline_ns": round(baseline_ns, 1),
        "disabled_ns_per_hit": round(disabled_ns, 1),
        "armed_other_ns_per_hit": round(armed_other_ns, 1),
        "disabled_overhead_ns": round(disabled_ns - baseline_ns, 1),
    }


def _paired_ratio(slow_fn, fast_fn, iters: int = 15):
    """(min slow s, min fast s, median per-pair ratio). Measures in
    INTERLEAVED pairs with GC paused and takes the median per-pair
    ratio: the two paths must see the same CPU frequency / cache /
    scheduler conditions, or whole-run drift lands on one side and an
    acceptance gate flakes (observed on the codec bench: a 4.9x
    outlier from separate-block best-of-N against a 6.5x steady
    state). Shared by the codec record and the upload-batch record."""
    import gc
    import statistics
    import time as _time

    def timed(fn) -> float:
        t0 = _time.perf_counter()
        fn()
        return _time.perf_counter() - t0

    slow_ts, fast_ts, ratios = [], [], []
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        timed(slow_fn), timed(fast_fn)  # warm first-touch pages
        for _ in range(iters):
            s = timed(slow_fn)
            f = timed(fast_fn)
            slow_ts.append(s)
            fast_ts.append(f)
            ratios.append(s / f)
    finally:
        if gc_was_enabled:
            gc.enable()
    return min(slow_ts), min(fast_ts), statistics.median(ratios)


def _codec_speed_record(inst=None, batch: int = 2048) -> dict:
    """Measured leader<->helper wire-codec speed (ISSUE 9 acceptance:
    columnar >= 5x the per-report loop at batch >= 1024, bit-identical
    bytes). Builds a prepare-shaped init request two ways — the
    pre-ISSUE-9 per-report loop (encode_field_rows rows ->
    encode_prep_share_raw -> encode_pingpong -> PrepareInit dataclasses
    -> items encode) and the columnar path (one vectorized framing pass
    + PreEncoded splices) — asserts the request bytes are IDENTICAL,
    and times both; the response side (AggregationJobResp.from_bytes vs
    decode_prepare_resps_fast) rides along."""
    import secrets
    import time as _time

    import numpy as np

    from janus_tpu.messages import (
        AggregationJobInitializeReq,
        AggregationJobResp,
        HpkeCiphertext,
        HpkeConfigId,
        PartialBatchSelector,
        PreEncoded,
        PrepareInit,
        PrepareResp,
        PrepareStepResult,
        ReportId,
        ReportMetadata,
        ReportShare,
        Time,
        decode_prepare_resps_fast,
        encode_report_share_raw,
    )
    from janus_tpu.vdaf.registry import VdafInstance, circuit_for
    from janus_tpu.vdaf.wire import (
        PP_FINISH,
        PP_INITIALIZE,
        Prio3Wire,
        encode_field_rows,
        encode_pingpong,
        encode_pingpong_share_column,
    )

    if inst is None or inst.kind == "poplar1":
        inst = VdafInstance.histogram(10)
    circ = circuit_for(inst)
    wire = Prio3Wire(circ)

    class _JF:
        LIMBS = circ.FIELD.ENCODED_SIZE // 8
        MODULUS = circ.FIELD.MODULUS

    jf = _JF()
    rng = np.random.default_rng(0xC0DEC)
    n = batch
    v = circ.verifier_len
    ver0 = tuple(
        rng.integers(0, 1 << 31, size=(n, v), dtype=np.uint64)
        for _ in range(jf.LIMBS)
    )
    part0 = (
        rng.integers(0, 1 << 63, size=(n, 2), dtype=np.uint64)
        if wire.uses_jr
        else None
    )
    # stored-report columns shared by both paths (the driver reads
    # these from the datastore rows)
    rids = [secrets.token_bytes(16) for _ in range(n)]
    t = Time(1_600_000_000)
    pub = secrets.token_bytes(wire.public_share_len)
    ct = HpkeCiphertext(
        HpkeConfigId(1),
        secrets.token_bytes(32),
        secrets.token_bytes(wire.helper_share_len + 44),
    )
    pbs = PartialBatchSelector.time_interval()

    def loop_path() -> bytes:
        ver_rows = encode_field_rows(jf, ver0)
        part_rows = (
            [row.tobytes() for row in np.asarray(part0, dtype="<u8")]
            if wire.uses_jr
            else [None] * n
        )
        prep_inits = []
        for i in range(n):
            prep_share = wire.encode_prep_share_raw(ver_rows[i], part_rows[i])
            prep_inits.append(
                PrepareInit(
                    ReportShare(ReportMetadata(ReportId(rids[i]), t), pub, ct),
                    encode_pingpong(PP_INITIALIZE, None, prep_share),
                )
            )
        return AggregationJobInitializeReq(b"", pbs, tuple(prep_inits)).to_bytes()

    def columnar_path() -> bytes:
        frames = encode_pingpong_share_column(jf, ver0, part0)
        items = tuple(
            PreEncoded(
                encode_report_share_raw(rids[i], t.seconds, pub, ct) + frames.row(i)
            )
            for i in range(n)
        )
        return AggregationJobInitializeReq(b"", pbs, items).to_bytes()

    identical = loop_path() == columnar_path()

    enc_loop_s, enc_col_s, enc_ratio = _paired_ratio(loop_path, columnar_path)

    # response side: the helper's typical 1-round answer per report
    msg = encode_pingpong(PP_FINISH, b"x" * 16, None)
    body = AggregationJobResp(
        tuple(
            PrepareResp(ReportId(r), PrepareStepResult.cont(msg)) for r in rids
        )
    ).to_bytes()
    dec_loop_s, dec_col_s, dec_ratio = _paired_ratio(
        lambda: AggregationJobResp.from_bytes(body),
        lambda: decode_prepare_resps_fast(body),
    )
    # content equivalence, not just count: the record's claim must be
    # the one tests/test_wire_columnar.py pins
    ref = AggregationJobResp.from_bytes(body)
    col = decode_prepare_resps_fast(body)
    decoded_identical = (
        col.report_ids == [r.report_id.data for r in ref.prepare_resps]
        and list(col.kinds) == [r.result.kind for r in ref.prepare_resps]
        and col.messages == [r.result.message for r in ref.prepare_resps]
        and col.errors == [r.result.prepare_error for r in ref.prepare_resps]
    )

    return {
        "vdaf": inst.kind,
        "batch": n,
        "wire_bytes_identical": identical,
        "decode_roundtrip_ok": decoded_identical,
        "encode_us_per_report_loop": round(enc_loop_s / n * 1e6, 3),
        "encode_us_per_report_columnar": round(enc_col_s / n * 1e6, 3),
        "encode_speedup": round(enc_ratio, 2),
        "decode_us_per_report_loop": round(dec_loop_s / n * 1e6, 3),
        "decode_us_per_report_columnar": round(dec_col_s / n * 1e6, 3),
        "decode_speedup": round(dec_ratio, 2),
    }


def _hist_totals(metric) -> tuple[int, float]:
    """(observation count, sum) across every label set of a Histogram
    (delta-based batching evidence for the ingest-batch records)."""
    with metric._lock:
        return sum(metric._totals.values()), sum(metric._sums.values())


def _upload_client_stack(cfg=None, inst=None, max_handler_threads: int = 24):
    """A served upload stack on loopback HTTP (leader Aggregator +
    DapServer + a Client for one provisioned task), shared by the
    ingest-batch smoke and the open-loop load generator. Returns
    (eph, srv, task, params, client, clock)."""
    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.time_util import MockClock
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.messages import Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    clock = MockClock(Time(1_600_000_000))
    eph = EphemeralDatastore(clock=clock)
    agg = Aggregator(eph.datastore, clock, cfg or Config())
    srv = DapServer(DapHttpApp(agg), max_handler_threads=max_handler_threads).start()
    vdaf = inst or VdafInstance.count()
    leader_kp = generate_hpke_config_and_private_key(config_id=0)
    helper_kp = generate_hpke_config_and_private_key(config_id=1)
    task = (
        TaskBuilder(QueryTypeConfig.time_interval(), vdaf, Role.LEADER)
        .with_(
            leader_aggregator_endpoint=srv.url,
            helper_aggregator_endpoint=srv.url,
            hpke_keys=(leader_kp,),
            min_batch_size=1,
        )
        .build()
    )
    eph.datastore.run_tx(lambda tx: tx.put_task(task))
    params = ClientParameters(task.task_id, srv.url, srv.url, task.time_precision)
    client = Client(params, vdaf, leader_kp.config, helper_kp.config, clock=clock)
    return eph, srv, task, params, client, clock


def _upload_batch_speed_record(inst=None, window: int = 256) -> dict:
    """Measured server-side upload decrypt+decode speed (ISSUE 11
    acceptance: batched >= 3x the per-report path at window >= 256,
    bit-identical results). Runs the same window of REAL client upload
    bodies two ways — the per-report oracle (Report.from_bytes ->
    upload_prepare -> upload_decrypt_validate, exactly what the
    pre-batching decrypt pool executed per report) and the batched
    path (decode_reports_fast -> upload_prepare_columns ->
    upload_decrypt_validate_batch) — asserts the stored reports are
    IDENTICAL, and times both interleaved (median per-pair ratio, GC
    paused; the codec bench's anti-drift discipline)."""
    import numpy as np

    from janus_tpu.aggregator import Config
    from janus_tpu.aggregator.core import TaskAggregator
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.time_util import MockClock
    from janus_tpu.messages import Report, Role, Time, decode_reports_fast
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance
    from janus_tpu.vdaf.testing import random_measurements

    if inst is None or inst.kind == "poplar1":
        inst = VdafInstance.count()
    clock = MockClock(Time(1_600_000_000))
    leader_kp = generate_hpke_config_and_private_key(config_id=0)
    helper_kp = generate_hpke_config_and_private_key(config_id=1)
    task = (
        TaskBuilder(QueryTypeConfig.time_interval(), inst, Role.LEADER)
        .with_(
            leader_aggregator_endpoint="http://leader",
            helper_aggregator_endpoint="http://helper",
            hpke_keys=(leader_kp,),
            min_batch_size=1,
        )
        .build()
    )
    params = ClientParameters(
        task.task_id, "http://leader", "http://helper", task.time_precision
    )
    client = Client(params, inst, leader_kp.config, helper_kp.config, clock=clock)
    rng = np.random.default_rng(0xB47C4)
    meas = random_measurements(inst, window, rng)
    bodies = [
        client.prepare_report(
            m.tolist() if getattr(m, "ndim", 0) else int(m)
        ).to_bytes()
        for m in meas
    ]
    ta = TaskAggregator(task, Config())

    def per_report():
        out = []
        for b in bodies:
            r = Report.from_bytes(b)
            kp = ta.upload_prepare(clock, r)
            out.append(ta.upload_decrypt_validate(r, kp))
        return out

    idxs = list(range(len(bodies)))

    def batched():
        col = decode_reports_fast(bodies)
        kps = ta.upload_prepare_columns(clock, col, idxs)
        return ta.upload_decrypt_validate_batch(col, idxs, kps[0])

    identical = per_report() == batched()
    slow_s, fast_s, ratio = _paired_ratio(per_report, batched, iters=9)
    return {
        "vdaf": inst.kind,
        "window": window,
        "stored_reports_identical": identical,
        "per_report_us_per_report": round(slow_s / window * 1e6, 2),
        "batched_us_per_report": round(fast_s / window * 1e6, 2),
        "per_report_rps": round(window / slow_s, 1),
        "batched_rps": round(window / fast_s, 1),
        "speedup": round(ratio, 2),
    }


def _ingest_batch_smoke() -> dict:
    """Batched-ingest smoke (ISSUE 11): a real loopback HTTP burst
    through the window-batched decode/decrypt stages — 12 valid
    uploads, 1 with a tampered leader ciphertext, 3 undecodable bodies
    — must answer EXACTLY 12x201 + 4x400 with the 12 committed exactly
    once (a replayed PUT stays 201 and adds no row); a direct
    pipeline feed then proves the windowing deterministically (8
    submits inside one linger -> ONE hpke_open_batch call of 8
    lanes)."""
    import dataclasses
    from concurrent.futures import ThreadPoolExecutor

    from janus_tpu import metrics as _m
    from janus_tpu.aggregator import Config
    from janus_tpu.aggregator.core import TaskAggregator
    from janus_tpu.aggregator.report_writer import ReportWriteBatcher
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.ingest import IngestPipeline

    cfg = Config(ingest_batch_linger_ms=40.0)
    eph, srv, task, params, client, clock = _upload_client_stack(
        cfg, max_handler_threads=24
    )
    try:
        reports = [client.prepare_report(1) for _ in range(13)]
        tampered = dataclasses.replace(
            reports[12],
            leader_encrypted_input_share=dataclasses.replace(
                reports[12].leader_encrypted_input_share,
                payload=bytes(
                    [reports[12].leader_encrypted_input_share.payload[0] ^ 1]
                )
                + reports[12].leader_encrypted_input_share.payload[1:],
            ),
        )
        bodies = [r.to_bytes() for r in reports[:12]]
        burst = bodies + [tampered.to_bytes()] + [b"not-a-dap-report"] * 3

        def put(body):
            http = HttpClient()
            return http.put(
                params.upload_uri(), body, {"Content-Type": "application/dap-report"}
            )[0]

        calls0, lanes0 = _hist_totals(_m.hpke_batch_size)
        with ThreadPoolExecutor(max_workers=len(burst)) as pool:
            statuses = list(pool.map(put, burst))
        http_calls, http_lanes = _hist_totals(_m.hpke_batch_size)
        http_calls -= calls0
        http_lanes -= lanes0
        replay_status = put(bodies[0])  # exactly-once: replays stay 201
        stored, _ = eph.datastore.run_tx(
            lambda tx: tx.count_client_reports_for_task(task.task_id)
        )

        # windowing proof: 8 back-to-back submits (microseconds of
        # work) against a 2 s linger — the decode worker drains them
        # into one window and returns the moment the 8th arrives, so
        # the linger costs nothing in the good case and only a >2 s
        # scheduler stall between two queue puts could split the
        # window (tier-1 pins direct_batch_calls == 1 on this)
        eph2 = EphemeralDatastore(clock=clock)
        try:
            eph2.datastore.run_tx(lambda tx: tx.put_task(task))
            ta = TaskAggregator(task, cfg)
            writer = ReportWriteBatcher(eph2.datastore, 100, 0)
            pipe = IngestPipeline(
                writer, queue_depth=16, batch_window=8, batch_linger_ms=2000.0
            )
            try:
                calls0, lanes0 = _hist_totals(_m.hpke_batch_size)
                tickets = [pipe.submit(ta, clock, b) for b in bodies[:8]]
                ok = all(t.result(timeout_s=60) for t in tickets)
                calls1, lanes1 = _hist_totals(_m.hpke_batch_size)
            finally:
                pipe.close()
                writer.close()
        finally:
            eph2.cleanup()
        batch_secs_count, _ = _hist_totals(_m.ingest_decrypt_batch_seconds)
        return {
            "accepted": statuses.count(201),
            "rejected_4xx": sum(1 for s in statuses if 400 <= s < 500),
            "statuses_other": sorted(
                {s for s in statuses if s != 201 and not 400 <= s < 500}
            ),
            "stored_reports": int(stored),
            "committed_exactly_once": int(stored) == statuses.count(201),
            "replay_still_201": replay_status == 201,
            # batching evidence over HTTP (informational: arrival
            # clustering depends on host load) and the deterministic
            # direct-feed proof (asserted by test_bench_dry_run_smoke)
            "http_batch_calls": int(http_calls),
            "http_batched_reports": int(http_lanes),
            "direct_feed_ok": bool(ok),
            "direct_batch_calls": int(calls1 - calls0),
            "direct_batch_lanes": int(lanes1 - lanes0),
            "decrypt_batch_seconds_sampled": batch_secs_count > 0,
        }
    finally:
        srv.stop()
        eph.cleanup()


def _open_loop_upload_record(
    duration_s: float = 3.0,
    capacity_rps: float = 120.0,
    rate_factor: float = 2.0,
) -> dict:
    """Open-loop (coordinated-omission-free) upload load generator
    (ISSUE 11): arrivals on a FIXED schedule at `rate_factor`x the
    configured admission capacity, each request's latency measured
    from its INTENDED send time — a stalled server accumulates
    lateness into the recorded tail instead of silently slowing the
    generator down (the classic closed-loop bench lie). The stack is
    given a token-bucket capacity (`capacity_rps`) so sustained
    overload is a deterministic condition, not a host-speed accident:
    ~half the offered load must shed 429 while admitted uploads'
    p50/p99-under-overload and the exact shed split become tracked
    BENCH numbers."""
    import threading
    import time as _time
    from concurrent.futures import ThreadPoolExecutor

    from janus_tpu import metrics as _m
    from janus_tpu.aggregator import Config
    from janus_tpu.core.http_client import HttpClient

    cfg = Config(
        ingest_batch_linger_ms=5.0,
        upload_bucket_rate=capacity_rps,
        upload_bucket_burst=max(8, int(capacity_rps / 4)),
    )
    eph, srv, task, params, client, clock = _upload_client_stack(
        cfg, max_handler_threads=32
    )
    try:
        hdrs = {"Content-Type": "application/dap-report"}
        offered_rps = capacity_rps * rate_factor
        n = min(1500, max(30, int(offered_rps * duration_s)))
        bodies = [client.prepare_report(1).to_bytes() for _ in range(n)]

        local = threading.local()

        def get_http() -> HttpClient:
            h = getattr(local, "http", None)
            if h is None:
                h = local.http = HttpClient()
            return h

        start = _time.perf_counter() + 0.2
        results = []
        lock = threading.Lock()

        def fire(k: int, body: bytes) -> None:
            intended = start + k / offered_rps
            now = _time.perf_counter()
            if intended > now:
                _time.sleep(intended - now)
            t_begin = _time.perf_counter()
            try:
                status, _body = get_http().put(params.upload_uri(), body, hdrs)
            except Exception:
                status = -1
            done = _time.perf_counter()
            with lock:
                # latency FROM INTENDED send: queueing in the generator
                # (all workers busy) and in the server both count
                results.append((status, done - intended, t_begin - intended))

        shed0 = _m.upload_shed_counter.total()
        with ThreadPoolExecutor(max_workers=48) as pool:
            for k, body in enumerate(bodies):
                pool.submit(fire, k, body)
        wall = _time.perf_counter() - start
        shed_delta = _m.upload_shed_counter.total() - shed0

        def pctl(vals, q):
            if not vals:
                return None
            vals = sorted(vals)
            return vals[min(len(vals) - 1, int(q * len(vals)))]

        lat_ok = [lat for s, lat, _ in results if s == 201]
        lat_all = [lat for s, lat, _ in results if s > 0]
        lag = [b for _, _, b in results]
        n201 = sum(1 for s, _, _ in results if s == 201)
        n429 = sum(1 for s, _, _ in results if s == 429)
        return {
            "capacity_rps_configured": capacity_rps,
            "offered_rps": round(offered_rps, 1),
            "requests": len(results),
            "duration_s": round(wall, 2),
            "accepted_201": n201,
            "shed_429": n429,
            "errors": sum(1 for s, _, _ in results if s not in (201, 429) ),
            "served_rps": round(n201 / wall, 1) if wall > 0 else None,
            "shed_accounted": shed_delta == n429,
            # the tracked overload numbers: latency measured from the
            # intended (scheduled) send instant
            "p50_ms_201": round(pctl(lat_ok, 0.50) * 1000, 1) if lat_ok else None,
            "p99_ms_201": round(pctl(lat_ok, 0.99) * 1000, 1) if lat_ok else None,
            "p50_ms_all": round(pctl(lat_all, 0.50) * 1000, 1) if lat_all else None,
            "p99_ms_all": round(pctl(lat_all, 0.99) * 1000, 1) if lat_all else None,
            # generator honesty: how late requests LEFT the generator
            # relative to their schedule (large = the generator itself
            # could not offer the load; the lateness is still charged
            # to the recorded latencies above, never hidden)
            "start_lag_p99_ms": round(pctl(lag, 0.99) * 1000, 1) if lag else None,
        }
    finally:
        srv.stop()
        eph.cleanup()


def _pipeline_smoke() -> dict:
    """Stage-pipeline overlap smoke (scripts/chaos_run.py --scenario
    pipeline --smoke): the REAL driver binary with the pipelined
    stepper (the default) steps many small jobs against a loopback
    helper whose RTT is stretched by a delay failpoint; the smoke
    asserts overlap actually happened — the device lane was busy while
    an HTTP leg was in flight (janus_step_pipeline_overlap_total > 0,
    overlap ratio > 0 recorded), stage metrics populated, SIGTERM
    drain clean, and the final collection exactly equals the admitted
    ground truth."""
    return _run_chaos_subprocess(
        ["--scenario", "pipeline", "--smoke", "--json"], timeout=300
    )


def _run_chaos_subprocess(extra_args: list, timeout: float) -> dict:
    """Run scripts/chaos_run.py with `extra_args` and return its JSON
    record. A hung/garbled/failed harness degrades to an ok:false
    record — the dry run always emits its JSON line (the BENCH rc:124
    lesson), and test_bench_dry_run_smoke reports THAT dict instead of
    an opaque traceback."""
    import pathlib
    import subprocess

    repo = pathlib.Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # single-device, like the real drivers
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join("scripts", "chaos_run.py"), *extra_args],
            cwd=repo,
            env=env,
            capture_output=True,
            text=True,
            timeout=timeout,
        )
        lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
        if not lines:
            return {
                "ok": False,
                "returncode": proc.returncode,
                "stderr_tail": proc.stderr[-1500:],
            }
        # a failed run (rc != 0) still emitted its record: return THAT —
        # the per-invariant *_ok fields beat an opaque stderr tail
        record = json.loads(lines[-1])
        if proc.returncode != 0:
            record.setdefault("returncode", proc.returncode)
            record.setdefault("stderr_tail", proc.stderr[-1500:])
        return record
    except (subprocess.TimeoutExpired, json.JSONDecodeError, OSError) as e:
        return {"ok": False, "error": f"{type(e).__name__}: {e}"[:1500]}


def _chaos_smoke() -> dict:
    """Crash-recovery chaos smoke (scripts/chaos_run.py --smoke):
    driver killed between helper ack and leader commit, helper
    transport/5xx storm through the circuit breaker, lease reacquired
    within TTL, and the final collection equal to the admitted ground
    truth exactly."""
    return _run_chaos_subprocess(["--smoke", "--json"], timeout=560)


def _watchdog_overhead(iters: int = 200_000) -> dict:
    """Measure — not assume — the disarmed dispatch-watchdog cost: ns
    per supervised call with NO ambient deadline (the production state
    for un-deadlined paths and the constant prefix for deadlined ones:
    one contextvar read + a None check) against an empty-loop baseline,
    plus the armed-path cost (worker handoff) for context. The
    acceptance bound is ≤ 1 µs/dispatch disarmed."""
    import time as _time

    from janus_tpu.aggregator.device_watchdog import DispatchWatchdog
    from janus_tpu.core.deadline import deadline_scope

    wd = DispatchWatchdog()
    fn = lambda: None  # noqa: E731

    def measure(call) -> float:
        t0 = _time.perf_counter()
        for _ in range(iters):
            call()
        return (_time.perf_counter() - t0) / iters * 1e9

    baseline_ns = measure(fn)
    disarmed_ns = measure(lambda: wd.run(fn))
    # armed: real worker handoff per call (amortized by thread reuse)
    armed_iters = 2_000
    with deadline_scope(_time.monotonic() + 3600):
        t0 = _time.perf_counter()
        for _ in range(armed_iters):
            wd.run(fn, deadline=_time.monotonic() + 60)
        armed_ns = (_time.perf_counter() - t0) / armed_iters * 1e9
    return {
        "iters": iters,
        "baseline_ns": round(baseline_ns, 1),
        "disarmed_ns_per_dispatch": round(disarmed_ns, 1),
        "disarmed_overhead_ns": round(disarmed_ns - baseline_ns, 1),
        "armed_ns_per_dispatch": round(armed_ns, 1),
    }


def _profiler_overhead_record() -> dict:
    """Measure — not assume — the continuous profiler's cost (ISSUE 13
    acceptance: ≤ 2% served-throughput regression with the sampler on):
    a serving-shaped workload (spans around numpy field work, the span
    hot path the sampler sees in production) timed in INTERLEAVED
    blocks with the sampler running at the production 19 Hz vs off
    (median per-pair ratio, GC paused — the codec-bench lesson), plus
    the sampler's own self-measured overhead ratio and a collapsed-
    format well-formedness check under a hostile thread name."""
    import threading as _threading

    import numpy as np

    from janus_tpu import profiler as _prof
    from janus_tpu.trace import span

    rng = np.random.default_rng(0xF0)
    data = rng.integers(0, 2**32 - 1, size=1 << 20).astype(np.uint64)

    def workload():
        # ~100 ms of span-wrapped numpy per block (the serving shape:
        # ms-scale work under spans, which is what the sampler walks) —
        # blocks must be long enough that the per-block sampler
        # start/stop below is sub-permille, or the A/B measures thread
        # lifecycle instead of sampling cost
        acc = data
        for _ in range(24):
            with span("bench.profiler_ab"):
                acc = (acc * np.uint64(6364136223846793005) + np.uint64(1)) % np.uint64(
                    0xFFFFFFFB
                )
        return acc

    cfg = _prof.ProfilerConfig(hz=19.0, window_secs=60.0)

    def sampled():
        p = _prof.SamplingProfiler(cfg)
        p.start()
        try:
            workload()
        finally:
            p.stop()

    # interleaved pairs with ALTERNATING order (GC paused): the signal
    # (~0.3% at 19 Hz) is far below scheduler/cache noise on a shared
    # CI host, and a fixed measurement order leaves a systematic warm/
    # cold bias on one side — alternating cancels it, the median does
    # the rest
    import gc
    import statistics
    import time as _time

    def timed(fn) -> float:
        t0 = _time.perf_counter()
        fn()
        return _time.perf_counter() - t0

    on_ts, off_ts, ratios = [], [], []
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        timed(sampled), timed(workload)  # warm first-touch pages
        for i in range(16):
            if i % 2 == 0:
                s = timed(sampled)
                f = timed(workload)
            else:
                f = timed(workload)
                s = timed(sampled)
            on_ts.append(s)
            off_ts.append(f)
            ratios.append(s / f)
    finally:
        if gc_was_enabled:
            gc.enable()
    on_s, off_s, ratio = min(on_ts), min(off_ts), statistics.median(ratios)
    overhead_pct = max(0.0, (ratio - 1.0) * 100.0)

    # self-measured overhead + hostile-name fold: a fast sampler over a
    # thread whose name carries separators/quotes must yield a
    # well-formed collapsed document (shared validator) and 0 overhead
    # reported once stopped... the ratio itself comes from the window
    p = _prof.SamplingProfiler(_prof.ProfilerConfig(hz=97.0, window_secs=30.0))
    stop = _threading.Event()
    hostile = _threading.Thread(
        target=stop.wait, name='evil;role name\n"x" 42', daemon=True
    )
    hostile.start()
    p.start()
    time.sleep(0.4)
    doc = p.profile_json()
    collapsed = p.collapsed()
    p.stop()
    stop.set()
    fold_errors = _prof.validate_collapsed(collapsed)
    return {
        "sampler_hz": cfg.hz,
        "on_block_s": round(on_s, 4),
        "off_block_s": round(off_s, 4),
        "median_pair_ratio": round(ratio, 4),
        # THE acceptance number: sampler-on vs sampler-off throughput
        # regression (gate: <= 2.0)
        "overhead_pct": round(overhead_pct, 3),
        "gate_ok": overhead_pct <= 2.0,
        "self_measured_overhead_ratio": doc["overhead_ratio"],
        "samples": doc["samples"],
        "roles_seen": sorted(doc["roles"]),
        "collapsed_well_formed": not fold_errors,
        "collapsed_errors": fold_errors[:3],
    }


def _device_hang_smoke() -> dict:
    """Deadline-aware device-path smoke (scripts/chaos_run.py
    --scenario device_hang --smoke): the real driver binary's first
    dispatch wedges forever; the watchdog abandons it inside the lease
    budget, the job steps back (reason=device_hang), the engine runs
    quarantined → canary-probed → restored observed live over
    /metrics + /statusz (incl. the stalled-thread stack dump), interim
    work lands through host fallback, and the final collection equals
    the admitted ground truth exactly."""
    return _run_chaos_subprocess(
        ["--scenario", "device_hang", "--smoke", "--json"], timeout=300
    )


def _resident_chaos_smoke() -> dict:
    """Resident-state flush-contract smoke (scripts/chaos_run.py
    --scenario resident --smoke): the real driver binary with resident
    accumulators on — LRU eviction, mid-stream quarantine sweep, and
    SIGTERM drain each flush resident state through the write-tx path,
    no flush reports outcome=lost, and both tasks' collections equal
    their admitted ground truths exactly."""
    return _run_chaos_subprocess(
        ["--scenario", "resident", "--smoke", "--json"], timeout=300
    )


def _resident_accumulate_record(inst=None, n: int = 256, k: int = 16, jobs: int = 4) -> dict:
    """Resident vs re-stage A/B on the SAME dataset (ISSUE 12): `jobs`
    job steps of `n` out-share rows spread over `k` batch buckets run
    through BOTH accumulate legs on one engine — the classic per-bucket
    path (one n-bool mask upload + one aggregate fetch per bucket per
    job) and the resident path (one [n] int32 upload per job, one fetch
    for the whole run at take time). Reports host<->device bytes per
    report on the accumulate leg from the real janus_engine_hd_bytes
    accounting, rows per dispatch from the real dispatch counter, and
    asserts the aggregate shares BIT-IDENTICAL (field elements mod p).
    The >=2x bytes/report acceptance gate reads this record."""
    import numpy as np

    from janus_tpu import metrics as _m
    from janus_tpu.aggregator.engine_cache import EngineCache
    from janus_tpu.messages import Duration, Interval, Time
    from janus_tpu.vdaf.registry import VdafInstance
    from janus_tpu.vdaf.testing import make_report_batch, random_measurements

    inst = inst or VdafInstance.count()
    eng = EngineCache(inst, bytes(range(16)))
    p = eng.p3.jf.MODULUS
    iv = Interval(Time(0), Duration(3600))
    rng = np.random.default_rng(0xAB12)

    def hd_totals() -> tuple[float, float]:
        return (
            _m.engine_hd_bytes_total.get(direction="h2d"),
            _m.engine_hd_bytes_total.get(direction="d2h"),
        )

    total_rows = n * jobs
    classic_totals: dict[int, list[int]] = {}
    classic_h2d = classic_d2h = 0.0
    resident_h2d = resident_d2h = 0.0
    classic_dispatches = resident_dispatches = 0
    out_shares = []
    lane_buckets = []
    for j in range(jobs):
        meas = random_measurements(inst, n, rng)
        args, _ = make_report_batch(inst, meas, seed=0xC0 + j)
        nonce, public, mv, proof, blind0, _, _ = args
        out0, _, _, _ = eng.leader_init(nonce, public, mv, proof, blind0)
        out_shares.append(out0)
        lane_buckets.append(rng.integers(0, k, size=n).astype(np.int32))

    # --- A: classic re-stage leg (the pre-resident shape) -------------
    d0 = _m.engine_dispatches_total.get(op="aggregate")
    h0, f0 = hd_totals()
    for out0, lane_bucket in zip(out_shares, lane_buckets):
        for j in range(k):
            share = eng.aggregate(out0, lane_bucket == j)
            tot = classic_totals.setdefault(j, [0] * len(share))
            for i, x in enumerate(share):
                tot[i] = (tot[i] + x) % p
    h1, f1 = hd_totals()
    classic_h2d, classic_d2h = h1 - h0, f1 - f0
    classic_dispatches = int(_m.engine_dispatches_total.get(op="aggregate") - d0)

    # --- B: resident leg (same rows, same buckets) --------------------
    d0 = _m.engine_dispatches_total.get(op="aggregate")
    h0, f0 = hd_totals()
    for out0, lane_bucket in zip(out_shares, lane_buckets):
        pend = eng.aggregate_pending(out0, lane_bucket, k)
        entries = [
            ((b"bench-task", b"", b"bucket-%d" % j), j, int((lane_bucket == j).sum()), iv)
            for j in range(k)
        ]
        evicted = eng.resident_merge(entries, pend)
        assert evicted == [], "bench run must not hit the byte cap"
    recs = {r["key"][2]: r["share"] for r in eng.resident_take()}
    h1, f1 = hd_totals()
    resident_h2d, resident_d2h = h1 - h0, f1 - f0
    resident_dispatches = int(_m.engine_dispatches_total.get(op="aggregate") - d0)

    identical = all(
        recs.get(b"bucket-%d" % j) == classic_totals[j] for j in range(k)
    )
    classic_bpr = (classic_h2d + classic_d2h) / total_rows
    resident_bpr = (resident_h2d + resident_d2h) / total_rows
    return {
        "n_per_job": n,
        "jobs": jobs,
        "buckets": k,
        "total_rows": total_rows,
        "classic": {
            "h2d_bytes_per_report": round(classic_h2d / total_rows, 2),
            "d2h_bytes_per_report": round(classic_d2h / total_rows, 2),
            "hd_bytes_per_report": round(classic_bpr, 2),
            "dispatches": classic_dispatches,
            "rows_per_dispatch": round(total_rows / max(1, classic_dispatches), 1),
        },
        "resident": {
            "h2d_bytes_per_report": round(resident_h2d / total_rows, 2),
            "d2h_bytes_per_report": round(resident_d2h / total_rows, 2),
            "hd_bytes_per_report": round(resident_bpr, 2),
            "dispatches": resident_dispatches,
            "rows_per_dispatch": round(total_rows / max(1, resident_dispatches), 1),
        },
        # THE acceptance number: host<->device bytes/report on the
        # accumulate leg, classic / resident (gate: >= 2.0)
        "hd_bytes_per_report_ratio": round(classic_bpr / max(1e-9, resident_bpr), 2),
        "aggregates_identical": identical,
    }


_MESH_SMOKE_MARK = "JANUS_MESH_SMOKE:"

_MESH_SMOKE_CHILD = r'''
import json, time
import numpy as np
import jax; jax.config.update("jax_platforms", "cpu")
from janus_tpu.aggregator import engine_cache as ec
from janus_tpu.aggregator.engine_cache import EngineCache, mesh_status
from janus_tpu.messages import Duration, Interval, Time
from janus_tpu.vdaf.registry import VdafInstance
from janus_tpu.vdaf.testing import make_report_batch, random_measurements

inst = VdafInstance.sum_vec(length=4, bits=2)
n = 64
rng = np.random.default_rng(0xE5)
args, _ = make_report_batch(inst, random_measurements(inst, n, rng), seed=0xE5)
nonce, parts, meas, proof, blind0, hseed, blind1 = args
eng = EngineCache(inst, bytes(range(16)))
ok = np.ones(n, dtype=bool); ok[::9] = False

def round_once():
    out0, _s, ver0, part0 = eng.leader_init(nonce, parts, meas, proof, blind0)
    part0_l = part0 if part0 is not None else np.zeros((n, 2), dtype=np.uint64)
    out1, _m, _p = eng.helper_init(nonce, parts, hseed, blind1, ver0, part0_l, ok)
    return out0, eng.aggregate(out0, ok), eng.aggregate(out1, ok)

round_once()  # compile round, untimed
t0 = time.monotonic()
out0, agg0, agg1 = round_once()
dt = time.monotonic() - t0
deltas = eng.aggregate_pending(out0, (np.arange(n) % 2).astype(np.int32), 2)
iv = Interval(Time(0), Duration(3600))
eng.resident_merge([(("s", 0), 0, n // 2, iv), (("s", 1), 1, n // 2, iv)], deltas)
res = sorted((str(r["key"]), [str(x) for x in r["share"]]) for r in eng.resident_take())
q = mesh_status()["queue"]
print("JANUS_MESH_SMOKE:" + json.dumps({
    "devices": len(jax.devices()), "dp": eng.dp, "sp": eng.sp,
    "agg0": [str(x) for x in agg0], "agg1": [str(x) for x in agg1],
    "resident": res, "rps": round(n / dt, 2) if dt > 0 else 0.0,
    "queue_submitted": q["submitted"], "queue_errors": q["errors"],
    "lane_alive": q["lane_alive"],
    "dispatch_lock_removed": not hasattr(ec, "_MESH_DISPATCH_LOCK"),
}), flush=True)
'''


def _mesh_serving_smoke() -> dict:
    """Mesh serving smoke (ISSUE 16): ONE subprocess with 4 forced
    virtual CPU devices drives the SERVING EngineCache path — leader +
    helper init, masked aggregate with rejected lanes, sharded
    resident accumulate + flush — over a (dp, sp) mesh behind the
    single-controller dispatch queue; the parent recomputes the SAME
    batch on its single-device engine and asserts every aggregate and
    resident share BIT-IDENTICAL. Gates: bit_identical, mesh active
    (dp*sp > 1), queue submitted > 0 with zero errors, the old
    process-global dispatch lock gone, rps > 0."""
    import subprocess

    import numpy as np

    from janus_tpu.aggregator.engine_cache import EngineCache
    from janus_tpu.messages import Duration, Interval, Time
    from janus_tpu.vdaf.registry import VdafInstance
    from janus_tpu.vdaf.testing import make_report_batch, random_measurements

    rec: dict = {"ok": False}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = f"{flags} --xla_force_host_platform_device_count=4".strip()
    env.pop("JANUS_MESH_DP", None)
    env.pop("JANUS_MESH_SP", None)
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _MESH_SMOKE_CHILD],
            env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True,
            text=True,
            timeout=420,
        )
    except subprocess.TimeoutExpired:
        rec["error"] = "mesh smoke child timeout"
        return rec
    rec["rc"] = proc.returncode
    child = None
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith(_MESH_SMOKE_MARK):
            child = json.loads(line[len(_MESH_SMOKE_MARK):])
            break
    if child is None:
        rec["error"] = "no mesh smoke record in child stdout"
        rec["stderr_tail"] = proc.stderr[-1500:]
        return rec
    rec.update(child)

    # single-device reference through the SAME serving entry points
    inst = VdafInstance.sum_vec(length=4, bits=2)
    n = 64
    rng = np.random.default_rng(0xE5)
    args, _ = make_report_batch(inst, random_measurements(inst, n, rng), seed=0xE5)
    nonce, parts, meas, proof, blind0, hseed, blind1 = args
    ref = EngineCache(inst, bytes(range(16)))
    ok = np.ones(n, dtype=bool)
    ok[::9] = False
    out0, _s, ver0, part0 = ref.leader_init(nonce, parts, meas, proof, blind0)
    part0_l = part0 if part0 is not None else np.zeros((n, 2), dtype=np.uint64)
    out1, _m, _p = ref.helper_init(nonce, parts, hseed, blind1, ver0, part0_l, ok)
    agg0 = [str(x) for x in ref.aggregate(out0, ok)]
    agg1 = [str(x) for x in ref.aggregate(out1, ok)]
    deltas = ref.aggregate_pending(out0, (np.arange(n) % 2).astype(np.int32), 2)
    iv = Interval(Time(0), Duration(3600))
    ref.resident_merge([(("s", 0), 0, n // 2, iv), (("s", 1), 1, n // 2, iv)], deltas)
    res = sorted(
        (str(r["key"]), [str(x) for x in r["share"]]) for r in ref.resident_take()
    )
    # the child's record crossed JSON, so its resident tuples are lists
    rec["bit_identical"] = (
        rec.get("agg0") == agg0
        and rec.get("agg1") == agg1
        and rec.get("resident") == [list(t) for t in res]
    )
    rec["ok"] = bool(
        rec["bit_identical"]
        and rec.get("rc") == 0
        and rec.get("dp", 1) * rec.get("sp", 1) > 1
        and rec.get("queue_submitted", 0) > 0
        and rec.get("queue_errors", 1) == 0
        and rec.get("dispatch_lock_removed")
        and rec.get("rps", 0) > 0
    )
    return rec


def _cold_start_record() -> dict:
    """Cold-start A/B (scripts/chaos_run.py --scenario cold_start
    --smoke): interleaved cold-cache vs warm-cache boots of the REAL
    driver binary, restart-to-first-dispatch measured via /debug/boot
    (phase sums proven exact in the boot-timeline tests). Both boots
    replay the same shape manifest through the AOT prewarm before
    /readyz flips ready; the warm boot loads serialized executables
    (no re-trace) + the persistent XLA cache. Gates: warm under 10 s,
    warm >= 1.5x cold, AOT saves observed cold / loads observed warm."""
    return _run_chaos_subprocess(["--scenario", "cold_start", "--json", "--smoke"], timeout=420)


def _fleet_smoke() -> dict:
    """In-process fleet smoke (ISSUE 15): TWO driver replicas — each
    with its own fleet identity and shard slice — over ONE datastore.
    Replica A claims its shard's jobs on a 2 s lease and DIES holding
    them (never steps, never releases: the SIGKILL analog), replica B
    finishes its own shard immediately and STEALS A's jobs once their
    leases expire past the steal delay. Gates: every job finishes, the
    collection equals the admitted ground truth exactly, the
    lease-conflict counter stays at zero (nothing double-stepped), B's
    claims were batched (jobs per claim tx > 1), and the dead
    replica's shard drained through the steal fallback."""
    import dataclasses
    import secrets as _secrets
    import tempfile
    import threading

    from janus_tpu import metrics as _m
    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.aggregation_job_driver import AggregationJobDriver
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.binary_utils import warmup_engines
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.config import FleetConfig
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, Datastore, job_shard_key
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    rec: dict = {}
    tmp = tempfile.mkdtemp(prefix="janus-bench-fleet-")
    key = _secrets.token_bytes(16)
    clock = RealClock()
    leader_ds = Datastore(os.path.join(tmp, "leader.sqlite"), Crypter([key]), clock)
    helper_ds = Datastore(os.path.join(tmp, "helper.sqlite"), Crypter([key]), clock)
    leader_srv = helper_srv = None
    job_size = 2
    try:
        helper_srv = DapServer(DapHttpApp(Aggregator(helper_ds, clock, Config()))).start()
        leader_srv = DapServer(
            DapHttpApp(Aggregator(leader_ds, clock, Config(collection_retry_after_s=1)))
        ).start()
        vdaf = VdafInstance.count()
        collector_kp = generate_hpke_config_and_private_key(config_id=206)
        leader_task = (
            TaskBuilder(QueryTypeConfig.time_interval(), vdaf, Role.LEADER)
            .with_(
                leader_aggregator_endpoint=leader_srv.url,
                helper_aggregator_endpoint=helper_srv.url,
                collector_hpke_config=collector_kp.config,
                aggregator_auth_token=AuthenticationToken.random_bearer(),
                collector_auth_token=AuthenticationToken.random_bearer(),
                min_batch_size=1,
            )
            .build()
        )
        helper_task = dataclasses.replace(
            leader_task,
            role=Role.HELPER,
            hpke_keys=(generate_hpke_config_and_private_key(config_id=6),),
        )
        leader_ds.run_tx(lambda tx: tx.put_task(leader_task), "provision")
        helper_ds.run_tx(lambda tx: tx.put_task(helper_task), "provision")
        warmup_engines(leader_ds, batch=job_size)

        http = HttpClient()
        client = Client.with_fetched_configs(
            ClientParameters(
                leader_task.task_id,
                leader_srv.url,
                helper_srv.url,
                leader_task.time_precision,
            ),
            vdaf,
            http,
            clock=clock,
        )
        creator = AggregationJobCreator(
            leader_ds,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=job_size
            ),
        )
        measurements = []

        def upload(n):
            wave = [(i % 3 != 0) * 1 for i in range(n)]
            for m in wave:
                client.upload(m)
            measurements.extend(wave)
            creator.run_once()

        def shard_census():
            jobs = leader_ds.run_tx(
                lambda tx: tx.get_aggregation_jobs_for_task(leader_task.task_id),
                "fleet_smoke_census",
            )
            by_shard = {0: 0, 1: 0}
            for j in jobs:
                by_shard[
                    job_shard_key(leader_task.task_id.data, j.job_id.data) % 2
                ] += 1
            return len(jobs), by_shard

        upload(16)
        # both shards must be populated for the steal proof to mean
        # anything; random job ids make an empty shard a ~0.8% event —
        # top up deterministically instead of flaking
        for _ in range(6):
            n_jobs, by_shard = shard_census()
            if by_shard[0] and by_shard[1]:
                break
            upload(job_size)
        rec["jobs"] = n_jobs
        rec["jobs_by_shard"] = by_shard
        rec["both_shards_populated"] = bool(by_shard[0] and by_shard[1])

        fleet_a = FleetConfig(
            replica_id="bench-fleet-a", shard_count=2, shard_index=0, steal_after_secs=1
        )
        fleet_b = FleetConfig(
            replica_id="bench-fleet-b", shard_count=2, shard_index=1, steal_after_secs=1
        )
        conflicts0 = _m.lease_conflicts_total.total()
        steals0 = _m.lease_steals_total.total()
        tx0 = _m.lease_acquire_tx_total.get(kind="aggregation", outcome="claimed")
        jobs0 = _m.lease_acquired_jobs_total.get(kind="aggregation")

        # replica A: claim on a 2 s lease, then die holding the leases
        dead = AggregationJobDriver(leader_ds, http)
        held = dead.acquirer(2, fleet=fleet_a)(16)
        rec["held_by_dead_replica"] = len(held)
        del held  # nothing ever steps or releases these — SIGKILL analog

        # replica B: steps its shard now, steals A's after expiry+delay
        live = AggregationJobDriver(leader_ds, http)
        jd = JobDriver(
            JobDriverConfig(job_discovery_interval_s=0.05, max_concurrent_job_workers=4),
            live.acquirer(60, fleet=fleet_b),
            live.stepper,
        )

        def finished():
            counts = leader_ds.run_tx(
                lambda tx: tx.count_jobs_by_state(), "fleet_smoke_monitor"
            )
            return sum(
                n
                for (typ, state), n in counts.items()
                if typ == "aggregation" and state == "finished"
            )

        deadline = time.monotonic() + 90
        while time.monotonic() < deadline and finished() < rec["jobs"]:
            jd.run_once()
            time.sleep(0.05)
        rec["jobs_finished"] = finished()
        rec["survivor_finished_all"] = rec["jobs_finished"] >= rec["jobs"]
        rec["lease_conflicts_delta"] = _m.lease_conflicts_total.total() - conflicts0
        rec["zero_conflicts"] = rec["lease_conflicts_delta"] == 0
        rec["steals_delta"] = _m.lease_steals_total.total() - steals0
        rec["dead_shard_stolen"] = rec["steals_delta"] >= 1
        claim_txs = _m.lease_acquire_tx_total.get(
            kind="aggregation", outcome="claimed"
        ) - tx0
        claimed = _m.lease_acquired_jobs_total.get(kind="aggregation") - jobs0
        rec["claim_txs"] = claim_txs
        rec["jobs_claimed"] = claimed
        rec["jobs_per_claim_tx"] = round(claimed / max(1.0, claim_txs), 2)
        rec["batched_claims"] = claim_txs > 0 and rec["jobs_per_claim_tx"] > 1.0

        # collect and compare against ground truth exactly
        cdrv = CollectionJobDriver(leader_ds, HttpClient())
        stop_collect = threading.Event()

        def collect_loop():
            cjd = JobDriver(
                JobDriverConfig(job_discovery_interval_s=0.2),
                cdrv.acquirer(60),
                cdrv.stepper,
            )
            while not stop_collect.is_set():
                cjd.run_once()
                stop_collect.wait(0.2)

        ct = threading.Thread(target=collect_loop, daemon=True)
        ct.start()
        try:
            collector = Collector(
                CollectorParameters(
                    leader_task.task_id,
                    leader_srv.url,
                    leader_task.collector_auth_token,
                    collector_kp,
                ),
                vdaf,
                HttpClient(),
            )
            tp = leader_task.time_precision
            start = clock.now().to_batch_interval_start(tp)
            query = Query.time_interval(
                Interval(Time(start.seconds - tp.seconds), Duration(3 * tp.seconds))
            )
            collected = collector.collect(query, timeout_s=90.0)
            rec["admitted"] = len(measurements)
            rec["collected_count"] = collected.report_count
            rec["collected_sum"] = collected.aggregate_result
            rec["exactly_once"] = (
                collected.report_count == len(measurements)
                and collected.aggregate_result == sum(measurements)
            )
        finally:
            stop_collect.set()
            ct.join(timeout=10)
        return rec
    finally:
        for srv in (leader_srv, helper_srv):
            if srv is not None:
                srv.stop()
        leader_ds.close()
        helper_ds.close()


def _peer_outage_smoke() -> dict:
    """Peer-outage survival smoke (scripts/chaos_run.py --scenario
    peer_outage --smoke): the real aggregation + collection driver
    binaries reach the helper only through a netsim fault proxy; a
    blackhole past the breaker-open threshold keeps uploads at 201
    while BOTH binaries park (claim transactions frozen,
    janus_peer_parked=1, zero lease conflicts), a cheap half-open
    probe resumes them when the wire heals, slow-drip and mid-body
    truncation lanes recover without wedging a worker, and the
    collections equal the admitted ground truth exactly."""
    return _run_chaos_subprocess(
        ["--scenario", "peer_outage", "--smoke", "--json"], timeout=480
    )


def _db_outage_smoke() -> dict:
    """Datastore-outage survival smoke (scripts/chaos_run.py
    --scenario db_outage --smoke): uploads keep acking 201 through a
    full datastore outage (durable spill journal, fsync-on-ack),
    /readyz flips 503 -> 200 across recovery, the journal drains to
    empty, and the final collection equals every 201-acked report
    exactly once. Healthy-path proof rides along: the armed-but-idle
    journal performed zero fsyncs."""
    return _run_chaos_subprocess(
        ["--scenario", "db_outage", "--smoke", "--json"], timeout=300
    )


def _soak_smoke() -> dict:
    """Endurance-soak smoke (scripts/chaos_run.py --scenario soak
    --smoke): sustained open-loop load with per-epoch task churn and GC
    really deleting expired rows, every epoch collected EXACTLY while
    churn continues, judged by the flight recorder — zero-slope
    verdicts on rss/datastore-rows from the clean driver with recorder
    self-overhead <= 1%, and the injected synthetic leak on the second
    driver flipping janus_flight_leak_active and firing the
    resource_trend SLO alert through the window_scale-shrunk ladder."""
    return _run_chaos_subprocess(
        ["--scenario", "soak", "--smoke", "--json"], timeout=560
    )


def _flight_rider() -> dict:
    """ISSUE 18: the measured run's flight-recorder view — top trend
    slopes, leak verdicts, and the ring's on-disk bytes/hour — from the
    recorder sampling THIS process since bench start."""
    from janus_tpu import flight_recorder as _fr

    fr = _fr.get_flight_recorder()
    if fr is None:
        return {"enabled": False}
    analysis = fr.analyze()
    st = fr.status()
    series = analysis.get("series", {})
    top = sorted(
        (
            (n, d)
            for n, d in series.items()
            if isinstance(d.get("slope_per_s"), (int, float))
        ),
        key=lambda kv: -abs(kv[1]["slope_per_s"]),
    )[:5]
    covered = max(
        (d.get("covered_s") or 0.0 for d in series.values()), default=0.0
    )
    ring = st.get("ring") or {}
    return {
        "enabled": True,
        "snapshots": st.get("snapshots"),
        "overhead_ratio": st.get("overhead_ratio"),
        "top_slopes": [
            {
                "series": n,
                "slope_per_s": d["slope_per_s"],
                "verdict": d.get("verdict"),
            }
            for n, d in top
        ],
        "leak_verdicts": {n: d.get("verdict") for n, d in series.items()},
        "leaking": analysis.get("leaking", []),
        "ring_bytes": ring.get("bytes"),
        "ring_bytes_per_hour": (
            round(ring.get("bytes", 0) * 3600.0 / covered, 1) if covered else None
        ),
    }


def _feasibility_record(inst):
    """The HBM model's view of a config: (describe dict, raw device
    budget, stream plan). Shared by --dry-run and the measured run's
    JSON rider so the two can never report different feasibility
    numbers for the same config."""
    from janus_tpu.vdaf import engine
    from janus_tpu.vdaf.feasibility import describe, device_memory_budget
    from janus_tpu.vdaf.registry import circuit_for

    circ = circuit_for(inst)
    plan = engine.stream_plan(engine.batched_circuit(circ))
    budget = device_memory_budget()
    desc = describe(
        circ,
        tile_elems=plan.group if plan is not None else None,
        draft=inst.xof_mode != "fast",
        budget_bytes=budget,
    )
    return desc, budget, plan


def run_dry(args, ap) -> None:
    """--dry-run: no accelerator required. Prints the HBM feasibility
    model's view of the config (modeled bytes/row, largest safe bucket,
    stream-plan tile geometry), smoke-tests the EngineCache
    bucketing/OOM-fallback path on a toy circuit, smoke-tests the
    admission-controlled ingest pipeline's 429-shed path over loopback
    HTTP, measures the span() tracing overhead, drives the full
    observability surface (live /metrics scrape validation, /statusz,
    profile capture + 409 guard, scrape_check), measures the disarmed
    failpoint hot-path cost, and runs the crash-recovery chaos smoke
    (driver SIGKILL mid-step + helper storms -> exactly-once
    collection; scripts/chaos_run.py), as one JSON line."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    inst = _make_inst(args, ap)
    desc, budget, plan = _feasibility_record(inst)
    # order matters: the OOM smoke's real engine dispatches feed
    # janus_engine_dispatch_seconds through the span->metric bridge,
    # which the observability smoke then asserts non-zero over HTTP
    oom_smoke = _oom_fallback_smoke()
    ingest_smoke = _ingest_shed_smoke()
    print(
        json.dumps(
            {
                "metric": "dry_run",
                "config": inst.to_dict(),
                "stream_plan": (
                    {
                        "tile_elems": plan.group,
                        "gcalls": plan.gcalls,
                        "n_steps": plan.n_steps,
                    }
                    if plan is not None
                    else None
                ),
                "feasibility": desc,
                "device_budget_bytes": budget,
                "oom_fallback_smoke": oom_smoke,
                "ingest_smoke": ingest_smoke,
                "tracing_overhead": _tracing_overhead(),
                "observability_smoke": _observability_smoke(),
                "failpoint_overhead": _failpoint_overhead(),
                "watchdog_overhead": _watchdog_overhead(),
                # ISSUE 13: the continuous profiler's measured cost
                # (sampler on/off A/B, <= 2% gate) + hostile-name fold
                "profiler_overhead": _profiler_overhead_record(),
                "chaos_smoke": _chaos_smoke(),
                "db_outage_smoke": _db_outage_smoke(),
                # ISSUE 19: the other aggregator behind a hostile wire
                # (netsim fault proxy) — peer-outage parking, half-open
                # probe recovery, slow-drip/truncation survival
                "peer_outage_smoke": _peer_outage_smoke(),
                "device_hang_smoke": _device_hang_smoke(),
                # ISSUE 14: cold-cache vs warm-cache real-binary boots —
                # the warm number (restart-to-first-dispatch) is gated
                # under 10 s and must beat cold by the smoke ratio
                "cold_start": _cold_start_record(),
                # ISSUE 12: resident vs re-stage accumulate A/B
                # (bit-identical shares asserted; the >=2x bytes/report
                # gate reads hd_bytes_per_report_ratio) + the live
                # flush-contract proof against the real driver binary
                "resident_accumulate": _resident_accumulate_record(inst),
                "resident_smoke": _resident_chaos_smoke(),
                # ISSUE 9: columnar wire codec vs the per-report loop
                # (bit-identical bytes asserted) + the stage-pipeline
                # overlap proof against the REAL driver binary
                "step_pipeline": {"codec": _codec_speed_record(inst)},
                "pipeline_smoke": _pipeline_smoke(),
                # ISSUE 11: batched ingest crypto/decode — server-side
                # speed vs the per-report oracle (bit-identical stored
                # reports asserted), a real loopback burst through the
                # batched path, and the open-loop upload-overload
                # p50/p99 + shed split
                "upload_batch_speed": _upload_batch_speed_record(inst, window=256),
                "ingest_batch_smoke": _ingest_batch_smoke(),
                "open_loop_upload": _open_loop_upload_record(),
                # ISSUE 15: two in-process fleet replicas over one
                # store — one dies holding its batched claims, the
                # survivor steals the dead shard after the delay and
                # the collection stays exact (the full fleet_scaling
                # record with REAL replica binaries rides measured
                # BENCH runs and chaos_run.py --scenario fleet)
                "fleet_smoke": _fleet_smoke(),
                # ISSUE 16: mesh serving smoke — 4 forced virtual
                # devices drive the serving EngineCache path through
                # the single-controller dispatch queue; aggregates and
                # resident shares bit-identical to the single-device
                # reference computed in this process
                "mesh_serving_smoke": _mesh_serving_smoke(),
                # ISSUE 17: block-sparse scatter-merge — sparse vs the
                # dense expanded oracle, bit-identical on both the
                # classic and resident paths, scatter ledger rows proven
                "sparse_scatter": _sparse_scatter_smoke(),
                # ISSUE 18: endurance soak under churn + GC, judged by
                # flight-recorder trend verdicts (zero-slope clean
                # driver, injected leak fires the trend alert, recorder
                # self-overhead <= 1%)
                "soak_smoke": _soak_smoke(),
                # ISSUE 20: report-flow conservation ledger — balanced
                # books through the real admission path, then an
                # injected silent loss (ledger.drop_report) detected as
                # a +1 ingest imbalance on the next evaluation, breach
                # + conservation SLO firing on the same tick
                "ledger_smoke": _ledger_smoke(),
            }
        )
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    # Default is the flagship config (BASELINE.json configs[2]):
    # SumVec(len=1000, bits=16) two-party prepare+accumulate.
    ap.add_argument(
        "--config",
        default="sumvec",
        choices=["count", "sum", "sumvec", "histogram", "fixedpoint", "sparse", "poplar1"],
    )
    ap.add_argument("--batch", type=int, default=0, help="0 = auto per backend")
    ap.add_argument(
        "--length",
        type=int,
        default=0,
        help="override the vector length for sumvec/histogram/fixedpoint "
        "(0 = the BASELINE.json config)",
    )
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument(
        "--xof-mode",
        default="fast",
        choices=["fast", "draft"],
        help="fast = the TPU counter-mode framing; draft "
        "= the VDAF-07 spec framing (sequential sponge + rejection "
        "sampling, device engine via vdaf.draft_jax)",
    )
    ap.add_argument(
        "--mode",
        default="device",
        choices=["device", "served"],
        help="device = fused two-party step only; served = also drive "
        "reports through the real HTTP serving path (HPKE + decode + "
        "SQLite + engine) and report both numbers",
    )
    ap.add_argument(
        "--reports", type=int, default=256, help="report count for --mode served"
    )
    ap.add_argument("--host-reports", type=int, default=2, help="reports for the host baseline")
    ap.add_argument(
        "--dry-run",
        action="store_true",
        help="no accelerator: print the HBM feasibility model for the "
        "config (modeled row bytes, largest safe bucket, stream tile) "
        "and smoke-test the EngineCache OOM retry/host-fallback path "
        "on CPU, then exit",
    )
    args = ap.parse_args()

    if args.dry_run:
        if args.config == "poplar1":
            ap.error("--dry-run models Prio3 prepare; poplar1 has no FLP circuit")
        run_dry(args, ap)
        return

    import jax
    import numpy as np

    from janus_tpu.binary_utils import enable_compile_cache

    enable_compile_cache()
    # a measured run reports chip numbers only: without a TPU it fails
    # (--dry-run is the CPU path)
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(
            f"bench.py: a measured run needs a TPU; JAX found {backend!r} "
            "(--dry-run runs on the CPU)"
        )

    # ISSUE 18: sample this process for the whole measured run so the
    # BENCH json carries the flight rider (top trend slopes, leak
    # verdicts, ring bytes/hour) — never let the recorder kill the run
    try:
        import tempfile as _tempfile

        from janus_tpu import flight_recorder as _fr_mod

        _fr_mod.install_flight_recorder(
            _fr_mod.FlightRecorderConfig(
                interval_s=1.0,
                window_s=1800.0,
                dir=os.path.join(
                    _tempfile.mkdtemp(prefix="janus-bench-flight-"), "ring"
                ),
            )
        )
    except Exception:
        pass

    from janus_tpu.parallel.api import two_party_step
    from janus_tpu.vdaf.registry import VdafInstance, prio3_host
    from janus_tpu.vdaf.testing import make_report_batch, random_measurements

    if args.config == "poplar1":
        if args.mode != "device" or args.length or args.xof_mode != "fast":
            ap.error(
                "--config poplar1 supports only --mode device with the "
                "fixed Poplar1<16> config (no --length/--xof-mode)"
            )
        run_poplar1(args)
        return

    # BASELINE.json measurement configs
    inst = _make_inst(args, ap)
    batch = args.batch or {
        "count": 8192,
        "sum": 16384,
        "sumvec": 2048,
        "histogram": 1024,
        "fixedpoint": 1024,
        "sparse": 1024,
    }[args.config]

    rng = np.random.default_rng(0xBE7C)
    verify_key = bytes(range(16))

    def measure_device(inst, batch: int, iters: int):
        """Stage + compile + time the two-party step. A device OOM is
        an error like any other. Returns (device_rps, batch, compile_s)."""
        # stage in prove-sized sub-batches for long vectors (the prove
        # graph peaks at [chunk, arity, n2]; prepare has no such tensor).
        # Sparse configs stage at the COMPACT width, not the logical one.
        eff_len = (
            inst.max_blocks * inst.block_size
            if inst.kind == "sparse_sumvec"
            else getattr(inst, "length", 0)
        )
        shard_chunk = 8 if eff_len * max(inst.bits, 1) > (1 << 18) else 0
        meas = random_measurements(inst, batch, rng)
        t0 = time.time()
        step_args, _ = make_report_batch(inst, meas, seed=1, shard_chunk=shard_chunk)
        print(
            f"[bench] backend={backend} batch={batch} shard: {time.time()-t0:.1f}s",
            file=sys.stderr,
            flush=True,
        )
        # stage the report columns DEVICE-RESIDENT before timing: the
        # metric is per-chip step throughput (compute + HBM)
        step_args = jax.device_put(step_args)
        jax.block_until_ready(step_args)
        step = jax.jit(two_party_step(inst, verify_key))
        t0 = time.time()
        out = step(*step_args)
        # int() forces a value fetch = actual completion
        assert int(out[2]) == batch, f"reports rejected: {int(out[2])}/{batch}"
        compile_s = time.time() - t0
        print(
            f"[bench] two_party_step compile+first: {compile_s:.1f}s",
            file=sys.stderr,
            flush=True,
        )

        t0 = time.time()
        for _ in range(iters):
            out = step(*step_args)
            assert int(out[2]) == batch
        elapsed = time.time() - t0
        return batch * iters / elapsed, batch, compile_s

    device_rps, batch, compile_s = measure_device(inst, batch, args.iters)

    # the literal north-star config (BASELINE.json configs[2]:
    # SumVec len=100k) rides along on the default driver run
    north_star = None
    if (
        args.config == "sumvec"
        and not args.length
        and args.mode == "device"
        and args.xof_mode == "fast"
    ):
        # (fast mode only: draft-mode len=100k runs on device since r5
        # but at ~1.3-5 r/s with ~50 s steps — measured separately,
        # scripts/measure_draft_sponge.py --full-prepare)
        import dataclasses

        ns_inst = dataclasses.replace(inst, length=100_000)
        ns_rps, ns_batch, ns_compile = measure_device(ns_inst, 64, max(2, args.iters // 2))
        north_star = {
            "metric": "prio3_sumvec_len100k_two_party_prepare_accumulate",
            "value": round(ns_rps, 2),
            "unit": "report_shares_per_sec_per_chip",
            "batch": ns_batch,
            "compile_s": round(ns_compile, 1),
        }

    def measure_sparse(sp_batch: int, sp_iters: int) -> dict:
        """The block-sparse north-star (ISSUE 17): two-party prepare at
        the compact width PLUS the gather/scatter-add of every verified
        report's blocks into one dense logical len-1M resident
        accumulator — the full serving device path, timed end to end.
        The resident HBM figure is the one dense logical row the
        accumulator owns regardless of report count."""
        from janus_tpu.aggregator.engine_cache import EngineCache
        from janus_tpu.vdaf.registry import circuit_for
        from janus_tpu.vdaf.testing import sparse_compact_batch
        from janus_tpu.vdaf.wire import flat_scatter_indices

        sp_inst = (
            inst
            if inst.kind == "sparse_sumvec"
            else VdafInstance.sparse_sumvec(
                bits=16, length=1_000_000, block_size=64, max_blocks=16
            )
        )
        circ = circuit_for(sp_inst)
        sp_meas = random_measurements(sp_inst, sp_batch, rng)
        t0 = time.time()
        (nonce, public, mv, proof, blind0, seeds, blind1), _ = make_report_batch(
            sp_inst, sp_meas, seed=2
        )
        _, block_idx = sparse_compact_batch(sp_inst, sp_meas)
        flat_idx = flat_scatter_indices(block_idx, circ)
        print(
            f"[bench] sparse shard: {time.time()-t0:.1f}s",
            file=sys.stderr,
            flush=True,
        )
        eng = EngineCache(sp_inst, verify_key)
        ok = np.ones(sp_batch, dtype=bool)

        def step():
            out0, _, ver0, part0 = eng.leader_init(nonce, public, mv, proof, blind0)
            _, accept, _ = eng.helper_init(
                nonce, public, seeds, blind1, ver0, part0, ok
            )
            assert bool(accept.all()), "sparse bench reports rejected"
            return eng.aggregate_sparse(out0, accept, flat_idx)

        t0 = time.time()
        step()  # compile + first dispatch
        compile_s = time.time() - t0
        print(
            f"[bench] sparse step compile+first: {compile_s:.1f}s",
            file=sys.stderr,
            flush=True,
        )
        t0 = time.time()
        for _ in range(sp_iters):
            step()
        rps = sp_batch * sp_iters / (time.time() - t0)
        return {
            "metric": "prio3_sparse_sumvec_len1m_two_party_prepare_scatter",
            "value": round(rps, 2),
            "unit": "report_shares_per_sec_per_chip",
            "batch": sp_batch,
            "iters": sp_iters,
            "compile_s": round(compile_s, 1),
            "logical_length": circ.logical_length,
            "block_size": circ.block_size,
            "max_blocks": circ.max_blocks,
            "resident_hbm_bytes": circ.logical_length * eng.p3.jf.LIMBS * 8,
            "scatter_rows": eng._scatter_rows,
            "block_occupancy": eng._sparse_last_occupancy,
            "mesh_fallback_reason": eng.mesh_fallback_reason,
        }

    # the block-sparse north-star rides the default driver run (like
    # north_star_len100k) and IS the main measurement for --config sparse
    sparse_northstar = None
    if args.config == "sparse" or (
        args.config == "sumvec"
        and not args.length
        and args.mode == "device"
        and args.xof_mode == "fast"
    ):
        try:
            sparse_northstar = measure_sparse(
                batch if args.config == "sparse" else 1024,
                args.iters if args.config == "sparse" else max(2, args.iters // 2),
            )
        except Exception as e:  # never lose the main record to the rider
            sparse_northstar = {"error": str(e)[:300]}

    served = None
    if args.mode == "served":
        served = run_served(inst, args.reports, min(batch, 512))

    # host (CPU oracle) baseline, extrapolated per report. For long
    # vectors the oracle is too slow to run at full length; measure at
    # a capped length and scale LINEARLY in the vector length —
    # conservative, since the FLP cost is superlinear (NTT +
    # sqrt-chunked gadget), so linear scaling overstates the host and
    # understates vs_baseline.
    host_len_cap = 2000
    host_inst = inst
    host_scale = 1.0
    if inst.length > host_len_cap and inst.kind in ("sumvec", "histogram", "fixedpoint", "countvec"):
        import dataclasses

        host_inst = dataclasses.replace(inst, length=host_len_cap)
        host_scale = inst.length / host_len_cap
    host = prio3_host(host_inst)
    host_meas = random_measurements(host_inst, args.host_reports, rng)
    t0 = time.time()
    for i in range(args.host_reports):
        mi = host_meas[i]
        if isinstance(mi, list):  # sparse pair-measurement, pass as-is
            m = mi
        else:
            m = mi.tolist() if getattr(mi, "ndim", 0) else int(mi)
        nonce = bytes(16)
        public, (ls, hs) = host.shard(m, nonce)
        st0, ps0 = host.prepare_init(verify_key, 0, nonce, public, ls)
        st1, ps1 = host.prepare_init(verify_key, 1, nonce, public, hs)
        prep = host.prepare_shares_to_prep([ps0, ps1])
        host.prepare_next(st0, prep)
        host.prepare_next(st1, prep)
    host_s_per_report = (time.time() - t0) * host_scale / args.host_reports
    # the host loop above includes shard(); prepare is ~2/3 of it — keep
    # the conservative (higher) host number by not discounting
    host_rps = 1.0 / host_s_per_report if host_s_per_report > 0 else float("inf")

    # achieved bucket + peak HBM per config (ISSUE r6): the feasibility
    # model's view of this circuit plus the device's own high-water
    # mark, so every record says whether the run was
    # memory-bounded and what bucket the serving engine would pick.
    hbm = {}
    try:
        hbm["feasibility"], _, _ = _feasibility_record(inst)
        stats = jax.local_devices()[0].memory_stats() or {}
        if stats.get("peak_bytes_in_use"):
            hbm["peak_hbm_bytes"] = int(stats["peak_bytes_in_use"])
    except Exception:  # the record must never die to the rider
        pass
    riders = {}
    try:
        # the span() hot path claims to be near-free; measure it in the
        # same record the throughput numbers live in
        riders["tracing_overhead"] = _tracing_overhead()
    except Exception:
        pass
    try:
        # ISSUE 9: measured step_pipeline record — codec speed on this
        # config's circuit, plus the overlap numbers from the served
        # phase when it ran (the dry-run form gets them from
        # pipeline_smoke against the real driver binary)
        riders["step_pipeline"] = {
            "codec": _codec_speed_record(inst),
            **(
                {
                    "overlap_ratio": served["step_pipeline"]["overlap_ratio"],
                    "device_lane_busy_ratio": served["step_pipeline"][
                        "device_lane_busy_ratio"
                    ],
                }
                if served and served.get("step_pipeline")
                else {}
            ),
        }
    except Exception:
        pass
    try:
        # ISSUE 11: batched ingest crypto — measured on this config's
        # circuit — plus the open-loop upload-overload numbers
        riders["ingest_batch"] = {
            "upload_batch_speed": _upload_batch_speed_record(inst, window=256),
            "open_loop_upload": _open_loop_upload_record(),
        }
    except Exception:
        pass
    try:
        # ISSUE 12: resident vs re-stage accumulate A/B on this
        # config's circuit (the >=2x bytes/report acceptance gate)
        riders["resident_accumulate"] = _resident_accumulate_record(inst)
    except Exception:
        pass
    try:
        # ISSUE 18: the flight recorder's trend view of this very run
        riders["flight"] = _flight_rider()
    except Exception:
        pass
    if args.mode != "served":
        # the served phase already embeds a scraped snapshot; give the
        # device-only record the registry view so observability data
        # rides every BENCH json
        try:
            riders["metrics_snapshot"] = _metrics_snapshot_rider()
        except Exception:
            pass
    print(
        json.dumps(
            {
                "metric": f"prio3_{args.config}_two_party_prepare_accumulate",
                "value": round(device_rps, 2),
                "unit": "report_shares_per_sec_per_chip",
                "vs_baseline": round(device_rps / host_rps, 2),
                "backend": backend,
                "batch": batch,
                "iters": args.iters,
                "compile_s": round(compile_s, 1),
                "host_oracle_rps": round(host_rps, 3),
                "host_oracle_extrapolated": host_scale != 1.0,
                **({"north_star_len100k": north_star} if north_star else {}),
                **({"sparse_northstar": sparse_northstar} if sparse_northstar else {}),
                **({"served": served} if served else {}),
                **hbm,
                **riders,
                "config": inst.to_dict(),
            }
        )
    )


if __name__ == "__main__":
    main()
