"""Serve Prio3 SumVec end to end on one TPU chip, in one process.

Boots the classes `binary_utils.janus_main` wires — two `Aggregator`s
behind `DapHttpApp`/`DapServer` over loopback HTTP, on-disk SQLite
datastores sharing one explicit `Crypter` key, the
`AggregationJobCreator`, a `JobDriver` over the aggregation and
collection job drivers, and a `Collector` polling over HTTP — and
drives the flagship configuration (Prio3SumVec length 1000, bits 16;
BASELINE.json configs[2]) from upload to collected result.

Both aggregator roles live in this one process because a chip belongs
to one process. The clients' reports are sharded on the host CPU:
clients are not the aggregator, and nothing on the aggregator side
touches the CPU device.

    python chip_smoke.py  # one chip: the process is pinned to one

The last line of standard output is the contract line
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}`,
printed only when every phase and check passed. Without a TPU the
script exits non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

_T0 = time.monotonic()

# Janus upstream's aggregation job sizes
# (docs/samples/aggregation_job_creator.yaml): 2,000 reports make four
# jobs of 500, which dispatch at jit bucket 512.
N_REPORTS = 2000
JOB_SIZE = 500
MIN_JOB_SIZE = 10
# client reports are sharded on the host in chunks of this many
CLIENT_CHUNK = 250
# libtpu's visible-chip variables: one process, one chip, one device
ONE_CHIP_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


# persistent compile-cache lookups, counted from JAX's monitoring events
# (the listener is registered in main)
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0, "/jax/compilation_cache/cache_misses": 0}


def _count_cache_event(event: str, **_) -> None:
    if event in _CACHE_EVENTS:
        _CACHE_EVENTS[event] += 1


def log(msg: str) -> None:
    print(f"[chip_smoke {time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


class _Phase:
    """Wall seconds of one phase, the engine compiles inside it
    (janus_engine_compile_seconds) and the persistent compile cache's
    hits and misses, logged when the phase ends."""

    def __init__(self, name: str, record: dict):
        from janus_tpu import metrics

        self.name = name
        self.record = record
        self.hist = metrics.engine_compile_seconds

    def __enter__(self):
        self.t0 = time.monotonic()
        self.c0 = self.hist.total()
        self.cache0 = list(_CACHE_EVENTS.values())
        return self

    def __exit__(self, exc_type, *exc):
        n, s = self.hist.total()
        wall = time.monotonic() - self.t0
        hits, misses = (b - a for a, b in zip(self.cache0, _CACHE_EVENTS.values()))
        phase = {
            "wall_s": wall,
            "compiles": n - self.c0[0],
            "compile_s": s - self.c0[1],
            "cache_hits": hits,
            "cache_misses": misses,
        }
        self.record["phases"][self.name] = phase
        if exc_type is None:
            log(
                f"phase {self.name}: wall {wall:.1f}s, {phase['compiles']} engine "
                f"compiles taking {phase['compile_s']:.1f}s, compile cache "
                f"{hits} hits {misses} misses"
            )
        return False


def verify_key(seed: int) -> bytes:
    """The task's VDAF verify key, made from the seed: it is a constant
    of the compiled programs, so a later run with the same seed finds
    them in the persistent compile cache."""
    import numpy as np

    return np.random.default_rng([seed, 0x7E5]).bytes(16)


def run_smoke(inst, n_reports: int, job_size: int, seed: int) -> dict:
    """Serve `n_reports` random measurements of `inst` through a leader
    and helper pair and collect them. Returns the record: the collected
    aggregate beside the host's column sum, per-phase wall and compile
    seconds, and the engines' states."""
    import dataclasses
    import secrets
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import numpy as np

    from janus_tpu import metrics
    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.aggregation_job_driver import (
        AggregationJobDriver,
        AggregationJobDriverConfig,
        ResidentConfig,
    )
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.engine_cache import engine_cache_status
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.binary_utils import warmup_engines
    from janus_tpu.client import ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import MockClock
    from janus_tpu.datastore.store import Crypter, open_datastore
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.testing import make_report_batch, make_wire_reports, random_measurements

    record: dict = {"phases": {}, "n_reports": n_reports, "job_size": job_size}
    fallbacks0 = metrics.engine_host_fallback_counter.total()
    clock = MockClock(Time(1_600_000_000))
    crypter_key = secrets.token_bytes(16)
    servers, stores = [], []
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        try:
            with _Phase("boot", record):
                for role in ("leader", "helper"):
                    ds = open_datastore(
                        os.path.join(tmp, f"{role}.sqlite"), Crypter([crypter_key]), clock
                    )
                    stores.append(ds)
                    servers.append(DapServer(DapHttpApp(Aggregator(ds, clock, Config()))).start())
                leader_ds, helper_ds = stores
                leader_srv, helper_srv = servers
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
                        vdaf_verify_key=verify_key(seed),
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
                record["verify_key"] = leader_task.vdaf_verify_key

            # the binaries' boot warm-up, at every job size the run uses
            warm_sizes = {min(job_size, n_reports)}
            if n_reports % job_size >= MIN_JOB_SIZE:
                warm_sizes.add(n_reports % job_size)
            with _Phase("warmup", record):
                for size in sorted(warm_sizes):
                    warmup_engines(leader_ds, batch=size)
                    warmup_engines(helper_ds, batch=size)

            when = clock.now().to_batch_interval_start(leader_task.time_precision)
            with _Phase("client", record):
                meas = random_measurements(inst, n_reports, np.random.default_rng(seed))
                # the clients shard on the host CPU device, where the
                # Mosaic kernels give way to the scan path
                with jax.default_device(jax.devices("cpu")[0]):
                    batch_args, _ = make_report_batch(
                        inst, meas, seed=seed, shard_chunk=CLIENT_CHUNK
                    )
                reports = make_wire_reports(
                    inst,
                    meas,
                    leader_task.task_id,
                    leader_task.hpke_keys[0].config,
                    helper_task.hpke_keys[0].config,
                    when,
                    batch_args=batch_args,
                )
                del batch_args
            record["expected"] = [int(x) for x in np.asarray(meas).sum(axis=0)]
            log(f"client: {len(reports)} reports sharded on the host cpu device and sealed")

            http = HttpClient()
            upload_uri = ClientParameters(
                leader_task.task_id, leader_srv.url, helper_srv.url, leader_task.time_precision
            ).upload_uri()

            def upload(report) -> None:
                status, body = http.put(
                    upload_uri, report.to_bytes(), {"Content-Type": "application/dap-report"}
                )
                if status != 201:
                    raise RuntimeError(f"upload answered {status}: {body[:200]!r}")

            with _Phase("upload", record):
                with ThreadPoolExecutor(max_workers=16) as pool:
                    list(pool.map(upload, reports))
            del reports

            with _Phase("aggregate", record):
                creator = AggregationJobCreator(
                    leader_ds,
                    AggregationJobCreatorConfig(
                        min_aggregation_job_size=MIN_JOB_SIZE,
                        max_aggregation_job_size=job_size,
                    ),
                )
                record["jobs_created"] = creator.run_once()
                driver = AggregationJobDriver(
                    leader_ds,
                    http,
                    AggregationJobDriverConfig(resident=ResidentConfig(enabled=True)),
                )
                # one job at a time: every dispatch is one job at its
                # warmed bucket, none coalesced into a larger one
                jd = JobDriver(
                    JobDriverConfig(max_concurrent_job_workers=1),
                    driver.acquirer(),
                    driver.stepper,
                )
                record["job_steps"] = 0
                while (stepped := jd.run_once()) > 0:
                    record["job_steps"] += stepped
                driver.flush_resident_state(reason="drain")

            with _Phase("collect", record):
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
                job_id = collector.start_collection(query)
                cdriver = CollectionJobDriver(leader_ds, http)
                JobDriver(
                    JobDriverConfig(max_concurrent_job_workers=1),
                    cdriver.acquirer(),
                    cdriver.stepper,
                ).run_once()
                result = collector.poll_until_complete(job_id, query, timeout_s=120.0)
            record["report_count"] = result.report_count
            record["aggregate"] = [int(x) for x in result.aggregate_result]
            record["engines"] = engine_cache_status()["engines"]
            record["host_fallbacks"] = metrics.engine_host_fallback_counter.total() - fallbacks0
            return record
        finally:
            for srv in servers:
                srv.stop()
            for ds in stores:
                ds.close()


def check_record(rec: dict) -> list[str]:
    """What a served run must show, whatever the backend."""
    failures = []
    if rec["report_count"] != rec["n_reports"]:
        failures.append(f"report_count {rec['report_count']} != {rec['n_reports']}")
    if rec["aggregate"] != rec["expected"]:
        failures.append("collected aggregate != column sum of the measurements")
    for e in rec["engines"]:
        if e["backend"] != "device" or e.get("quarantined"):
            failures.append(f"engine {e['vdaf']} is in state {e['backend']}")
    if rec["host_fallbacks"]:
        failures.append(f"janus_engine_host_fallbacks = {rec['host_fallbacks']}")
    return failures


def log_record(rec: dict) -> None:
    log(
        f"served {rec['report_count']} reports in {rec['jobs_created']} jobs "
        f"({rec['job_steps']} job steps); aggregate == host sum: "
        f"{rec['aggregate'] == rec['expected']}"
    )
    for e in rec["engines"]:
        log(
            f"engine {e['vdaf']}: backend={e['backend']} quarantined={e.get('quarantined')} "
            f"dp={e.get('dp')} sp={e.get('sp')} bucket_cap={e.get('bucket_cap')}"
        )


def leader_program_text(inst, vk: bytes, bucket: int) -> str:
    """Compiled HLO of the leader prepare program the serving engine
    dispatches at `bucket` (the compile cache answers it)."""
    import jax

    from janus_tpu.aggregator.engine_cache import engine_cache
    from janus_tpu.vdaf.testing import zero_report_batch

    step = engine_cache(inst, vk).init_step("leader_init")
    return jax.jit(step).lower(*zero_report_batch(inst, bucket)[:5]).compile().as_text()


def run_one_chip(seed: int) -> list[str]:
    from janus_tpu import native
    from janus_tpu.aggregator.engine_cache import bucket_size
    from janus_tpu.vdaf.registry import VdafInstance

    inst = VdafInstance.sum_vec(length=1000, bits=16)
    rec = run_smoke(inst, N_REPORTS, JOB_SIZE, seed)
    log_record(rec)
    failures = check_record(rec)
    t0 = time.monotonic()
    text = leader_program_text(inst, rec["verify_key"], bucket_size(JOB_SIZE))
    has_kernel = "tpu_custom_call" in text
    log(
        f"leader program at bucket {bucket_size(JOB_SIZE)}: tpu_custom_call present: "
        f"{has_kernel} (read in {time.monotonic() - t0:.1f}s)"
    )
    if not has_kernel:
        failures.append("leader prepare program has no tpu_custom_call (Keccak on the scan path)")
    if not native.available():
        failures.append("janus_tpu.native is not available")
    return failures


def _versions() -> str:
    import importlib.metadata as md

    import jax
    import jaxlib

    return f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={md.version('libtpu')}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--seed", type=int, default=20261015, help="seed of the measurements and reports"
    )
    args = ap.parse_args(argv)
    import janus_tpu  # noqa: F401  - alone, outside its checkout, the script stops here

    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms:
        names = platforms.split(",")
        if "tpu" not in names:
            print(f"chip_smoke: JAX_PLATFORMS={platforms!r} leaves no TPU", file=sys.stderr)
            return 2
        if "cpu" not in names:
            # the clients shard on the host's CPU device
            os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    # one chip per process, fixed before JAX initializes
    for k, v in ONE_CHIP_ENV.items():
        os.environ.setdefault(k, v)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform})", file=sys.stderr)
        return 2
    log(f"{_versions()} platform={dev.platform} device_kind={dev.device_kind} count={len(devices)}")
    if len(devices) != 1:
        print(f"chip_smoke: pinned to one chip, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from janus_tpu.binary_utils import enable_compile_cache
    from janus_tpu.ops import keccak_pallas

    cache_dir, source = enable_compile_cache()
    jax.monitoring.register_event_listener(_count_cache_event)
    log(f"compile cache: {cache_dir} (from {source})")
    log(f"Pallas kernels: {keccak_pallas.status()}")

    failures = run_one_chip(args.seed)
    stats = dev.memory_stats() or {}
    log(
        f"device 0 peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"of bytes_limit={stats.get('bytes_limit')}"
    )
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
