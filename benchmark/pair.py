"""The served DAP pair of one deployment, in one process.

Builds what the binaries build, from the deployment file's copies of
the sample configurations: a leader and a helper `Aggregator` behind
`DapHttpApp`/`DapServer` on loopback HTTP, SQLite datastores sharing
one `Crypter` key, the `AggregationJobCreator`, and the leader's
aggregation job driver wired as `janus_tpu/bin/aggregation_job_driver.py`
wires it (`JobDriver` over `StepPipeline`). Collection goes through the
`CollectionJobDriver` and a `Collector` over HTTP.

The benchmark's own `jax.profiler.TraceAnnotation`s wrap the driver's
stage calls, the acquirer and each upload, so that a traced run can say
what the host was doing while the device sat idle.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import secrets
import threading
import time

# the fixed time of the pair's clock; every report is stamped with the
# start of its batch interval
CLOCK_START = 1_600_000_000
# reports per backlog write transaction
BACKLOG_TX = 500


def annotated(name: str, fn):
    """`fn` inside a profiler annotation named `name`."""
    from jax.profiler import TraceAnnotation

    def call(*a, **k):
        with TraceAnnotation(name):
            return fn(*a, **k)

    return call


def seeded_hpke_keypair(seed: int, config_id: int):
    """An X25519 HPKE keypair drawn from the seed, so that a corpus the
    clients sealed for one run of a seed opens in the next."""
    from janus_tpu.core.hpke import HpkeKeypair
    from janus_tpu.core.hpke_backend import x25519_exchange
    from janus_tpu.messages import HpkeAeadId, HpkeConfig, HpkeConfigId, HpkeKdfId, HpkeKemId

    sk = hashlib.sha256(b"janus-bench hpke %d %d" % (seed, config_id)).digest()
    pk = x25519_exchange(sk, (9).to_bytes(32, "little"))  # X25519(sk, base point)
    config = HpkeConfig(
        HpkeConfigId(config_id),
        HpkeKemId.X25519_HKDF_SHA256,
        HpkeKdfId.HKDF_SHA256,
        HpkeAeadId.AES_128_GCM,
        pk,
    )
    return HpkeKeypair(config, sk)


class Pair:
    def __init__(self, config: dict, seed: int, workdir: str):
        from janus_tpu.aggregator import Aggregator
        from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
        from janus_tpu.client import ClientParameters
        from janus_tpu.config import AggregatorConfig, JobCreatorConfig, JobDriverBinaryConfig
        from janus_tpu.core.auth import AuthenticationToken
        from janus_tpu.core.hpke import generate_hpke_config_and_private_key
        from janus_tpu.core.time_util import MockClock
        from janus_tpu.datastore.store import Crypter, open_datastore
        from janus_tpu.messages import Duration, Role, TaskId, Time
        from janus_tpu.task import QueryTypeConfig, TaskBuilder
        from janus_tpu.vdaf.registry import VdafInstance

        self.config = config
        self.inst = VdafInstance.from_dict(config["vdaf"])
        self.clock = MockClock(Time(CLOCK_START))
        self.agg_cfg = AggregatorConfig.from_dict(config["aggregator"]).protocol_config()
        self.creator_cfg = JobCreatorConfig.from_dict(
            config["aggregation_job_creator"]
        ).creator_config()
        self.driver_cfg = JobDriverBinaryConfig.from_dict(config["aggregation_job_driver"])
        crypter_key = secrets.token_bytes(16)
        self.stores, self.servers, self.aggregators = [], [], []
        for role in ("leader", "helper"):
            ds = open_datastore(
                os.path.join(workdir, f"{role}.sqlite"), Crypter([crypter_key]), self.clock
            )
            self.stores.append(ds)
            agg = Aggregator(ds, self.clock, self.agg_cfg)
            self.aggregators.append(agg)
            self.servers.append(DapServer(DapHttpApp(agg)).start())
        self.leader_ds, self.helper_ds = self.stores
        leader_srv, helper_srv = self.servers
        self.leader_url, self.helper_url = leader_srv.url, helper_srv.url

        t = config["task"]
        precision = Duration(int(t["time_precision_secs"]))
        self.collector_kp = generate_hpke_config_and_private_key(config_id=200)
        task_id = hashlib.sha256(b"janus-bench task" + str(seed).encode()).digest()
        self.leader_task = (
            TaskBuilder(QueryTypeConfig.time_interval(), self.inst, Role.LEADER)
            .with_(
                task_id=TaskId(task_id),
                leader_aggregator_endpoint=self.leader_url,
                helper_aggregator_endpoint=self.helper_url,
                collector_hpke_config=self.collector_kp.config,
                aggregator_auth_token=AuthenticationToken.random_bearer(),
                collector_auth_token=AuthenticationToken.random_bearer(),
                min_batch_size=int(t["min_batch_size"]),
                time_precision=precision,
                tolerable_clock_skew=Duration(int(t["tolerable_clock_skew_secs"])),
                # a constant of the compiled programs: fixed per
                # deployment, so a new seed compiles nothing
                vdaf_verify_key=hashlib.sha256(config["name"].encode()).digest()[:16],
                hpke_keys=(seeded_hpke_keypair(seed, 0),),
            )
            .build()
        )
        self.helper_task = dataclasses.replace(
            self.leader_task,
            role=Role.HELPER,
            hpke_keys=(seeded_hpke_keypair(seed, 1),),
        )
        self.leader_ds.run_tx(lambda tx: tx.put_task(self.leader_task))
        self.helper_ds.run_tx(lambda tx: tx.put_task(self.helper_task))
        self.task_id = self.leader_task.task_id
        self.when = self.clock.now().to_batch_interval_start(precision)
        self.upload_url = ClientParameters(
            self.task_id, self.leader_url, self.helper_url, precision
        ).upload_uri()

    def client_view(self) -> dict:
        """What a client knows of the task (corpus.CorpusJob)."""
        return {
            "task_id": self.task_id.data,
            "leader_hpke": self.leader_task.hpke_keys[0].config.to_bytes(),
            "helper_hpke": self.helper_task.hpke_keys[0].config.to_bytes(),
            "when": self.when.seconds,
        }

    @property
    def job_size(self) -> int:
        return self.creator_cfg.max_aggregation_job_size

    # the device programs the warm-up runs, in the order it first runs
    # them (binary_utils.warmup_engines: leader init, helper init, then
    # the aggregate of the leader's output shares)
    WARM_PROGRAMS = ("leader_init", "helper_init", "aggregate")

    def warm_up(self) -> None:
        """The binaries' boot warm-up at the one job size the cell uses."""
        from janus_tpu.binary_utils import warmup_engines

        for ds in self.stores:
            warmup_engines(ds, batch=self.job_size)

    def load_backlog(self, reports: list[bytes]) -> tuple[float, float]:
        """Stores reports in the leader's datastore through its own
        upload stages (decrypt, validate) and write transaction, without
        HTTP. Returns the seconds spent (decrypting, writing)."""
        from janus_tpu.messages import Report

        leader = self.aggregators[0]
        ta = leader.task_aggregator_for(self.task_id)
        decrypt_s = write_s = 0.0
        for lo in range(0, len(reports), BACKLOG_TX):
            t = time.monotonic()
            stored = []
            for raw in reports[lo : lo + BACKLOG_TX]:
                report = Report.from_bytes(raw)
                keypair = ta.upload_prepare(self.clock, report)
                stored.append(ta.upload_decrypt_validate(report, keypair))
            t2 = time.monotonic()
            if not all(leader.report_writer.flush_direct(stored)):
                raise RuntimeError("a backlog report was already stored")
            decrypt_s += t2 - t
            write_s += time.monotonic() - t2
        return decrypt_s, write_s

    def create_jobs(self) -> int:
        from janus_tpu.aggregator.aggregation_job_creator import AggregationJobCreator

        return AggregationJobCreator(self.leader_ds, self.creator_cfg).run_once()

    def job_states(self) -> dict[bytes, tuple[str, int]]:
        """{job id: (state, report count)} of the leader's jobs."""

        def tx_fn(tx):
            return {
                j.job_id.data: (
                    j.state.value,
                    len(tx.get_report_aggregations_for_job(self.task_id, j.job_id)),
                )
                for j in tx.get_aggregation_jobs_for_task(self.task_id)
            }

        return self.leader_ds.run_tx(tx_fn, "bench_job_states")

    def verdicts(self, ds) -> dict[bytes, list[str]]:
        """{report id: [state of each of its report aggregations]}."""

        def tx_fn(tx):
            out: dict[bytes, list[str]] = {}
            for j in tx.get_aggregation_jobs_for_task(self.task_id):
                for ra in tx.get_report_aggregations_for_job(self.task_id, j.job_id):
                    out.setdefault(ra.report_id.data, []).append(ra.state.value)
            return out

        return ds.run_tx(tx_fn, "bench_verdicts")

    def job_driver(self, on_acquire, on_done):
        """(JobDriver, Stopper, close) wired as the driver binary wires
        them; `on_acquire(job_id)` and `on_done(job_id)` see each lease
        and each finished step."""
        from janus_tpu.aggregator.aggregation_job_driver import (
            AggregationJobDriver,
            AggregationJobDriverConfig,
        )
        from janus_tpu.aggregator.job_driver import JobDriver, Stopper
        from janus_tpu.aggregator.step_pipeline import StepPipeline

        cfg = self.driver_cfg
        stopper = Stopper()
        driver = AggregationJobDriver(
            self.leader_ds,
            cfg.helper_http.build(),
            AggregationJobDriverConfig(
                maximum_attempts_before_failure=cfg.job_driver.maximum_attempts_before_failure,
                circuit_breaker=cfg.outbound_circuit_breaker,
                resident=cfg.resident_accumulators,
            ),
            stopper=stopper,
        )
        for stage in (
            "read_job",
            "stage_init",
            "device_init",
            "http_init",
            "device_accumulate",
            "commit_finish",
        ):
            setattr(driver, stage, annotated(f"bench.{stage}", getattr(driver, stage)))
        self.driver = driver
        releaser = lambda acquired: driver.step_back(acquired, "shutdown_drain", 0.0)  # noqa: E731
        acquire = annotated(
            "bench.acquire",
            driver.acquirer(cfg.job_driver.worker_lease_duration_s, fleet=cfg.common.fleet),
        )

        def acquirer(limit):
            jobs = acquire(limit)
            for j in jobs:
                on_acquire(j.job_id.data)
            return jobs

        pipeline = (
            StepPipeline(driver, cfg.step_pipeline, stopper=stopper, releaser=releaser)
            if cfg.step_pipeline.enabled
            else None
        )

        class _Watched:
            """The pipeline, with each job's finish reported."""

            def submit(self, acquired):
                fut = pipeline.submit(acquired)
                jid = acquired.job_id.data
                fut.add_done_callback(lambda _f: on_done(jid))
                return fut

        jd = JobDriver(
            cfg.job_driver,
            acquirer,
            driver.stepper,
            stopper,
            releaser=releaser,
            pipeline=_Watched() if pipeline is not None else None,
        )

        def close():
            if pipeline is not None:
                pipeline.close()

        return jd, stopper, close

    def collect(self):
        """Collects the batch over HTTP; the `CollectionResult`."""
        from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
        from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
        from janus_tpu.collector import Collector, CollectorParameters
        from janus_tpu.core.http_client import HttpClient
        from janus_tpu.messages import Interval, Query

        http = HttpClient()
        collector = Collector(
            CollectorParameters(
                self.task_id,
                self.leader_url,
                self.leader_task.collector_auth_token,
                self.collector_kp,
            ),
            self.inst,
            http,
        )
        query = Query.time_interval(Interval(self.when, self.leader_task.time_precision))
        job_id = collector.start_collection(query)
        cdriver = CollectionJobDriver(self.leader_ds, http)
        JobDriver(
            JobDriverConfig(max_concurrent_job_workers=1), cdriver.acquirer(), cdriver.stepper
        ).run_once()
        return collector.poll_until_complete(job_id, query, timeout_s=120.0)

    def close(self) -> None:
        for srv in self.servers:
            srv.stop()
        for ds in self.stores:
            ds.close()


class Uploader:
    """Open-loop client: report k is due at `start + k / rate` whatever
    the server does, and its latency runs from that due time, so a
    stall is charged to every upload queued behind it. An upload that
    is refused (the leader sheds load with 429 or 503) is sent again
    once the schedule has run out, as a client retries; the latency
    metrics keep its first answer."""

    WORKERS = 48
    RETRIES = 20
    RETRY_WAIT_S = 1.0

    def __init__(self, url: str, reports: list[bytes], rate: float):
        from concurrent.futures import ThreadPoolExecutor

        from janus_tpu.core.http_client import HttpClient

        self.url = url
        self.reports = reports
        self.rate = rate
        self.http = HttpClient(timeout=120.0)
        # first answer of each upload: (status, latency s from due time,
        # lateness s of the send)
        self.results: list = [None] * len(reports)
        self.final: list = [None] * len(reports)  # last status, after retries
        self._pool = ThreadPoolExecutor(self.WORKERS, thread_name_prefix="bench-upload")
        self._futures: list = []

    def _send(self, raw: bytes) -> int:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.upload"):
            try:
                status, _ = self.http.put(
                    self.url, raw, {"Content-Type": "application/dap-report"}
                )
            except Exception:
                status = -1
        return status

    def _fire(self, k: int, due: float) -> None:
        now = time.monotonic()
        if due > now:
            time.sleep(due - now)
        began = time.monotonic()
        status = self._send(self.reports[k])
        self.results[k] = (status, time.monotonic() - due, began - due)
        self.final[k] = status

    def start(self, start: float) -> None:
        """Schedules every report from `start` on."""
        self._futures = [
            self._pool.submit(self._fire, k, start + k / self.rate)
            for k in range(len(self.reports))
        ]

    def wait(self) -> None:
        """Waits for every scheduled upload's first answer."""
        for f in self._futures:
            f.result()

    def finish(self) -> None:
        """Waits for the schedule, then retries what was refused."""
        self.wait()
        for _ in range(self.RETRIES):
            refused = [k for k, st in enumerate(self.final) if st != 201]
            if not refused:
                break
            time.sleep(self.RETRY_WAIT_S)
            statuses = self._pool.map(lambda k: self._send(self.reports[k]), refused)
            for k, st in zip(refused, statuses):
                self.final[k] = st
        self._pool.shutdown(wait=True)


class Window:
    """The leader's job driver running in its own thread, with the time
    each job was leased and each step finished."""

    def __init__(self, pair: Pair):
        self.acquired: list = []  # (job id, t)
        self.done: list = []  # (job id, t)
        self.jd, self.stopper, self._close = pair.job_driver(
            lambda jid: self.acquired.append((jid, time.monotonic())),
            lambda jid: self.done.append((jid, time.monotonic())),
        )
        self._thread = threading.Thread(target=self.jd.run, name="bench-job-driver", daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self.stopper.stop()
        self._thread.join()
        self._close()
