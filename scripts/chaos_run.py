"""Crash-recovery chaos harness: prove exactly-once aggregation under
injected faults (docs/ROBUSTNESS.md; the standing answer to "what
breaks when X dies").

Topology — chosen so the component being killed is the REAL binary
while everything else stays fast:

  - leader + helper DAP servers run in-process (DapServer threads over
    loopback HTTP) with file-backed SQLite datastores in a temp dir,
    so the aggregation job drivers cross a real process + HTTP + DB
    boundary;
  - the aggregation job driver — the thing that crashes — runs as the
    real `python -m janus_tpu.bin.aggregation_job_driver` binary
    against the leader database file, armed via JANUS_FAILPOINTS;
  - the job creator and collection job driver run in-process.

Deterministic schedule (all probabilistic faults are count-budgeted):

  1. upload N reports through the real Client; the admitted
     measurements are the ground truth.
  2. driver A boots with
       datastore.commit.step_agg_job_write=crash:1.0,count=1
     — it steps the job: the helper aggregates and acks the init, and
     the leader dies (os._exit, the SIGKILL analog) BEFORE its own
     write commits. Assert exit code CRASH_EXIT_CODE and a still-held
     lease.
  3. driver B boots into a storm:
       env  helper.request=error:1.0,count=2   (transport failures)
            datastore.commit=error:0.2          (transient tx faults,
                                                 absorbed by run_tx)
       harness-side helper.aggregate=error:1.0,count=2 (real HTTP 500s
                                                 from the helper)
     Its outbound circuit must open, the job steps back (lease
     released early, attempt refunded), the breaker half-opens and
     closes once the storm budget is spent, and the job completes —
     the helper's request-hash dedup makes the replayed init
     idempotent. The lease must be reacquired within the lease TTL.
  4. (full schedule only) a second batch + driver C with
       datastore.post_commit.step_agg_job_write=crash:1.0,count=1
     — death AFTER the commit, before anything was acked — then a
     clean driver D that must find nothing left to redo.
  5. collect through the real Collector and assert the aggregate
     equals the ground truth EXACTLY (count and sum: no loss, no
     double-count), the breaker cycle is visible in
     janus_outbound_circuit_state / _transitions_total and on
     /statusz, and driver B SIGTERM-drains cleanly.

A second scenario, `--scenario db_outage`, proves DATASTORE-outage
survival (docs/ROBUSTNESS.md "Datastore outages"): under a sustained
upload load, the leader's database is taken down via the
`datastore.connect` failpoint (scoped to the leader's store — no real
process is killed). Invariants:

  - every upload acked 201 before, DURING and after the outage window
    is present exactly once in the final collected aggregate — during
    the outage the acks rest on the durable spill journal's fsync;
  - the datastore supervisor walks up → degraded → down → recovering →
    up, `/readyz` flips 200 → 503 (with a JSON reason) → 200 while
    `/healthz` stays live, and aggregate-step routes shed 503 while
    the store is down;
  - on recovery the journal drains to empty (replay through the write
    batcher, report-id dedup = exactly-once) and is truncated;
  - while the datastore is healthy the armed-but-idle journal performs
    ZERO fsyncs — the hot path is unchanged.

A third scenario, `--scenario device_hang`, proves the DEADLINE-AWARE
DEVICE PATH (docs/ROBUSTNESS.md "Device hangs & deadlines"): the real
aggregation job driver binary runs with `engine.dispatch=hang,count=1`
armed — its first device dispatch wedges forever, exactly like a hung
XLA dispatch. Invariants:

  - the hung step never outlives its lease: the dispatch watchdog
    abandons the dispatch within the lease budget and the job steps
    back (`janus_job_step_back_total{reason="device_hang"}`), releasing
    the lease BEFORE its expiry;
  - the abandoned thread is visible (`janus_hung_dispatches_total`,
    `janus_abandoned_dispatch_threads` under the cap, a live stack dump
    in /statusz `device_watchdog.stalled`) and the engine transitions
    device → quarantined → (canary recompile + probe) → device, all
    observed live over the driver's /metrics + /statusz;
  - interim work lands through the host fallback while quarantined, and
    the final collection equals the admitted ground truth exactly;
  - the driver SIGTERM-drains cleanly (release_hangs unparks the
    modeled wedge on shutdown).

A fifth scenario, `--scenario resident`, proves the RESIDENT
AGGREGATE STATE flush contract (docs/ARCHITECTURE.md "Resident
aggregate state"): the real driver binary runs with
`resident_accumulators` enabled, a one-slot `resident_max_bytes`, and
`engine.dispatch=hang,count=1,after=4` armed. Invariants: an LRU
eviction flushes through the write-tx path live
(`janus_engine_resident_flushes_total{reason="eviction"}`), the
mid-stream quarantine's flusher sweep writes the surviving slot out
(`reason="quarantine"`) while the wedged job re-steps on the host
path, a post-restore job lands resident and SIGTERM drains it, no
flush reports `outcome="lost"`, and BOTH tasks' collections equal
their admitted ground truths exactly.

A further scenario, `--scenario peer_outage`, proves PEER-outage
survival (docs/ARCHITECTURE.md "Surviving the other aggregator"): the
REAL aggregation + collection job driver binaries reach the in-process
helper only through a core/netsim.py FaultProxy, and the wire is
degraded toxiproxy-style. Invariants:

  - clean baseline traffic flows through the proxy and aggregates
    exactly;
  - a full blackhole longer than the breaker-open threshold keeps
    uploads at 201 (the leader is untouched) while BOTH driver
    binaries open their breakers, step back (`reason="circuit_open"`,
    bounded), then PARK: claim transactions stop cold
    (`janus_lease_acquire_tx_total` frozen), `janus_peer_parked` = 1,
    `janus_peer_outage_seconds_total` grows, `/statusz` grows a
    `peer_health` section, and `janus_lease_conflicts_total` stays 0;
  - when the wire heals, the cheap half-open probe
    (`janus_peer_probes_total{outcome="alive"}`) closes the breaker,
    both drivers resume, and the parked work drains;
  - a slow-drip (slicer) response trips the wall-clock body budget and
    a mid-body truncation retries as a torn connection — neither
    wedges a worker, both lanes complete;
  - (full schedule) latency+jitter and flaky mid-request reset lanes
    also complete;
  - the final collections equal the admitted ground truth EXACTLY and
    both binaries SIGTERM-drain cleanly.

Usage:
    python scripts/chaos_run.py --smoke --json   # fast deterministic
    python scripts/chaos_run.py --json           # full schedule (slow)
    python scripts/chaos_run.py --scenario db_outage --smoke --json
    python scripts/chaos_run.py --scenario device_hang --smoke --json
    python scripts/chaos_run.py --scenario resident --smoke --json
    python scripts/chaos_run.py --scenario peer_outage --smoke --json

Exit code 0 iff every invariant held; the result JSON rides on stdout
(bench.py --dry-run embeds the smokes as its chaos_smoke and
db_outage_smoke phases).
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import re
import secrets
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# single-device CPU everywhere, shared persistent compile cache: the
# harness pre-warms the engine programs so the driver subprocesses load
# them from disk instead of paying a cold jit inside a short lease
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
os.environ["XLA_FLAGS"] = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "", _flags
).strip()

CRASH_SCHEDULE = "datastore.commit.step_agg_job_write=crash:1.0,count=1"
POST_COMMIT_CRASH_SCHEDULE = (
    "datastore.post_commit.step_agg_job_write=crash:1.0,count=1"
)
STORM_SCHEDULE = "helper.request=error:1.0,count=2;datastore.commit=error:0.2"
HELPER_5XX_SCHEDULE = "helper.aggregate=error:1.0,count=2"
# full datastore outage, scoped to the store whose failpoint_scope is
# "leader" (the harness names the leader's store; the in-process
# helper's store keeps its default scope and stays up)
DB_OUTAGE_SCHEDULE = "datastore.connect.leader=error:1.0"
# the driver's first device dispatch wedges FOREVER (released only by
# the stopper): the hung-XLA-dispatch model for --scenario device_hang
DEVICE_HANG_SCHEDULE = "engine.dispatch=hang,count=1"
# --scenario pipeline: stretch every helper RTT so the stage pipeline
# has a real window to overlap device work with (loopback RTTs are
# otherwise microseconds and the overlap proof would be flaky)
PIPELINE_RTT_SCHEDULE = "helper.request=delay:0.08"
# --scenario fleet: stretch the helper RTT so job throughput is
# RTT-bound — N replicas' worker pools then overlap N times the
# sleeping round trips and the served-rps scaling curve measures FLEET
# parallelism, not a 2-core host's CPU arithmetic
FLEET_RTT_SCHEDULE = "helper.request=delay:0.1"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _driver_cfg(
    path, db, health_port, ttl_s, cooldown_s, extra: str = "",
):
    cfg = (
        f"database: {{url: {db}}}\n"
        f'health_check_listen_address: "127.0.0.1:{health_port}"\n'
        "jax_platform: cpu\n"
        "min_job_discovery_delay_secs: 0.1\n"
        "max_job_discovery_delay_secs: 0.5\n"
        f"worker_lease_duration_secs: {ttl_s}\n"
        "maximum_attempts_before_failure: 20\n"
        "outbound_circuit_breaker:\n"
        "  failure_threshold: 3\n"
        f"  open_cooldown_secs: {cooldown_s}\n"
        + extra
    )
    with open(path, "w") as f:
        f.write(cfg)
    return str(path)


def _spawn_driver(
    cfg_path, key, log_path, failpoints: str | None, extra_env=None,
    module: str = "janus_tpu.bin.aggregation_job_driver",
):
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        DATASTORE_KEYS=key,
        JAX_PLATFORMS="cpu",
    )
    # hermetic shape manifest per scenario run: a stale manifest
    # inherited from the developer/test environment would make every
    # driver boot pay an unrelated prewarm pass (scenarios that test
    # the prewarm itself pass an explicit path via extra_env)
    env["JANUS_SHAPE_MANIFEST"] = os.path.join(
        os.path.dirname(str(cfg_path)), "shape-manifest.jsonl"
    )
    env.update(extra_env or {})
    if failpoints:
        env["JANUS_FAILPOINTS"] = failpoints
    else:
        env.pop("JANUS_FAILPOINTS", None)
    logf = open(log_path, "wb")
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            module,
            "--config-file",
            str(cfg_path),
        ],
        env=env,
        stdout=logf,
        stderr=subprocess.STDOUT,
        cwd=REPO,
    )


def _wait_healthz(port: int, deadline_s: float = 120.0) -> None:
    deadline = time.monotonic() + deadline_s
    while True:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2
            ) as r:
                assert r.status == 200
                return
        except Exception:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.2)


def _scrape(port: int, path: str) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.read().decode()


def _metric_samples(text: str, name: str) -> dict[str, float]:
    """{label_block_or_'': value} for one family of a scraped /metrics
    page, via the shared exposition parser (janus_tpu.exposition — the
    same one scrape_check and the metrics tests use, incl. escaped
    label values)."""
    from janus_tpu.exposition import parse_exposition

    fam = parse_exposition(text)[0].get(name)
    if fam is None:
        return {}
    return {
        ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())): float(value)
        for sample_name, labels, value in fam.samples
        if sample_name == name
    }


def run_chaos(
    n_reports: int = 5,
    lease_ttl_s: int = 8,
    breaker_cooldown_s: float = 1.5,
    full: bool = False,
    workdir: str | None = None,
) -> dict:
    """Run the schedule; returns the invariant-assertion record. Every
    `*_ok` key must be True for the run to count as a pass."""
    from janus_tpu import failpoints
    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.binary_utils import enable_compile_cache, warmup_engines
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, Datastore
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    import dataclasses

    t_run0 = time.monotonic()
    tmp = workdir or tempfile.mkdtemp(prefix="janus-chaos-")
    os.makedirs(tmp, exist_ok=True)
    key_bytes = secrets.token_bytes(16)
    key = base64.urlsafe_b64encode(key_bytes).decode().rstrip("=")
    clock = RealClock()
    leader_db = os.path.join(tmp, "leader.sqlite")
    helper_db = os.path.join(tmp, "helper.sqlite")
    leader_ds = Datastore(leader_db, Crypter([key_bytes]), clock)
    helper_ds = Datastore(helper_db, Crypter([key_bytes]), clock)

    result: dict = {"workdir": tmp, "schedule": "full" if full else "smoke"}
    procs: list[subprocess.Popen] = []
    leader_srv = helper_srv = None
    try:
        helper_srv = DapServer(
            DapHttpApp(Aggregator(helper_ds, clock, Config()))
        ).start()
        leader_srv = DapServer(
            DapHttpApp(Aggregator(leader_ds, clock, Config(collection_retry_after_s=1)))
        ).start()

        vdaf = VdafInstance.count()
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
        leader_ds.run_tx(lambda tx: tx.put_task(leader_task), "provision")
        helper_ds.run_tx(lambda tx: tx.put_task(helper_task), "provision")

        # pre-warm the engine programs into the persistent XLA cache:
        # the driver subprocesses (same single-device CPU config) load
        # them from disk instead of cold-compiling inside a short lease
        enable_compile_cache()
        warmup_engines(leader_ds)

        # --- phase 1: ground truth -------------------------------------
        http = HttpClient()
        params = ClientParameters(
            leader_task.task_id, leader_srv.url, helper_srv.url, leader_task.time_precision
        )
        client = Client.with_fetched_configs(params, vdaf, http, clock=clock)
        measurements = [(i % 3 != 0) * 1 for i in range(n_reports)]
        for m in measurements:
            client.upload(m)
        creator = AggregationJobCreator(
            leader_ds,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=100
            ),
        )
        creator.run_once()
        result["admitted"] = len(measurements)
        result["ground_truth_sum"] = sum(measurements)

        def held_agg_leases():
            return [
                e
                for e in leader_ds.run_tx(
                    lambda tx: tx.get_held_lease_expiries(), "chaos_monitor"
                )
                if e[0] == "aggregation"
            ]

        def agg_jobs_by_state():
            counts = leader_ds.run_tx(
                lambda tx: tx.count_jobs_by_state(), "chaos_monitor"
            )
            return {
                state: n for (typ, state), n in counts.items() if typ == "aggregation"
            }

        # --- phase 2: crash between helper ack and leader commit --------
        from janus_tpu.failpoints import CRASH_EXIT_CODE

        ttl = int(lease_ttl_s)
        port_a = _free_port()
        cfg_a = _driver_cfg(
            os.path.join(tmp, "driver_a.yaml"), leader_db, port_a, ttl, breaker_cooldown_s
        )
        drv_a = _spawn_driver(
            cfg_a, key, os.path.join(tmp, "driver_a.log"), CRASH_SCHEDULE
        )
        procs.append(drv_a)
        rc_a = drv_a.wait(timeout=300)
        t_crash = time.monotonic()
        result["crash_exit_code"] = rc_a
        result["crash_ok"] = rc_a == CRASH_EXIT_CODE
        leases = held_agg_leases()
        # the dead driver's lease is still outstanding: nobody rolled it
        # back, exactly like SIGKILL
        result["lease_held_after_crash_ok"] = len(leases) == 1
        crashed_expiry = leases[0][3] if leases else 0
        states = agg_jobs_by_state()
        result["job_in_progress_after_crash_ok"] = states.get("in_progress", 0) >= 1

        # --- phase 3: restart into a helper storm -----------------------
        failpoints.configure(HELPER_5XX_SCHEDULE)  # helper-side real 500s
        port_b = _free_port()
        cfg_b = _driver_cfg(
            os.path.join(tmp, "driver_b.yaml"), leader_db, port_b, ttl, breaker_cooldown_s
        )
        drv_b = _spawn_driver(
            cfg_b, key, os.path.join(tmp, "driver_b.log"), STORM_SCHEDULE
        )
        procs.append(drv_b)
        _wait_healthz(port_b)
        # the recovery clock starts once a live driver exists: reacquire
        # latency must not be charged for driver B's own boot time
        t_recoverable = max(t_crash, time.monotonic())

        reacquired_at = None
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            if reacquired_at is None:
                now_leases = held_agg_leases()
                if any(e[3] != crashed_expiry for e in now_leases):
                    reacquired_at = time.monotonic()
            states = agg_jobs_by_state()
            if states.get("in_progress", 0) == 0 and states.get("finished", 0) >= 1:
                if reacquired_at is None:
                    reacquired_at = time.monotonic()
                break
            time.sleep(0.05)
        states = agg_jobs_by_state()
        result["job_finished_ok"] = (
            states.get("finished", 0) >= 1 and states.get("in_progress", 0) == 0
        )
        result["lease_reacquire_s"] = (
            round(reacquired_at - t_recoverable, 3) if reacquired_at else None
        )
        # the crashed lease must be picked up within its TTL (plus
        # discovery latency margin): leases are always recovered
        result["lease_reacquired_within_ttl_ok"] = (
            reacquired_at is not None and (reacquired_at - t_recoverable) <= ttl + 3.0
        )
        failpoints.clear()

        # --- breaker cycle visibility (driver B is still alive) ---------
        metrics_text = _scrape(port_b, "/metrics")
        state_samples = _metric_samples(metrics_text, "janus_outbound_circuit_state")
        trans = _metric_samples(
            metrics_text, "janus_outbound_circuit_transitions_total"
        )
        result["circuit_state_samples"] = state_samples
        result["circuit_transitions"] = trans
        opened = sum(v for k, v in trans.items() if 'to="open"' in k)
        half = sum(v for k, v in trans.items() if 'to="half_open"' in k)
        closed = sum(v for k, v in trans.items() if 'to="closed"' in k)
        result["circuit_cycle_ok"] = (
            opened >= 1
            and half >= 1
            and closed >= 1
            and state_samples
            and all(v == 0.0 for v in state_samples.values())  # closed again
        )
        statusz = json.loads(_scrape(port_b, "/statusz"))
        result["statusz_circuit_ok"] = bool(
            statusz.get("outbound_circuit", {}).get("peers")
        )
        result["statusz_failpoints_armed_ok"] = (
            statusz.get("failpoints", {}).get("enabled") is True
        )
        step_backs = _metric_samples(metrics_text, "janus_job_step_back_total")
        result["step_backs"] = step_backs
        result["stepped_back_ok"] = (
            sum(v for k, v in step_backs.items() if "circuit_open" in k) >= 1
        )

        # --- SIGTERM drain of driver B ----------------------------------
        drv_b.send_signal(signal.SIGTERM)
        rc_b = drv_b.wait(timeout=60)
        log_b = open(os.path.join(tmp, "driver_b.log"), "rb").read()
        result["drain_ok"] = rc_b == 0 and b"shut down" in log_b

        # --- phase 4 (full): crash AFTER commit, before ack --------------
        if full:
            extra = [1] * max(3, n_reports // 2)
            for m in extra:
                client.upload(m)
            measurements += extra
            result["admitted"] = len(measurements)
            result["ground_truth_sum"] = sum(measurements)
            creator.run_once()
            port_c = _free_port()
            cfg_c = _driver_cfg(
                os.path.join(tmp, "driver_c.yaml"),
                leader_db,
                port_c,
                ttl,
                breaker_cooldown_s,
            )
            drv_c = _spawn_driver(
                cfg_c, key, os.path.join(tmp, "driver_c.log"), POST_COMMIT_CRASH_SCHEDULE
            )
            procs.append(drv_c)
            rc_c = drv_c.wait(timeout=300)
            result["post_commit_crash_ok"] = rc_c == CRASH_EXIT_CODE
            # death after the commit: the work IS durable; a clean
            # restart must find nothing left to redo (and the final
            # exact-count collection proves nothing was re-done)
            states = agg_jobs_by_state()
            result["post_commit_job_finished_ok"] = states.get("in_progress", 0) == 0
            port_d = _free_port()
            cfg_d = _driver_cfg(
                os.path.join(tmp, "driver_d.yaml"),
                leader_db,
                port_d,
                ttl,
                breaker_cooldown_s,
            )
            drv_d = _spawn_driver(
                cfg_d, key, os.path.join(tmp, "driver_d.log"), None
            )
            procs.append(drv_d)
            _wait_healthz(port_d)
            time.sleep(2.0)  # a couple of discovery passes
            drv_d.send_signal(signal.SIGTERM)
            rc_d = drv_d.wait(timeout=60)
            states = agg_jobs_by_state()
            result["clean_restart_ok"] = rc_d == 0 and states.get("in_progress", 0) == 0

        # --- phase 5: collect and compare against ground truth ----------
        import threading

        cdrv = CollectionJobDriver(leader_ds, HttpClient())
        stop_collect = threading.Event()

        def collect_loop():
            from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig

            jd = JobDriver(
                JobDriverConfig(job_discovery_interval_s=0.2),
                cdrv.acquirer(60),
                cdrv.stepper,
            )
            while not stop_collect.is_set():
                jd.run_once()
                stop_collect.wait(0.3)

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
            collected = collector.collect(query, timeout_s=120.0)
            result["collected_count"] = collected.report_count
            result["collected_sum"] = collected.aggregate_result
            # THE invariant: exactly the admitted reports, no loss, no
            # double count — across a mid-commit crash, commit faults,
            # transport storms and helper 500s
            result["exactly_once_ok"] = (
                collected.report_count == len(measurements)
                and collected.aggregate_result == sum(measurements)
            )
        finally:
            stop_collect.set()
            ct.join(timeout=10)

        result["elapsed_s"] = round(time.monotonic() - t_run0, 1)
        result["ok"] = all(v for k, v in result.items() if k.endswith("_ok"))
        return result
    finally:
        failpoints_mod = sys.modules.get("janus_tpu.failpoints")
        if failpoints_mod is not None:
            failpoints_mod.clear()
        for p in procs:
            if p.poll() is None:
                p.kill()
        if leader_srv is not None:
            leader_srv.stop()
        if helper_srv is not None:
            helper_srv.stop()
        leader_ds.close()
        helper_ds.close()


def _http_status(url: str, method: str = "GET", body: bytes | None = None,
                 headers: dict | None = None, timeout: float = 10.0):
    """(status, body bytes) tolerating non-2xx (urllib raises on those);
    the shared helper lives beside the HTTP client."""
    from janus_tpu.core.http_client import fetch_any_status

    return fetch_any_status(url, method=method, body=body, headers=headers, timeout=timeout)


def run_db_outage(
    n_warm: int = 4,
    outage_hold_s: float = 1.5,
    probe_interval_s: float = 0.15,
    full: bool = False,
    workdir: str | None = None,
) -> dict:
    """Datastore-outage survival schedule (see module docstring); every
    `*_ok` key must be True for the run to pass."""
    import threading

    from janus_tpu import failpoints
    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.aggregation_job_driver import AggregationJobDriver
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.binary_utils import (
        HealthServer,
        enable_compile_cache,
        register_readiness_check,
        unregister_readiness_check,
        warmup_engines,
    )
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, Datastore
    from janus_tpu.messages import (
        AggregationJobInitializeReq,
        Duration,
        Interval,
        Query,
        Role,
        Time,
    )
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    import dataclasses

    t_run0 = time.monotonic()
    tmp = workdir or tempfile.mkdtemp(prefix="janus-dbout-")
    os.makedirs(tmp, exist_ok=True)
    key_bytes = secrets.token_bytes(16)
    clock = RealClock()
    leader_ds = Datastore(
        os.path.join(tmp, "leader.sqlite"), Crypter([key_bytes]), clock
    )
    # the outage schedule targets ONLY this store (the in-process
    # helper's store keeps its default scope and stays up)
    leader_ds.failpoint_scope = "leader"
    helper_ds = Datastore(
        os.path.join(tmp, "helper.sqlite"), Crypter([key_bytes]), clock
    )
    sup = leader_ds.start_supervision(
        probe_interval_s=probe_interval_s,
        down_threshold=2,
        reconnect_max_interval_s=max(1.0, 4 * probe_interval_s),
    )
    register_readiness_check("datastore", sup.readiness)

    result: dict = {
        "workdir": tmp,
        "schedule": "db_outage_full" if full else "db_outage_smoke",
    }
    leader_srv = helper_srv = health_srv = None
    leader_agg = None
    try:
        journal_dir = os.path.join(tmp, "upload-journal")
        leader_agg = Aggregator(
            leader_ds,
            clock,
            Config(
                collection_retry_after_s=1,
                upload_journal_path=journal_dir,
                upload_journal_replay_interval_s=0.2,
            ),
        )
        journal = leader_agg.upload_journal
        helper_srv = DapServer(
            DapHttpApp(Aggregator(helper_ds, clock, Config()))
        ).start()
        leader_srv = DapServer(DapHttpApp(leader_agg)).start()
        health_srv = HealthServer("127.0.0.1:0").start()
        hp = health_srv.port

        vdaf = VdafInstance.count()
        collector_kp = generate_hpke_config_and_private_key(config_id=201)
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
            hpke_keys=(generate_hpke_config_and_private_key(config_id=2),),
        )
        leader_ds.run_tx(lambda tx: tx.put_task(leader_task), "provision")
        helper_ds.run_tx(lambda tx: tx.put_task(helper_task), "provision")
        enable_compile_cache()
        warmup_engines(leader_ds)

        http = HttpClient()
        params = ClientParameters(
            leader_task.task_id,
            leader_srv.url,
            helper_srv.url,
            leader_task.time_precision,
        )
        client = Client.with_fetched_configs(params, vdaf, http, clock=clock)

        # --- sustained upload load: one background uploader running
        # across the whole schedule; every 201-acked measurement is
        # ground truth, wherever the ack came from -------------------
        acked: list[int] = []
        upload_errors: list[str] = []
        stop_uploader = threading.Event()

        def uploader():
            i = 0
            while not stop_uploader.is_set():
                m = (i % 3 != 0) * 1
                try:
                    client.upload(m)
                    acked.append(m)
                except Exception as e:  # shed/refused: NOT ground truth
                    upload_errors.append(f"{type(e).__name__}: {e}")
                i += 1
                stop_uploader.wait(0.04)

        # --- phase 1: healthy, journal armed but idle ----------------
        t0 = time.monotonic()
        for i in range(n_warm):
            client.upload(1)
            acked.append(1)
        result["healthy_upload_ms"] = round(
            (time.monotonic() - t0) / max(1, n_warm) * 1000, 2
        )
        # the armed-but-idle journal must not touch the hot path
        result["healthy_fsyncs"] = journal.fsyncs
        result["healthy_fsyncs_ok"] = journal.fsyncs == 0
        status, body = _http_status(f"http://127.0.0.1:{hp}/readyz")
        result["readyz_up_ok"] = (
            status == 200 and json.loads(body).get("ready") is True
        )
        # jobs created now but NOT stepped: the outage-window driver
        # pass below must park instead of burning their lease attempts
        creator = AggregationJobCreator(
            leader_ds,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=100
            ),
        )
        creator.run_once()

        ut = threading.Thread(target=uploader, daemon=True)
        ut.start()
        time.sleep(6 * 0.04)  # a few sustained-load acks while healthy

        # --- phase 2: kill the datastore under load ------------------
        acked_before_outage = len(acked)
        failpoints.configure(DB_OUTAGE_SCHEDULE)
        deadline = time.monotonic() + 30
        while sup.state != "down" and time.monotonic() < deadline:
            time.sleep(0.02)
        result["supervisor_down_ok"] = sup.state == "down"
        status, body = _http_status(f"http://127.0.0.1:{hp}/readyz")
        try:
            reasons = json.loads(body).get("reasons", {})
        except Exception:
            reasons = {}
        result["readyz_down_status"] = status
        result["readyz_down_ok"] = status == 503 and bool(reasons)
        # aggregate-step routes shed 503 up front while the store is
        # down (the helper would only waste work on a doomed handler)
        tid = base64.urlsafe_b64encode(leader_task.task_id.data).decode().rstrip("=")
        jid = base64.urlsafe_b64encode(secrets.token_bytes(16)).decode().rstrip("=")
        status, _ = _http_status(
            f"{leader_srv.url}tasks/{tid}/aggregation_jobs/{jid}",
            method="PUT",
            body=b"x",
            headers={"Content-Type": AggregationJobInitializeReq.MEDIA_TYPE},
        )
        result["aggregate_shed_status"] = status
        result["aggregate_shed_ok"] = status == 503
        # a driver pass during the outage parks (no acquire, no lease
        # attempts burned) instead of crashing or marching to abandon
        drv = AggregationJobDriver(leader_ds, http)
        jd = JobDriver(
            JobDriverConfig(job_discovery_interval_s=0.1),
            drv.acquirer(60),
            drv.stepper,
        )
        result["driver_parked_ok"] = jd.run_once() == 0
        time.sleep(outage_hold_s)  # sustained load keeps acking into the journal
        depth_during = journal.depth()
        result["journal_depth_during_outage"] = depth_during[0]
        acked_during_outage = len(acked) - acked_before_outage
        result["acked_during_outage"] = acked_during_outage
        result["spilled_acked_ok"] = (
            acked_during_outage > 0 and depth_during[0] > 0
        )

        # --- phase 3: recovery ---------------------------------------
        failpoints.clear()
        deadline = time.monotonic() + 60
        while (
            sup.state != "up" or journal.depth()[0] > 0
        ) and time.monotonic() < deadline:
            time.sleep(0.05)
        result["supervisor_recovered_ok"] = sup.state == "up"
        result["journal_drained_ok"] = journal.depth()[0] == 0
        status, body = _http_status(f"http://127.0.0.1:{hp}/readyz")
        result["readyz_recovered_ok"] = (
            status == 200 and json.loads(body).get("ready") is True
        )
        time.sleep(6 * 0.04)  # a few more sustained-load acks while healthy
        stop_uploader.set()
        ut.join(timeout=30)
        result["admitted"] = len(acked)
        result["ground_truth_sum"] = sum(acked)
        result["upload_errors"] = upload_errors[:5]
        result["uploads_all_acked_ok"] = not upload_errors

        # --- phase 4: aggregate + collect == ground truth ------------
        creator.run_once()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            jd.run_once()
            counts = leader_ds.run_tx(
                lambda tx: tx.count_jobs_by_state(), "dbout_monitor"
            )
            agg = {s: n for (t, s), n in counts.items() if t == "aggregation"}
            if agg.get("in_progress", 0) == 0:
                break
            time.sleep(0.1)
        # absent key = zero jobs in that state (count_jobs_by_state only
        # returns states with rows)
        result["aggregation_done_ok"] = agg.get("in_progress", 0) == 0 and bool(
            agg.get("finished", 0)
        )

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
                stop_collect.wait(0.3)

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
            collected = collector.collect(query, timeout_s=120.0)
            result["collected_count"] = collected.report_count
            result["collected_sum"] = collected.aggregate_result
            # THE invariant: every 201 — healthy, spilled, replayed —
            # exactly once; no loss, no double count
            result["exactly_once_ok"] = (
                collected.report_count == len(acked)
                and collected.aggregate_result == sum(acked)
            )
        finally:
            stop_collect.set()
            ct.join(timeout=10)

        result["journal_fsyncs_total"] = journal.fsyncs
        result["elapsed_s"] = round(time.monotonic() - t_run0, 1)
        result["ok"] = all(v for k, v in result.items() if k.endswith("_ok"))
        return result
    finally:
        failpoints_mod = sys.modules.get("janus_tpu.failpoints")
        if failpoints_mod is not None:
            failpoints_mod.clear()
        unregister_readiness_check("datastore")
        unregister_readiness_check("upload_journal")
        if leader_agg is not None:
            leader_agg.close()
        for srv in (leader_srv, helper_srv):
            if srv is not None:
                srv.stop()
        if health_srv is not None:
            health_srv.stop()
        leader_ds.close()
        helper_ds.close()


def run_device_hang(
    n_reports: int = 5,
    lease_ttl_s: int = 8,
    canary_delay_s: float = 1.5,
    full: bool = False,
    workdir: str | None = None,
) -> dict:
    """Deadline-aware device-path schedule (see module docstring):
    hung dispatch → watchdog abandon within the lease budget → engine
    quarantine → host-fallback serving → canary restore → exactly-once
    collection. Every `*_ok` key must be True to pass."""
    import threading

    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.binary_utils import enable_compile_cache, warmup_engines
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, Datastore
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    import dataclasses

    t_run0 = time.monotonic()
    tmp = workdir or tempfile.mkdtemp(prefix="janus-devhang-")
    os.makedirs(tmp, exist_ok=True)
    key_bytes = secrets.token_bytes(16)
    key = base64.urlsafe_b64encode(key_bytes).decode().rstrip("=")
    clock = RealClock()
    leader_db = os.path.join(tmp, "leader.sqlite")
    leader_ds = Datastore(leader_db, Crypter([key_bytes]), clock)
    helper_ds = Datastore(os.path.join(tmp, "helper.sqlite"), Crypter([key_bytes]), clock)

    result: dict = {
        "workdir": tmp,
        "schedule": "device_hang_full" if full else "device_hang_smoke",
    }
    procs: list[subprocess.Popen] = []
    leader_srv = helper_srv = None
    try:
        helper_srv = DapServer(
            DapHttpApp(Aggregator(helper_ds, clock, Config()))
        ).start()
        leader_srv = DapServer(
            DapHttpApp(Aggregator(leader_ds, clock, Config(collection_retry_after_s=1)))
        ).start()

        vdaf = VdafInstance.count()
        collector_kp = generate_hpke_config_and_private_key(config_id=202)
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
            hpke_keys=(generate_hpke_config_and_private_key(config_id=3),),
        )
        leader_ds.run_tx(lambda tx: tx.put_task(leader_task), "provision")
        helper_ds.run_tx(lambda tx: tx.put_task(helper_task), "provision")
        enable_compile_cache()
        warmup_engines(leader_ds)

        http = HttpClient()
        params = ClientParameters(
            leader_task.task_id, leader_srv.url, helper_srv.url, leader_task.time_precision
        )
        client = Client.with_fetched_configs(params, vdaf, http, clock=clock)
        measurements = [(i % 3 != 0) * 1 for i in range(n_reports)]
        for m in measurements:
            client.upload(m)
        AggregationJobCreator(
            leader_ds,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=100
            ),
        ).run_once()
        result["admitted"] = len(measurements)
        result["ground_truth_sum"] = sum(measurements)

        def held_agg_leases():
            return [
                e
                for e in leader_ds.run_tx(
                    lambda tx: tx.get_held_lease_expiries(), "devhang_monitor"
                )
                if e[0] == "aggregation"
            ]

        def agg_jobs_by_state():
            counts = leader_ds.run_tx(
                lambda tx: tx.count_jobs_by_state(), "devhang_monitor"
            )
            return {
                state: n for (typ, state), n in counts.items() if typ == "aggregation"
            }

        # --- spawn the real driver with the hang armed ------------------
        port = _free_port()
        cfg = _driver_cfg(
            os.path.join(tmp, "driver.yaml"), leader_db, port, int(lease_ttl_s), 1.5
        )
        drv = _spawn_driver(
            cfg,
            key,
            os.path.join(tmp, "driver.log"),
            DEVICE_HANG_SCHEDULE,
            extra_env={
                # fast canary cycle so the quarantine window is short but
                # still reliably observable by the 0.05s poll below
                "JANUS_CANARY_DELAY_S": str(canary_delay_s),
                "JANUS_CANARY_TIMEOUT_S": "30",
            },
        )
        procs.append(drv)
        _wait_healthz(port)

        # --- observe: lease bounded, watchdog + quarantine visible -----
        first_expiry = None
        released_at = None  # wall clock when the FIRST (hung) lease left
        quarantined_seen = False
        quarantined_at = None  # monotonic when quarantine first observed
        stalled_stack_seen = False
        abandoned_max = 0.0
        cap = None
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            leases = held_agg_leases()
            now_wall = clock.now().seconds
            if leases and first_expiry is None:
                first_expiry = leases[0][3]
                result["first_lease_expiry"] = first_expiry
            if (
                first_expiry is not None
                and released_at is None
                and not any(e[3] == first_expiry for e in leases)
            ):
                released_at = now_wall
            try:
                mtext = _scrape(port, "/metrics")
                backend = _metric_samples(mtext, "janus_engine_backend")
                if backend.get('state="quarantined",vdaf="count"') == 1.0:
                    if not quarantined_seen:
                        quarantined_at = time.monotonic()
                    quarantined_seen = True
                ab = _metric_samples(mtext, "janus_abandoned_dispatch_threads")
                abandoned_max = max(abandoned_max, *(ab.values() or [0.0]))
                statusz = json.loads(_scrape(port, "/statusz"))
                wd = statusz.get("device_watchdog", {})
                cap = wd.get("abandoned_thread_cap", cap)
                for ent in wd.get("stalled", []):
                    if ent.get("stack"):
                        stalled_stack_seen = True
            except Exception:
                pass  # scrape raced the driver's own work; retry next poll
            states = agg_jobs_by_state()
            if states.get("in_progress", 0) == 0 and states.get("finished", 0) >= 1:
                break
            time.sleep(0.05)

        states = agg_jobs_by_state()
        result["job_finished_ok"] = (
            states.get("finished", 0) >= 1 and states.get("in_progress", 0) == 0
        )
        # THE lease-bound invariant: the hung step released its lease
        # (stepped back) BEFORE the lease expired — the wedge never
        # outlives the lease and runs concurrently with a re-acquirer.
        # (+1s margin covers the 0.05s poll + second-granularity clock.)
        result["hung_lease_released_at"] = released_at
        result["lease_bounded_ok"] = (
            first_expiry is not None
            and released_at is not None
            and released_at <= first_expiry + 1
        )
        result["quarantined_observed_ok"] = quarantined_seen
        result["stalled_stack_ok"] = stalled_stack_seen
        result["abandoned_max"] = abandoned_max
        result["abandoned_under_cap_ok"] = (
            abandoned_max >= 1.0 and cap is not None and abandoned_max < cap
        )

        # --- wait for the canary to restore the device path (the job
        # usually finishes on host fallback BEFORE the canary's
        # cool-down elapses; the restore is observed live) ------------
        restore_deadline = time.monotonic() + 60
        restored_at = None
        mtext = _scrape(port, "/metrics")
        while time.monotonic() < restore_deadline:
            mtext = _scrape(port, "/metrics")
            quar = _metric_samples(mtext, "janus_engine_quarantines_total")
            if sum(v for k, v in quar.items() if 'event="restored"' in k) >= 1:
                restored_at = time.monotonic()
                break
            time.sleep(0.1)

        # warm canary restore (ISSUE 14): with the persistent compile
        # cache on (driver YAML) the canary's recompile+probe is a disk
        # load, so quarantine-open -> restored must be FAST — the
        # canary cool-down plus a bounded warm recompile, nothing like
        # the cold multi-minute rebuild this scenario used to tolerate.
        # 20s leaves CI headroom over the ~1.5s cool-down + warm probe.
        restore_elapsed = (
            None
            if quarantined_at is None or restored_at is None
            else restored_at - quarantined_at
        )
        result["restore_elapsed_s"] = (
            round(restore_elapsed, 2) if restore_elapsed is not None else None
        )
        result["restore_warm_ok"] = (
            restore_elapsed is not None and restore_elapsed <= 20.0
        )

        # --- steady state: restored to device, counters tell the story --
        hung = _metric_samples(mtext, "janus_hung_dispatches_total")
        result["hung_dispatches"] = hung
        result["hung_dispatch_ok"] = sum(hung.values()) >= 1
        step_backs = _metric_samples(mtext, "janus_job_step_back_total")
        result["step_backs"] = step_backs
        result["stepped_back_device_hang_ok"] = (
            sum(v for k, v in step_backs.items() if "device_hang" in k) >= 1
        )
        quar = _metric_samples(mtext, "janus_engine_quarantines_total")
        result["quarantine_events"] = quar
        result["quarantine_cycle_ok"] = (
            sum(v for k, v in quar.items() if 'event="open"' in k) >= 1
            and sum(v for k, v in quar.items() if 'event="restored"' in k) >= 1
        )
        backend = _metric_samples(mtext, "janus_engine_backend")
        result["restored_ok"] = (
            backend.get('state="device",vdaf="count"') == 1.0
            and backend.get('state="quarantined",vdaf="count"') == 0.0
        )
        statusz = json.loads(_scrape(port, "/statusz"))
        result["statusz_watchdog_ok"] = (
            statusz.get("device_watchdog", {}).get("hung_dispatches_total", 0) >= 1
        )

        # --- SIGTERM drain (release_hangs unparks the modeled wedge) ----
        drv.send_signal(signal.SIGTERM)
        rc = drv.wait(timeout=60)
        log_text = open(os.path.join(tmp, "driver.log"), "rb").read()
        result["drain_rc"] = rc
        result["drain_ok"] = rc == 0 and b"shut down" in log_text

        # --- collect and compare against ground truth -------------------
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
                stop_collect.wait(0.3)

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
            collected = collector.collect(query, timeout_s=120.0)
            result["collected_count"] = collected.report_count
            result["collected_sum"] = collected.aggregate_result
            # interim work landed through the host fallback, restored
            # work on device — and every admitted report exactly once
            result["exactly_once_ok"] = (
                collected.report_count == len(measurements)
                and collected.aggregate_result == sum(measurements)
            )
        finally:
            stop_collect.set()
            ct.join(timeout=10)

        result["elapsed_s"] = round(time.monotonic() - t_run0, 1)
        result["ok"] = all(v for k, v in result.items() if k.endswith("_ok"))
        return result
    finally:
        failpoints_mod = sys.modules.get("janus_tpu.failpoints")
        if failpoints_mod is not None:
            failpoints_mod.clear()
        for p in procs:
            if p.poll() is None:
                p.kill()
        if leader_srv is not None:
            leader_srv.stop()
        if helper_srv is not None:
            helper_srv.stop()
        leader_ds.close()
        helper_ds.close()


def run_cold_start(
    pairs: int = 1,
    full: bool = False,
    warm_budget_s: float = 10.0,
    workdir: str | None = None,
) -> dict:
    """Cold-start A/B (ISSUE 14): interleaved cold-cache vs warm-cache
    boots of the REAL driver binary, restart-to-first-dispatch measured
    via /debug/boot (phase sums proven exact by the boot-timeline
    tests). Both boots replay the SAME shape manifest through the AOT
    prewarm engine before /readyz flips ready — so ready means "every
    recorded specialization compiled", and the boot total IS the
    restart-to-first-dispatch number (the first real dispatch after
    ready runs an already-compiled program). The only difference
    between the two boots is the persistent XLA compile cache: empty
    (cold — every specialization pays trace + XLA compile) vs populated
    by the cold boot (warm — trace + disk load).

    Gates: warm restart-to-first-dispatch under `warm_budget_s` (the
    ROADMAP item 1 target: 10 s), warm at least 1.5x (smoke) / 3x
    (full) faster than cold, prewarm observed live on the warm boot
    (janus_engine_prewarm_total warmed > 0 AND statusz engine_prewarm
    cache hits > 0), and /debug/boot carrying the engine_warm_manifest
    sub-phase with ready only after the prewarm set compiled."""
    from janus_tpu.aggregator.shape_manifest import ShapeManifest
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, Datastore
    from janus_tpu.messages import Role
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    t_run0 = time.monotonic()
    tmp = workdir or tempfile.mkdtemp(prefix="janus-coldstart-")
    os.makedirs(tmp, exist_ok=True)
    key_bytes = secrets.token_bytes(16)
    key = base64.urlsafe_b64encode(key_bytes).decode().rstrip("=")
    db = os.path.join(tmp, "leader.sqlite")
    ds = Datastore(db, Crypter([key_bytes]), RealClock())
    result: dict = {"workdir": tmp, "schedule": "cold_start", "pairs": pairs}

    # two provisioned tasks with distinct circuits, so the manifest's
    # recorded geometry spans real production variety (count is the
    # cheap compile, histogram carries joint randomness and costs more
    # — its cold trace+compile is the 6-17 s/program class). The smoke
    # drops histogram to keep the tier-1 wall time bounded; the full
    # record (bench --mode served / standalone) measures both.
    insts = (
        (VdafInstance.count(), VdafInstance.histogram(length=4))
        if full
        else (VdafInstance.count(),)
    )
    for i, inst in enumerate(insts):
        task = (
            TaskBuilder(QueryTypeConfig.time_interval(), inst, Role.LEADER)
            .with_(
                collector_hpke_config=generate_hpke_config_and_private_key(
                    config_id=30 + i
                ).config,
            )
            .build()
        )
        ds.run_tx(lambda tx, t=task: tx.put_task(t), "provision")
    ds.close()

    # the manifest both boots replay: every (op, bucket) specialization
    # a serving driver observes on these two tasks — exactly what a
    # production restart finds on disk. Costs are descending so the
    # priority order is deterministic.
    def seed_manifest(path: str) -> int:
        man = ShapeManifest(path)
        n = 0
        for inst in insts:
            for b in (32, 64, 128):
                for op in ("leader_init", "helper_init", "aggregate"):
                    man.record(inst.to_dict(), op, b, (op, b), float(b) / 10, rows=b)
                    n += 1
            man.record(
                inst.to_dict(), "aggregate_pending", 64,
                ("aggregate_pending", 8, 64), 3.0, rows=64,
            )
            n += 1
        return n

    def one_boot(idx: int, label: str, cache_dir: str, manifest: str) -> dict:
        port = _free_port()
        cfg = _driver_cfg(
            os.path.join(tmp, f"driver-{idx}-{label}.yaml"),
            db,
            port,
            600,
            1.5,
            extra="engine:\n  prewarm_boot_budget_secs: 300\n",
        )
        # the A/B needs its own cold cache per pair, so it is the one
        # place that points the binary at another cache directory
        drv = _spawn_driver(
            cfg,
            key,
            os.path.join(tmp, f"driver-{idx}-{label}.log"),
            None,
            extra_env={"JANUS_SHAPE_MANIFEST": manifest, "JAX_COMPILATION_CACHE_DIR": cache_dir},
        )
        boot: dict = {"label": label}
        try:
            _wait_healthz(port, deadline_s=600.0)
            deadline = time.monotonic() + 60
            doc = {}
            while time.monotonic() < deadline:
                doc = json.loads(_scrape(port, "/debug/boot"))
                if doc.get("ready"):
                    break
                time.sleep(0.1)
            boot["ready_ok"] = bool(doc.get("ready"))
            boot["total_s"] = doc.get("total_s")
            boot["phases"] = {
                p["phase"]: p["seconds"] for p in doc.get("phases", [])
            }
            boot["manifest_phase_ok"] = "engine_warm_manifest" in boot["phases"]
            mtext = _scrape(port, "/metrics")
            pw = _metric_samples(mtext, "janus_engine_prewarm_total")
            boot["prewarm_total"] = pw
            boot["warmed"] = sum(
                v for k, v in pw.items() if 'outcome="warmed"' in k
            )
            statusz = json.loads(_scrape(port, "/statusz"))
            ep = statusz.get("engine_prewarm", {})
            boot["cache_hits"] = ep.get("prewarm", {}).get("cache_hits", 0)
            boot["cache_misses"] = ep.get("prewarm", {}).get("cache_misses", 0)
            boot["manifest_entries"] = ep.get("manifest", {}).get("entries", 0)
            boot["aot_loads"] = ep.get("aot", {}).get("loads", 0)
            boot["aot_saves"] = ep.get("aot", {}).get("saves", 0)
            drv.send_signal(signal.SIGTERM)
            boot["drain_rc"] = drv.wait(timeout=60)
        finally:
            if drv.poll() is None:
                drv.kill()
        return boot

    boots: list[dict] = []
    try:
        for i in range(pairs):
            cache_dir = os.path.join(tmp, f"xla-cache-{i}")
            manifest = os.path.join(tmp, f"shape-manifest-{i}.jsonl")
            result["manifest_seeded_entries"] = seed_manifest(manifest)
            # interleaved: cold then warm on the same (cache, manifest)
            # pair — the warm boot reads exactly what the cold one wrote
            boots.append(one_boot(i, "cold", cache_dir, manifest))
            boots.append(one_boot(i, "warm", cache_dir, manifest))
        result["boots"] = boots
        colds = [b for b in boots if b["label"] == "cold"]
        warms = [b for b in boots if b["label"] == "warm"]
        ok_shape = all(
            b.get("ready_ok") and b.get("total_s") is not None for b in boots
        )
        result["boots_ready_ok"] = ok_shape
        if ok_shape:
            cold_s = sorted(b["total_s"] for b in colds)[len(colds) // 2]
            warm_s = sorted(b["total_s"] for b in warms)[len(warms) // 2]
            result["cold_restart_to_first_dispatch_s"] = round(cold_s, 3)
            result["warm_restart_to_first_dispatch_s"] = round(warm_s, 3)
            result["speedup"] = round(cold_s / max(1e-9, warm_s), 2)
            # THE acceptance numbers (ISSUE 14 / ROADMAP item 1): warm
            # restart under 10 s, and >= 3x faster than cold (the full
            # record gate; the tier-1 smoke gates 1.5x so a CPU-starved
            # CI run cannot flake a real regression signal)
            result["warm_under_budget_ok"] = warm_s < warm_budget_s
            result["speedup_gate"] = 3.0 if full else 1.5
            result["speedup_ok"] = result["speedup"] >= result["speedup_gate"]
            result["manifest_phase_ok"] = all(
                b.get("manifest_phase_ok") for b in boots
            )
            result["prewarm_observed_ok"] = all(
                b.get("warmed", 0) >= result["manifest_seeded_entries"]
                for b in boots
            )
            result["warm_cache_hits_ok"] = all(
                b.get("cache_hits", 0) > 0 for b in warms
            )
            result["cold_cache_misses_ok"] = all(
                b.get("cache_misses", 0) > 0 for b in colds
            )
            # the AOT executable layer: cold boots SERIALIZE compiled
            # programs, warm boots LOAD them (no re-trace)
            result["cold_aot_saves_ok"] = all(
                b.get("aot_saves", 0) > 0 for b in colds
            )
            result["warm_aot_loads_ok"] = all(
                b.get("aot_loads", 0) > 0 for b in warms
            )
            result["drain_ok"] = all(b.get("drain_rc") == 0 for b in boots)
        result["elapsed_s"] = round(time.monotonic() - t_run0, 1)
        result["ok"] = bool(boots) and all(
            v for k, v in result.items() if k.endswith("_ok")
        )
        return result
    finally:
        # one_boot() kills any straggler in its own finally; the
        # workdir (sqlite, caches, logs) is kept for postmortems like
        # every other scenario's
        pass


def _histogram_counts(text: str, name: str) -> dict[str, float]:
    """{label_block: value} of a histogram family's _count samples."""
    from janus_tpu.exposition import parse_exposition

    fam = parse_exposition(text)[0].get(name)
    if fam is None:
        return {}
    return {
        ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())): float(value)
        for sample_name, labels, value in fam.samples
        if sample_name == name + "_count"
    }


def run_pipeline(
    n_reports: int = 24,
    job_size: int = 3,
    lease_ttl_s: int = 60,
    full: bool = False,
    workdir: str | None = None,
) -> dict:
    """Stage-pipeline overlap proof (ISSUE 9): the REAL driver binary —
    pipelined stepper enabled via its YAML `step_pipeline:` stanza —
    steps many small jobs against a loopback helper whose RTT is
    stretched by a `helper.request=delay` failpoint. Asserts the
    overlap actually happened (the device lane ran while an HTTP leg
    was in flight: janus_step_pipeline_overlap_total > 0 and a
    recorded overlap ratio > 0), every pipeline stage executed
    (stage-seconds counts for read/device/http/commit), the device-lane
    busy ratio is live, SIGTERM drains rc 0, and the final collection
    equals the admitted ground truth exactly — the pipeline never loses
    or double-steps a job. Every `*_ok` key must be True to pass."""
    import threading

    import dataclasses

    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.binary_utils import enable_compile_cache, warmup_engines
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, Datastore
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    t_run0 = time.monotonic()
    tmp = workdir or tempfile.mkdtemp(prefix="janus-pipeline-")
    os.makedirs(tmp, exist_ok=True)
    key_bytes = secrets.token_bytes(16)
    key = base64.urlsafe_b64encode(key_bytes).decode().rstrip("=")
    clock = RealClock()
    leader_db = os.path.join(tmp, "leader.sqlite")
    leader_ds = Datastore(leader_db, Crypter([key_bytes]), clock)
    helper_ds = Datastore(os.path.join(tmp, "helper.sqlite"), Crypter([key_bytes]), clock)

    result: dict = {
        "workdir": tmp,
        "schedule": "pipeline_full" if full else "pipeline_smoke",
    }
    procs: list[subprocess.Popen] = []
    leader_srv = helper_srv = None
    try:
        helper_srv = DapServer(
            DapHttpApp(Aggregator(helper_ds, clock, Config()))
        ).start()
        leader_srv = DapServer(
            DapHttpApp(Aggregator(leader_ds, clock, Config(collection_retry_after_s=1)))
        ).start()

        vdaf = VdafInstance.count()
        collector_kp = generate_hpke_config_and_private_key(config_id=204)
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
            hpke_keys=(generate_hpke_config_and_private_key(config_id=3),),
        )
        leader_ds.run_tx(lambda tx: tx.put_task(leader_task), "provision")
        helper_ds.run_tx(lambda tx: tx.put_task(helper_task), "provision")
        enable_compile_cache()
        warmup_engines(leader_ds, batch=job_size)

        http = HttpClient()
        params = ClientParameters(
            leader_task.task_id, leader_srv.url, helper_srv.url, leader_task.time_precision
        )
        client = Client.with_fetched_configs(params, vdaf, http, clock=clock)
        measurements = [(i % 3 != 0) * 1 for i in range(n_reports)]
        for m in measurements:
            client.upload(m)
        # many SMALL jobs: the pipeline needs several concurrently
        # leased steps for its stages to interleave
        AggregationJobCreator(
            leader_ds,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=job_size
            ),
        ).run_once()
        result["admitted"] = len(measurements)
        result["ground_truth_sum"] = sum(measurements)
        result["jobs_created"] = (n_reports + job_size - 1) // job_size

        def agg_jobs_by_state():
            counts = leader_ds.run_tx(
                lambda tx: tx.count_jobs_by_state(), "pipeline_monitor"
            )
            return {
                state: n for (typ, state), n in counts.items() if typ == "aggregation"
            }

        # --- spawn the real driver: pipelined stepper via YAML ----------
        port = _free_port()
        cfg = _driver_cfg(
            os.path.join(tmp, "driver.yaml"),
            leader_db,
            port,
            int(lease_ttl_s),
            1.5,
            extra=(
                "max_concurrent_job_workers: 4\n"
                "step_pipeline:\n"
                "  enabled: true\n"
                "  prefetch_depth: 2\n"
                "  http_inflight: 2\n"
                "  commit_inflight: 2\n"
            ),
        )
        drv = _spawn_driver(
            cfg, key, os.path.join(tmp, "driver.log"), PIPELINE_RTT_SCHEDULE
        )
        procs.append(drv)
        _wait_healthz(port)

        # --- wait for all jobs to finish, scraping the pipeline live ----
        deadline = time.monotonic() + 180
        mtext = ""
        while time.monotonic() < deadline:
            states = agg_jobs_by_state()
            if states.get("in_progress", 0) == 0 and states.get("finished", 0) >= result[
                "jobs_created"
            ]:
                break
            time.sleep(0.1)
        states = agg_jobs_by_state()
        result["job_states"] = states
        result["jobs_finished_ok"] = (
            states.get("finished", 0) >= result["jobs_created"]
            and states.get("in_progress", 0) == 0
        )

        mtext = _scrape(port, "/metrics")
        overlap = _metric_samples(mtext, "janus_step_pipeline_overlap_total")
        result["overlapped_dispatches"] = sum(overlap.values())
        result["overlap_ok"] = result["overlapped_dispatches"] >= 1
        busy = _metric_samples(mtext, "janus_device_lane_busy_ratio")
        result["device_lane_busy_ratio"] = max(busy.values() or [0.0])
        result["device_lane_busy_ok"] = result["device_lane_busy_ratio"] > 0
        stage_counts = _histogram_counts(mtext, "janus_step_pipeline_stage_seconds")
        result["stage_seconds_counts"] = stage_counts
        result["stages_executed_ok"] = all(
            any(f'stage="{s}"' in k and v > 0 for k, v in stage_counts.items())
            for s in ("read", "device", "http", "commit")
        )
        statusz = json.loads(_scrape(port, "/statusz"))
        sp = statusz.get("step_pipeline", {})
        result["statusz_overlap_ratio"] = sp.get("overlap_ratio", 0)
        result["statusz_overlap_events"] = sp.get("overlap_events", 0)
        result["statusz_pipeline_ok"] = (
            sp.get("jobs_done", 0) >= result["jobs_created"]
            and sp.get("overlap_events", 0) > 0
            and sp.get("device_lane", {}).get("concurrent_peak", 99) <= 1
        )

        # --- SIGTERM drain ---------------------------------------------
        drv.send_signal(signal.SIGTERM)
        rc = drv.wait(timeout=60)
        log_text = open(os.path.join(tmp, "driver.log"), "rb").read()
        result["drain_rc"] = rc
        result["drain_ok"] = rc == 0 and b"shut down" in log_text

        # --- collect and compare against ground truth -------------------
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
                stop_collect.wait(0.3)

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
            collected = collector.collect(query, timeout_s=120.0)
            result["collected_count"] = collected.report_count
            result["collected_sum"] = collected.aggregate_result
            result["exactly_once_ok"] = (
                collected.report_count == len(measurements)
                and collected.aggregate_result == sum(measurements)
            )
        finally:
            stop_collect.set()
            ct.join(timeout=10)

        result["elapsed_s"] = round(time.monotonic() - t_run0, 1)
        result["ok"] = all(v for k, v in result.items() if k.endswith("_ok"))
        return result
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        if leader_srv is not None:
            leader_srv.stop()
        if helper_srv is not None:
            helper_srv.stop()
        leader_ds.close()
        helper_ds.close()


# --scenario resident: the first four SERVING device dispatches (two
# count tasks x leader_init + masked-delta) land clean, the FIFTH
# wedges forever — quarantining the engine while earlier jobs'
# aggregate state sits resident in device memory; two canary probes
# fail to hold the quarantine window open long enough to observe the
# flush live. The driver's boot warmup dispatches don't shift the
# anchor: warmup runs under failpoints.suppressed()
RESIDENT_SCHEDULE = "engine.dispatch=hang,count=1,after=4;engine.canary=error:1.0,count=2"


def run_resident(
    wave_sizes: tuple = (3, 3, 4, 3),
    lease_ttl_s: int = 6,
    full: bool = False,
    workdir: str | None = None,
) -> dict:
    """Resident aggregate state flush contract (docs/ARCHITECTURE.md
    "Resident aggregate state") against the REAL driver binary with
    `resident_accumulators` enabled and an 8-byte `resident_max_bytes`
    (one count slot). Deterministic schedule:

      1. two tasks (A, B) each land one job resident; task B's merge
         overflows the byte cap and LRU-EVICTS task A's slot through
         the flush path (reason="eviction") — observed live;
      2. task A's next job wedges on its device dispatch
         (engine.dispatch hang, after=4) → watchdog abandon →
         quarantine; the flusher's quarantine sweep writes task B's
         resident slot out (reason="quarantine") while the wedged job
         re-steps through the interim host engine;
      3. after the canary restores the device path, one more task-A
         job lands resident; SIGTERM drains it through the write-tx
         path (drain contract) and the final collections equal ALL
         tasks' admitted ground truths exactly — no share bytes lost
         across eviction, quarantine, or drain.

    A block-sparse sumvec task ("s", ISSUE 17) rides the same run: its
    first wave uploads inside the quarantine window (the sparse engine
    keeps dispatching while the count engine is wedged), its logical
    len-48 slot always overflows the 8-byte cap so every merge exits
    through the eviction flush, a second wave rides the restore->drain
    window, and its collection must equal the dense expansion of the
    admitted (block, values) pairs exactly — with the scatter row
    counter proving the gather/scatter kernel carried the deltas.

    wave_sizes: (task A wave 1, task B wave 1, task A hang wave,
    task A drain wave). Every `*_ok` key must be True to pass."""
    import threading

    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.binary_utils import enable_compile_cache, warmup_engines
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, Datastore
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    import dataclasses

    t_run0 = time.monotonic()
    tmp = workdir or tempfile.mkdtemp(prefix="janus-resident-")
    os.makedirs(tmp, exist_ok=True)
    key_bytes = secrets.token_bytes(16)
    key = base64.urlsafe_b64encode(key_bytes).decode().rstrip("=")
    clock = RealClock()
    leader_db = os.path.join(tmp, "leader.sqlite")
    leader_ds = Datastore(leader_db, Crypter([key_bytes]), clock)
    helper_ds = Datastore(os.path.join(tmp, "helper.sqlite"), Crypter([key_bytes]), clock)

    result: dict = {
        "workdir": tmp,
        "schedule": "resident_full" if full else "resident_smoke",
    }
    procs: list[subprocess.Popen] = []
    leader_srv = helper_srv = None
    try:
        helper_srv = DapServer(
            DapHttpApp(Aggregator(helper_ds, clock, Config()))
        ).start()
        leader_srv = DapServer(
            DapHttpApp(Aggregator(leader_ds, clock, Config(collection_retry_after_s=1)))
        ).start()

        vdaf = VdafInstance.count()
        # ISSUE 17: a block-sparse task rides the same chaos phases as
        # the count tasks — its 768-byte slot always overflows the
        # 8-byte cap, so every merge exits through the eviction flush
        # path, and collection must still be exact
        sparse_vdaf = VdafInstance.sparse_sumvec(
            bits=3, length=48, block_size=4, max_blocks=3
        )
        tasks = {}
        for name, cfg_id, task_vdaf in (
            ("a", 210, vdaf),
            ("b", 211, vdaf),
            ("s", 212, sparse_vdaf),
        ):
            collector_kp = generate_hpke_config_and_private_key(config_id=cfg_id)
            leader_task = (
                TaskBuilder(QueryTypeConfig.time_interval(), task_vdaf, Role.LEADER)
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
                hpke_keys=(generate_hpke_config_and_private_key(config_id=4),),
            )
            leader_ds.run_tx(lambda tx, t=leader_task: tx.put_task(t), "provision")
            helper_ds.run_tx(lambda tx, t=helper_task: tx.put_task(t), "provision")
            tasks[name] = (leader_task, collector_kp, task_vdaf)
        # warm into the driver's persistent cache dir (the same default
        # resolution the binary uses) so the subprocess loads compiled programs from disk instead of
        # paying cold compiles against the lease watchdog: the sparse
        # leader_init compile alone (~15 s on CPU) would wedge past the
        # 6 s budget and spuriously quarantine the sparse engine
        enable_compile_cache()
        warmup_engines(leader_ds)

        creator = AggregationJobCreator(
            leader_ds,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=100
            ),
        )
        truth = {"a": [], "b": [], "s": []}

        def upload(task_name: str, measurements) -> None:
            leader_task, _, task_vdaf = tasks[task_name]
            http = HttpClient()
            params = ClientParameters(
                leader_task.task_id, leader_srv.url, helper_srv.url,
                leader_task.time_precision,
            )
            client = Client.with_fetched_configs(params, task_vdaf, http, clock=clock)
            for m in measurements:
                client.upload(m)
            truth[task_name].extend(measurements)
            creator.run_once()

        def finished_jobs() -> int:
            counts = leader_ds.run_tx(
                lambda tx: tx.count_jobs_by_state(), "resident_monitor"
            )
            return sum(
                n
                for (typ, state), n in counts.items()
                if typ == "aggregation" and state == "finished"
            )

        def flush_samples(mtext: str) -> dict:
            return _metric_samples(mtext, "janus_engine_resident_flushes_total")

        # --- spawn the real driver: resident mode on, interval flush
        # effectively off (3600 s) so every flush observed below is an
        # EVICTION, QUARANTINE, or DRAIN flush — never the timer ------
        port = _free_port()
        cfg = _driver_cfg(
            os.path.join(tmp, "driver.yaml"),
            leader_db,
            port,
            int(lease_ttl_s),
            1.5,
            extra=(
                "resident_accumulators:\n"
                "  enabled: true\n"
                "  flush_interval_secs: 3600\n"
                "engine:\n"
                "  resident_max_bytes: 8\n"  # exactly ONE count slot
                # blocking engine warmup BEFORE the health listener: the
                # sparse leader_init/scatter compiles must not race the
                # lease watchdog mid-phase (the in-process warmup above
                # seeds the shared compile cache, so boot pays disk
                # loads, not cold compiles)
                "warmup_engines_at_boot: true\n"
            ),
        )
        drv = _spawn_driver(
            cfg,
            key,
            os.path.join(tmp, "driver.log"),
            RESIDENT_SCHEDULE,
            extra_env={
                "JANUS_CANARY_DELAY_S": "1.5",
                "JANUS_CANARY_TIMEOUT_S": "30",
            },
        )
        procs.append(drv)
        _wait_healthz(port)

        # --- phase 1: task A then task B land resident; B's merge
        # LRU-evicts A's slot through the flush path ------------------
        upload("a", [1, 0, 1][: wave_sizes[0]] or [1])
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and finished_jobs() < 1:
            time.sleep(0.05)
        upload("b", [1, 1, 0][: wave_sizes[1]] or [1])
        eviction_seen = False
        while time.monotonic() < deadline and not eviction_seen:
            if finished_jobs() >= 2:
                samples = flush_samples(_scrape(port, "/metrics"))
                eviction_seen = (
                    samples.get('outcome="flushed",reason="eviction"', 0) >= 1
                )
            time.sleep(0.05)
        result["eviction_flush_ok"] = eviction_seen

        # --- phase 2: task A's next job wedges (hang armed after=4) ->
        # quarantine; the flusher sweep flushes B's slot live ---------
        upload("a", [1, 1, 1, 0][: wave_sizes[2]] or [1])
        quarantined_seen = False
        quarantine_flush_seen = False
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            try:
                mtext = _scrape(port, "/metrics")
            except Exception:
                time.sleep(0.1)
                continue
            backend = _metric_samples(mtext, "janus_engine_backend")
            if backend.get('state="quarantined",vdaf="count"') == 1.0:
                quarantined_seen = True
            samples = flush_samples(mtext)
            if samples.get('outcome="flushed",reason="quarantine"', 0) >= 1:
                quarantine_flush_seen = True
            if quarantine_flush_seen and finished_jobs() >= 3:
                break
            time.sleep(0.05)
        result["quarantined_observed_ok"] = quarantined_seen
        result["quarantine_flush_ok"] = quarantine_flush_seen
        step_backs = _metric_samples(
            _scrape(port, "/metrics"), "janus_job_step_back_total"
        )
        result["stepped_back_device_hang_ok"] = (
            sum(v for k, v in step_backs.items() if "device_hang" in k) >= 1
        )

        # --- sparse wave 1: uploaded inside the quarantine window (the
        # count engine is still wedged; the sparse engine dispatches on
        # its own device path).  Its 768-byte slot overflows the 8-byte
        # cap at merge time, so the state exits through the EVICTION
        # flush — observed via the flush counter delta plus the scatter
        # row counter proving the gather/scatter kernel ran (ISSUE 17)
        pre_sparse_evictions = flush_samples(_scrape(port, "/metrics")).get(
            'outcome="flushed",reason="eviction"', 0
        )
        upload(
            "s",
            [
                [(0, [1, 2, 3, 4]), (5, [7, 0, 1, 2])],
                [(0, [0, 1, 0, 1]), (3, [2, 2, 2, 2]), (11, [5, 0, 0, 6])],
            ],
        )
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and finished_jobs() < 4:
            time.sleep(0.05)

        # --- phase 3: canary restores the device path; one more job
        # lands resident and SIGTERM drains it ------------------------
        restore_deadline = time.monotonic() + 90
        while time.monotonic() < restore_deadline:
            backend = _metric_samples(
                _scrape(port, "/metrics"), "janus_engine_backend"
            )
            if backend.get('state="device",vdaf="count"') == 1.0:
                break
            time.sleep(0.1)
        result["restored_ok"] = backend.get('state="device",vdaf="count"') == 1.0
        # sparse wave 2 rides the restore->drain window; it merges (and
        # self-evicts through the flush path) BEFORE task A's final job
        # lands resident, so the LRU sweep cannot evict A's slot and
        # the drain contract below stays deterministic
        upload("s", [[(2, [1, 0, 0, 3]), (7, [0, 4, 0, 0])]])
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and finished_jobs() < 5:
            time.sleep(0.05)
        upload("a", [0, 1, 1][: wave_sizes[3]] or [1])
        resident_before_drain = 0
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if finished_jobs() >= 6:
                statusz = json.loads(_scrape(port, "/statusz"))
                ra = statusz.get("resident_accumulators", {})
                resident_before_drain = sum(
                    e.get("buffers", 0) for e in ra.get("engines", [])
                )
                if resident_before_drain >= 1:
                    result["statusz_resident_bytes"] = ra.get("total_bytes")
                    break
            time.sleep(0.05)
        result["resident_before_drain_ok"] = resident_before_drain >= 1

        mtext = _scrape(port, "/metrics")
        samples = flush_samples(mtext)
        result["flush_samples"] = samples
        result["no_lost_flushes_ok"] = not any(
            'outcome="lost"' in k and v > 0 for k, v in samples.items()
        )
        # sparse ride-along (ISSUE 17), judged cumulatively before the
        # drain: the count choreography contributes exactly ONE
        # eviction flush, so any excess over the pre-sparse count is
        # the sparse slot exiting through the eviction path, and the
        # scatter row counter proves the gather/scatter kernel (not a
        # dense or host detour) carried the sparse deltas
        scatter_samples = _metric_samples(
            mtext, "janus_engine_scatter_rows_total"
        )
        result["sparse_scatter_rows"] = sum(scatter_samples.values())
        result["sparse_scatter_observed_ok"] = (
            scatter_samples.get('vdaf="sparse_sumvec"', 0) > 0
        )
        result["sparse_eviction_flush_ok"] = (
            samples.get('outcome="flushed",reason="eviction"', 0)
            > pre_sparse_evictions
        )
        hd = _metric_samples(mtext, "janus_engine_hd_bytes_total")
        result["hd_bytes"] = hd
        result["hd_bytes_ok"] = (
            sum(v for k, v in hd.items() if 'direction="h2d"' in k) > 0
        )

        # --- SIGTERM drain: the resident remainder flushes through the
        # write-tx path before exit (collection proves it landed) -----
        drv.send_signal(signal.SIGTERM)
        rc = drv.wait(timeout=60)
        log_text = open(os.path.join(tmp, "driver.log"), "rb").read()
        result["drain_rc"] = rc
        result["drain_ok"] = rc == 0 and b"shut down" in log_text

        # --- collect BOTH tasks and compare against ground truth -----
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
                stop_collect.wait(0.3)

        ct = threading.Thread(target=collect_loop, daemon=True)
        ct.start()
        try:
            for name in ("a", "b", "s"):
                leader_task, collector_kp, task_vdaf = tasks[name]
                collector = Collector(
                    CollectorParameters(
                        leader_task.task_id,
                        leader_srv.url,
                        leader_task.collector_auth_token,
                        collector_kp,
                    ),
                    task_vdaf,
                    HttpClient(),
                )
                tp = leader_task.time_precision
                start = clock.now().to_batch_interval_start(tp)
                query = Query.time_interval(
                    Interval(Time(start.seconds - tp.seconds), Duration(3 * tp.seconds))
                )
                collected = collector.collect(query, timeout_s=120.0)
                if name == "s":
                    # ground truth at the LOGICAL length: expand every
                    # (block, values) pair onto the dense vector
                    want = [0] * sparse_vdaf.length
                    for m in truth["s"]:
                        for blk, vals in m:
                            for j, v in enumerate(vals):
                                want[blk * sparse_vdaf.block_size + j] += v
                    got = list(collected.aggregate_result)
                else:
                    want = sum(truth[name])
                    got = collected.aggregate_result
                result[f"collected_count_{name}"] = collected.report_count
                result[f"collected_sum_{name}"] = got
                result[f"exactly_once_{name}_ok"] = (
                    collected.report_count == len(truth[name]) and got == want
                )
                result[f"admitted_{name}"] = len(truth[name])
                result[f"ground_truth_sum_{name}"] = want
        finally:
            stop_collect.set()
            ct.join(timeout=10)

        result["elapsed_s"] = round(time.monotonic() - t_run0, 1)
        result["ok"] = all(v for k, v in result.items() if k.endswith("_ok"))
        return result
    finally:
        failpoints_mod = sys.modules.get("janus_tpu.failpoints")
        if failpoints_mod is not None:
            failpoints_mod.clear()
        for p in procs:
            if p.poll() is None:
                p.kill()
        if leader_srv is not None:
            leader_srv.stop()
        if helper_srv is not None:
            helper_srv.stop()
        leader_ds.close()
        helper_ds.close()


def claim_roundtrip_stats(n_jobs: int = 32, batch: int = 16) -> dict:
    """Claim round-trips per job, measured not assumed (ISSUE 15): the
    batched claim transaction vs a reimplementation of the old per-row
    loop, both over the recorded-conversation pg_fake driver so every
    statement is counted exactly as it would hit the PG wire. The
    batched form issues ONE statement per claim transaction; the
    per-row loop issued 1 SELECT + K guarded UPDATEs."""
    import secrets as _secrets

    from janus_tpu.core.time_util import MockClock
    from janus_tpu.datastore.models import AggregationJobModel, AggregationJobState
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.messages import AggregationJobId, Duration, Interval, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    def seeded_store():
        eph = EphemeralDatastore(clock=MockClock(Time(1_600_000_000)), engine="pgfake")
        ds = eph.datastore
        task = (
            TaskBuilder(QueryTypeConfig.time_interval(), VdafInstance.count(), Role.LEADER)
            .with_(min_batch_size=1)
            .build()
        )
        ds.run_tx(lambda tx: tx.put_task(task))

        def put_jobs(tx):
            for i in range(n_jobs):
                tx.put_aggregation_job(
                    AggregationJobModel(
                        task.task_id,
                        AggregationJobId(i.to_bytes(16, "big")),
                        b"",
                        b"\x01",
                        Interval(Time(1_600_000_000), Duration(1)),
                        AggregationJobState.IN_PROGRESS,
                        0,
                    )
                )

        ds.run_tx(put_jobs)
        return eph, ds

    def count_statements(ds, claim_fn) -> tuple[int, int]:
        """(statements executed, jobs claimed) draining the store."""
        driver = ds._driver
        driver.clear_log()
        claimed = 0
        while True:
            got = ds.run_tx(lambda tx: claim_fn(tx))
            if not got:
                break
            claimed += len(got)
        return len(driver.statements("execute")), claimed

    def legacy_per_row(tx):
        """The pre-ISSUE-15 per-row claim loop, preserved here as the
        measurement oracle (one SELECT, then a guarded UPDATE ..
        RETURNING per candidate row)."""
        now = tx._clock.now().seconds
        rows = tx._c.execute(
            "SELECT task_id, job_id FROM aggregation_jobs"
            " WHERE state = 'in_progress' AND lease_expiry <= ?"
            " ORDER BY lease_expiry LIMIT ?" + tx._lease_suffix,
            (now, batch),
        ).fetchall()
        out = []
        for task_id, job_id in rows:
            token = _secrets.token_bytes(16)
            cur = tx._c.execute(
                "UPDATE aggregation_jobs SET lease_expiry = ?, lease_token = ?,"
                " lease_attempts = lease_attempts + 1"
                " WHERE task_id = ? AND job_id = ? AND state = 'in_progress'"
                " AND lease_expiry <= ? RETURNING lease_attempts",
                (now + 600, token, task_id, job_id, now),
            ).fetchone()
            if cur is not None:
                out.append((task_id, job_id))
        return out

    eph, ds = seeded_store()
    try:
        batched_stmts, batched_claimed = count_statements(
            ds,
            lambda tx: tx.acquire_incomplete_aggregation_jobs(Duration(600), batch),
        )
    finally:
        eph.cleanup()
    eph, ds = seeded_store()
    try:
        legacy_stmts, legacy_claimed = count_statements(ds, legacy_per_row)
    finally:
        eph.cleanup()
    batched_per_job = batched_stmts / max(1, batched_claimed)
    legacy_per_job = legacy_stmts / max(1, legacy_claimed)
    return {
        "jobs": n_jobs,
        "claim_batch": batch,
        "batched_statements": batched_stmts,
        "batched_claimed": batched_claimed,
        "batched_stmts_per_job": round(batched_per_job, 3),
        "per_row_statements": legacy_stmts,
        "per_row_claimed": legacy_claimed,
        "per_row_stmts_per_job": round(legacy_per_job, 3),
        # THE acceptance comparison: claim round-trips per job,
        # batched vs the per-row loop (gate: measurably below)
        "roundtrip_ratio": round(legacy_per_job / max(1e-9, batched_per_job), 1),
        "claim_roundtrips_ok": (
            batched_claimed == n_jobs
            and legacy_claimed == n_jobs
            and batched_per_job < legacy_per_job / 2
        ),
    }


def run_fleet(
    replicas: int = 4,
    jobs_per_replica: int = 24,
    job_size: int = 2,
    lease_ttl_s: int = 5,
    steal_after_s: int = 2,
    full: bool = False,
    workdir: str | None = None,
) -> dict:
    """Fleet-grade scale-out proof (ISSUE 15; docs/ARCHITECTURE.md
    "Running a fleet"): N REAL aggregation-job-driver binaries — each
    with its own fleet identity and shard slice — over ONE leader
    datastore, under RTT-bound load. Phases:

      1. claim-efficiency: batched claim tx vs the old per-row loop,
         statements counted on the recorded PG wire (in-process);
      2. scaling curve: served rps with 1, 2 and 4 replicas (2 in the
         smoke), each phase its own driver set + fresh job wave — the
         BENCH `fleet_scaling` record;
      3. chaos: a full fleet under load — SIGKILL one replica while it
         HOLDS leases (lease expires, survivors steal its shard after
         the delay, attempt accounting intact), SIGTERM-drain another
         (leases handed back immediately, rc 0), restart the killed
         replica (warm-boot path) and prove it serves a fresh wave;
      4. collection == admitted ground truth EXACTLY across every
         wave, zero lease-token conflicts on every scraped replica
         (no job double-stepped), and no job starves past
         ttl + steal + margin after the kill.

    Every `*_ok` key must be True to pass."""
    import threading

    import dataclasses

    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.binary_utils import enable_compile_cache, warmup_engines
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, Datastore, replica_holder_tag
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    t_run0 = time.monotonic()
    tmp = workdir or tempfile.mkdtemp(prefix="janus-fleet-")
    os.makedirs(tmp, exist_ok=True)
    key_bytes = secrets.token_bytes(16)
    key = base64.urlsafe_b64encode(key_bytes).decode().rstrip("=")
    clock = RealClock()
    leader_db = os.path.join(tmp, "leader.sqlite")
    leader_ds = Datastore(leader_db, Crypter([key_bytes]), clock)
    helper_ds = Datastore(os.path.join(tmp, "helper.sqlite"), Crypter([key_bytes]), clock)

    result: dict = {
        "workdir": tmp,
        "schedule": "fleet_full" if full else "fleet_smoke",
        "replicas": replicas,
    }
    procs: list[subprocess.Popen] = []
    leader_srv = helper_srv = None
    # report-flow conservation gate (ISSUE 20): the ledger evaluates
    # against the shared leader store at every quiesce point — the
    # books must close (imbalance 0) after every wave, through the
    # kill, the drain, the steal and the restart. grace 0: a nonzero
    # residual at a quiesce point breaches immediately. The installed
    # evaluator also powers the in-process collection driver's
    # cross-aggregator reconciliation in phase 4.
    from janus_tpu import ledger as ledger_mod

    ledger_ev = ledger_mod.install_ledger(
        leader_ds, ledger_mod.LedgerConfig(grace_s=0.0)
    )
    conservation: dict[str, dict] = {}

    def conservation_check(tag: str) -> bool:
        doc = ledger_ev.evaluate_once()
        imb = {
            label: dict(t["imbalance"]) for label, t in doc.get("tasks", {}).items()
        }
        conservation[tag] = imb
        return bool(imb) and all(
            v.get("ingest") == 0 and v.get("collect") == 0 for v in imb.values()
        )

    try:
        # --- phase 1: claim round-trips per job, measured ------------
        result["claim_stats"] = claim_roundtrip_stats()
        result["claim_roundtrips_ok"] = result["claim_stats"]["claim_roundtrips_ok"]

        helper_srv = DapServer(
            DapHttpApp(Aggregator(helper_ds, clock, Config()))
        ).start()
        leader_srv = DapServer(
            DapHttpApp(Aggregator(leader_ds, clock, Config(collection_retry_after_s=1)))
        ).start()

        vdaf = VdafInstance.count()
        collector_kp = generate_hpke_config_and_private_key(config_id=205)
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
            hpke_keys=(generate_hpke_config_and_private_key(config_id=5),),
        )
        leader_ds.run_tx(lambda tx: tx.put_task(leader_task), "provision")
        helper_ds.run_tx(lambda tx: tx.put_task(helper_task), "provision")
        enable_compile_cache()
        warmup_engines(leader_ds, batch=job_size)

        http = HttpClient()
        params = ClientParameters(
            leader_task.task_id, leader_srv.url, helper_srv.url, leader_task.time_precision
        )
        client = Client.with_fetched_configs(params, vdaf, http, clock=clock)
        creator = AggregationJobCreator(
            leader_ds,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=job_size
            ),
        )
        measurements: list[int] = []
        finished_target = {"jobs": 0}

        def upload_wave(n_reports: int) -> int:
            wave = [(i % 3 != 0) * 1 for i in range(n_reports)]
            for m in wave:
                client.upload(m)
            measurements.extend(wave)
            return (n_reports + job_size - 1) // job_size

        def finished_jobs() -> int:
            counts = leader_ds.run_tx(
                lambda tx: tx.count_jobs_by_state(), "fleet_monitor"
            )
            return sum(
                n
                for (typ, state), n in counts.items()
                if typ == "aggregation" and state == "finished"
            )

        def wait_finished(deadline_s: float) -> bool:
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                if finished_jobs() >= finished_target["jobs"]:
                    return True
                time.sleep(0.05)
            return finished_jobs() >= finished_target["jobs"]

        def spawn_replica(i: int, shard_count: int, tag: str):
            """One REAL driver binary with fleet identity replica-i of
            shard_count; `tag` keeps per-phase artifacts apart."""
            port = _free_port()
            cfg = _driver_cfg(
                os.path.join(tmp, f"driver-{tag}-{i}.yaml"),
                leader_db,
                port,
                int(lease_ttl_s),
                1.5,
                extra=(
                    "max_concurrent_job_workers: 4\n"
                    "fleet:\n"
                    f"  replica_id: replica-{i}\n"
                    f"  shard_count: {shard_count}\n"
                    f"  shard_index: {i}\n"
                    f"  steal_after_secs: {steal_after_s}\n"
                ),
            )
            drv = _spawn_driver(
                cfg, key, os.path.join(tmp, f"driver-{tag}-{i}.log"), FLEET_RTT_SCHEDULE
            )
            procs.append(drv)
            return i, port, drv

        def drain(replica_set, expect_rc0: bool = True) -> bool:
            ok = True
            for _i, _port, drv in replica_set:
                if drv.poll() is None:
                    drv.send_signal(signal.SIGTERM)
            for _i, _port, drv in replica_set:
                try:
                    rc = drv.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    drv.kill()
                    rc = None
                ok = ok and (rc == 0 or not expect_rc0)
            return ok

        # --- phase 2: served-rps scaling curve -----------------------
        phase_counts = (1, 2, 4) if full else (1, 2)
        rps: dict[int, float] = {}
        for n in phase_counts:
            fleet = [spawn_replica(i, n, f"scale{n}") for i in range(n)]
            for _i, port, _drv in fleet:
                _wait_healthz(port)
            jobs = upload_wave(jobs_per_replica * n * job_size)
            finished_target["jobs"] += jobs
            t0 = time.monotonic()
            creator.run_once()
            done = wait_finished(120)
            elapsed = time.monotonic() - t0
            result[f"scale_{n}_done_ok"] = done
            rps[n] = (jobs_per_replica * n * job_size) / max(1e-9, elapsed)
            result[f"drain_scale_{n}_ok"] = drain(fleet)
            # quiesce point: the wave is finished and the replicas are
            # drained — every admitted report must be accounted for
            result[f"conservation_scale_{n}_ok"] = conservation_check(f"scale_{n}")
        n_max = max(phase_counts)
        result["fleet_scaling"] = {
            "replica_counts": list(phase_counts),
            "served_rps": {str(n): round(rps[n], 1) for n in phase_counts},
            "speedup_max_vs_1": round(rps[n_max] / max(1e-9, rps[1]), 2),
            "scaling_efficiency": round(
                rps[n_max] / max(1e-9, rps[1]) / n_max, 2
            ),
            "claim_stats": result["claim_stats"],
        }
        # CI-honest gate: RTT-bound work must scale meaningfully with
        # replica count (full 1->4: >= 1.8x; smoke 1->2: >= 1.2x) — the
        # record carries the real efficiency number either way
        gate = 1.8 if full else 1.2
        result["scaling_gate"] = gate
        result["scaling_ok"] = result["fleet_scaling"]["speedup_max_vs_1"] >= gate

        # --- phase 3: kill / drain / restart under load --------------
        chaos_n = replicas if full else 2
        fleet = [spawn_replica(i, chaos_n, "chaos") for i in range(chaos_n)]
        by_idx = {i: (i, port, drv) for i, port, drv in fleet}
        for _i, port, _drv in fleet:
            _wait_healthz(port)
        jobs = upload_wave(jobs_per_replica * chaos_n * job_size)
        finished_target["jobs"] += jobs
        creator.run_once()

        # wait until the victim (replica 0) HOLDS a lease mid-step,
        # proven by the provenance tag on the held row. If a wave
        # drains before the poll catches it (a fast machine, not a
        # product defect), upload ANOTHER wave and keep looking — the
        # kill must be provably mid-step, never a guess.
        victim_tag = replica_holder_tag("replica-0").hex()
        tags = {replica_holder_tag(f"replica-{i}").hex(): i for i in range(chaos_n)}
        victim_holding = False
        seen_holder_tags: set = set()
        for _attempt in range(4):
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                holders = leader_ds.run_tx(
                    lambda tx: tx.get_lease_holders(), "fleet_monitor"
                )
                seen_holder_tags.update(h[3] for h in holders)
                if any(h[3] == victim_tag for h in holders):
                    victim_holding = True
                    break
                if finished_jobs() >= finished_target["jobs"]:
                    break  # wave drained before we caught the victim
                time.sleep(0.01)
            if victim_holding:
                break
            finished_target["jobs"] += upload_wave(jobs_per_replica * chaos_n * job_size)
            creator.run_once()
        result["victim_held_lease_ok"] = victim_holding
        result["holder_tags_are_replica_tags_ok"] = bool(seen_holder_tags) and all(
            t in tags for t in seen_holder_tags
        )

        # SIGKILL the victim MID-STEP: nothing releases its leases —
        # they must expire and drain through TTL + steal-after
        _, victim_port, victim = by_idx[0]
        victim.send_signal(signal.SIGKILL)
        t_kill = time.monotonic()
        result["victim_killed_rc"] = victim.wait(timeout=30)
        result["victim_sigkill_ok"] = result["victim_killed_rc"] == -signal.SIGKILL

        # SIGTERM-drain another replica: clean rc 0, leases handed back
        drain_idx = 1
        result["drain_mid_load_ok"] = drain([by_idx[drain_idx]])

        # survivors (or nobody, in the 2-replica smoke: the restarted
        # victim) must finish the wave; no job starves past the bound
        survivors = [by_idx[i] for i in range(chaos_n) if i not in (0, drain_idx)]

        # restart the killed replica (same identity + shard; warm-boot
        # path: shared compile cache + shape manifest)
        restarted = spawn_replica(0, chaos_n, "restart")
        _wait_healthz(restarted[1])
        result["restart_boot_ok"] = True
        survivors.append(restarted)

        starvation_bound_s = lease_ttl_s + steal_after_s + 45
        done = wait_finished(starvation_bound_s)
        result["chaos_wave_done_ok"] = done
        result["post_kill_drain_s"] = round(time.monotonic() - t_kill, 1)
        result["no_starvation_ok"] = (
            done and result["post_kill_drain_s"] <= starvation_bound_s
        )

        # a fresh wave lands with the restarted replica participating
        jobs = upload_wave(jobs_per_replica * job_size)
        finished_target["jobs"] += jobs
        creator.run_once()
        result["restart_wave_done_ok"] = wait_finished(60)

        # fleet observability on every live replica: replica_info
        # carries the configured identity, the batched claim metrics
        # are live, and the lease-conflict counter reads ZERO — no job
        # was ever double-stepped
        conflicts = 0.0
        acquired_jobs = 0.0
        claim_txs = 0.0
        steals = 0.0
        replica_info_ok = True
        mesh_statusz_ok = True
        for i, port, _drv in survivors:
            mtext = _scrape(port, "/metrics")
            info = _metric_samples(mtext, "janus_replica_info")
            want = f'replica_id="replica-{i}"'
            if not any(want in k and v == 1.0 for k, v in info.items()):
                replica_info_ok = False
            conflicts += sum(
                _metric_samples(mtext, "janus_lease_conflicts_total").values()
            )
            acquired_jobs += sum(
                _metric_samples(mtext, "janus_lease_acquired_jobs_total").values()
            )
            claim_txs += sum(
                v
                for k, v in _metric_samples(
                    mtext, "janus_lease_acquire_tx_total"
                ).items()
                if 'outcome="claimed"' in k
            )
            steals += sum(
                _metric_samples(mtext, "janus_lease_steals_total").values()
            )
            statusz = json.loads(_scrape(port, "/statusz"))
            if statusz.get("fleet", {}).get("replica_id") != f"replica-{i}":
                replica_info_ok = False
            # every replica — including the restart that replaced the
            # killed one — must publish the mesh dispatch section (the
            # single-controller lane is per-process state; a restart
            # that lost it would dispatch mesh programs unserialized)
            mesh = statusz.get("mesh")
            if not (isinstance(mesh, dict) and isinstance(mesh.get("queue"), dict)):
                mesh_statusz_ok = False
        result["replica_info_ok"] = replica_info_ok
        result["mesh_statusz_ok"] = mesh_statusz_ok
        result["lease_conflicts_total"] = conflicts
        result["zero_lease_conflicts_ok"] = conflicts == 0.0
        result["fleet_acquired_jobs"] = acquired_jobs
        result["fleet_claim_txs"] = claim_txs
        result["batched_claims_ok"] = (
            claim_txs > 0 and acquired_jobs / max(1.0, claim_txs) > 1.0
        )
        result["lease_steals"] = steals
        result["steals_observed_ok"] = steals >= 1.0  # the dead shard drained

        result["drain_final_ok"] = drain(survivors)
        # quiesce point: kill + drain + steal + restart are behind us
        # and every wave is finished — the books must still close
        result["conservation_chaos_ok"] = conservation_check("chaos")

        # --- phase 4: collect EVERYTHING vs ground truth -------------
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
                stop_collect.wait(0.3)

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
            collected = collector.collect(query, timeout_s=180.0)
            result["admitted"] = len(measurements)
            result["ground_truth_sum"] = sum(measurements)
            result["collected_count"] = collected.report_count
            result["collected_sum"] = collected.aggregate_result
            # THE invariant: every admitted report exactly once across
            # kill, drain, steal, and restart — no loss, no double
            result["exactly_once_ok"] = (
                collected.report_count == len(measurements)
                and collected.aggregate_result == sum(measurements)
            )
        finally:
            stop_collect.set()
            ct.join(timeout=10)

        # quiesce point: post-collection BOTH stages must close —
        # ingest (admitted == aggregated) and collect (aggregated ==
        # collected, nothing left awaiting)
        result["conservation_collected_ok"] = conservation_check("collected")
        result["conservation"] = conservation
        # cross-aggregator reconciliation ran inside the collection
        # driver's step (the installed evaluator + the helper's
        # authenticated /tasks/{id}/ledger endpoint): on this clean
        # lane the per-batch counts must AGREE — divergence 0
        from janus_tpu.metrics import task_id_label

        label = task_id_label(leader_task.task_id.data)
        peer = ledger_ev.document().get("tasks", {}).get(label, {}).get("peer")
        result["peer_reconciliation"] = peer
        result["peer_reconciled_ok"] = (
            peer is not None and peer.get("divergence") == 0
        )

        # --- phase 5: injected-loss lane -----------------------------
        # the ledger.drop_report failpoint silently deletes ONE
        # admitted report AFTER its admission tx counted it — the
        # tamper no throughput metric can see. The next ledger
        # evaluation (one sampler tick) must book a +1 ingest
        # imbalance, breach immediately (grace 0), and turn the
        # `conservation` SLO signal bad.
        from janus_tpu import failpoints as failpoints_inproc
        from janus_tpu.slo import ConservationSignal

        class _SigState:
            _condition_state: dict = {}

        sig_engine = _SigState()
        sig = ConservationSignal()
        slo_bad_before, _, _ = sig.read(sig_engine)
        failpoints_inproc.configure("ledger.drop_report=error:1.0,count=1")
        try:
            client.upload(1)
        finally:
            failpoints_inproc.clear()
        loss_doc = ledger_ev.evaluate_once()
        loss_imb = loss_doc.get("tasks", {}).get(label, {}).get("imbalance", {})
        slo_bad_after, _, _ = sig.read(sig_engine)
        result["loss_injected_imbalance"] = loss_imb.get("ingest")
        result["loss_breaches"] = list(loss_doc.get("breaches", []))
        result["loss_detected_ok"] = (
            loss_imb.get("ingest") == 1
            and any(s.endswith("/ingest") for s in loss_doc.get("breaches", []))
            and slo_bad_after > slo_bad_before
        )

        result["elapsed_s"] = round(time.monotonic() - t_run0, 1)
        result["ok"] = all(v for k, v in result.items() if k.endswith("_ok"))
        return result
    finally:
        failpoints_mod = sys.modules.get("janus_tpu.failpoints")
        if failpoints_mod is not None:
            failpoints_mod.clear()
        ledger_mod.uninstall_ledger()
        for p in procs:
            if p.poll() is None:
                p.kill()
        if leader_srv is not None:
            leader_srv.stop()
        if helper_srv is not None:
            helper_srv.stop()
        leader_ds.close()
        helper_ds.close()


def run_soak(
    epochs: int = 4,
    reports_per_epoch: int = 8,
    job_size: int = 4,
    report_expiry_s: float = 30.0,
    full: bool = False,
    workdir: str | None = None,
) -> dict:
    """Endurance soak (ISSUE 18; docs/OBSERVABILITY.md "Flight recorder
    and trend alerts"): sustained open-loop load with TIME-INTERVAL TASK
    CHURN and GC actually deleting collected rows, judged by the flight
    recorder's trend verdicts instead of a single end-state snapshot.

      - one epoch = a fresh time-interval task (short report_expiry_age)
        + an upload wave with known ground truth + aggregation by two
        REAL driver binaries + an EXACT collection of that epoch + a GC
        pass (old epochs' rows are expired by then and really deleted);
      - driver A runs clean: its /debug/flight analysis must call
        rss_bytes and datastore_rows FLAT over the trailing window (no
        leak-gated series leaking), p99 families stable, recorder
        self-overhead <= 1%, ring inside its byte budget, statusz
        `flight` section fresh;
      - driver B runs with the flight.synthetic_leak failpoint armed:
        the injected leak must flip janus_flight_leak_active, land the
        series in analysis.leaking, and fire the resource_trend SLO
        alert on /alertz within the window_scale-shrunk ladder.

    The smoke runs on sqlite in tier-1 minutes; the full run targets
    PostgreSQL when JANUS_TEST_DATABASE_URL points at the server from
    docker-compose.pg.yaml (falls back to sqlite otherwise). Every
    `*_ok` key must be True to pass."""
    import threading

    import dataclasses

    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.collection_job_driver import CollectionJobDriver
    from janus_tpu.aggregator.garbage_collector import GarbageCollector
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.aggregator.job_driver import JobDriver, JobDriverConfig
    from janus_tpu.binary_utils import enable_compile_cache, warmup_engines
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, open_datastore
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    t_run0 = time.monotonic()
    tmp = workdir or tempfile.mkdtemp(prefix="janus-soak-")
    os.makedirs(tmp, exist_ok=True)
    key_bytes = secrets.token_bytes(16)
    key = base64.urlsafe_b64encode(key_bytes).decode().rstrip("=")
    clock = RealClock()
    # the full run soaks the real PostgreSQL datastore when the
    # docker-compose.pg.yaml server is up (JANUS_TEST_DATABASE_URL);
    # the smoke — and a full run without the server — uses sqlite
    pg_url = os.environ.get("JANUS_TEST_DATABASE_URL") if full else None
    leader_db = pg_url or os.path.join(tmp, "leader.sqlite")
    leader_ds = open_datastore(leader_db, Crypter([key_bytes]), clock)
    helper_ds = open_datastore(
        os.path.join(tmp, "helper.sqlite"), Crypter([key_bytes]), clock
    )

    # flight/SLO cadences: production-shaped in the full run, shrunk to
    # tier-1 seconds in the smoke (window_scale turns the 1h/5m page
    # ladder into 36s/3s — the injected leak fires the trend page in
    # seconds instead of an hour)
    flight_interval_s = 2.0 if full else 0.5
    # a TRAILING window: long enough for robust slopes, short enough
    # that by verdict time it covers steady state instead of the boot
    # ramp (a window spanning the whole run would honestly — and
    # uselessly — report "rows grew" for the fill phase)
    flight_window_s = 600.0 if full else 15.0
    window_scale = 0.1 if full else 0.01

    def soak_extra(flight_dir: str) -> str:
        return (
            "max_concurrent_job_workers: 4\n"
            "health_sampler_interval_secs: 0.5\n"
            "flight:\n"
            f"  dir: {flight_dir}\n"
            f"  interval_secs: {flight_interval_s}\n"
            "  analyze_every: 3\n"
            f"  window_secs: {flight_window_s}\n"
            "  min_points: 10\n"
            "  rollup_secs: [2, 10]\n"
            "  max_segment_bytes: 65536\n"
            "  max_total_bytes: 262144\n"
            "  latency_families: [janus_database_transaction_duration_seconds]\n"
            "slo:\n"
            "  evaluation_interval_secs: 0.25\n"
            f"  window_scale: {window_scale}\n"
        )

    result: dict = {
        "workdir": tmp,
        "schedule": "soak_full" if full else "soak_smoke",
        "engine": "postgres" if pg_url else "sqlite",
        "epochs": epochs,
        "reports_per_epoch": reports_per_epoch,
    }
    procs: list[subprocess.Popen] = []
    leader_srv = helper_srv = None
    # continuous conservation gate (ISSUE 20): the books must close at
    # EVERY epoch quiesce point — through task churn, GC really
    # deleting expired rows (expiry attribution keeps the equation
    # balanced), and continuous collection. grace 0: any residual at a
    # quiesce point is an immediate breach. The installed evaluator
    # also powers the collect loop's cross-aggregator reconciliation.
    from janus_tpu import ledger as ledger_mod

    ledger_ev = ledger_mod.install_ledger(
        leader_ds, ledger_mod.LedgerConfig(grace_s=0.0)
    )
    conservation_by_epoch: list[dict] = []

    def conservation_check() -> bool:
        doc = ledger_ev.evaluate_once()
        imb = {
            label: dict(t["imbalance"]) for label, t in doc.get("tasks", {}).items()
        }
        conservation_by_epoch.append(imb)
        return bool(imb) and all(
            v.get("ingest") == 0 and v.get("collect") == 0 for v in imb.values()
        )

    try:
        helper_srv = DapServer(
            DapHttpApp(Aggregator(helper_ds, clock, Config()))
        ).start()
        leader_srv = DapServer(
            DapHttpApp(Aggregator(leader_ds, clock, Config(collection_retry_after_s=1)))
        ).start()

        vdaf = VdafInstance.count()

        def provision_epoch_task(e: int):
            """Task churn: each epoch gets its OWN time-interval task
            with a short report_expiry_age, so by the time later epochs
            run, earlier epochs' collected rows are expired and GC has
            real rows to delete."""
            collector_kp = generate_hpke_config_and_private_key(
                config_id=100 + (e % 100)
            )
            leader_task = (
                TaskBuilder(QueryTypeConfig.time_interval(), vdaf, Role.LEADER)
                .with_(
                    leader_aggregator_endpoint=leader_srv.url,
                    helper_aggregator_endpoint=helper_srv.url,
                    collector_hpke_config=collector_kp.config,
                    aggregator_auth_token=AuthenticationToken.random_bearer(),
                    collector_auth_token=AuthenticationToken.random_bearer(),
                    min_batch_size=1,
                    # a fine time precision keeps the report-timestamp
                    # round-down well inside the short expiry window
                    # (the default 1h precision would round every
                    # report to "already expired")
                    time_precision=Duration(5),
                    report_expiry_age=Duration(int(report_expiry_s)),
                )
                .build()
            )
            helper_task = dataclasses.replace(
                leader_task,
                role=Role.HELPER,
                hpke_keys=(generate_hpke_config_and_private_key(config_id=5),),
            )
            leader_ds.run_tx(lambda tx: tx.put_task(leader_task), "provision")
            helper_ds.run_tx(lambda tx: tx.put_task(helper_task), "provision")
            return leader_task, collector_kp

        # provision epoch 0 before boot so the harness can pre-warm the
        # engine programs into the shared compile cache (warm driver
        # boots; the cache covers every later epoch's identical shapes)
        epoch_tasks = [provision_epoch_task(0)]
        enable_compile_cache()
        warmup_engines(leader_ds, batch=job_size)

        flight_dirs = {
            "A": os.path.join(tmp, "flight-A"),
            "B": os.path.join(tmp, "flight-B"),
        }
        ports: dict[str, int] = {}
        for tag, failpoints in (("A", None), ("B", "flight.synthetic_leak=error:1.0")):
            port = _free_port()
            ports[tag] = port
            cfg = _driver_cfg(
                os.path.join(tmp, f"driver-{tag}.yaml"),
                leader_db,
                port,
                8,
                1.5,
                extra=soak_extra(flight_dirs[tag]),
            )
            procs.append(
                _spawn_driver(
                    cfg, key, os.path.join(tmp, f"driver-{tag}.log"), failpoints
                )
            )
        for port in ports.values():
            _wait_healthz(port)

        creator = AggregationJobCreator(
            leader_ds,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=job_size
            ),
        )
        gc_leader = GarbageCollector(leader_ds, clock)
        gc_helper = GarbageCollector(helper_ds, clock)
        http = HttpClient()

        # background collection-job driver (the leader side of collect)
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
                stop_collect.wait(0.3)

        ct = threading.Thread(target=collect_loop, daemon=True)
        ct.start()

        def aggregation_idle(deadline_s: float) -> bool:
            """Wait until no aggregation job is in a non-finished state
            (GC-deleted jobs simply vanish from the counts)."""
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                counts = leader_ds.run_tx(
                    lambda tx: tx.count_jobs_by_state(), "soak_monitor"
                )
                pending = sum(
                    n
                    for (typ, state), n in counts.items()
                    if typ == "aggregation" and state != "finished"
                )
                if pending == 0:
                    return True
                time.sleep(0.1)
            return False

        gc_deleted_total = 0
        epochs_exact = []
        epoch_details = []
        rows_by_epoch = []
        epochs_balanced: list[bool] = []
        try:
            for e in range(epochs):
                if e >= len(epoch_tasks):
                    epoch_tasks.append(provision_epoch_task(e))
                leader_task, collector_kp = epoch_tasks[e]
                params = ClientParameters(
                    leader_task.task_id,
                    leader_srv.url,
                    helper_srv.url,
                    leader_task.time_precision,
                )
                client = Client.with_fetched_configs(params, vdaf, http, clock=clock)
                t_epoch = clock.now()
                wave = [(i % 3 != 0) * 1 for i in range(reports_per_epoch)]
                for m in wave:
                    client.upload(m)
                creator.run_once()
                # the drivers must finish this epoch's jobs before the
                # collect — a collection issued mid-aggregation honestly
                # reports only the shares aggregated so far
                aggregation_idle(90.0)
                # collection == admitted ground truth, CONTINUOUSLY:
                # every epoch is collected exactly while churn and GC
                # keep running around it (the collect itself polls the
                # leader until the drivers finish the epoch's jobs).
                # The batch interval anchors at the epoch's UPLOAD time
                # — the fine precision means "now" at collect time can
                # be several batch units past the wave.
                tp = leader_task.time_precision
                start = t_epoch.to_batch_interval_start(tp)
                query = Query.time_interval(
                    Interval(
                        Time(start.seconds - tp.seconds), Duration(6 * tp.seconds)
                    )
                )
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
                collected = collector.collect(query, timeout_s=120.0)
                exact = (
                    collected.report_count == len(wave)
                    and collected.aggregate_result == sum(wave)
                )
                epochs_exact.append(exact)
                epoch_details.append(
                    {
                        "admitted": len(wave),
                        "sum": sum(wave),
                        "collected_count": collected.report_count,
                        "collected_sum": collected.aggregate_result,
                    }
                )
                # GC pass after every epoch: earlier epochs' rows age
                # past report_expiry_age mid-run and must REALLY vanish
                deleted = gc_leader.run_once()
                gc_helper.run_once()
                gc_deleted_total += sum(deleted.values())
                rows_by_epoch.append(
                    sum(
                        leader_ds.run_tx(
                            lambda tx: tx.count_table_rows(), "soak_monitor"
                        ).values()
                    )
                )
                # epoch quiesce point: the epoch is collected and GC
                # has run — every task's books (including earlier,
                # partially GC'd epochs) must close
                epochs_balanced.append(conservation_check())
        finally:
            stop_collect.set()
            ct.join(timeout=10)

        result["epochs_exact"] = epochs_exact
        result["epoch_details"] = epoch_details
        result["epochs_exact_ok"] = bool(epochs_exact) and all(epochs_exact)
        result["leader_rows_by_epoch"] = rows_by_epoch

        # keep GC pressure on until expiry has provably deleted rows
        # (the last epochs' reports only expire after the loop)
        gc_deadline = time.monotonic() + (60 if full else 30)
        while gc_deleted_total == 0 and time.monotonic() < gc_deadline:
            time.sleep(1.0)
            gc_deleted_total += sum(gc_leader.run_once().values())
            gc_helper.run_once()
        result["gc_deleted_rows"] = gc_deleted_total
        result["gc_deleted_ok"] = gc_deleted_total > 0

        # final quiesce: even after the late GC passes expired the last
        # epochs' rows, every epoch's books still close — expiry is an
        # ATTRIBUTED terminal, not silent row loss
        final_balanced = conservation_check()
        result["conservation_by_epoch"] = conservation_by_epoch
        result["conservation_ok"] = (
            bool(epochs_balanced) and all(epochs_balanced) and final_balanced
        )

        # --- verdict phase: the drivers idle on steady state while the
        # recorder's trailing window sheds the boot/ramp-up slope ------
        def flight_doc(tag: str, window_s: float | None = None) -> dict:
            q = f"?window_secs={window_s:g}" if window_s else ""
            return json.loads(_scrape(ports[tag], f"/debug/flight{q}"))

        judge_window_s = 6 * flight_interval_s + 2.0  # >= min_points span
        deadline = time.monotonic() + (120 if full else 45)
        fa: dict = {}
        while time.monotonic() < deadline:
            fa = flight_doc("A", judge_window_s)
            sv = fa.get("analysis", {}).get("series", {})
            # settle poll: the first trailing windows still straddle the
            # final epoch's churn; the steady-state question is whether
            # the series SETTLE to flat, not the first verdict computed
            if all(
                sv.get(n, {}).get("verdict") == "flat"
                for n in ("rss_bytes", "datastore_rows")
            ) and not fa.get("analysis", {}).get("leaking"):
                break
            time.sleep(1.0)
        series_a = fa.get("analysis", {}).get("series", {})
        result["flight_a_verdicts"] = {
            n: d.get("verdict") for n, d in series_a.items()
        }
        result["flight_a_slopes"] = {
            n: d.get("slope_per_s") for n, d in series_a.items()
        }
        # THE soak invariant: sustained load + churn + GC leaves the
        # leak-gated resource series FLAT over the trailing window
        result["zero_slope_ok"] = all(
            series_a.get(n, {}).get("verdict") == "flat"
            for n in ("rss_bytes", "datastore_rows")
        ) and not fa.get("analysis", {}).get("leaking")
        # p99 window-vs-window over the FULL recorder window (the 5s
        # judge window has too few txs per half for a stable quantile)
        latency_a = flight_doc("A").get("analysis", {}).get("latency", {})
        result["p99_verdicts"] = {f: d.get("verdict") for f, d in latency_a.items()}
        result["p99_stable_ok"] = all(
            d.get("verdict") != "degraded" for d in latency_a.values()
        )
        result["recorder_overhead_ratio"] = fa.get("overhead_ratio")
        result["overhead_ok"] = (
            fa.get("overhead_ratio") is not None and fa["overhead_ratio"] <= 0.01
        )
        ring = fa.get("ring") or {}
        result["ring"] = ring
        result["ring_budget_ok"] = (
            ring.get("segments", 0) >= 1
            and ring.get("bytes", 1 << 60) <= 262144
        )
        statusz = json.loads(_scrape(ports["A"], "/statusz"))
        fl = statusz.get("flight", {})
        age = fl.get("last_snapshot_age_s")
        result["statusz_flight_fresh_ok"] = (
            fl.get("enabled") is True
            and fl.get("running") is True
            and age is not None
            and age <= 3 * flight_interval_s + 2.0
        )
        # the gauge follows the PERIODIC analysis over the full window;
        # right after the last epoch that window can still contain the
        # fill ramp — the clean-driver claim is that it settles to zero
        no_leak = False
        settle_deadline = time.monotonic() + (60 if full else 30)
        while time.monotonic() < settle_deadline:
            leak_a = _metric_samples(
                _scrape(ports["A"], "/metrics"), "janus_flight_leak_active"
            )
            no_leak = sum(leak_a.values()) == 0.0
            if no_leak:
                break
            time.sleep(1.0)
        result["clean_driver_no_leak_ok"] = no_leak

        # --- injected-leak negative control: driver B ----------------
        leak_seen = alert_fired = False
        deadline = time.monotonic() + (120 if full else 45)
        fb: dict = {}
        while time.monotonic() < deadline:
            fb = flight_doc("B")
            leak_seen = "synthetic_leak_bytes" in (
                fb.get("analysis", {}).get("leaking") or []
            )
            if leak_seen:
                alertz = json.loads(_scrape(ports["B"], "/alertz"))
                alert_fired = any(
                    f.startswith("resource_trend/")
                    for f in alertz.get("firing", [])
                )
                if alert_fired:
                    break
            time.sleep(0.5)
        result["leak_detected_ok"] = leak_seen
        result["trend_alert_fired_ok"] = alert_fired
        leak_b = _metric_samples(
            _scrape(ports["B"], "/metrics"), "janus_flight_leak_active"
        )
        result["leak_gauge_ok"] = any(
            'series="synthetic_leak_bytes"' in k and v == 1.0
            for k, v in leak_b.items()
        )
        result["flight_b_leaking"] = fb.get("analysis", {}).get("leaking")

        # drain both drivers cleanly
        drain_ok = True
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                rc = p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                rc = None
            drain_ok = drain_ok and rc == 0
        result["drain_ok"] = drain_ok

        result["elapsed_s"] = round(time.monotonic() - t_run0, 1)
        result["ok"] = all(v for k, v in result.items() if k.endswith("_ok"))
        return result
    finally:
        failpoints_mod = sys.modules.get("janus_tpu.failpoints")
        if failpoints_mod is not None:
            failpoints_mod.clear()
        for p in procs:
            if p.poll() is None:
                p.kill()
        if leader_srv is not None:
            leader_srv.stop()
        if helper_srv is not None:
            helper_srv.stop()
        ledger_mod.uninstall_ledger()
        leader_ds.close()
        helper_ds.close()


def run_peer_outage(
    n_reports: int = 4,
    lease_ttl_s: int = 8,
    breaker_cooldown_s: float = 1.5,
    full: bool = False,
    workdir: str | None = None,
) -> dict:
    """Peer-outage survival schedule (see module docstring): REAL
    aggregation + collection driver binaries reach the in-process
    helper only through a netsim FaultProxy; every `*_ok` key must be
    True for the run to pass."""
    import threading

    from janus_tpu.aggregator import Aggregator, Config
    from janus_tpu.aggregator.aggregation_job_creator import (
        AggregationJobCreator,
        AggregationJobCreatorConfig,
    )
    from janus_tpu.aggregator.http_handlers import DapHttpApp, DapServer
    from janus_tpu.binary_utils import enable_compile_cache, warmup_engines
    from janus_tpu.client import Client, ClientParameters
    from janus_tpu.collector import Collector, CollectorParameters
    from janus_tpu.core.auth import AuthenticationToken
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.core.http_client import HttpClient
    from janus_tpu.core.netsim import FaultProxy
    from janus_tpu.core.time_util import RealClock
    from janus_tpu.datastore.store import Crypter, Datastore
    from janus_tpu.messages import Duration, Interval, Query, Role, Time
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    import dataclasses

    t_run0 = time.monotonic()
    tmp = workdir or tempfile.mkdtemp(prefix="janus-peerout-")
    os.makedirs(tmp, exist_ok=True)
    key_bytes = secrets.token_bytes(16)
    key = base64.urlsafe_b64encode(key_bytes).decode().rstrip("=")
    clock = RealClock()
    leader_db = os.path.join(tmp, "leader.sqlite")
    leader_ds = Datastore(leader_db, Crypter([key_bytes]), clock)
    helper_ds = Datastore(
        os.path.join(tmp, "helper.sqlite"), Crypter([key_bytes]), clock
    )

    result: dict = {
        "workdir": tmp,
        "schedule": "peer_outage_full" if full else "peer_outage_smoke",
    }
    procs: list[subprocess.Popen] = []
    leader_srv = helper_srv = proxy = None
    try:
        helper_srv = DapServer(
            DapHttpApp(Aggregator(helper_ds, clock, Config()))
        ).start()
        leader_srv = DapServer(
            DapHttpApp(Aggregator(leader_ds, clock, Config(collection_retry_after_s=1)))
        ).start()
        # the hostile wire: driver traffic to the helper crosses this
        # proxy (the task's helper endpoint below points at it); client
        # + collector traffic goes direct so proxy stats are driver-only
        from urllib.parse import urlsplit

        helper_netloc = urlsplit(helper_srv.url).netloc
        hhost, hport = helper_netloc.split(":")
        proxy = FaultProxy(hhost, int(hport)).start()

        vdaf = VdafInstance.count()
        collector_kp = generate_hpke_config_and_private_key(config_id=202)
        leader_task = (
            TaskBuilder(QueryTypeConfig.time_interval(), vdaf, Role.LEADER)
            .with_(
                leader_aggregator_endpoint=leader_srv.url,
                helper_aggregator_endpoint=proxy.url,
                collector_hpke_config=collector_kp.config,
                aggregator_auth_token=AuthenticationToken.random_bearer(),
                collector_auth_token=AuthenticationToken.random_bearer(),
                min_batch_size=1,
                # small buckets so the waves before and after the
                # blackhole land in disjoint batch intervals and the
                # two collections partition the ground truth exactly
                time_precision=Duration(2),
            )
            .build()
        )
        helper_task = dataclasses.replace(
            leader_task,
            role=Role.HELPER,
            hpke_keys=(generate_hpke_config_and_private_key(config_id=3),),
        )
        leader_ds.run_tx(lambda tx: tx.put_task(leader_task), "provision")
        helper_ds.run_tx(lambda tx: tx.put_task(helper_task), "provision")
        enable_compile_cache()
        warmup_engines(leader_ds)
        # warm the helper too: the drivers run with a tight per-attempt
        # timeout, so the helper must not pay a cold compile on the
        # first proxied init
        warmup_engines(helper_ds)

        # tight split so the schedule's clock stays short: 2 s attempts
        # against an 8 s lease, breaker opens after 3 failures, 1.5 s
        # cooldown, prober every 0.5 s
        extra = (
            "peer_health:\n"
            "  probe_interval_secs: 0.5\n"
            "  probe_timeout_secs: 1.0\n"
            "helper_http:\n"
            "  attempt_timeout_secs: 2.0\n"
            "  body_budget_secs: 2.0\n"
            "  max_response_mb: 8\n"
        )
        ttl = int(lease_ttl_s)
        port_a = _free_port()
        cfg_a = _driver_cfg(
            os.path.join(tmp, "agg_driver.yaml"), leader_db, port_a, ttl,
            breaker_cooldown_s, extra=extra,
        )
        drv_a = _spawn_driver(
            cfg_a, key, os.path.join(tmp, "agg_driver.log"), None
        )
        procs.append(drv_a)
        port_c = _free_port()
        cfg_c = _driver_cfg(
            os.path.join(tmp, "collect_driver.yaml"), leader_db, port_c, ttl,
            breaker_cooldown_s, extra=extra,
        )
        drv_c = _spawn_driver(
            cfg_c, key, os.path.join(tmp, "collect_driver.log"), None,
            module="janus_tpu.bin.collection_job_driver",
        )
        procs.append(drv_c)
        _wait_healthz(port_a)
        _wait_healthz(port_c)
        ports = (port_a, port_c)

        http = HttpClient()
        params = ClientParameters(
            leader_task.task_id, leader_srv.url, helper_srv.url,
            leader_task.time_precision,
        )
        client = Client.with_fetched_configs(params, vdaf, http, clock=clock)
        creator = AggregationJobCreator(
            leader_ds,
            AggregationJobCreatorConfig(
                min_aggregation_job_size=1, max_aggregation_job_size=100
            ),
        )

        acked: list[int] = []
        upload_errors: list[str] = []

        def upload_wave(measurements) -> None:
            for m in measurements:
                try:
                    client.upload(m)
                    acked.append(m)
                except Exception as e:
                    upload_errors.append(f"{type(e).__name__}: {e}")

        def agg_jobs_by_state() -> dict:
            counts = leader_ds.run_tx(
                lambda tx: tx.count_jobs_by_state(), "peerout_monitor"
            )
            return {
                s: n for (t, s), n in counts.items() if t == "aggregation"
            }

        def wait_agg_done(deadline_s: float) -> bool:
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                st = agg_jobs_by_state()
                if st and st.get("in_progress", 0) == 0:
                    return True
                time.sleep(0.1)
            return False

        def family_sum(port: int, name: str) -> float:
            return sum(
                _metric_samples(_scrape(port, "/metrics"), name).values()
            )

        def parked_value(port: int) -> float:
            samples = _metric_samples(
                _scrape(port, "/metrics"), "janus_peer_parked"
            )
            return max(samples.values()) if samples else 0.0

        def wait_parked(value: float, deadline_s: float) -> bool:
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                if all(parked_value(p) == value for p in ports):
                    return True
                time.sleep(0.2)
            return False

        tp = leader_task.time_precision

        def bucket_now() -> int:
            return clock.now().to_batch_interval_start(tp).seconds

        def cross_bucket_boundary() -> int:
            """Sleep into a FRESH bucket; returns its start. Everything
            uploaded before the call stays strictly below it."""
            last = bucket_now()
            while bucket_now() <= last:
                time.sleep(0.1)
            return bucket_now()

        # --- phase 1: clean baseline through the proxy ----------------
        interval_start = bucket_now()
        upload_wave([(i % 3 != 0) * 1 for i in range(n_reports)])
        wave_a_count, wave_a_sum = len(acked), sum(acked)
        creator.run_once()
        result["baseline_agg_ok"] = wait_agg_done(120)
        result["proxy_connections_baseline"] = proxy.stats["connections_total"]
        result["proxied_baseline_ok"] = proxy.stats["connections_total"] >= 1
        boundary = cross_bucket_boundary()

        # --- phase 2: blackhole past the breaker-open threshold -------
        proxy.set_toxics("up", [{"kind": "blackhole"}])
        proxy.set_toxics("down", [{"kind": "blackhole"}])
        # uploads only touch the leader: they must keep acking 201
        upload_wave([1] * 3)
        result["uploads_during_blackhole_ok"] = not upload_errors
        creator.run_once()  # the agg driver now steps into the blackhole
        # a mid-outage collection over the BASELINE interval drives the
        # collection binary into the blackhole too (wave A is already
        # aggregated, so its step reaches the helper dial)
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
        q1 = Query.time_interval(
            Interval(Time(interval_start), Duration(boundary - interval_start))
        )
        collect1: dict = {}

        def collect1_loop():
            try:
                c = collector.collect(q1, timeout_s=240.0)
                collect1["count"] = c.report_count
                collect1["sum"] = c.aggregate_result
            except Exception as e:
                collect1["error"] = f"{type(e).__name__}: {e}"

        c1t = threading.Thread(target=collect1_loop, daemon=True)
        c1t.start()

        # both binaries must PARK: breaker opens, acquirers gate off
        result["both_parked_ok"] = wait_parked(1.0, 90)
        # while parked: claim transactions stop cold and circuit_open
        # step-backs stay bounded (no churn — that's the whole point)
        pre = {
            p: (
                family_sum(p, "janus_lease_acquire_tx_total"),
                sum(
                    v
                    for k, v in _metric_samples(
                        _scrape(p, "/metrics"), "janus_job_step_back_total"
                    ).items()
                    if "circuit_open" in k
                ),
            )
            for p in ports
        }
        time.sleep(2.0)
        frozen = True
        bounded = True
        for p in ports:
            claims_then, backs_then = pre[p]
            claims_now = family_sum(p, "janus_lease_acquire_tx_total")
            backs_now = sum(
                v
                for k, v in _metric_samples(
                    _scrape(p, "/metrics"), "janus_job_step_back_total"
                ).items()
                if "circuit_open" in k
            )
            frozen = frozen and claims_now == claims_then
            bounded = bounded and (backs_now - backs_then) <= 1
        result["claims_frozen_while_parked_ok"] = frozen
        result["step_backs_bounded_ok"] = bounded
        result["outage_seconds_counted_ok"] = all(
            family_sum(p, "janus_peer_outage_seconds_total") > 0 for p in ports
        )
        statusz = json.loads(_scrape(port_a, "/statusz"))
        ph = statusz.get("peer_health", {})
        result["statusz_peer_health_ok"] = (
            ph.get("parked") is True and bool(ph.get("peers"))
        )

        # --- phase 3: heal the wire; probes resume both drivers -------
        proxy.clear()
        result["unparked_ok"] = wait_parked(0.0, 60)
        result["recovery_agg_ok"] = wait_agg_done(120)
        c1t.join(timeout=240)
        result["collect1"] = collect1
        result["collect1_exact_ok"] = (
            collect1.get("count") == wave_a_count
            and collect1.get("sum") == wave_a_sum
        )

        if full:
            # --- latency + jitter lane --------------------------------
            lat = [{"kind": "latency", "latency_s": 0.08, "jitter_s": 0.04}]
            proxy.set_toxics("up", lat)
            proxy.set_toxics("down", lat)
            upload_wave([1] * 3)
            creator.run_once()
            result["latency_lane_ok"] = wait_agg_done(120)
            proxy.clear()
            # --- flaky mid-request resets -----------------------------
            proxy.set_toxics(
                "up", [{"kind": "reset", "after_bytes": 120, "count": 2}]
            )
            upload_wave([1] * 2)
            creator.run_once()
            result["reset_lane_ok"] = (
                wait_agg_done(120) and proxy.stats["resets"] >= 1
            )
            proxy.clear()

        # --- phase 4: slow-drip (slicer) lane -------------------------
        # one connection's responses drip in 24-byte slices, 0.7 s
        # apart: each slice resets a per-read socket timer, so only the
        # client's wall-clock body budget can end the attempt; the
        # retry rides a fresh (clean) connection
        proxy.set_toxics(
            "down",
            [{"kind": "slicer", "slice_bytes": 24, "delay_s": 0.7, "count": 1}],
        )
        upload_wave([1] * 2)
        creator.run_once()
        result["slicer_lane_ok"] = (
            wait_agg_done(150)
            and proxy.stats["toxic_fired"].get("slicer", 0) >= 1
        )
        # --- phase 5: mid-request truncation lane ---------------------
        # cut one connection's REQUEST 150 bytes in — mid-headers for
        # any HTTP request, so the fire is deterministic regardless of
        # DAP body sizes (helper responses can be under ~200 bytes
        # total, which made a response-side cut point flaky). The
        # driver sees the connection die before a response and retries
        # on a fresh (clean) wire; the helper never got a full request,
        # so no state moved. Response-side mid-body truncation (the
        # short-body-under-Content-Length detection) is pinned by
        # tests/test_netsim.py against the same proxy.
        proxy.set_toxics(
            "up", [{"kind": "truncate", "after_bytes": 150, "count": 1}]
        )
        upload_wave([1] * 2)
        creator.run_once()
        result["truncate_lane_ok"] = (
            wait_agg_done(150) and proxy.stats["truncates"] >= 1
        )
        proxy.clear()
        result["upload_errors"] = upload_errors[:5]
        result["uploads_all_acked_ok"] = not upload_errors

        # --- phase 6: collect everything after the baseline boundary --
        end = cross_bucket_boundary()
        q2 = Query.time_interval(
            Interval(Time(boundary), Duration(end - boundary))
        )
        collected = collector.collect(q2, timeout_s=240.0)
        result["collect2"] = {
            "count": collected.report_count,
            "sum": collected.aggregate_result,
        }
        # THE invariant: the two disjoint collections partition the
        # admitted ground truth exactly — through a blackhole, parking,
        # probing, slow-drip and truncation
        result["exactly_once_ok"] = (
            collect1.get("count", 0) + collected.report_count == len(acked)
            and collect1.get("sum", 0) + collected.aggregate_result == sum(acked)
        )
        result["admitted"] = len(acked)
        result["ground_truth_sum"] = sum(acked)

        # --- final gates + drain --------------------------------------
        result["lease_conflicts_ok"] = all(
            family_sum(p, "janus_lease_conflicts_total") == 0 for p in ports
        )
        result["probes_alive_ok"] = all(
            sum(
                v
                for k, v in _metric_samples(
                    _scrape(p, "/metrics"), "janus_peer_probes_total"
                ).items()
                if 'outcome="alive"' in k
            )
            >= 1
            for p in ports
        )
        result["proxy_stats"] = {
            k: v for k, v in proxy.stats.items() if k != "toxic_fired"
        } | {"toxic_fired": dict(proxy.stats["toxic_fired"])}
        drains = []
        for p, logname in ((drv_a, "agg_driver.log"), (drv_c, "collect_driver.log")):
            p.send_signal(signal.SIGTERM)
            rc = p.wait(timeout=60)
            body = open(os.path.join(tmp, logname), "rb").read()
            drains.append(rc == 0 and b"shut down" in body)
        result["drain_ok"] = all(drains)

        result["elapsed_s"] = round(time.monotonic() - t_run0, 1)
        result["ok"] = all(v for k, v in result.items() if k.endswith("_ok"))
        return result
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        if proxy is not None:
            proxy.stop()
        for srv in (leader_srv, helper_srv):
            if srv is not None:
                srv.stop()
        leader_ds.close()
        helper_ds.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="fast deterministic schedule (crash + storm + collect); "
        "the default runs the full schedule incl. the post-commit crash",
    )
    ap.add_argument(
        "--scenario",
        choices=[
            "crash_storm", "db_outage", "device_hang", "pipeline", "resident",
            "cold_start", "fleet", "soak", "peer_outage",
        ],
        default="crash_storm",
        help="crash_storm = driver SIGKILL + helper storms (default); "
        "db_outage = datastore outage under upload load (journal spill, "
        "degraded serving, replay, exactly-once); device_hang = wedged "
        "device dispatch (watchdog abandon, quarantine + canary "
        "restore, host-fallback serving, exactly-once); pipeline = "
        "stage-pipelined stepper overlap proof (device lane busy while "
        "a stretched helper RTT is in flight, exactly-once); resident = "
        "device-resident accumulator flush contract (LRU eviction, "
        "quarantine sweep, SIGTERM drain each flush resident state; "
        "collections exact); cold_start = interleaved cold-cache vs "
        "warm-cache real-binary boots, restart-to-first-dispatch via "
        "/debug/boot (manifest prewarm before ready, warm < 10 s, "
        "speedup gated); fleet = N real driver replicas over one "
        "store (sharded batched claims): served-rps scaling at 1/2/4 "
        "replicas, SIGKILL + SIGTERM + restart mid-load, zero lease "
        "conflicts, exact collection; soak = endurance soak under task "
        "churn + GC deletion, judged by flight-recorder trend verdicts "
        "(zero-slope on clean driver, injected leak fires the trend "
        "alert; full run targets PostgreSQL via docker-compose.pg.yaml "
        "when JANUS_TEST_DATABASE_URL is set); peer_outage = helper "
        "behind a netsim fault proxy (blackhole past the breaker "
        "threshold parks BOTH real driver binaries, a cheap probe "
        "resumes them, slow-drip + truncation lanes recover, "
        "collections exact)",
    )
    ap.add_argument("--reports", type=int, default=0, help="0 = schedule default")
    ap.add_argument("--json", action="store_true", help="print the result record as JSON")
    ap.add_argument("--workdir", default=None, help="keep artifacts here (default: temp dir)")
    args = ap.parse_args(argv)

    if args.scenario == "db_outage":
        result = run_db_outage(
            n_warm=args.reports or (4 if args.smoke else 10),
            outage_hold_s=1.5 if args.smoke else 5.0,
            full=not args.smoke,
            workdir=args.workdir,
        )
    elif args.scenario == "device_hang":
        result = run_device_hang(
            n_reports=args.reports or (5 if args.smoke else 12),
            full=not args.smoke,
            workdir=args.workdir,
        )
    elif args.scenario == "pipeline":
        result = run_pipeline(
            n_reports=args.reports or (24 if args.smoke else 60),
            full=not args.smoke,
            workdir=args.workdir,
        )
    elif args.scenario == "resident":
        result = run_resident(
            full=not args.smoke,
            workdir=args.workdir,
        )
    elif args.scenario == "cold_start":
        result = run_cold_start(
            pairs=1 if args.smoke else 2,
            full=not args.smoke,
            workdir=args.workdir,
        )
    elif args.scenario == "fleet":
        result = run_fleet(
            full=not args.smoke,
            workdir=args.workdir,
        )
    elif args.scenario == "soak":
        result = run_soak(
            epochs=4 if args.smoke else 12,
            reports_per_epoch=args.reports or (8 if args.smoke else 24),
            report_expiry_s=30.0 if args.smoke else 120.0,
            full=not args.smoke,
            workdir=args.workdir,
        )
    elif args.scenario == "peer_outage":
        result = run_peer_outage(
            n_reports=args.reports or (4 if args.smoke else 8),
            full=not args.smoke,
            workdir=args.workdir,
        )
    else:
        n = args.reports or (5 if args.smoke else 12)
        result = run_chaos(
            n_reports=n,
            full=not args.smoke,
            workdir=args.workdir,
        )
    if args.json:
        print(json.dumps(result))
    else:
        print(json.dumps(result, indent=2))
    if not result.get("ok"):
        failed = [k for k, v in result.items() if k.endswith("_ok") and not v]
        print(f"CHAOS FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
