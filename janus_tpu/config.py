"""YAML configuration for the five binaries.

Equivalent of reference aggregator/src/config.rs: CommonConfig shared
by every binary (database, logging, health-check listener), the
JobDriverConfig knobs (config.rs:121-141) and per-binary sections.
Secrets (datastore keys) arrive via flags/env, never the YAML file
(binary_utils.rs:40-66).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

import yaml

from .aggregator import Config as AggregatorProtocolConfig
from .aggregator.aggregation_job_creator import AggregationJobCreatorConfig
from .aggregator.aggregation_job_driver import ResidentConfig
from .aggregator.job_driver import JobDriverConfig
from .aggregator.peer_health import PeerHealthConfig
from .aggregator.step_pipeline import StepPipelineConfig
from .core.circuit_breaker import CircuitBreakerConfig
from .core.http_client import HttpClientConfig
from .flight_recorder import FlightRecorderConfig
from .ledger import LedgerConfig
from .profiler import ProfilerConfig
from .slo import SloEngineConfig
from .trace import TraceConfiguration

# The compile cache's default home: one fixed, git-ignored directory in
# the checkout (a cache directory that moves never finds its entries).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def resolve_compile_cache_dir(
    configured: str | None = DEFAULT_COMPILE_CACHE_DIR,
) -> tuple[str | None, str]:
    """(directory, where it came from) of the persistent compile cache.
    A set `JAX_COMPILATION_CACHE_DIR` always wins; otherwise the
    configured directory (None = the cache is off)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env, "JAX_COMPILATION_CACHE_DIR"
    if configured is None:
        return None, "disabled"
    path = os.path.expanduser(configured)
    return path, "checkout default" if path == DEFAULT_COMPILE_CACHE_DIR else "config"


@dataclass
class FleetConfig:
    """YAML `fleet:` stanza (docs/ARCHITECTURE.md "Running a fleet"):
    the replica's identity and its slice of the job-claim shard space.
    Every field is env-overridable (JANUS_REPLICA_ID /
    JANUS_SHARD_COUNT / JANUS_SHARD_INDEX / JANUS_STEAL_AFTER_S) so a
    container fleet can stamp per-replica identity onto one shared
    YAML file."""

    # stable replica identity; None auto-generates hostname-pid (and
    # keeps the per-replica metric labels OFF — single-process
    # deployments keep their exact label sets)
    replica_id: str | None = None
    # shard predicate over the persisted job shard keys: this replica
    # claims shard_key % shard_count == shard_index immediately, any
    # other shard only after steal_after_secs of eligibility (a dead
    # replica's shard drains instead of starving)
    shard_count: int = 1
    shard_index: int = 0
    steal_after_secs: float = 30.0

    @classmethod
    def from_dict(cls, d: dict | None) -> "FleetConfig":
        import os

        d = d or {}
        replica_id = os.environ.get("JANUS_REPLICA_ID") or d.get("replica_id")
        count = os.environ.get("JANUS_SHARD_COUNT") or d.get("shard_count", 1)
        index = os.environ.get("JANUS_SHARD_INDEX") or d.get("shard_index", 0)
        steal = os.environ.get("JANUS_STEAL_AFTER_S") or d.get(
            "steal_after_secs", 30.0
        )
        return cls(
            replica_id=str(replica_id) if replica_id else None,
            shard_count=max(1, int(count)),
            shard_index=int(index),
            steal_after_secs=max(0.0, float(steal)),
        )

    def resolved_replica_id(self) -> str:
        from .metrics import default_replica_id

        return self.replica_id or default_replica_id()

    def shard_spec(self):
        """ShardSpec for the batched lease claims (None when the fleet
        is unsharded — the predicate compiles away entirely)."""
        from .datastore.models import ShardSpec

        import math

        if self.shard_count <= 1:
            return None
        return ShardSpec(
            shard_count=self.shard_count,
            shard_index=self.shard_index % self.shard_count,
            # ceil, never truncate: the claim predicate works in whole
            # seconds, and a fractional steal_after (0.5) must round to
            # a 1 s fence — int() would silently DISABLE stealing
            # fencing while the creator path honors the float
            steal_after_s=math.ceil(max(0.0, self.steal_after_secs)),
        )

    def holder_tag(self) -> bytes:
        """8-byte provenance tag stamped into every lease token this
        replica mints."""
        from .datastore.store import replica_holder_tag

        return replica_holder_tag(self.resolved_replica_id())


@dataclass
class EngineConfig:
    """YAML `engine:` stanza (docs/ARCHITECTURE.md "Resident aggregate
    state"): engine-layer knobs shared by every binary with a device
    path."""

    # persistent XLA compilation cache directory; overrides the
    # top-level compilation_cache_dir when set (the cheap slice of the
    # cold-start roadmap item: restarts and canary rebuilds reload
    # compiled executables from disk instead of recompiling). The cache
    # is ON by default via CommonConfig.compilation_cache_dir; set
    # `compilation_cache_dir: null` (and no engine-level dir) to
    # explicitly disable it.
    compile_cache_dir: str | None = None
    # process-wide device-byte bound on resident aggregate buffers
    # (EngineCache.RESIDENT_MAX_BYTES; LRU overflow evicts through the
    # flush path). 0/None keeps the class default.
    resident_max_bytes: int | None = None
    # merge small jobs across TASKS into one device dispatch (per-lane
    # verify keys). None keeps the process default (on).
    cross_task_coalesce: bool | None = None
    # --- geometry-manifest prewarm (docs/ARCHITECTURE.md "Cold-start
    # and prewarm") ---
    # persisted shape manifest of observed dispatch specializations.
    # None (default) puts it next to the compile cache
    # (<cache_dir>/shape_manifest.jsonl); "" disables recording AND
    # manifest-driven prewarm. The JANUS_SHAPE_MANIFEST env var is the
    # operator override.
    shape_manifest_path: str | None = None
    shape_manifest_max_entries: int = 512
    # serialized-executable AOT cache (<compile cache dir>/aot): a
    # restarted process deserializes compiled engine programs instead
    # of re-tracing them — the layer that takes a warm restart from
    # ~trace-per-program to ~tens of ms per program. JANUS_AOT_CACHE=0
    # disables it.
    aot_cache: bool = True
    # AOT-compile the manifest's recorded specializations at boot,
    # before /readyz reports ready (highest recorded cost first,
    # bounded by the boot budget; the remainder warms in background)
    prewarm: bool = True
    prewarm_boot_budget_secs: float = 30.0
    # --- mesh serving geometry (docs/ARCHITECTURE.md "Multi-chip
    # serving") ---
    # `mesh: {dp, sp}` pins the serving mesh axes (dp = report batch,
    # sp = measurement/out-share columns) instead of auto-selecting
    # from the device count. Validated per engine — a single-device
    # process, or a request for more devices than exist, falls back to
    # the unsharded path. JANUS_MESH_DP / JANUS_MESH_SP envs override.
    mesh_dp: int | None = None
    mesh_sp: int | None = None

    @classmethod
    def from_dict(cls, d: dict | None) -> "EngineConfig":
        d = d or {}
        rmb = d.get("resident_max_bytes")
        xt = d.get("cross_task_coalesce")
        mesh = d.get("mesh") or {}
        mdp = mesh.get("dp")
        msp = mesh.get("sp")
        return cls(
            compile_cache_dir=d.get("compile_cache_dir"),
            resident_max_bytes=int(rmb) if rmb is not None else None,
            cross_task_coalesce=bool(xt) if xt is not None else None,
            shape_manifest_path=d.get("shape_manifest_path"),
            shape_manifest_max_entries=int(d.get("shape_manifest_max_entries", 512)),
            aot_cache=bool(d.get("aot_cache", True)),
            prewarm=bool(d.get("prewarm", True)),
            prewarm_boot_budget_secs=float(d.get("prewarm_boot_budget_secs", 30.0)),
            mesh_dp=int(mdp) if mdp is not None else None,
            mesh_sp=int(msp) if msp is not None else None,
        )


@dataclass
class DbConfig:
    """reference config.rs:61 (url + connection knobs). `url` selects
    the engine: a postgres://…/postgresql://… URL opens the Postgres
    backend (multi-host work queue, datastore.rs:203); any other value
    is a SQLite filesystem path (or ":memory:") for single-host
    deployments and tests."""

    url: str = "janus.sqlite"
    # WARN-log threshold for one datastore transaction (run_tx wall
    # time, retries included); <= 0 disables the warning.
    slow_tx_warn_secs: float = 1.0
    # Cap on one run_tx retry sleep (full-jitter exponential backoff
    # below it). Stretch for outage-heavy deployments so a retry storm
    # spreads out; janus_tx_retries_total{tx,kind} counts the retries.
    retry_max_interval_secs: float = 0.128
    # Datastore connection supervision (docs/ROBUSTNESS.md "Datastore
    # outages"): background health-probe period driving the
    # up/degraded/down/recovering state machine, /readyz, degraded-mode
    # shedding and the upload journal spill decision. 0 disables.
    health_probe_interval_secs: float = 5.0
    # consecutive connection-class failures before the state goes down
    down_after_failures: int = 3
    # ceiling of the jittered reconnect/probe backoff while down
    reconnect_max_interval_secs: float = 30.0

    @classmethod
    def from_dict(cls, d: dict) -> "DbConfig":
        return cls(
            url=str(d.get("url", "janus.sqlite")),
            slow_tx_warn_secs=float(d.get("slow_tx_warn_secs", 1.0)),
            retry_max_interval_secs=float(d.get("retry_max_interval_secs", 0.128)),
            health_probe_interval_secs=float(
                d.get("health_probe_interval_secs", 5.0)
            ),
            down_after_failures=int(d.get("down_after_failures", 3)),
            reconnect_max_interval_secs=float(
                d.get("reconnect_max_interval_secs", 30.0)
            ),
        )


@dataclass
class TaskprovConfig:
    """reference config.rs:93."""

    enabled: bool = False

    @classmethod
    def from_dict(cls, d: dict | None) -> "TaskprovConfig":
        return cls(enabled=bool((d or {}).get("enabled", False)))


@dataclass
class CommonConfig:
    """reference config.rs:28-45."""

    database: DbConfig = field(default_factory=DbConfig)
    logging_config: TraceConfiguration = field(default_factory=TraceConfiguration)
    health_check_listen_address: str = "0.0.0.0:9001"
    # Which JAX backend this process uses (e.g. "cpu", "tpu"). A TPU chip
    # is single-process: give it to the VDAF hot path (the helper-side
    # aggregator server, and the leader-side aggregation job driver) and
    # pin every other process to "cpu". None = leave the environment alone.
    jax_platform: str | None = None
    # Persistent XLA compilation cache directory. First compile of a
    # (VDAF, step, batch-bucket) is minutes; with the cache a process
    # restart reloads compiled executables in seconds. None disables;
    # a set JAX_COMPILATION_CACHE_DIR overrides (resolve_compile_cache_dir).
    compilation_cache_dir: str | None = DEFAULT_COMPILE_CACHE_DIR
    # Warm the engines for every provisioned task at boot (trace+compile
    # the helper/leader steps for the smallest batch bucket) instead of
    # stalling the first request. Only the VDAF-hot-path binaries use it.
    warmup_engines_at_boot: bool = False
    # With warmup_buckets set (e.g. [32, 256, 1024]), warmup runs in a
    # background thread per ascending bucket — serving starts
    # immediately and big job buckets compile ahead of their first job.
    warmup_buckets: tuple[int, ...] = ()
    # Period of the job/task health sampler (aggregator/health_sampler.py:
    # janus_jobs backlog gauges, lease age, aggregation lag). 0 disables.
    # Wired by the aggregator server and both job driver binaries.
    health_sampler_interval_s: float = 15.0
    # Fault injection (janus_tpu/failpoints.py; docs/ROBUSTNESS.md): a
    # spec string ("datastore.commit=error:0.3;helper.request=delay:2")
    # or a {name: "action:arg,..."} mapping. The JANUS_FAILPOINTS env
    # var overrides. None (the default) arms nothing and every
    # instrumented site compiles to a one-flag-check no-op.
    failpoints: object = None
    # Device-path watchdog + quarantine (YAML `device_watchdog:`
    # section; docs/ROBUSTNESS.md "Device hangs & deadlines"): parked
    # abandoned-dispatch threads tolerated before the process trips
    # host-only mode, and the quarantined engine's canary cadence.
    watchdog_abandoned_thread_cap: int = 8
    quarantine_canary_delay_secs: float = 5.0
    quarantine_canary_timeout_secs: float = 30.0
    # In-process SLO burn-rate engine (YAML `slo:` section;
    # docs/OBSERVABILITY.md "SLO engine & /alertz"): evaluation cadence
    # and alert definitions (merged over the shipped defaults by name).
    # Enabled by default — every binary answers GET /alertz.
    slo: SloEngineConfig = field(default_factory=SloEngineConfig)
    # Engine-layer knobs (YAML `engine:` section): compile cache dir
    # override, resident-buffer byte bound, cross-task coalescing.
    engine: EngineConfig = field(default_factory=EngineConfig)
    # Always-on sampling profiler (YAML `profiler:` section;
    # docs/OBSERVABILITY.md "Continuous profiling"): wall-clock stack
    # sampling rate and window ring behind GET /debug/profile. Enabled
    # by default in every binary.
    profiler: ProfilerConfig = field(default_factory=ProfilerConfig)
    # Telemetry flight recorder (YAML `flight:` section;
    # docs/OBSERVABILITY.md "Flight recorder and trend alerts"):
    # low-cadence resource/metric history ring behind GET /debug/flight
    # plus the trend/leak analyzer feeding the `trend` SLO signal.
    # Enabled by default in every binary (memory-only until `dir` set).
    flight: FlightRecorderConfig = field(default_factory=FlightRecorderConfig)
    # Report-flow conservation ledger (YAML `ledger:` section;
    # docs/OBSERVABILITY.md "Conservation accounting"): per-task balance
    # evaluation at health-sampler cadence behind GET /debug/ledger,
    # grace window before an imbalance pages, and the leader collection
    # driver's cross-aggregator reconciliation fetch. Enabled by default
    # in every datastore-owning binary.
    ledger: LedgerConfig = field(default_factory=LedgerConfig)
    # Fleet identity + job-claim sharding (YAML `fleet:` section;
    # docs/ARCHITECTURE.md "Running a fleet"): replica id stamped into
    # lease tokens/metrics/traces, and this replica's slice of the
    # shard space for the batched lease claims. Env-overridable
    # (JANUS_REPLICA_ID / JANUS_SHARD_COUNT / JANUS_SHARD_INDEX /
    # JANUS_STEAL_AFTER_S) for container fleets.
    fleet: FleetConfig = field(default_factory=FleetConfig)

    @classmethod
    def from_dict(cls, d: dict) -> "CommonConfig":
        wd = d.get("device_watchdog", {}) or {}
        return cls(
            database=DbConfig.from_dict(d.get("database", {})),
            logging_config=TraceConfiguration.from_dict(d.get("logging_config")),
            health_check_listen_address=str(
                d.get("health_check_listen_address", "0.0.0.0:9001")
            ),
            jax_platform=d.get("jax_platform"),
            compilation_cache_dir=d.get("compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR),
            warmup_engines_at_boot=bool(d.get("warmup_engines_at_boot", False)),
            warmup_buckets=tuple(int(b) for b in d.get("warmup_buckets", ())),
            health_sampler_interval_s=float(d.get("health_sampler_interval_secs", 15.0)),
            failpoints=d.get("failpoints"),
            watchdog_abandoned_thread_cap=int(wd.get("abandoned_thread_cap", 8)),
            quarantine_canary_delay_secs=float(wd.get("canary_delay_secs", 5.0)),
            quarantine_canary_timeout_secs=float(wd.get("canary_timeout_secs", 30.0)),
            slo=SloEngineConfig.from_dict(d.get("slo")),
            engine=EngineConfig.from_dict(d.get("engine")),
            profiler=ProfilerConfig.from_dict(d.get("profiler")),
            flight=FlightRecorderConfig.from_dict(d.get("flight")),
            ledger=LedgerConfig.from_dict(d.get("ledger")),
            fleet=FleetConfig.from_dict(d.get("fleet")),
        )


def _job_driver_from_dict(d: dict) -> JobDriverConfig:
    """reference config.rs:121-141 field names."""
    return JobDriverConfig(
        job_discovery_interval_s=d.get("min_job_discovery_delay_secs", 0.2),
        max_job_discovery_interval_s=d.get("max_job_discovery_delay_secs", 5.0),
        max_concurrent_job_workers=int(d.get("max_concurrent_job_workers", 4)),
        worker_lease_duration_s=int(d.get("worker_lease_duration_secs", 600)),
        maximum_attempts_before_failure=int(
            d.get("maximum_attempts_before_failure", 10)
        ),
        discovery_jitter=float(d.get("job_discovery_jitter", 0.25)),
    )


@dataclass
class AggregatorConfig:
    """reference aggregator/src/bin/aggregator.rs Config."""

    common: CommonConfig = field(default_factory=CommonConfig)
    listen_address: str = "0.0.0.0:8080"
    aggregator_api_listen_address: str | None = None
    aggregator_api_auth_tokens: tuple[str, ...] = ()
    max_upload_batch_size: int = 100
    max_upload_batch_write_delay_ms: int = 0
    batch_aggregation_shard_count: int = 1
    taskprov: TaskprovConfig = field(default_factory=TaskprovConfig)
    garbage_collection_interval_s: float | None = None
    collection_retry_after_s: int = 1
    # --- ingest pipeline + admission control (YAML `ingest:` section;
    # docs/INGEST.md tuning table) ---
    ingest_decrypt_workers: int = 0  # 0 = GIL-capability-sized (INGEST.md)
    ingest_decode_workers: int = 1
    # flush-window batching of decode+decrypt (docs/INGEST.md "Batched
    # decrypt"); window 1 restores the per-report path
    ingest_batch_window: int = 32
    ingest_batch_linger_ms: float = 2.0
    # must stay below max_handler_threads (each in-flight upload parks
    # a handler thread, so a larger bound can never fill)
    ingest_queue_depth: int = 24
    upload_bucket_rate: float = 0.0  # 0 = unlimited
    upload_bucket_burst: int = 0
    aggregate_bucket_rate: float = 0.0
    aggregate_bucket_burst: int = 0
    shed_priority: tuple = ("upload", "aggregate")
    queue_high_watermark: float = 0.75
    upload_shed_retry_after_s: float = 1.0
    max_handler_threads: int = 32
    # --- durable upload spill journal (YAML `upload_journal:` section;
    # docs/ROBUSTNESS.md "Datastore outages"). No path = disarmed: the
    # upload flush path is unchanged and adds no fsyncs. ---
    upload_journal_path: str | None = None
    upload_journal_max_segment_bytes: int = 8 << 20
    upload_journal_max_total_bytes: int = 256 << 20
    upload_journal_max_segments: int = 1024
    # commit latency past this spills subsequent flushes to the journal
    # (bounded ack latency through a brownout); 0 = connection-class
    # errors / datastore-down only
    upload_journal_spill_latency_secs: float = 0.0
    upload_journal_replay_interval_secs: float = 1.0
    # Retry-After advertised on the 503 when the journal is full
    upload_journal_full_retry_after_secs: float = 30.0

    @classmethod
    def from_dict(cls, d: dict) -> "AggregatorConfig":
        gc = d.get("garbage_collection", {}) or {}
        api = d.get("aggregator_api", {}) or {}
        ingest = d.get("ingest", {}) or {}
        journal = d.get("upload_journal", {}) or {}
        return cls(
            common=CommonConfig.from_dict(d),
            listen_address=str(d.get("listen_address", "0.0.0.0:8080")),
            aggregator_api_listen_address=api.get("listen_address"),
            aggregator_api_auth_tokens=tuple(api.get("auth_tokens", ())),
            max_upload_batch_size=int(d.get("max_upload_batch_size", 100)),
            max_upload_batch_write_delay_ms=int(
                d.get("max_upload_batch_write_delay_ms", 0)
            ),
            batch_aggregation_shard_count=int(
                d.get("batch_aggregation_shard_count", 1)
            ),
            taskprov=TaskprovConfig.from_dict(d.get("taskprov_config")),
            garbage_collection_interval_s=gc.get("gc_frequency_s"),
            collection_retry_after_s=int(d.get("collection_retry_after_secs", 1)),
            ingest_decrypt_workers=int(ingest.get("decrypt_workers", 0)),
            ingest_decode_workers=int(ingest.get("decode_workers", 1)),
            ingest_batch_window=int(ingest.get("decrypt_batch_window", 32)),
            ingest_batch_linger_ms=float(ingest.get("decrypt_batch_linger_ms", 2.0)),
            ingest_queue_depth=int(ingest.get("queue_depth", 24)),
            upload_bucket_rate=float(ingest.get("upload_bucket_rate", 0.0)),
            upload_bucket_burst=int(ingest.get("upload_bucket_burst", 0)),
            aggregate_bucket_rate=float(ingest.get("aggregate_bucket_rate", 0.0)),
            aggregate_bucket_burst=int(ingest.get("aggregate_bucket_burst", 0)),
            shed_priority=tuple(ingest.get("shed_priority", ("upload", "aggregate"))),
            queue_high_watermark=float(ingest.get("queue_high_watermark", 0.75)),
            upload_shed_retry_after_s=float(ingest.get("shed_retry_after_secs", 1.0)),
            max_handler_threads=int(ingest.get("max_handler_threads", 32)),
            upload_journal_path=journal.get("path"),
            upload_journal_max_segment_bytes=int(
                journal.get("max_segment_bytes", 8 << 20)
            ),
            upload_journal_max_total_bytes=int(
                journal.get("max_total_bytes", 256 << 20)
            ),
            upload_journal_max_segments=int(journal.get("max_segments", 1024)),
            upload_journal_spill_latency_secs=float(
                journal.get("spill_commit_latency_secs", 0.0)
            ),
            upload_journal_replay_interval_secs=float(
                journal.get("replay_interval_secs", 1.0)
            ),
            upload_journal_full_retry_after_secs=float(
                journal.get("full_retry_after_secs", 30.0)
            ),
        )

    def protocol_config(self) -> AggregatorProtocolConfig:
        return AggregatorProtocolConfig(
            max_upload_batch_size=self.max_upload_batch_size,
            max_upload_batch_write_delay_ms=self.max_upload_batch_write_delay_ms,
            batch_aggregation_shard_count=self.batch_aggregation_shard_count,
            taskprov_enabled=self.taskprov.enabled,
            collection_retry_after_s=self.collection_retry_after_s,
            ingest_decrypt_workers=self.ingest_decrypt_workers,
            ingest_decode_workers=self.ingest_decode_workers,
            ingest_batch_window=self.ingest_batch_window,
            ingest_batch_linger_ms=self.ingest_batch_linger_ms,
            ingest_queue_depth=self.ingest_queue_depth,
            upload_bucket_rate=self.upload_bucket_rate,
            upload_bucket_burst=self.upload_bucket_burst,
            aggregate_bucket_rate=self.aggregate_bucket_rate,
            aggregate_bucket_burst=self.aggregate_bucket_burst,
            shed_priority=self.shed_priority,
            queue_high_watermark=self.queue_high_watermark,
            upload_shed_retry_after_s=self.upload_shed_retry_after_s,
            max_handler_threads=self.max_handler_threads,
            upload_journal_path=self.upload_journal_path,
            upload_journal_max_segment_bytes=self.upload_journal_max_segment_bytes,
            upload_journal_max_total_bytes=self.upload_journal_max_total_bytes,
            upload_journal_max_segments=self.upload_journal_max_segments,
            upload_journal_spill_latency_s=self.upload_journal_spill_latency_secs,
            upload_journal_replay_interval_s=self.upload_journal_replay_interval_secs,
            upload_journal_full_retry_after_s=self.upload_journal_full_retry_after_secs,
        )


@dataclass
class JobCreatorConfig:
    """reference aggregator/src/bin/aggregation_job_creator.rs Config."""

    common: CommonConfig = field(default_factory=CommonConfig)
    aggregation_job_creation_interval_s: float = 1.0
    min_aggregation_job_size: int = 10
    max_aggregation_job_size: int = 100
    max_concurrent_tasks: int = 8

    @classmethod
    def from_dict(cls, d: dict) -> "JobCreatorConfig":
        # (tasks_update_frequency_secs is accepted but unused: the creator
        # re-reads the task list on every pass, unlike the reference's
        # long-lived per-task workers, aggregation_job_creator.rs:154)
        return cls(
            common=CommonConfig.from_dict(d),
            aggregation_job_creation_interval_s=float(
                d.get("aggregation_job_creation_interval_secs", 1.0)
            ),
            min_aggregation_job_size=int(d.get("min_aggregation_job_size", 10)),
            max_aggregation_job_size=int(d.get("max_aggregation_job_size", 100)),
            max_concurrent_tasks=int(d.get("max_concurrent_tasks", 8)),
        )

    def creator_config(self) -> AggregationJobCreatorConfig:
        return AggregationJobCreatorConfig(
            min_aggregation_job_size=self.min_aggregation_job_size,
            max_aggregation_job_size=self.max_aggregation_job_size,
            max_concurrent_tasks=self.max_concurrent_tasks,
        )


@dataclass
class JobDriverBinaryConfig:
    """reference aggregator/src/bin/{aggregation,collection}_job_driver.rs."""

    common: CommonConfig = field(default_factory=CommonConfig)
    job_driver: JobDriverConfig = field(default_factory=JobDriverConfig)
    # leader->helper outbound circuit breaker knobs (YAML
    # `outbound_circuit_breaker:` section; docs/ROBUSTNESS.md)
    outbound_circuit_breaker: CircuitBreakerConfig = field(
        default_factory=CircuitBreakerConfig
    )
    # peer-outage parking + half-open probing (YAML `peer_health:`
    # section; docs/ARCHITECTURE.md "Surviving the other aggregator")
    peer_health: PeerHealthConfig = field(default_factory=PeerHealthConfig)
    # per-attempt timeout / body budget / size cap for the outbound
    # helper client (YAML `helper_http:` section)
    helper_http: HttpClientConfig = field(default_factory=HttpClientConfig)
    # stage-pipelined leader stepper knobs (YAML `step_pipeline:`
    # section; docs/ARCHITECTURE.md "The stepper pipeline"). Enabled by
    # default — `step_pipeline: {enabled: false}` restores the serial
    # per-worker stepper.
    step_pipeline: StepPipelineConfig = field(default_factory=StepPipelineConfig)
    # device-resident accumulator state (YAML `resident_accumulators:`
    # section; docs/ARCHITECTURE.md "Resident aggregate state"). Off by
    # default — the per-job share fetch+write stays crash-durable.
    resident_accumulators: ResidentConfig = field(default_factory=ResidentConfig)

    @classmethod
    def from_dict(cls, d: dict) -> "JobDriverBinaryConfig":
        return cls(
            common=CommonConfig.from_dict(d),
            job_driver=_job_driver_from_dict(d),
            outbound_circuit_breaker=CircuitBreakerConfig.from_dict(
                d.get("outbound_circuit_breaker")
            ),
            peer_health=PeerHealthConfig.from_dict(d.get("peer_health")),
            helper_http=HttpClientConfig.from_dict(d.get("helper_http")),
            step_pipeline=StepPipelineConfig.from_dict(d.get("step_pipeline")),
            resident_accumulators=ResidentConfig.from_dict(
                d.get("resident_accumulators")
            ),
        )


def load_config(path: str, cls):
    with open(path) as f:
        doc = yaml.safe_load(f) or {}
    return cls.from_dict(doc)
