"""Test configuration: force the CPU backend with 8 virtual devices.

Multi-chip sharding is validated on a virtual device mesh
(xla_force_host_platform_device_count); the chip itself is exercised
by chip_smoke.py through the chip tool, and tests/test_chip_compile.py
compiles the main-path programs for a described TPU.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
# Shape-manifest hermeticity: binaries booted by tests (in-process or
# as subprocesses inheriting this env) must not read/append the
# developer's real manifest next to the compile cache — a stale
# populated manifest would make every test boot pay a prewarm pass.
import tempfile as _tempfile

os.environ.setdefault(
    "JANUS_SHAPE_MANIFEST",
    os.path.join(_tempfile.mkdtemp(prefix="janus-shapes-"), "shape_manifest.jsonl"),
)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

from janus_tpu.config import resolve_compile_cache_dir

# Persistent compilation cache: the suite jit-compiles many small
# programs; caching them across runs keeps `pytest tests/` fast. The
# binaries and scripts the tests start resolve the same directory.
jax.config.update("jax_compilation_cache_dir", resolve_compile_cache_dir()[0])
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

# Datastore engines under test: SQLite always; Postgres when a server
# URL and psycopg are both available (the reference's datastore tests
# run against a real postgres testcontainer,
# datastore/test_util.rs:26-120). Shared by every engine-parameterized
# suite so coverage can't silently diverge between files.
import importlib.util

DATASTORE_ENGINES = ["sqlite", "pgfake"]
if os.environ.get("JANUS_TEST_DATABASE_URL") and importlib.util.find_spec("psycopg"):
    DATASTORE_ENGINES.append("postgres")

# XLA:CPU's in-process compiler state degrades after many hundreds of
# compilations in one interpreter (observed: deterministic segfault in
# backend_compile_and_load roughly two-thirds into `pytest tests/`,
# independent of which test runs there; every file passes in
# isolation). Clearing jax's tracing/executable caches between test
# modules bounds that growth — subsequent modules retrace, which the
# persistent on-disk cache keeps cheap.
import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    yield
    jax.clear_caches()
    # lru-cached engine wrappers hold compiled callables; drop them with
    # the caches they reference
    try:
        from janus_tpu.aggregator.engine_cache import engine_cache

        engine_cache.cache_clear()
    except Exception:
        pass
