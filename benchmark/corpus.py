"""Seeded client reports.

The reports are sharded by the client path the repository's load
generators use (`make_report_batch`, then `make_wire_reports`, which
HPKE-seals each share for its aggregator), in chunks of at most
`CHUNK_ELEMENTS` measurement elements (`client_chunk`). The sharding
runs on the default device: on the chip it takes seconds where the
host CPU took minutes of every SumVec run's set-up, and the compiled
client program is cached like the aggregator's.

A corpus of at most `CACHE_MAX_BYTES` is kept under `.cache/corpus/`,
keyed by everything it is made from (`cache_key`), so a later run of
the same cell and seed in the checkout reads it instead of making it.

Every seed gives the same amount of work: the report count and the
number of invalid reports depend on the cell alone, the seed picks the
measurements, the nonces and shares, and which reports are invalid.
An invalid report carries a leader proof share whose first element is
off by one, so the pair's joint verification must reject it.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import pickle
from dataclasses import dataclass

import numpy as np


# measurement elements one client call shards: 250 SumVec reports of
# length 1000, or a whole job of 500 smaller ones; it bounds the client
# program's buffers and its compile
CHUNK_ELEMENTS = 250_000
# the largest corpus the cache keeps (a Count corpus is tens of MB, a
# SumVec one GBs, which would double the disk writes of its runs), and
# the most the cache holds before it drops its oldest corpora
CACHE_MAX_BYTES = 256 << 20
CACHE_TOTAL_BYTES = 1 << 30


def seed_words(seed: int) -> list[int]:
    """A seed of any sign and size as non-negative 32-bit words."""
    s = abs(int(seed))
    words = [int(seed < 0)]
    while True:
        words.append(s & 0xFFFFFFFF)
        s >>= 32
        if not s:
            return words


def sizes(traffic: dict, job_size: int, seconds: float) -> tuple[int, int]:
    """(backlog reports, uploaded reports), each a multiple of the job
    size so that every job the creator cuts is full."""

    def jobs(rate: float) -> int:
        return max(1, math.ceil(rate * seconds / job_size)) * job_size

    return jobs(traffic["backlog_per_s"]), jobs(traffic["upload_rps"])


def invalid_mask(seed: int, n: int, share: float) -> np.ndarray:
    rng = np.random.default_rng(seed_words(seed) + [0x1BAD])
    mask = np.zeros(n, dtype=bool)
    mask[rng.choice(n, size=round(share * n), replace=False)] = True
    return mask


@dataclass
class Corpus:
    """Reports 0..n_backlog-1 are the backlog, the rest the uploads."""

    n_backlog: int
    report_ids: list  # 16-byte report ids
    reports: list  # encoded DAP Report messages
    measurements: np.ndarray  # one row (or value) per report
    invalid: np.ndarray  # bool per report

    def __len__(self) -> int:
        return len(self.reports)


def _chunk(inst, seed: int, chunk: int, n: int, invalid_rows, client: dict):
    from janus_tpu.messages import HpkeConfig, TaskId, Time
    from janus_tpu.vdaf.registry import prio3_batched
    from janus_tpu.vdaf.testing import make_report_batch, make_wire_reports, random_measurements

    words = seed_words(seed) + [chunk]
    meas = random_measurements(inst, n, np.random.default_rng(words))
    shard_seed = int(np.random.SeedSequence(words).generate_state(1, np.uint64)[0] >> 1)
    args, _ = make_report_batch(inst, meas, seed=shard_seed)
    args = [tuple(np.array(x) for x in a) if isinstance(a, tuple) else a for a in args]
    jf = prio3_batched(inst).jf
    proof = args[3]
    for r in invalid_rows:
        v = sum(int(limb[r, 0]) << (64 * i) for i, limb in enumerate(proof))
        v = (v + 1) % jf.MODULUS
        for i, limb in enumerate(proof):
            limb[r, 0] = (v >> (64 * i)) & 0xFFFFFFFFFFFFFFFF
    reports = make_wire_reports(
        inst,
        meas,
        TaskId(client["task_id"]),
        HpkeConfig.from_bytes(client["leader_hpke"]),
        HpkeConfig.from_bytes(client["helper_hpke"]),
        Time(client["when"]),
        batch_args=tuple(args),
    )
    return [r.metadata.report_id.data for r in reports], [r.to_bytes() for r in reports], meas


def client_chunk(inst, job_size: int) -> int:
    """Reports per client call: the largest divisor of the job size
    whose measurements hold at most `CHUNK_ELEMENTS` elements."""
    from janus_tpu.vdaf.testing import random_measurements

    width = np.asarray(random_measurements(inst, 1, np.random.default_rng(0))).size
    for c in range(job_size, 1, -1):
        if job_size % c == 0 and c * width <= CHUNK_ELEMENTS:
            return c
    return 1


def make_corpus(inst, seed: int, n_backlog: int, n_upload: int, invalid_share: float, chunk: int, client: dict) -> Corpus:
    """`client` holds what a client knows of the task: `task_id`, the
    two aggregators' encoded HPKE configs `leader_hpke` and
    `helper_hpke`, and the report time `when` in seconds."""
    n = n_backlog + n_upload
    if n % chunk:
        raise ValueError(f"client_chunk {chunk} does not divide {n} reports")
    invalid = invalid_mask(seed, n, invalid_share)
    ids, reports, meas = [], [], []
    for c, lo in enumerate(range(0, n, chunk)):
        i, r, m = _chunk(inst, seed, c, chunk, np.flatnonzero(invalid[lo : lo + chunk]), client)
        ids += i
        reports += r
        meas.append(np.asarray(m))
    return Corpus(n_backlog, ids, reports, np.concatenate(meas), invalid)


def cache_key(vdaf: dict, seed: int, n_backlog: int, n_upload: int, invalid_share: float, chunk: int, client: dict) -> str:
    """Names a corpus by all it is made from."""
    made_from = [vdaf, seed, n_backlog, n_upload, invalid_share, chunk,
                 {k: v.hex() if isinstance(v, bytes) else v for k, v in sorted(client.items())}]
    return hashlib.sha256(json.dumps(made_from, sort_keys=True).encode()).hexdigest()[:32]


def cached_corpus(cache_dir: str, vdaf: dict, inst, seed: int, n_backlog: int, n_upload: int,
                  invalid_share: float, chunk: int, client: dict) -> tuple[Corpus, bool]:
    """(the corpus, whether it came from the cache): read from
    `cache_dir` if a run made it before, else made and, if small
    enough, kept there."""
    key = cache_key(vdaf, seed, n_backlog, n_upload, invalid_share, chunk, client)
    path = os.path.join(cache_dir, f"{key}.pkl")
    try:
        with open(path, "rb") as f:
            return pickle.load(f), True
    except (OSError, EOFError, pickle.UnpicklingError):
        pass
    corpus = make_corpus(inst, seed, n_backlog, n_upload, invalid_share, chunk, client)
    if sum(map(len, corpus.reports)) <= CACHE_MAX_BYTES:
        os.makedirs(cache_dir, exist_ok=True)
        _make_room(cache_dir, CACHE_TOTAL_BYTES - CACHE_MAX_BYTES)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            pickle.dump(corpus, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)
    return corpus, False


def _make_room(cache_dir: str, keep_bytes: int) -> None:
    """Drops the oldest corpora until the rest hold at most `keep_bytes`."""
    paths = [os.path.join(cache_dir, n) for n in os.listdir(cache_dir) if n.endswith(".pkl")]
    paths.sort(key=os.path.getmtime, reverse=True)
    total = 0
    for p in paths:
        total += os.path.getsize(p)
        if total > keep_bytes:
            os.remove(p)
