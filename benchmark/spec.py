"""What one cell of the benchmark is, read from files found by name.

`BENCHMARK.json` at the root of the checkout names the cells; each cell
has a traffic file `workloads/<cell>.json` (its rates and shares) that
names a deployment file `configs/<config>.json`, and each metric has a
reader `metrics/<metric>.py`. Adding a cell or a metric adds files and
a `BENCHMARK.json` entry; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compile cache and the program's serialized
# executables: a fixed path inside the checkout, so only a checkout's
# first run of a cell compiles
CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "jax")
# client corpora kept for later runs of a seed (corpus.cached_corpus)
CORPUS_DIR = os.path.join(BENCH_DIR, ".cache", "corpus")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    read: object  # read(record) -> float | None


@dataclass
class Cell:
    name: str
    chips: int
    traffic: dict  # workloads/<cell>.json
    config: dict  # configs/<config>.json
    end_to_end: list[Metric] = field(default_factory=list)
    per_layer: list[Metric] = field(default_factory=list)

    def metrics(self, traced: bool) -> list[Metric]:
        return self.per_layer if traced else self.end_to_end


def load_reader(name: str):
    """The `read` function of `metrics/<name>.py`."""
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    traffic = _load_json(os.path.join(BENCH_DIR, "workloads", f"{name}.json"))
    if traffic["config"] != entry["config"] or traffic["traffic"] != entry["traffic"]:
        raise SystemExit(f"workloads/{name}.json disagrees with BENCHMARK.json on config or traffic")
    config = _load_json(os.path.join(BENCH_DIR, "configs", f"{entry['config']}.json"))

    def metrics(kind: str) -> list[Metric]:
        return [
            Metric(m["name"], m["unit"], load_reader(m["name"]))
            for m in bench[kind]
            if name in m.get("workloads", [name])
        ]

    return Cell(
        name, int(entry["chips"]), traffic, config, metrics("end_to_end"), metrics("per_layer")
    )
