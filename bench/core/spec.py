"""``BENCHMARK.json`` and the files it names, found by name.

- a cell is an entry of ``workloads``;
- its configuration is the file its ``configs`` entry names;
- its traffic is ``bench/traffic/<traffic>.json``;
- a metric's reader is ``bench/metrics/<name>.py``, or, for a name split
  by cell group such as ``mfu.backlog``, ``bench/metrics/<name up to the
  first dot>.py``; each defines ``read(run) -> float | None``.

Adding a configuration, a traffic mix, a metric or a cell therefore
adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; known: "
                     f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metrics this cell reports: its end-to-end metrics untraced, its
    per-layer metrics traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def _module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_reader_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The ``read`` function of metric ``name``."""
    for stem in (name, name.split(".", 1)[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.is_file():
            return _module(path).read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{BENCH / 'metrics'}")


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` imported as ``bench.<kind>.<name>``."""
    return importlib.import_module(f"bench.{kind}.{name}")
