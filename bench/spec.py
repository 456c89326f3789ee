"""What a cell is made of, found by name: ``BENCHMARK.json`` at the
checkout's root names each cell's configuration and traffic mix; the
configuration is ``bench/configs/<name>.json``, the mix
``bench/traffic/<name>.json`` and each metric's reader
``bench/metrics/<name>.py``. Adding one is adding files and entries."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(BENCH / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = _json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def names() -> dict:
    return _json(BENCH / "names.json")


def cell(workload: str, bench: dict | None = None) -> dict:
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_for(workload: str, per_layer: bool,
                bench: dict | None = None) -> list:
    """The cell's end-to-end (``per_layer`` False) or per-layer metrics:
    each entry that lists the cell under ``workloads``, or lists none."""
    bench = bench or benchmark()
    group = bench["per_layer" if per_layer else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
