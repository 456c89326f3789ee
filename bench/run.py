#!/usr/bin/env python3
"""Run one benchmark cell once; the last line of standard output is the
result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell (a configuration under a traffic
mix) is looked up in ``BENCHMARK.json``; its configuration, mix and
metric readers are files under ``bench/``. A run makes its weights and
traffic from ``--seed``, warms up every shape the cell uses, measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints ``{"correct", "attempted", "failed", "metrics",
"device"[, "breakdown"], "checks"}``. With ``--trace 0`` the metrics are
the cell's end-to-end ones; with ``--trace 1`` a profiler trace of the
window gives its per-layer ones.

It needs a TPU and as many chips as the cell asks for: without them it
exits non-zero and prints no result. ``bench/rehearse.py`` runs the same
path on the CPU at reduced sizes.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402


class NoDevice(SystemExit):
    """The cell's chips are not there: no result."""


class Run:
    """What a driver gets: the cell's files and seed, and the hooks that
    time set-up, open and close the window and read the device."""

    def __init__(self, args, cell, config, mix, *, rehearsal: bool):
        self.args, self.cell, self.config, self.mix = args, cell, config, mix
        self.seed, self.seconds = args.seed, args.seconds
        self.traced = bool(args.trace)
        self.rehearsal = rehearsal
        self.devices = None
        self.phases: list = []
        self._t_phase = T_PROCESS
        self.t_open = self.t_close = self.t_trace_end = None
        self.trace_dir = None
        self.memory_peak = None
        self.window_compiles = None
        self._log = None

    def note(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    def phase(self, name: str) -> None:
        now = time.perf_counter()
        self.phases.append((name, now - self._t_phase))
        self._t_phase = now

    def open_window(self) -> float:
        import jax
        self.phase("to window")
        if self.traced:
            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        self._compiles0 = self._log.compiles
        self.t_open = time.perf_counter()
        return self.t_open

    def stop_trace(self) -> None:
        """End the traced part of the window (by default, all of it)."""
        import jax
        if self.traced and self.t_trace_end is None:
            self.t_trace_end = time.perf_counter()
            jax.profiler.stop_trace()

    def close_window(self) -> float:
        self.t_close = time.perf_counter()
        self.window_compiles = self._log.compiles - self._compiles0
        self.stop_trace()
        return self.t_close

    def read_memory(self) -> None:
        """Peak bytes on the fullest chip the cell used; read before the
        reference runs, since a peak never falls again."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak = max(peaks) if peaks else None


class View:
    """What a metric reader gets."""

    def __init__(self, run: Run, record: dict, trace, peaks):
        self.record, self.trace, self.peaks = record, trace, peaks
        self.config, self.cell = run.config, run.cell
        self.window = (run.t_open, run.t_close)
        self.window_s = run.t_close - run.t_open
        self.trace_s = ((run.t_trace_end or run.t_close) - run.t_open)
        self.setup_s = run.t_open - T_PROCESS


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="also copy the traced window's profile into DIR")
    return ap.parse_args(argv)


def execute(args, *, rehearsal: bool = False, config_override=None,
            mix_override=None, cell_override=None):
    """One run; returns (result dict, the Run). ``rehearsal`` allows the
    CPU and reduced sizes, and the result then names no device metric;
    ``cell_override`` runs a cell that ``BENCHMARK.json`` does not hold
    (a rehearsal of one not yet measured on the chip)."""
    bench = spec.benchmark()
    cell = cell_override or spec.cell(args.workload, bench)
    import jax
    devices = jax.devices()
    if not rehearsal and devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < cell["chips"]:
        raise NoDevice(f"the cell asks for {cell['chips']} chips; JAX "
                       f"found {len(devices)}")
    peaks = None if rehearsal else spec.peaks(devices[0].device_kind)
    from repro import compile_cache
    if not rehearsal:
        compile_cache.enable()
        # every program, however quick to compile, goes to the cache, so
        # that a second run of a cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config = config_override or spec.config(cell["config"])
    mix = mix_override or spec.traffic(cell["traffic"])
    run = Run(args, cell, config, mix, rehearsal=rehearsal)
    run.devices = devices[:cell["chips"]]
    driver = importlib.import_module(f"bench.drivers.{config['driver']}")
    with compile_cache.CompileLog() as log:
        run._log = log
        record = driver.run(run)
    trace = None
    if run.traced:
        from bench import trace as trace_mod
        if args.keep_trace:
            shutil.copytree(run.trace_dir, args.keep_trace, dirs_exist_ok=True)
        t0 = time.perf_counter()
        trace = trace_mod.reduce(run.trace_dir, spec.names(),
                                 n_devices=len(run.devices))
        run.note(f"trace reduced in {time.perf_counter() - t0:.1f} s; "
                 f"programs run: {trace['module_counts']}")
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    view = View(run, record, trace, peaks)
    metrics = {}
    for m in spec.metrics_for(args.workload, per_layer=run.traced, bench=bench):
        value = spec.reader(m["name"])(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = record["checks"]
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak}
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = view.trace_s
        result["breakdown"] = trace["breakdown"]
    result["window_compiles"] = run.window_compiles
    result["setup_phases_s"] = {n: round(s, 3) for n, s in run.phases}
    result["checks"] = checks
    return result, run


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result, run = execute(args)
    except NoDevice as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    print(f"compiles inside the window: {run.window_compiles}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
