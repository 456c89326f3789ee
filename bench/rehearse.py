#!/usr/bin/env python3
"""Rehearse cells on the CPU: the whole path of ``bench/run.py`` (engine
build, warm-up, traffic, window, reference check) at the reduced sizes
each configuration file gives under ``rehearsal``, with four virtual
devices for a four-chip cell. Prints what it served and the compared
numbers; it reports no device metric, since a CPU run measures none.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload NAME ...] [--seconds 4]
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import argparse  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run, spec  # noqa: E402


def reduced(config: dict) -> dict:
    """The configuration with its ``rehearsal`` sizes applied."""
    out = dict(config)
    for k, v in config.get("rehearsal", {}).items():
        out[k] = v
    return out


def rehearse_mix(mix: dict) -> dict:
    """Short prompts and answers, same shape of traffic."""
    out = dict(mix, lead_in_s=min(mix["lead_in_s"], 1))
    if mix["kind"] == "lm":
        out["prompt"] = {"median": 24, "sigma": 0.6, "min": 4, "max": 60}
        out["output"] = {"median": 6, "sigma": 0.5, "min": 2, "max": 12}
        out["pool"] = 64
        if mix["loop"] == "closed":
            out["clients"] = 8
        else:
            out["rate_per_s"] = 4.0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    names = args.workload or [w["name"] for w in spec.benchmark()["workloads"]]
    bad = 0
    for name in names:
        cell = spec.cell(name)
        a = bench_run.parse(["--workload", name, "--seed", str(args.seed),
                             "--seconds", str(args.seconds)])
        result, run = bench_run.execute(
            a, rehearsal=True,
            config_override=reduced(spec.config(cell["config"])),
            mix_override=rehearse_mix(spec.traffic(cell["traffic"])))
        ok = result["correct"]
        bad += not ok
        print(f"{name}: correct {ok}, attempted {result['attempted']}, "
              f"failed {result['failed']}, checks {result['checks']}, "
              f"compiles in window {result['window_compiles']}, "
              f"metrics found {sorted(result['metrics'])}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
