"""Benchmark harness — one module per paper table/figure (deliverable d).

    PYTHONPATH=src python -m benchmarks.run [--only SUBSTR] [--verbose]
        [--save DIR]

Output: ``name,value,unit,derived`` CSV rows + CHECK PASS/FAIL lines
validating the paper's claims. Exit code 1 if any check fails.

| module            | reproduces                                        |
|-------------------|---------------------------------------------------|
| bench_ahp         | Tables 3/4/5 (AHP on the paper's Table 2)         |
| bench_framework   | §3.1 methodology re-run on hostable backends      |
| bench_parallel    | Fig 8 / Table 6 (parallel vs sequential PaaS)     |
| bench_concurrency | Tables 7/8 (latency vs concurrency)               |
| bench_multimodel  | TPU adaptation: mesh space-sharing                |
| bench_kernels     | Pallas kernel correctness + analytic intensity    |
| bench_serving     | slot-native engine: device admission vs host copy |
|                   | + the paged default path end to end               |
| bench_paged_kv    | paged KV pool: concurrency at equal KV memory,    |
|                   | prefix sharing: prefill tokens actually computed  |
| bench_speculative | draft-and-verify decode: acceptance x draft       |
|                   | quality x k, tokens per target step               |
| bench_roofline    | §Roofline over the 40 dry-run artifacts           |
| bench_extraction  | end-to-end extraction quality (trains the stack)  |
"""
from __future__ import annotations

import argparse
import importlib
import time
import traceback
from pathlib import Path

MODULES = [
    "bench_ahp",
    "bench_framework",
    "bench_parallel",
    "bench_concurrency",
    "bench_multimodel",
    "bench_kernels",
    "bench_serving",
    "bench_paged_kv",
    "bench_speculative",
    "bench_roofline",
    "bench_extraction",     # trains the full stack: ~6 min on 1 core
]


def select_modules(only: str) -> list[str]:
    """Resolve a ``--only`` comma-filter against MODULES. Every filter
    must match at least one module — a typo ("pagedkv") used to silently
    run *nothing* and exit 0, which in CI reads as a green bench run."""
    filters = [f for f in only.split(",") if f]
    if not filters:
        return list(MODULES)
    for f in filters:
        if not any(f in name for name in MODULES):
            raise SystemExit(
                f"--only filter {f!r} matches no benchmark module; "
                f"choose from: {', '.join(MODULES)}")
    return [name for name in MODULES
            if any(f in name for f in filters)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="substring filter; comma-separate to run "
                         "several (e.g. --only paged_kv,serving)")
    ap.add_argument("--verbose", action="store_true")
    ap.add_argument("--save", default="experiments/bench",
                    help="directory for results.csv/tables.md ('' = off)")
    args = ap.parse_args()

    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}")
    from benchmarks.report import Report
    report = Report(verbose=args.verbose)
    failed_modules = []
    for name in select_modules(args.only):
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            mod.run(report)
        except Exception:  # noqa: BLE001 — keep the suite going
            failed_modules.append(name)
            traceback.print_exc()
        print(f"----- {name} done in {time.time() - t0:.1f}s", flush=True)

    if args.save:
        report.save(Path(args.save))
    n_checks = len(report.checks)
    print(f"\n{n_checks} checks, {report.n_failed} failed; "
          f"{len(report.rows)} rows; crashed modules: {failed_modules}")
    if report.n_failed or failed_modules:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
