#!/usr/bin/env python3
"""Readings for a hybrid (Mamba-2 + attention) cell's ``max_logit_gap``
limit, as ``bench/control.py`` takes them for the dense cells: on the
chip, at the cell's own size, load and window, the program's widest
reference-logit gap on many seeds and the float8 control's on the same
sample, in one process (the engine built once, each seed bringing its
own weights and traffic).

    python3 bench/control_hybrid.py --workload <cell> --seeds 11,12,... \\
        [--seconds 30] [--control-seeds 4] [--rehearsal]

Prints one JSON line per seed, then the largest program reading and the
smallest control reading.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as bench_run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, reduced sizes (bench/rehearse.py)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    import jax
    from repro import compile_cache
    from bench.drivers import hybrid_serve, lm_serve
    from bench.reference import granite_hybrid
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    if args.rehearsal:
        from bench import rehearse
        config, mix = rehearse.reduced(config), rehearse.rehearse_mix(mix)
    else:
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    program, control = [], []
    eng = None
    with compile_cache.CompileLog() as log:
        for i, seed in enumerate(seeds):
            a = bench_run.parse(["--workload", args.workload, "--seed",
                                 str(seed), "--seconds", str(args.seconds)])
            ctx = bench_run.Run(a, cell, config, mix,
                                rehearsal=args.rehearsal)
            ctx.devices, ctx._log = jax.devices()[:cell["chips"]], log
            if eng is not None:
                eng.params = None
            gc.collect()
            params = granite_hybrid.weights(config, seed)
            if eng is None:
                eng = hybrid_serve.make_engine(ctx, params)
            eng.params = params
            record = lm_serve.serve(ctx, eng)
            got = hybrid_serve.check(record, params, config, seed,
                                     control=i < args.control_seeds)
            got.update(seed=seed, attempted=record["attempted"],
                       window_compiles=ctx.window_compiles)
            print(json.dumps(got), flush=True)
            program.append(got["max_logit_gap"])
            if "control_gap" in got:
                control.append(got["control_gap"])
            del params
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "program_max": max(program),
                      "control_min": min(control) if control else None,
                      "program": program, "control": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
