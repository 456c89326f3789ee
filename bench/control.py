#!/usr/bin/env python3
"""Readings for the limits that decide ``correct``: on the chip, at a
cell's own size, load and window, the program's compared number on many
seeds and the control's on the same sample, in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,... \\
        [--seconds 30] [--control-seeds 4]

The control is the plain reference put in the program's place one
precision step down: float8 e4m3 matmuls for the bf16 language models,
bfloat16 for the float32 NER services. Prints one JSON line per seed,
then the largest program reading and the smallest control reading. The
benchmark's own runs never run the control.

For a language-model cell the engine is built once; each seed brings its
own weights (the previous ones freed first) and its own traffic.
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

from bench import run as bench_run, spec, weights  # noqa: E402


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
    cell = spec.cell(args.workload)
    config = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    if args.rehearsal:
        from bench import rehearse
        config, mix = rehearse.reduced(config), rehearse.rehearse_mix(mix)
    else:
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()[:cell["chips"]]
    program, control = [], []

    def ctx_for(seed):
        a = bench_run.parse(["--workload", args.workload, "--seed",
                             str(seed), "--seconds", str(args.seconds)])
        ctx = bench_run.Run(a, cell, config, mix, rehearsal=args.rehearsal)
        ctx.devices = devices
        return ctx

    with compile_cache.CompileLog() as log:
        if config["driver"] == "lm_serve":
            from bench.drivers import lm_serve
            eng = None
            for i, seed in enumerate(seeds):
                ctx = ctx_for(seed)
                ctx._log = log
                if eng is not None:
                    eng.params = None
                gc.collect()
                params = weights.dense(config, seed)
                if eng is None:
                    eng = lm_serve.make_engine(ctx, params)
                eng.params = params
                record = lm_serve.serve(ctx, eng)
                got = lm_serve.check(record, params, config, seed,
                                     control=i < args.control_seeds)
                got.update(seed=seed, attempted=record["attempted"],
                           window_compiles=ctx.window_compiles)
                print(json.dumps(got), flush=True)
                program.append(got["max_logit_gap"])
                if "control_gap" in got:
                    control.append(got["control_gap"])
                del params
        else:
            from bench.drivers import ner_parallel
            for i, seed in enumerate(seeds):
                ctx = ctx_for(seed)
                ctx._log = log
                got = ner_parallel.readings(ctx, control=i < args.control_seeds)
                got.update(seed=seed)
                print(json.dumps(got), flush=True)
                program.append(got["max_label_gap"])
                if "control_gap" in got:
                    control.append(got["control_gap"])
                gc.collect()
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "program_max": max(program),
                      "control_min": min(control) if control else None,
                      "program": program, "control": control}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
