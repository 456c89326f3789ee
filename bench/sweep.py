#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the engine is built
once, then the cell's traffic runs at each rate in turn, each with its
lead-in and a window. Per rate it prints the queue at the window's open
and close, the time to first token of the first and second half of the
requests due in the window, and the output tokens per second. The knee
is the highest rate whose queue does not grow over the window.

    python3 bench/sweep.py --workload <cell> --rates 0.8,1.0,1.2 [--seconds 30]
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as bench_run, spec, stats, weights  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2**31 + 101)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from repro import compile_cache
    from bench.drivers import lm_serve
    cell = spec.cell(args.workload)
    config, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    if args.rehearsal:
        from bench import rehearse
        config, mix = rehearse.reduced(config), rehearse.rehearse_mix(mix)
    else:
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    params = weights.dense(config, args.seed)
    eng = None
    with compile_cache.CompileLog() as log:
        for rate in (float(r) for r in args.rates.split(",")):
            a = bench_run.parse(["--workload", args.workload, "--seed",
                                 str(args.seed), "--seconds",
                                 str(args.seconds)])
            ctx = bench_run.Run(a, cell, config, dict(mix, rate_per_s=rate),
                                rehearsal=args.rehearsal)
            ctx.devices, ctx._log = jax.devices()[:1], log
            if eng is None:
                eng = lm_serve.make_engine(ctx, params)
            r = lm_serve.serve(ctx, eng)
            due = r["due"]
            ttft = [(q.times[0] if q.times else r["drain_end"]) - q.due
                    for q in due]
            half = len(ttft) // 2
            t0, t1 = r["window"]
            toks = sum(1 for q in r["reqs"] for t in q.times if t0 <= t < t1)
            print(json.dumps({
                "rate": rate, "due": len(due),
                "queue_open": r["queue_open"],
                "queue_close": r["queue_close"],
                "ttft_p50_first_half_ms": 1e3 * stats.percentile(
                    ttft[:half], 0.5) if half else None,
                "ttft_p50_second_half_ms": 1e3 * stats.percentile(
                    ttft[half:], 0.5) if ttft[half:] else None,
                "ttft_p90_ms": 1e3 * stats.percentile(ttft, 0.9)
                if ttft else None,
                "output_tok_s": toks / (t1 - t0),
                "window_compiles": ctx.window_compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
