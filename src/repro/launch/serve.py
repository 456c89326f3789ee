"""Serving launcher: slot-native continuous-batching engine for one
architecture, behind an SLO-aware scheduler.

    PYTHONPATH=src python -m repro.launch.serve [--arch qwen3-4b] \
        [--requests 6] [--batch 8] [--max-new 8] [--policy spf]
    PYTHONPATH=src JAX_PLATFORMS=cpu python -m repro.launch.serve --reduced

Serves synthetic token requests at the architecture's published widths
and dtype, with seeded random weights, through the paged engine and the
Pallas paged-attention kernel. ``--reduced`` serves the 2-layer float32
``ArchConfig.reduced()`` variant instead: the size for CPU runs and CI.
Before serving, the engine's decode, chunk-window and admission programs
are compiled ahead of time and their ``memory_analysis()`` sizes the KV
pool against the device's memory. For the multi-model parallel-PaaS
serving of the paper, see examples/serve_parallel_pipeline.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import statistics

import jax

from repro import compile_cache
from repro.configs.base import ARCH_IDS, get_config
from repro.models.model import build_model
from repro.serve.engine import Request, ServingEngine
from repro.serve.scheduler import POLICIES, Scheduler

# engine shape per mode when --batch / --max-seq are not given
DEFAULT_SHAPE = {"published": {"batch": 8, "max_seq": 2048},
                 "reduced": {"batch": 4, "max_seq": 128}}
# share of the device's memory the resident state plus one program may
# plan to use; the rest is left to the runtime and fragmentation
HBM_BUDGET = 0.9


def load_model(arch: str, *, reduced: bool, seed: int = 0):
    """Config, model and seeded random parameters. The parameters are
    built on the device by one jitted program, so each float32 draw
    fuses into its cast to the model dtype instead of living as an eager
    float32 transient (3.6 GB for Qwen3-4B's stacked FFN weight)."""
    cfg = get_config(arch)
    if reduced:
        cfg = dataclasses.replace(cfg.reduced(), dtype=jax.numpy.float32)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(seed))
    return cfg, model, params


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _hbm_limit():
    """bytes_limit of device 0; None where the backend reports no memory
    statistics (the CPU)."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def plan_memory(eng: ServingEngine, compiled: dict) -> dict:
    """Device bytes each serving program needs while it runs: its
    arguments, outputs not aliased to donated arguments, and
    temporaries, plus the KV pool where the pool is not an argument
    (admission). ``peak`` is the largest of them."""
    pool = _nbytes(eng.caches)
    plan = {"params": _nbytes(eng.params), "pool": pool,
            "num_blocks": eng.pool.total}
    for name, c in compiled.items():
        ma = c.memory_analysis()
        live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        plan[name] = live + (pool if name == "admit" else 0)
    plan["peak"] = max(plan[name] for name in compiled)
    return plan


def build_engine(model, params, *, batch: int, max_seq: int,
                 prefill_chunk=None, prefill_budget=None, tracer=None):
    """The serving engine as this launcher runs it: paged KV, the Pallas
    paged-attention kernel (compiled on TPU, interpret mode elsewhere),
    and a pool sized from the compiled programs. The pool starts at one
    ``max_seq`` stripe per slot; while the peak program exceeds
    ``HBM_BUDGET`` of the device's memory it shrinks, counting each
    block twice (resident, and its copy in a program's temporaries).
    Returns (engine, compiled programs, memory plan); the compiled
    programs are the ones the engine then runs."""
    limit = _hbm_limit()
    num_blocks = None
    while True:
        eng = ServingEngine(model, params, batch_size=batch,
                            max_seq=max_seq, num_blocks=num_blocks,
                            use_kernel=True, prefill_chunk=prefill_chunk,
                            prefill_budget=prefill_budget, tracer=tracer)
        if not eng.paged:
            return eng, {}, None
        compiled = eng.compile_programs()
        plan = plan_memory(eng, compiled)
        plan["hbm"] = limit
        if limit is None or plan["peak"] <= HBM_BUDGET * limit:
            return eng, compiled, plan
        per_block = plan["pool"] / plan["num_blocks"]
        over = plan["peak"] - HBM_BUDGET * limit
        num_blocks = plan["num_blocks"] - int(-(-over // (2 * per_block)))
        if num_blocks < batch + 1:
            raise RuntimeError(
                f"batch {batch} x max_seq {max_seq} does not fit: peak "
                f"program {plan['peak'] / 1e9:.2f} GB of "
                f"{limit / 1e9:.2f} GB with the smallest pool")
        del eng, compiled


def describe_plan(plan) -> str:
    if plan is None:
        return "memory: stripe cache, no ahead-of-time sizing"
    gb = lambda n: f"{n / 1e9:.2f} GB"                     # noqa: E731
    hbm = (f"{gb(plan['hbm'])} device memory, budget {HBM_BUDGET:.0%}"
           if plan["hbm"] else "device memory not reported")
    return (f"memory: params {gb(plan['params'])}, KV pool "
            f"{plan['num_blocks']} blocks {gb(plan['pool'])}; per program "
            f"(args + unaliased outputs + temps, + pool at admit): decode "
            f"{gb(plan['decode'])}, chunk {gb(plan['chunk'])}, admit "
            f"{gb(plan['admit'])}; peak {gb(plan['peak'])} of {hbm}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the 2-layer float32 reduced config (CPU "
                         "runs and CI) instead of the published widths")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=None,
                    help="engine slots (default: 8 published, 4 reduced)")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=None,
                    help="tokens per slot (default: 2048 published, 128 "
                         "reduced)")
    ap.add_argument("--policy", default="fifo", choices=POLICIES)
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="per-request deadline; 0 = no SLO")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill width (default: engine auto; "
                         "0 = monolithic admission)")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="per-tick prefill token budget (chunk "
                         "continuation + new admissions)")
    ap.add_argument("--stream", action="store_true",
                    help="serve through the async dispatch/plan-ahead/"
                         "commit loop with per-token streaming (reports "
                         "TTFT and host/device overlap)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record request lifecycles, tick phases, and "
                         "pool events to a Chrome trace-event JSON "
                         "(open in Perfetto / chrome://tracing)")
    args = ap.parse_args()

    tracer = None
    if args.trace_out:
        from repro.serve.telemetry import Tracer
        tracer = Tracer()

    print(f"compile cache: {compile_cache.enable()}")
    mode = "reduced" if args.reduced else "published"
    batch = args.batch or DEFAULT_SHAPE[mode]["batch"]
    max_seq = args.max_seq or DEFAULT_SHAPE[mode]["max_seq"]
    cfg, model, params = load_model(args.arch, reduced=args.reduced)
    eng, _, plan = build_engine(model, params, batch=batch,
                                max_seq=max_seq,
                                prefill_chunk=args.prefill_chunk,
                                prefill_budget=args.prefill_budget,
                                tracer=tracer)
    print(describe_plan(plan))

    sched = Scheduler(eng, policy=args.policy,
                      prefill_budget=args.prefill_budget)

    import time
    rng = jax.random.key(1)
    reqs = []
    for i in range(args.requests):
        rng, k = jax.random.split(rng)
        # mixed prompt lengths exercise per-slot decode
        plen = max(2, args.prompt_len - (i % 4) * 2)
        prompt = jax.random.randint(k, (plen,), 2,
                                    cfg.vocab_size).tolist()
        deadline = (time.perf_counter() + args.slo_ms / 1e3
                    if args.slo_ms else None)
        reqs.append(Request(rid=i, prompt=prompt, deadline_s=deadline,
                            max_new_tokens=args.max_new))

    print(f"serving {args.requests} requests on {args.arch} "
          f"({cfg.family}, {mode}: {cfg.n_layers} x {cfg.d_model}, "
          f"{jax.numpy.dtype(cfg.dtype).name}) on "
          f"{jax.devices()[0].device_kind} — engine batch {batch}, "
          f"policy {args.policy}"
          + (" — async streaming loop" if args.stream else ""))
    if args.stream:
        from repro.serve.async_loop import AsyncServeLoop
        loop = AsyncServeLoop(sched, name=f"{args.arch}/0")
        ttft: dict = {}
        handles = []
        for r in reqs:
            def _first(tok, logp, rid=r.rid, t0=time.perf_counter()):
                ttft.setdefault(rid, time.perf_counter() - t0)
            handles.append(loop.submit(r, _first))
        done = []
        for h in handles:
            try:
                loop.wait(h)
                done.append(h.request)
            except Exception as e:  # shed / queue full
                print(f"  req {h.rid}: {e}")
        if ttft:
            print(f"TTFT p50={statistics.median(ttft.values())*1e3:.0f}ms "
                  f"max={max(ttft.values())*1e3:.0f}ms; "
                  f"loop: {loop.metrics['ticks']} ticks, "
                  f"{loop.metrics['planned']} admissions planned in-flight "
                  f"(plan {loop.metrics['plan_time_s']*1e3:.0f}ms hidden "
                  f"behind {loop.metrics['commit_wait_s']*1e3:.0f}ms of "
                  f"device wait)")
    else:
        for r in reqs:
            sched.submit(r)
        done = sched.drain()
    lats = [r.latency_s for r in done]
    toks = sum(len(r.out_tokens) for r in done)
    if lats:
        print(f"completed {len(done)}; {toks} tokens; "
              f"latency p50={statistics.median(lats)*1e3:.0f}ms "
              f"max={max(lats)*1e3:.0f}ms; "
              f"queue wait mean={sched.stats.mean_queue_wait_s()*1e3:.0f}ms")
    else:
        print("completed 0 (all requests shed past their deadline)")
    print(f"engine metrics: {eng.metrics}")
    if args.slo_ms:
        print(f"SLO: hits={sched.stats.slo_hits} "
              f"misses={sched.stats.slo_misses} shed={sched.stats.shed} "
              f"rejected={sched.stats.rejected}")
    for r in done[:3]:
        print(f"  req {r.rid}: out={r.out_tokens}")
    assert len(done) + sched.stats.shed + sched.stats.rejected \
        == args.requests
    if tracer is not None:
        n = tracer.write_chrome_trace(args.trace_out)
        print(f"trace: {n} events -> {args.trace_out}"
              + (f" ({tracer.dropped} dropped)" if tracer.dropped else ""))
    print("OK")


if __name__ == "__main__":
    main()
