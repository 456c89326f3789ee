#!/usr/bin/env python3
"""Smoke test of the serving stack on a TPU, through its normal entry
points.

    python chip_smoke.py             # one chip: Qwen3-4B, published width
    python chip_smoke.py --chips 4   # four chips: the paper's parallel
                                     # NER services, one per chip
    JAX_PLATFORMS=cpu python chip_smoke.py --reduced [--chips 4]
                                     # every phase at reduced size on the
                                     # CPU; reports no result

One chip: the engine is built exactly as ``repro.launch.serve`` builds it
(36 layers x d 2560 in bf16, seeded random weights, paged KV, the Pallas
paged-attention kernel, the pool sized from the compiled programs'
``memory_analysis()``). Phases:

1. ``engine``: the decode program must contain the compiled kernel
   (``tpu_custom_call``);
2. ``kernel``: the kernel at the served widths must write each
   window's K/V into the pool where a plain scatter does, and match the
   gather-then-softmax oracle ``gathered_window_ref`` for windows of 1, 4
   and 64 tokens, within ``TOLERANCE``;
3. ``serve``: eight requests with prompts of 256-448 tokens (chunked
   prefill, 64-token chunks) and 32-48 generated tokens each, greedy and
   sampled mixed, go through ``Scheduler`` and ``AsyncServeLoop``; every
   request must complete with the tokens it asked for.

Four chips (``--chips 4``, that phase alone): four of the CV parser's
per-section NER models (``core.pipeline.NERModel`` at their configured
``LANConfig`` widths), one service per chip behind
``core.multimodel.MultiModelServer``. ``serve_parallel`` must equal
``serve_sequential`` and each model's own prediction, and the four
outputs must live on four different devices.

TTFT and inter-token gaps are printed as smoke timings, not metrics. The
last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed at published width on a TPU. Off the chip the script exits
non-zero without it; any failed check raises. It runs in one process and
starts none.
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-4b"
# (requests, prompt lengths, new tokens, prefill chunk) per size
SERVE = {"published": (8, (256, 320, 384, 448), (32, 48), 64),
         "reduced": (8, (24, 32, 40, 48), (6, 8), 8)}
WINDOWS = (1, 4, 64)
# kernel vs gather oracle, max |a - b| <= tol * (1 + |b|): bf16 pools and
# a bf16 output round at 2^-8 relative, and the compiled kernel may run
# its float32 products through single-pass bf16 on the MXU
TOLERANCE = {"bfloat16": 2e-2, "float32": 1e-4}


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _gb(n) -> str:
    return "not reported" if n is None else f"{n / 1e9:.2f} GB"


# ------------------------------------------------------------- one chip
def engine_phase(args, on_chip: bool):
    import jax

    from repro.launch.serve import (DEFAULT_SHAPE, build_engine,
                                    describe_plan, load_model)
    mode = "reduced" if args.reduced else "published"
    cfg, model, params = load_model(ARCH, reduced=args.reduced,
                                    seed=args.seed)
    print(f"arch {ARCH} ({mode}): {cfg.n_layers} layers x d {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.hd}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{jax.numpy.dtype(cfg.dtype).name}")
    shape = DEFAULT_SHAPE[mode]
    eng, compiled, plan = build_engine(model, params,
                                       batch=shape["batch"],
                                       max_seq=shape["max_seq"],
                                       prefill_chunk=SERVE[mode][3])
    print(f"engine: batch {eng.B}, max_seq {eng.max_seq}, block "
          f"{eng.block_size}, kernel {eng.use_kernel}")
    print(describe_plan(plan))
    has_kernel = "tpu_custom_call" in compiled["decode"].as_text()
    if on_chip:
        check(has_kernel, "decode program holds no tpu_custom_call: the "
                          "paged kernel is not in it")
    print(f"phase engine: ok (decode program tpu_custom_call: {has_kernel}"
          + ("" if on_chip else "; interpret mode off the chip") + ")")
    return cfg, eng


def _window_case(cfg, eng, S: int, seed: int):
    """Random q, the window's new K/V and a head-major one-layer pool at
    the engine's widths, disjoint per-row block tables and ragged base
    lengths with room for S."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    B, bs, mb = eng.B, eng.block_size, eng.blocks_per_slot
    nb = B * mb + 1
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, S, cfg.n_heads, cfg.hd), cfg.dtype)
    new = (B, S, cfg.n_kv_heads, cfg.hd)
    kn = jax.random.normal(ks[3], new, cfg.dtype)
    vn = jax.random.normal(ks[4], new, cfg.dtype)
    pool = (1, nb, cfg.n_kv_heads, bs, cfg.hd)
    pk = jax.random.normal(ks[1], pool, cfg.dtype)
    pv = jax.random.normal(ks[2], pool, cfg.dtype)
    rng = np.random.default_rng(seed)
    free = list(rng.permutation(np.arange(1, nb)))
    base = rng.integers(0, mb * bs - S + 1, size=B).astype(np.int32)
    table = np.zeros((B, mb), np.int32)
    for b in range(B):
        for i in range(-(-(int(base[b]) + S) // bs)):
            table[b, i] = free.pop()
    return q, kn, vn, pk, pv, jnp.asarray(table), jnp.asarray(base)


def kernel_phase(cfg, eng, seed: int) -> None:
    import jax
    import numpy as np

    from repro.kernels.paged_attention.ops import paged_window_attention
    from repro.kernels.paged_attention.ref import gathered_window_ref
    tol = TOLERANCE[jax.numpy.dtype(cfg.dtype).name]
    for S in WINDOWS:
        q, kn, vn, pk, pv, table, base = _window_case(cfg, eng, S, seed + S)
        B, bs = q.shape[0], pk.shape[3]
        out, lse, wk, wv = paged_window_attention(
            q, kn, vn, pk, pv, table, base, 0, np.full(B, S, np.int32))
        # the same writes by a plain scatter, then the gather oracle
        pos = np.asarray(base)[:, None] + np.arange(S)[None]
        phys = np.asarray(table)[np.arange(B)[:, None], pos // bs]
        pk = pk.at[0, phys, :, pos % bs].set(kn)
        pv = pv.at[0, phys, :, pos % bs].set(vn)
        for got, want in ((wk, pk), (wv, pv)):
            check(np.array_equal(np.asarray(got), np.asarray(want)),
                  f"kernel S={S}: the pool it wrote differs from the "
                  "scatter's")
        with jax.default_matmul_precision("highest"):
            ref_out, ref_lse = gathered_window_ref(q, pk[0], pv[0], table,
                                                   base)
        errs = []
        for a, b in ((out, ref_out), (lse, ref_lse)):
            a, b = np.float32(a), np.float32(b)
            check(np.all(np.isfinite(a)), f"kernel S={S}: non-finite")
            errs.append(float(np.max(np.abs(a - b) / (1 + np.abs(b)))))
        print(f"  kernel S={S}: q {tuple(q.shape)}, pool "
              f"{tuple(pk.shape)}; max |kernel - gather| / (1 + "
              f"|gather|): out {errs[0]:.2e}, lse {errs[1]:.2e} "
              f"(tolerance {tol:g})")
        check(max(errs) <= tol, f"kernel S={S} differs from the gather "
                                f"oracle: {errs} > {tol}")
    print(f"phase kernel: ok (windows {list(WINDOWS)})")


def serve_phase(cfg, eng, args) -> None:
    import jax

    from repro.serve.async_loop import AsyncServeLoop
    from repro.serve.engine import Request
    from repro.serve.sampling import SamplingParams
    from repro.serve.scheduler import Scheduler
    n, plens, news, chunk = SERVE["reduced" if args.reduced else "published"]
    loop = AsyncServeLoop(Scheduler(eng), name=f"{ARCH}/0")
    key = jax.random.key(args.seed + 1)
    stamps: dict = {}
    handles = []
    t_submit = time.perf_counter()
    for i in range(n):
        key, k = jax.random.split(key)
        plen = plens[i % len(plens)]
        prompt = jax.random.randint(k, (plen,), 2, cfg.vocab_size).tolist()
        samp = (SamplingParams(temperature=0.8, top_k=50, seed=i) if i % 2
                else SamplingParams())
        req = Request(rid=i, prompt=prompt, sampling=samp,
                      max_new_tokens=news[i % len(news)])
        stamps[i] = []
        handles.append(loop.submit(
            req, lambda tok, lp, rid=i: stamps[rid].append(
                time.perf_counter())))
    replies = {h.rid: loop.wait(h) for h in handles}
    wall = time.perf_counter() - t_submit
    tokens = 0
    for h in handles:
        want = h.request.max_new_tokens
        got = replies[h.rid]["tokens"]
        check(len(got) == want, f"request {h.rid}: {len(got)} of {want} "
                                f"tokens")
        check(len(stamps[h.rid]) == want,
              f"request {h.rid}: streamed {len(stamps[h.rid])} of {want}")
        tokens += len(got)
    m = eng.metrics
    check(m["chunked_admissions"] > 0, "no prompt was admitted in chunks")
    check(m["blocks_grown"] > 0, "no slot grew its block table")
    check(eng.pool.available == eng.pool.total, "blocks leaked")
    ttft = [s[0] - t_submit for s in stamps.values()]
    gaps = [b - a for s in stamps.values() for a, b in zip(s, s[1:])]
    print(f"  served {len(handles)} requests, {tokens} tokens in "
          f"{wall:.2f} s; prompts {list(plens)} tokens, chunk {chunk}; "
          f"{m['chunk_steps']} chunk steps, {m['decode_steps']} steps, "
          f"{m['blocks_grown']} blocks grown")
    print(f"  smoke timings (first run, not metrics): TTFT median "
          f"{statistics.median(ttft) * 1e3:.1f} ms, max "
          f"{max(ttft) * 1e3:.1f} ms; inter-token gap median "
          f"{statistics.median(gaps) * 1e3:.2f} ms, max "
          f"{max(gaps) * 1e3:.2f} ms")
    print("phase serve: ok (every request complete)")


# ----------------------------------------------------------- four chips
def _ner_step(params, ids, *, cfg):
    from repro.models import bilstm_lan
    return bilstm_lan.predict(params, cfg, ids)


def ner_phase(args) -> None:
    import jax
    import numpy as np

    from repro.core import cvdata, router
    from repro.core.multimodel import ModelService, MultiModelServer
    from repro.core.pipeline import MAX_SENT_LEN, NERModel
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices; JAX found "
                             f"{len(devices)}")
    names = list(router.ROUTES)[:4]
    keys = jax.random.split(jax.random.key(args.seed), len(names))
    ners = [NERModel.create(name, k) for name, k in zip(names, keys)]
    docs = cvdata.make_corpus(8 if args.reduced else 64, seed=args.seed)
    sectioned: dict = {s: [] for s in router.SECTIONS}
    for doc in docs:
        for sent in doc.sentences:
            sectioned[sent.section].append(sent.tokens)
    routed = router.route(sectioned)
    batches = {}
    for ner in ners:
        sents = routed[ner.name]
        rows = max(8, 1 << (len(sents) - 1).bit_length())
        ids = np.zeros((rows, MAX_SENT_LEN), np.int32)
        for i, s in enumerate(sents):
            ids[i] = ner.tokenizer.pad(ner.tokenizer.encode(s), MAX_SENT_LEN)
        batches[ner.name] = ids
    server = MultiModelServer(
        [ModelService(n.name, functools.partial(_ner_step, cfg=n.cfg),
                      n.params) for n in ners], devices=devices[:4])
    par, t_cold = server.serve_parallel(batches)
    seq, t_seq = server.serve_sequential(batches)
    par, t_par = server.serve_parallel(batches)
    homes = {}
    for ner in ners:
        p, s = np.asarray(par[ner.name]), np.asarray(seq[ner.name])
        own = np.asarray(ner._predict(ner.params, batches[ner.name]))
        check(np.array_equal(p, s), f"{ner.name}: parallel != sequential")
        check(np.array_equal(p, own), f"{ner.name}: parallel != the "
                                      f"model's own prediction")
        devs = par[ner.name].devices()
        check(len(devs) == 1, f"{ner.name}: output on {len(devs)} devices")
        homes[ner.name] = next(iter(devs))
        print(f"  {ner.name}: {len(routed[ner.name])} sentences "
              f"({batches[ner.name].shape[0]} rows), d_model "
              f"{ner.cfg.d_model}, on {homes[ner.name]}")
    check(len(set(homes.values())) == len(ners),
          f"services share devices: {homes}")
    print(f"  smoke timings (not metrics): parallel {t_par * 1e3:.2f} ms, "
          f"sequential {t_seq * 1e3:.2f} ms, first parallel call "
          f"{t_cold:.2f} s")
    print(f"phase multimodel: ok (parallel == sequential == own predict; "
          f"{len(set(homes.values()))} distinct devices)")


# ---------------------------------------------------------- entry point
def run(args, on_chip: bool) -> None:
    """Every phase of the selected path; raises SmokeFailure on a failed
    check."""
    if args.chips == 4:
        ner_phase(args)
        return
    cfg, eng = engine_phase(args, on_chip)
    kernel_phase(cfg, eng, args.seed)
    serve_phase(cfg, eng, args)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the four-chip parallel-services phase only")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced sizes for a CPU rehearsal (reports no "
                         "result)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.reduced:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform}); "
              f"nothing run", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}")
    from repro import compile_cache
    if on_chip:
        print(f"compile cache: {compile_cache.enable()}")
    with compile_cache.CompileLog() as log:
        run(args, on_chip)
    print(f"compiles: {log.summary()}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {_gb(stats.get('peak_bytes_in_use'))} of "
          f"{_gb(stats.get('bytes_limit'))}")
    if not on_chip or args.reduced:
        print("chip_smoke: every phase passed, but not at published width "
              "on a TPU: no result", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
