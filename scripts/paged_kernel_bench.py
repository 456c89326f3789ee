#!/usr/bin/env python
"""Time the paged-attention kernel alone on a TPU, against the jnp
gather path (``use_kernel=False``), at the serving cells' shapes.

    python scripts/paged_kernel_bench.py [--reps N] [--out FILE]

Shapes: the cells' engine (batch 16, 1024 tokens per slot, 16-token
pages, bfloat16 pool) at Qwen3-4B widths (32 query heads on 8 KV heads
of 128) and DeepSeek-LLM-7B widths (32 on 32), for plain decode (S = 1)
and a 64-token chunk window. Row lengths come from a fixed seed: decode
rows hold 64-512 tokens, chunk rows start their window anywhere in
0-832; a second set fills every table. Each timing is one jitted loop of
``layers`` dependent calls (one per layer of the cell's model), median
of ``--reps`` runs, reported per call. The gather path is the read the
engine runs without the kernel: gather the pool through the table, then
masked attention over the whole table. The kernel also writes each
window's K/V into the pool (here the values the pool already holds, so
every call sees the same pool).

One JSON line per case: milliseconds per call for each path, the
largest difference between the paths' outputs, the share of table
pages the rows hold, and the HBM floor of the held K/V bytes at 819
GB/s (TPU v5e). Refuses to run off the TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import kernel as paged_kernel
from repro.models.attention import (_gather_pool, decode_attention,
                                    verify_decode_attention)

BATCH, MAX_SEQ, BLOCK = 16, 1024, 16
HBM_BYTES_PER_S = 819e9                       # TPU v5e (Google Cloud)
MODELS = {"qwen3-4b": (32, 8, 128, 36), "deepseek-7b": (32, 32, 128, 15)}


def lengths(kind: str, S: int, seed: int = 0) -> np.ndarray:
    """Per-row base lengths (tokens resident before the window)."""
    rng = np.random.default_rng(seed)
    if kind == "full":
        return np.full(BATCH, MAX_SEQ - S, np.int32)
    if S == 1:
        return rng.integers(64, 513, BATCH).astype(np.int32) - 1
    return rng.integers(0, MAX_SEQ - 3 * S + 1, BATCH).astype(np.int32)


def inputs(Hq: int, Hkv: int, hd: int, S: int, base: np.ndarray):
    mb = MAX_SEQ // BLOCK
    nb = BATCH * mb + 1
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (BATCH, S, Hq, hd), jnp.bfloat16)
    # a one-layer stack, the kernel's operand
    pk = jax.random.normal(ks[1], (1, nb, Hkv, BLOCK, hd), jnp.bfloat16)
    pv = jax.random.normal(ks[2], (1, nb, Hkv, BLOCK, hd), jnp.bfloat16)
    # each row owns its own pages, in a shuffled order; tails at scratch 0
    perm = np.random.default_rng(1).permutation(np.arange(1, nb))
    table = np.zeros((BATCH, mb), np.int32)
    for b in range(BATCH):
        n = -(-int(base[b] + S) // BLOCK)
        table[b, :n] = perm[b * mb:b * mb + n]
    return q, pk, pv, jnp.asarray(table), jnp.asarray(base)


def held(pool, table, base, S):
    """What layer 0 of the pool holds at each row's window positions."""
    pos = base[:, None] + jnp.arange(S)[None]
    phys = table[jnp.arange(BATCH)[:, None], pos // BLOCK]
    return pool[0, phys, :, pos % BLOCK]


def kernel_read(q, pk, pv, table, base):
    S = q.shape[1]
    out, _, pk, pv = paged_kernel.paged_window_attention(
        q, held(pk, table, base, S), held(pv, table, base, S), pk, pv,
        table, base, 0, jnp.full((BATCH,), S, jnp.int32), interpret=False)
    return out, pk, pv


def gather_read(q, pk, pv, table, base):
    k, v = _gather_pool(pk[0], table), _gather_pool(pv[0], table)
    if q.shape[1] == 1:
        out = decode_attention(q, k, v, base + 1)
    else:
        out = verify_decode_attention(q, k, v, base)
    return out.reshape(q.shape), pk, pv


def per_call_ms(read, args, layers: int, reps: int) -> float:
    """Median over ``reps`` of one jitted loop of ``layers`` calls, each
    fed the last one's output (and pool) so none can be hoisted or
    skipped."""
    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def run(q, pk, pv, table, base):
        def body(_, carry):
            q, pk, pv = carry
            out, pk, pv = read(q, pk, pv, table, base)
            return q + (out * 1e-3).astype(q.dtype), pk, pv
        return jax.lax.fori_loop(0, layers, body, (q, pk, pv))

    q, pk, pv, table, base = args
    pk, pv = jnp.copy(pk), jnp.copy(pv)          # the loop donates its own
    q, pk, pv = run(q, pk, pv, table, base)              # compile, warm
    q.block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        q, pk, pv = run(q, pk, pv, table, base)
        q.block_until_ready()
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times) / layers


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--paths", default="kernel,gather")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    reads = {"kernel": kernel_read, "gather": gather_read}
    lines = []
    for model, (Hq, Hkv, hd, layers) in MODELS.items():
        for S in (1, 64):
            for kind in ("cell", "full"):
                base = lengths(kind, S)
                a = inputs(Hq, Hkv, hd, S, base)
                held = np.minimum(-(-(base + S) // BLOCK), MAX_SEQ // BLOCK)
                kv_bytes = int(held.sum()) * BLOCK * Hkv * hd * 2 * 2
                row = {"model": model, "S": S, "rows": kind,
                       "held_share": float(held.sum())
                       / (BATCH * (MAX_SEQ // BLOCK)),
                       "hbm_floor_ms": 1e3 * kv_bytes / HBM_BYTES_PER_S,
                       "device": dev.device_kind}
                row["P_hg"] = paged_kernel.tile_plan(
                    Hkv=Hkv, bs=BLOCK, hd=hd, S=S, G=Hq // Hkv, itemsize=2,
                    max_blocks=MAX_SEQ // BLOCK)
                paths = args.paths.split(",")
                for path in paths:
                    row[f"{path}_ms"] = per_call_ms(reads[path], a, layers,
                                                    args.reps)
                if len(paths) > 1:
                    outs = [np.float32(jax.jit(reads[p])(*a)[0])
                            for p in paths]
                    row["max_abs_diff"] = float(np.abs(outs[0] - outs[1]).max())
                print(json.dumps(row), flush=True)
                lines.append(row)
    if args.out:
        with open(args.out, "w") as f:
            for row in lines:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
