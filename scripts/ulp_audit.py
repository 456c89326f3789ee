#!/usr/bin/env python
"""ULP audit for the paged-attention kernels (decode + fused window).

    PYTHONPATH=src python scripts/ulp_audit.py [--out DIR] [--seeds N]

Runs the float32 differential grids — the same shape families the
pytest suite gates — in interpret mode and records the *measured*
maximum ULP distance between the Pallas kernel and the streaming jnp
oracle, per configuration, for both the attention output and the LSE.
The summary (JSON + markdown) is uploaded as a CI artifact so the
contract headroom is visible over time: the tests assert out <= 4 ulp
/ lse <= 32 ulp; this audit shows how close the toolchain actually
sits to those bounds (historically out is bitwise on nearly every
case and lse within ~16 ulp — see kernels/paged_attention/ref.py for
why universal bitwise equality is not contractable).

Exit code 1 if any case exceeds the contract — the audit is a gate,
not just a report.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

OUT_ULP, LSE_ULP = 4, 32

# B, Hq, Hkv, hd, bs, max_blocks, sliding_window  (float32 only: ULP
# distance against an f32 oracle is meaningless for bf16 outputs)
DECODE_GRID = [
    (1, 4, 1, 64, 16, 4, 0),
    (2, 8, 2, 64, 16, 4, 0),
    (3, 4, 4, 32, 8, 6, 0),
    (4, 2, 1, 128, 16, 5, 0),
    (2, 8, 8, 64, 8, 4, 0),
    (4, 4, 1, 64, 16, 5, 24),
]

# S, B, Hq, Hkv, hd, bs, max_blocks, sliding_window
WINDOW_GRID = [
    (1, 2, 8, 2, 64, 16, 4, 0),
    (2, 3, 4, 4, 32, 8, 6, 0),
    (4, 2, 8, 2, 64, 16, 4, 0),
    (4, 3, 4, 1, 64, 8, 6, 0),
    (8, 2, 4, 2, 64, 16, 4, 0),
    (8, 2, 4, 4, 32, 8, 8, 0),
    (4, 2, 8, 2, 64, 16, 5, 24),
]


def _ulp_key(x: np.ndarray) -> np.ndarray:
    """Map float32 bit patterns to a monotonic integer line so the ULP
    distance between any two finite floats (sign crossings included) is
    a plain integer difference; -0.0 and +0.0 both land on 0."""
    i = np.ascontiguousarray(np.float32(x)).view(np.int32).astype(np.int64)
    return np.where(i >= 0, i, np.int64(-2147483648) - i)


def ulp_max(a, b) -> int:
    return int(np.max(np.abs(_ulp_key(a) - _ulp_key(b)), initial=0))


def _decode_case(jax, jnp, B, Hq, Hkv, hd, bs, mb, seed):
    nb = B * mb + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
    pk = jax.random.normal(ks[1], (nb, Hkv, bs, hd), jnp.float32)
    pv = jax.random.normal(ks[2], (nb, Hkv, bs, hd), jnp.float32)
    rng = np.random.default_rng(seed + B * 1000 + hd)
    free = list(rng.permutation(np.arange(1, nb)))
    lens = np.zeros(B, np.int32)
    table = np.zeros((B, mb), np.int32)
    for b in range(B):
        lens[b] = int(rng.integers(1, mb * bs + 1))
        for i in range(-(-int(lens[b]) // bs)):
            table[b, i] = free.pop()
    return q, pk, pv, jnp.asarray(table), jnp.asarray(lens)


def _window_case(jax, jnp, B, S, Hq, Hkv, hd, bs, mb, seed):
    nb = B * mb + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd), jnp.float32)
    pk = jax.random.normal(ks[1], (nb, Hkv, bs, hd), jnp.float32)
    pv = jax.random.normal(ks[2], (nb, Hkv, bs, hd), jnp.float32)
    rng = np.random.default_rng(seed + B * 1000 + S * 100 + hd)
    free = list(rng.permutation(np.arange(1, nb)))
    base = np.zeros(B, np.int32)
    table = np.zeros((B, mb), np.int32)
    for b in range(B):
        base[b] = int(rng.integers(0, mb * bs - S + 1))
        for i in range(-(-int(base[b] + S) // bs)):
            table[b, i] = free.pop()
    return q, pk, pv, jnp.asarray(table), jnp.asarray(base)


def _held(jnp, pool, table, base, S):
    """What a one-layer pool holds at each row's positions ``base +
    [0, S)``: the kernel writes these back, so it reads the pool as
    built."""
    bs = pool.shape[2]
    pos = jnp.maximum(jnp.asarray(base)[:, None] + jnp.arange(S)[None], 0)
    phys = table[jnp.arange(table.shape[0])[:, None], pos // bs]
    return pool[phys, :, pos % bs]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, default=Path("experiments/ulp-audit"))
    ap.add_argument("--seeds", type=int, default=2)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_window_attention)
    from repro.kernels.paged_attention.ref import (
        paged_decode_attention_ref, paged_window_attention_ref)

    cases = []
    for B, Hq, Hkv, hd, bs, mb, win in DECODE_GRID:
        for seed in range(args.seeds):
            q, pk, pv, tb, ln = _decode_case(jax, jnp, B, Hq, Hkv, hd, bs,
                                             mb, seed)
            kn, vn = (_held(jnp, p, tb, ln - 1, 1)[:, 0] for p in (pk, pv))
            out, lse, _, _ = paged_decode_attention(
                q, kn, vn, pk[None], pv[None], tb, ln, 0,
                sliding_window=win)
            ro, rl = paged_decode_attention_ref(q, pk, pv, tb, ln,
                                                sliding_window=win)
            cases.append({
                "kind": "decode", "seed": seed, "sliding_window": win,
                "shape": f"B{B} Hq{Hq} Hkv{Hkv} hd{hd} bs{bs} mb{mb}",
                "out_ulp": ulp_max(out, ro), "lse_ulp": ulp_max(lse, rl)})
    for S, B, Hq, Hkv, hd, bs, mb, win in WINDOW_GRID:
        for seed in range(args.seeds):
            q, pk, pv, tb, base = _window_case(jax, jnp, B, S, Hq, Hkv, hd,
                                               bs, mb, seed)
            kn, vn = (_held(jnp, p, tb, base, S) for p in (pk, pv))
            out, lse, _, _ = paged_window_attention(
                q, kn, vn, pk[None], pv[None], tb, base, 0,
                jnp.full((B,), S, jnp.int32), sliding_window=win)
            ro, rl = paged_window_attention_ref(q, pk, pv, tb, base,
                                                sliding_window=win)
            cases.append({
                "kind": "window", "seed": seed, "sliding_window": win,
                "shape": f"S{S} B{B} Hq{Hq} Hkv{Hkv} hd{hd} bs{bs} mb{mb}",
                "out_ulp": ulp_max(out, ro), "lse_ulp": ulp_max(lse, rl)})

    worst_out = max(c["out_ulp"] for c in cases)
    worst_lse = max(c["lse_ulp"] for c in cases)
    ok = worst_out <= OUT_ULP and worst_lse <= LSE_ULP
    summary = {
        "contract": {"out_ulp": OUT_ULP, "lse_ulp": LSE_ULP},
        "worst": {"out_ulp": worst_out, "lse_ulp": worst_lse},
        "n_cases": len(cases), "ok": ok, "cases": cases,
    }
    args.out.mkdir(parents=True, exist_ok=True)
    (args.out / "ulp_audit.json").write_text(
        json.dumps(summary, indent=2) + "\n")
    lines = ["# Paged-attention ULP audit", "",
             f"Contract: out <= {OUT_ULP} ulp, lse <= {LSE_ULP} ulp "
             "(f32, interpret mode vs streaming oracle).", "",
             f"Worst observed: out {worst_out} ulp, lse {worst_lse} ulp "
             f"over {len(cases)} cases.", "",
             "| kind | shape | win | seed | out ulp | lse ulp |",
             "|------|-------|----:|-----:|--------:|--------:|"]
    lines += [f"| {c['kind']} | {c['shape']} | {c['sliding_window']} "
              f"| {c['seed']} | {c['out_ulp']} | {c['lse_ulp']} |"
              for c in cases]
    (args.out / "ulp_audit.md").write_text("\n".join(lines) + "\n")

    print(f"{len(cases)} cases: worst out {worst_out} ulp "
          f"(contract {OUT_ULP}), worst lse {worst_lse} ulp "
          f"(contract {LSE_ULP}) -> {args.out}")
    if not ok:
        print("ULP CONTRACT EXCEEDED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
