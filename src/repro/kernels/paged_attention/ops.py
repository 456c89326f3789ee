"""Public ops for fused paged attention (block-table in-place reads).

One Pallas kernel body serves every paged consumer — plain decode
(``paged_decode_attention``, the q_len = 1 degenerate case),
speculative verify, and chunked prefill windows
(``paged_window_attention``, q_len > 1 with causal-in-window masking
and per-row base lengths). On TPU the kernel runs compiled; everywhere
else it runs in interpret mode so the *same* kernel body is what CI
exercises — the differential grids in ``tests/test_kernels.py`` hold it
bit-exact (f32) against the streaming oracles in ``ref.py`` and
tolerance-close to the independent gather oracles.
"""
from __future__ import annotations

import jax

from repro.kernels.paged_attention.kernel import (
    paged_decode_attention as _decode_kernel,
    paged_window_attention as _window_kernel)
from repro.kernels.paged_attention.ref import (gathered_decode_ref,
                                               gathered_window_ref,
                                               paged_decode_attention_ref,
                                               paged_window_attention_ref)

__all__ = ["paged_decode_attention", "paged_decode_attention_ref",
           "paged_window_attention", "paged_window_attention_ref",
           "gathered_decode_ref", "gathered_window_ref"]


def paged_decode_attention(q, pool_k, pool_v, block_table, lengths, *,
                           sliding_window: int = 0, force_ref: bool = False):
    """q (B,Hq,hd); pool_k/pool_v (num_blocks, Hkv, bs, hd); block_table
    (B, max_blocks) int32; lengths (B,) valid tokens per row (new token
    already scattered). Returns (out (B,Hq,hd), lse (B,Hq) f32)."""
    if force_ref:
        return paged_decode_attention_ref(q, pool_k, pool_v, block_table,
                                          lengths,
                                          sliding_window=sliding_window)
    on_tpu = jax.default_backend() == "tpu"
    return _decode_kernel(q, pool_k, pool_v, block_table, lengths,
                          sliding_window=sliding_window, interpret=not on_tpu)


def paged_window_attention(q, pool_k, pool_v, block_table, base_lens, *,
                           sliding_window: int = 0, force_ref: bool = False):
    """Fused multi-token window: q (B,S,Hq,hd) at absolute positions
    ``base_lens[b] + [0, S)`` (K/V already scattered — diverted writes
    landed in scratch and are masked by causality for every position
    the caller commits); base_lens (B,) int32 tokens resident per row
    before the window. Returns (out (B,S,Hq,hd), lse (B,S,Hq) f32)."""
    if force_ref:
        return paged_window_attention_ref(q, pool_k, pool_v, block_table,
                                          base_lens,
                                          sliding_window=sliding_window)
    on_tpu = jax.default_backend() == "tpu"
    return _window_kernel(q, pool_k, pool_v, block_table, base_lens,
                          sliding_window=sliding_window, interpret=not on_tpu)
