"""Public ops for fused paged attention (block-table in-place reads and
writes).

One Pallas kernel body serves every paged consumer — plain decode
(``paged_decode_attention``, the q_len = 1 degenerate case),
speculative verify, and chunked prefill windows
(``paged_window_attention``, q_len > 1 with causal-in-window masking
and per-row base lengths). Each call takes the whole stacked pool and a
layer index, writes the window's K/V into that layer's pages in place
and reads the pages each row holds. On TPU the kernel runs compiled;
everywhere else it runs in interpret mode so the *same* kernel body is
what CI exercises — the differential grids in ``tests/test_kernels.py``
hold it bit-exact (f32) against the streaming oracles in ``ref.py`` and
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


def paged_decode_attention(q, k_new, v_new, pool_k, pool_v, block_table,
                           lengths, layer, *, sliding_window: int = 0,
                           scale=None):
    """q (B,Hq,hd); k_new/v_new (B,Hkv,hd) the new token's K/V, written
    at position ``lengths - 1`` of layer ``layer`` of pool_k/pool_v (L,
    num_blocks, Hkv, bs, hd); block_table (B, max_blocks) int32; lengths
    (B,) valid tokens per row, the new one included; ``scale`` the
    softmax scale (None: 1/sqrt(hd)). Returns (out (B,Hq,hd), lse (B,Hq)
    f32, pool_k, pool_v)."""
    on_tpu = jax.default_backend() == "tpu"
    return _decode_kernel(q, k_new, v_new, pool_k, pool_v, block_table,
                          lengths, layer, sliding_window=sliding_window,
                          interpret=not on_tpu, scale=scale)


def paged_window_attention(q, k_new, v_new, pool_k, pool_v, block_table,
                           base_lens, layer, n_write, *,
                           sliding_window: int = 0, scale=None):
    """Fused multi-token window: q (B,S,Hq,hd) at absolute positions
    ``base_lens[b] + [0, S)``, k_new/v_new (B,S,Hkv,hd) their K/V, of
    which row b writes its first ``n_write[b]`` into layer ``layer`` of
    the stacked pools (the rest read whatever their pages hold, and are
    masked by causality for every position the caller commits);
    base_lens (B,) int32 tokens resident per row before the window.
    Returns (out (B,S,Hq,hd), lse (B,S,Hq) f32, pool_k, pool_v)."""
    on_tpu = jax.default_backend() == "tpu"
    return _window_kernel(q, k_new, v_new, pool_k, pool_v, block_table,
                          base_lens, layer, n_write,
                          sliding_window=sliding_window, interpret=not on_tpu,
                          scale=scale)
