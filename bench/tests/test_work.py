"""The work counts and the roofline arithmetic."""
import pytest

from bench import spec
from bench.work import Dense, Tally, roofline_share

PEAKS = spec.peaks("TPU v5 lite")


def qwen():
    return Dense.from_config(spec.config("qwen3-4b"))


def test_sizes_from_the_configuration_files():
    q = qwen()
    assert (q.n_layers, q.d_model, q.n_heads, q.n_kv_heads, q.head_dim,
            q.d_ff, q.vocab, q.dtype_bytes) == (36, 2560, 32, 8, 128, 9728,
                                                151936, 2)
    d = Dense.from_config(spec.config("deepseek-7b"))
    assert (d.n_layers, d.n_kv_heads, d.head_dim) == (15, 32, 128)
    # matmul parameters per layer: q and o 2 x d x 4096, kv d x 2048,
    # gated MLP 3 x d x 9728
    assert q.layer_matmul_params == 2 * 2560 * 4096 + 2560 * 2048 \
        + 3 * 2560 * 9728


def test_kernel_work_counts_held_tokens_in_bf16():
    q = qwen()
    # one decode query at context 100: 4 * 100 * 32 * 128 FLOPs per layer
    assert q.attn_flops(100) == 4 * 100 * 32 * 128 * 36
    # K and V of 100 held tokens on 8 heads, q and out of one query on 32
    assert q.kernel_bytes(100, 1) == (2 * 100 * 8 * 128
                                      + 2 * 1 * 32 * 128) * 2 * 36


def test_tally_of_a_chunk_row():
    q = qwen()
    t = Tally()
    t.add_row(q, [65, 66, 67], logits=1)
    assert t.queries == 3
    assert t.attn_flops == sum(q.attn_flops(c) for c in (65, 66, 67))
    assert t.kernel_bytes == q.kernel_bytes(67, 3)
    assert t.model_flops == sum(q.token_flops(c, False) for c in (65, 66, 67)) \
        + 2 * 2560 * 151936


def test_roofline_share_takes_the_binding_bound():
    # 819 GB in one second is the memory roof: 100%
    share, bound = roofline_share(1.0, 819e9, 1.0, PEAKS)
    assert share == pytest.approx(100.0) and bound == "memory"
    # 197 TFLOP in two seconds: half the compute roof
    share, bound = roofline_share(197e12, 1.0, 2.0, PEAKS)
    assert share == pytest.approx(50.0) and bound == "compute"


def test_a_device_missing_from_the_peaks_table_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v4")
