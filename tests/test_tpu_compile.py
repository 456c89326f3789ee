"""Compile-only rehearsal for one TPU v5e chip at Qwen3-4B's published
widths: the paged-attention kernel and the full 36-layer serving steps
are compiled by the TPU compiler for a described (not attached) v5e.
Nothing runs; these tests catch what interpret mode cannot — a block
shape that breaks Mosaic's tiling, a kernel missing from the program, a
step that does not fit the chip's memory.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and the test workers each import
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.paged_attention.kernel import paged_window_attention
from repro.models.model import build_model
from repro.serve.engine import paged_program_args, paged_programs

# one v5e chip's HBM (Google Cloud, "TPU v5e"): 16 GB
V5E_HBM_BYTES = 16e9
# the served shape: engine slots, tokens per slot, KV block, chunk width
BATCH, MAX_SEQ, BLOCK, CHUNK = 8, 2048, 16, 64


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branch(monkeypatch):
    """The kernel ops pick compiled vs interpret from
    ``jax.default_backend()``, which is the CPU here; point it at the
    described chip while tracing for it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("S", [1, 4, 64])
def test_paged_kernel_compiles_at_qwen3_4b_widths(one_chip, S):
    """Decode (S=1), speculative verify (S=4) and a chunk window (S=64)
    against a head-major bf16 pool of 128 blocks per row."""
    cfg = get_config("qwen3-4b")
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    mb = MAX_SEQ // BLOCK
    nb = BATCH * mb + 1
    pool = _sds((nb, Hkv, BLOCK, hd), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v, t, b: paged_window_attention(
        q, k, v, t, b, interpret=False))
    compiled = fn.lower(_sds((BATCH, S, Hq, hd), jnp.bfloat16, one_chip),
                        pool, pool, _sds((BATCH, mb), jnp.int32, one_chip),
                        _sds((BATCH,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def serving_args(one_chip):
    """Qwen3-4B parameters and the KV pool as sharded shape stand-ins
    (``jax.eval_shape``: nothing is allocated)."""
    cfg = get_config("qwen3-4b")
    model = build_model(cfg)
    put = lambda tree: jax.tree.map(                       # noqa: E731
        lambda x: _sds(x.shape, x.dtype, one_chip), tree)
    params = put(jax.eval_shape(model.init, jax.random.key(0)))
    num_blocks = BATCH * (MAX_SEQ // BLOCK) + 1
    caches = put(jax.eval_shape(
        lambda: model.init_paged_cache(num_blocks, BLOCK)))
    args = paged_program_args(params, caches, batch=BATCH,
                              blocks_per_slot=MAX_SEQ // BLOCK,
                              width=CHUNK, sharding=one_chip)
    return model, args


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_full_width_serving_step_compiles_and_fits(serving_args, tpu_branch,
                                                   program):
    """The engine's jitted paged decode step and chunk-window step at 36
    layers x d 2560 in bf16, batch 8 x 2048 tokens: the kernel is in the
    program and arguments plus temporaries fit one chip."""
    model, args = serving_args
    progs = paged_programs(model, block_size=BLOCK, use_kernel=True)
    compiled = progs[program].lower(*args[program]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (program, used)
