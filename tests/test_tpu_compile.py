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
import re

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


def _compile_paged_kernel(sharding, arch: str, batch: int, max_seq: int,
                          S: int) -> str:
    """HLO text of the paged kernel compiled for the described chip:
    ``batch`` rows of ``S`` window tokens, their K/V written in place
    into one layer of a head-major bf16 pool stacked over ``arch``'s
    layers, ``max_seq / BLOCK`` blocks per row at ``arch``'s widths."""
    cfg = get_config(arch)
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    mb = max_seq // BLOCK
    nb = batch * mb + 1
    pool = _sds((cfg.n_layers, nb, Hkv, BLOCK, hd), jnp.bfloat16, sharding)
    new = _sds((batch, S, Hkv, hd), jnp.bfloat16, sharding)
    rows = _sds((batch,), jnp.int32, sharding)
    fn = jax.jit(lambda q, kn, vn, k, v, t, b, l, n: paged_window_attention(
        q, kn, vn, k, v, t, b, l, n, interpret=False), donate_argnums=(3, 4))
    return fn.lower(_sds((batch, S, Hq, hd), jnp.bfloat16, sharding),
                    new, new, pool, pool,
                    _sds((batch, mb), jnp.int32, sharding), rows,
                    _sds((), jnp.int32, sharding), rows).compile().as_text()


@pytest.mark.parametrize("S", [1, 4, 64])
def test_paged_kernel_compiles_at_qwen3_4b_widths(one_chip, S):
    """Decode (S=1), speculative verify (S=4) and a chunk window (S=64)
    against a head-major bf16 pool of 128 blocks per row."""
    text = _compile_paged_kernel(one_chip, "qwen3-4b", BATCH, MAX_SEQ, S)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", ["qwen3-4b", "deepseek-7b"])
@pytest.mark.parametrize("S", [1, 64])
def test_paged_kernel_compiles_at_cell_shapes(one_chip, arch, S):
    """The benchmark cells' engine shape (batch 16 x 1024 tokens, 16-token
    pages): decode and a 64-token chunk window at Qwen3-4B's widths (GQA,
    8 KV heads) and DeepSeek-LLM-7B's (MHA, 32 KV heads), so that a
    kernel tile plan past the chip's VMEM fails here, not on the chip."""
    text = _compile_paged_kernel(one_chip, arch, 16, 1024, S)
    assert "tpu_custom_call" in text


# the served shapes: Qwen3-4B at the engine shape above, Granite at its
# cell's (batch, tokens per slot, chunk width)
SERVED = {"qwen3-4b": (BATCH, MAX_SEQ, CHUNK),
          "granite-4.0-h-micro": (32, 3072, 256)}


@pytest.fixture(scope="module")
def compiled_step(one_chip):
    """``get(arch, program, quarter=False)``: the engine's jitted paged
    ``program`` for ``arch`` at its served shape, compiled once for the
    described chip, and its K pool leaf (a shape stand-in). Parameters
    and the pool are sharded shape stand-ins (``jax.eval_shape``:
    nothing is allocated); ``quarter`` compiles it over a pool of a
    quarter of the blocks."""
    done = {}

    def get(arch, program, quarter=False):
        key = (arch, program, quarter)
        if key not in done:
            model = build_model(get_config(arch))
            batch, max_seq, width = SERVED[arch]
            put = lambda tree: jax.tree.map(               # noqa: E731
                lambda x: _sds(x.shape, x.dtype, one_chip), tree)
            params = put(jax.eval_shape(model.init, jax.random.key(0)))
            blocks = batch * (max_seq // BLOCK) // (4 if quarter else 1)
            caches = put(jax.eval_shape(lambda: model.init_paged_cache(
                blocks + 1, BLOCK, slots=batch)))
            args = paged_program_args(params, caches, batch=batch,
                                      blocks_per_slot=max_seq // BLOCK,
                                      width=width, sharding=one_chip)
            progs = paged_programs(model, block_size=BLOCK, use_kernel=True)
            # the kernel ops pick compiled vs interpret from
            # ``jax.default_backend()``, the CPU here
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: "tpu")
                done[key] = (progs[program].lower(*args[program]).compile(),
                             caches["k"])
        return done[key]
    return get


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_full_width_serving_step_compiles_and_fits(compiled_step, program):
    """The engine's jitted paged decode step and chunk-window step at 36
    layers x d 2560 in bf16, batch 8 x 2048 tokens: the kernel is in the
    program and arguments plus temporaries fit one chip."""
    compiled, _ = compiled_step("qwen3-4b", program)
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (program, used)


def test_paged_kernel_compiles_at_granite_widths(one_chip, tpu_branch):
    """Granite-4.0-H-Micro's attention: heads of 64 (32 query on 8 KV,
    G 4) at scale 1/64, decode and a 256-token chunk window. Heads
    narrower than the 128 lanes are lane-packed two to a pool row, so
    the op compiles the kernel at rows of 128 over 4 packed heads."""
    from repro.models.attention import lane_pack, paged_decode_attention
    cfg = get_config("granite-4.0-h-micro")
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    assert (Hq, Hkv, hd, cfg.attention_multiplier) == (32, 8, 64, 1 / 64)
    f = lane_pack(hd, Hkv)
    batch, mb = 32, 3072 // BLOCK
    pool = _sds((cfg.layer_types.count("attention"), batch * mb + 1,
                 Hkv // f, BLOCK, hd * f), jnp.bfloat16, one_chip)
    fn = jax.jit(lambda q, k, v, kn, vn, t, n, l: paged_decode_attention(
        q, k, v, kn, vn, t, n, l, use_kernel=True,
        scale=cfg.attention_multiplier), donate_argnums=(1, 2))
    one = _sds((batch, 1, Hkv, hd), jnp.bfloat16, one_chip)
    text = fn.lower(_sds((batch, 1, Hq, hd), jnp.bfloat16, one_chip), pool,
                    pool, one, one, _sds((batch, mb), jnp.int32, one_chip),
                    _sds((batch,), jnp.int32, one_chip),
                    _sds((), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_granite_serving_step_compiles_and_fits(compiled_step, program):
    """Granite-4.0-H-Micro at published width (40 layers: 36 Mamba-2,
    4 attention) at its cell's engine shape, batch 32 x 3072 tokens and
    256-token chunk windows: the paged kernel is in the program, and
    arguments (weights, pool, 32 state rows) plus temporaries fit one
    chip."""
    compiled, _ = compiled_step("granite-4.0-h-micro", program)
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (program, used)


# an op's result in the compiled program's text: name, dims, opcode
_RESULT = re.compile(r"^\s*(?:ROOT\s+)?(%\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     re.M)
# ops that would copy, slice or rebuild the pool or a layer of it
POOL_OPS = ("copy", "dynamic-slice", "dynamic-update-slice", "scatter",
            "fusion")


@pytest.mark.parametrize("arch", ["qwen3-4b", "granite-4.0-h-micro"])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_step_updates_pool_in_place(compiled_step, arch, program):
    """The paged steps carry the stacked K/V pool through the layer loop
    and the kernel writes each layer's pages in place, its pool
    operands aliased to its outputs: no copy, dynamic-slice,
    dynamic-update-slice, scatter or fusion in the compiled program
    yields the pool's shape or one layer's slice of it, and the
    program's temporaries hold no extra pool. For Qwen3-4B the
    temporaries are under 5% of the pool's bytes. Granite's Mamba-2
    layers need more than its pool on their own (a re-laid-out copy of
    the stacked in-projections in decode, the chunked scan's
    activations in a window), none of it sized by the pool: there the
    temporaries may grow by under 5% of the pool's bytes from the same
    program over a pool of a quarter of the blocks."""
    compiled, pool = compiled_step(arch, program)
    layer = pool.shape[1:]
    sized = {tuple(pool.shape), layer, (1, *layer)}
    hits = [name for name, dims, op in _RESULT.findall(compiled.as_text())
            if op in POOL_OPS
            and tuple(int(d) for d in dims.split(",") if d) in sized]
    assert not hits, hits
    pool_bytes = 2 * pool.size * pool.dtype.itemsize         # K and V
    temp = compiled.memory_analysis().temp_size_in_bytes
    if arch == "granite-4.0-h-micro":
        small, _ = compiled_step(arch, program, quarter=True)
        temp -= small.memory_analysis().temp_size_in_bytes
    assert temp < 0.05 * pool_bytes, (temp, pool_bytes)
