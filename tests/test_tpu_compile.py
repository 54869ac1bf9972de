"""Compile-only checks of the main-path Pallas kernels for a described TPU v5e.

The kernel suites (tests/test_paged_attention.py, tests/test_paged_prefill.py)
run in interpret mode, which accepts any tiling and any amount of SMEM/VMEM.
Here the TPU compiler that ships with jaxlib compiles the fused paged decode
and paged prefill kernels for a v5e that is described, not attached, at
yi-6b serving widths (32 q-heads, 4 kv-heads, head_dim 128, 16-token blocks,
a 1024-block pool) for every pool dtype, plus zamba2's head_dim 80, and the
decode kernel at the yi-6b.chat deployment's shape (16 slots, 256-entry
tables, a 1400-block pool) for every pool dtype, and both kernels at the
deepseek-moe-16b.chat deployment's (16 q-heads and 16 kv-heads, group 1,
a 705-block bf16 pool). The kernels read layer 1 of a two-layer stacked
pool, as the model's layer loop hands them the whole pool and a layer
index. Nothing runs: a pass means the chip's compiler accepts the kernel,
and the compiled text holds the Mosaic kernel (``tpu_custom_call``).

The whole decode-chunk and prefill-chunk programs are compiled too, at
yi-6b's and deepseek-moe-16b's widths with two layers and each cell's pool:
the pool rides the layer loop's carry and is written in place, so neither
program holds a temporary as large as one layer's pool, nor a copy of the
whole pool (DESIGN.md §3).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and pytest-xdist workers each
import every test file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quantizer import exaq_params
from repro.kernels.exaq_paged_attention import exaq_paged_decode_attention
from repro.kernels.exaq_paged_prefill import exaq_paged_prefill_attention
from repro.kernels.kv_codec import kv4_num_sub

H, KV, BS, N_BLOCKS = 32, 4, 16, 1024
LAYERS = 2  # the kernels read layer 1 of a two-layer pool
SLOTS, MAX_BLOCKS = 4, 128  # 2048-token windows
CHUNK = 32  # EngineConfig's default prefill chunk
# the yi-6b.chat deployment: 16 slots, max_seq 4096, a 22,384-token pool
SERVED_SLOTS, SERVED_MAX_BLOCKS, SERVED_N_BLOCKS = 16, 256, 22384 // BS + 1
# the deepseek-moe-16b.chat deployment: MHA, 16 heads, an 11,264-token pool
MOE_HEADS, MOE_N_BLOCKS = 16, 11264 // BS + 1

CASES = [("bf16", 128), ("int8", 128), ("int4", 128), ("bf16", 80)]
CASE_IDS = [f"{kv}-d{dh}" for kv, dh in CASES]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _pool_args(kv_dtype: str, dh: int, sharding, n_blocks: int = N_BLOCKS,
               kv: int = KV) -> tuple[tuple, dict]:
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    if kv_dtype == "int4":
        pool = sds((LAYERS, n_blocks, kv, BS, dh // 2), jnp.uint8)
    else:
        pool = sds((LAYERS, n_blocks, kv, BS, dh),
                   {"bf16": jnp.bfloat16, "int8": jnp.int8}[kv_dtype])
    planes = {"layer": sds((), jnp.int32)}
    if kv_dtype in ("int8", "int4"):
        planes["k_scale"] = planes["v_scale"] = sds((LAYERS, n_blocks, kv), jnp.float32)
    if kv_dtype == "int4":
        planes["k_sub"] = planes["v_sub"] = sds((LAYERS, n_blocks, kv, kv4_num_sub(BS)),
                                                jnp.uint8)
    return (pool, pool), planes


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype,dh", CASES, ids=CASE_IDS)
def test_paged_decode_compiles_for_v5e(one_chip, kv_dtype, dh):
    (pk, pv), planes = _pool_args(kv_dtype, dh, one_chip)
    q = jax.ShapeDtypeStruct((SLOTS, H, 1, dh), jnp.bfloat16, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((SLOTS, MAX_BLOCKS), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    compiled = exaq_paged_decode_attention.lower(
        q, pk, pv, tables, lens, exaq_params(2.0, 2), dh**-0.5, **planes
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8", "int4"])
def test_paged_decode_compiles_for_v5e_at_served_shape(one_chip, kv_dtype):
    """The served decode shape: 16 slots, 256-entry tables, so the kernel's
    512-token tiles and their VMEM double buffers are what the cell runs."""
    (pk, pv), planes = _pool_args(kv_dtype, 128, one_chip, SERVED_N_BLOCKS)
    q = jax.ShapeDtypeStruct((SERVED_SLOTS, H, 1, 128), jnp.bfloat16, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((SERVED_SLOTS, SERVED_MAX_BLOCKS), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((SERVED_SLOTS,), jnp.int32, sharding=one_chip)
    compiled = exaq_paged_decode_attention.lower(
        q, pk, pv, tables, lens, exaq_params(2.0, 2), 128**-0.5, **planes
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("kv_dtype,dh", CASES, ids=CASE_IDS)
def test_paged_prefill_compiles_for_v5e(one_chip, kv_dtype, dh):
    (pk, pv), planes = _pool_args(kv_dtype, dh, one_chip)
    q = jax.ShapeDtypeStruct((1, H, CHUNK, dh), jnp.bfloat16, sharding=one_chip)
    table = jax.ShapeDtypeStruct((MAX_BLOCKS,), jnp.int32, sharding=one_chip)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = exaq_paged_prefill_attention.lower(
        q, pk, pv, table, start, exaq_params(2.0, 2), dh**-0.5, **planes
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_kernels_compile_for_v5e_at_moe_served_shape(one_chip, kernel):
    """deepseek-moe-16b.chat: 16 kv heads of group 1 (256-token decode
    tiles at this width, against 512 for yi-6b), 16 slots, 256-entry
    tables."""
    (pk, pv), planes = _pool_args("bf16", 128, one_chip, MOE_N_BLOCKS, kv=MOE_HEADS)

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if kernel == "decode":
        q = jax.ShapeDtypeStruct((SERVED_SLOTS, MOE_HEADS, 1, 128), jnp.bfloat16, sharding=one_chip)
        lowered = exaq_paged_decode_attention.lower(
            q, pk, pv, sds((SERVED_SLOTS, SERVED_MAX_BLOCKS)), sds((SERVED_SLOTS,)),
            exaq_params(2.0, 2), 128**-0.5, **planes)
    else:
        q = jax.ShapeDtypeStruct((1, MOE_HEADS, CHUNK, 128), jnp.bfloat16, sharding=one_chip)
        lowered = exaq_paged_prefill_attention.lower(
            q, pk, pv, sds((SERVED_MAX_BLOCKS,)), sds(()), exaq_params(2.0, 2), 128**-0.5, **planes)
    _assert_kernel(lowered.compile())


# ------------------------------------------------ whole programs, pool in place

PROGRAM_CELLS = {
    # config, its served pool, and the registry fields the cell's program sets
    "yi-6b": (SERVED_N_BLOCKS, {}),
    "deepseek-moe-16b": (MOE_N_BLOCKS, {"expert_parallel": 4, "expert_shard": 0}),
}


def _programs(sharding, arch):
    """(decode chunk, prefill chunk) of ``PagedDeviceStep`` compiled for the
    described chip at ``arch``'s widths with two layers and the cell's pool,
    bf16 weights and pool, the fused kernels; and the pool's shape."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime.device_step import PagedDeviceStep

    n_blocks, program = PROGRAM_CELLS[arch]
    cfg = dataclasses.replace(get_config(arch), num_layers=LAYERS, **program)
    cfg = cfg.with_quant(softmax_impl="exaq", bits=2, use_fused_kernel=True)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)))
    step = PagedDeviceStep(cfg, params, num_blocks=n_blocks, block_size=BS, max_seq=4096)
    pool = jax.tree.map(sds, jax.eval_shape(
        lambda: step.model.init_block_pool(n_blocks, BS, jnp.bfloat16)))
    i32, S, MB = jnp.int32, SERVED_SLOTS, SERVED_MAX_BLOCKS
    decode = step._jit_chunk.lower(
        params, pool, arg(i32, S, MB), arg(i32, S, 1), arg(i32, S), arg(jnp.bool_, S),
        arg(i32, S), arg(jnp.float32, S), arg(i32, S), arg(jnp.float32, S),
        arg(jnp.uint32, 2), steps=8, sampler="greedy").compile()
    prefill = step._jit_prefill_chunk.lower(
        params, pool, arg(i32, 1, CHUNK), arg(i32, MB), arg(i32), arg(i32),
        arg(i32, CHUNK), arg(i32, CHUNK)).compile()
    return decode, prefill, pool["k"].shape


@pytest.mark.parametrize("arch", list(PROGRAM_CELLS))
def test_paged_programs_update_the_pool_in_place_for_v5e(one_chip, arch, monkeypatch):
    """Neither program copies the pool: no temporary reaches one layer's K
    and V bytes, and the optimised HLO holds no copy of a whole pool plane."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_cpu", lambda: False)  # lower the kernels for the chip
    decode, prefill, shape = _programs(one_chip, arch)
    layer_bytes = 2 * (shape[1] * shape[2] * shape[3] * shape[4]) * 2  # K and V, bf16
    whole = re.compile(r"= bf16\[%s\]\S* copy(-start)?\(" % ",".join(map(str, shape)))
    for program in (decode, prefill):
        _assert_kernel(program)
        assert program.memory_analysis().temp_size_in_bytes < layer_bytes
        copies = [line for line in program.as_text().splitlines() if whole.search(line)]
        assert not copies, copies[:2]
