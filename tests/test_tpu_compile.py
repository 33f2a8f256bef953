"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

The TPU compiler is installed alongside JAX, so each kernel of the
serving path is lowered and compiled here for a *described* v5e chip at
the widths of the configs it serves (mamba2-780m for the SSD scan,
llama3.2-1b for attention and the quantized projections). Nothing runs:
this catches what interpret mode cannot — block shapes the TPU tiling
rejects, memory-space mismatches, Mosaic lowering gaps. Every compiled
program must contain the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library at a time, and the file's tests all
run in the process that describes it.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.flash_attention.kernel import flash_attention_gqa_pallas
from repro.kernels.quant_matmul.kernel import (quant_matmul_int4_pallas,
                                               quant_matmul_int8_pallas)
from repro.kernels.rmsnorm.kernel import fused_rmsnorm_pallas
from repro.kernels.ssd_scan.kernel import (ssd_extend_chunked_pallas,
                                           ssd_extend_pallas, ssd_pallas)
from repro.kernels.ssd_scan.ops import EXTEND_CHUNK

f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
# mamba2-780m: d_inner 3072 = 48 heads x 64, d_state 128, 1 group, chunk 256
SSD = dict(h=48, p=64, n=128, chunk=256)
# llama3.2-1b: 32 q heads, 8 kv heads, head_dim 64, d_model 2048, d_ff 8192
ATTN = dict(B=8, Hq=32, Hkv=8, hd=64, S=2048)
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _ssd_extend_chunk_shapes():
    """(state, x, dt, A, B, C, D) of the mamba2-780m admission chunk."""
    h, p, n, T = SSD["h"], SSD["p"], SSD["n"], 512
    return [((1, h, p, n), f32), ((1, T, h, p), f32), ((1, T, h), f32),
            ((h,), f32), ((1, T, 1, n), f32), ((1, T, 1, n), f32),
            ((h,), f32)]


def _cases():
    h, p, n, chunk = SSD["h"], SSD["p"], SSD["n"], SSD["chunk"]
    B, Hq, Hkv, hd, S = (ATTN[k] for k in ("B", "Hq", "Hkv", "hd", "S"))
    NB = S // PAGE
    K, N, M = 2048, 8192, 8
    return {
        # prefill: bf16 activations, 512 tokens in two chunks
        "ssd": (lambda *a: ssd_pallas(*a, chunk=chunk),
                [((1, 512, h, p), bf16), ((1, 512, h), f32), ((h,), f32),
                 ((1, 512, 1, n), bf16), ((1, 512, 1, n), bf16),
                 ((h,), f32)]),
        # the serial form: one 1024-token chunk from a carried state
        "ssd_extend": (ssd_extend_pallas,
                       [((1, h, p, n), f32), ((1, 1024, h, p), f32),
                        ((1, 1024, h), f32), ((h,), f32),
                        ((1, 1024, 1, n), f32), ((1, 1024, 1, n), f32),
                        ((h,), f32)]),
        # chunked admission: the 512-token chunk in the chunked form
        "ssd_extend_chunked": (
            functools.partial(ssd_extend_chunked_pallas, chunk=EXTEND_CHUNK),
            _ssd_extend_chunk_shapes()),
        "decode_attention": (decode_attention_pallas,
                             [((B, Hq, hd), bf16), ((B, Hkv, S, hd), bf16),
                              ((B, Hkv, S, hd), bf16), ((B, S), i32),
                              ((B,), i32)]),
        "paged_decode_attention": (
            paged_decode_attention_pallas,
            [((B, 1, Hq, hd), bf16), ((B * NB + 1, PAGE, Hkv, hd), bf16),
             ((B * NB + 1, PAGE, Hkv, hd), bf16), ((B, NB), i32),
             ((B, S), i32), ((B, 1), i32)]),
        "quant_matmul_int8": (quant_matmul_int8_pallas,
                              [((M, K), bf16), ((K, N), jnp.int8),
                               ((N,), f32)]),
        "quant_matmul_int4": (quant_matmul_int4_pallas,
                              [((M, K), bf16), ((K // 2, N), jnp.int8),
                               ((K // 32, N), f32)]),
        "fused_rmsnorm": (fused_rmsnorm_pallas,
                          [((256, 2048), bf16), ((256, 2048), bf16),
                           ((2048,), bf16)]),
        "flash_attention_gqa": (flash_attention_gqa_pallas,
                                [((1, Hq, 1024, hd), bf16),
                                 ((1, Hkv, 1024, hd), bf16),
                                 ((1, Hkv, 1024, hd), bf16)]),
    }


@pytest.mark.parametrize("name", sorted(_cases()))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_extend_chunked_is_read_by_the_roofline(one_chip,
                                                    no_compile_cache):
    """The benchmark's ``ssd_extend`` yardstick reads the compiled
    chunked call, from the instruction text a profiler event carries
    (operand shapes printed), as the chat cell's admission chunk."""
    from jax._src.lib import xla_client
    from bench.kernels import ssd_extend as yardstick
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in _ssd_extend_chunk_shapes()]
    fn = functools.partial(ssd_extend_chunked_pallas, chunk=EXTEND_CHUNK)
    compiled = jax.jit(fn).lower(*args).compile()
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    calls = [line.strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and calls[0].startswith("%ssd_extend_chunked.")
    assert yardstick.shapes(calls[0]) == (1, 512, 48, 64, 1, 128)
