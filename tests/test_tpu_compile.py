"""Compile-only checks of the served kernels for one TPU v5e chip.

Interpret mode (every other kernel test) runs the kernel body as JAX ops
and never meets Mosaic's tiling rules; these tests hand the real TPU
compiler each main-path kernel at the served widths (qwen3-1.7b: H=16,
Hkv=8; qwen3-8b: H=32; D=128, page 16, bf16) for a DESCRIBED v5e chip —
nothing runs, no chip needed.  The topology is described inside a
fixture (never at import: only one process may load the TPU library, and
pytest-xdist workers all import this file), and the persistent
compilation cache is off around the compiles (an entry compiled for a
described chip cannot be read back).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import gemv_cid as gv

# qwen3-1.7b attention widths on the paged pool
H, HKV, D, PAGE = 16, 8, 128, 16
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()      # the Mosaic kernel
    return compiled


@pytest.mark.parametrize("T,n_pages,h,hkv", [
    (2048, 2560, 32, 8),     # qwen3-8b-l12.halo-longctx
    (256, 1536, 16, 8),      # qwen3-1.7b.chat-shared: a user turn
    (2048, 1536, 16, 8),     # qwen3-1.7b.chat-shared: a full chunk
])
def test_packed_prefill_attention_compiles(one_chip, no_persistent_cache,
                                           T, n_pages, h, hkv):
    """The served prefill kernel at the benchmark's two shapes: a packed
    stream of 8 segments over the cell's pool (block-table width = the
    pool), under its stable trace name."""
    n_seg = 8
    compiled = _compile(
        lambda *a: fa.packed_prefill_attention(*a, ring=n_pages * PAGE,
                                               bq=128),
        one_chip,
        ((T, h, D), BF16), ((T, hkv, D), BF16), ((T, hkv, D), BF16),
        ((n_pages, PAGE, hkv, D), BF16), ((n_pages, PAGE, hkv, D), BF16),
        ((n_seg, n_pages), I32), ((n_seg,), I32), ((n_seg,), I32),
        ((n_seg,), I32))
    assert "packed_prefill_attention" in compiled.as_text()


def test_paged_decode_attention_compiles(one_chip, no_persistent_cache):
    B, n_pages = 8, 4096
    _compile(da.paged_decode_attention, one_chip,
             ((B, H, D), BF16), ((n_pages, PAGE, HKV, D), BF16),
             ((n_pages, PAGE, HKV, D), BF16), ((B, n_pages), I32),
             ((B,), I32))


def test_int8_gemv_compiles(one_chip, no_persistent_cache):
    """The decode GEMV at the fused gate/up width: (8, 2048) x (2048, 6144)
    int8 with per-channel f32 scales."""
    _compile(gv.gemv, one_chip,
             ((8, 2048), BF16), ((2048, 6144), jnp.int8),
             ((6144,), jnp.float32))
