"""Compile every Pallas kernel for a described (not attached) TPU v5e chip.

Interpret mode cannot see the chip compiler's block-tiling rule (the last
two block dims divisible by (8, 128) or equal to the array's), so each
kernel is lowered and compiled here at the widths the chip smoke runs:
smollm-135m's 9 query heads, 3 KV heads and head_dim 64 in bfloat16, the
paged decodes at the chat cells' geometries, and a Mamba2-sized SSD scan.
Nothing executes; a compile takes about a second.

The topology is described inside a module fixture, never at import, so
that every test worker collects the same tests and only the worker that
runs this file loads the TPU compiler library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (flash_decode, paged_flash_decode,
                                            paged_latent_decode)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

# smollm-135m attention widths; train seq 1024; 8 serving slots, paged
# block 16 over a 1024-token max_len.
H, HKV, D, SEQ, SLOTS, BLOCK = 9, 3, 64, 1024, 8, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


def _flash_attention(spec):
    return (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [spec((2, SEQ, H, D)), spec((2, SEQ, HKV, D)), spec((2, SEQ, HKV, D))],
    )


def _flash_decode(spec):
    return (
        flash_decode,
        [spec((SLOTS, H, D)), spec((SLOTS, SEQ, HKV, D)),
         spec((SLOTS, SEQ, HKV, D)), spec((SLOTS,), jnp.int32)],
    )


def _paged_flash_decode(spec):
    n_blocks = SLOTS * SEQ // BLOCK
    arena = (n_blocks + 1, BLOCK, HKV, D)
    return (
        paged_flash_decode,
        [spec((SLOTS, H, D)), spec(arena), spec(arena),
         spec((SLOTS, SEQ // BLOCK), jnp.int32), spec((SLOTS,), jnp.int32)],
    )


def _paged_flash_decode_chat(spec):
    # The chat cell's serving geometry: qwen2.5-3b's 16/2 heads of 128,
    # 32 slots of 256 table slots of 16 rows over a 4,096-block arena.
    slots, table, block, n_blocks, h, hkv, d = 32, 256, 16, 4096, 16, 2, 128
    arena = (n_blocks + 1, block, hkv, d)
    return (
        paged_flash_decode,
        [spec((slots, h, d)), spec(arena), spec(arena),
         spec((slots, table), jnp.int32), spec((slots,), jnp.int32)],
    )


def _paged_latent_decode_moonlight(spec):
    # Moonlight chat's serving geometry: 16 heads over a latent rank of
    # 512 and rope keys of 64, 32 slots of 512 table slots of 16 rows over
    # a 5,121-row arena.
    slots, table, block, rows, h, r, rope = 32, 512, 16, 5121, 16, 512, 64
    return (
        lambda *a: paged_latent_decode(*a, scale=192 ** -0.5),
        [spec((slots, h, r)), spec((slots, h, rope)), spec((rows, block, r)),
         spec((rows, block, rope)), spec((slots, table), jnp.int32),
         spec((slots,), jnp.int32)],
    )


def _ssd_scan(spec):
    # Mamba2-130m-like mixer: 24 heads of 64, d_state 128, one group.
    return (
        lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c, chunk=128),
        [spec((2, SEQ, 24, 64)), spec((2, SEQ, 24), jnp.float32),
         spec((24,), jnp.float32), spec((2, SEQ, 1, 128)),
         spec((2, SEQ, 1, 128))],
    )


def _rmsnorm(spec):
    return rmsnorm, [spec((16, SEQ, 576)), spec((576,))]


@pytest.mark.parametrize(
    "build",
    [_flash_attention, _flash_decode, _paged_flash_decode,
     _paged_flash_decode_chat, _paged_latent_decode_moonlight, _ssd_scan,
     _rmsnorm],
    ids=["flash_attention", "flash_decode", "paged_flash_decode",
         "paged_flash_decode_chat", "paged_latent_decode_moonlight",
         "ssd_scan", "rmsnorm"],
)
def test_kernel_compiles_for_v5e(one_chip, build):
    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = build(spec)
    compiled = jax.jit(fn).lower(*args).compile()
    # The kernel reached the chip compiler as a Mosaic custom call, not
    # as an XLA fallback.
    assert "tpu_custom_call" in compiled.as_text()
