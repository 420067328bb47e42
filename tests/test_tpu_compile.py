"""Compile the main-path Pallas kernels for a described TPU v5e chip.

The TPU compiler ships with jaxlib, and it compiles for a chip that is
described (``topologies.get_topology_desc``) rather than attached.  That
catches what interpret mode cannot: block shapes off the (8, 128) tiling,
primitives Mosaic has no lowering for, VMEM over budget.  Nothing runs,
so these say nothing about results or times.

The topology is described inside a module-scope fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("grouped", [True, False])
def test_paged_decode_qwen3_widths(one_chip, grouped):
    from repro.kernels.paged_attention import paged_decode_attention
    # qwen3-0.6b: 8 kv heads, 2 q heads each, head_dim 128; 8 slots of
    # 1024 tokens over 128-token pages
    B, K, G, hd, ps, pps = 8, 8, 2, 128, 128, 8
    P = B * pps
    fn = lambda q, k, v, pt, pos: paged_decode_attention(
        q, k, v, pt, pos, scale=hd ** -0.5, grouped=grouped)
    _compile(fn, one_chip,
             ((B, K, G, hd), jnp.bfloat16),
             ((P, K, ps, hd), jnp.bfloat16),
             ((P, K, ps, hd), jnp.bfloat16),
             ((B, pps), jnp.int32), ((B,), jnp.int32))


def test_flash_attention_s2048(one_chip):
    from repro.kernels.flash_attention import flash_attention
    # qwen3-0.6b heads (16 q over 8 kv), one 2048-token sequence
    H, K, S, hd = 16, 8, 2048, 128
    fn = lambda q, k, v: flash_attention(q, k, v, group=H // K,
                                         scale=hd ** -0.5)
    _compile(fn, one_chip,
             ((H, S, hd), jnp.bfloat16),
             ((K, S, hd), jnp.bfloat16),
             ((K, S, hd), jnp.bfloat16))


def test_mla_paged_decode_deepseek_v2_widths(one_chip):
    from repro.kernels.paged_attention import mla_paged_decode_attention
    # deepseek-v2: 128 heads over one latent of kv_lora 512 + rope 64
    B, H, lora, rd, ps, pps = 8, 128, 512, 64, 128, 8
    P = B * pps
    fn = lambda q, c, r, pt, pos: mla_paged_decode_attention(
        q, c, r, pt, pos, scale=(128 + rd) ** -0.5)
    _compile(fn, one_chip,
             ((B, H, lora + rd), jnp.bfloat16),
             ((P, ps, lora), jnp.bfloat16),
             ((P, ps, rd), jnp.bfloat16),
             ((B, pps), jnp.int32), ((B,), jnp.int32))


def test_rglru_scan_recurrentgemma_widths(one_chip):
    from repro.kernels.rglru_scan import rglru_scan
    # recurrentgemma-9b: rnn_width 4096
    B, S, R = 2, 2048, 4096
    _compile(rglru_scan, one_chip,
             ((B, S, R), jnp.float32), ((B, S, R), jnp.float32),
             ((B, R), jnp.float32))


def test_wkv6_rwkv6_widths(one_chip):
    from repro.kernels.rwkv6_wkv import wkv6
    # rwkv6-7b: 64 heads of 64 channels; one sequence of 2048 tokens
    BH, S, N = 64, 2048, 64
    _compile(wkv6, one_chip,
             ((BH, S, N), jnp.bfloat16), ((BH, S, N), jnp.bfloat16),
             ((BH, S, N), jnp.bfloat16), ((BH, S, N), jnp.float32),
             ((BH, 1, N), jnp.bfloat16), ((BH, N, N), jnp.float32))
