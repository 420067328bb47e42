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
import re

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
    B, K, G, hd, ps, pps, L = 8, 8, 2, 128, 128, 8, 28
    P = B * pps
    fn = lambda q, k, v, pt, pos, layer: paged_decode_attention(
        q, k, v, pt, pos, layer, scale=hd ** -0.5, grouped=grouped)
    _compile(fn, one_chip,
             ((B, K, G, hd), jnp.bfloat16),
             ((L, P, ps, K * hd), jnp.bfloat16),
             ((L, P, ps, K * hd), jnp.bfloat16),
             ((B, pps), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("grouped", [True, False])
def test_paged_decode_granite_widths(one_chip, grouped):
    from repro.kernels.paged_attention import paged_decode_attention
    # granite-moe-1b-a400m: 8 kv heads of 64, 2 q heads each: a kv head
    # is half a lane row, so tiles read 2 (narrowest) or 4 heads
    B, K, G, hd, ps, pps, L = 32, 8, 2, 64, 128, 12, 24
    P = B * pps
    fn = lambda q, k, v, pt, pos, layer: paged_decode_attention(
        q, k, v, pt, pos, layer, scale=hd ** -0.5, grouped=grouped)
    _compile(fn, one_chip,
             ((B, K, G, hd), jnp.bfloat16),
             ((L, P, ps, K * hd), jnp.bfloat16),
             ((L, P, ps, K * hd), jnp.bfloat16),
             ((B, pps), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))


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
    B, H, lora, rd, ps, pps, L = 8, 128, 512, 64, 128, 8, 5
    P = B * pps
    fn = lambda q, c, r, pt, pos, layer: mla_paged_decode_attention(
        q, c, r, pt, pos, layer, scale=(128 + rd) ** -0.5)
    _compile(fn, one_chip,
             ((B, H, lora + rd), jnp.bfloat16),
             ((L, P, ps, lora), jnp.bfloat16),
             ((L, P, ps, rd), jnp.bfloat16),
             ((B, pps), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))


def test_mla_blocked_prefill_deepseek_v2_widths(one_chip):
    """The blocked MLA prefill of one 8,192-token row over the doc-qa
    cell's latent pool stack (5 layers of 544 pages of 256 tokens): its
    temporaries, beside the cut's 7.63 GB of bfloat16 weights and its own
    arguments, fit one 16 GB chip."""
    from repro.models.attention import mla_prefill_attention_paged
    S0, H, lora, rd, nope, ps, pool, pps = 8192, 128, 512, 64, 128, 256, \
        544, 34
    bf = jnp.bfloat16
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in [
        ((1, S0, H, nope), bf), ((1, S0, H, rd), bf), ((lora, H, nope), bf),
        ((lora, H, nope), bf), ((5, pool, ps, lora), bf),
        ((5, pool, ps, rd), bf), ((1, pps), jnp.int32), ((1, S0), jnp.int32),
        ((1,), jnp.int32), ((), jnp.int32)]]
    fn = lambda *a: mla_prefill_attention_paged(*a, scale=0.11472)
    m = jax.jit(fn).lower(*args).compile().memory_analysis()
    weights = 7.63e9
    assert m.temp_size_in_bytes + m.argument_size_in_bytes + weights < 16e9
    # no (S0, H, T) float32 score tensor: that alone would be 36 GB
    assert m.temp_size_in_bytes < 2e9, m.temp_size_in_bytes


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


_INSTR = re.compile(r"\s+(ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")


def _pool_shaped_ops(hlo: str, dims: set) -> list:
    """(opcode, name) of every instruction of a compiled module that
    yields a buffer of one of the shapes in ``dims``; a fusion is named
    by its fused computation's root opcode."""
    roots, comp = {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            m = re.match(r"(?:ENTRY )?%([\w.\-]+) ", line)
            comp = m.group(1) if m else None
        else:
            m = _INSTR.match(line)
            if m and m.group(1):
                roots[comp] = m.group(4)
    out = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m and m.group(3) in dims:
            op = m.group(4)
            if op == "fusion":
                op = roots[re.search(r"calls=%([\w.\-]+)", line).group(1)]
            out.append((op, m.group(2)))
    return out


@pytest.mark.parametrize("arch,hd", [("granite-moe-1b-a400m", 64),
                                     ("qwen3-0.6b", 128)])
def test_serve_steps_write_pools_in_place(one_chip, monkeypatch, arch, hd):
    """The serve steps of a 3-layer paged stack at published widths
    write the token-major page pools in place: the stacks ride the layer
    scan's carry and the donated cache, so nothing but the row scatter
    and the decode kernel makes a pool-shaped buffer, the stacks come
    back aliased to the arguments, and the temporaries (bfloat16
    weights, so no cast of them) stay below one layer's pool."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models.layers import Ctx
    from repro.models.model import abstract_cache
    from repro.models.params import abstract_params
    from repro.train.steps import make_serve_steps

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    L, B, ps, budget, max_len, width = 3, 8, 128, 64, 1024, 256
    cfg = dataclasses.replace(get_config(arch), num_layers=L,
                              cache_layout="paged", page_size=ps,
                              dtype="bfloat16")
    assert cfg.head_dim == hd
    prefill, decode = make_serve_steps(cfg, Ctx(dtype=jnp.bfloat16,
                                                 use_pallas=True))
    isab = lambda x: hasattr(x, "logical_axes")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree.map(lambda ab: sds(ab.shape, jnp.bfloat16),
                          abstract_params(cfg), is_leaf=isab)

    def cache(rows):
        return jax.tree.map(lambda ab: sds(ab.shape, jnp.dtype(ab.dtype)),
                            abstract_cache(cfg, rows, max_len,
                                           page_budget=budget),
                            is_leaf=isab)

    row = cfg.num_kv_heads * hd
    one_pool = budget * ps * row * 2                   # bytes, one layer
    dims = {f"{budget},{ps},{row}", f"{L},{budget},{ps},{row}"}
    ok = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
          "scatter", "custom-call"}
    i32 = lambda *s: sds(s, jnp.int32)
    for name, lowered in (
            ("decode", decode.lower(params, {"tokens": i32(B, 1)}, cache(B),
                                    i32(B))),
            ("prefill", prefill.lower(params, {"tokens": i32(1, width)},
                                      cache(1), i32(1), i32(1)))):
        compiled = lowered.compile()
        hlo = compiled.as_text()
        assert "tpu_custom_call" in hlo or name == "prefill", name
        bad = [o for o in _pool_shaped_ops(hlo, dims) if o[0] not in ok]
        assert not bad, (name, bad)
        m = compiled.memory_analysis()
        assert m.temp_size_in_bytes < one_pool, (name, m.temp_size_in_bytes)
        assert m.alias_size_in_bytes >= 2 * L * one_pool, (
            name, m.alias_size_in_bytes)
