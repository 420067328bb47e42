"""Flash attention for TPU (Pallas): blocked online-softmax, VMEM-resident
accumulators, causal/sliding-window masking, GQA, logit softcap.

Grid = (batch·q_heads, n_q_blocks, n_kv_blocks); the kv dim is minormost so
on TPU it iterates sequentially per (bh, qi) and the running (m, l, acc)
live in VMEM scratch across kv steps.  Fully-masked kv blocks (beyond the
causal frontier or before the sliding window) are skipped with pl.when —
the MXU sees only live blocks, giving O(S·W) work for windowed layers.

Block shapes are MXU-aligned (q_blk, kv_blk multiples of 128; head_dim is
the lane dim).  VMEM working set per grid step:
    q (q_blk·hd) + k,v (kv_blk·hd) + scores (q_blk·kv_blk) + acc (q_blk·hd)
e.g. 512×128 blocks at f32 ≈ 1.3 MB — comfortably under the ~16 MB VMEM.

Layouts: q (B·H, Sq, hd); k, v (B·K, Sk, hd); kv head = q head // G.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.layout import KernelLayout, SpecDesc

NEG_INF = -2.0e38


def flash_layout(BH: int, Sq: int, Sk: int, hd: int, q_blk: int,
                 kv_blk: int, group: int) -> KernelLayout:
    """Grid layout of :func:`flash_attention` — the single source of truth
    the pallas_call is built from and ``staticcheck`` abstractly checks."""
    q_map = lambda bh, qi, ki: (bh, qi, 0)
    kv_map = lambda bh, qi, ki, group=group: (bh // group, ki, 0)
    return KernelLayout(
        name="flash_attention",
        grid=(BH, Sq // q_blk, Sk // kv_blk),
        in_specs=(
            SpecDesc("q", (BH, Sq, hd), (1, q_blk, hd), q_map),
            SpecDesc("k", (BH // group, Sk, hd), (1, kv_blk, hd), kv_map),
            SpecDesc("v", (BH // group, Sk, hd), (1, kv_blk, hd), kv_map),
        ),
        out_specs=(
            SpecDesc("o", (BH, Sq, hd), (1, q_blk, hd), q_map),
        ),
        scratch=(
            ((q_blk, 1), jnp.float32),
            ((q_blk, 1), jnp.float32),
            ((q_blk, hd), jnp.float32),
        ),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
                  scale: float, causal: bool, window: int, logit_cap: float,
                  q_blk: int, kv_blk: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q0 = qi * q_blk
    t0 = ki * kv_blk
    # live test for this (q, kv) block pair
    live = True
    if causal:
        live = t0 <= q0 + q_blk - 1
    if window:
        live = jnp.logical_and(live, t0 + kv_blk - 1 >= q0 - window + 1) \
            if causal else (t0 + kv_blk - 1 >= q0 - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (q_blk, hd)
        k = k_ref[0].astype(jnp.float32)                  # (kv_blk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (q_blk,kv_blk)
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        pq = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        pk = t0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= pk <= pq
        if window:
            mask &= pq - pk < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())))
        m_s[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_s[...] /
                    jnp.maximum(l_s[...], 1e-37)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,           # (BH, Sq, hd)
    k: jax.Array,           # (BK, Sk, hd)
    v: jax.Array,           # (BK, Sk, hd)
    *,
    group: int,             # q heads per kv head (GQA)
    scale: float,
    causal: bool = True,
    window: int = 0,
    logit_cap: float = 0.0,
    q_blk: int = 512,
    kv_blk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    q_blk = min(q_blk, Sq)
    kv_blk = min(kv_blk, Sk)
    assert Sq % q_blk == 0 and Sk % kv_blk == 0, (Sq, q_blk, Sk, kv_blk)
    n_kv = Sk // kv_blk

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        logit_cap=logit_cap, q_blk=q_blk, kv_blk=kv_blk, n_kv=n_kv)

    layout = flash_layout(BH, Sq, Sk, hd, q_blk, kv_blk, group)
    return pl.pallas_call(
        kernel,
        grid=layout.grid,
        in_specs=layout.block_specs(),
        out_specs=layout.out_block_specs()[0],
        out_shape=layout.out_shape_structs([q.dtype])[0],
        scratch_shapes=layout.scratch_shapes(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.dimension_semantics),
        interpret=interpret,
    )(q, k, v)
