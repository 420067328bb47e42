"""jit'd model-facing wrappers around the Pallas kernels.

These accept the model's tensor layouts, handle padding to block multiples,
and select interpret mode automatically off-TPU (the brief's validation
path: kernel bodies execute in Python on CPU, compiled on real TPUs).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import (
    mla_paged_decode_attention as _mla_paged,
    paged_decode_attention as _paged,
)
from repro.kernels.rglru_scan import rglru_scan as _rglru
from repro.kernels.rwkv6_wkv import wkv6 as _wkv6


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "window", "logit_cap", "q_blk", "kv_blk"))
def flash_attention_bshd(
    q: jax.Array,          # (B, S, H, hd)
    k: jax.Array,          # (B, S, K, hd)
    v: jax.Array,          # (B, S, K, hd)
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    logit_cap: float = 0.0,
    q_blk: int = 512,
    kv_blk: int = 512,
) -> jax.Array:
    B, S, H, hd = q.shape
    K = k.shape[2]
    group = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, S, hd)
    out = _flash(qf, kf, vf, group=group, scale=scale, causal=causal,
                 window=window, logit_cap=logit_cap,
                 q_blk=min(q_blk, S), kv_blk=min(kv_blk, S),
                 interpret=_interpret())
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("scale", "logit_cap"))
def paged_decode_bhd(
    q: jax.Array,            # (B, 1, H, hd) — one new token per sequence
    k_pages: jax.Array,      # (P, K, ps, hd) shared physical pool
    v_pages: jax.Array,      # (P, K, ps, hd)
    page_table: jax.Array,   # (B, pps) int32; -1 = unallocated
    pos_q: jax.Array,        # (B,) int32; -1 = inactive slot
    *,
    scale: float,
    logit_cap: float = 0.0,
) -> jax.Array:
    """Model-layout wrapper for the paged flash-decode kernel: regroup the
    q heads per kv head, run the kernel (interpret mode off-TPU), ungroup."""
    B, _, H, hd = q.shape
    K = k_pages.shape[1]
    qg = q.reshape(B, K, H // K, hd)
    out = _paged(qg, k_pages, v_pages, page_table.astype(jnp.int32),
                 pos_q.astype(jnp.int32), scale=scale, logit_cap=logit_cap,
                 interpret=_interpret())
    return out.reshape(B, 1, H, hd)


@functools.partial(jax.jit, static_argnames=("scale",))
def mla_paged_decode_bhd(
    q_lat: jax.Array,        # (B, H, lora + rd) absorbed latent query
    ckv_pages: jax.Array,    # (P, ps, lora) shared latent pool
    krope_pages: jax.Array,  # (P, ps, rd) shared rope-key pool
    page_table: jax.Array,   # (B, pps) int32; -1 = unallocated
    pos_q: jax.Array,        # (B,) int32; -1 = inactive slot
    *,
    scale: float,
) -> jax.Array:
    """Model-layout wrapper for the MLA latent flash-decode kernel;
    returns the latent context (B, H, lora) — the caller expands it
    through W_vc (interpret mode off-TPU)."""
    return _mla_paged(q_lat, ckv_pages, krope_pages,
                      page_table.astype(jnp.int32), pos_q.astype(jnp.int32),
                      scale=scale, interpret=_interpret())


@jax.jit
def rglru_scan_bsr(log_a: jax.Array, b: jax.Array,
                   h0: Optional[jax.Array] = None) -> jax.Array:
    """(B,S,R) fp32 inputs; returns the h sequence (B,S,R) fp32."""
    B, S, R = log_a.shape
    if h0 is None:
        h0 = jnp.zeros((B, R), jnp.float32)
    t_blk = 16
    pad = (-S) % t_blk
    if pad:
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
    out = _rglru(log_a.astype(jnp.float32), b.astype(jnp.float32),
                 h0.astype(jnp.float32), t_blk=t_blk,
                 interpret=_interpret())
    return out[:, :S]


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6_bshn(r: jax.Array, k: jax.Array, v: jax.Array, lw: jax.Array,
              u: jax.Array, s0: jax.Array, *, chunk: int = 32
              ) -> Tuple[jax.Array, jax.Array]:
    """Model layout: r/k/v/lw (B,S,H,N), u (H,N), s0 (B,H,N,N).
    Returns (o (B,S,H,N), s_final (B,H,N,N))."""
    B, S, H, N = r.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, N)
    rf, kf, vf, lwf = fold(r), fold(k), fold(v), fold(lw.astype(jnp.float32))
    uf = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, 1, N)
    s0f = s0.reshape(B * H, N, N).astype(jnp.float32)
    pad = (-S) % chunk
    if pad:
        z = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        rf, kf, vf, lwf = z(rf), z(kf), z(vf), z(lwf)
    o, s_fin = _wkv6(rf, kf, vf, lwf, uf, s0f, chunk=chunk,
                     interpret=_interpret())
    o = o[:, :S].reshape(B, H, S, N).transpose(0, 2, 1, 3)
    return o, s_fin.reshape(B, H, N, N)
