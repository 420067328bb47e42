"""RWKV6 WKV for TPU (Pallas): chunked linear attention with data-dependent
per-channel decay; the (N, N) state lives in VMEM scratch across chunks.

    o_t = r_t · S_{t-1} + (r_t · (u ⊙ k_t)) v_t
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ,   w_t = exp(lw_t), lw ≤ 0

Grid = (B·H, n_chunks), chunks sequential (minormost).  Per chunk the
intra-chunk pairwise decays are computed in log space — every exp argument
is ≤ 0 so no rescaling is needed.  VMEM per step with L=32, N=64 (fp32):
r/k/v/lw 4·L·N + decay L·L·N + state N·N ≈ 0.3 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.layout import KernelLayout, SpecDesc


def wkv_layout(BH: int, S: int, N: int, chunk: int) -> KernelLayout:
    """Grid layout of :func:`wkv6` — the single source of truth the
    pallas_call is built from and ``staticcheck`` abstractly checks."""
    seq_map = lambda bh, ci: (bh, ci, 0)
    head_map = lambda bh, ci: (bh, 0, 0)
    return KernelLayout(
        name="rwkv6_wkv",
        grid=(BH, S // chunk),
        in_specs=(
            SpecDesc("r", (BH, S, N), (1, chunk, N), seq_map),
            SpecDesc("k", (BH, S, N), (1, chunk, N), seq_map),
            SpecDesc("v", (BH, S, N), (1, chunk, N), seq_map),
            SpecDesc("lw", (BH, S, N), (1, chunk, N), seq_map),
            SpecDesc("u", (BH, 1, N), (1, 1, N), head_map),
            SpecDesc("s0", (BH, N, N), (1, N, N), head_map),
        ),
        out_specs=(
            SpecDesc("o", (BH, S, N), (1, chunk, N), seq_map),
            SpecDesc("s_out", (BH, N, N), (1, N, N), head_map),
        ),
        scratch=(((N, N), jnp.float32),),
        dimension_semantics=("parallel", "arbitrary"),
    )


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref,
                o_ref, sout_ref, state, *, L: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)               # (L, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0]                                 # (L, N) fp32, ≤ 0
    u = u_ref[0].astype(jnp.float32)               # (1, N)
    s = state[...]                                 # (N, N)

    # inclusive prefix sum as a lower-triangular matmul: Mosaic has no
    # cumsum; HIGHEST keeps the fp32 log-decays exact enough to exp
    incl = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    clw = jax.lax.dot_general(incl.astype(jnp.float32), lw,
                              (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST)
    clw_ex = clw - lw                              # exclusive
    # inter-chunk: contribution of the carried state
    o_inter = jax.lax.dot_general(r * jnp.exp(clw_ex), s,
                                  (((1,), (0,)), ((), ())))     # (L, N)
    # intra-chunk pairwise (log-space decays, strictly lower-triangular)
    decay = jnp.exp(clw_ex[:, None, :] - clw[None, :, :])       # (L, L, N)
    a = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=-1)  # (L, L)
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    a = jnp.where(tri, a, 0.0)
    bonus = jnp.sum(r * (u * k), axis=-1, keepdims=True)         # (L, 1)
    o_intra = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ()))) + bonus * v
    o_ref[0] = (o_inter + o_intra).astype(o_ref.dtype)

    # state update: decay to end of chunk + decayed outer products
    last = clw[L - 1:L]                                          # (1, N)
    k_dec = k * jnp.exp(last - clw)                              # (L, N)
    s_new = jnp.exp(last).T * s + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())))
    state[...] = s_new

    @pl.when(ci == n_chunks - 1)
    def _final():
        sout_ref[0] = s_new


def wkv6(
    r: jax.Array,          # (BH, S, N)
    k: jax.Array,          # (BH, S, N)
    v: jax.Array,          # (BH, S, N)
    lw: jax.Array,         # (BH, S, N) fp32 log-decay ≤ 0
    u: jax.Array,          # (BH, 1, N) bonus (per-head row, pre-expanded)
    s0: jax.Array,         # (BH, N, N) fp32 initial state
    *,
    chunk: int = 32,
    interpret: bool = False,
):
    BH, S, N = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    kernel = functools.partial(_wkv_kernel, L=chunk, n_chunks=n_chunks)
    layout = wkv_layout(BH, S, N, chunk)
    o, s_fin = pl.pallas_call(
        kernel,
        grid=layout.grid,
        in_specs=layout.block_specs(),
        out_specs=layout.out_block_specs(),
        out_shape=layout.out_shape_structs([r.dtype, jnp.float32]),
        scratch_shapes=layout.scratch_shapes(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.dimension_semantics),
        interpret=interpret,
    )(r, k, v, lw, u, s0)
    return o, s_fin
