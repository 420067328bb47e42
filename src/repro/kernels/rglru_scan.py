"""RG-LRU linear recurrence for TPU (Pallas): h_t = a_t ⊙ h_{t-1} + b_t.

Grid = (B, n_r_blocks, n_t_blocks); time is minormost so the carry vector
(1, r_blk) persists in VMEM scratch across time blocks.  Each time block is
a *statically unrolled* chain of ``t_blk`` vector FMAs on the VPU — the
recurrence is elementwise per channel, so there is no MXU work; the kernel
exists to keep the carry resident in VMEM and stream a_t/b_t once from HBM
(the jnp associative-scan path reads/writes O(S·R·log S) intermediates).

Inputs are fp32: ``log_a`` (≤ 0) and ``b``; decay applied as exp(log_a).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.layout import KernelLayout, SpecDesc


def rglru_layout(B: int, S: int, R: int, t_blk: int,
                 r_blk: int) -> KernelLayout:
    """Grid layout of :func:`rglru_scan` — the single source of truth the
    pallas_call is built from and ``staticcheck`` abstractly checks."""
    seq_map = lambda bi, ri, ti: (bi, ti, ri)
    # h0 rides as (B, 1, R): a (1, r_blk) block of a 2-D (B, R) array
    # breaks the TPU's (8, 128) tiling rule on its batch dim
    h0_map = lambda bi, ri, ti: (bi, 0, ri)
    return KernelLayout(
        name="rglru_scan",
        grid=(B, R // r_blk, S // t_blk),
        in_specs=(
            SpecDesc("log_a", (B, S, R), (1, t_blk, r_blk), seq_map),
            SpecDesc("b", (B, S, R), (1, t_blk, r_blk), seq_map),
            SpecDesc("h0", (B, 1, R), (1, 1, r_blk), h0_map),
        ),
        out_specs=(
            SpecDesc("o", (B, S, R), (1, t_blk, r_blk), seq_map),
        ),
        scratch=(((1, r_blk), jnp.float32),),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def _rglru_kernel(la_ref, b_ref, h0_ref, o_ref, carry, *, t_blk: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        carry[...] = h0_ref[0]

    h = carry[0]                                   # (r_blk,)
    la = la_ref[0]                                 # (t_blk, r_blk)
    b = b_ref[0]
    rows = []
    for t in range(t_blk):                         # static unroll
        h = jnp.exp(la[t]) * h + b[t]
        rows.append(h)
    o_ref[0] = jnp.stack(rows)
    carry[0] = h


def rglru_scan(
    log_a: jax.Array,       # (B, S, R) fp32, ≤ 0
    b: jax.Array,           # (B, S, R) fp32
    h0: jax.Array,          # (B, R)    fp32 initial state
    *,
    t_blk: int = 16,
    r_blk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, S, R = log_a.shape
    t_blk = min(t_blk, S)
    r_blk = min(r_blk, R)
    assert S % t_blk == 0 and R % r_blk == 0, (S, t_blk, R, r_blk)

    kernel = functools.partial(_rglru_kernel, t_blk=t_blk)
    layout = rglru_layout(B, S, R, t_blk, r_blk)
    return pl.pallas_call(
        kernel,
        grid=layout.grid,
        in_specs=layout.block_specs(),
        out_specs=layout.out_block_specs()[0],
        out_shape=layout.out_shape_structs([jnp.float32])[0],
        scratch_shapes=layout.scratch_shapes(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.dimension_semantics),
        interpret=interpret,
    )(log_a, b, h0[:, None, :])
