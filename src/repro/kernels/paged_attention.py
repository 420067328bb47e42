"""Paged-attention decode (flash-decode over the page table).

One new token per sequence attends a vLLM-style paged KV cache: a shared
physical pool ``(P, K, page_size, hd)`` plus per-sequence page tables
``(B, pages_per_seq)``.  The *reference* walk
(``models.attention.decode_attention_paged``) gathers the table-bounded
dense ``(B, pps·ps, K, hd)`` view every step — transient bandwidth scales
with the table length, not with what the sequence actually holds.  Both
implementations here are **O(live pages)**: they walk each sequence's
pages with a running online-softmax ``(m, l, acc)`` and never materialize
the gathered view.

* :func:`paged_decode_attention` — the Pallas TPU kernel.  Grid
  ``(batch, kv_head, page)`` with the page dim minormost/sequential so the
  running state lives in VMEM scratch; the page table and per-sequence
  positions are **scalar-prefetched** so the BlockSpec index map can DMA
  exactly the physical page each grid step needs.  Pages past the last
  live one (slot ``t`` holds position ``t``, so pages ``> pos_q // ps``
  are dead weight) re-map to the last live page — the block index repeats,
  Pallas issues no new copy, and the tail of a mostly-empty table costs
  nothing.  Runs in interpret mode off-TPU (CPU tests).
* :func:`paged_decode_jnp` — a ``lax.scan`` fallback with the same
  contract and the same O(pages) transient footprint, for serving without
  ``use_pallas`` (the scan carries one ``(B, K, ps, hd)`` page gather per
  step instead of the whole table).

When the query group G is small (GQA with few q heads per kv head), the
per-kv-head grid issues a starving ``(G, hd) × (hd, ps)`` matmul per page;
``grouped=True`` (the default) switches to a ``(batch, head_tile, page)``
grid where a *tile* of ``kt`` kv heads' query groups hit the page in ONE
MXU call — a block-diagonal masked ``(kt·G, hd) × (hd, kt·ps)`` score
matmul (kt× redundant compute, traded for MXU occupancy).  ``kt`` is the
largest divisor of K keeping ``kt·G`` within one MXU band (≤ 8 query
rows), so G > 4 now runs grouped too: large groups simply tile one kv
head at a time (kt = 1) with shared (m, l, acc) scratch per tile.
Contract and numerics match the per-kv-head kernel, the scan fallback,
and the ``decode_attention_paged`` oracle.

MLA's latent cache gets the same treatment (:func:`mla_paged_layout` /
:func:`mla_paged_decode_attention`): pages hold compressed latents +
rope keys, the walk is MQA-shaped — H absorbed query heads against ONE
shared latent kv head of width ``lora + rd`` — and the accumulator reads
the latent itself (``W_vc`` is applied outside the kernel).

Masking rules (shared by both, and by the reference):

* slot ``t`` of a sequence holds absolute position ``t`` — a key is live
  iff ``t <= pos_q`` *and* its page-table entry is allocated (``>= 0``);
* ``pos_q < 0`` marks an inactive continuous-batching slot: every key is
  masked and the output row is **zero** (the reference's plain softmax
  returns a garbage average there instead; callers ignore those rows);
* unallocated entries (``-1``) cost no bandwidth — the kernel's index
  map re-maps them to an already-fetched live page (no new DMA) and the
  fallback's ``take`` fills with zeros without reading the pool (the
  clamp-to-page-0 of the old reference paid page 0's bandwidth for
  every hole).

Layouts: q ``(B, K, G, hd)`` (G = query heads per kv head), pool
``(P, K, ps, hd)``, table ``(B, pps)`` int32, pos_q ``(B,)`` int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.layout import KernelLayout, SpecDesc

NEG_INF = -2.0e38


def _decode_kernel(pt_ref, pq_ref, q_ref, k_ref, v_ref, o_ref,
                   m_s, l_s, acc_s, *,
                   scale: float, logit_cap: float, ps: int, n_pages: int):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    pq = pq_ref[b]
    live = jnp.logical_and(pq >= 0,
                           jnp.logical_and(i * ps <= pq, pt_ref[b, i] >= 0))

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (ps, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, ps)
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        t = i * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = t <= pq
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # mask p explicitly: a fully-dead row would otherwise see
        # exp(NEG_INF - NEG_INF) == 1 (NEG_INF is a finite sentinel)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())))
        m_s[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[...] /
                       jnp.maximum(l_s[...], 1e-37)).astype(o_ref.dtype)


def _decode_kernel_grouped(pt_ref, pq_ref, q_ref, k_ref, v_ref, o_ref,
                           m_s, l_s, acc_s, *,
                           scale: float, logit_cap: float, ps: int,
                           n_pages: int):
    """Grouped variant: grid (batch, head_tile, page) — a tile of ``kt``
    kv heads' query groups (kt·G query heads) hits the page in ONE MXU
    call.  The (kt·G, hd) × (hd, kt·ps) score matmul computes every
    q-head × kv-head block *within the tile*; a block-diagonal mask
    (query head r belongs to kv head r // G, key column c to kv head
    c // ps) keeps only the matching ones.  The kt× redundant compute is
    a win when G is small: the per-page matmul of the per-kv-head kernel
    is a skinny (G, hd) × (hd, ps) that starves the MXU.  The tile size
    comes from the BlockSpec (q block (1, kt, G, hd)) — the kernel body
    is tile-size agnostic, so G > 4 runs the same code with kt = 1."""
    b = pl.program_id(0)
    i = pl.program_id(2)
    _, kt, G, hd = q_ref.shape

    @pl.when(i == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    pq = pq_ref[b]
    live = jnp.logical_and(pq >= 0,
                           jnp.logical_and(i * ps <= pq, pt_ref[b, i] >= 0))

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32).reshape(kt * G, hd) * scale
        k = k_ref[0].astype(jnp.float32).reshape(kt * ps, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (ktG, ktps)
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        row_head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
        col_head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // ps
        t = i * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) % ps
        mask = jnp.logical_and(row_head == col_head, t <= pq)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # mask p explicitly: a fully-dead row would otherwise see
        # exp(NEG_INF - NEG_INF) == 1 (NEG_INF is a finite sentinel)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
        # cross-head products are exact zeros (p is masked), so the one
        # (ktG, ktps) × (ktps, hd) value matmul sums only the right block
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32).reshape(kt * ps, hd),
            (((1,), (0,)), ((), ())))
        m_s[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_s[...] /
                    jnp.maximum(l_s[...], 1e-37)
                    ).reshape(kt, G, hd).astype(o_ref.dtype)


def _decode_kernel_mla(pt_ref, pq_ref, q_ref, ckv_ref, kr_ref, o_ref,
                       m_s, l_s, acc_s, *,
                       scale: float, ps: int, n_pages: int):
    """MLA latent flash-decode: grid (batch, page).  The latent cache is
    MQA-shaped — ONE shared latent kv head serves all H absorbed query
    heads — so each page costs one (H, lora+rd) × (lora+rd, ps) score
    matmul (keys are the concatenation of the compressed latent and the
    rotated rope key) and one (H, ps) × (ps, lora) accumulate against the
    latent itself (``W_vc`` expands outside the kernel)."""
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    pq = pq_ref[b]
    live = jnp.logical_and(pq >= 0,
                           jnp.logical_and(i * ps <= pq, pt_ref[b, i] >= 0))

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale           # (H, lora+rd)
        c = ckv_ref[0].astype(jnp.float32)                 # (ps, lora)
        r = kr_ref[0].astype(jnp.float32)                  # (ps, rd)
        k = jnp.concatenate([c, r], axis=1)                # (ps, lora+rd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (H, ps)
        t = i * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = t <= pq
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # mask p explicitly: a fully-dead row would otherwise see
        # exp(NEG_INF - NEG_INF) == 1 (NEG_INF is a finite sentinel)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())))                # (H, lora)
        m_s[...] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_s[...] /
                    jnp.maximum(l_s[...], 1e-37)).astype(o_ref.dtype)


def _page_block(b, i, pt_ref, pq_ref, ps: int):
    """Physical page for grid step (b, ·, i).  Dead tail pages (beyond the
    last live page) re-map to the last live page: the block index repeats
    across those steps, so the pipeline issues no new DMA for them.
    A -1 hole *inside* the live prefix (never produced by the allocator's
    contiguous-prefix tables, but legal input) borrows the last live
    page's entry — an already-fetched page, not physical page 0, so holes
    cost no extra bandwidth; compute is skipped either way.  Inactive
    rows (pos < 0, table all -1) clamp to page 0 with all compute
    skipped."""
    last_live = jnp.maximum(pq_ref[b], 0) // ps
    ii = jnp.minimum(i, last_live)
    entry = pt_ref[b, ii]
    entry = jnp.where(entry >= 0, entry, pt_ref[b, last_live])
    return jnp.maximum(entry, 0)


def group_tile(K: int, G: int) -> int:
    """kv heads per grouped-grid tile: the largest divisor of K keeping
    the tile's query rows (kt·G) within one MXU band (8 rows).  Small
    groups pack several kv heads per matmul; G >= 8 tiles one kv head at
    a time — still grouped (shared scratch, one matmul per page), just
    without cross-head packing."""
    kt = 1
    for d in range(1, K + 1):
        if K % d == 0 and d * G <= max(G, 8):
            kt = d
    return kt


def paged_layout(B: int, K: int, G: int, hd: int, ps: int, pps: int,
                 n_pool: int, *, grouped: bool) -> KernelLayout:
    """Grid layout of the flash-decode kernel (both variants).  The
    ``pallas_call`` below is built from this; ``staticcheck.kernel_check``
    abstractly evaluates the same index maps over adversarial page
    tables.  Page-table and position operands are scalar-prefetched and
    therefore not listed as blocked inputs."""
    if grouped:
        kt = group_tile(K, G)

        def kv_map_g(b, t, i, pt, pq):
            return (_page_block(b, i, pt, pq, ps), t, 0, 0)

        def q_map_g(b, t, i, pt, pq):
            return (b, t, 0, 0)

        return KernelLayout(
            name="paged_decode_grouped",
            grid=(B, K // kt, pps),
            num_scalar_prefetch=2,
            in_specs=(
                SpecDesc("q", (B, K, G, hd), (1, kt, G, hd), q_map_g),
                SpecDesc("k_pages", (n_pool, K, ps, hd), (1, kt, ps, hd),
                         kv_map_g),
                SpecDesc("v_pages", (n_pool, K, ps, hd), (1, kt, ps, hd),
                         kv_map_g),
            ),
            out_specs=(
                SpecDesc("o", (B, K, G, hd), (1, kt, G, hd), q_map_g),),
            scratch=(((kt * G, 1), jnp.float32),
                     ((kt * G, 1), jnp.float32),
                     ((kt * G, hd), jnp.float32)),
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        )

    def kv_map(b, h, i, pt, pq):
        return (_page_block(b, i, pt, pq, ps), h, 0, 0)

    def q_map(b, h, i, pt, pq):
        return (b, h, 0, 0)

    return KernelLayout(
        name="paged_decode",
        grid=(B, K, pps),
        num_scalar_prefetch=2,
        in_specs=(
            SpecDesc("q", (B, K, G, hd), (1, 1, G, hd), q_map),
            SpecDesc("k_pages", (n_pool, K, ps, hd), (1, 1, ps, hd), kv_map),
            SpecDesc("v_pages", (n_pool, K, ps, hd), (1, 1, ps, hd), kv_map),
        ),
        out_specs=(SpecDesc("o", (B, K, G, hd), (1, 1, G, hd), q_map),),
        scratch=(((G, 1), jnp.float32),
                 ((G, 1), jnp.float32),
                 ((G, hd), jnp.float32)),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


def paged_decode_attention(
    q: jax.Array,            # (B, K, G, hd)
    k_pages: jax.Array,      # (P, K, ps, hd)
    v_pages: jax.Array,      # (P, K, ps, hd)
    page_table: jax.Array,   # (B, pps) int32; -1 = unallocated
    pos_q: jax.Array,        # (B,) int32; -1 = inactive slot
    *,
    scale: float,
    logit_cap: float = 0.0,
    interpret: bool = False,
    grouped: "bool | None" = None,
) -> jax.Array:
    B, K, G, hd = q.shape
    ps = k_pages.shape[2]
    pps = page_table.shape[1]

    # the grouped grid tiles head batches to MXU-friendly sizes for every
    # G (see group_tile), so it is the default; grouped=False keeps the
    # per-kv-head grid for A/B numerics checks
    if grouped is None:
        grouped = True
    layout = paged_layout(B, K, G, hd, ps, pps, k_pages.shape[0],
                          grouped=grouped)
    if grouped:
        kernel = functools.partial(
            _decode_kernel_grouped, scale=scale, logit_cap=logit_cap,
            ps=ps, n_pages=pps)
    else:
        kernel = functools.partial(
            _decode_kernel, scale=scale, logit_cap=logit_cap, ps=ps,
            n_pages=pps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=layout.num_scalar_prefetch,
        grid=layout.grid,
        in_specs=layout.block_specs(),
        out_specs=layout.out_block_specs()[0],
        scratch_shapes=layout.scratch_shapes(),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=layout.out_shape_structs([q.dtype])[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.dimension_semantics),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos_q.astype(jnp.int32), q,
      k_pages, v_pages)


def paged_decode_jnp(
    q: jax.Array,            # (B, K, G, hd)
    k_pages: jax.Array,      # (P, K, ps, hd)
    v_pages: jax.Array,      # (P, K, ps, hd)
    page_table: jax.Array,   # (B, pps) int32; -1 = unallocated
    pos_q: jax.Array,        # (B,) int32; -1 = inactive slot
    *,
    scale: float,
    logit_cap: float = 0.0,
) -> jax.Array:
    """Same contract as the kernel, pure jnp: ``lax.scan`` over logical
    pages carrying (m, l, acc) — transient memory is one (B, K, ps, hd)
    page gather per step, not the (B, pps·ps, K, hd) view."""
    B, K, G, hd = q.shape
    ps = k_pages.shape[2]
    pps = page_table.shape[1]
    qf = q.astype(jnp.float32) * scale
    pq = pos_q.astype(jnp.int32)

    def body(carry, i):
        m, l, acc = carry
        entry = jax.lax.dynamic_index_in_dim(page_table, i, axis=1,
                                             keepdims=False)     # (B,)
        # fill-mode gather: -1 is out of bounds -> zeros, page 0 untouched
        kb = jnp.take(k_pages, entry, axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)          # (B,K,ps,hd)
        vb = jnp.take(v_pages, entry, axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)
        s = jnp.einsum("bkgd,bktd->bkgt", qf, kb)                # (B,K,G,ps)
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        t = i * ps + jnp.arange(ps, dtype=jnp.int32)
        valid = (entry[:, None] >= 0) & (t[None, :] <= pq[:, None])  # (B,ps)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(valid[:, None, None, :],
                      jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgt,bktd->bkgd", p, vb)
        return (m_new, l, acc), None

    m0 = jnp.full((B, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G), jnp.float32)
    a0 = jnp.zeros((B, K, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  jnp.arange(pps, dtype=jnp.int32))
    return (acc / jnp.maximum(l, 1e-37)[..., None]).astype(q.dtype)


def mla_paged_layout(B: int, H: int, lora: int, rd: int, ps: int,
                     pps: int, n_pool: int) -> KernelLayout:
    """Grid layout of the MLA latent flash-decode kernel.  The latent
    pool has no kv-head axis (MQA-shaped), so the grid is just
    (batch, page); the page index maps reuse :func:`_page_block` and get
    the same adversarial-table walk in ``staticcheck.kernel_check``."""
    def kv_map(b, i, pt, pq):
        return (_page_block(b, i, pt, pq, ps), 0, 0)

    def q_map(b, i, pt, pq):
        return (b, 0, 0)

    return KernelLayout(
        name="mla_paged_decode",
        grid=(B, pps),
        num_scalar_prefetch=2,
        in_specs=(
            SpecDesc("q_lat", (B, H, lora + rd), (1, H, lora + rd), q_map),
            SpecDesc("ckv_pages", (n_pool, ps, lora), (1, ps, lora), kv_map),
            SpecDesc("krope_pages", (n_pool, ps, rd), (1, ps, rd), kv_map),
        ),
        out_specs=(SpecDesc("o", (B, H, lora), (1, H, lora), q_map),),
        scratch=(((H, 1), jnp.float32),
                 ((H, 1), jnp.float32),
                 ((H, lora), jnp.float32)),
        dimension_semantics=("parallel", "arbitrary"),
    )


def mla_paged_decode_attention(
    q_lat: jax.Array,        # (B, H, lora + rd) absorbed query
    ckv_pages: jax.Array,    # (P, ps, lora)
    krope_pages: jax.Array,  # (P, ps, rd)
    page_table: jax.Array,   # (B, pps) int32; -1 = unallocated
    pos_q: jax.Array,        # (B,) int32; -1 = inactive slot
    *,
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Latent-space flash-decode over MLA pages; returns the latent
    context ``(B, H, lora)`` (caller applies ``W_vc`` and the output
    projection).  Same masking contract as :func:`paged_decode_attention`:
    holes cost no DMA, dead tails repeat the last live page, inactive
    rows come back zero."""
    B, H, qd = q_lat.shape
    ps, lora = ckv_pages.shape[1], ckv_pages.shape[2]
    rd = krope_pages.shape[2]
    assert qd == lora + rd, (qd, lora, rd)
    pps = page_table.shape[1]

    layout = mla_paged_layout(B, H, lora, rd, ps, pps, ckv_pages.shape[0])
    kernel = functools.partial(_decode_kernel_mla, scale=scale, ps=ps,
                               n_pages=pps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=layout.num_scalar_prefetch,
        grid=layout.grid,
        in_specs=layout.block_specs(),
        out_specs=layout.out_block_specs()[0],
        scratch_shapes=layout.scratch_shapes(),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=layout.out_shape_structs([q_lat.dtype])[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.dimension_semantics),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos_q.astype(jnp.int32), q_lat,
      ckv_pages, krope_pages)


def mla_paged_decode_jnp(
    q_lat: jax.Array,        # (B, H, lora + rd) absorbed query
    ckv_pages: jax.Array,    # (P, ps, lora)
    krope_pages: jax.Array,  # (P, ps, rd)
    page_table: jax.Array,   # (B, pps) int32; -1 = unallocated
    pos_q: jax.Array,        # (B,) int32; -1 = inactive slot
    *,
    scale: float,
) -> jax.Array:
    """Same contract as :func:`mla_paged_decode_attention`, pure jnp:
    ``lax.scan`` over logical pages carrying (m, l, acc) — transient
    memory is one (B, ps, lora + rd) page gather per step."""
    B, H, _ = q_lat.shape
    ps, lora = ckv_pages.shape[1], ckv_pages.shape[2]
    pps = page_table.shape[1]
    qf = q_lat.astype(jnp.float32) * scale
    pq = pos_q.astype(jnp.int32)

    def body(carry, i):
        m, l, acc = carry
        entry = jax.lax.dynamic_index_in_dim(page_table, i, axis=1,
                                             keepdims=False)     # (B,)
        cb = jnp.take(ckv_pages, entry, axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)          # (B,ps,lora)
        rb = jnp.take(krope_pages, entry, axis=0, mode="fill",
                      fill_value=0).astype(jnp.float32)          # (B,ps,rd)
        kb = jnp.concatenate([cb, rb], axis=-1)                  # (B,ps,l+r)
        s = jnp.einsum("bhe,bte->bht", qf, kb)                   # (B,H,ps)
        t = i * ps + jnp.arange(ps, dtype=jnp.int32)
        valid = (entry[:, None] >= 0) & (t[None, :] <= pq[:, None])  # (B,ps)
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(valid[:, None, :],
                      jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bht,btl->bhl", p, cb)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H), jnp.float32)
    a0 = jnp.zeros((B, H, lora), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  jnp.arange(pps, dtype=jnp.int32))
    return (acc / jnp.maximum(l, 1e-37)[..., None]).astype(q_lat.dtype)
