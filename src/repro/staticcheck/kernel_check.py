"""SC202 — abstract evaluation of the Pallas kernel grid layouts.

Every kernel module exposes a ``*_layout(...)`` returning the
:class:`repro.kernels.layout.KernelLayout` its ``pallas_call`` is built
from, so checking the layout checks the shipped kernel.  For
representative (small, structure-preserving) sizes the checker walks
every grid point and proves, per layout:

* each index map returns one block index per block dimension, in bounds
  for the operand's logical block grid;
* every output block is written, and two grid points mapping to the same
  output block differ only in ``"arbitrary"`` (sequential) grid dims —
  the exactly-once / accumulate-in-scratch discipline;
* accumulator scratch buffers are float32 (online-softmax / state carry
  precision);
* the paged-decode page walk — for the GQA pool layouts (grouped
  head-tile grid and the narrowest lane tile, across head-tile shapes
  including G > 4 and head widths below one lane row) and the MLA
  latent-pool layout alike — evaluated against adversarial page tables
  (contiguous, mostly-empty, holes inside the live prefix, inactive
  rows) on a layer stack: every block reads the prefetched layer, block
  indices stay inside the physical pool, ``-1`` holes borrow an
  already-live page of the *same row* (never physical page 0's
  bandwidth), every dead-tail step repeats the previous page so the
  pipeline issues no new DMA (the NaN-gather / wasted-bandwidth class
  the flash-decode PR fixed by hand), and a page block's columns fill
  whole 128-lane rows or are the pool's whole row.
"""
from __future__ import annotations

import itertools
from typing import List

from repro.staticcheck.engine import Finding

RULE_ID = "SC202"


def _blocks(shape, block):
    """Logical block-grid extent per dimension (ceil division)."""
    return tuple(-(-s // b) for s, b in zip(shape, block))


def _check_layout(layout, path: str,
                  grid_args=None) -> List[Finding]:
    """Walk every grid point of ``layout``; ``grid_args`` maps a grid
    point to the full index_map argument tuple (identity when None —
    used by scalar-prefetch layouts to append the prefetched operands)."""
    out: List[Finding] = []

    def fail(msg: str) -> None:
        out.append(Finding(RULE_ID, path, 0, f"{layout.name}: {msg}"))

    if len(layout.dimension_semantics) != len(layout.grid):
        fail(f"dimension_semantics arity {layout.dimension_semantics} != "
             f"grid arity {layout.grid}")
        return out
    for shape, dtype in layout.scratch:
        import jax.numpy as jnp
        if jnp.dtype(dtype) != jnp.float32:
            fail(f"scratch {shape} is {jnp.dtype(dtype)}; accumulators "
                 "must be float32")

    points = list(itertools.product(*(range(g) for g in layout.grid)))
    arb = [d for d, s in enumerate(layout.dimension_semantics)
           if s == "arbitrary"]

    for spec in tuple(layout.in_specs) + tuple(layout.out_specs):
        grid_of = _blocks(spec.shape, spec.block)
        for pt in points:
            args = grid_args(pt) if grid_args is not None else pt
            idx = tuple(int(v) for v in spec.index_map(*args))
            if len(idx) != len(spec.block):
                fail(f"{spec.name}: index map returned {len(idx)} indices "
                     f"for a {len(spec.block)}-dim block")
                break
            for d, (i, n) in enumerate(zip(idx, grid_of)):
                if not 0 <= i < n:
                    fail(f"{spec.name}: grid point {pt} maps dim {d} to "
                         f"block {i}, outside [0, {n})")
                    break
            else:
                continue
            break

    for spec in layout.out_specs:
        grid_of = _blocks(spec.shape, spec.block)
        writers: dict = {}
        for pt in points:
            args = grid_args(pt) if grid_args is not None else pt
            idx = tuple(int(v) for v in spec.index_map(*args))
            writers.setdefault(idx, []).append(pt)
        expected = set(itertools.product(*(range(n) for n in grid_of)))
        missing = expected - set(writers)
        if missing:
            fail(f"{spec.name}: {len(missing)} output block(s) never "
                 f"written, e.g. {sorted(missing)[0]}")
        for idx, pts in writers.items():
            base = pts[0]
            for p in pts[1:]:
                diff = [d for d in range(len(p)) if p[d] != base[d]]
                bad = [d for d in diff if d not in arb]
                if bad:
                    fail(f"{spec.name}: output block {idx} written from "
                         f"grid points {base} and {p}, which differ in "
                         f"non-arbitrary dim(s) {bad} — same block would "
                         "be computed twice in parallel")
                    break
    return out


def _check_simple_layouts() -> List[Finding]:
    from repro.kernels.flash_attention import flash_layout
    from repro.kernels.rglru_scan import rglru_layout
    from repro.kernels.rwkv6_wkv import wkv_layout

    out: List[Finding] = []
    out += _check_layout(
        flash_layout(BH=4, Sq=256, Sk=256, hd=8, q_blk=128, kv_blk=128,
                     group=2),
        "src/repro/kernels/flash_attention.py")
    out += _check_layout(
        wkv_layout(BH=2, S=64, N=16, chunk=32),
        "src/repro/kernels/rwkv6_wkv.py")
    out += _check_layout(
        rglru_layout(B=2, S=32, R=64, t_blk=16, r_blk=32),
        "src/repro/kernels/rglru_scan.py")
    return out


def _paged_tables():
    """Adversarial (page_table, pos_q) pairs: contiguous prefix, nearly
    empty, -1 hole inside the live prefix, inactive row."""
    import numpy as np
    pt = np.array([
        [2, 3, 4, 5],      # fully allocated, live through page 3 (pos 13)
        [6, -1, -1, -1],   # one live page (pos 1); dead tail
        [7, -1, 5, -1],    # hole at slot 1 inside the live prefix (pos 9)
        [-1, -1, -1, -1],  # inactive row
    ], dtype=np.int32)
    pos = np.array([13, 1, 9, -1], dtype=np.int32)
    return pt, pos


#: the layer-stack depth and the layer the paged walks are checked on
N_LAYERS, LAYER = 3, 2


def _walk_page_specs(layout, path, pt_np, pos_np, pt, pos, ps, n_pool,
                     points_for) -> List[Finding]:
    """Adversarial page walk over every ``*_pages`` operand of ``layout``.

    ``points_for(b, i)`` yields the grid point(s) covering row ``b`` at
    page-table step ``i`` (several for head-tiled grids).  The index map
    sees the prefetched (layer, table, positions); its first block
    coordinate is the layer, its second the physical page."""
    import jax.numpy as jnp
    out: List[Finding] = []

    def fail(msg: str) -> None:
        out.append(Finding(RULE_ID, path, 0, f"{layout.name}: {msg}"))

    B, pps = pt_np.shape
    kv = [s for s in layout.in_specs if s.name.endswith("_pages")]
    if not kv:
        fail("no *_pages operand found — page walk unchecked")
        return out
    lay = jnp.asarray([LAYER], jnp.int32)
    for spec in kv:
        cols = spec.block[-1]
        if cols % 128 and cols != spec.shape[-1]:
            fail(f"{spec.name}: a page block of {cols} columns neither "
                 f"fills whole 128-lane rows nor is the pool's row of "
                 f"{spec.shape[-1]}")
        for b in range(B):
            live = {int(e) for e in pt_np[b] if e >= 0}
            last_live = max(int(pos_np[b]), 0) // ps
            prev: dict = {}
            for i in range(pps):
                for point in points_for(b, i):
                    idx = spec.index_map(*point, lay, pt, pos)
                    if int(idx[0]) != LAYER:
                        fail(f"{spec.name}: row {b} step {i} reads layer "
                             f"{int(idx[0])}, not the prefetched {LAYER}")
                    page = int(idx[1])
                    if not 0 <= page < n_pool:
                        fail(f"{spec.name}: row {b} step {i} fetches "
                             f"physical page {page}, outside the pool "
                             f"[0, {n_pool})")
                    if pos_np[b] >= 0 and i <= last_live \
                            and pt_np[b, i] < 0 and page not in live:
                        fail(f"{spec.name}: row {b} has a -1 hole at slot "
                             f"{i} but fetches page {page}, not an "
                             f"already-live page of that row {sorted(live)}"
                             " — holes must cost no extra bandwidth")
                    key = point[:-1]       # pipeline: page dim is last
                    if i > last_live and key in prev \
                            and page != prev[key]:
                        fail(f"{spec.name}: dead-tail step {i} of row {b} "
                             f"fetches page {page} != previous {prev[key]} "
                             "— the tail must repeat its block index so no "
                             "new DMA is issued")
                    prev[key] = page
    return out


def _check_paged() -> List[Finding]:
    import jax.numpy as jnp
    from repro.kernels.paged_attention import paged_layout

    path = "src/repro/kernels/paged_attention.py"
    out: List[Finding] = []
    pt_np, pos_np = _paged_tables()
    pt, pos = jnp.asarray(pt_np), jnp.asarray(pos_np)
    lay = jnp.asarray([LAYER], jnp.int32)
    B, pps = pt_np.shape
    ps, n_pool = 4, 8

    # (K, G, hd) sweeps the head-tile grid: kt = K (one tile, the whole
    # row), kt < K (several tiles per row), the large-G regime (G > 4,
    # the MXU tile is one head, raised to the lane floor at hd = 64),
    # and the narrowest lane tiles (two heads of 64, one of 128)
    for K, G, hd, grouped in ((2, 2, 8, True), (4, 2, 64, True),
                              (4, 8, 64, True), (4, 2, 64, False),
                              (2, 2, 128, False)):
        layout = paged_layout(B=B, K=K, G=G, hd=hd, ps=ps, pps=pps,
                              n_pool=n_pool, n_layers=N_LAYERS,
                              grouped=grouped)
        # structural walk: index maps see the prefetched operands
        out += _check_layout(layout, path,
                             grid_args=lambda p: p + (lay, pt, pos))
        # the head-tile grid (B, K//kt, pps) iterates tiles in dim 1
        n_t = layout.grid[1]
        out += _walk_page_specs(
            layout, path, pt_np, pos_np, pt, pos, ps, n_pool,
            lambda b, i: [(b, t, i) for t in range(n_t)])
    return out


def _check_mla_paged() -> List[Finding]:
    import jax.numpy as jnp
    from repro.kernels.paged_attention import mla_paged_layout

    path = "src/repro/kernels/paged_attention.py"
    out: List[Finding] = []
    pt_np, pos_np = _paged_tables()
    pt, pos = jnp.asarray(pt_np), jnp.asarray(pos_np)
    B, pps = pt_np.shape
    ps, n_pool = 4, 8

    layout = mla_paged_layout(B=B, H=2, lora=8, rd=4, ps=ps, pps=pps,
                              n_pool=n_pool, n_layers=N_LAYERS)
    lay = jnp.asarray([LAYER], jnp.int32)
    out += _check_layout(layout, path,
                         grid_args=lambda p: p + (lay, pt, pos))
    # latent grid is (B, pps): one fused block walks both latent pools
    out += _walk_page_specs(
        layout, path, pt_np, pos_np, pt, pos, ps, n_pool,
        lambda b, i: [(b, i)])
    return out


def check() -> List[Finding]:
    return _check_simple_layouts() + _check_paged() + _check_mla_paged()
