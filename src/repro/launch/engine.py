"""Resumable continuous-batching serving engine + the real-payload seam.

``launch/executor.py:run_continuous`` used to be a ~170-line monolith whose
entire state (page pool, slot table, host page table, queue cursor) was
function-locals — unrecoverable, unpreemptible, unreachable from a platform
job.  :class:`ServingEngine` is that loop turned into an explicit state
machine:

* **admit** — one admission round: FIFO requests from the queue into free
  decode slots, each prefilled alone in a one-row view of the cache (rows
  mid-decode cost the round nothing), gated by a per-shard *worst-case
  page reservation* scaled by ``ServeSpec.overcommit`` (1.0 = the old
  conservative admission; > 1.0 = optimistic admission with preemption).
  Pages are allocated lazily (prompt pages at admission, one page at a
  time as decode grows), so overcommitted admission can actually run out
  — see evict.  With **prefix caching** on (the default for all-global
  paged decoders), each prompt's full pages are chain-hashed against the
  shard's prefix index: hits are attached read-only with a refcount bump
  — no prefill compute, no new residency — a first-divergent-token
  overlap gets its page copy-on-write duplicated, and each admitted
  request's prefill covers only its uncached tail (at its start offset).
  A request whose prefix is being prefilled by an earlier request in the
  same round defers one round and attaches instead of recomputing, so N
  requests sharing a P-token prefix pay ~one prefill and one set of
  resident prefix pages.
* **step** — one batched decode step over every active slot; grows each
  sequence's page list on demand first.  On page exhaustion the engine
  **evicts the youngest sequence in the starving shard** back to the front
  of the queue (requeue-on-eviction): its pages are freed, its partial
  generation is discarded, and re-admission re-prefills from the prompt.
  Greedy decode is deterministic, so the re-generated response is
  identical — no request is ever lost or answered differently.  The
  oldest sequence in a shard is never evicted, so it always completes:
  admission is reservation-bounded and the queue drains FIFO — no
  deadlock, no livelock.
* **finish** — frees pages, logs the completed response (exactly-once by
  request id), releases the reservation.
* **snapshot / restore** — the whole engine state (pool free lists, slot
  records, host page table, queue, responses, the append-only
  :attr:`journal` of admissions/evictions/completions, KV-cache arrays
  pulled to host) as one plain-Python structure.  ``restore`` on a fresh
  engine reproduces the exact device state, so a killed-and-restarted
  server continues **byte-identically** with the uninterrupted run.  A
  platform pod persists snapshots to the job volume, journals request
  *claims* there separately, and replays the claim suffix after
  ``restore`` to recover requests claimed after the last snapshot (see
  ``core/server.py``).

:class:`RealServePayload` / :class:`RealDryRunPayload` are the builders the
``FrameworkAdapter.payload`` hook returns so platform serve jobs run this
engine (and dryrun jobs real compile cells) inside their workload pods,
under the unchanged Guardian/LCM dependability machinery.
"""
from __future__ import annotations

import hashlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.core.jobspec import JobSpec, ServeSpec
from repro.launch import spans

#: Parent hash of a prompt's first page in the chained prefix hash.
PREFIX_ROOT = "root"


def page_chain_hashes(tokens, page_size: int) -> List[Tuple[str, str]]:
    """``(parent_hash, chain_hash)`` for every FULL page of a prompt.

    The chain hash of page ``i`` commits to the entire prefix through
    page ``i`` (it hashes the parent's chain hash plus the page's token
    ids), so two prompts share page ``i`` iff they agree on ALL tokens
    up to and including it — a hash hit is a safe alias, not a guess.
    blake2b, not Python's builtin ``hash``: the index must round-trip
    snapshots byte-identically across process incarnations, and builtin
    hashes are salted per process."""
    toks = np.asarray(tokens, np.int64)
    out: List[Tuple[str, str]] = []
    parent = PREFIX_ROOT
    for i in range(len(toks) // page_size):
        chunk = toks[i * page_size:(i + 1) * page_size]
        h = hashlib.blake2b(parent.encode() + chunk.tobytes(),
                            digest_size=16).hexdigest()
        out.append((parent, h))
        parent = h
    return out


class PagePool:
    """Host-side physical-page allocator for the paged KV cache.

    Manages page ids ``0 .. n_pages-1``.  ``n_shards > 1`` partitions the
    id space into contiguous per-shard free lists.  The pool's pages dim
    shards contiguously over the data axis (``cache_pages`` rule), so
    allocating a sequence's pages from its own data shard's range keeps
    every decode gather/scatter data-shard-local — the runtime half of the
    locality contract whose spec half is
    ``dist.sharding.check_cache_locality``.

    Pages are **refcounted** so prefix caching can alias one physical page
    into many sequences' tables: ``alloc`` hands pages out at refcount 1,
    ``attach`` bumps a cached page (pulling it back off the free list if
    it was cached-but-free), ``free`` decrements — a page returns to its
    shard's free list only when nobody references it.  Hash-addressed
    prefix metadata (chain hash, parent hash, token content) lives in
    ``page_meta`` with a per-shard ``prefix_index`` mapping
    ``parent_hash -> {chain_hash: page}``.  A freed page KEEPS its
    metadata (cached-but-free, vLLM-style: the KV bytes are intact until
    the allocator reuses the physical page, at which point ``alloc``
    deregisters it) — so a finished sequence's prefix stays hittable for
    followers at zero residency cost.
    """

    def __init__(self, n_pages: int, n_shards: int = 1):
        assert n_shards >= 1 and n_pages % n_shards == 0, (n_pages, n_shards)
        self.n_pages = n_pages
        self.n_shards = n_shards
        per = n_pages // n_shards
        self.free_lists: List[List[int]] = [
            list(range(s * per, (s + 1) * per)) for s in range(n_shards)]
        self.high_water = 0
        self.refcount: List[int] = [0] * n_pages
        # page -> {"parent": str, "hash": str, "tokens": [int]}
        self.page_meta: Dict[int, dict] = {}
        # per shard: parent_hash -> {chain_hash: page}
        self.prefix_index: List[Dict[str, Dict[str, int]]] = [
            {} for _ in range(n_shards)]

    @property
    def in_use(self) -> int:
        """Unique resident pages (each aliased page counts once)."""
        return self.n_pages - sum(len(f) for f in self.free_lists)

    def shard_of(self, p: int) -> int:
        per = self.n_pages // self.n_shards
        return min(p // per, self.n_shards - 1)

    def alloc(self, n: int, shard: int = 0) -> Optional[List[int]]:
        fl = self.free_lists[shard]
        if n > len(fl):
            return None
        pages, self.free_lists[shard] = fl[:n], fl[n:]
        for p in pages:
            assert self.refcount[p] == 0, (p, self.refcount[p])
            self.refcount[p] = 1
            self._deregister(p)          # physical reuse ends its cache life
        self.high_water = max(self.high_water, self.in_use)
        return pages

    def free(self, pages: List[int]) -> None:
        """Drop one reference per page; pages nobody references anymore
        return to their home shard's free list (metadata retained —
        cached-but-free until reallocated)."""
        for p in pages:
            assert self.refcount[p] > 0, f"free of unreferenced page {p}"
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self.free_lists[self.shard_of(p)].append(p)

    def attach(self, p: int) -> None:
        """Add a reference to a cached page (prefix hit).  A
        cached-but-free page leaves the free list again — its KV bytes
        were never touched, so no prefill is needed."""
        if self.refcount[p] == 0:
            self.free_lists[self.shard_of(p)].remove(p)
        self.refcount[p] += 1
        self.high_water = max(self.high_water, self.in_use)

    def lookup(self, shard: int, parent: str, chain: str) -> Optional[int]:
        return self.prefix_index[shard].get(parent, {}).get(chain)

    def candidates(self, shard: int, parent: str) -> Dict[str, int]:
        """All cached continuations of ``parent`` (CoW donor search)."""
        return self.prefix_index[shard].get(parent, {})

    def publish(self, page: int, parent: str, chain: str, tokens) -> bool:
        """Register a full, immutable page in the prefix index.  First
        publisher wins: an already-indexed chain (or a page already
        carrying metadata) is left alone."""
        idx = self.prefix_index[self.shard_of(page)]
        kids = idx.setdefault(parent, {})
        if chain in kids or page in self.page_meta:
            if not kids:
                del idx[parent]
            return False
        kids[chain] = page
        self.page_meta[page] = {"parent": parent, "hash": chain,
                                "tokens": [int(t) for t in tokens]}
        return True

    def _deregister(self, p: int) -> None:
        meta = self.page_meta.pop(p, None)
        if meta is None:
            return
        idx = self.prefix_index[self.shard_of(p)]
        kids = idx.get(meta["parent"])
        if kids is not None and kids.get(meta["hash"]) == p:
            del kids[meta["hash"]]
            if not kids:
                del idx[meta["parent"]]


def _set_page_tables(cache, host_table: np.ndarray):
    """Broadcast the (B, pps) host page table into every per-layer
    ``page_table`` leaf (layers index their own pools identically).  Each
    new leaf keeps the old one's placement, so the jitted steps see the
    same input types and compile once."""
    import jax
    import jax.numpy as jnp

    with spans.span("engine.tables"):
        table = jnp.asarray(host_table, jnp.int32)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(cache)
        out = []
        for path, leaf in leaves:
            if getattr(path[-1], "key", None) == "page_table":
                out.append(jax.device_put(
                    jnp.broadcast_to(table, leaf.shape), leaf.sharding))
            else:
                out.append(leaf)
        return jax.tree.unflatten(treedef, out)


def _copy_pool_pages(cache, srcs, dsts):
    """Device-side ``src -> dst`` page copies in every layer's K/V pool —
    the copy half of copy-on-write: a sequence diverging mid-page from a
    cached prefix gets the partially-shared page duplicated into its own
    private page, then the chunk prefill overwrites the divergent tail
    slots.  Scanned-group pool leaves carry a leading layers dim, so the
    pages axis is 1 there and 0 on unrolled leaves (mirrors
    ``models.model._slot_axis``).  ``dsts`` past the pool are dropped."""
    import jax

    from repro.models.model import _POOL_LEAVES

    leaves, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for path, leaf in leaves:
        if getattr(path[-1], "key", None) in _POOL_LEAVES:
            if any(getattr(p, "key", None) == "groups" for p in path):
                leaf = leaf.at[:, dsts].set(leaf[:, srcs], mode="drop")
            else:
                leaf = leaf.at[dsts].set(leaf[srcs], mode="drop")
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def _to_host(tokens, counts, sp):
    """The step's next tokens on the host.  A MoE stack's step also gives
    its expert counts (``models.moe``): they come back in the same
    transfer and become the span's ``picks_here`` and ``expert_rows``."""
    import jax

    if not counts:
        return np.asarray(tokens)
    tokens, (picks, rows) = jax.device_get((tokens, counts[0]))
    sp.attrs.update(picks_here=int(picks), expert_rows=int(rows))
    return tokens


# ---------------------------------------------------------------------------
# Requests and per-slot records
# ---------------------------------------------------------------------------
@dataclass
class Request:
    """One serving request: a prompt and a greedy generation budget."""

    req: int                       # stable id (claim index on the platform)
    tokens: np.ndarray             # (L,) prompt token ids
    gen_len: int                   # tokens to generate (incl. prefill token)


@dataclass
class SeqRecord:
    """Everything the engine knows about one active decode slot."""

    request: Request
    pages: List[int]               # physical pages held, table order
    shard: int
    need_worst: int                # reserved pages (worst case minus shared)
    remaining: int                 # tokens still to generate
    out_tokens: List[int] = field(default_factory=list)
    admit_seq: int = 0             # admission order; larger = younger
    n_shared: int = 0              # leading pages attached from the index
    cached_tokens: int = 0         # prompt tokens served from the cache


class ServingEngine:
    """Continuous batching over the paged cache as a resumable state
    machine.  See the module docstring for the state-machine contract."""

    def __init__(self, cfg, ctx, params, sv: ServeSpec):
        import jax

        from repro.configs.base import GLOBAL_ATTN
        from repro.models.model import init_cache, num_pages
        from repro.train.steps import make_serve_steps

        # ValueError, not SystemExit: inside a platform pod these must fail
        # THIS pod/job (sim catches Exception), never the whole simulator;
        # run_continuous maps them to SystemExit for the CLI
        if cfg.cache_layout != "paged":
            raise ValueError("--continuous requires --layout paged")
        # ragged (each admitted prompt padded to a page multiple, the
        # padding masked by its length) covers every decoder-only stack:
        # paged globals + ring locals mask their writes, paged MLA
        # latents scatter per row, recurrent/RWKV carries are
        # length-masked.  Enc-dec prefills the exact prompt with its
        # encoder input (the model's ragged path has no cross-attention).
        ragged_ok = not cfg.is_encoder_decoder
        ragged = ragged_ok if sv.ragged_prefill is None else sv.ragged_prefill
        if ragged and not ragged_ok:
            raise ValueError(
                "--ragged-prefill needs a decoder-only stack; enc-dec "
                "prefills each prompt at its exact length")
        # hash-addressed prefix caching: needs the chunked-prefill seam,
        # which covers all-global paged decoders only (ring locals would
        # have to replay the evicted prefix; vision frontends shift pos 0)
        self.prefix_cache = bool(sv.prefix_cache) and ragged \
            and set(cfg.layer_kinds()) == {GLOBAL_ATTN} \
            and cfg.frontend != "vision"

        B, P, G = sv.batch, sv.prompt_len, sv.gen
        self.cfg, self.ctx, self.params, self.sv = cfg, ctx, params, sv
        self.ragged = ragged
        self.B = B
        self.ps = cfg.page_size
        self.max_len = P + G
        self.pps = num_pages(self.max_len, self.ps)
        budget = sv.page_budget or B * self.pps
        if budget < self.pps:
            raise ValueError(f"--page-budget {budget} cannot hold one "
                             f"request ({self.pps} pages)")
        self.overcommit = sv.overcommit or 1.0
        if self.overcommit < 1.0:
            raise ValueError(f"--overcommit {self.overcommit} must be >= 1")

        self.prefill, self.decode = make_serve_steps(cfg, ctx)
        self._copy_pages = jax.jit(_copy_pool_pages, donate_argnums=(0,))
        from repro.launch.specs import src_len_for
        self.src_len = src_len_for(cfg, self.max_len)
        self.cache = self._on_mesh(init_cache(
            cfg, B, self.max_len, self.src_len, layout="paged",
            page_budget=budget, paged_tables="empty"))

        # page→data-shard locality (see PagePool); one shard when the budget
        # doesn't split evenly or a shard couldn't hold a full request
        n_shards = dict(zip(ctx.mesh.axis_names, ctx.mesh.axis_sizes)).get(
            "data", 1) if ctx.mesh is not None else 1
        if budget % n_shards or B % n_shards \
                or budget // n_shards < self.pps:
            n_shards = 1
        self.pool = PagePool(budget, n_shards)
        self.per_shard = budget // n_shards
        self.reserved = [0] * n_shards          # worst-case pages admitted
        self.host_table = np.full((B, self.pps), -1, np.int32)

        self.slots: List[Optional[SeqRecord]] = [None] * B
        self.toks = np.zeros((B, 1), np.int64)
        self.pos = np.full((B,), -1, np.int64)
        self.queue: Deque[Request] = deque()
        # request id -> when it (re)entered the queue, for its
        # ``engine.queue`` span; host diagnostics, never snapshotted
        self._queued_at: Dict[int, float] = {}
        self.responses: Dict[int, List[int]] = {}
        self.journal: List[dict] = []

        # stats
        self.decode_steps = 0
        self.generated = 0
        self.stalled_admissions = 0
        self.evictions = 0
        self._admit_seq = 0
        self.prefill_tokens = 0      # prompt tokens actually computed
        self.cached_tokens = 0       # prompt tokens served from the cache
        self.prefix_hits = 0         # admissions reusing >= 1 cached page
        self.prefix_misses = 0
        self.cow_copies = 0          # copy-on-write page duplications

    def _src_embeds(self, req_id: int):
        """Deterministic stub frontend embeddings for one enc-dec request,
        keyed on the request id alone — an evict-replay or a restored
        incarnation re-synthesizes the identical encoder input, keeping
        the cross K/V (and so the whole continuation) byte-identical."""
        import jax
        import jax.numpy as jnp

        return 0.02 * jax.random.normal(
            jax.random.key(req_id), (1, self.src_len, self.cfg.d_model),
            jnp.float32)

    # -- queue -------------------------------------------------------------
    def submit(self, request: Request) -> None:
        """Enqueue a request (FIFO).  Rejects requests whose worst-case
        page need exceeds a shard's capacity — admitting one would
        deadlock the pool."""
        from repro.models.model import num_pages
        need = num_pages(len(request.tokens) + request.gen_len, self.ps)
        if need > self.per_shard:
            raise ValueError(
                f"request {request.req} needs {need} pages worst-case; "
                f"a shard holds {self.per_shard}")
        self.queue.append(request)
        self._queued_at[request.req] = time.perf_counter()

    def free_slot_count(self) -> int:
        return sum(1 for s in self.slots if s is None)

    @property
    def idle(self) -> bool:
        return not self.queue and all(s is None for s in self.slots)

    def active_records(self) -> List[SeqRecord]:
        return [s for s in self.slots if s is not None]

    def _shard_of(self, b: int) -> int:
        return b * self.pool.n_shards // self.B

    def unique_resident_pages(self) -> int:
        """Physical pages referenced by anyone (aliases count once)."""
        return self.pool.in_use

    def resident_prefix_pages(self) -> int:
        """Unique physical pages serving some active sequence's cached
        prompt span — the residency N prefix-sharing requests split."""
        return len({p for rec in self.slots if rec is not None
                    for p in rec.pages[:rec.n_shared]})

    # -- prefix matching ---------------------------------------------------
    def _match_prefix(self, req: Request, shard: int, pending) -> tuple:
        """Match a prompt against the shard's prefix index.

        Returns ``(shared, cow, C, hashes, defer)``: the leading cached
        pages to attach read-only, an optional ``(src_page, overlap)``
        copy-on-write donor for the first divergent page, the number of
        prompt tokens served from the cache (``C = full-page span +
        overlap``), the prompt's per-page chain hashes, and whether to
        defer admission because an unmatched hash is being published by
        THIS round's prefill (first-come-first-prefilled: the follower
        waits one round and attaches instead of recomputing).

        At least one prompt token is always left uncached (cap at
        ``(L-1)//ps`` pages / ``L-1`` tokens): the next-token logits need
        the last prompt token's hidden state, so a fully-cached prompt
        must still compute its final token."""
        L = len(req.tokens)
        if not self.prefix_cache:
            return [], None, 0, [], False
        hashes = page_chain_hashes(req.tokens, self.ps)
        shared: List[int] = []
        for i in range((L - 1) // self.ps):
            parent, chain = hashes[i]
            page = self.pool.lookup(shard, parent, chain)
            if page is None:
                if chain in pending:
                    return [], None, 0, hashes, True
                break
            shared.append(page)
        m = len(shared)
        cow = None
        parent = hashes[m - 1][1] if m else PREFIX_ROOT
        limit = min(self.ps, L - 1 - m * self.ps)
        if limit > 0:
            chunk = np.asarray(req.tokens[m * self.ps:
                                          m * self.ps + limit], np.int64)
            best_page, best_ov = None, 0
            # deterministic donor choice: sorted by chain hash
            for chain in sorted(self.pool.candidates(shard, parent)):
                page = self.pool.candidates(shard, parent)[chain]
                ptoks = np.asarray(
                    self.pool.page_meta[page]["tokens"][:limit], np.int64)
                n = min(len(chunk), len(ptoks))
                ne = chunk[:n] != ptoks[:n]
                ov = int(np.argmax(ne)) if ne.any() else n
                if ov > best_ov:
                    best_page, best_ov = page, ov
            if best_ov > 0:
                cow = (best_page, best_ov)
        C = m * self.ps + (cow[1] if cow else 0)
        return shared, cow, C, hashes, False

    # -- admission ---------------------------------------------------------
    def admit(self) -> List[int]:
        """One admission round: FIFO queue head into free slots while the
        shard reservation (scaled by ``overcommit``) and the prompt's
        physical pages are available, then prefills each admitted request
        in a one-row view of the cache.  Returns the admitted request
        ids."""
        with spans.span("engine.admit") as sp:
            with spans.span("engine.match"):
                admitted, plans, cow_pairs = self._match_round()
            out = self._prefill_round(admitted, plans, cow_pairs) \
                if admitted else []
            sp.attrs.update(rows=len(out), rids=tuple(out))
        for rid in out:
            t_queued = self._queued_at.pop(rid, None)
            if t_queued is not None:
                spans.mark("engine.queue", t_queued, sp.t0, rid=rid)
        return out

    def _match_round(self) -> tuple:
        """The round's slot loop: queue head into each free slot while its
        prefix match, reservation and prompt pages allow.  Returns
        ``(admitted, plans, cow_pairs)``: ``(slot, request)`` pairs, each
        slot's ``(C, hashes, m)`` and the copy-on-write page pairs."""
        from repro.models.model import num_pages

        admitted: List[tuple] = []               # (slot, request)
        plans: Dict[int, tuple] = {}             # slot -> (C, hashes, m)
        cow_pairs: List[Tuple[int, int]] = []    # (src, dst) page copies
        pending: set = set()                     # hashes this round publishes
        for b in range(self.B):
            if self.slots[b] is not None or not self.queue:
                continue
            req = self.queue[0]
            shard = self._shard_of(b)
            L = len(req.tokens)
            need_worst = num_pages(L + req.gen_len, self.ps)
            cap = int(self.overcommit * self.per_shard)
            prompt_pages = num_pages(L, self.ps)
            shared, cow, C, hashes, defer = self._match_prefix(
                req, shard, pending)
            if defer:
                # its prefix is being prefilled RIGHT NOW by an earlier
                # request in this round — next round it is a cache hit
                self.stalled_admissions += 1
                break                            # FIFO: no out-of-order admit
            m = len(shared)
            # shared pages are refcount-held, not stolen-from, so only the
            # private remainder needs a worst-case reservation — dedup
            # shows up directly as admission capacity
            reserve = need_worst - m
            if self.reserved[shard] + reserve > cap:
                self.stalled_admissions += 1
                break
            # attach BEFORE alloc: a cached-but-free shared page must
            # leave the free list before the allocator could hand it out
            # as somebody's private page
            for p in shared:
                self.pool.attach(p)
            pages = self.pool.alloc(prompt_pages - m, shard)
            if pages is None:
                self.pool.free(shared)           # roll the attaches back
                self.stalled_admissions += 1
                break
            self.queue.popleft()
            self.reserved[shard] += reserve
            pages = shared + pages
            self.host_table[b, :prompt_pages] = pages
            self.host_table[b, prompt_pages:] = -1
            self._admit_seq += 1
            self.slots[b] = SeqRecord(
                request=req, pages=pages, shard=shard,
                need_worst=reserve, remaining=req.gen_len,
                admit_seq=self._admit_seq, n_shared=m, cached_tokens=C)
            if cow is not None:
                # duplicate the partially-shared page into this sequence's
                # first private page; the chunk overwrites the divergent
                # suffix slots before anything reads them
                cow_pairs.append((cow[0], pages[m]))
                self.cow_copies += 1
            if self.prefix_cache:
                if C > 0:
                    self.prefix_hits += 1
                else:
                    self.prefix_misses += 1
                pending.update(ch for _, ch in hashes[m:L // self.ps])
            plans[b] = (C, hashes, m)
            admitted.append((b, req))
        return admitted, plans, cow_pairs

    def _prefill_round(self, admitted, plans, cow_pairs) -> List[int]:
        """Prefill each admitted request alone, in a one-row view of the
        cache, publish its full pages and record its first token.
        Returns their ids.

        A row computes only its own uncached tail: rows mid-decode and
        the round's other rows cost nothing.  Ragged prefill pads the
        tail to a page multiple (one compile per width bucket, whatever
        the slot or the round's size) and masks the padding by
        ``lengths``; the per-slot path prefills the exact prompt."""
        import jax.numpy as jnp

        from repro.models.model import cache_slot_merge, cache_slot_view

        self.cache = _set_page_tables(self.cache, self.host_table)
        out: List[int] = []
        for b, r in admitted:
            C, hashes, m = plans[b]
            tail = r.tokens[C:]
            width = -(-len(tail) // self.ps) * self.ps if self.ragged \
                else len(tail)
            with spans.span("engine.prefill", slots=1, width=width,
                            tokens=len(tail)) as sp:
                if cow_pairs:
                    # every copy of the round, before its first prefill,
                    # in place; padded to B pairs, one compile
                    src = np.zeros(self.B, np.int32)
                    dst = np.full(self.B, self.pool.n_pages, np.int32)
                    src[:len(cow_pairs)], dst[:len(cow_pairs)] = \
                        zip(*cow_pairs)
                    self.cache = self._copy_pages(
                        self.cache, jnp.asarray(src), jnp.asarray(dst))
                    cow_pairs = []
                toks = np.zeros((1, width), tail.dtype)
                toks[0, :len(tail)] = tail
                batch = {"tokens": jnp.asarray(toks)}
                if self.cfg.is_encoder_decoder:
                    batch["src_embeds"] = self._src_embeds(r.req)
                per_row = ()
                if self.ragged:
                    per_row = (jnp.asarray([len(tail)], jnp.int32),)
                if self.prefix_cache:
                    # chunked path even at C == 0: one numeric family for
                    # every prefill, so evict-replay stays byte-identical
                    per_row += (jnp.asarray([C], jnp.int32),)
                view = cache_slot_view(self.cache, self.B, b)
                logits, view, *counts = self.prefill(self.params, batch,
                                                     view, *per_row)
                self.cache = cache_slot_merge(self.cache, view, self.B, b)
                tok = int(_to_host(jnp.argmax(logits[0, -1]), counts, sp))
            rec = self.slots[b]
            if self.prefix_cache:
                # the round's freshly prefilled full pages become cache
                # content (including a full CoW page — its bytes are now
                # exactly the chain's)
                for i in range(m, len(r.tokens) // self.ps):
                    parent, chain = hashes[i]
                    self.pool.publish(
                        rec.pages[i], parent, chain,
                        r.tokens[i * self.ps:(i + 1) * self.ps])
            self.prefill_tokens += len(r.tokens) - C
            self.cached_tokens += C
            rec.out_tokens.append(tok)
            rec.remaining -= 1
            self.toks[b, 0] = tok
            self.pos[b] = len(r.tokens)
            self.generated += 1
            self.journal.append({"ev": "admit", "req": r.req, "slot": b,
                                 "cached": C})
            out.append(r.req)
            if rec.remaining <= 0:
                self.finish(b)                   # gen_len == 1: prefill was it
        return out

    # -- eviction (preemption / requeue path) --------------------------------
    def evict(self, b: int) -> int:
        """Preempt slot ``b`` back to the FRONT of the queue: free its
        pages, release its reservation, discard its partial generation
        (re-admission re-prefills the prompt; greedy decode regenerates
        the identical response).  Crash recovery and preemption share this
        one path.  Returns the evicted request id."""
        rec = self.slots[b]
        assert rec is not None, f"evict of empty slot {b}"
        self.pool.free(rec.pages)
        self.reserved[rec.shard] -= rec.need_worst
        self.host_table[b, :] = -1
        self.cache = _set_page_tables(self.cache, self.host_table)
        self.slots[b] = None
        self.pos[b] = -1
        self.toks[b, 0] = 0
        self.queue.appendleft(rec.request)
        self._queued_at[rec.request.req] = time.perf_counter()
        self.evictions += 1
        self.journal.append({"ev": "evict", "req": rec.request.req,
                             "slot": b})
        return rec.request.req

    def _youngest_in_shard(self, shard: int) -> Optional[int]:
        best, best_seq = None, -1
        for b, rec in enumerate(self.slots):
            if rec is not None and rec.shard == shard \
                    and rec.admit_seq > best_seq:
                best, best_seq = b, rec.admit_seq
        return best

    def _ensure_pages(self) -> None:
        """Grow every active sequence's page list to cover its next decode
        write.  On exhaustion, evict the youngest sequence in the starving
        shard (possibly the needy one itself) until the allocation
        succeeds — the shard's oldest sequence is never evicted, so it
        always completes (no deadlock)."""
        dirty = False
        for b in range(self.B):
            rec = self.slots[b]
            if rec is None:
                continue
            needed = int(self.pos[b]) // self.ps + 1
            while rec is not None and len(rec.pages) < needed:
                got = self.pool.alloc(1, rec.shard)
                if got is not None:
                    self.host_table[b, len(rec.pages)] = got[0]
                    rec.pages.extend(got)
                    dirty = True
                    continue
                victim = self._youngest_in_shard(rec.shard)
                assert victim is not None, \
                    "page exhaustion with no active sequence to evict"
                self.evict(victim)
                dirty = False  # evict() already pushed the table
                if victim == b:
                    rec = None                   # the needy one was youngest
        if dirty:
            self.cache = _set_page_tables(self.cache, self.host_table)

    # -- decode ------------------------------------------------------------
    def step(self) -> List[int]:
        """One batched decode step over every active slot (inactive rows
        carry pos = -1 and are masked inside the kernel).  Returns the
        request ids finished by this step."""
        import jax.numpy as jnp

        if all(s is None for s in self.slots):
            return []
        with spans.span("engine.step"):
            with spans.span("engine.grow"):
                self._ensure_pages()
            if all(s is None for s in self.slots):
                return []                        # everything got evicted
            with spans.span("engine.decode") as sp:
                logits, self.cache, *counts = self.decode(
                    self.params, {"tokens": jnp.asarray(self.toks)},
                    self.cache, jnp.asarray(self.pos, jnp.int32))
                nxt = _to_host(jnp.argmax(logits[:, -1], axis=-1), counts,
                               sp)
            self.decode_steps += 1
            finished: List[int] = []
            for b in range(self.B):
                rec = self.slots[b]
                if rec is None:
                    continue
                tok = int(nxt[b])
                self.toks[b, 0] = tok
                self.pos[b] += 1
                rec.out_tokens.append(tok)
                self.generated += 1
                rec.remaining -= 1
                if rec.remaining <= 0:
                    finished.append(rec.request.req)
                    self.finish(b)
            return finished

    def finish(self, b: int) -> None:
        """Complete slot ``b``: free pages, release the reservation, log
        the response (exactly-once by request id — a deterministic
        re-execution after restore rewrites identical bytes)."""
        rec = self.slots[b]
        assert rec is not None, f"finish of empty slot {b}"
        with spans.span("engine.finish", rid=rec.request.req):
            self.pool.free(rec.pages)
            self.reserved[rec.shard] -= rec.need_worst
            self.host_table[b, :] = -1
            self.cache = _set_page_tables(self.cache, self.host_table)
            prev = self.responses.get(rec.request.req)
            assert prev is None or prev == rec.out_tokens, \
                (rec.request.req, prev, rec.out_tokens)
            self.responses[rec.request.req] = list(rec.out_tokens)
            self.journal.append({"ev": "finish", "req": rec.request.req,
                                 "tokens": list(rec.out_tokens)})
            self.slots[b] = None
            self.pos[b] = -1
            self.toks[b, 0] = 0

    # -- snapshot / restore --------------------------------------------------
    def snapshot(self) -> dict:
        """The complete engine state as plain host data.  ``restore`` of
        this structure on a fresh engine (same cfg/params) reproduces the
        device state exactly — continuation is byte-identical."""
        import jax

        with spans.span("engine.snapshot"):
            def rec_doc(rec: Optional[SeqRecord]):
                if rec is None:
                    return None
                return {"req": rec.request.req,
                        "tokens": np.asarray(rec.request.tokens).copy(),
                        "gen_len": rec.request.gen_len,
                        "pages": list(rec.pages), "shard": rec.shard,
                        "need_worst": rec.need_worst,
                        "remaining": rec.remaining,
                        "out_tokens": list(rec.out_tokens),
                        "admit_seq": rec.admit_seq,
                        "n_shared": rec.n_shared,
                        "cached_tokens": rec.cached_tokens}

            return {
                "queue": [(r.req, np.asarray(r.tokens).copy(), r.gen_len)
                          for r in self.queue],
                "slots": [rec_doc(s) for s in self.slots],
                "host_table": self.host_table.copy(),
                "free_lists": [list(f) for f in self.pool.free_lists],
                "high_water": self.pool.high_water,
                "refcount": list(self.pool.refcount),
                "page_meta": {int(p): {"parent": m["parent"],
                                       "hash": m["hash"],
                                       "tokens": list(m["tokens"])}
                              for p, m in self.pool.page_meta.items()},
                "prefix_index": [{par: dict(kids)
                                  for par, kids in idx.items()}
                                 for idx in self.pool.prefix_index],
                "reserved": list(self.reserved),
                "toks": self.toks.copy(),
                "pos": self.pos.copy(),
                "responses": {r: list(t)
                              for r, t in self.responses.items()},
                "journal": [dict(e) for e in self.journal],
                "stats": {"decode_steps": self.decode_steps,
                          "generated": self.generated,
                          "stalled_admissions": self.stalled_admissions,
                          "evictions": self.evictions,
                          "admit_seq": self._admit_seq,
                          "prefill_tokens": self.prefill_tokens,
                          "cached_tokens": self.cached_tokens,
                          "prefix_hits": self.prefix_hits,
                          "prefix_misses": self.prefix_misses,
                          "cow_copies": self.cow_copies},
                "journal_len": len(self.journal),
                "cache": jax.device_get(self.cache),
            }

    def restore(self, snap: dict) -> None:
        """Load a :meth:`snapshot` into this (freshly built) engine."""
        import jax
        import jax.numpy as jnp

        self.queue = deque(Request(req=r, tokens=np.asarray(t),
                                   gen_len=g)
                           for r, t, g in snap["queue"])
        self._queued_at = {}         # waits before the restore are unknown
        self.slots = []
        for doc in snap["slots"]:
            if doc is None:
                self.slots.append(None)
                continue
            self.slots.append(SeqRecord(
                request=Request(req=doc["req"],
                                tokens=np.asarray(doc["tokens"]),
                                gen_len=doc["gen_len"]),
                pages=list(doc["pages"]), shard=doc["shard"],
                need_worst=doc["need_worst"], remaining=doc["remaining"],
                out_tokens=list(doc["out_tokens"]),
                admit_seq=doc["admit_seq"],
                n_shared=doc.get("n_shared", 0),
                cached_tokens=doc.get("cached_tokens", 0)))
        self.host_table = np.asarray(snap["host_table"]).copy()
        self.pool.free_lists = [list(f) for f in snap["free_lists"]]
        self.pool.high_water = snap["high_water"]
        self.pool.refcount = list(snap["refcount"])
        self.pool.page_meta = {
            int(p): {"parent": m["parent"], "hash": m["hash"],
                     "tokens": [int(t) for t in m["tokens"]]}
            for p, m in snap["page_meta"].items()}
        self.pool.prefix_index = [
            {par: dict(kids) for par, kids in idx.items()}
            for idx in snap["prefix_index"]]
        self.reserved = list(snap["reserved"])
        self.toks = np.asarray(snap["toks"]).copy()
        self.pos = np.asarray(snap["pos"]).copy()
        self.responses = {r: list(t) for r, t in snap["responses"].items()}
        self.journal = [dict(e) for e in snap["journal"]]
        st = snap["stats"]
        self.decode_steps = st["decode_steps"]
        self.generated = st["generated"]
        self.stalled_admissions = st["stalled_admissions"]
        self.evictions = st["evictions"]
        self._admit_seq = st["admit_seq"]
        self.prefill_tokens = st.get("prefill_tokens", 0)
        self.cached_tokens = st.get("cached_tokens", 0)
        self.prefix_hits = st.get("prefix_hits", 0)
        self.prefix_misses = st.get("prefix_misses", 0)
        self.cow_copies = st.get("cow_copies", 0)
        self.cache = self._on_mesh(snap["cache"])

    def _on_mesh(self, cache):
        """Commit a fresh or restored cache to the serving mesh (without
        one, to the default device).  The
        jitted steps return their cache on the mesh; a first input that
        is not would give every step a second input type to compile."""
        import jax
        if self.ctx.mesh is None:
            return jax.device_put(cache, jax.devices()[0])
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(
            cache, NamedSharding(self.ctx.mesh, PartitionSpec()))

    # -- drive to completion --------------------------------------------------
    def run(self) -> None:
        """Drain the queue: alternate admission rounds and decode steps
        until nothing is queued or active (the old run_continuous loop)."""
        while not self.idle:
            self.admit()
            if all(s is None for s in self.slots):
                if not self.queue:
                    break                        # drained at prefill
                continue                         # re-admit (gen_len == 1 round)
            self.step()


# ---------------------------------------------------------------------------
# Workload synthesis (shared by the CLI and every platform replica)
# ---------------------------------------------------------------------------
def synthesize_requests(cfg, sv: ServeSpec, seed: int,
                        ragged: bool) -> List[Request]:
    """The deterministic request workload for a ServeSpec: every replica of
    a platform gang derives the identical list, so a claim index fully
    identifies a request (claim-then-serve exactly-once)."""
    import jax

    rng = np.random.default_rng(seed)
    n_req, P, G = sv.requests, sv.prompt_len, sv.gen
    prompts = np.array(jax.random.randint(
        jax.random.key(1), (n_req, P), 0, cfg.vocab_size))
    # shared-prefix workload (system prompt / few-shot template traffic):
    # every request opens with request 0's leading span
    C = int(round(P * getattr(sv, "shared_prefix_frac", 0.0)))
    if C > 0:
        prompts[:, :C] = prompts[0, :C]
    gen_lens = rng.integers(max(G // 2, 1), G + 1, size=n_req)
    # ragged workload: per-request prompt lengths in [P/2, P]; the lockstep
    # fallback serves every prompt at full length P.  Shared-prefix runs
    # keep full-length prompts so the share ratio is exact.
    prompt_lens = rng.integers(max(P // 2, 1), P + 1, size=n_req) \
        if ragged and C == 0 else np.full(n_req, P, np.int64)
    return [Request(req=r, tokens=prompts[r, :int(prompt_lens[r])].copy(),
                    gen_len=int(gen_lens[r])) for r in range(n_req)]


# ---------------------------------------------------------------------------
# Real payloads for platform workload pods (FrameworkAdapter.payload hook)
# ---------------------------------------------------------------------------
class RealServePayload:
    """Builds the real serving engine for one platform serve job.  Each pod
    incarnation calls :meth:`build` fresh — parameters are re-initialized
    from the job seed (pure function), so a restarted container holds the
    exact model the dead one did, and ``ServingEngine.restore`` + journal
    replay recover the serving state."""

    def __init__(self, spec: JobSpec):
        self.spec = spec

    def build(self):
        """Returns ``(engine, requests)`` for this job's ServeSpec."""
        import dataclasses

        import jax

        from repro.configs import get_config
        from repro.launch.executor import serve_ctx
        from repro.models.params import init_params

        spec, sv = self.spec, self.spec.serve
        cfg = get_config(spec.framework)
        if sv.reduced:
            cfg = cfg.reduced()
        overrides = {"cache_layout": sv.cache_layout or "paged"}
        if sv.page_size:
            overrides["page_size"] = sv.page_size
        cfg = dataclasses.replace(cfg, **overrides)
        ctx = serve_ctx(sv)
        params = init_params(cfg, jax.random.key(spec.seed))
        engine = ServingEngine(cfg, ctx, params, sv)
        requests = synthesize_requests(cfg, sv, spec.seed, engine.ragged)
        return engine, requests


class RealDryRunPayload:
    """Real compile cells for a platform dryrun job.  ``run_cell`` lowers
    and compiles the cell for real (``launch.dryrun.run_cell``); tests may
    inject a cheaper cell runner via ``platform.register_payload``."""

    def __init__(self, spec: JobSpec, run_cell=None):
        self.spec = spec
        self._run_cell = run_cell

    def run_cell(self, cell) -> dict:
        if self._run_cell is not None:
            return self._run_cell(cell)
        from repro.launch import dryrun
        return dryrun.run_cell(cell.arch, cell.shape, cell.multi_pod)
