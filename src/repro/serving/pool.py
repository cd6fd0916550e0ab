"""Paged KV pool for continuous batching, with cross-request prefix caching.

The device-side store is literally ``model.init_cache(num_blocks,
block_size)``: the cache's BATCH axis becomes the physical-block axis and
its capacity axis the within-block slot axis.  Every leaf therefore keeps
the ``pos``-derived mask semantics of serving/cache.py (``pos == -1`` marks
an empty/invalid slot), so full, QUOKA-selected and baseline-selected
attention over gathered views all share the one position-mask code path.

A request's logical cache is the concatenation of its blocks in
block-table order, materialised per step by ``gather`` (block-table indexed
``jnp.take`` with out-of-range fill: table id -1 reads as an empty block)
and written back by ``scatter`` (table id -1 / untouched blocks drop).
Host-side bookkeeping (free-list, per-request tables) lives on
``PagedKVCache``; the gather/scatter functions are pure and live inside the
engine's jitted step functions.

Prefix caching (multi-turn chats, shared system prompts):

  * FULL blocks of prompt KV are content-addressed by a rolling hash chain
    over their token ids (``h_i = hash(h_{i-1}, tokens_of_block_i)``, so a
    block's identity covers its whole prefix, not just its own tokens).
  * Blocks are REFCOUNTED: a cache-hit request pins a donor's prefix blocks
    into its own table read-only (the engine's scatter only ever writes
    blocks at/after the request's own prefill offset, so shared blocks are
    never written through a sharer's table).
  * When a block's refcount drops to zero it is not recycled immediately:
    registered (content-addressed) blocks move to an LRU list and stay
    resident — still matchable — until memory pressure evicts them into a
    fresh allocation.  Unregistered blocks are pos=-1-stamped and returned
    to the plain free list, so a recycled block can never leak a previous
    request's KV into a new allocation (stale ``pos`` values from a donor
    that sat at a *different* logical offset would otherwise look valid to
    the position masks).
  * Partially filled tail blocks (prompt_len % block_size != 0) are also
    registered, keyed by the hash of the full-block prefix they extend; a
    new request sharing the tail gets a COPY-ON-WRITE clone — the donor's
    block is copied into a privately owned block and the slots past the
    shared length are pos=-1-stamped — because the sharer must immediately
    write its own suffix into that block.

Hierarchical pool (``host_tier_blocks > 0``): a host-memory tier sits
behind the device pool, Double Sparsity-style.  Pressure-eviction DEMOTES
a registered block — content + registration move to a pinned host buffer
(``jax.device_put`` onto the device's ``pinned_host`` memory kind) instead
of being pos=-1-stamped away, so eviction becomes tiering rather than cache loss.
``match_prefix`` walks the hash chain across BOTH tiers; a host match is
PROMOTED at allocation time — an async H2D ``jax.device_put`` plus a
jitted block write into a fresh device block, re-registered on device so
the next sharer hits HBM directly.  ``stage`` lets the engine dispatch the
H2D copy for an upcoming promotion ahead of time (double buffering: the
copy for step N+1 overlaps step N's compute); staged buffers are consumed
by the promotion that needed them.  A hash is resident in exactly one
tier at a time (demotion moves it out, promotion/registration moves it
back), so matching never double-counts content.

Supported cache kinds: linear attention KV ("attn", "attn_moe", enc-free
GQA) and MLA latent caches.  Recurrent states (mamba/rwkv) do not
block-decompose over time, whisper cross-KV is encoder-owned, and
sliding-window ring buffers wrap at the window rather than the block — all
three are rejected at pool construction.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

_UNSUPPORTED_KINDS = ("mamba", "mamba_shared_attn", "rwkv", "dec_cross",
                      "attn_local")

# chain-hash seed for the empty prefix (any fixed int; tuples of ints hash
# deterministically, unaffected by PYTHONHASHSEED)
_HASH_SEED = 0x51554F4B


def blocks_for_request(prompt_len: int, max_new: int, chunk_size: int,
                       block_size: int, cached_len: int = 0) -> int:
    """Blocks reserved at admission (conservative: no mid-flight OOM).

    Prefill writes whole B_CP chunks (the ragged tail is right-padded with
    pos = -1 garbage that decode later overwrites), so the reservation
    covers max(chunk-padded prefill span, prompt + max_new) slots.  With a
    prefix-cache hit the prefill chunks start at ``cached_len``, so the
    chunk grid — and its padded span — shifts with the hit."""
    span = cached_len + -(-(prompt_len - cached_len) // chunk_size) * chunk_size
    span = max(span, prompt_len + max_new)
    return -(-span // block_size)


def blocks_for_resume(kv_len: int, prompt_len: int, max_new: int,
                      chunk_size: int, block_size: int,
                      cached_len: int) -> int:
    """Blocks reserved when re-admitting a SUSPENDED request: the replay
    chunks (if any KV was evicted between suspend and resume) span from
    ``cached_len`` to ``kv_len`` on the chunk grid, and the table must
    still cover the request's full prompt + max_new token span."""
    span = kv_len if cached_len >= kv_len else \
        cached_len + -(-(kv_len - cached_len) // chunk_size) * chunk_size
    span = max(span, prompt_len + max_new)
    return -(-span // block_size)


def max_blocks_bound(prompt_len: int, max_new: int, chunk_size: int,
                     block_size: int, align: int = 0,
                     preempt: bool = False) -> int:
    """Upper bound of ``blocks_for_request`` over every admissible
    ``cached_len`` (static jit geometry must cover the worst case).

    ``align`` is the prefix-hit granularity: when it is a multiple of the
    chunk size the chunk grid never shifts and the cold bound holds; token
    granularity (align=1, dense attention) can shift the last chunk to
    start at prompt_len - 1.

    ``preempt``: the policy may suspend/resume this request mid-decode, so
    the bound must also cover the worst ``blocks_for_resume`` — a resume
    with the KV grown to ``prompt_len + max_new - 1`` tokens and the least
    favourable surviving-cache offset."""
    worst = 0 if (align and align % chunk_size == 0) \
        else max(0, prompt_len - 1)
    bound = max(blocks_for_request(prompt_len, max_new, chunk_size,
                                   block_size),
                blocks_for_request(prompt_len, max_new, chunk_size,
                                   block_size, cached_len=worst))
    if preempt:
        kv = prompt_len + max(0, max_new - 1)
        worst_r = 0 if (align and align % chunk_size == 0) \
            else max(0, kv - 1)
        bound = max(bound,
                    blocks_for_resume(kv, prompt_len, max_new, chunk_size,
                                      block_size, 0),
                    blocks_for_resume(kv, prompt_len, max_new, chunk_size,
                                      block_size, worst_r))
    return bound


def _chain_hashes(tokens: np.ndarray, block_size: int) -> List[int]:
    """Rolling hash per FULL block: identity covers the whole prefix."""
    h, out = _HASH_SEED, []
    for i in range(len(tokens) // block_size):
        h = hash((h, tuple(map(int, tokens[i * block_size:
                                           (i + 1) * block_size]))))
        out.append(h)
    return out


class HostTier:
    """Slot-addressed host-memory store of demoted block slabs + LRU.

    Pure storage: registration metadata and the cross-tier hash indices
    stay on ``PagedKVCache`` (mirroring the device tier's ``_reg``/
    ``_full``/``_tail``) so ``check_invariants`` covers both tiers in one
    place.  The pool drives eviction: ``oldest()`` names the victim, the
    pool unregisters it, then ``drop`` releases the slot.

    Slabs live in ``device``'s ``pinned_host`` memory (H2D from pinned
    pages is what lets ``jax.device_put`` overlap compute); ``fetch``
    copies one back into the device's own memory."""

    def __init__(self, capacity: int, device):
        self.capacity = int(capacity)
        self._free: List[int] = list(range(self.capacity))
        self._slabs: Dict[int, object] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._host = SingleDeviceSharding(device, memory_kind="pinned_host")
        self._device = SingleDeviceSharding(device, memory_kind="device")

    def __len__(self) -> int:
        return len(self._slabs)

    @property
    def num_free(self) -> int:
        return len(self._free)

    def oldest(self) -> int:
        return next(iter(self._lru))

    def put(self, slab) -> int:
        """Store one block slab (caller ensured a free slot)."""
        slot = self._free.pop()
        self._slabs[slot] = jax.device_put(slab, self._host)
        self._lru[slot] = None                     # MRU end
        return slot

    def fetch(self, slot: int):
        """Async H2D copy of slot ``slot``'s slab into device memory."""
        return jax.device_put(self._slabs[slot], self._device)

    def touch(self, slot: int) -> None:
        self._lru.move_to_end(slot)

    def drop(self, slot: int) -> None:
        del self._slabs[slot]
        del self._lru[slot]
        self._free.append(slot)


class PagedKVCache:
    """Fixed-size-block KV pool + per-request block tables + free-list +
    content-addressed prefix cache (refcounts, LRU eviction, COW tails)."""

    def __init__(self, model, num_blocks: int, block_size: int, mesh=None,
                 host_tier_blocks: int = 0):
        kinds = [k for s in model.stacks for k in s.period]
        bad = sorted(set(k for k in kinds if k in _UNSUPPORTED_KINDS))
        if bad:
            raise ValueError(
                f"paged KV pool supports attention/MLA caches only; "
                f"model has unsupported block kinds {bad}")
        if model.cfg.family == "vlm":
            raise ValueError("paged KV pool does not support VLM frontends")
        if host_tier_blocks and mesh is not None:
            raise ValueError(
                "host tier + mesh is not supported yet: demotion would "
                "have to gather a sharded block slab per eviction")
        self.model = model
        self.mesh = mesh
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.data = model.init_cache(self.num_blocks, self.block_size)
        if mesh is not None:
            # blocks batch-shard over the FSDP axes (pool memory scales
            # with the data-parallel degree), heads over `model`; the
            # within-block slot axis is never split (sharding/specs.py
            # ``paged=True``) — a block is the atomic placement unit
            from repro.sharding import specs as sh
            self.data = jax.device_put(self.data, sh.to_shardings(
                mesh, sh.cache_specs(model.cfg, self.data, mesh,
                                     paged=True)))
        self._free: List[int] = list(range(self.num_blocks))
        self._tables: Dict[int, List[int]] = {}
        # ---- prefix cache state ----
        self._ref: Dict[int, int] = {}              # block -> live refcount
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # evictable
        self._reg: Dict[int, Tuple] = {}            # block -> registration
        self._full: Dict[int, int] = {}             # chain hash -> block
        self._tail: Dict[int, int] = {}             # prefix hash -> block
        # ---- host tier (hierarchical pool; see module docstring;
        # mesh-incompatibility guarded at the top of __init__) ----
        dev = next(iter(jax.tree.leaves(self.data)[0].devices()))
        self.host: Optional[HostTier] = (HostTier(host_tier_blocks, dev)
                                         if host_tier_blocks else None)
        self._h_reg: Dict[int, Tuple] = {}          # host slot -> registration
        self._h_full: Dict[int, int] = {}           # chain hash -> host slot
        self._h_tail: Dict[int, int] = {}           # prefix hash -> host slot
        self._staged: Dict[int, object] = {}        # host slot -> device slab
        # ---- counters (Engine.stats / ServeResult.prefix) ----
        self.evictions = 0
        self.cow_copies = 0
        self.lookups = 0
        self.hit_requests = 0
        self.hit_tokens = 0
        self.prompt_tokens = 0
        self.demoted = 0
        self.promoted = 0
        self.host_evictions = 0                     # host-tier cache LOSS
        self.staged_used = 0                        # promotions from staging
        # module-level jit singletons: the compiled-executable cache lives
        # on the WRAPPER, so per-instance jax.jit(...) here would recompile
        # for every pool (each warm/measure serve state builds its own)
        self._stamp_fn = _stamp_fn
        self._cow_fn = _cow_fn
        self._extract_fn = _extract_fn
        self._write_fn = _write_fn
        self._cow_slab_fn = _cow_slab_fn

    # ---- free-list bookkeeping ------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_evictable(self) -> int:
        return len(self._lru)

    @property
    def num_cached(self) -> int:
        """Registered (matchable) blocks, live or evictable."""
        return len(self._reg)

    @property
    def num_allocated(self) -> int:
        return self.num_blocks - len(self._free) - len(self._lru)

    def can_alloc(self, n: int, exclude: Sequence[int] = ()) -> bool:
        """Can ``n`` FRESH blocks be produced (free list + LRU eviction),
        without evicting any block in ``exclude``?"""
        lru = len(self._lru) - sum(1 for b in exclude if b in self._lru)
        return n <= len(self._free) + lru

    def alloc(self, rid: int, n: int) -> List[int]:
        return self.alloc_prefix(rid, n)

    def alloc_prefix(self, rid: int, n_total: int,
                     shared: Sequence = (),
                     cow: Optional[Tuple] = None) -> List[int]:
        """Build request ``rid``'s table: ``shared`` prefix entries in
        logical order followed by the remaining fresh blocks.  An entry is
        either a physical DEVICE block id (int, refcount-pinned read-only)
        or ``("host", slot)`` — a host-tier block, PROMOTED here: its slab
        is written into a fresh device block (the staged H2D buffer when
        the engine prefetched it, an async ``jax.device_put`` otherwise)
        and re-registered on device under its hash, so only device-shared
        entries come for free while promotions consume fresh blocks.

        ``cow = (src, keep)`` initialises the first post-prefix fresh block
        as a copy of ``src`` — a device block id or ``("host", slot)`` —
        with slots >= ``keep`` invalidated (shared partial tail).  A host
        COW source is COPIED, not consumed: the clone is private to the
        sharer, so the host copy stays matchable."""
        if rid in self._tables:
            raise RuntimeError(f"request {rid} already holds blocks")
        dev_shared = [e for e in shared if not isinstance(e, tuple)]
        promote = [e[1] for e in shared if isinstance(e, tuple)]
        cow_host = cow is not None and isinstance(cow[0], tuple)
        n_fresh = n_total - len(dev_shared)
        protect = dev_shared + ([cow[0]] if cow and not cow_host else [])
        if not self.can_alloc(n_fresh, exclude=protect):
            raise RuntimeError(
                f"pool exhausted: need {n_fresh} fresh blocks, "
                f"{len(self._free)} free + {len(self._lru)} evictable")
        # pin the shared prefix FIRST so fresh allocation cannot evict it
        for b in dev_shared:
            self._pin(b)
        # consume host sources BEFORE fresh allocation: taking fresh blocks
        # can itself demote device blocks into the host tier, and a host
        # eviction triggered by that must not race the slots this request
        # is about to promote
        promo = [self._take_host(s) for s in promote]   # [(slab, reg)]
        cow_slab = self._peek_host(cow[0][1]) if cow_host else None
        fresh, stale = [], []
        for _ in range(n_fresh):
            b, was_cached = self._take_fresh(protect)
            if was_cached:
                stale.append(b)
            fresh.append(b)
            self._ref[b] = 1
        self._stamp(stale)                 # evicted content is stale
        # build the table in logical order, promotions drawing fresh blocks
        it = iter(fresh)
        table, promo_dst = [], []
        for e in shared:
            if isinstance(e, tuple):
                promo_dst.append(next(it))
                table.append(promo_dst[-1])
            else:
                table.append(e)
        rest = list(it)
        for (slab, reg), dst in zip(promo, promo_dst):
            self.data = self._write_fn(self.data, slab,
                                       jnp.asarray(dst, jnp.int32))
            self._reg[dst] = reg           # re-registered on DEVICE
            index = self._full if reg[0] == "full" else self._tail
            index[reg[1]] = dst
            self.promoted += 1
        if cow is not None:
            src, keep = cow
            if cow_host:
                self.data = self._cow_slab_fn(
                    self.data, cow_slab, jnp.asarray(rest[0], jnp.int32),
                    jnp.asarray(keep, jnp.int32))
            else:
                if src not in self._ref and src not in self._lru:
                    raise RuntimeError(f"COW source block {src} not resident")
                self.data = self._cow_fn(
                    self.data, jnp.asarray(src, jnp.int32),
                    jnp.asarray(rest[0], jnp.int32),
                    jnp.asarray(keep, jnp.int32))
            self.cow_copies += 1
        self._tables[rid] = table + rest
        return self._tables[rid]

    def free(self, rid: int) -> List[int]:
        """Release a request's blocks.  Registered blocks stay resident on
        the LRU list (matchable until evicted); the rest are pos=-1-stamped
        so no stale KV can leak into a later allocation.  Returns the
        blocks that landed on the LRU list (the suspend path demotes
        exactly those)."""
        blocks = self._tables.pop(rid)   # KeyError on double free
        stale, retained = [], []
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                if b in self._reg:
                    self._lru[b] = None          # MRU end, content kept
                    retained.append(b)
                else:
                    stale.append(b)
                    self._free.append(b)
        self._stamp(stale)
        return retained

    def table(self, rid: int) -> List[int]:
        return self._tables[rid]

    def table_array(self, rids: Sequence[int], rows: int,
                    max_blocks: int) -> np.ndarray:
        """(rows, max_blocks) int32 block table, -1 padded (empty block).
        Rows beyond len(rids) are idle (all -1)."""
        tab = np.full((rows, max_blocks), -1, np.int32)
        for i, rid in enumerate(rids):
            blocks = self._tables[rid]
            tab[i, :len(blocks)] = blocks
        return tab

    # ---- prefix cache ----------------------------------------------------
    def match_prefix(self, tokens: np.ndarray,
                     chain: Optional[List[int]] = None
                     ) -> Tuple[List, Optional[Tuple]]:
        """Longest cached prefix of ``tokens``: (matched full blocks, tail).
        Each full-block entry is a device block id (int) or — with the host
        tier on — ``("host", slot)`` for a demoted block (device wins when
        a hash could be in either tier; demotion keeps them disjoint).
        ``tail = (src, n_common)`` if a registered partial tail (device id
        or host entry, same encoding) extends the matched full-block prefix
        by ``n_common`` shared tokens.  ``chain`` is the precomputed
        ``_chain_hashes`` of ``tokens`` — the scheduler caches it so a
        pool-blocked request re-matched every engine step doesn't re-hash
        its whole prompt each time."""
        toks = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        if chain is None:
            chain = _chain_hashes(toks, bs)
        h, fulls = _HASH_SEED, []
        for h2 in chain:
            b = self._full.get(h2)
            if b is None and self.host is not None:
                s = self._h_full.get(h2)
                if s is not None:
                    b = ("host", s)
                    self.host.touch(s)
            if b is None:
                break
            fulls.append(b)
            h = h2
        tail = None
        tb = self._tail.get(h)
        t_toks = self._reg[tb][2] if tb is not None else None
        if tb is None and self.host is not None:
            s = self._h_tail.get(h)
            if s is not None:
                tb = ("host", s)
                t_toks = self._h_reg[s][2]
                self.host.touch(s)
        if tb is not None:
            rem = toks[len(fulls) * bs:]
            m = 0
            while m < min(len(rem), len(t_toks)) and \
                    int(rem[m]) == t_toks[m]:
                m += 1
            if m > 0:
                tail = (tb, m)
        return fulls, tail

    def register_prefix(self, rid: int, tokens: np.ndarray,
                        chain: Optional[List[int]] = None) -> None:
        """Content-address request ``rid``'s prompt blocks (call once the
        prompt is fully prefilled: full blocks are final; the partial tail's
        prompt slots are final — later decode tokens land past them)."""
        toks = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        table = self._tables[rid]
        if chain is None:
            chain = _chain_hashes(toks, bs)
        h = _HASH_SEED
        for i, h2 in enumerate(chain):
            h = h2
            b = table[i]
            if b in self._reg or h in self._full:
                continue                 # shared / duplicate content
            self._reg[b] = ("full", h)
            self._full[h] = b
        rem = len(toks) % bs
        if rem:
            tb = table[len(toks) // bs]
            if tb not in self._reg and h not in self._tail:
                self._reg[tb] = ("tail", h,
                                 tuple(map(int, toks[len(toks) - rem:])))
                self._tail[h] = tb
        if self.host is not None:
            # single-residency: a degraded (cold) admit can re-prefill and
            # register content whose demoted copy still sits on the host
            # tier — drop the host copy so a hash matches in exactly one
            # tier (the device copy is the one future sharers should pin)
            for idx, hmap in ((self._full, self._h_full),
                              (self._tail, self._h_tail)):
                for hh in [hh for hh in hmap if hh in idx]:
                    self._h_unregister(hmap[hh])

    def register_suspend(self, rid: int, tokens: np.ndarray) -> None:
        """Content-address request ``rid``'s blocks over ``tokens`` — the
        prompt PLUS the generated tokens whose KV the cache holds — before
        suspension releases them.  Unlike ``register_prefix`` this must
        UPGRADE stale registrations: decode may have grown a registered
        partial tail (same block, more valid tokens) or filled it into a
        full block (tail registration replaced by a full-chain one)."""
        toks = np.asarray(tokens).reshape(-1)
        bs = self.block_size
        table = self._tables[rid]
        chain = _chain_hashes(toks, bs)
        h = _HASH_SEED
        for i, h2 in enumerate(chain):
            h = h2
            b = table[i]
            reg = self._reg.get(b)
            if reg is not None and reg[0] == "tail":
                # decode filled this once-partial tail into a full block
                self._unregister(b)
                reg = None
            if reg is not None or h in self._full:
                continue                 # shared / duplicate content
            self._reg[b] = ("full", h)
            self._full[h] = b
        rem = len(toks) % bs
        if rem:
            tb = table[len(toks) // bs]
            t_toks = tuple(map(int, toks[len(toks) - rem:]))
            cur = self._reg.get(tb)
            if cur is not None and cur[0] == "tail" and cur[1] == h \
                    and self._tail.get(h) == tb:
                if len(cur[2]) < rem:    # decode extended the tail
                    self._reg[tb] = ("tail", h, t_toks)
            elif cur is None and h not in self._tail:
                self._reg[tb] = ("tail", h, t_toks)
                self._tail[h] = tb
        if self.host is not None:
            for idx, hmap in ((self._full, self._h_full),
                              (self._tail, self._h_tail)):
                for hh in [hh for hh in hmap if hh in idx]:
                    self._h_unregister(hmap[hh])

    def suspend(self, rid: int, tokens: np.ndarray) -> Tuple[int, int]:
        """Preemption: release request ``rid``'s blocks with their KV kept
        matchable for resume.  ``tokens`` is the prompt plus the generated
        tokens the cache holds KV for (``Request.kv_len`` of them).  The
        blocks are content-registered (``register_suspend``) and freed;
        with the host tier on, the exclusively-owned ones are demoted
        IMMEDIATELY — suspension's whole point is to free device blocks
        now, not at the next pressure eviction — while blocks shared with
        live requests stay pinned on device.  Without a host tier they
        park on the LRU list (resume re-pins them; pressure in between is
        real cache loss and forces a replay).  Returns (blocks released
        from this request's table, blocks demoted to host)."""
        self.register_suspend(rid, tokens)
        n_total = len(self._tables[rid])
        retained = self.free(rid)
        demoted = 0
        if self.host is not None:
            stale = []
            for b in retained:
                if b in self._lru and b in self._reg:
                    del self._lru[b]
                    self._demote(b)
                    stale.append(b)
                    self._free.append(b)
                    demoted += 1
            self._stamp(stale)
        return n_total, demoted

    # ---- internals -------------------------------------------------------
    def _pin(self, b: int) -> None:
        """Refcount++ a resident block (pulling it off the LRU list)."""
        if b not in self._ref:
            if b not in self._lru:
                raise RuntimeError(f"block {b} not resident, cannot share")
            del self._lru[b]
            self._ref[b] = 1
        else:
            self._ref[b] += 1

    def _take_fresh(self, protect: Sequence[int]) -> Tuple[int, bool]:
        """One fresh block: free list first, then LRU eviction.  With the
        host tier on the evicted block DEMOTES (content + registration move
        to a host slab, still matchable); otherwise it just loses its cache
        entry.  Returns (block, needs stamping) — free-list blocks were
        stamped when freed."""
        if self._free:
            return self._free.pop(), False
        for b in self._lru:                        # oldest first
            if b not in protect:
                del self._lru[b]
                if self.host is not None:
                    self._demote(b)
                else:
                    self._unregister(b)
                self.evictions += 1
                return b, True
        raise RuntimeError("pool exhausted: no evictable block")

    def _unregister(self, b: int) -> None:
        reg = self._reg.pop(b)
        index = self._full if reg[0] == "full" else self._tail
        if index.get(reg[1]) == b:
            del index[reg[1]]

    # ---- host tier internals --------------------------------------------
    def _demote(self, b: int) -> None:
        """Move an evicted registered block into the host tier: slice its
        slab out of the pool (a jitted read dispatched BEFORE the caller
        stamps/recycles the block — dataflow keeps it ordered), place it on
        pinned host memory, and move the hash registration across tiers.
        The host tier's own eviction (oldest slot) is real cache loss."""
        reg = self._reg.pop(b)
        index = self._full if reg[0] == "full" else self._tail
        if index.get(reg[1]) != b:
            return                          # duplicate content; nothing owned
        del index[reg[1]]
        hmap = self._h_full if reg[0] == "full" else self._h_tail
        old = hmap.get(reg[1])
        if old is not None:                 # stale host copy of the same hash
            self._h_unregister(old)
        if self.host.num_free == 0:
            self._h_unregister(self.host.oldest())
            self.host_evictions += 1
        slab = self._extract_fn(self.data, jnp.asarray(b, jnp.int32))
        slot = self.host.put(slab)
        self._h_reg[slot] = reg
        hmap[reg[1]] = slot
        self.demoted += 1

    def _h_unregister(self, slot: int) -> None:
        reg = self._h_reg.pop(slot)
        index = self._h_full if reg[0] == "full" else self._h_tail
        if index.get(reg[1]) == slot:
            del index[reg[1]]
        self.host.drop(slot)
        self._staged.pop(slot, None)

    def _take_host(self, slot: int) -> Tuple[object, Tuple]:
        """Consume host slot ``slot`` for promotion: returns (device slab,
        registration).  A staged buffer (``stage``) is used when present —
        its H2D copy was dispatched while an earlier step computed; the
        fallback ``jax.device_put`` still dispatches asynchronously, and
        the jitted write that scatters the slab into ``self.data`` orders
        after it by dataflow."""
        reg = self._h_reg.pop(slot)
        index = self._h_full if reg[0] == "full" else self._h_tail
        if index.get(reg[1]) == slot:
            del index[reg[1]]
        slab = self._staged.pop(slot, None)
        if slab is not None:
            self.staged_used += 1
        else:
            slab = self.host.fetch(slot)
        self.host.drop(slot)
        return slab, reg

    def _peek_host(self, slot: int) -> object:
        """Device slab of host slot ``slot`` WITHOUT consuming it (COW tail
        sources: the sharer's clone is private, so the host copy stays
        matchable for the next sharer)."""
        self.host.touch(slot)
        slab = self._staged.get(slot)
        if slab is not None:
            self.staged_used += 1
            return slab
        return self.host.fetch(slot)

    def stage(self, slot: int) -> bool:
        """Dispatch the H2D copy for host slot ``slot`` ahead of its
        promotion (the engine's prefetch hook calls this while the step it
        just dispatched is still computing — double buffering).  Idempotent;
        returns True when a new copy was started."""
        if self.host is None or slot in self._staged:
            return False
        self._staged[slot] = self.host.fetch(slot)
        return True

    def _stamp(self, blocks: List[int]) -> None:
        """pos=-1-stamp ``blocks`` on device: recycled blocks must read as
        empty (a donor's stale positions would pass the validity masks).
        The id vector is padded to the next power of two (not the pool
        size) so per-free device work is O(freed blocks) while the jit
        cache stays bounded to log2(num_blocks) shape variants."""
        if not blocks:
            return
        n = 1
        while n < len(blocks):
            n *= 2
        ids = np.full((min(n, self.num_blocks),), self.num_blocks, np.int32)
        ids[:len(blocks)] = blocks                 # rest drop out of range
        self.data = self._stamp_fn(self.data, jnp.asarray(ids))

    def check_invariants(self) -> None:
        """No block leaked, double-allocated, double-freed, or in two of
        {allocated, free, LRU}; refcounts match table membership; the hash
        indices and registrations agree."""
        refs: Dict[int, int] = {}
        for t in self._tables.values():
            assert len(set(t)) == len(t), "block twice in one table"
            for b in t:
                refs[b] = refs.get(b, 0) + 1
        assert refs == self._ref, "refcounts out of sync with tables"
        held = set(refs)
        free, lru = set(self._free), set(self._lru)
        assert len(self._free) == len(free), "block double-freed"
        assert not (held & free), "allocated block on the free list"
        assert not (held & lru), "allocated block on the LRU list"
        assert not (free & lru), "block both free and evictable"
        assert sorted(held | free | lru) == list(range(self.num_blocks)), \
            "block leaked or invented"
        for h, b in self._full.items():
            assert self._reg.get(b, (None, None))[:2] == ("full", h)
        for h, b in self._tail.items():
            r = self._reg.get(b)
            assert r is not None and r[0] == "tail" and r[1] == h
        for b in self._reg:
            assert b in held or b in lru, "registered block recycled"
        if self.host is not None:
            slots = set(self._h_reg)
            assert slots == set(self.host._slabs) == set(self.host._lru), \
                "host registrations out of sync with stored slabs"
            assert len(slots) + self.host.num_free == self.host.capacity, \
                "host slot leaked or invented"
            assert not (slots & set(self.host._free)), \
                "host slot both stored and free"
            for h, s in self._h_full.items():
                assert self._h_reg.get(s, (None, None))[:2] == ("full", h)
            for h, s in self._h_tail.items():
                r = self._h_reg.get(s)
                assert r is not None and r[0] == "tail" and r[1] == h
            assert not (set(self._h_full) & set(self._full)), \
                "full-block hash resident in both tiers"
            assert not (set(self._h_tail) & set(self._tail)), \
                "tail hash resident in both tiers"
            assert set(self._staged) <= slots, "staged buffer for freed slot"


# ---------------------------------------------------------------------------
# pure device helpers (jitted once per pool, donated data)
# ---------------------------------------------------------------------------

def _stamp_blocks(data, ids):
    """Set pos = -1 across blocks ``ids`` (padded with out-of-range ids,
    which drop).  Only integer leaves carry positions; KV payloads are left
    in place — the position masks make them unreadable."""
    def s(leaf):
        if leaf.ndim < 3 or not jnp.issubdtype(leaf.dtype, jnp.integer):
            return leaf
        upd = jnp.full((leaf.shape[0], ids.shape[0]) + leaf.shape[2:],
                       -1, leaf.dtype)
        return leaf.at[:, ids].set(upd, mode="drop")

    return jax.tree.map(s, data)


def _cow_block(data, src, dst, keep):
    """Copy block ``src`` into ``dst`` (copy-on-write of a shared partial
    tail), invalidating slots >= ``keep``: those hold the donor's private
    suffix/decode KV, which the sharer must not see."""
    def c(leaf):
        if leaf.ndim < 3:
            return leaf
        row = jnp.take(leaf, src, axis=1)          # (R, block_size, ...)
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            slot = jnp.arange(leaf.shape[2], dtype=jnp.int32)
            valid = (slot < keep).reshape((1, -1) + (1,) * (row.ndim - 2))
            row = jnp.where(valid, row, -1)
        return leaf.at[:, dst].set(row)

    return jax.tree.map(c, data)


def _extract_block(data, b):
    """Slice block ``b`` out of the pool as a standalone slab pytree
    (each KV leaf (R, block_size, ...)) — the D2H half of demotion."""
    def e(leaf):
        if leaf.ndim < 3:
            return leaf
        return jnp.take(leaf, b, axis=1)

    return jax.tree.map(e, data)


def _write_block(data, slab, dst):
    """Write an extracted slab into block ``dst`` — the H2D half of
    promotion.  The slab's buffers arrive via ``jax.device_put`` (possibly
    pre-staged); dataflow orders this write after that copy completes."""
    def w(leaf, s):
        if leaf.ndim < 3:
            return leaf
        return leaf.at[:, dst].set(s.astype(leaf.dtype))

    return jax.tree.map(w, data, slab)


def _cow_from_slab(data, slab, dst, keep):
    """``_cow_block`` with a host-tier source: copy an extracted slab into
    ``dst``, invalidating slots >= ``keep`` (shared partial tail)."""
    def c(leaf, s):
        if leaf.ndim < 3:
            return leaf
        row = s.astype(leaf.dtype)
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            slot = jnp.arange(leaf.shape[2], dtype=jnp.int32)
            valid = (slot < keep).reshape((1, -1) + (1,) * (row.ndim - 2))
            row = jnp.where(valid, row, -1)
        return leaf.at[:, dst].set(row)

    return jax.tree.map(c, data, slab)


# shared jit singletons (see PagedKVCache.__init__): compiled executables
# are cached per wrapper, so one wrapper per process amortises compilation
# across every pool instance of the same geometry
_stamp_fn = jax.jit(_stamp_blocks, donate_argnums=0)
_cow_fn = jax.jit(_cow_block, donate_argnums=0)
_extract_fn = jax.jit(_extract_block)
_write_fn = jax.jit(_write_block, donate_argnums=0)
_cow_slab_fn = jax.jit(_cow_from_slab, donate_argnums=0)


# ---------------------------------------------------------------------------
# pure gather/scatter (used inside the engine's jitted step functions)
# ---------------------------------------------------------------------------

def gather(data, table, num_blocks: int, block_size: int):
    """Materialise per-request linear caches from the pool.

    table: (b, max_nb) int32 physical block ids, -1 = empty.  Returns a
    cache pytree whose KV leaves are (R, b, max_nb * block_size, ...) — a
    standard linear cache view; empty blocks read as pos = -1 / zeros, so
    the position-mask machinery needs no special case."""
    b, nb = table.shape
    idx = jnp.where(table < 0, num_blocks, table).reshape(-1)

    def g(leaf):
        if leaf.ndim < 3:
            return leaf                          # enc_done & friends
        fill = -1 if jnp.issubdtype(leaf.dtype, jnp.integer) else 0
        out = jnp.take(leaf, idx, axis=1, mode="fill", fill_value=fill)
        return out.reshape(leaf.shape[0], b, nb * block_size,
                           *leaf.shape[3:])

    with jax.named_scope("pool_gather"):
        return jax.tree.map(g, data)


def gather_blocks(data, table, block_ids, num_blocks: int, block_size: int):
    """Materialise only SELECTED blocks of each request: a sub-view of
    ``gather`` driven by per-request logical block indices (b, nb_sel)
    int32, -1 = padding.

    This is the paged backing of core/plan.py's block-granular
    materialize: a plan built on the pool grid (granularity divides
    block_size) names whole logical blocks, so re-indexing the block
    TABLE — not the tokens — keeps the physical gather whole-block
    contiguous (one dynamic slice of ``block_size`` rows per selected
    block, never a per-token gather).  Padding ids read as pos = -1 /
    zeros, same as ``gather``."""
    sub = jnp.take_along_axis(table, jnp.maximum(block_ids, 0), axis=1)
    sub = jnp.where(block_ids >= 0, sub, -1)
    return gather(data, sub, num_blocks, block_size)


def scatter(data, gathered, table, touched, num_blocks: int,
            block_size: int):
    """Write gathered views back into the pool.

    ``touched`` (b, max_nb) bool limits the write to blocks the step
    actually modified; untouched and null (-1) table entries are mapped out
    of range and dropped.  Prefix-shared blocks are safe behind this mask:
    a sharer's writes start at its own prefill offset, so its touched
    window never covers the shared prefix."""
    b, nb = table.shape
    idx = jnp.where((table >= 0) & touched, table, num_blocks).reshape(-1)

    def s(pool_leaf, gath_leaf):
        if pool_leaf.ndim < 3:
            return pool_leaf
        blocks = gath_leaf.reshape(gath_leaf.shape[0], b * nb, block_size,
                                   *gath_leaf.shape[3:])
        return pool_leaf.at[:, idx].set(blocks.astype(pool_leaf.dtype),
                                        mode="drop")

    with jax.named_scope("pool_scatter"):
        return jax.tree.map(s, data, gathered)


def touched_blocks(slot, n_tokens, max_nb: int, block_size: int):
    """(b, max_nb) bool: logical blocks covered by a write of ``n_tokens``
    rows starting at ``slot`` (both (b,) int32; n_tokens == 0 -> none)."""
    slot = jnp.asarray(slot, jnp.int32)
    n = jnp.asarray(n_tokens, jnp.int32)
    lo = slot // block_size
    hi = (slot + jnp.maximum(n, 1) - 1) // block_size
    ar = jnp.arange(max_nb, dtype=jnp.int32)[None]
    return (ar >= lo[:, None]) & (ar <= hi[:, None]) & (n > 0)[:, None]
