"""Pooled KV page cache + slot-major state cache for the serving engine.

Device layout is a true block-table design: attention KV lives in ONE
shared page pool ``[U, num_pages, page_size, Hkv*dh]`` whose page dim
is sharded over all mesh axes (dp x tp), and each request slot maps an
ordered list of pages through a per-slot block-table row
``[pages_per_slot]`` of global page ids (-1 = unmapped).  Decode/verify
attention gathers K/V through that table (``cache[page, offset]``), so
a slot's HBM footprint is ``ceil(len / page_size)`` pages — NOT a dense
``max_seq`` reservation — and ``num_pages`` caps concurrent context,
independent of the slot count.  Recurrent/SSM state (mamba / xLSTM /
RWKV) stays slot-major ``[U, slots, ...]``: it is O(1) per slot and
every block reads all of it every step, so paging buys it nothing.
Buffers are allocated once at engine start and donated through every
step — steady-state serving is still allocation-free.

The host side is a ``SlotAllocator``: a free-list of request slots plus
a REAL page allocator — global free list (partitioned into one region
per dp group, because slots are batch-sharded over dp and a slot's
pages must live on its own dp group's tp shards), per-slot page lists,
alloc-on-extend (``ensure``), and page-exact ``rollback``/``free`` that
return the tail's pages to the pool.  Exhaustion is typed:
``SlotsExhausted`` vs ``PagePoolExhausted`` (see ``serving.errors``).
Reclamation under pressure is the engine's job, built on this
allocator's primitives: pool-pressure preemption (``free`` the victim,
re-admit later) and replica-loss/suspend paths all return pages through
the same ``free``/limbo machinery, so a fault can never leak a page.

Deferred-free epochs (async serving): when the engine pipelines decode
steps (``EngineConfig.async_depth > 0``) it dispatches step t+1 before
it has synced step t's tokens, so a block-table snapshot for an
in-flight step may still name pages the host has since decided to free
(late EOS retirement, speculative rollback).  ``note_dispatch()`` /
``note_commit()`` bracket every device step; while any dispatched step
is uncommitted, freed pages park on a limbo list tagged with the
newest dispatch epoch and only rejoin the free pool once every step
whose snapshot could name them has committed.  A limbo page can never
be remapped to a new slot, so an in-flight step's reads and writes
always land in pages still owned by the slot its snapshot mapped them
to.  With no step in flight (the synchronous engine), frees are
immediate and behavior is byte-identical to the pre-async allocator.

``insert`` splices a freshly prefilled single-request cache into the
pool: state leaves are a slot-row write; KV leaves all_gather the one
request's seq-sharded prefill KV over tp (the natural admit cost) and
scatter it page-block-wise into the slot's freshly mapped pages —
out-of-shard / unmapped targets drop, so only ``ceil(prompt_len /
page_size)`` pages are ever touched.

Safety invariant (why stale pool rows can never leak between slots): a
slot's visible positions ``[0, len)`` are always positions the slot
itself wrote — prefill fills its pages at admit, decode/verify writes
run contiguously upward from there, and pages are only mapped/unmapped
at the tail — while every read masks entries beyond the slot's own
positions, so a recycled page's previous contents are overwritten
before they could ever score.
"""
from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..kernels.paged_decode import (blocks_walked, pages_fetched,
                                    pages_per_block)
from ..launch.specs import (CellPlan, cache_specs, default_num_pages,
                            paged_cache_specs, pages_per_slot)
from ..models.context import axes_linear_index, pool_local_pages
from .errors import CacheOverflowError, PagePoolExhausted, SlotsExhausted

_KV_KEYS = ("kv", "cross_kv")


class SlotAllocator:
    """Free-list slot allocation + a real shared-pool page allocator.

    ``num_pages`` defaults to ``num_slots * pages_per_slot`` (the dense
    reservation — can never exhaust before the slots do); sizing it
    smaller is the paging payoff: slots share the pool and long-context
    slots no longer reserve ``max_seq`` up front.  ``num_groups`` > 1
    partitions the pool into equal contiguous regions and pins each
    slot to the region of its dp group (``slot // slots_per_group``),
    matching the device-side page sharding over dp x tp.

    Compacted per-shard page lists: with ``shards_per_group`` > 1 each
    group's region further splits into one contiguous range per tp
    shard (``pages_local`` pages each — the device-side pool slice),
    and alongside the block table the allocator maintains
    ``page_list_loc`` / ``page_list_pos``: ``[num_slots,
    shards_per_group, pages_per_shard]`` int32 arrays naming, for each
    (slot, shard), the shard-LOCAL pool rows of the slot's resident
    pages and the absolute position of each page's first token
    (ordinal * page_size); -1 = no page.  The fused paged-decode
    kernel walks these lists instead of the full block table, so every
    page a slot maps must land within ``pages_per_shard =
    ceil(pages_per_slot / shards_per_group)`` rows on its shard —
    ``_map_pages`` balances placement to keep that invariant (fewest
    of the slot's pages first).  The cost of the static per-shard
    width is a mild admission tightening: free pages clustered on one
    shard beyond ``pages_per_shard`` are unusable by a single slot, so
    capacity checks count ``min(free_on_shard, headroom_on_shard)``
    per shard rather than the group total.  An overflowing page would
    be invisible to the fused kernel (silently unattended positions),
    so the invariant is enforced at allocation, never best-effort.
    ``shards_per_group=1`` (the default) keeps one list per group and
    is behavior-identical to the pre-compaction allocator.
    """

    def __init__(self, num_slots: int, max_seq: int, page_size: int = 64,
                 num_pages: int | None = None, num_groups: int = 1,
                 shards_per_group: int = 1):
        if num_slots <= 0 or page_size <= 0 or max_seq <= 0:
            raise ValueError((num_slots, max_seq, page_size))
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot(max_seq, page_size)
        if num_pages is None:
            num_pages = num_slots * self.pages_per_slot
        if num_pages <= 0 or num_pages % num_groups != 0 \
                or num_slots % num_groups != 0:
            raise ValueError(
                f"num_pages={num_pages} / num_slots={num_slots} must be "
                f"positive multiples of num_groups={num_groups}")
        self.num_pages = num_pages
        self.num_groups = num_groups
        self.pages_per_group = num_pages // num_groups
        if shards_per_group <= 0 \
                or self.pages_per_group % shards_per_group != 0:
            raise ValueError(
                f"pages_per_group={self.pages_per_group} must be a "
                f"positive multiple of shards_per_group={shards_per_group}")
        self.shards_per_group = shards_per_group
        #: pages of one (group, shard) range — the device pool slice size
        self.pages_local = self.pages_per_group // shards_per_group
        #: static width of one (slot, shard) compacted page list
        self.pages_per_shard = -(-self.pages_per_slot // shards_per_group)
        self._slots_per_group = num_slots // num_groups
        self._free = deque(range(num_slots))
        self._free_pages = [
            [deque(range(g * self.pages_per_group + s * self.pages_local,
                         g * self.pages_per_group
                         + (s + 1) * self.pages_local))
             for s in range(shards_per_group)]
            for g in range(num_groups)]
        self._len = np.zeros(num_slots, np.int64)   # current seq occupancy
        self._pages: list[list[int]] = [[] for _ in range(num_slots)]
        #: pages each slot holds on each shard (compacted-list fill level)
        self._shard_count = np.zeros((num_slots, shards_per_group),
                                     np.int32)
        # deferred-free epoch state: device steps launched vs joined, and
        # pages freed while a snapshot may still name them —
        # (release_epoch, page) pairs, nondecreasing in epoch
        self._dispatched = 0
        self._committed = 0
        self._limbo: deque[tuple[int, int]] = deque()
        #: [num_slots, pages_per_slot] int32 global page ids, -1 unmapped —
        #: passed verbatim as the device block table every step
        self.block_table = np.full((num_slots, self.pages_per_slot), -1,
                                   np.int32)
        #: [num_slots, shards_per_group, pages_per_shard] int32 — the
        #: compacted per-shard page lists the fused decode kernel walks:
        #: shard-local pool row of each resident page (-1 = none), and
        #: the absolute position of the page's first token.  Staged to
        #: device per dispatch exactly like the block table.
        self.page_list_loc = np.full(
            (num_slots, shards_per_group, self.pages_per_shard), -1,
            np.int32)
        self.page_list_pos = np.full(
            (num_slots, shards_per_group, self.pages_per_shard), -1,
            np.int32)

    # -- sizing / introspection -------------------------------------------

    def group_of(self, slot: int) -> int:
        return slot // self._slots_per_group

    def _shard_of(self, page: int) -> int:
        """tp-shard index (within its group) holding global ``page``."""
        return (page // self.pages_local) % self.shards_per_group

    @property
    def num_free(self) -> int:
        return len(self._free)

    def free_pages_in_group(self, group: int) -> int:
        return sum(len(d) for d in self._free_pages[group])

    def _limbo_by_shard(self, group: int) -> list:
        """Limbo page count per tp shard of ``group`` — what each shard's
        free deque gets back once the pipeline drains."""
        counts = [0] * self.shards_per_group
        lo = group * self.pages_per_group
        hi = lo + self.pages_per_group
        for _, p in self._limbo:
            if lo <= p < hi:
                counts[self._shard_of(p)] += 1
        return counts

    def _fresh_capacity(self, group: int) -> int:
        """Pages a FRESH slot of ``group`` could map right now: per-shard
        free pages, capped at the compacted-list width per shard."""
        return sum(min(len(d), self.pages_per_shard)
                   for d in self._free_pages[group])

    def _admit_capacity(self, group: int, after_flush: bool = False) -> int:
        """Pages ADMISSION may count on for a fresh slot of ``group``.

        Unlike ``_fresh_capacity`` (the mechanism ``alloc`` enforces),
        this is admission POLICY and it is limbo-aware: pages parked in
        deferred-free limbo are claims the pool already owes to slots
        that will grow — admitting against them lets a request in whose
        first alloc-on-extend then starves the group mid-flight and
        triggers needless preemption churn.  Limbo pages count AGAINST
        the free list here, so a dry-pool-plus-limbo group reports 0.
        With ``after_flush=True`` the same capacity is computed as if
        the pipeline had drained (limbo pages rejoined their shards'
        free deques) — the engine uses it to decide whether a
        flush-then-retry would unblock the queue head.
        """
        limbo = self._limbo_by_shard(group)
        if after_flush:
            return sum(min(len(d) + limbo[s], self.pages_per_shard)
                       for s, d in enumerate(self._free_pages[group]))
        return max(0, self._fresh_capacity(group) - sum(limbo))

    def _slot_capacity(self, slot: int) -> int:
        """Additional pages ``slot`` could map right now (per-shard free
        pages capped at the slot's remaining compacted-list headroom)."""
        free = self._free_pages[self.group_of(slot)]
        cnt = self._shard_count[slot]
        return sum(min(len(free[s]), self.pages_per_shard - int(cnt[s]))
                   for s in range(self.shards_per_group))

    def pages_needed(self, seq_len: int) -> int:
        return -(-seq_len // self.page_size)

    def pages_used(self, slot: int) -> int:
        return len(self._pages[slot])

    @property
    def total_pages(self) -> int:
        return self.num_pages

    @property
    def pages_in_use(self) -> int:
        return sum(len(p) for p in self._pages)

    @property
    def pages_in_limbo(self) -> int:
        """Pages freed but not yet safe to remap (an uncommitted device
        step's block-table snapshot may still name them)."""
        return len(self._limbo)

    @property
    def pressure(self) -> float:
        """Fraction of the pool unavailable for new mappings (mapped or
        parked in limbo).  1.0 means the next alloc-on-extend in a dry
        group triggers the engine's pool-pressure preemption path (or
        a typed ``PagePoolExhausted`` with ``preempt=False``) — the
        per-step signal ``repro.serving.slo.SLOMonitor`` trends."""
        return (self.pages_in_use + self.pages_in_limbo) / self.num_pages

    # -- deferred-free epochs (async dispatch/commit) ----------------------

    def note_dispatch(self):
        """A device step was launched against the CURRENT block table.

        Until the matching ``note_commit``, any page freed (evict,
        rollback) parks on the limbo list instead of the free pool: the
        in-flight step's snapshot may still read or write it, and
        handing it to a new slot would let two owners race on one page.
        """
        self._dispatched += 1

    def note_commit(self):
        """The OLDEST in-flight device step joined the host (its output
        was synced, so its reads/writes have fully executed).  Limbo
        pages whose every possible holder has now committed rejoin their
        group's free pool."""
        if self._committed >= self._dispatched:
            raise ValueError("note_commit without a matching "
                             "note_dispatch: no device step is in flight")
        self._committed += 1
        while self._limbo and self._limbo[0][0] <= self._committed:
            _, page = self._limbo.popleft()
            g = page // self.pages_per_group
            self._free_pages[g][self._shard_of(page)].append(page)

    def _release_page(self, page: int):
        if self._dispatched > self._committed:
            # unsafe until every step dispatched so far has committed:
            # tag with the newest epoch that could hold a snapshot
            self._limbo.append((self._dispatched, page))
        else:
            g = page // self.pages_per_group
            self._free_pages[g][self._shard_of(page)].append(page)

    # -- page mapping (internal) ------------------------------------------

    def _map_pages(self, slot: int, n: int):
        g = self.group_of(slot)
        if n > self._slot_capacity(slot):
            free = self.free_pages_in_group(g)
            raise PagePoolExhausted(
                f"slot {slot} (group {g}) needs {n} page(s); capacity "
                f"{self._slot_capacity(slot)} ({free} free of "
                f"{self.pages_per_group} in its group, per-shard "
                f"compacted-list width {self.pages_per_shard}; "
                f"{self.pages_in_use}/{self.num_pages} mapped pool-wide)")
        free = self._free_pages[g]
        cnt = self._shard_count[slot]
        for _ in range(n):
            # balanced placement: the shard where this slot holds the
            # fewest pages (so no shard's compacted list overflows its
            # static width), tie-broken toward the shard with the most
            # free pages (global balance), then lowest index (determinism)
            s = min((s for s in range(self.shards_per_group)
                     if free[s] and cnt[s] < self.pages_per_shard),
                    key=lambda s: (int(cnt[s]), -len(free[s]), s))
            page = free[s].popleft()
            ordinal = len(self._pages[slot])
            self.block_table[slot, ordinal] = page
            self.page_list_loc[slot, s, cnt[s]] = page % self.pages_local
            self.page_list_pos[slot, s, cnt[s]] = ordinal * self.page_size
            cnt[s] += 1
            self._pages[slot].append(page)

    def _unmap_tail(self, slot: int, keep: int):
        cnt = self._shard_count[slot]
        while len(self._pages[slot]) > keep:
            page = self._pages[slot].pop()
            self.block_table[slot, len(self._pages[slot])] = -1
            # the popped page has the slot's highest ordinal, and each
            # per-shard list is ordinal-ordered, so it is the LAST live
            # entry of its own shard's compacted list
            s = self._shard_of(page)
            cnt[s] -= 1
            self.page_list_loc[slot, s, cnt[s]] = -1
            self.page_list_pos[slot, s, cnt[s]] = -1
            self._release_page(page)

    # -- slot lifecycle ----------------------------------------------------

    def can_admit(self, seq_len: int, after_flush: bool = False) -> bool:
        """True iff some free slot's group can map ``seq_len`` tokens.

        Limbo-aware (see ``_admit_capacity``): pages parked in
        deferred-free limbo never count toward admission, so a dry pool
        with parked pages rejects instead of admitting a request that
        would starve mid-flight.  ``after_flush=True`` answers the
        counterfactual "would this admit pass once the pipeline drains
        and limbo pages rejoin the pool?" — the engine's
        flush-then-retry gate.
        """
        if not 0 < seq_len <= self.max_seq:
            return False
        need = self.pages_needed(seq_len)
        return any(need <= self._admit_capacity(self.group_of(s),
                                                after_flush=after_flush)
                   for s in self._free)

    def alloc(self, seq_len: int) -> int:
        """Claim a slot + map pages for ``seq_len`` already-held tokens.

        Picks the first free slot (FIFO) whose group has enough free
        pages.  Typed failures: ``SlotsExhausted`` when no slot is
        free, ``PagePoolExhausted`` when slots are free but no group can
        map the request — the caller queues in either case.  Deliberately
        limbo-PERMISSIVE (mechanism, not policy): free-list pages are
        usable the instant they are free — admission policy
        (``can_admit``) is where limbo pressure gates new work.
        """
        if not 0 < seq_len <= self.max_seq:
            raise ValueError(f"seq_len {seq_len} not in (0, {self.max_seq}]")
        if not self._free:
            raise SlotsExhausted(f"all {self.num_slots} slots in use")
        need = self.pages_needed(seq_len)
        for slot in self._free:
            if need <= self._fresh_capacity(self.group_of(slot)):
                break
        else:
            raise PagePoolExhausted(
                f"{need} page(s) for seq_len {seq_len}: no free slot's "
                f"group has them ({self.pages_in_use}/{self.num_pages} "
                "mapped)")
        self._free.remove(slot)
        self._map_pages(slot, need)
        self._len[slot] = seq_len
        return slot

    def ensure(self, slot: int, new_len: int):
        """Alloc-on-extend: grow ``slot``'s mapping to cover ``new_len``
        positions (no-op if already covered).  The engine calls this
        BEFORE launching a decode/verify step so every position the step
        writes has a mapped page.  Raises ``CacheOverflowError`` past
        ``max_seq`` (the old silent clamp hid scheduler bugs) and
        ``PagePoolExhausted`` when the slot's group has no page left.
        """
        if self._len[slot] <= 0:
            raise ValueError(f"ensure on free slot {slot}")
        if new_len > self.max_seq:
            raise CacheOverflowError(
                f"slot {slot}: {new_len} positions > max_seq "
                f"{self.max_seq}")
        self._map_pages(slot,
                        self.pages_needed(new_len) - self.pages_used(slot))
        self._len[slot] = max(self._len[slot], new_len)

    def extend(self, slot: int, n: int = 1):
        self.ensure(slot, int(self._len[slot]) + n)

    def rollback(self, slot: int, new_len: int):
        """Roll a slot's occupancy back to ``new_len`` positions,
        returning the rejected tail's pages to the pool (page-exact).

        Speculative decoding maps+writes KV for every draft position
        before acceptance is known; the scheduler calls this to shrink
        to the committed length.  Only shrinking (or no-op) is legal —
        growth goes through ``ensure``/``extend``.
        """
        if not 0 < new_len <= self._len[slot]:
            raise ValueError(
                f"rollback slot {slot} to {new_len}: occupancy is "
                f"{int(self._len[slot])} (must shrink to a positive length)")
        self._unmap_tail(slot, self.pages_needed(new_len))
        self._len[slot] = new_len

    def free(self, slot: int):
        if self._len[slot] <= 0:
            # typed (not assert): a double free surviving `python -O`
            # would put the slot on the free list twice and hand it to
            # two requests at once
            raise ValueError(f"slot {slot} already free")
        self._unmap_tail(slot, 0)
        self._len[slot] = 0
        self._free.append(slot)


def _is_kv_path(path) -> bool:
    return any(getattr(p, "key", None) in _KV_KEYS for p in path)


def _init_leaf(path, s):
    # rwkv's log-space max-tracker must start at -inf, everything else 0
    if any(getattr(p, "key", None) == "pp" for p in path):
        return jnp.full(s.shape, -1e30, s.dtype)
    return jnp.zeros(s.shape, s.dtype)


def make_init_fn(plan: CellPlan, mesh, page_size: int, num_pages: int):
    """Build the zeroed pool+state cache, sharded per the decode plan."""
    structs, specs = paged_cache_specs(plan, page_size, num_pages)
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                             is_leaf=lambda x: isinstance(x, P))

    def init():
        return jax.tree_util.tree_map_with_path(
            _init_leaf, structs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    return jax.jit(init, out_shardings=shardings)


def make_insert_fn(plan: CellPlan, plan_pre: CellPlan, mesh,
                   page_size: int, num_pages: int):
    """insert(cache, pre_cache, slot, pages) -> cache (donated, in place).

    ``pre_cache`` is the B=1 cache returned by the engine prefill step
    (seq length ``plan_pre.cell.seq_len``); ``slot`` a replicated int32;
    ``pages`` the slot's freshly mapped block-table row (replicated
    int32 [pages_per_slot], -1 for entries beyond the prompt).  State
    leaves are a slot-row write; KV leaves gather the request's prefill
    KV over tp and scatter it page-block-wise into the pool — only the
    mapped pages are written (unmapped / non-resident targets drop), so
    an admit touches O(prompt_len), not O(max_seq), pool bytes.
    """
    assert plan.cp == (plan.tp,) and plan_pre.cp == (plan_pre.tp,), (
        "engine admit requires tp-only context parallelism on both the "
        "prefill and decode plans")
    _, cspecs = paged_cache_specs(plan, page_size, num_pages)
    _, pspecs = cache_specs(plan_pre)
    num_slots = plan.cell.global_batch
    dp_size = plan.dp_size if plan.batch_sharded else 1
    slots_loc = num_slots // dp_size
    S_pre = plan_pre.cell.seq_len
    tp = plan.tp
    pool_axes = tuple(plan.dp) + (plan.tp,)
    psz = page_size

    def ins(cache, pre, slot, pages):
        pidx = axes_linear_index(pool_axes)        # pool shard index
        if dp_size > 1:
            r_dp = jnp.zeros((), jnp.int32)
            for a in plan.dp:
                r_dp = r_dp * lax.axis_size(a) + lax.axis_index(a)
        else:
            r_dp = jnp.zeros((), jnp.int32)
        own = (slot >= r_dp * slots_loc) & (slot < (r_dp + 1) * slots_loc)
        ls = jnp.clip(slot - r_dp * slots_loc, 0, slots_loc - 1)

        def merge(path, c, p):
            p0 = p[:, 0]                              # drop the B=1 dim
            if _is_kv_path(path):
                # c: pool shard [U, P_loc, psz, Hkv*dh]; gather the one
                # request's full prefill KV, re-slice it into lane-flat
                # page blocks, scatter through the slot's fresh table row
                P_loc = c.shape[1]
                full = lax.all_gather(p0, tp, axis=1, tiled=True)
                pps = pages.shape[0]
                gpos = jnp.arange(pps * psz)
                src = jnp.take(full, jnp.minimum(gpos, S_pre - 1), axis=1)
                src = src.astype(c.dtype).reshape(c.shape[0], pps, psz, -1)
                loc, _ = pool_local_pages(pages, pidx, P_loc)
                return c.at[:, loc].set(src, mode="drop")
            cur = lax.dynamic_index_in_dim(c, ls, axis=1, keepdims=False)
            row = jnp.where(own, p0.astype(c.dtype), cur)
            return c.at[:, ls].set(row)

        return jax.tree_util.tree_map_with_path(merge, cache, pre)

    fn = jax.shard_map(ins, mesh=mesh, in_specs=(cspecs, pspecs, P(), P()),
                       out_specs=cspecs, check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


class PagedKVCache:
    """Shared device KV page pool + slot-major state + host allocator."""

    def __init__(self, plan: CellPlan, plan_pre: CellPlan, mesh,
                 page_size: int = 64, num_pages: int | None = None):
        self.plan = plan
        self.mesh = mesh
        self.page_size = page_size
        self.num_pages = (default_num_pages(plan, page_size)
                          if num_pages is None else num_pages)
        groups = plan.dp_size if plan.batch_sharded else 1
        # pool shards per group: the page dim is sharded over dp x tp, so
        # each group's contiguous region spans this many device slices —
        # the compacted per-shard page lists are built against it
        shards = (plan.dp_size * plan.tp_size) // groups
        self.allocator = SlotAllocator(
            plan.cell.global_batch, plan.cell.seq_len, page_size,
            num_pages=self.num_pages, num_groups=groups,
            shards_per_group=shards)
        self.buffers = make_init_fn(plan, mesh, page_size, self.num_pages)()
        kv = [leaf for path, leaf in
              jax.tree_util.tree_leaves_with_path(self.buffers)
              if _is_kv_path(path)]
        #: list entries one grid step of the fused paged-decode kernel
        #: walks at this pool's shapes (None: no attention KV)
        self.kv_block_pages = (pages_per_block(
            self.allocator.pages_per_shard, page_size, kv[0].shape[-1],
            kv[0].dtype.itemsize) if kv else None)
        self._insert = make_insert_fn(plan, plan_pre, mesh, page_size,
                                      self.num_pages)
        #: exact-length prefill buckets: one compiled insert per prefill
        #: seq length (the gather/re-slice inside depends on S_pre)
        self._insert_fns = {plan_pre.cell.seq_len: self._insert}
        self.peak_pages_in_use = 0

    def _note_peak(self):
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.allocator.pages_in_use)

    @property
    def block_table(self) -> np.ndarray:
        """Host block table [slots, pages_per_slot] int32, -1 unmapped."""
        return self.allocator.block_table

    @property
    def page_list_loc(self) -> np.ndarray:
        """Compacted per-shard page lists [slots, shards, pages_per_shard]
        int32: shard-local pool row of each resident page, -1 = none."""
        return self.allocator.page_list_loc

    @property
    def page_list_pos(self) -> np.ndarray:
        """Absolute position of each compacted-list page's first token
        [slots, shards, pages_per_shard] int32, -1 = no page."""
        return self.allocator.page_list_pos

    def insert_fn_for(self, plan_pre: CellPlan):
        """The insert program for ``plan_pre``'s prefill length, compiled
        lazily — exact-length prefill buckets for recurrent families
        share one cache keyed by ``S_pre``."""
        S = plan_pre.cell.seq_len
        if S not in self._insert_fns:
            self._insert_fns[S] = make_insert_fn(
                self.plan, plan_pre, self.mesh, self.page_size,
                self.num_pages)
        return self._insert_fns[S]

    def admit(self, pre_cache, seq_len: int,
              plan_pre: CellPlan = None) -> int:
        """Allocate a slot, map ``ceil(seq_len/page_size)`` pages, and
        splice the prefilled cache into them.  ``plan_pre`` selects a
        non-default exact-length prefill bucket's insert program."""
        slot = self.allocator.alloc(seq_len)
        self._note_peak()
        ins = (self._insert if plan_pre is None
               else self.insert_fn_for(plan_pre))
        # a snapshot of the row, not a view: the array handed to the
        # asynchronously dispatched insert may alias host memory, and the
        # allocator rewrites this row as the slot grows or is recycled
        self.buffers = ins(
            self.buffers, pre_cache, jnp.asarray(slot, jnp.int32),
            np.array(self.allocator.block_table[slot], np.int32))
        return slot

    def ensure(self, slot: int, new_len: int):
        """Map pages (alloc-on-extend) so positions < ``new_len`` are
        writable; called before every decode/verify step."""
        self.allocator.ensure(slot, new_len)
        self._note_peak()

    def evict(self, slot: int):
        """Retire a slot: all its pages return to the pool and its block
        table row zeroes to -1, so any in-flight write the retired slot
        shape still carries is dropped on device."""
        self.allocator.free(slot)

    def rollback(self, slot: int, new_len: int):
        """Page-exact rollback after rejected speculative drafts.

        Returns the pages beyond ``ceil(new_len/page_size)`` to the
        pool.  The device-side KV rows for the rejected range are left
        in place deliberately: rows in still-mapped pages sit strictly
        beyond the slot's committed position (masked until the next
        verify window overwrites them), and rows in unmapped pages are
        unreachable — the table row is -1, and a future owner of the
        recycled page overwrites every position before exposing it.
        """
        self.allocator.rollback(slot, new_len)

    # -- async dispatch/commit epochs --------------------------------------

    def note_dispatch(self):
        """A decode/verify step was launched against a snapshot of the
        current block table; frees defer until it commits."""
        self.allocator.note_dispatch()

    def note_commit(self):
        """The oldest in-flight step's output was synced: release limbo
        pages no uncommitted snapshot can name anymore."""
        self.allocator.note_commit()

    @property
    def pages_in_limbo(self) -> int:
        return self.allocator.pages_in_limbo

    def kv_blocks_walked(self) -> int:
        """Page blocks one fused paged-decode kernel call computes at the
        allocator's current fill, summed over slots and pool shards."""
        if self.kv_block_pages is None:
            return 0
        return blocks_walked(self.allocator._shard_count,
                             self.kv_block_pages)

    def kv_pages_fetched(self) -> int:
        """Pages of K (and as many of V) one fused paged-decode kernel call
        copies at the allocator's current fill: the mapped list entries,
        summed over slots and pool shards."""
        if self.kv_block_pages is None:
            return 0
        return pages_fetched(self.allocator._shard_count)

    # -- memory accounting -------------------------------------------------

    def kv_page_bytes(self) -> int:
        """Device bytes of ONE pool page summed over layers/units."""
        per = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.buffers):
            if _is_kv_path(path):
                per += leaf.nbytes // self.num_pages
        return per

    def kv_bytes_mapped(self) -> int:
        """KV bytes actually backing live slots right now."""
        return self.allocator.pages_in_use * self.kv_page_bytes()

    def kv_bytes_pool(self) -> int:
        """Total pool capacity in bytes (the new HBM budget knob)."""
        return self.num_pages * self.kv_page_bytes()

    def kv_bytes_dense_reservation(self) -> int:
        """What the old slot-major layout reserved: every slot charged
        ``pages_per_slot`` pages up front, idle or not."""
        return (self.allocator.num_slots * self.allocator.pages_per_slot
                * self.kv_page_bytes())

    def state_bytes_per_slot(self) -> int:
        """Slot-major (recurrent state) bytes per slot — unchanged by
        paging, reported so the pool numbers aren't mistaken for the
        whole cache."""
        per = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.buffers):
            if not _is_kv_path(path):
                per += leaf.nbytes // leaf.shape[1]
        return per
