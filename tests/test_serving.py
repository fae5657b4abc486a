"""Serving engine tests: host-side scheduling logic in-process, model
parity + distributed sampling in 8-device subprocesses (see
dist_scenarios.py for why multi-device runs out-of-process)."""
import numpy as np
import pytest

from _hyp import given, settings, st
from _ref_sampling import host_reference_probs
from test_distributed import run


# ---------------------------------------------------------------------------
# host-side slot + page-pool allocator (no devices involved)
# ---------------------------------------------------------------------------


def test_slot_allocator_reuse_and_pages():
    from repro.serving import SlotAllocator
    a = SlotAllocator(num_slots=3, max_seq=64, page_size=16)
    assert a.num_free == 3 and a.total_pages == 12
    s0 = a.alloc(17)                      # 2 pages
    s1 = a.alloc(64)                      # 4 pages
    assert {s0, s1} == {0, 1}
    assert a.pages_used(s0) == 2 and a.pages_used(s1) == 4
    assert a.pages_in_use == 6
    a.extend(s0, 15)                      # 32 tokens -> still 2 pages
    assert a.pages_used(s0) == 2
    a.extend(s0, 1)                       # 33 tokens -> 3 pages
    assert a.pages_used(s0) == 3
    a.free(s1)
    assert a.num_free == 2 and a.pages_in_use == 3
    s2 = a.alloc(1)
    assert s2 == 2                        # FIFO free list
    a.free(s0)
    a.free(s2)
    s3 = a.alloc(5)
    assert s3 == s1                       # freed slot recycled
    with pytest.raises(ValueError):
        a.alloc(65)
    a.alloc(64)
    a.alloc(64)
    with pytest.raises(RuntimeError):     # pool exhausted
        a.alloc(1)


def test_slot_allocator_rejects_double_free():
    from repro.serving import SlotAllocator
    a = SlotAllocator(2, 8, 4)
    s = a.alloc(4)
    a.free(s)
    with pytest.raises(ValueError):      # typed: must survive python -O
        a.free(s)


def test_slot_allocator_admit_when_full_raises():
    from repro.serving import SlotAllocator
    a = SlotAllocator(num_slots=2, max_seq=16, page_size=4)
    a.alloc(8)
    a.alloc(8)
    with pytest.raises(RuntimeError):
        a.alloc(1)                       # pool exhausted -> caller queues


def test_slot_allocator_evict_admit_no_stale_occupancy():
    from repro.serving import SlotAllocator
    a = SlotAllocator(num_slots=2, max_seq=16, page_size=4)
    s0 = a.alloc(16)                     # 4 pages
    assert a.pages_used(s0) == 4
    a.free(s0)
    assert a.pages_in_use == 0           # occupancy fully returned
    s1 = a.alloc(2)
    a.alloc(2)
    assert s1 in (0, 1)
    # the recycled slot starts from the NEW request's length, not the old
    assert a.pages_used(s1) == 1 and a.pages_in_use == 2


def test_slot_allocator_extend_matches_positions():
    """``extend`` accounting tracks the engine's ``_pos`` invariant:
    after admit at P tokens and n decode commits, occupancy == P + n.
    Crossing ``max_seq`` is a typed ``CacheOverflowError`` — the old
    silent clamp hid scheduler bugs (a slot must retire at max_seq,
    never keep decoding into it)."""
    from repro.serving import CacheOverflowError, SlotAllocator
    a = SlotAllocator(num_slots=1, max_seq=16, page_size=4)
    s = a.alloc(5)
    pos = 5
    for _ in range(8):
        a.extend(s)
        pos += 1
        assert int(a._len[s]) == pos
    with pytest.raises(CacheOverflowError):
        a.extend(s, 10)                  # would cross max_seq: typed
    assert int(a._len[s]) == 13          # ...and state is untouched
    assert a.pages_used(s) == 4
    assert issubclass(CacheOverflowError, ValueError)


def test_slot_allocator_rollback_restores_occupancy():
    """Speculative accept/rollback: ``ensure`` maps the k+1 positions a
    verify step writes, rollback returns the rejected tail — occupancy
    AND page mapping land exactly at the committed length."""
    from repro.serving import SlotAllocator
    a = SlotAllocator(num_slots=2, max_seq=32, page_size=4)
    s = a.alloc(10)
    k = 3
    a.ensure(s, 10 + k + 1)              # verify writes pos 10..13
    assert int(a._len[s]) == 14
    a.rollback(s, 12)                    # committed 2 of 4
    assert int(a._len[s]) == 12 and a.pages_used(s) == 3
    # rejecting everything but the fixup token
    a.ensure(s, 12 + k + 1)
    a.rollback(s, 13)
    assert int(a._len[s]) == 13
    # near max_seq the engine clips its ensure; rollback still exact
    a.ensure(s, min(13 + 100, 32))
    assert int(a._len[s]) == 32
    a.rollback(s, 14)
    assert int(a._len[s]) == 14 and a.pages_used(s) == 4
    with pytest.raises(ValueError):
        a.rollback(s, 15)                # growth must go through ensure
    with pytest.raises(ValueError):
        a.rollback(s, 0)                 # zero-length slot is `free`'s job


def test_page_allocator_block_table_exact_and_disjoint():
    """Block-table rows mirror the mapping exactly: mapped prefixes are
    real page ids, the tail is -1, live rows are pairwise disjoint, and
    rollback/free return pages that a new slot can remap."""
    from repro.serving import SlotAllocator
    a = SlotAllocator(num_slots=3, max_seq=32, page_size=8, num_pages=6)
    s0 = a.alloc(17)                     # 3 pages
    s1 = a.alloc(8)                      # 1 page
    bt = a.block_table
    assert (bt[s0, :3] >= 0).all() and (bt[s0, 3:] == -1).all()
    assert (bt[s1, :1] >= 0).all() and (bt[s1, 1:] == -1).all()
    assert not set(bt[s0, :3]) & set(bt[s1, :1])
    a.rollback(s0, 9)                    # 3 -> 2 pages, page-exact
    assert (bt[s0, :2] >= 0).all() and (bt[s0, 2:] == -1).all()
    assert a.pages_in_use == 3
    s2 = a.alloc(24)                     # 3 pages from the returned pool
    live = [set(bt[s][bt[s] >= 0]) for s in (s0, s1, s2)]
    assert sum(len(x) for x in live) == len(set().union(*live))
    a.free(s1)
    assert (bt[s1] == -1).all()
    assert a.pages_in_use == 5


def test_page_allocator_typed_exhaustion():
    """``SlotsExhausted`` when slots run out, ``PagePoolExhausted`` when
    the pool does — slots can be free while pages are not, which is the
    regime a shrunk ``num_pages`` creates on purpose."""
    from repro.serving import (PagePoolExhausted, SlotAllocator,
                               SlotsExhausted)
    a = SlotAllocator(num_slots=4, max_seq=32, page_size=8, num_pages=4)
    s0 = a.alloc(32)                     # whole pool in one slot
    assert a.num_free == 3               # slots ARE free...
    assert not a.can_admit(1)
    with pytest.raises(PagePoolExhausted):
        a.alloc(1)                       # ...but no pages
    a.rollback(s0, 24)
    s1 = a.alloc(3)
    with pytest.raises(PagePoolExhausted):
        a.ensure(s1, 9)                  # live slot cannot grow either
    a.free(s0)
    for n in (8, 8, 8):
        a.alloc(n)
    with pytest.raises(SlotsExhausted):
        a.alloc(1)                       # now it IS the slot count
    assert issubclass(SlotsExhausted, RuntimeError)
    assert issubclass(PagePoolExhausted, RuntimeError)


def test_deferred_free_epoch_blocks_remap_until_commit():
    """Async overlap invariant: pages freed while a dispatched step's
    block-table snapshot may still name them park in limbo — they can
    NOT be remapped to a new slot until that step commits, at which
    point they rejoin the free pool exactly."""
    from repro.serving import PagePoolExhausted, SlotAllocator
    a = SlotAllocator(num_slots=3, max_seq=32, page_size=8, num_pages=4)
    s0 = a.alloc(16)                     # 2 pages
    old_pages = set(int(p) for p in a.block_table[s0][:2])
    a.note_dispatch()                    # step t snapshots s0's table
    a.free(s0)                           # retirement lands mid-flight
    assert a.pages_in_limbo == 2 and a.pages_in_use == 0
    # the freed pages are NOT available: only the 2 never-mapped pages
    # can back a new slot, so a 3-page request must fail typed even
    # though 4 - pages_in_use == 4
    assert not a.can_admit(17)
    with pytest.raises(PagePoolExhausted):
        a.alloc(17)
    s1 = a.alloc(16)                     # fits in the 2 untouched pages
    assert not set(int(p) for p in a.block_table[s1][:2]) & old_pages
    a.note_commit()                      # step t joined: limbo releases
    assert a.pages_in_limbo == 0
    s2 = a.alloc(16)                     # now the old pages remap
    assert set(int(p) for p in a.block_table[s2][:2]) == old_pages
    a.free(s1)
    a.free(s2)
    assert a.pages_in_use == 0 and a.pages_in_limbo == 0


def test_deferred_free_rollback_page_exact_under_overlap():
    """Speculative rollback while a step is in flight: the rejected
    tail's pages go to limbo (never straight back to the pool), the
    committed occupancy is exact, and with NO step in flight frees stay
    immediate — the sync engine's accounting is untouched."""
    from repro.serving import SlotAllocator
    a = SlotAllocator(num_slots=2, max_seq=32, page_size=4, num_pages=8)
    s = a.alloc(10)                      # 3 pages
    a.note_dispatch()
    a.ensure(s, 14)                      # verify window: 4 pages
    assert a.pages_used(s) == 4
    a.rollback(s, 11)                    # reject the tail mid-flight
    assert int(a._len[s]) == 11 and a.pages_used(s) == 3
    assert a.pages_in_limbo == 1
    a.note_commit()
    assert a.pages_in_limbo == 0
    # sync mode: dispatched == committed, frees are immediate
    a.ensure(s, 14)
    a.rollback(s, 11)
    assert a.pages_in_limbo == 0
    assert a.free_pages_in_group(0) == 8 - 3
    with pytest.raises(ValueError):      # commit without dispatch: typed
        a.note_commit()


def test_page_allocator_group_partitioning():
    """With dp groups, a slot only draws pages from its own group's
    contiguous range (device-side pages shard over dp x tp, so a slot's
    pages must live on its own dp group's shards)."""
    from repro.serving import PagePoolExhausted, SlotAllocator
    a = SlotAllocator(num_slots=4, max_seq=32, page_size=8, num_pages=8,
                      num_groups=2)
    assert a.pages_per_group == 4
    s0 = a.alloc(32)                     # slot 0 -> group 0, pages 0..3
    assert a.group_of(s0) == 0 and set(a.block_table[s0]) == {0, 1, 2, 3}
    # group 0 is now empty, but group 1's slots/pages still admit
    assert a.can_admit(32)
    s2 = a.alloc(32)                     # slots 2,3 -> group 1, pages 4..7
    assert a.group_of(s2) == 1 and set(a.block_table[s2]) == {4, 5, 6, 7}
    with pytest.raises(PagePoolExhausted):
        a.alloc(1)                       # slots 1 and 3 free, pools empty


@pytest.mark.slow
@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 6), st.integers(1, 40)),
                min_size=1, max_size=60),
       st.integers(1, 3))
def test_fuzz_page_allocator_never_leaks_or_double_maps(ops, groups):
    """Hypothesis fuzz of the page allocator: ANY alloc/ensure/rollback/
    free/preempt sequence — interleaved with note_dispatch/note_commit
    epoch marks, so frees land in the deferred-free limbo whenever a
    step is "in flight" — keeps (a) every page mapped at most once, (b)
    live slots' block-table rows disjoint and exactly mirroring the
    mapping, (c) free + mapped + limbo == num_pages, (d) failed ops
    state-neutral, (e) limbo empty whenever no step is outstanding.
    The preempt op (6) frees the YOUNGEST live slot mid-epoch — the
    allocator-level footprint of the engine's pool-pressure preemption
    — and must be page-clean like any other free."""
    from repro.serving import SlotAllocator
    from repro.serving.errors import (CacheOverflowError,
                                      PagePoolExhausted, SlotsExhausted)
    a = SlotAllocator(num_slots=3 * groups, max_seq=32, page_size=8,
                      num_pages=6 * groups, num_groups=groups)
    live = {}                            # slot -> len
    order = []                           # admission order (preempt victim
    #                                      selection is youngest-first)

    def check():
        mapped = []
        for s in range(a.num_slots):
            row = a.block_table[s]
            used = a.pages_used(s)
            assert (row[:used] >= 0).all() and (row[used:] == -1).all()
            if s in live:
                assert used == -(-live[s] // a.page_size)
                grp = a.group_of(s)
                lo = grp * a.pages_per_group
                assert all(lo <= p < lo + a.pages_per_group
                           for p in row[:used])
            else:
                assert used == 0
            mapped += list(row[:used])
        assert len(mapped) == len(set(mapped)), "double-mapped page"
        free_total = sum(a.free_pages_in_group(g) for g in range(groups))
        assert free_total + len(mapped) + a.pages_in_limbo \
            == a.num_pages, "page leak"
        if a._dispatched == a._committed:
            assert a.pages_in_limbo == 0, "limbo outlived its epochs"

    for op, arg in ops:
        try:
            if op == 0:
                s = a.alloc(min(arg, 32))
                live[s] = min(arg, 32)
                order.append(s)
            elif op == 1 and live:
                s = sorted(live)[arg % len(live)]
                a.ensure(s, live[s] + arg)
                live[s] = max(live[s], live[s] + arg)
            elif op == 2 and live:
                s = sorted(live)[arg % len(live)]
                new_len = max(1, live[s] - arg)
                a.rollback(s, new_len)
                live[s] = new_len
            elif op == 3 and live:
                s = sorted(live)[arg % len(live)]
                a.free(s)
                del live[s]
                order.remove(s)
            elif op == 4 and a._dispatched - a._committed < 2:
                a.note_dispatch()        # a step starts: frees now defer
            elif op == 5 and a._dispatched > a._committed:
                a.note_commit()          # oldest step joins: limbo drains
            elif op == 6 and live:
                s = order[-1]            # preempt: evict the youngest
                a.free(s)                # (its pages limbo mid-epoch)
                del live[s]
                order.pop()
        except (SlotsExhausted, PagePoolExhausted, CacheOverflowError):
            pass                         # typed refusals must not mutate
        check()
    while a._dispatched > a._committed:
        a.note_commit()
    for s in sorted(live):
        a.free(s)
    assert a.pages_in_use == 0 and a.num_free == a.num_slots
    assert a.pages_in_limbo == 0
    assert (a.block_table == -1).all()


# ---------------------------------------------------------------------------
# n-gram drafter (host-side, deterministic)
# ---------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup_and_fallback():
    from repro.serving import NGramDrafter
    d = NGramDrafter([1, 2, 3, 9, 1, 2, 3])
    # suffix [1,2,3] matched at position 0 -> proposes its continuation
    assert d.propose(3) == [9, 1, 2]
    d.extend([9])                        # history ...1,2,3,9
    assert d.propose(2) == [1, 2]        # suffix [2,3,9] -> cont [1,2]
    # no n-gram recurrence: falls back to repeating the last token
    d2 = NGramDrafter([5, 6, 7, 8])
    assert d2.propose(3) == [8, 8, 8]
    # deterministic: same history, same proposal
    assert d.propose(2) == d.propose(2)
    with pytest.raises(ValueError):
        NGramDrafter([1], max_n=0)


# ---------------------------------------------------------------------------
# sampling, single-device path (tp_size == 1: pure local math)
# ---------------------------------------------------------------------------


def test_sampling_single_device_greedy_topk_topp():
    import jax
    import jax.numpy as jnp
    from repro.serving.sampling import SamplingConfig, sample
    B, V = 8, 128
    logits = jax.random.normal(jax.random.PRNGKey(0), (B, V)) * 3.0
    key = jax.random.PRNGKey(3)
    zero = jnp.zeros(B, jnp.float32)

    tok = np.asarray(sample(logits, key, zero, tp=None, tp_size=1))
    np.testing.assert_array_equal(tok, np.asarray(logits).argmax(-1))

    # temps mix greedy + stochastic per slot in one call
    temps = jnp.asarray([0.0, 1.0] * (B // 2), jnp.float32)
    k = 4
    topk = np.argsort(np.asarray(logits), -1)[:, -k:]
    tok = np.asarray(sample(logits, key, temps, tp=None, tp_size=1,
                            cfg=SamplingConfig(top_k=k)))
    for b in range(B):
        if temps[b] == 0:
            assert tok[b] == np.asarray(logits)[b].argmax()
        else:
            assert tok[b] in topk[b]

    p = 0.5
    pr = np.asarray(jax.nn.softmax(logits.astype(jnp.float32), -1))
    order = np.argsort(-pr, -1)
    csum = np.cumsum(np.take_along_axis(pr, order, -1), -1)
    for s in range(3):
        tok = np.asarray(sample(logits, jax.random.PRNGKey(s),
                                jnp.ones(B, jnp.float32), tp=None,
                                tp_size=1, cfg=SamplingConfig(top_p=p)))
        for b in range(B):
            nucleus = set(order[b, :int((csum[b] < p).sum()) + 1])
            assert tok[b] in nucleus


# ---------------------------------------------------------------------------
# sampling statistics (tp_size == 1 in-process; tp > 1 in subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("scfg_kw", [dict(), dict(top_k=8),
                                     dict(top_p=0.6)])
def test_sampling_statistics_match_host_reference(scfg_kw):
    """Total-variation distance between >=2k fused-sampler draws and the
    host reference softmax sampler, single-device path (tp_size == 1).
    Per-slot independence turns one [DRAWS, V] batch into DRAWS
    independent draws of the same distribution."""
    import jax
    import jax.numpy as jnp
    from repro.serving.sampling import SamplingConfig, sample
    V, DRAWS, TEMP = 64, 4096, 0.7
    rng = np.random.RandomState(5)
    row = rng.randn(V) * 2.0
    logits = jnp.asarray(np.broadcast_to(row, (DRAWS, V)), jnp.float32)
    tok = np.asarray(sample(logits, jax.random.PRNGKey(11),
                            jnp.full(DRAWS, TEMP, jnp.float32),
                            tp=None, tp_size=1,
                            cfg=SamplingConfig(**scfg_kw)))
    emp = np.bincount(tok, minlength=V) / DRAWS
    ref = host_reference_probs(row, TEMP, **scfg_kw)
    tv = 0.5 * np.abs(emp - ref).sum()
    assert tv < 0.06, (scfg_kw, tv)


def test_top_p_bisection_matches_sorted_cumsum_nucleus():
    """``_apply_top_p``'s bisected probability threshold must keep
    exactly the reference nucleus (smallest top-probability set with
    mass >= p) on random logits."""
    import jax
    import jax.numpy as jnp
    from repro.serving.sampling import _apply_top_p
    B, V = 16, 128
    lt = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (B, V)),
                    np.float64) * 3.0
    for p in (0.1, 0.3, 0.6, 0.9, 0.99):
        out = np.asarray(_apply_top_p(jnp.asarray(lt, jnp.float32), p,
                                      None, 1))
        kept = np.isfinite(out)
        probs = np.exp(lt - lt.max(-1, keepdims=True))
        probs = probs / probs.sum(-1, keepdims=True)
        for b in range(B):
            order = np.argsort(-probs[b])
            csum = np.cumsum(probs[b][order])
            n_ref = int((csum < p).sum()) + 1       # minimal nucleus size
            ref = np.zeros(V, bool)
            ref[order[:n_ref]] = True
            np.testing.assert_array_equal(kept[b], ref, err_msg=f"p={p}")


# ---------------------------------------------------------------------------
# multi-device engine parity + statistics (subprocess)
# ---------------------------------------------------------------------------


def test_engine_matches_single_request_and_teacher_forced():
    """Prefill->decode parity: N-step batched engine decode (6 requests
    over 4 slots) equals the single-request run AND the teacher-forced
    forward argmax, across `none` and `spike_fused` boundary modes."""
    out = run("serving_parity")
    assert out.count("serving parity OK") == 2


def test_distributed_sampling_matches_host():
    run("serving_sampling")


@pytest.mark.slow
def test_distributed_sampling_statistics():
    """TV distance of the fused sampler vs the host reference at tp=8."""
    out = run("sampling_stats")
    assert out.count("sampling stats OK") == 3


def test_paged_pool_shared_across_mixed_lengths():
    """Block-table paging payoff: one long slot and several short ones
    share a pool SMALLER than the dense reservation, on the 2x4 mesh
    (pool pages sharded over dp x tp, slots batch-sharded over dp),
    token-identical to the dense-equivalent full pool."""
    out = run("serving_paged_mixed")
    assert "paged mixed OK" in out


def test_fused_paged_decode_parity_on_mesh():
    """Fused Pallas paged-decode on the 2x4 mesh: 4-way compacted
    per-shard page lists, pool below the dense reservation, and every
    served token the teacher-forced argmax for both codecs and through
    the speculative verify path."""
    out = run("serving_fused_parity")
    assert out.count("fused parity OK") == 2


def test_speculative_decoding_parity_and_acceptance():
    """Tentpole invariant: greedy spec decoding (spec_k=3) is
    token-identical to the vanilla engine for `none` and `spike_fused`,
    accepts >1 token per verify step on a repetitive workload, uses
    fewer device steps, and leaks no pages through accept/rollback."""
    out = run("serving_spec_parity")
    assert out.count("spec parity OK") == 2


def test_speculative_recurrent_fallback():
    """Recurrent-state families force spec_k=0 and still serve."""
    run("serving_spec_recurrent_fallback")
