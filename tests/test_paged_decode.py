"""Fused paged-decode attention: kernel-vs-oracle sweeps and compacted
per-shard page-list invariants.

Two layers, matching the data path (the engine end to end is held to
the teacher-forced forward in tests/test_engine_teacher_forced.py):

1. ``kernels.paged_decode`` (interpret mode) against the dense
   single-softmax oracle ``kernels.ref.paged_decode_ref`` — GQA, K1 > 1
   (spec verify), sliding window, softcap, evicted slots (all ``-1``
   lists), partially filled last pages, pool much larger than the live
   set, and the int8 wire epilogue bit-matching
   ``core.boundary.quantize_partial``.

2. ``SlotAllocator`` compacted-list bookkeeping under random
   alloc/extend/rollback/free interleavings: disjointness, per-shard
   residency, position ordering, agreement with the block table, and
   the enforced (never best-effort) per-shard width invariant.
"""
import numpy as np
import pytest

from _hyp import HAVE_HYPOTHESIS, given, settings, st

# ---------------------------------------------------------------------------
# 1. kernel vs oracle
# ---------------------------------------------------------------------------


def _rand_case(seed, B, K1, Hq, Hkv, dh, P_loc, psz, ppc, n_live=None,
               partial_last=False, dense=False):
    """Random pool + well-formed compacted lists (distinct local rows,
    ascending positions; consecutive pages with ``dense``) + per-slot
    qpos at the write frontier."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, K1, Hq, dh), jnp.float32)
    # two units of lane-flat pool pages [2, P_loc, psz, Hkv*dh], the
    # engine's layout
    k_pool = jax.random.normal(kk, (2, P_loc, psz, Hkv * dh), jnp.float32)
    v_pool = jax.random.normal(kv, (2, P_loc, psz, Hkv * dh), jnp.float32)
    clp = np.full((B, ppc), -1, np.int32)
    clo = np.full((B, ppc), -1, np.int32)
    qpos = np.zeros((B, K1), np.int32)
    for b in range(B):
        n = rng.randint(1, ppc + 1) if n_live is None else n_live
        if n:
            clp[b, :n] = rng.choice(P_loc, n, replace=False)
            pages = (np.arange(n) if dense else
                     np.sort(rng.choice(ppc * 4, n, replace=False)))
            clo[b, :n] = pages * psz
            last = int(clo[b, n - 1])
            off = rng.randint(0, psz) if partial_last else psz - 1
            qpos[b] = last + max(off, K1 - 1) - np.arange(K1)[::-1]
    return (q, k_pool, v_pool, jnp.asarray(clp), jnp.asarray(clo),
            jnp.asarray(qpos))


def _assert_matches_oracle(case, window=0, cap=0.0):
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    q, kp, vp, clp, clo, qpos = case
    # interpret=True runs the Pallas kernel body (the default off-TPU
    # dispatch runs the oracle itself — see ops.paged_flash_decode)
    o, lse = ops.paged_flash_decode(q, kp, vp, clp, clo, qpos, 1,
                                    window=window, cap=cap,
                                    interpret=True)
    oe, le = ref.paged_decode_ref(q, kp, vp, clp, clo, qpos, 1,
                                  window=window, cap=cap)
    np.testing.assert_allclose(np.array(o), np.array(oe), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(np.array(lse), np.array(le), atol=2e-4,
                               rtol=2e-5)


# (page size, list width, id suffix): at page 8 a block holds the whole
# 4-entry list; at page 32 a block holds 8 entries, so 11 entries take
# two blocks, the second padded with 5 unmapped ones
WALKS = [(8, 4, ""), (32, 11, "-blocks")]


@pytest.mark.parametrize("K1,Hq,Hkv,psz,ppc", [
    pytest.param(K1, Hq, Hkv, psz, ppc, id=f"{K1}-{Hq}-{Hkv}{sfx}")
    for K1 in (1, 3) for Hq, Hkv in ((4, 4), (8, 2))
    for psz, ppc, sfx in WALKS])
def test_kernel_matches_oracle(Hq, Hkv, K1, psz, ppc):
    _assert_matches_oracle(_rand_case(0, B=5, K1=K1, Hq=Hq, Hkv=Hkv,
                                      dh=16, P_loc=3 * ppc, psz=psz,
                                      ppc=ppc))


@pytest.mark.parametrize("window,cap,psz,ppc", [
    pytest.param(window, cap, psz, ppc, id=f"{window}-{cap}{sfx}")
    for window, cap in ((24, 0.0), (0, 12.0), (16, 8.0))
    for psz, ppc, sfx in WALKS])
def test_kernel_window_softcap(window, cap, psz, ppc):
    """At page 32 the pages are consecutive: a window of 24 or 16 ends
    inside the last entry, in the walk's last block, and masks the block
    before it whole."""
    _assert_matches_oracle(_rand_case(1, B=4, K1=2, Hq=4, Hkv=4, dh=16,
                                      P_loc=3 * ppc, psz=psz, ppc=ppc,
                                      n_live=ppc if psz == 32 else None,
                                      dense=psz == 32),
                           window=window, cap=cap)


@pytest.mark.parametrize("n_live", [11, 16])
def test_walk_ends_mid_block_and_at_block_boundary(n_live):
    """20 entries at page 32 are three blocks of 8; every slot's list
    ends inside its second block (11 mapped) or exactly at its end (16),
    so no slot walks the third."""
    _assert_matches_oracle(_rand_case(6, B=3, K1=2, Hq=4, Hkv=4, dh=16,
                                      P_loc=40, psz=32, ppc=20,
                                      n_live=n_live))


def _assert_evicted_slot_matches(psz, ppc, n_live):
    """Slot 1 of three holds an all ``-1`` list."""
    from repro.kernels import ops, ref
    q, kp, vp, clp, clo, qpos = _rand_case(2, B=3, K1=2, Hq=4, Hkv=4,
                                           dh=16, P_loc=3 * ppc, psz=psz,
                                           ppc=ppc, n_live=n_live)
    clp = clp.at[1].set(-1)
    clo = clo.at[1].set(-1)
    o, lse = ops.paged_flash_decode(q, kp, vp, clp, clo, qpos, 0,
                                    interpret=True)
    oe, le = ref.paged_decode_ref(q, kp, vp, clp, clo, qpos, 0)
    assert np.isfinite(np.array(o)).all()
    assert (np.array(o[1]) == 0).all()
    np.testing.assert_allclose(np.array(lse[1]), -1e30)
    np.testing.assert_allclose(np.array(le[1]), -1e30)
    np.testing.assert_allclose(np.array(o[1]), np.array(oe[1]), atol=2e-5)
    # combine weight of the dead partial is identically zero
    assert (np.exp(np.array(lse[1], np.float64) - 0.0) == 0.0).all()
    return o, lse, oe, le


def test_evicted_slot_all_invalid():
    """An all ``-1`` list (evicted slot riding in the batch, or a shard
    holding none of a slot's pages) must stay finite with lse = -1e30:
    no page is fetched, so the row's o is exactly 0 (every score masked
    to the same -1e30, every V row zero), and its weight in the
    cross-shard LSE combine is exp(-1e30 - m) = 0 exactly, so it can
    never contaminate a real partial — and it must agree with the
    oracle."""
    _assert_evicted_slot_matches(psz=8, ppc=3, n_live=None)


def test_evicted_slot_between_full_walks():
    """The dead slot between two slots whose 11 entries fill two blocks
    of 8 at page 32: it walks one block, and its neighbours' partials
    match the oracle as well."""
    o, lse, oe, le = _assert_evicted_slot_matches(psz=32, ppc=11,
                                                  n_live=11)
    for b in (0, 2):
        np.testing.assert_allclose(np.array(o[b]), np.array(oe[b]),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.array(lse[b]), np.array(le[b]),
                                   atol=2e-4, rtol=2e-5)


@pytest.mark.parametrize("encode_wire", [False, True])
def test_kernel_on_poisoned_memory(encode_wire):
    """The kernel copies only mapped pages, so the rest of a block's ring
    buffer holds whatever VMEM held: here NaN, as the TPU interpreter
    fills uninitialised memory, with its race detector on.  20 entries
    at page 32 are three blocks of 8; slot 0 fills two blocks whole,
    slot 1 after it ends mid-block (11), slot 2 maps nothing, slot 3
    ends at a block boundary (16) and slot 4 mid-block (5).  Every
    output is finite and matches the oracle, and no copy races a read:
    the unfetched V rows are zeroed, and each slot's first block, fetched
    while its predecessor's last is scored, has landed before use."""
    from jax.experimental.pallas import tpu as pltpu
    from jax._src.pallas.mosaic.interpret import (
        interpret_pallas_call as tpu_interpret)
    from repro.kernels import ref
    from repro.kernels.paged_decode import paged_decode_pallas
    q, kp, vp, clp, clo, qpos = _rand_case(7, B=5, K1=2, Hq=4, Hkv=4,
                                           dh=16, P_loc=100, psz=32,
                                           ppc=20, n_live=16)
    for b, n in enumerate((16, 11, 0, 16, 5)):
        clp = clp.at[b, n:].set(-1)
        clo = clo.at[b, n:].set(-1)
    outs = paged_decode_pallas(
        q, kp, vp, clp, clo, qpos, 1, encode_wire=encode_wire,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan",
                                        detect_races=True))
    assert not tpu_interpret.races.races_found
    oe, le = ref.paged_decode_ref(q, kp, vp, clp, clo, qpos, 1)
    for x in outs:
        assert np.isfinite(np.array(x, np.float32)).all()
    lse = np.array(outs[-1])
    np.testing.assert_allclose(lse, np.array(le), atol=2e-4, rtol=2e-5)
    if encode_wire:
        wire, scale = np.array(outs[0], np.float32), np.array(outs[1])
        # the decoded wire lies within one quantization step of the oracle
        assert (np.abs(wire * scale - np.array(oe)) <= scale + 1e-6).all()
    else:
        np.testing.assert_allclose(np.array(outs[0]), np.array(oe),
                                   atol=2e-5, rtol=2e-5)
    assert (np.array(outs[0])[2] == 0).all()      # slot 2: nothing mapped


def _partial_last_page(psz, ppc):
    _assert_matches_oracle(_rand_case(3, B=6, K1=1, Hq=4, Hkv=4, dh=16,
                                      P_loc=3 * ppc, psz=psz, ppc=ppc,
                                      partial_last=True))


def test_partial_last_page():
    """qpos strictly inside the last mapped page: positions past the
    write frontier must not score."""
    _partial_last_page(psz=8, ppc=3)


@pytest.mark.parametrize("ppc", [3, 11])
def test_partial_last_page_in_block_walk(ppc):
    """The same at page 32, where a block holds 8 entries: a 3-entry list
    is shorter than a block (one block of 3), an 11-entry one ends
    inside its second block."""
    _partial_last_page(psz=32, ppc=ppc)


def test_pool_much_larger_than_live():
    """num_pages >> live pages: compaction means cost scales with the
    list width, and untouched pool rows never leak into the output."""
    _assert_matches_oracle(_rand_case(4, B=3, K1=2, Hq=4, Hkv=4, dh=16,
                                      P_loc=128, psz=8, ppc=2, n_live=1))


def test_wire_epilogue_matches_quantize_partial():
    """The kernel's fused int8 epilogue implements the SAME per-token
    absmax contract as the host-side ``boundary.quantize_partial`` (the
    dense-cache decode path's encoder), so ``coded_combine_partials`` decodes
    either identically: scales agree to fp epsilon (the two are
    separately compiled programs, so bit-identity is not guaranteed)
    and the decoded wires agree to within one quantization step."""
    from repro.core import boundary
    from repro.kernels import ops
    q, kp, vp, clp, clo, qpos = _rand_case(5, B=4, K1=2, Hq=4, Hkv=4,
                                           dh=16, P_loc=10, psz=8, ppc=3)
    o, lse = ops.paged_flash_decode(q, kp, vp, clp, clo, qpos, 0,
                                    interpret=True)
    we, se = boundary.quantize_partial(o)
    # both the Pallas epilogue and the off-TPU XLA dispatch must honor
    # the contract
    for interp in (True, False):
        wire, scale, lse_w = ops.paged_flash_decode(
            q, kp, vp, clp, clo, qpos, 0, encode_wire=True,
            interpret=interp)
        assert wire.dtype == np.int8 and we.dtype == np.int8
        assert scale.shape == se.shape == (4, 2, 4, 1)
        np.testing.assert_allclose(np.array(scale), np.array(se),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.array(lse_w), np.array(lse),
                                   rtol=1e-6, atol=1e-6)
        dec_k = np.array(wire, np.float32) * np.array(scale)
        dec_h = np.array(we, np.float32) * np.array(se)
        step = np.array(se)
        assert (np.abs(dec_k - dec_h) <= step + 1e-7).all()
        # int8 range actually used, never overflowed
        assert np.abs(np.array(wire)).max() <= 127


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       gqa=st.sampled_from([(4, 4), (4, 2), (8, 2)]),
       K1=st.integers(1, 3),
       psz=st.sampled_from([4, 8]),
       ppc=st.integers(1, 5),
       window=st.sampled_from([0, 16]),
       partial=st.booleans())
def test_fuzz_kernel_vs_oracle(seed, gqa, K1, psz, ppc, window, partial):
    Hq, Hkv = gqa
    _assert_matches_oracle(
        _rand_case(seed % 100000, B=3, K1=K1, Hq=Hq, Hkv=Hkv, dh=8,
                   P_loc=4 * ppc + 3, psz=psz, ppc=ppc,
                   partial_last=partial),
        window=window)


# ---------------------------------------------------------------------------
# 2. allocator compacted-list invariants
# ---------------------------------------------------------------------------


def _check_lists(a):
    """Every structural invariant the fused kernel relies on."""
    live_all = []
    for slot in range(a.num_slots):
        pages = a._pages[slot]
        live_all.extend(pages)
        g = a.group_of(slot)
        base = g * a.pages_per_group
        seen = []
        for s in range(a.shards_per_group):
            cnt = int(a._shard_count[slot, s])
            loc = a.page_list_loc[slot, s]
            pos = a.page_list_pos[slot, s]
            # compact prefix, -1 beyond
            assert (loc[:cnt] >= 0).all() and (loc[cnt:] == -1).all()
            assert (pos[:cnt] >= 0).all() and (pos[cnt:] == -1).all()
            # per-shard residency + local-row range
            assert (loc[:cnt] < a.pages_local).all()
            # strictly increasing positions (ordinal order within shard)
            assert (np.diff(pos[:cnt]) > 0).all()
            for j in range(cnt):
                page = base + s * a.pages_local + int(loc[j])
                assert a._shard_of(page) == s
                ordinal = pages.index(page)       # raises if not resident
                assert int(pos[j]) == ordinal * a.page_size
                seen.append(page)
        # the lists name exactly the slot's pages, each once
        assert sorted(seen) == sorted(pages)
        # block table agrees
        bt = a.block_table[slot]
        assert list(bt[:len(pages)]) == pages
        assert (bt[len(pages):] == -1).all()
    # pool-wide disjointness
    assert len(live_all) == len(set(live_all))


def _mk_alloc(**kw):
    from repro.serving.kv_cache import SlotAllocator
    base = dict(num_slots=4, max_seq=64, page_size=8, num_pages=24,
                num_groups=2, shards_per_group=2)
    base.update(kw)
    return SlotAllocator(**base)


def test_compacted_list_width():
    a = _mk_alloc()
    assert a.pages_per_slot == 8
    assert a.pages_per_shard == 4                 # ceil(8 / 2)
    assert a.page_list_loc.shape == (4, 2, 4)
    b = _mk_alloc(shards_per_group=3, num_pages=24)
    assert b.pages_per_shard == 3                 # ceil(8 / 3)


def test_compacted_lists_track_lifecycle():
    a = _mk_alloc()
    s0 = a.alloc(20)                              # 3 pages
    s1 = a.alloc(64)                              # 8 pages (full)
    _check_lists(a)
    a.extend(s0, 12)                              # -> 4 pages
    _check_lists(a)
    a.rollback(s1, 33)                            # 8 -> 5 pages
    _check_lists(a)
    a.free(s0)
    _check_lists(a)
    assert (a.page_list_loc[s0] == -1).all()
    assert int(a._shard_count.sum()) == a.pages_in_use == 5
    a.free(s1)
    _check_lists(a)
    assert a.pages_in_use == 0
    assert (a.page_list_loc == -1).all() and (a.page_list_pos == -1).all()


def test_balanced_placement_fills_shards_evenly():
    a = _mk_alloc()
    s0 = a.alloc(64)                              # 8 pages over 2 shards
    assert list(a._shard_count[s0]) == [4, 4]
    _check_lists(a)


def test_width_invariant_enforced_not_best_effort():
    """Drain one shard's free range: placement must route to the other
    shard until ITS width is exhausted, then raise typed — an
    overflowing page would be invisible to the fused kernel."""
    from repro.serving.errors import PagePoolExhausted
    a = _mk_alloc(num_slots=2, num_groups=1, num_pages=12,
                  shards_per_group=2)             # pages_local=6, width=4
    a._free_pages[0][1].clear()                   # shard 1 dry
    assert a._fresh_capacity(0) == 4 < a.free_pages_in_group(0) == 6
    s0 = a.alloc(32)                              # 4 pages, all shard 0
    assert list(a._shard_count[s0]) == [4, 0]
    _check_lists(a)
    with pytest.raises(PagePoolExhausted):
        a.ensure(s0, 33)                          # shard 0 width is full
    assert not a.can_admit(40)                    # 5 pages > capacity 2
    assert a.can_admit(16)


def test_degenerate_single_shard_matches_block_table():
    """shards_per_group=1 (single-device engine): the one compacted list
    is the block table's live prefix, locally renumbered."""
    a = _mk_alloc(num_groups=1, shards_per_group=1, num_pages=32)
    s = a.alloc(30)
    assert a.pages_per_shard == a.pages_per_slot
    np.testing.assert_array_equal(
        a.page_list_loc[s, 0, :4], a.block_table[s, :4] % a.pages_local)
    np.testing.assert_array_equal(a.page_list_pos[s, 0, :4],
                                  np.arange(4) * a.page_size)
    _check_lists(a)


def test_kv_blocks_walked_matches_hand_count():
    """Page 16, 1024-token slots over two shards: 32-entry lists, walked
    16 entries a block (256 tokens) at 1024 lanes of bf16.  Each (slot,
    shard) list walks ceil(fill / 16) blocks, and one block when empty:
    a free or released slot still computes block 0."""
    from repro.kernels.paged_decode import blocks_walked, pages_per_block
    a = _mk_alloc(max_seq=1024, page_size=16, num_pages=512)
    n = pages_per_block(a.pages_per_shard, a.page_size, 1024, 2)
    assert (a.pages_per_shard, n) == (32, 16)
    assert blocks_walked(a._shard_count, n) == 4 * 2      # all free
    s0 = a.alloc(600)              # 38 pages: 19 + 19 -> 2 + 2 blocks
    s1 = a.alloc(512)              # 32 pages: 16 + 16 -> 1 + 1 (boundary)
    s2 = a.alloc(1024)             # 64 pages: 32 + 32 -> 2 + 2 (full)
    assert list(a._shard_count[s0]) == [19, 19]
    assert list(a._shard_count[s1]) == [16, 16]
    # the fourth slot is free: 1 + 1
    assert blocks_walked(a._shard_count, n) == 4 + 2 + 4 + 2
    a.extend(s1, 1)                # 17 on one shard -> 2 + 1
    assert blocks_walked(a._shard_count, n) == 4 + 3 + 4 + 2
    a.free(s0)                     # released: 1 + 1
    assert blocks_walked(a._shard_count, n) == 2 + 3 + 4 + 2
    a.free(s2)
    assert blocks_walked(a._shard_count, n) == 2 + 3 + 2 + 2


def test_kv_pages_fetched_matches_hand_count():
    """The same slots: the kernel copies each list's mapped entries and
    nothing else, where the walk's blocks span ``blocks_walked x 16``
    entries; the gap is the stand-in fetches a block walk would make."""
    from repro.kernels.paged_decode import (blocks_walked, pages_fetched,
                                            pages_per_block)
    a = _mk_alloc(max_seq=1024, page_size=16, num_pages=512)
    n = pages_per_block(a.pages_per_shard, a.page_size, 1024, 2)
    assert pages_fetched(a._shard_count) == 0               # all free
    s0 = a.alloc(600)              # 38 pages: 19 + 19
    s1 = a.alloc(512)              # 32 pages: 16 + 16 (boundary)
    s2 = a.alloc(1024)             # 64 pages: 32 + 32 (full)
    assert pages_fetched(a._shard_count) == 38 + 32 + 64 == a.pages_in_use
    # 12 blocks of 16 entries span 192: 58 of them are not fetched
    assert blocks_walked(a._shard_count, n) * n == 192
    a.extend(s1, 1)                # one more page on one shard
    assert pages_fetched(a._shard_count) == 38 + 33 + 64
    a.free(s0)
    a.free(s2)
    assert pages_fetched(a._shard_count) == 33 == a.pages_in_use


def test_block_width_at_the_decode_cell_shape():
    """qwen1.5-0.5b served at page 16 and max_seq 2048 on one chip:
    128-entry lists of [16, 1024] bf16 pages walk 16 pages a block; the
    kernel's grid is one step per slot, the pools stay in HBM (the kernel
    copies its own pages), and its K and V rings hold ``RING_DEPTH``
    blocks of 16 pages each, one DMA semaphore per buffer."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from repro.kernels.paged_decode import (RING_DEPTH, paged_decode_pallas,
                                            pages_per_block)
    assert pages_per_block(128, 16, 1024, 2) == 16
    sds = jax.ShapeDtypeStruct
    pool = sds((24, 8192, 16, 1024), jnp.bfloat16)
    lists = sds((64, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda *a: paged_decode_pallas(
        *a, encode_wire=True))(sds((64, 1, 16, 64), jnp.bfloat16), pool,
                               pool, lists, lists, sds((64, 1), jnp.int32),
                               sds((), jnp.int32))
    calls = [e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    gm = calls[0].params["grid_mapping"]
    assert gm.grid == (64,)
    # q rows, head mask, then the two pools, whole and in HBM
    assert gm.num_inputs == 4
    pools = gm.block_mappings[2:4]
    assert all(m.block_aval.memory_space == pl.ANY for m in pools)
    assert all(m.block_aval.shape == pool.shape for m in pools)
    scratch = [v.aval for v in calls[0].params["jaxpr"].invars[
        -gm.num_scratch_operands:]]
    assert [a.shape for a in scratch[:3]] == [
        (RING_DEPTH, 16 * 16, 1024), (RING_DEPTH, 16 * 16, 1024),
        (2, RING_DEPTH)]
    assert scratch[0].dtype == scratch[1].dtype == jnp.bfloat16


@pytest.mark.slow
@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), shards=st.sampled_from([1, 2, 4]),
       steps=st.integers(5, 40))
def test_fuzz_allocator_invariants(seed, shards, steps):
    """Random alloc/extend/rollback/free interleavings keep every
    compacted-list invariant, including under exhaustion."""
    from repro.serving.errors import PagePoolExhausted, SlotsExhausted
    rng = np.random.RandomState(seed % 100000)
    a = _mk_alloc(num_slots=4, max_seq=64, page_size=8, num_pages=16,
                  num_groups=1, shards_per_group=shards)
    live = {}
    for _ in range(steps):
        op = rng.randint(4)
        try:
            if op == 0:
                n = int(rng.randint(1, 65))
                live[a.alloc(n)] = n
            elif op == 1 and live:
                s = rng.choice(sorted(live))
                n = min(64, live[s] + int(rng.randint(1, 17)))
                a.ensure(s, n)        # may raise: occupancy stays put
                live[s] = n
            elif op == 2 and live:
                s = rng.choice(sorted(live))
                live[s] = int(rng.randint(1, live[s] + 1))
                a.rollback(s, live[s])
            elif op == 3 and live:
                s = rng.choice(sorted(live))
                a.free(s)
                del live[s]
        except (PagePoolExhausted, SlotsExhausted):
            pass
        _check_lists(a)
    for s in sorted(live):
        a.free(s)
    _check_lists(a)
    assert a.pages_in_use == 0
