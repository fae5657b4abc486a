"""Fused paged-decode attention: kernel-vs-oracle sweeps, compacted
per-shard page-list invariants, and engine fused-vs-reference identity.

Three layers, matching the data path:

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

3. The serving engine end-to-end: greedy token streams of the fused
   kernel path vs the reference gather path must be identical across
   spec_k x async_depth x codec (the acceptance bar for making
   ``attn_kernel="fused"`` the default).
"""
import numpy as np
import pytest

from _hyp import HAVE_HYPOTHESIS, given, settings, st

# ---------------------------------------------------------------------------
# 1. kernel vs oracle
# ---------------------------------------------------------------------------


def _rand_case(seed, B, K1, Hq, Hkv, dh, P_loc, psz, ppc, n_live=None,
               partial_last=False):
    """Random pool + well-formed compacted lists (distinct local rows,
    ascending positions) + per-slot qpos at the write frontier."""
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
            clo[b, :n] = np.sort(rng.choice(ppc * 4, n, replace=False)) * psz
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


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("K1", [1, 3])
def test_kernel_matches_oracle(Hq, Hkv, K1):
    _assert_matches_oracle(_rand_case(0, B=5, K1=K1, Hq=Hq, Hkv=Hkv,
                                      dh=16, P_loc=12, psz=8, ppc=4))


@pytest.mark.parametrize("window,cap", [(24, 0.0), (0, 12.0), (16, 8.0)])
def test_kernel_window_softcap(window, cap):
    _assert_matches_oracle(_rand_case(1, B=4, K1=2, Hq=4, Hkv=4, dh=16,
                                      P_loc=10, psz=8, ppc=4),
                           window=window, cap=cap)


def test_evicted_slot_all_invalid():
    """An all ``-1`` list (evicted slot riding in the batch, or a shard
    holding none of a slot's pages) must stay finite with lse = -1e30:
    the row's o is a degenerate uniform mean (all scores masked to the
    same -1e30), but its weight in the cross-shard LSE combine is
    exp(-1e30 - m) = 0 exactly, so it can never contaminate a real
    partial — and it must agree with the oracle bit-for-bit in kind."""
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    q, kp, vp, clp, clo, qpos = _rand_case(2, B=3, K1=2, Hq=4, Hkv=4,
                                           dh=16, P_loc=8, psz=8, ppc=3)
    clp = clp.at[1].set(-1)
    clo = clo.at[1].set(-1)
    o, lse = ops.paged_flash_decode(q, kp, vp, clp, clo, qpos, 0,
                                    interpret=True)
    oe, le = ref.paged_decode_ref(q, kp, vp, clp, clo, qpos, 0)
    assert np.isfinite(np.array(o)).all()
    np.testing.assert_allclose(np.array(lse[1]), -1e30)
    np.testing.assert_allclose(np.array(le[1]), -1e30)
    np.testing.assert_allclose(np.array(o[1]), np.array(oe[1]), atol=2e-5)
    # combine weight of the dead partial is identically zero
    assert (np.exp(np.array(lse[1], np.float64) - 0.0) == 0.0).all()


def test_partial_last_page():
    """qpos strictly inside the last mapped page: positions past the
    write frontier must not score."""
    _assert_matches_oracle(_rand_case(3, B=6, K1=1, Hq=4, Hkv=4, dh=16,
                                      P_loc=9, psz=8, ppc=3,
                                      partial_last=True))


def test_pool_much_larger_than_live():
    """num_pages >> live pages: compaction means cost scales with the
    list width, and untouched pool rows never leak into the output."""
    _assert_matches_oracle(_rand_case(4, B=3, K1=2, Hq=4, Hkv=4, dh=16,
                                      P_loc=128, psz=8, ppc=2, n_live=1))


def test_wire_epilogue_matches_quantize_partial():
    """The kernel's fused int8 epilogue implements the SAME per-token
    absmax contract as the host-side ``boundary.quantize_partial`` (the
    reference path's encoder), so ``coded_combine_partials`` decodes
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


# ---------------------------------------------------------------------------
# 3. engine: fused vs reference token identity
# ---------------------------------------------------------------------------

PREFILL_LEN = 16
MAX_SEQ = 32
NUM_SLOTS = 3
VOCAB = 256
EOS = 7

_MODELS = {}
_ENGINES = {}


def _model(codec):
    if codec not in _MODELS:
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import ShapeCell
        from repro.configs.reduced import reduced
        from repro.launch import specs as SP, train as TR
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, 1), ("data", "model"))
        hnn = "ann" if codec == "none" else "hnn"
        cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode=hnn)).replace(
            dtype=jnp.float32, codec=codec)
        cell = ShapeCell("serve_decode", MAX_SEQ, NUM_SLOTS, "decode")
        plan = SP.make_plan(cfg, cell, mesh)
        params = TR.init_sharded_params(cfg, plan, mesh,
                                        jax.random.PRNGKey(0))
        _MODELS[codec] = (cfg, mesh, params)
    return _MODELS[codec]


def _engine(codec, kernel, spec_k, async_depth):
    key = (codec, kernel, spec_k, async_depth)
    if key not in _ENGINES:
        from repro.serving import EngineConfig, ServingEngine
        cfg, mesh, params = _model(codec)
        _ENGINES[key] = ServingEngine(
            cfg, mesh, params,
            EngineConfig(num_slots=NUM_SLOTS, max_seq=MAX_SEQ,
                         prefill_len=PREFILL_LEN, page_size=8, eos_id=EOS,
                         spec_k=spec_k, async_depth=async_depth,
                         attn_kernel=kernel))
    return _ENGINES[key]


def _run_schedule(eng, schedule, seed=77):
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    reqs = [Request(rid=i, prompt=list(rng.randint(0, VOCAB, plen)),
                    max_new_tokens=mnt)
            for i, (plen, mnt) in enumerate(schedule)]
    return eng.run(reqs)


_SCHEDULE = [(16, 6), (3, 4), (9, 5), (1, 3), (12, 6)]


@pytest.mark.parametrize("codec", ["none", "spike_fused"])
@pytest.mark.parametrize("spec_k", [0, 2])
@pytest.mark.parametrize("async_depth", [0, 1])
def test_engine_fused_matches_reference(codec, spec_k, async_depth):
    """The acceptance bar: byte-identical greedy streams from the fused
    Pallas path and the reference dense-gather path, across speculative
    and pipelined variants and both codecs."""
    ref = _run_schedule(_engine(codec, "reference", spec_k, async_depth),
                        _SCHEDULE)
    fus = _run_schedule(_engine(codec, "fused", spec_k, async_depth),
                        _SCHEDULE)
    assert set(ref) == set(fus)
    for rid in ref:
        assert fus[rid] == ref[rid], (codec, spec_k, async_depth, rid)
    for eng in (_engine(codec, "reference", spec_k, async_depth),
                _engine(codec, "fused", spec_k, async_depth)):
        alloc = eng.cache.allocator
        assert alloc.pages_in_use == 0 and alloc.pages_in_limbo == 0


@pytest.mark.slow
@settings(max_examples=5, deadline=None)
@given(schedule=st.lists(
    st.tuples(st.integers(1, PREFILL_LEN), st.integers(1, 8)),
    min_size=1, max_size=6))
def test_fuzz_engine_fused_matches_reference(schedule):
    """Random schedules (queue pressure, mixed lengths, eos) through the
    sync vanilla pair — the cheapest combo, fuzzed hardest."""
    ref = _run_schedule(_engine("none", "reference", 0, 0), schedule)
    fus = _run_schedule(_engine("none", "fused", 0, 0), schedule)
    assert ref == fus


def test_engine_rejects_unknown_kernel():
    from repro.serving import EngineConfig
    from repro.serving.errors import EngineConfigError
    cfg, mesh, params = _model("none")
    from repro.serving import ServingEngine
    with pytest.raises(EngineConfigError):
        ServingEngine(cfg, mesh, params,
                      EngineConfig(num_slots=NUM_SLOTS, max_seq=MAX_SEQ,
                                   prefill_len=PREFILL_LEN, page_size=8,
                                   attn_kernel="dense"))
