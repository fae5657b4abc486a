"""Fault-injection fuzz: greedy token-identity through every
graceful-degradation path.

The house rule the SLO harness is built on: a preempted / suspended /
replica-lost request restarts from scratch on re-admit, and under
greedy sampling the restarted stream is bit-identical to an
uninterrupted run — per-slot streams are batch-independent and greedy
ignores the PRNG key — so faults may only ever cost latency, never
change tokens.  These tests inject faults across the engine matrix
(``spec_k`` 0/2 x ``async_depth`` 0/1), force pool-pressure preemption
with a deliberately undersized page pool, and suspend/resume
mid-schedule, asserting every rid's output equals the fault-free
reference and that every engine drains slot-, page- and limbo-clean.

Engines are compiled once per (spec_k, async_depth, num_pages) cell and
reused across schedules — a drained engine is a clean engine, and that
reuse is itself part of the property.
"""
import numpy as np
import pytest

from _hyp import given, settings, st

PREFILL_LEN = 16
MAX_SEQ = 32
NUM_SLOTS = 3
VOCAB = 256
EOS = 7

_ENGINES = {}
_MODEL = None
_REF = None


def _model():
    global _MODEL
    if _MODEL is None:
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import ShapeCell
        from repro.configs.reduced import reduced
        from repro.launch import specs as SP, train as TR
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1, 1), ("data", "model"))
        cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode="ann")).replace(
            dtype=jnp.float32, codec="none")
        cell = ShapeCell("serve_decode", MAX_SEQ, NUM_SLOTS, "decode")
        plan = SP.make_plan(cfg, cell, mesh)
        params = TR.init_sharded_params(cfg, plan, mesh,
                                        jax.random.PRNGKey(0))
        _MODEL = (cfg, mesh, params)
    return _MODEL


def _engine(spec_k=0, async_depth=0, num_pages=0):
    key = (spec_k, async_depth, num_pages)
    if key not in _ENGINES:
        from repro.serving import EngineConfig, ServingEngine
        cfg, mesh, params = _model()
        _ENGINES[key] = ServingEngine(cfg, mesh, params, EngineConfig(
            num_slots=NUM_SLOTS, max_seq=MAX_SEQ, prefill_len=PREFILL_LEN,
            page_size=8, eos_id=EOS, spec_k=spec_k,
            async_depth=async_depth, num_pages=num_pages))
    return _ENGINES[key]


def _reqs(schedule, seed=1234):
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=list(rng.randint(0, VOCAB, plen)),
                    max_new_tokens=mnt)
            for i, (plen, mnt) in enumerate(schedule)]


def _clone(r):
    from repro.serving import Request
    return Request(rid=r.rid, prompt=r.prompt,
                   max_new_tokens=r.max_new_tokens)


SCHEDULE = [(16, 6), (3, 1), (16, 8), (1, 4), (9, 8), (16, 2), (5, 5)]


def _reference(schedule=None):
    """Fault-free outputs of SCHEDULE on the plain engine (cached)."""
    global _REF
    if schedule is not None:
        eng = _engine()
        res = eng.run([_clone(r) for r in _reqs(schedule)])
        _assert_drained(eng)
        return res
    if _REF is None:
        _REF = _reference(SCHEDULE)
    return _REF


def _assert_drained(engine):
    alloc = engine.cache.allocator
    assert engine.idle
    assert not engine._inflight, "uncommitted dispatched step"
    assert alloc._dispatched == alloc._committed, "unbalanced epochs"
    assert alloc.num_free == NUM_SLOTS, "slot leak"
    assert alloc.pages_in_use == 0, "page leak"
    assert alloc.pages_in_limbo == 0, "page stuck in deferred-free limbo"
    assert (alloc._len == 0).all(), "stale occupancy"
    assert (alloc.block_table == -1).all(), "stale block-table mapping"


def _run_with_injector(engine, reqs, plan, max_steps=2000):
    """Serve ``reqs`` with a ``FaultInjector`` striking between ticks;
    returns ({rid: tokens}, injector)."""
    from repro.serving import FaultInjector
    inj = FaultInjector(plan)
    for r in reqs:
        engine.submit(_clone(r))
    results = {}
    for _ in range(max_steps):
        for req, out in engine.step():
            results[req.rid] = out
        inj.on_step(engine)
        if engine.idle:
            break
    assert engine.idle, "fault run did not drain"
    return results, inj


# ---------------------------------------------------------------------------
# acceptance criterion: injected-fault identity over the engine matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec_k,async_depth",
                         [(0, 0), (2, 0), (0, 1), (2, 1)])
def test_injected_faults_token_identity(spec_k, async_depth):
    """Preempt + replica-loss + suspend faults injected on a seeded
    schedule: every rid's greedy stream equals the fault-free reference,
    for all four (spec_k, async_depth) engine cells, and the engine
    drains clean."""
    from repro.serving import FaultPlan
    ref = _reference()
    eng = _engine(spec_k=spec_k, async_depth=async_depth)
    res, inj = _run_with_injector(
        eng, _reqs(SCHEDULE),
        FaultPlan(seed=3, p_preempt=0.15, p_replica_loss=0.1,
                  p_suspend=0.05, max_faults=6))
    assert inj.total_injected > 0, "fault plan never struck"
    assert res == ref, (spec_k, async_depth, inj.injected)
    assert eng.preemptions + eng.suspends >= inj.total_injected
    _assert_drained(eng)
    eng.reset_stats()


def test_pool_pressure_preemption_token_identity():
    """A pool sized below the schedule's concurrent demand forces
    evict + re-queue mid-decode (engine.preemptions > 0); outputs stay
    bit-identical to the roomy-pool reference, sync and async."""
    ref = _reference()
    for depth in (0, 1):
        eng = _engine(async_depth=depth, num_pages=5)
        res = eng.run([_clone(r) for r in _reqs(SCHEDULE)])
        assert eng.preemptions > 0, f"tight pool never preempted (d={depth})"
        assert res == ref, (depth, eng.preemptions)
        _assert_drained(eng)
        eng.reset_stats()


def test_pool_pressure_preemption_spec_token_identity():
    """Same tight pool through the speculative scheduler: verify-step
    ensure failures preempt too, and greedy spec acceptance keeps the
    streams identical."""
    ref = _reference()
    eng = _engine(spec_k=2, num_pages=5)
    res = eng.run([_clone(r) for r in _reqs(SCHEDULE)])
    assert eng.preemptions > 0
    assert res == ref
    _assert_drained(eng)
    eng.reset_stats()


def test_suspend_resume_token_identity():
    """Drain + snapshot + resume mid-schedule: the snapshot releases
    every slot and page, resumed requests restart from scratch, and the
    final outputs equal an uninterrupted run."""
    ref = _reference()
    eng = _engine()
    for r in _reqs(SCHEDULE):
        eng.submit(_clone(r))
    results = {}
    for _ in range(4):
        for req, out in eng.step():
            results[req.rid] = out
    snap = eng.suspend()
    assert eng.num_active == 0
    assert eng.cache.allocator.pages_in_use == 0
    assert eng.cache.allocator.pages_in_limbo == 0
    assert snap, "nothing was in flight at the suspend point"
    eng.resume(snap)
    for _ in range(2000):
        for req, out in eng.step():
            results[req.rid] = out
        if eng.idle:
            break
    assert results == ref
    assert eng.suspends == 1
    _assert_drained(eng)
    eng.reset_stats()


def test_suspend_preserves_committed_work():
    """``suspend()`` is work-preserving (the PR-8 bugfix): a
    mid-generation slot's committed tokens ride the snapshot as a
    ``_Resume`` entry and re-admission prefills ``prompt + committed``
    instead of regenerating token by token.  Outputs stay greedy-
    identical to an uninterrupted run AND ``tokens_generated`` equals
    the total delivered — the restart-from-scratch engine regenerated
    the pre-suspend tokens, so this count is exactly what the fix
    stops wasting."""
    from repro.serving.engine import _Resume
    # prompts short enough that prompt + committed always fits the
    # prefill window: every active slot must snapshot work-preserving
    schedule = [(6, 10), (4, 8), (5, 9), (6, 7)]
    eng = _engine()
    ref = _reference(schedule)
    eng.reset_stats()
    for r in _reqs(schedule):
        eng.submit(_clone(r))
    results = {}
    for _ in range(5):
        for req, out in eng.step():
            results[req.rid] = out
    snap = eng.suspend()
    resumed = [e for e in snap if isinstance(e, _Resume)]
    assert resumed, "no mid-generation slot carried committed work"
    assert all(isinstance(e, _Resume) for e in snap
               if getattr(e, "prior", None) is not None)
    preserved = sum(len(e.prior) for e in resumed)
    assert preserved > 0
    eng.resume(snap)
    for _ in range(2000):
        for req, out in eng.step():
            results[req.rid] = out
        if eng.idle:
            break
    assert results == ref
    # every token was generated exactly once across the suspension
    assert eng.tokens_generated == sum(len(v) for v in ref.values())
    assert eng.suspends == 1
    _assert_drained(eng)
    eng.reset_stats()


def test_limbo_blind_admission_regression():
    """Regression for the limbo-blind admission bug (PR-8): the old
    ``can_admit`` checked the free list alone, so an admit could claim
    the last fresh pages while the deferred-free limbo still owed pages
    to the pipeline — the very next ``ensure`` starved mid-flight.  On
    this exact trace the pre-fix engine raises ``PagePoolExhausted``
    with ``preempt=False`` (and burns a pipeline-drain bubble on the
    rescue path otherwise); the limbo-aware gate defers the admission
    one tick and the run completes preemption-free with identical
    tokens."""
    from repro.serving import (EngineConfig, Request, ServingEngine,
                               SlotAllocator)
    cfg, mesh, params = _model()
    rng = np.random.RandomState(0)
    A = Request(rid=0, prompt=list(rng.randint(0, 64, 6)),
                max_new_tokens=6)
    B = Request(rid=1, prompt=list(rng.randint(0, 64, 4)),
                max_new_tokens=2)
    C = Request(rid=2, prompt=list(rng.randint(0, 64, 6)),
                max_new_tokens=2)
    kw = dict(num_slots=3, max_seq=24, prefill_len=8, page_size=8)

    def drive(ecfg):
        e = ServingEngine(cfg, mesh, params, ecfg)
        e.submit(_clone(A)); e.submit(_clone(B))
        res = {}
        for _ in range(2):               # B retires at tick 2's commit:
            for r, o in e.step():        # its page parks in limbo while
                res[r.rid] = o           # tick 2's step is in flight
        e.submit(_clone(C))              # 1 fresh page left + 1 in limbo
        for _ in range(60):
            for r, o in e.step():
                res[r.rid] = o
            if e.idle:
                break
        assert e.idle
        return res, e

    ref, _ = drive(EngineConfig(**kw, num_pages=9))      # roomy pool
    # tight pool, pipelined, no preemption rescue: pre-fix this raised
    # PagePoolExhausted at tick 3 (C admitted against the limbo page)
    res, eng = drive(EngineConfig(**kw, num_pages=3, async_depth=1,
                                  preempt=False))
    assert res == ref
    assert eng.preemptions == 0
    # allocator-level statement of the same fix: limbo pages never
    # count toward admission (pre-fix can_admit(24) was True here)
    a = SlotAllocator(num_slots=2, max_seq=32, page_size=8, num_pages=4)
    s = a.alloc(8)
    a.note_dispatch()                    # a step is in flight...
    a.free(s)                            # ...so this page parks in limbo
    assert a.pages_in_limbo == 1
    assert not a.can_admit(24)           # 3 free pages, 1 owed: refuse
    assert a.can_admit(16)               # 2 pages genuinely available
    assert a.can_admit(24, after_flush=True)   # the drain counterfactual
    a.note_commit()
    assert a.can_admit(24)               # limbo drained: fresh again


def test_preempt_slot_on_free_slot_is_typed():
    eng = _engine()
    with pytest.raises(ValueError):
        eng.preempt_slot(0)


def test_preempt_disabled_pool_exhaustion_propagates():
    """``preempt=False`` restores the raw typed error: the same tight
    pool that silently degrades by default now raises
    ``PagePoolExhausted`` mid-flight."""
    from repro.serving import (EngineConfig, PagePoolExhausted, Request,
                               ServingEngine)
    cfg, mesh, params = _model()
    eng = ServingEngine(cfg, mesh, params, EngineConfig(
        num_slots=NUM_SLOTS, max_seq=MAX_SEQ, prefill_len=PREFILL_LEN,
        page_size=8, eos_id=EOS, num_pages=5, preempt=False))
    rng = np.random.RandomState(0)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=list(rng.randint(0, VOCAB, 16)),
                           max_new_tokens=12))
    with pytest.raises(PagePoolExhausted):
        for _ in range(100):
            eng.step()


def test_results_retired_by_a_fault_are_handed_out():
    """A fault's flush between ticks can retire the last request after
    its final ``step()``: the engine is not idle until a later ``step()``
    has handed that result out, so a drive loop that stops at ``idle``
    loses nothing (and the next run does not receive it).  The fault
    fuzz found this schedule and seed."""
    from repro.serving import FaultPlan
    schedule = [(1, 4)]
    ref = _reference(schedule)
    eng = _engine(spec_k=0, async_depth=1)
    res, _ = _run_with_injector(
        eng, _reqs(schedule),
        FaultPlan(seed=62883, p_preempt=0.1, p_replica_loss=0.08,
                  p_suspend=0.05, max_faults=8))
    assert res == ref
    _assert_drained(eng)
    assert eng.step() == []


# ---------------------------------------------------------------------------
# hypothesis fuzz (skips cleanly when hypothesis is not installed)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(st.lists(st.tuples(st.integers(1, PREFILL_LEN), st.integers(1, 8)),
                min_size=1, max_size=2 * NUM_SLOTS + 1),
       st.integers(0, 1 << 16),
       st.sampled_from([(0, 0), (2, 0), (0, 1), (2, 1)]))
def test_fuzz_fault_schedules_token_identity(schedule, fault_seed, cell):
    """Random schedules x random fault seeds x the engine matrix: greedy
    outputs always equal the fault-free run of the same schedule, and
    every engine drains clean."""
    from repro.serving import FaultPlan
    spec_k, async_depth = cell
    ref = _reference(schedule)
    eng = _engine(spec_k=spec_k, async_depth=async_depth)
    res, _ = _run_with_injector(
        eng, _reqs(schedule),
        FaultPlan(seed=fault_seed, p_preempt=0.1, p_replica_loss=0.08,
                  p_suspend=0.05, max_faults=8))
    assert res == ref, (cell, fault_seed)
    _assert_drained(eng)
    eng.reset_stats()
