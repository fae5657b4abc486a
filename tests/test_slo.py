"""Host-side SLO harness tests: trace generator determinism, monitor
math under an injectable fake clock, and the step-trace -> NoC bridge
files.

Everything here is pure host code — no engine, no jit — so the whole
file runs in milliseconds and belongs to the tier-1 fast lane.  The
engine-in-the-loop counterparts (fault identity, drain cleanliness)
live in tests/test_faults.py.
"""
import warnings

import numpy as np
import pytest

from repro.serving import (FaultPlan, PRESETS, RequestClass, SLOMonitor,
                           SLOTargets, make_trace, preset_trace, zoo_mix)
from repro.serving.slo import load_trace, percentiles


# ---------------------------------------------------------------------------
# workload traces
# ---------------------------------------------------------------------------


def test_trace_seed_determinism():
    """Same seed -> identical trace (arrivals, prompts, budgets);
    different seed -> a different stream."""
    a = preset_trace("multitenant", 4.0, seed=7)
    b = preset_trace("multitenant", 4.0, seed=7)
    c = preset_trace("multitenant", 4.0, seed=8)
    assert a.requests == b.requests
    assert len(a) > 0
    assert a.requests != c.requests


def test_trace_sorted_and_budget_clamped():
    tr = preset_trace("longtail", 6.0, seed=1, prefill_len=12, max_gen=5)
    times = [r.t for r in tr.requests]
    assert times == sorted(times)
    for r in tr.requests:
        assert 0.0 <= r.t < tr.horizon_s
        assert 1 <= len(r.req.prompt) <= 12
        assert 1 <= r.req.max_new_tokens <= 5
        assert r.req.rid.split("/")[1] == r.cls


def test_trace_class_independence():
    """Adding a tenant never perturbs the existing tenants' streams
    (each class draws from its own derived seed)."""
    base = zoo_mix()
    small = make_trace(base[:2], 4.0, seed=3)
    full = make_trace(base, 4.0, seed=3)
    keep = {c.name for c in base[:2]}
    assert [r for r in full.requests if r.cls in keep] == list(small.requests)


def test_trace_fixed_prompt_len():
    tr = preset_trace("steady", 2.0, seed=0, fixed_prompt_len=9)
    assert tr.requests and all(len(r.req.prompt) == 9 for r in tr.requests)


def test_trace_validation_errors():
    with pytest.raises(ValueError):
        preset_trace("no-such-preset", 1.0)
    with pytest.raises(ValueError):
        RequestClass("bad", rate=0.0)
    with pytest.raises(ValueError):
        RequestClass("bad", rate=1.0, arrival="uniform")
    with pytest.raises(ValueError):
        RequestClass("bad", rate=1.0, prompt_len=(5, 2))
    with pytest.raises(ValueError):
        make_trace([], 1.0)


def test_presets_all_produce_arrivals():
    for name in PRESETS:
        assert len(preset_trace(name, 4.0, seed=0, load=8.0)) > 0, name


def test_lowmatch_preset_prompts_have_distinct_tokens():
    """Every lowmatch prompt is drawn without replacement: no repeated
    token means no n-gram for prompt-lookup drafting to match, which is
    the workload the learned-drafter bench compares on."""
    tr = preset_trace("lowmatch", 4.0, seed=0, prefill_len=16, max_gen=8,
                      load=8.0)
    assert len(tr) > 0
    for r in tr.requests:
        assert len(set(r.req.prompt)) == len(r.req.prompt)
    # and the prompt length still clamps to the vocab when oversized
    big = make_trace([RequestClass("lm", rate=8.0, prompt_len=(40, 40),
                                   distinct_tokens=True)],
                     2.0, seed=0, vocab=32)
    assert big.requests
    for r in big.requests:
        assert len(r.req.prompt) == 32
        assert len(set(r.req.prompt)) == 32


# ---------------------------------------------------------------------------
# monitor math (fake clock, stub engine)
# ---------------------------------------------------------------------------


class _Clock:
    """Injectable monotonic clock: ``clk.t = ...`` then the monitor
    reads exactly that."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _StubAlloc:
    pages_in_use = 3
    pages_in_limbo = 1


class _StubCache:
    allocator = _StubAlloc()


class _StubEngine:
    spec_k = 0
    cache = _StubCache()

    def __init__(self):
        self.tokens_generated = 0
        self.decode_steps = 0
        self.queue_depth = 0
        self.num_active = 1


def test_percentiles_empty_and_known():
    assert percentiles([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0,
                               "mean": 0.0, "n": 0}
    p = percentiles(range(1, 101))
    assert p["n"] == 100 and p["mean"] == 50.5
    assert p["p50"] == pytest.approx(50.5)
    assert p["p99"] == pytest.approx(np.percentile(range(1, 101), 99))


def test_monitor_ttft_tpot_attainment_math():
    """Hand-driven lifecycle on a fake clock: TTFT/TPOT come out exact,
    and attainment judges each request against the targets."""
    clk = _Clock()
    mon = SLOMonitor(targets=SLOTargets(ttft_ms=100.0, tpot_ms=10.0),
                     clock=clk)
    # r0: TTFT 50ms (ok), 5 tokens over 20ms -> TPOT 5ms (ok)
    mon.on_submit("r0", 8)
    clk.t = 0.050
    mon.on_first_token("r0")
    clk.t = 0.070
    mon.on_finish("r0", 5)
    # r1: TTFT 200ms (violates), 3 tokens at 4ms/tok (ok)
    clk.t = 0.0
    mon.on_submit("r1", 4)
    clk.t = 0.200
    mon.on_first_token("r1")
    clk.t = 0.208
    mon.on_finish("r1", 3)
    rep = mon.report()
    assert rep["requests"] == {"submitted": 2, "finished": 2, "restarts": 0}
    assert rep["ttft_ms"]["p50"] == pytest.approx(125.0)
    assert rep["ttft_ms"]["mean"] == pytest.approx(125.0)
    assert rep["tpot_ms"]["n"] == 2
    assert rep["tpot_ms"]["mean"] == pytest.approx((5.0 + 4.0) / 2)
    slo = rep["slo"]
    assert slo["ttft_attainment"] == 0.5
    assert slo["tpot_attainment"] == 1.0
    assert slo["attainment"] == 0.5


def test_monitor_restart_keeps_original_submit_clock():
    """A preempted request restarts from scratch but its TTFT keeps
    measuring from the ORIGINAL submit — the re-queue penalty is the
    SLO story."""
    clk = _Clock()
    mon = SLOMonitor(clock=clk)
    mon.on_submit("r0", 8)
    clk.t = 0.010
    mon.on_first_token("r0")
    clk.t = 0.020
    mon.on_preempt("r0", "pool_pressure")
    mon.on_submit("r0", 8)             # engine re-admits from the queue
    clk.t = 0.300
    mon.on_first_token("r0")
    clk.t = 0.350
    mon.on_finish("r0", 4)
    rep = mon.report()
    assert rep["requests"]["restarts"] == 2   # preempt + resubmit
    assert rep["faults"]["preemptions"] == 1
    assert rep["ttft_ms"]["mean"] == pytest.approx(300.0)


def test_monitor_suspend_resets_inflight_records():
    clk = _Clock()
    mon = SLOMonitor(clock=clk)
    mon.on_submit("a", 4)
    mon.on_submit("b", 4)
    clk.t = 0.010
    mon.on_first_token("a")
    mon.on_suspend(["a"])              # b was still queued: untouched
    assert mon.suspends == 1
    assert mon.requests["a"].t_first is None
    assert mon.requests["a"].restarts == 1
    assert mon.requests["b"].restarts == 0
    clk.t = 0.050
    mon.on_first_token("a")            # re-measures after the restart
    assert mon.requests["a"].t_first == pytest.approx(0.050)


def test_monitor_step_trace_and_wire_bytes():
    """on_step snapshots queue/pool state and prices wire bytes per
    DEVICE step (a tick that commits two async steps carries 2x)."""
    clk = _Clock()
    eng = _StubEngine()
    mon = SLOMonitor(wire_bytes_per_step={"decode": 100.0}, clock=clk)
    eng.decode_steps, eng.tokens_generated, eng.queue_depth = 1, 3, 5
    mon.on_step(eng)
    clk.t = 0.001
    eng.decode_steps, eng.tokens_generated = 3, 9   # 2 steps this tick
    mon.on_step(eng)
    trace = mon.step_trace()
    assert [s["wire_bytes"] for s in trace] == [100.0, 200.0]
    assert [s["tokens"] for s in trace] == [3, 6]
    assert trace[1]["dt_us"] == pytest.approx(1000.0)
    assert trace[0]["queue_depth"] == 5
    assert trace[0]["pages_in_use"] == 3
    rep = mon.report()
    assert rep["queue_depth"]["max"] == 5
    assert rep["pool"]["peak_pages_in_limbo"] == 1


def test_monitor_wire_streams_split_and_scaling():
    """A registered stream profile lands a per-collective breakdown in
    every StepEvent, scaled per DEVICE step, and always summing to the
    scalar wire_bytes."""
    clk = _Clock()
    eng = _StubEngine()
    mon = SLOMonitor(clock=clk, wire_streams_per_step={
        "decode": {"psum": 60.0, "head_all_gather": 40.0}})
    # scalar derived from the stream sums, no separate registration
    assert mon.wire_bytes_per_step == {"decode": 100.0}
    eng.decode_steps, eng.tokens_generated = 1, 2
    mon.on_step(eng)
    clk.t = 0.001
    eng.decode_steps, eng.tokens_generated = 3, 6   # 2 steps this tick
    mon.on_step(eng)
    trace = mon.step_trace()
    assert trace[0]["wire_streams"] == {"psum": 60.0,
                                        "head_all_gather": 40.0}
    assert trace[1]["wire_streams"] == {"psum": 120.0,
                                        "head_all_gather": 80.0}
    for s in trace:
        assert sum(s["wire_streams"].values()) == pytest.approx(
            s["wire_bytes"])


def test_monitor_scalar_only_falls_back_to_total_stream():
    """Callers without a stream profile still get a priceable trace:
    the scalar is recorded as one 'total' stream."""
    clk = _Clock()
    eng = _StubEngine()
    mon = SLOMonitor(wire_bytes_per_step={"decode": 64.0}, clock=clk)
    eng.decode_steps = 1
    mon.on_step(eng)
    assert mon.step_trace()[0]["wire_streams"] == {"total": 64.0}


def test_monitor_warns_on_unknown_step_kind():
    """Bug regression: an incomplete pricing table used to silently
    record 0 wire bytes for unregistered step kinds.  Now a mixed-kind
    trace warns once per unknown kind (and never for registered ones or
    when no pricing was registered at all)."""

    class _SpecEngine(_StubEngine):
        spec_k = 2                       # ticks are kind="verify"

    clk = _Clock()
    eng = _SpecEngine()
    # "verify" missing from the registered table -> warn
    mon = SLOMonitor(wire_bytes_per_step={"decode": 100.0}, clock=clk)
    eng.decode_steps = 1
    with pytest.warns(RuntimeWarning, match="verify"):
        mon.on_step(eng)
    # ...but only once per kind
    clk.t = 0.001
    eng.decode_steps = 2
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mon.on_step(eng)
    # a registered kind never warns
    mon2 = SLOMonitor(wire_bytes_per_step={"verify": 10.0}, clock=_Clock())
    eng2 = _SpecEngine()
    eng2.decode_steps = 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mon2.on_step(eng2)
    # an unpriced monitor (no table at all) stays silent too
    mon3 = SLOMonitor(clock=_Clock())
    eng3 = _SpecEngine()
    eng3.decode_steps = 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        mon3.on_step(eng3)


def test_monitor_report_appends_no_step():
    mon = SLOMonitor(clock=_Clock())
    eng = _StubEngine()
    mon.on_step(eng)
    assert len(mon.step_trace()) == 1
    mon.report()
    assert len(mon.step_trace()) == 1


def test_monitor_acceptance_math():
    """Accepted-draft length is the per-tick delta of the engine's
    commit/verify counters; the report's rate strips the always-kept
    correction token and normalises by spec_k."""

    class _SpecEngine(_StubEngine):
        spec_k = 2

        def __init__(self):
            super().__init__()
            self.spec_commits = 0
            self.spec_verifies = 0

    clk = _Clock()
    eng = _SpecEngine()
    mon = SLOMonitor(clock=clk)
    # tick 1: 3 verifies committed 6 tokens -> accepted_len 2.0
    eng.spec_commits, eng.spec_verifies = 6, 3
    mon.on_step(eng)
    # tick 2: +2 verifies, +6 tokens -> accepted_len 3.0
    clk.t = 0.001
    eng.spec_commits, eng.spec_verifies = 12, 5
    mon.on_step(eng)
    # tick 3: no verify participation -> not a speculative tick
    clk.t = 0.002
    mon.on_step(eng)
    assert [s["accepted_len"] for s in mon.step_trace()] == [2.0, 3.0, 0.0]
    acc = mon.report()["acceptance"]
    assert acc["accepted_len"]["n"] == 2
    assert acc["accepted_len"]["mean"] == pytest.approx(2.5)
    # mean accepted 2.5 = 1 correction + 1.5 of the 2 drafts kept
    assert acc["rate"] == pytest.approx(0.75)


def test_monitor_acceptance_zero_on_nonspec_runs():
    """A non-speculative engine (and host-side stubs without the spec
    counters at all) reports an all-zero acceptance block."""
    mon = SLOMonitor(clock=_Clock())
    mon.on_step(_StubEngine())
    acc = mon.report()["acceptance"]
    assert acc["rate"] == 0.0
    assert acc["accepted_len"]["n"] == 0


def test_write_trace_roundtrip(tmp_path):
    clk = _Clock()
    mon = SLOMonitor(wire_bytes_per_step={"decode": 64.0}, clock=clk)
    eng = _StubEngine()
    for i in range(3):
        clk.t = i * 0.002
        eng.decode_steps, eng.tokens_generated = i + 1, (i + 1) * 2
        mon.on_step(eng)
    path = tmp_path / "steps.jsonl"
    mon.write_trace(str(path))
    back = load_trace(str(path))
    assert back == mon.step_trace()


# ---------------------------------------------------------------------------
# fault plan validation
# ---------------------------------------------------------------------------


def test_fault_plan_probability_sum_validated():
    FaultPlan(p_preempt=0.5, p_replica_loss=0.3, p_suspend=0.2)
    with pytest.raises(ValueError):
        FaultPlan(p_preempt=0.6, p_replica_loss=0.3, p_suspend=0.2)
