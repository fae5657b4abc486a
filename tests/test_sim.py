"""NoC simulator: paper-claim ranges + structural properties."""
import math

import pytest
from _hyp import given, settings, st  # optional hypothesis (skips without)

from repro.sim.noc import NocConfig, NocSim, PAPER_MODELS, fc


def _ratios(model, **kw):
    layers = PAPER_MODELS[model]()
    reps = {m: NocSim(NocConfig(mode=m, **kw)).simulate(layers)
            for m in ("ann", "snn", "hnn")}
    a, s, h = reps["ann"], reps["snn"], reps["hnn"]
    return (a.latency_s / h.latency_s, a.total_energy / h.total_energy,
            reps)


def test_paper_baseline_ranges():
    """Fig 10/12 baseline: HNN speedup and energy gain in paper ranges."""
    for m in ("rwkv", "msresnet18", "efficientnet-b4"):
        lat, en, _ = _ratios(m)
        assert 1.0 <= lat <= 15.2, (m, lat)
        assert 0.95 <= en <= 10.0, (m, en)


def test_rwkv_smallest_margin():
    """Paper §5.3: RWKV (fewest chips) has the lowest HNN margin."""
    margins = {m: _ratios(m)[1] for m in PAPER_MODELS}
    assert margins["rwkv"] == min(margins.values())


def test_gain_grows_with_bits():
    """Fig 11: HNN speedup grows with activation bit width."""
    lats = [_ratios("msresnet18", bits=b)[0] for b in (8, 16, 32)]
    assert lats[0] < lats[1] < lats[2]


def test_sparsity_improves_latency():
    """Fig 7: more sparsity -> faster HNN inference."""
    h1 = NocSim(NocConfig(mode="hnn", spike_sparsity=0.8)).simulate(
        PAPER_MODELS["msresnet18"]())
    h2 = NocSim(NocConfig(mode="hnn", spike_sparsity=0.95)).simulate(
        PAPER_MODELS["msresnet18"]())
    assert h2.latency_s < h1.latency_s


def test_chip_scaling_claim():
    """§5.3: EfficientNet-B4 needs far more chips than RWKV/MS-ResNet."""
    chips = {m: NocSim(NocConfig(mode="hnn")).simulate(PAPER_MODELS[m]())
             .chips for m in PAPER_MODELS}
    assert chips["efficientnet-b4"] > 50 * chips["rwkv"]
    assert chips["efficientnet-b4"] > 10 * chips["msresnet18"]


@settings(max_examples=30, deadline=None)
@given(prev=st.integers(1, 4096), cur=st.integers(1, 4096))
def test_average_hops_eq4(prev, cur):
    sim = NocSim(NocConfig())
    h = sim.average_hops(prev, cur)
    assert h >= 1.0
    assert h == pytest.approx(
        abs(cur - prev) / 2.0 / NocConfig().grid + 1.0)


@settings(max_examples=20, deadline=None)
@given(n_in=st.integers(16, 4096), n_out=st.integers(16, 4096))
def test_energy_nonnegative_and_monotone_in_macs(n_in, n_out):
    cfg = NocConfig(mode="ann")
    r1 = NocSim(cfg).simulate([fc("a", n_in, n_out)])
    r2 = NocSim(cfg).simulate([fc("a", n_in, 2 * n_out)])
    assert 0 < r1.total_energy <= r2.total_energy


def test_emio_cost_from_trace_eq8():
    """The serving-trace bridge prices each step's wire bytes exactly on
    eq (8): floor(pb/nc)*cycles_ser + pb cycles, pb*e_d2d energy, with
    zero-byte and missing-field steps free."""
    from repro.sim.noc import emio_cost_from_trace

    cfg = NocConfig()
    nc = cfg.boundary_cores
    steps = [{"wire_bytes": 1000.0, "tokens": 4},
             {"wire_bytes": 0.0, "tokens": 2},
             {"tokens": 1},                       # no wire field: free
             {"wire_bytes": 7.0, "tokens": 1}]
    out = emio_cost_from_trace(steps, cfg)
    want_cycles = (math.floor(1000.0 / nc) * cfg.cycles_ser + 1000.0
                   + math.floor(7.0 / nc) * cfg.cycles_ser + 7.0)
    want_energy = (1000.0 + 7.0) * cfg.e_d2d
    assert out["steps"] == 4 and out["tokens"] == 8
    assert out["emio_cycles"] == pytest.approx(want_cycles)
    assert out["e_emio"] == pytest.approx(want_energy)
    assert out["emio_s"] == pytest.approx(want_cycles / cfg.freq_hz)
    assert out["emio_cycles_per_token"] == pytest.approx(want_cycles / 8)
    assert out["e_emio_per_token"] == pytest.approx(want_energy / 8)
    # an empty trace must not divide by zero
    empty = emio_cost_from_trace([], cfg)
    assert empty["tokens"] == 0 and empty["emio_cycles_per_token"] == 0.0


def test_emio_cost_from_trace_edge_cases():
    """Closed-form bridge corners: zero-token steps still price their
    bytes, and the per-token figures guard the zero-token
    denominator."""
    from repro.sim.noc import emio_cost_from_trace

    cfg = NocConfig()
    nc = cfg.boundary_cores
    steps = [
        {"wire_bytes": 500.0, "tokens": 0},               # drained tick
        {"wire_bytes": 300.0, "tokens": 0},
    ]
    out = emio_cost_from_trace(steps, cfg)
    assert out["tokens"] == 0
    want = (math.floor(500.0 / nc) * cfg.cycles_ser + 500.0
            + math.floor(300.0 / nc) * cfg.cycles_ser + 300.0)
    assert out["emio_cycles"] == pytest.approx(want)
    # per-token figures guard the zero-token denominator
    assert out["emio_cycles_per_token"] == pytest.approx(want)


# ---------------------------------------------------------------------------
# cycle-level trace front-end (NocSim.simulate_trace)
# ---------------------------------------------------------------------------


def _trace():
    return [
        {"kind": "decode", "tokens": 4,
         "wire_streams": {"psum": 1000.0, "head_all_gather": 500.0,
                          "partial_combine": 120.0},
         "wire_bytes": 1620.0},
        {"kind": "decode", "tokens": 4, "wire_bytes": 900.0},  # no split
        {"kind": "verify", "tokens": 0, "wire_bytes": 300.0,
         "wire_streams": {"permute": 300.0}},
        {"kind": "decode", "tokens": 2, "wire_bytes": 0.0},    # idle wire
    ]


def test_simulate_trace_exact_per_stream_pricing():
    """Each stream pays ceil(pb/nc)*cycles_ser + pb + cycles_des + hop
    fill; energy components follow §4.4 per packet."""
    from repro.sim.noc import NocSim

    cfg = NocConfig()
    nc = cfg.boundary_cores
    hops = cfg.grid / 4.0 + 1.0
    rep = NocSim(cfg).simulate_trace(_trace())
    assert len(rep.steps) == 4
    s0 = rep.steps[0]

    def cyc(pb):
        return (math.ceil(pb / nc) * cfg.cycles_ser + pb
                + cfg.cycles_des + hops)

    assert s0.cycles == pytest.approx(cyc(1000.0) + cyc(500.0)
                                      + cyc(120.0))
    tot0 = 1620.0
    assert s0.e_emio == pytest.approx(tot0 * cfg.e_d2d)
    assert s0.e_router == pytest.approx(tot0 * hops * cfg.e_hop)
    assert s0.e_pe == pytest.approx(tot0 * cfg.e_acc)
    assert s0.e_mem == pytest.approx(2.0 * tot0 * cfg.e_sram_rw)
    # a step without a stream split prices the aggregate as one stream
    assert rep.steps[1].cycles == pytest.approx(cyc(900.0))
    assert rep.steps[1].bytes_by_stream == {"total": 900.0}
    # zero-byte steps are free
    assert rep.steps[3].cycles == 0.0 and rep.steps[3].energy == 0.0
    assert rep.tokens == 10
    d = rep.to_dict()
    assert d["noc_cycles"] == pytest.approx(rep.total_cycles)
    assert d["joules_per_token"] == pytest.approx(
        rep.total_energy / 10 * 1e-12)
    assert set(d["energy_breakdown"]) == {"PE", "MEM", "Router", "EMIO"}
    assert d["wire_kb_by_stream"]["permute"] == pytest.approx(0.3)


def test_simulate_trace_bounds_closed_form():
    """Acceptance invariant: the cycle-level total is >= the closed-form
    eq (8) figure for the same trace — per-stream ceil plus deserialize
    and hop fill can only add cycles over floor-on-the-aggregate."""
    from repro.sim.noc import NocSim, emio_cost_from_trace

    for cfg in (NocConfig(), NocConfig(cores_per_chip=16),
                NocConfig(cores_per_chip=4)):
        sim = NocSim(cfg)
        for trace in (_trace(), [],
                      [{"tokens": 1, "wire_bytes": 3.0}],
                      [{"tokens": 0, "wire_streams": {"a": 1.0, "b": 1.0,
                                                      "c": 1.0}}]):
            cyc = sim.simulate_trace(trace).total_cycles
            closed = emio_cost_from_trace(trace, cfg)["emio_cycles"]
            assert cyc >= closed, (cfg.cores_per_chip, trace)


def test_simulate_trace_empty_and_streamless():
    """Edge cases: an empty trace and all-zero steps produce a valid,
    all-zero report (no division by zero in to_dict)."""
    from repro.sim.noc import NocSim

    rep = NocSim(NocConfig()).simulate_trace([])
    d = rep.to_dict()
    assert d["steps"] == 0 and d["tokens"] == 0
    assert d["noc_cycles"] == 0 and d["joules_per_token"] == 0.0
    rep2 = NocSim(NocConfig()).simulate_trace(
        [{"kind": "decode", "tokens": 0, "wire_bytes": 0.0}])
    assert rep2.total_cycles == 0.0 and rep2.total_energy == 0.0
    assert rep2.to_dict()["wire_kb_by_stream"] == {}
