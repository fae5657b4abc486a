"""NoC simulation framework — faithful re-implementation of the paper's
contribution (3): latency/throughput/energy for ANN, SNN, and HNN
mappings on the 2-D mesh NoC accelerator (paper §3-4).

Architecture constants follow Tables 1-3:
  * 8x8 core grid per chip; HNN: 28 boundary spiking + 36 interior
    artificial cores; ANN: 64 artificial; SNN: 64 spiking.
  * 200 MHz NoC, 65 nm, 1.0 V; 256 neurons/axons per core.
  * EMIO: 8-to-1 mux, 38-cycle serialization; 76-cycle die-to-die packet
    latency with pipelined deserialization (eq 8).
  * X-Y routing with directional-X mapping (eqs 4-5).
  * latency eqs (6), (7), (9); ORION-2.0-style energy scaled to the
    65 nm / 200 MHz / 1.0 V point; SNN ACC ~ 0.06x MAC energy; die-to-die
    packet ~ 10x MAC, 224x core-to-core hop (paper §4.4).

The model mapper consumes layer shapes (neurons in/out, MACs) — either
hand-specified or derived from a ``repro.configs`` ModelConfig — and
produces per-component latency/energy, reproducing Figs 10-13.

Two serving-trace front-ends bridge the SLO harness into this model:

``NocSim.simulate_trace(steps)``
    Cycle-level: maps each step's per-collective packet streams (the
    ``wire_streams`` breakdown an ``SLOMonitor`` records when the
    engine's ``wire_stream_profile()`` is registered) onto the
    boundary serdes ports and router hops individually — each
    collective pays its own eq (8) serialization (ceil over the ``nc``
    peripheral ports: dependent collectives cannot pack partial serdes
    batches), pipelined deserialization, and hop fill, and contributes
    PE/MEM/Router/EMIO energy per §4.4.  Returns per-step and total
    cycles + an energy breakdown (``TraceReport.to_dict()``).

``emio_cost_from_trace(steps)``
    Closed-form cross-check: prices the aggregate ``wire_bytes`` scalar
    with eq (8) directly (floor over the aggregate).  The cycle-level
    total is guaranteed to bound it from above —
    ``sum(ceil(pb_i/nc)) >= floor(sum(pb_i)/nc)`` plus the
    deserialize/hop terms — which tests/test_sim.py asserts.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence


@dataclasses.dataclass(frozen=True)
class NocConfig:
    cores_per_chip: int = 64       # 8x8 grid (Tab 1); Fig 11/13 sweep 8-64
    neurons_per_core: int = 256    # grouping G
    freq_hz: float = 200e6
    bits: int = 8                  # activation precision
    T: int = 8                     # rate-code tick window (paper: T=8)
    spike_sparsity: float = 0.9    # 90% sparsity (10% activity, §4.2)
    mode: str = "hnn"              # ann | snn | hnn
    # energy constants (normalized to one 8-bit MAC at 65nm ~ 1.0 pJ
    # baseline, paper §4.4 scalings)
    e_mac: float = 1.0
    e_acc: float = 0.20            # SNN accumulate (+scheduler/membrane
                                   # upkeep; Dampfhoffer et al. [6] range)
    e_sram_rw: float = 0.15        # per-operand SRAM access (scaled /bit)
    e_hop: float = 0.045           # router hop, core-to-core per packet
    e_d2d_factor: float = 224.0    # die-to-die = 224x core-to-core hop
    cycles_ser: int = 38           # EMIO serialization (eq 8)
    cycles_des: int = 38

    @property
    def grid(self) -> int:
        return max(2, int(math.sqrt(self.cores_per_chip)))

    @property
    def boundary_cores(self) -> int:
        # peripheral ring (28 of 64 at 8x8, paper Tab 1); small chips are
        # all-boundary
        g = self.grid
        ring = 4 * g - 4
        return min(self.cores_per_chip, max(ring, 1))

    @property
    def e_d2d(self) -> float:
        return self.e_hop * self.e_d2d_factor


@dataclasses.dataclass(frozen=True)
class Layer:
    """One mapped layer: dense (fc) or conv already flattened to MACs."""

    name: str
    n_in: int
    n_out: int
    macs: int                      # MAC count for a dense ANN layer
    kind: str = "fc"               # fc | conv | dwconv | pool


def fc(name, n_in, n_out):
    return Layer(name, n_in, n_out, n_in * n_out, "fc")


def conv(name, cin, cout, k, h, w):
    return Layer(name, cin * h * w, cout * h * w,
                 cout * h * w * cin * k * k, "conv")


@dataclasses.dataclass
class LayerReport:
    name: str
    cores: int
    cycles_compute: float
    cycles_emio: float
    local_packets: float
    routed_packets: float
    boundary_packets: float
    e_pe: float
    e_mem: float
    e_router: float
    e_emio: float

    @property
    def cycles(self):
        return self.cycles_compute + self.cycles_emio

    @property
    def energy(self):
        return self.e_pe + self.e_mem + self.e_router + self.e_emio


@dataclasses.dataclass
class SimReport:
    layers: List[LayerReport]
    cfg: NocConfig

    @property
    def total_cycles(self):
        return sum(l.cycles for l in self.layers)

    @property
    def latency_s(self):
        return self.total_cycles / self.cfg.freq_hz

    @property
    def total_energy(self):
        return sum(l.energy for l in self.layers)

    @property
    def chips(self):
        total_cores = sum(l.cores for l in self.layers)
        return max(1, math.ceil(total_cores / self.cfg.cores_per_chip))

    def breakdown(self):
        return {
            "PE": sum(l.e_pe for l in self.layers),
            "MEM": sum(l.e_mem for l in self.layers),
            "Router": sum(l.e_router for l in self.layers),
            "EMIO": sum(l.e_emio for l in self.layers),
        }


class NocSim:
    """Layer-accurate ANN/SNN/HNN simulator (paper §4.2-4.4)."""

    def __init__(self, cfg: NocConfig):
        self.cfg = cfg

    # -- eq (4): average hops between layer midpoints (directional-X map)
    def average_hops(self, cores_prev: int, cores_cur: int) -> float:
        m_prev = cores_prev / 2.0 / self.cfg.grid
        m_cur = cores_cur / 2.0 / self.cfg.grid
        return abs(m_cur - m_prev) + 1.0

    def _spiking_layer(self, idx: int, n_layers: int) -> bool:
        m = self.cfg.mode
        if m == "ann":
            return False
        if m == "snn":
            return True
        # hnn: spiking only where the partition crosses a chip boundary;
        # layers are packed chips-worth of cores at a time, so the layers
        # whose core allocation crosses a chip edge spike (approximated
        # as: every layer that starts a new chip — see _map()).
        return True  # decided per-layer in simulate() for hnn

    # ------------------------------------------------------------------
    def simulate(self, layers: Sequence[Layer], timesteps=None) -> SimReport:
        cfg = self.cfg
        T = timesteps or cfg.T
        act = 1.0 - cfg.spike_sparsity          # firing activity
        reports = []
        cores_prev = cfg.cores_per_chip
        core_budget = 0                          # cores used on this chip

        for i, L in enumerate(layers):
            cores = max(1, math.ceil(L.n_out / cfg.neurons_per_core))
            crosses_chip = (core_budget + cores) > cfg.cores_per_chip
            if crosses_chip:
                core_budget = (core_budget + cores) % cfg.cores_per_chip
            else:
                core_budget += cores

            # --- compute domain ------------------------------------
            # SNN: every core spikes (ACC PEs, eq 7).  ANN: dense MACs
            # (eq 6).  HNN: layers mapped across a die boundary run on
            # the peripheral spiking cores (SNN compute + spike wire,
            # §5.3 "computational cost reduction inherent in SNN
            # layers"); interior layers stay dense ANN.
            G = cfg.neurons_per_core
            spiking = (cfg.mode == "snn") or (cfg.mode == "hnn"
                                              and crosses_chip)
            if spiking:
                ops = L.macs * T * act
                cyc_compute = ops / (G * math.ceil(L.n_out / G))
                e_pe = ops * cfg.e_acc
                mem_scale = 0.5                  # 8b weights + potentials
                dense_flits = T * act            # spike packets on-chip too
                wire_flits = T * act
            else:
                ops = L.macs
                # Tab 2 PE is an 8bx8b MAC: wider data is multi-cycle
                # (latency x bits/8); switching energy per completed MAC
                # is dominated by the array + SRAM and stays ~flat
                cyc_compute = ops * (cfg.bits / 8.0) \
                    / (G * math.ceil(L.n_out / G))
                e_pe = ops * cfg.e_mac
                mem_scale = 1.0
                dense_flits = cfg.bits / 8.0     # 8-b payload flits (Tab 3)
                wire_flits = cfg.bits / 8.0

            # on-chip packets (eqs 4-5): "local packets" are the copies
            # received through each destination core's local port — every
            # core computing this layer needs every input activation, so
            # the fan-out multiplies the traffic (this is what makes
            # Router/EMIO grow superlinearly with model size, §4.4)
            # fc: every core needs every input; conv: operand streams
            # bounded by macs/G per core (weight-stationary reuse)
            fanout = min(L.n_in * cores, L.macs / G)
            local_packets = fanout * dense_flits
            hops = self.average_hops(cores_prev, cores)
            routed = hops * local_packets
            e_router = routed * cfg.e_hop
            e_mem = ops * cfg.e_sram_rw * mem_scale * (cfg.bits / 8.0)

            cyc_emio = 0.0
            e_emio = 0.0
            boundary_packets = 0.0
            if crosses_chip:
                # one serdes copy per far-side chip the layer spans
                far_chips = max(1, cores // cfg.cores_per_chip)
                pb = min(L.n_in * far_chips, L.macs / G) * wire_flits
                nc = min(cores, cfg.boundary_cores)
                # eq (8): parallel serialization over peripheral ports,
                # pipelined deserialization
                cyc_emio = (math.floor(pb / nc) * cfg.cycles_ser
                            + pb * 1.0)
                e_emio = pb * cfg.e_d2d
                boundary_packets = pb
                if cfg.mode == "hnn":
                    # CLP conversion cost: IF accumulate per tick on the
                    # boundary neurons (activation<->spike, Fig 4)
                    e_pe += L.n_out * T * act * cfg.e_acc

            reports.append(LayerReport(
                L.name, cores, cyc_compute, cyc_emio, local_packets,
                routed, boundary_packets, e_pe, e_mem, e_router, e_emio))
            cores_prev = cores
        return SimReport(reports, cfg)

    # ------------------------------------------------------------------
    def simulate_trace(self, steps: Sequence[dict]) -> TraceReport:
        """Cycle-level pricing of a serving step trace's boundary
        traffic, one collective stream at a time.

        ``steps`` is an ``SLOMonitor.step_trace()`` record list (or the
        ``slo.load_trace`` of its JSONL): each record's
        ``wire_streams`` maps collective stream kind (psum /
        head_all_gather / partial_combine / kv_migrate / ...) to the
        die-to-die bytes that collective moved during the tick; records
        without a stream split fall back to pricing the aggregate
        ``wire_bytes`` as one ``"total"`` stream.

        Each stream of ``pb`` bytes (one 8-bit boundary packet per
        byte) pays, over the ``nc`` peripheral serdes ports:

        * ``ceil(pb / nc) * cycles_ser`` serialization batches — ceil,
          not eq (8)'s floor-on-the-aggregate, because collectives
          execute in dependency order and cannot pack a partial final
          serdes batch with the next collective's packets;
        * ``pb`` pipelined transfer cycles plus one ``cycles_des``
          deserialization drain and a ``grid/4 + 1`` hop fill from the
          interior compute cores to the peripheral ring (eqs 4-5's
          average-hop shape for a boundary-bound stream);
        * energy per §4.4: ``e_d2d`` per packet at the boundary,
          ``e_hop`` per packet-hop getting there, one spike/activation
          accumulate (``e_acc``) per packet of boundary encode/decode
          work, and an SRAM read + write (``2 * e_sram_rw``).

        Summed over streams this strictly upper-bounds the closed-form
        ``emio_cost_from_trace`` figure for the same trace.
        """
        cfg = self.cfg
        nc = max(1, cfg.boundary_cores)
        hops = cfg.grid / 4.0 + 1.0
        out: List[TraceStepReport] = []
        for s in steps:
            streams = dict(s.get("wire_streams") or {})
            if not streams:
                total = float(s.get("wire_bytes", 0.0))
                if total > 0:
                    streams = {"total": total}
            cyc = e_pe = e_mem = e_router = e_emio = 0.0
            for pb in streams.values():
                pb = float(pb)
                if pb <= 0:
                    continue
                cyc += (math.ceil(pb / nc) * cfg.cycles_ser + pb
                        + cfg.cycles_des + hops)
                e_emio += pb * cfg.e_d2d
                e_router += pb * hops * cfg.e_hop
                e_pe += pb * cfg.e_acc
                e_mem += 2.0 * pb * cfg.e_sram_rw
            out.append(TraceStepReport(
                kind=str(s.get("kind", "")),
                tokens=int(s.get("tokens", 0)), cycles=cyc,
                e_pe=e_pe, e_mem=e_mem, e_router=e_router,
                e_emio=e_emio,
                bytes_by_stream={k: float(v) for k, v in streams.items()
                                 if float(v) > 0}))
        return TraceReport(out, cfg)


# ---------------------------------------------------------------------------
# paper benchmark models (§4.1) mapped to layer lists
# ---------------------------------------------------------------------------


def rwkv_layers(d_model=512, n_layers=6, vocab=256) -> List[Layer]:
    """Paper's 6-layer, 512-dim RWKV (Enwik8)."""
    out: List[Layer] = [fc("embed", vocab, d_model)]
    for i in range(n_layers):
        out += [
            fc(f"L{i}.tm_kvr", d_model, 3 * d_model),
            fc(f"L{i}.tm_out", d_model, d_model),
            fc(f"L{i}.cm_k", d_model, 4 * d_model),
            fc(f"L{i}.cm_v", 4 * d_model, d_model),
        ]
    out.append(fc("head", d_model, vocab))
    return out


def msresnet18_layers(img=32, classes=100) -> List[Layer]:
    """MS-ResNet18 on CIFAR-100 (paper Fig 5)."""
    out = [conv("stem", 3, 64, 3, img, img)]
    ch = [(64, img), (128, img // 2), (256, img // 4), (512, img // 8)]
    prev_c = 64
    for b, (c, hw) in enumerate(ch):
        for u in range(2):
            out.append(conv(f"b{b}u{u}c1", prev_c, c, 3, hw, hw))
            out.append(conv(f"b{b}u{u}c2", c, c, 3, hw, hw))
            prev_c = c
    out.append(fc("head", 512, classes))
    return out


def efficientnet_b4_layers(img=380, classes=1000) -> List[Layer]:
    """EfficientNet-B4 (approximate MBConv workload, paper §4.2)."""
    out = [conv("stem", 3, 48, 3, img // 2, img // 2)]
    # (expansion, channels, layers, stride, kernel)
    blocks = [(1, 24, 2, 1, 3), (6, 32, 4, 2, 3), (6, 56, 4, 2, 5),
              (6, 112, 6, 2, 3), (6, 160, 6, 1, 5), (6, 272, 8, 2, 5),
              (6, 448, 1, 1, 3)]
    c_in, hw = 48, img // 2
    for e, c, n, s, k in blocks:
        for i in range(n):
            stride = s if i == 0 else 1
            hw = max(4, hw // stride)
            mid = c_in * e
            out.append(conv(f"mb{c}_{i}e", c_in, mid, 1, hw, hw))
            out.append(Layer(f"mb{c}_{i}d", mid * hw * hw, mid * hw * hw,
                             mid * hw * hw * k * k, "dwconv"))
            out.append(conv(f"mb{c}_{i}p", mid, c, 1, hw, hw))
            c_in = c
    out.append(fc("head", c_in, classes))
    return out


PAPER_MODELS = {
    "rwkv": rwkv_layers,
    "msresnet18": msresnet18_layers,
    "efficientnet-b4": efficientnet_b4_layers,
}


# ---------------------------------------------------------------------------
# serving-trace -> NoC co-simulation bridge
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TraceStepReport:
    """Cycle-level cost of one serving tick's boundary traffic."""

    kind: str                       # step kind ("decode"/"verify"/"drain")
    tokens: int
    cycles: float                   # serdes + deserialize + hop fill
    e_pe: float                     # boundary encode/decode accumulates
    e_mem: float                    # SRAM read (encode) + write (decode)
    e_router: float                 # hops from compute cores to the ring
    e_emio: float                   # die-to-die packets (224x hop, §4.4)
    bytes_by_stream: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    @property
    def energy(self):
        return self.e_pe + self.e_mem + self.e_router + self.e_emio


@dataclasses.dataclass
class TraceReport:
    """``NocSim.simulate_trace`` result: per-step reports + totals."""

    steps: List[TraceStepReport]
    cfg: NocConfig

    @property
    def tokens(self):
        return sum(s.tokens for s in self.steps)

    @property
    def total_cycles(self):
        return sum(s.cycles for s in self.steps)

    @property
    def total_energy(self):
        return sum(s.energy for s in self.steps)

    def breakdown(self):
        return {
            "PE": sum(s.e_pe for s in self.steps),
            "MEM": sum(s.e_mem for s in self.steps),
            "Router": sum(s.e_router for s in self.steps),
            "EMIO": sum(s.e_emio for s in self.steps),
        }

    def bytes_by_stream(self):
        out: Dict[str, float] = {}
        for s in self.steps:
            for k, v in s.bytes_by_stream.items():
                out[k] = out.get(k, 0.0) + v
        return dict(sorted(out.items()))

    def to_dict(self) -> dict:
        """The simulated cost as a plain dict (the closed-form cross-check
        figure comes from ``emio_cost_from_trace``).  Energy is in normalized-pJ
        (e_mac = 1.0 pJ at 65 nm), so joules = energy * 1e-12."""
        toks = max(self.tokens, 1)
        return {
            "steps": len(self.steps),
            "tokens": self.tokens,
            "noc_cycles": self.total_cycles,
            "noc_cycles_per_token": self.total_cycles / toks,
            "noc_us_per_token": (self.total_cycles / toks
                                 / self.cfg.freq_hz * 1e6),
            "energy_breakdown": self.breakdown(),
            "energy_per_token": self.total_energy / toks,
            "joules_per_token": self.total_energy / toks * 1e-12,
            "wire_kb_by_stream": {k: v / 1e3
                                  for k, v in self.bytes_by_stream().items()},
        }


def emio_cost_from_trace(steps: Sequence[dict],
                         cfg: NocConfig | None = None) -> dict:
    """Price a serving engine's per-step wire-bytes trace on the EMIO.

    ``steps`` is the record list an ``SLOMonitor`` step trace exports
    (``slo.load_trace`` / ``SLOMonitor.step_trace()``): each dict needs
    ``wire_bytes`` — the total die-to-die bytes the tick's device step
    moved, from the compiled step's parsed collectives — and ``tokens``
    (committed that tick).  Every byte on the coded wire is one 8-bit
    boundary packet, so a step's serialization cost follows eq (8) —
    ``floor(pb / nc) * cycles_ser + pb`` over the ``nc`` peripheral
    serdes ports — and its energy is ``pb * e_d2d`` (224x a router hop,
    §4.4).  The returned per-token numbers are the co-simulation
    headline: what the measured serving workload, not a synthetic
    layer sweep, pays at the die boundary per generated token.
    """
    cfg = cfg or NocConfig()
    nc = max(1, cfg.boundary_cores)
    cycles = energy = mig_bytes = 0.0
    tokens = 0
    for s in steps:
        pb = float(s.get("wire_bytes", 0.0))
        if pb > 0:
            cycles += math.floor(pb / nc) * cfg.cycles_ser + pb
            energy += pb * cfg.e_d2d
        tokens += int(s.get("tokens", 0))
        # disagg KV migrations are already folded into wire_bytes (and
        # thus priced above); surface their share for the report
        mig_bytes += float(s.get("mig_bytes", 0.0))
    return {
        "steps": len(steps),
        "tokens": tokens,
        "emio_cycles": cycles,
        "emio_s": cycles / cfg.freq_hz,
        "e_emio": energy,
        "emio_cycles_per_token": cycles / max(tokens, 1),
        "e_emio_per_token": energy / max(tokens, 1),
        "mig_bytes": mig_bytes,
    }
