"""Three-term roofline from a compiled dry-run artifact.

  compute    = HLO_FLOPs / peak_FLOPs            (cost_analysis, per chip)
  memory     = HLO_bytes / HBM_bw                (cost_analysis, per chip)
  collective = wire_bytes / link_bw              (parsed from HLO text)

cost_analysis() of an SPMD-partitioned module reports per-device numbers;
collective wire bytes are parsed from ``compiled.as_text()`` (the
partitioned module, so shapes are per-device shards) with per-kind
ring-traffic factors.  Beyond the aggregate, ``parse_collectives`` emits
one ``CollectiveOp`` record per collective — HLO kind, semantic stream
(psum / head_all_gather / partial_combine / kv_migrate / ..., recovered
from the ``jax.named_scope`` labels ``repro.core.boundary`` puts on every
coded boundary), participant group size from the op's ``replica_groups``,
wire bytes, and whether the payload rides the coded (int8/int4) wire —
the per-collective packet streams the serving engine threads into the
cycle-level NoC co-simulation (``repro.sim.noc.NocSim.simulate_trace``).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
import warnings
from typing import List, Optional

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "s4": 0.5, "u4": 0.5,
}

#: result dtypes that mark a coded-wire payload (spike counts / absmax
#: int8 / packed uint4); a collective whose every result leaf is one of
#: these moves boundary packets, not fp activations
_CODED_DTYPES = frozenset({"s8", "u8", "s4", "u4", "pred"})

#: result type = everything between "=" and the op name; TPU modules
#: print tiled layouts (``s8[8,64]{1,0:T(8,128)(4,1)}``) inside it
_COLL_RE = re.compile(
    r"=\s*(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|s4|u4)\[([0-9,]*)\]")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_NPART_RE = re.compile(r"num_partitions=(\d+)")
#: a computation's header line: ``[ENTRY] %name (params) -> result {``
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*\)\s*->.*\{\s*$")
_CALLEE_RE = re.compile(r"\b(calls|to_apply|body|condition|true_computation|"
                        r"false_computation)=%?([\w.\-]+)")
_CALLEES_RE = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_INT_CONST_RE = re.compile(r"[su]\d+\[\][^=]*\bconstant\((\d+)\)")

#: ``jax.named_scope`` labels (repro.core.boundary) -> semantic stream;
#: first substring match on the op's ``metadata.op_name`` wins
_STREAM_HINTS = (
    ("kv_migrate", "kv_migrate"),
    ("combine_partials", "partial_combine"),
    ("quantize_partial", "partial_combine"),
    ("head_all_gather", "head_all_gather"),
)
#: fallback: HLO op kind -> stream for collectives without a scope hint
_KIND_STREAMS = {
    "all-reduce": "psum",
    "reduce-scatter": "psum",
    "all-gather": "all_gather",
    "all-to-all": "all_to_all",
    "collective-permute": "permute",
}


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> Optional[int]:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:  # iota format [num_groups, group_size]<=[N]
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return None


def _stream_of(op_name: str, kind: str) -> str:
    for hint, stream in _STREAM_HINTS:
        if hint in op_name:
            return stream
    return _KIND_STREAMS.get(kind, kind)


def _is_coded(type_str: str) -> bool:
    dts = [dt for dt, _ in _SHAPE_RE.findall(type_str)]
    return bool(dts) and all(dt in _CODED_DTYPES for dt in dts)


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One parsed collective: the unit of a per-collective packet stream."""

    kind: str                  # HLO op: all-gather | all-reduce | ...
    stream: str                # semantic stream (psum | head_all_gather |
    #                            partial_combine | kv_migrate | ...)
    group: int                 # participant count (replica_groups)
    t_bytes: float             # result tensor bytes (per device)
    bytes: float               # ring-model wire bytes (per device)
    coded: bool                # int8/int4 payload: the coded boundary
    op_name: str = ""          # HLO metadata op_name (scope trail)
    count: int = 1             # executions per run of the module (the
    #                            trip counts of the loops around it)


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    wire_bytes: float          # per-device bytes on the ICI
    by_kind: dict
    ops: List[CollectiveOp] = dataclasses.field(default_factory=list)
    by_stream: dict = dataclasses.field(default_factory=dict)


def _trip_count(while_line: str, bodies: dict, cond: str) -> int:
    """Iterations of one ``while``: XLA's ``known_trip_count`` where the
    module prints it, else the bound of a loop condition that compares
    the counter with one integer constant (``i < N``, as ``lax.scan`` and
    ``fori_loop`` lower), else 1."""
    m = _TRIP_RE.search(while_line)
    if m:
        return int(m.group(1))
    lines = bodies.get(cond, ())
    consts = [int(c) for ln in lines for c in _INT_CONST_RE.findall(ln)]
    if len(consts) == 1 and any("direction=LT" in ln for ln in lines):
        return consts[0]
    return 1


def _executions(lines: list) -> list:
    """How many times each line of a module's text runs per run of the
    module: the product of the trip counts of the ``while`` loops on the
    way from ``ENTRY`` to the line's computation, summed over the ways
    there.  Lines outside any computation, or in one no call reaches,
    count once."""
    comp_of, bodies, order, entry = [None] * len(lines), {}, [], None
    cur = None
    for i, line in enumerate(lines):
        m = _COMP_RE.match(line)
        if m:
            cur = m.group(2)
            bodies[cur] = []
            order.append(cur)
            entry = cur if m.group(1) else entry
        elif cur is not None:
            if line.startswith("}"):
                cur = None
            else:
                comp_of[i] = cur
                bodies[cur].append(line)
    runs = dict.fromkeys(order, 0)
    if entry is not None:
        runs[entry] = 1
    # XLA prints callees before their callers, so walking the module
    # backwards reaches every caller of a computation before it
    for comp in reversed(order):
        if not runs[comp]:
            continue
        for line in bodies[comp]:
            callees = _CALLEE_RE.findall(line)
            for group in _CALLEES_RE.findall(line):
                callees += [("", n.strip().lstrip("%"))
                            for n in group.split(",") if n.strip()]
            trips = 1
            if " while(" in line:
                cond = dict(callees).get("condition")
                trips = _trip_count(line, bodies, cond)
            for kind, name in callees:
                if name in runs:
                    runs[name] += runs[comp] * (
                        trips if kind in ("body", "condition") else 1)
    return [1 if c is None else runs[c] or 1 for c in comp_of]


def parse_collectives(hlo_text: str,
                      default_group: Optional[int] = None) -> CollectiveStats:
    """Sum per-device ICI traffic over every collective op.

    Ring-model factors (n = participant count, T = tensor bytes as printed
    on the op's *result*, which in the partitioned module is per-device):
      all-gather        result T (full):    recv (n-1)/n * T
      reduce-scatter    result T (shard):   recv (n-1) * T
      all-reduce        result T:           recv 2*(n-1)/n * T
      all-to-all        result T:           recv (n-1)/n * T
      collective-permute result T:          recv T

    ``n`` is parsed from each op's ``replica_groups`` (explicit or iota
    form); ops without one fall back to the module's ``num_partitions``
    header (the all-device group XLA prints as ``{}``), and only when
    neither is present does ``default_group`` apply — with a warning,
    because an assumed group size silently mis-scales wire bytes on any
    mesh whose HLO says otherwise (e.g. tp=4 all-gathers under the old
    hardwired ``default_group=2``).

    A collective inside a loop runs once per iteration: its bytes count
    as many times as the loops around it run (``CollectiveOp.count``,
    from ``_executions``), so a layer scan's collectives count once per
    layer, and the totals are those of one run of the module.
    """
    counts: dict = {}
    by_kind: dict = {}
    by_stream: dict = {}
    ops: List[CollectiveOp] = []
    total = 0.0
    unsized = 0
    m = _NPART_RE.search(hlo_text)
    num_partitions = int(m.group(1)) if m else None
    lines = hlo_text.splitlines()
    for line, runs in zip(lines, _executions(lines)):
        m = _COLL_RE.search(line)
        if not m:
            continue
        type_str, kind = m.group(1), m.group(2).lower()
        t_bytes = _shape_bytes(type_str)
        n = _group_size(line)
        if n is None:
            if kind == "collective-permute":
                n = 2          # point-to-point pairs; bytes are n-free
            else:
                n = num_partitions
            if n is None:
                unsized += 1
                n = default_group or 2
        if n <= 1:
            continue
        if kind == "all-gather":
            b = t_bytes * (n - 1) / n
        elif kind == "reduce-scatter":
            b = t_bytes * (n - 1)
        elif kind == "all-reduce":
            b = 2 * t_bytes * (n - 1) / n
        elif kind == "all-to-all":
            b = t_bytes * (n - 1) / n
        else:  # collective-permute
            b = t_bytes
        nm = _OPNAME_RE.search(line)
        op_name = nm.group(1) if nm else ""
        stream = _stream_of(op_name, kind)
        counts[kind] = counts.get(kind, 0) + runs
        by_kind[kind] = by_kind.get(kind, 0.0) + b * runs
        by_stream[stream] = by_stream.get(stream, 0.0) + b * runs
        ops.append(CollectiveOp(kind, stream, n, t_bytes, b,
                                _is_coded(type_str), op_name, runs))
        total += b * runs
    if unsized:
        warnings.warn(
            f"parse_collectives: {unsized} collective(s) carry no "
            f"replica_groups and the module prints no num_partitions; "
            f"assuming group size {default_group or 2} — wire bytes may "
            f"be mis-scaled", RuntimeWarning, stacklevel=2)
    return CollectiveStats(counts, total, by_kind, ops, by_stream)


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    wire_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float
    coll_counts: dict
    coll_by_kind: dict

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze(cost: dict, hlo_text: str, model_flops_per_chip: float) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = parse_collectives(hlo_text)
    c_s = flops / PEAK_FLOPS
    m_s = hbm / HBM_BW
    i_s = coll.wire_bytes / ICI_BW
    terms = {"compute": c_s, "memory": m_s, "collective": i_s}
    bn = max(terms, key=terms.get)
    ratio = model_flops_per_chip / flops if flops else 0.0
    return Roofline(flops, hbm, coll.wire_bytes, c_s, m_s, i_s, bn,
                    model_flops_per_chip, ratio, coll.counts, coll.by_kind)


# ---------------------------------------------------------------------------
# MODEL_FLOPS (6ND dense / 6·N_active·D MoE)
# ---------------------------------------------------------------------------


def count_params(cfg, tp: int = 16):
    """(total, active) parameter counts from the config (analytic)."""
    D = cfg.d_model
    dh = cfg.d_head
    V = cfg.vocab
    total = V * D * (1 if cfg.tie_embeddings else 2)
    # 6ND convention: the embedding LOOKUP does no matmul flops; only the
    # LM-head matmul counts toward MODEL_FLOPS
    active = V * D
    per_kind_t = {}
    for kind in cfg.pattern:
        t = a = 0
        if kind in ("attn", "global", "local", "attn_moe"):
            t += D * cfg.n_heads * dh * 2            # wq, wo
            t += D * cfg.n_kv_heads * dh * 2         # wk, wv
            a = t
            if kind == "attn_moe":
                e = 3 * D * cfg.d_ff_expert
                t += cfg.n_experts * e + D * cfg.n_experts
                a += cfg.top_k * e
                sh = 3 * D * cfg.n_shared_experts * cfg.d_ff_expert
                t += sh
                a += sh
            else:
                t += 3 * D * cfg.d_ff
                a += 3 * D * cfg.d_ff
        elif kind in ("mamba", "mamba_mlp", "mamba_moe"):
            Di = cfg.d_inner
            t += D * 2 * Di + Di * D + Di * cfg.d_conv
            t += 2 * D * cfg.d_state + D * cfg.dt_rank_eff \
                + cfg.dt_rank_eff * Di + 2 * Di * cfg.d_state
            a = t
            if kind == "mamba_moe":
                e = 3 * D * cfg.d_ff_expert
                t += cfg.n_experts * e
                a += cfg.top_k * e
            elif kind == "mamba_mlp":
                t += 3 * D * cfg.d_ff
                a += 3 * D * cfg.d_ff
        elif kind == "mlstm":
            t += 5 * D * cfg.n_heads * dh + 2 * D * cfg.n_heads
            a = t
        elif kind == "slstm":
            t += 5 * D * cfg.n_heads * dh \
                + cfg.n_heads * dh * 4 * dh
            a = t
        elif kind == "rwkv":
            F = cfg.d_ff or 4 * D
            t += 4 * D * D + D * F + F * D + D * D
            a = t
        per_kind_t[kind] = t
        total += t * cfg.n_units
        active += a * cfg.n_units
    if cfg.is_encdec:
        enc = (D * cfg.n_heads * dh * 2 + D * cfg.n_kv_heads * dh * 2
               + 3 * D * cfg.d_ff) * cfg.n_enc_layers
        cross = (D * cfg.n_heads * dh * 2 + D * cfg.n_kv_heads * dh * 2) \
            * cfg.n_layers
        total += enc + cross
        active += enc + cross
    return total, active


def model_flops_per_chip(cfg, cell, chips: int, mode: str) -> float:
    total, active = count_params(cfg, 16)
    tokens = cell.global_batch * cell.seq_len
    if mode == "train":
        return 6.0 * active * tokens / chips
    if mode == "prefill":
        return 2.0 * active * tokens / chips
    # decode: one token per sequence
    return 2.0 * active * cell.global_batch / chips
