"""The program's own instrumentation in a profiler trace, kept with the
benchmark.

``load`` reads the same newest ``.xplane.pb`` that ``reduce_trace.load``
reads, for what the program itself puts there:

- the serving engine's host spans (named ``engine.*``, see
  ``repro.serving.engine``), each with its stats (``tick``, ``rid``,
  ``prompt_len``, ``bytes``);
- the optimized HLO of every program that ran, which the profiler keeps
  beside the trace (one serialized ``HloProto`` per program run name,
  such as ``jit_step(11)``, on its ``/host:metadata`` plane).  The ops
  of a device trace are that HLO's instructions by name (``fusion.220``),
  so each op's ``op_name`` metadata — the ``jax.named_scope`` path it was
  traced under, ``spike_codec/encode`` for the codec — is read from it.

Everything after that works on plain data (``Program``), so the tests can
hand-build one.  A program without these spans or scopes (an older
checkout of the program) loads as an empty ``Program``, and the readers
then read nothing.
"""
from __future__ import annotations

import dataclasses
import glob
from pathlib import Path

from bench import reduce_trace as rt

#: the named scope ``core/boundary.py`` puts the codec's local ops under
CODEC_SCOPE = "spike_codec"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    stats: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Program:
    spans: list     # Span per engine.* host span, sorted by start
    #: program run name -> serialized HloProto, parsed on first use into
    #: {instruction name -> op_name}; tests give the dict itself
    hlo: dict = dataclasses.field(default_factory=dict)

    def op_names(self, run: str) -> dict:
        """{HLO instruction name -> ``op_name``} of the program run
        ``run`` (a module event's name, the same as the HLO's key), or {}
        where the trace holds no HLO for it."""
        v = self.hlo.get(run)
        if isinstance(v, bytes):
            v = self.hlo[run] = hlo_op_names(v)
        return v or {}


def load(trace_dir) -> Program:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = Path(files[-1]).read_bytes()
    pd = ProfileData.from_serialized_xspace(data)
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [Span(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9,
                               dict(e.stats))
                          for e in ln.events if e.name.startswith("engine.")]
    try:
        hlo = hlo_protos(data)
    except (ValueError, IndexError):    # not the wire format read here
        hlo = {}
    return Program(sorted(spans, key=lambda s: s.start), hlo)


def of(rec):
    """The traced run's ``Program``, loaded once and kept as
    ``rec.program``; None in an untraced run."""
    if getattr(rec, "program", None) is None and rec.trace is not None:
        from bench import run
        rec.program = load(run.TRACE_DIR)
    return getattr(rec, "program", None)


# -- protobuf wire format ---------------------------------------------------
#
# The field numbers of tsl's xplane.proto and xla's hlo.proto that the two
# readings below need; nothing else of either message is decoded.

_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA, _XPLANE_STAT_METADATA = 2, 4, 5
_XEVENTMETA_NAME, _XEVENTMETA_STATS = 2, 5
_XSTATMETA_NAME = 2
_XSTAT_METADATA_ID, _XSTAT_BYTES = 1, 6
_HLOPROTO_MODULE = 1
_MODULE_COMPUTATIONS = 3
_COMPUTATION_INSTRUCTIONS = 2
_INSTRUCTION_NAME, _INSTRUCTION_METADATA = 1, 7
_OPMETA_OP_NAME = 2


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one message: an int for
    a varint, a memoryview for anything length-delimited or fixed."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} not supported")
        yield key >> 3, v


def _map_values(entries) -> list:
    """The values of protobuf map entries (field 2 of each entry)."""
    return [dict(_fields(e)).get(2, b"") for e in entries]


def hlo_protos(xspace: bytes) -> dict:
    """{program run name -> serialized HloProto} from the
    ``/host:metadata`` plane of a serialized XSpace."""
    for num, plane in _fields(xspace):
        if num != _XSPACE_PLANES:
            continue
        name, emeta, smeta = None, [], []
        for f, v in _fields(plane):
            if f == _XPLANE_NAME:
                name = bytes(v).decode()
            elif f == _XPLANE_EVENT_METADATA:
                emeta.append(v)
            elif f == _XPLANE_STAT_METADATA:
                smeta.append(v)
        if name != "/host:metadata":
            continue
        stat_names = {}
        for sm in _map_values(smeta):
            d = dict(_fields(sm))
            stat_names[d.get(1, 0)] = bytes(d.get(_XSTATMETA_NAME,
                                                  b"")).decode()
        out = {}
        for em in _map_values(emeta):
            run, proto = None, None
            for f, v in _fields(em):
                if f == _XEVENTMETA_NAME:
                    run = bytes(v).decode()
                elif f == _XEVENTMETA_STATS:
                    st = dict(_fields(v))
                    if (stat_names.get(st.get(_XSTAT_METADATA_ID))
                            == "Hlo Proto" and _XSTAT_BYTES in st):
                        proto = st[_XSTAT_BYTES]
            if run is not None and proto is not None:
                out[run] = bytes(proto)
        return out
    return {}


def hlo_op_names(proto) -> dict:
    """{instruction name -> ``op_name`` metadata} over every computation
    of a serialized HloProto (instruction names are unique in a module)."""
    out = {}
    for f, module in _fields(proto):
        if f != _HLOPROTO_MODULE:
            continue
        for g, comp in _fields(module):
            if g != _MODULE_COMPUTATIONS:
                continue
            for h, inst in _fields(comp):
                if h != _COMPUTATION_INSTRUCTIONS:
                    continue
                name, op_name = None, ""
                for k, v in _fields(inst):
                    if k == _INSTRUCTION_NAME:
                        name = bytes(v).decode()
                    elif k == _INSTRUCTION_METADATA:
                        op_name = bytes(dict(_fields(v)).get(
                            _OPMETA_OP_NAME, b"")).decode()
                if name is not None:
                    out[name] = op_name
    return out


# -- what the metrics read --------------------------------------------------


def in_window(spans, lo: float, hi: float, name: str) -> list:
    return [s for s in spans if s.name == name and s.start >= lo
            and s.end <= hi]


def host_seconds_per_step(prog: Program, lo: float, hi: float):
    """Mean over the ``engine.step`` spans in ``[lo, hi]`` of the span's
    time less its ``engine.commit.wait`` time: the host's own work per
    scheduler tick, without the time it sat blocked on the device."""
    steps = in_window(prog.spans, lo, hi, "engine.step")
    if not steps:
        return None
    waits = in_window(prog.spans, lo, hi, "engine.commit.wait")
    total = 0.0
    for s in steps:
        total += s.dur - sum(w.dur for w in waits
                             if w.start >= s.start and w.end <= s.end)
    return total / len(steps)


def staged_bytes_per_step(prog: Program, lo: float, hi: float):
    """Host bytes handed to the device (the ``bytes`` of every
    ``engine.stage`` and ``engine.prefill`` span in ``[lo, hi]``) per
    device step launched there (``engine.launch`` spans)."""
    launches = in_window(prog.spans, lo, hi, "engine.launch")
    if not launches:
        return None
    staged = sum(s.stats.get("bytes", 0)
                 for name in ("engine.stage", "engine.prefill")
                 for s in in_window(prog.spans, lo, hi, name))
    return staged / len(launches)


def is_codec(op_name: str) -> bool:
    return CODEC_SCOPE in op_name.split("/")


def codec_seconds_per_step(dev, prog: Program, lo: float, hi: float):
    """Self time of the ops under the codec's scope inside the decode
    steps in ``[lo, hi]`` on one chip, per step; None where no op of
    those steps carries the scope."""
    steps = rt.decode_steps(dev, lo, hi)
    total, seen = 0.0, False
    for m in steps:
        names = prog.op_names(m.name)
        for op, t in rt.self_times(rt.within(dev.ops, m.start,
                                             m.end)).items():
            if is_codec(names.get(op, "")):
                total += t
                seen = True
    return total / len(steps) if seen else None


def engine_gaps(tr: rt.Trace, prog: Program, top: int = 10) -> list:
    """The longest idle gaps of the first chip in the traced window, each
    named by the innermost ``engine.*`` span open at its middle."""
    lo, hi = tr.window
    gaps = sorted(rt.idle_gaps(tr.devices[0], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    return [[rt.innermost_span(prog.spans, (s + t) / 2), t - s]
            for s, t in gaps]


def print_engine_gaps(tr: rt.Trace, prog: Program, out):
    """Write ``engine_gaps`` to ``out``, one gap a line."""
    for name, secs in engine_gaps(tr, prog):
        print(f"idle gap {secs * 1e6:.1f} us in {name}", file=out)
