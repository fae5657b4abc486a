"""Reduction of a profiler trace to device times, kept with the benchmark.

``load`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes into
plain ``Trace`` data: for each TPU the operations on its "XLA Ops" line
and the programs on its "XLA Modules" line, and the benchmark's own host
spans (named ``bench.*``).  Everything after that works on those plain
lists, so the tests can hand-build a trace.  Times are seconds on the
trace's clock, which the profiler shares between host and devices.

Names are matched, not positions: the paged-decode kernel is the custom
call named after its jitted wrapper (``paged_flash_decode``), collectives
by their HLO opcode, and a decode step is a program that runs the kernel.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import re
from pathlib import Path

KERNEL = "paged_flash_decode"
#: HLO collectives, sync or async (start/done), possibly fused
_COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter)")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    ops: list       # Event per HLO op (short name), sorted by start
    modules: list   # Event per program execution, sorted by start

    @functools.cached_property
    def starts(self) -> list:
        return [o.start for o in self.ops]

    @functools.cached_property
    def kernels(self) -> list:
        return [o for o in self.ops if is_kernel(o.name)]


@dataclasses.dataclass
class Trace:
    devices: list   # one Device per TPU chip
    spans: list     # host Event per bench.* span, sorted by start

    @property
    def window(self) -> tuple:
        """From the first benchmark span's start to the last one's end."""
        return (min(s.start for s in self.spans),
                max(s.end for s in self.spans))


def short_name(hlo: str) -> str:
    """``'%fusion.12 = bf16[..] fusion(...)'`` -> ``'fusion.12'``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def load(trace_dir) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = [Event(short_name(e.name), e.start_ns * 1e-9,
                         e.end_ns * 1e-9)
                   for e in (lines["XLA Ops"].events
                             if "XLA Ops" in lines else ())]
            mods = [Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in (lines["XLA Modules"].events
                              if "XLA Modules" in lines else ())]
            devices.append(Device(sorted(ops, key=lambda e: e.start),
                                  sorted(mods, key=lambda e: e.start)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                          for e in ln.events if e.name.startswith("bench.")]
    return Trace(devices, sorted(spans, key=lambda e: e.start))


# -- interval arithmetic ----------------------------------------------------


def union(events, lo: float, hi: float) -> list:
    """Merged ``(start, end)`` intervals of ``events`` clipped to
    ``[lo, hi]``."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [tuple(x) for x in out]


def busy_seconds(dev: Device, lo: float, hi: float) -> float:
    return sum(t - s for s, t in union(dev.ops, lo, hi))


def idle_gaps(dev: Device, lo: float, hi: float) -> list:
    """``(start, end)`` of every stretch in ``[lo, hi]`` with no op."""
    gaps, cur = [], lo
    for s, t in union(dev.ops, lo, hi):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def self_times(ops) -> dict:
    """Time of each op name excluding the ops nested inside it (a while
    loop's body, a fusion's callees)."""
    out: dict = {}
    stack: list = []            # [event, child time]
    for e in sorted(ops, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            ev, child = stack.pop()
            out[ev.name] = out.get(ev.name, 0.0) + ev.dur - child
        if stack:
            stack[-1][1] += e.dur
        stack.append([e, 0.0])
    for ev, child in stack:
        out[ev.name] = out.get(ev.name, 0.0) + ev.dur - child
    return out


def within(events, lo: float, hi: float) -> list:
    return [e for e in events if e.start >= lo and e.end <= hi]


# -- what the metrics read --------------------------------------------------


def is_kernel(name: str) -> bool:
    return name.startswith(KERNEL)


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name))


def _runs_kernel(dev: Device, mod: Event) -> bool:
    k = dev.kernels
    i = bisect.bisect_left([o.start for o in k], mod.start)
    return i < len(k) and k[i].end <= mod.end


def decode_steps(dev: Device, lo: float, hi: float) -> list:
    """Program runs in ``[lo, hi]`` that run the paged-decode kernel."""
    return [m for m in within(dev.modules, lo, hi) if _runs_kernel(dev, m)]


def prefill_steps(dev: Device, lo: float, hi: float) -> list:
    """Runs of the engine's prefill program: the program that shares the
    decode step's name (both are the jitted ``step``) but has no kernel."""
    dec = {m.name for m in decode_steps(dev, lo, hi)}
    stem = {n.split("(", 1)[0] for n in dec} or {"jit_step"}
    return [m for m in within(dev.modules, lo, hi)
            if m.name.split("(", 1)[0] in stem and m.name not in dec
            and not _runs_kernel(dev, m)]


def _inside(dev: Device, mod: Event) -> list:
    """The ops that run inside the program run ``mod``."""
    i = bisect.bisect_left(dev.starts, mod.start)
    out = []
    while i < len(dev.ops) and dev.ops[i].start < mod.end:
        if dev.ops[i].end <= mod.end:
            out.append(dev.ops[i])
        i += 1
    return out


def op_seconds(dev: Device, mods, pred) -> float:
    """Summed time of the ops matching ``pred`` inside the runs ``mods``
    (top-level matches only: a matching op inside another is not counted
    twice)."""
    total = 0.0
    for m in mods:
        last_end = m.start
        for o in _inside(dev, m):
            if pred(o.name) and o.start >= last_end:
                total += o.dur
                last_end = o.end
    return total


def innermost_span(spans, t: float) -> str:
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best is not None else "no span"


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device ops that took most time (self time, mean over chips)
    and the longest idle gaps of the first chip, each named by the host
    span open at its middle."""
    lo, hi = tr.window
    tot: dict = {}
    for dev in tr.devices:
        for name, t in self_times(within(dev.ops, lo, hi)).items():
            tot[name] = tot.get(name, 0.0) + t / len(tr.devices)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr.devices[0], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[innermost_span(tr.spans, (s + t) / 2), t - s]
                          for s, t in gaps]}
