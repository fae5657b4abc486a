"""The trace reduction on hand-built traces: busy union and idle share,
ops matched by name, self times, and idle gaps named by host spans."""
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import readings, reduce_trace as rt  # noqa: E402
from bench.reduce_trace import Device, Event, Trace  # noqa: E402

KERNEL_OP = "paged_flash_decode.11"


def _ev(name, start, end):
    return Event(name, float(start), float(end))


def _decode_device(offset=0.0):
    """Two decode steps (each a while loop holding two layers of kernel
    and a collective) and one prefill, with idle gaps between them."""
    o = offset
    ops = [
        _ev("while.8", o + 0, o + 10), _ev(KERNEL_OP, o + 1, o + 4),
        _ev("all-gather.3", o + 4, o + 5), _ev(KERNEL_OP, o + 5, o + 8),
        _ev("fusion.1", o + 8, o + 9),
        _ev("while.8", o + 12, o + 22), _ev(KERNEL_OP, o + 13, o + 16),
        _ev("all-reduce-start.2", o + 16, o + 17),
        _ev(KERNEL_OP, o + 17, o + 20),
        _ev("fusion.7", o + 25, o + 29),           # the prefill's work
        _ev("scatter.2", o + 29, o + 30),          # the insert's
    ]
    mods = [_ev("jit_step(11)", o + 0, o + 10), _ev("jit_step(11)", o + 12,
                                                    o + 22),
            _ev("jit_step(22)", o + 25, o + 29),
            _ev("jit_ins(33)", o + 29, o + 30)]
    return Device(sorted(ops, key=lambda e: e.start), mods)


def _trace(devices=1):
    spans = [_ev("bench.step", 0, 11), _ev("bench.commit", 9, 11),
             _ev("bench.step", 11, 24), _ev("bench.dispatch", 22, 24),
             _ev("bench.submit", 24, 30)]
    return Trace([_decode_device() for _ in range(devices)], spans)


def test_union_and_idle_share():
    tr = _trace()
    lo, hi = tr.window
    assert (lo, hi) == (0.0, 30.0)
    dev = tr.devices[0]
    # busy: [0,10] + [12,22] + [25,30] = 25 of 30
    assert rt.busy_seconds(dev, lo, hi) == pytest.approx(25.0)
    assert rt.idle_gaps(dev, lo, hi) == [(10.0, 12.0), (22.0, 25.0)]
    rec = types.SimpleNamespace(trace=tr)
    assert readings.idle_share(rec) == pytest.approx(100 * 5 / 30)


def test_union_clips_to_window_and_merges_overlaps():
    evs = [_ev("a", -5, 2), _ev("b", 1, 3), _ev("c", 8, 20)]
    assert rt.union(evs, 0, 10) == [(0.0, 3.0), (8.0, 10.0)]


def test_kernel_and_collectives_matched_by_name():
    dev = _decode_device()
    steps = rt.decode_steps(dev, 0, 30)
    assert [m.start for m in steps] == [0.0, 12.0]
    assert rt.op_seconds(dev, steps, rt.is_kernel) == pytest.approx(12.0)
    assert rt.op_seconds(dev, steps, rt.is_collective) == pytest.approx(2.0)
    assert rt.is_collective("all-gather-start.1")
    assert rt.is_collective("reduce-scatter.4")
    assert not rt.is_collective("fusion.12")
    assert not rt.is_kernel("fusion.12")


def test_prefill_is_the_step_program_without_the_kernel():
    dev = _decode_device()
    pre = rt.prefill_steps(dev, 0, 30)
    assert [(m.name, m.dur) for m in pre] == [("jit_step(22)", 4.0)]


def test_self_time_excludes_nested_ops():
    st = rt.self_times(_decode_device().ops)
    # the two loops hold 8 and 7 of their 10 units in nested ops
    assert st["while.8"] == pytest.approx(5.0)
    assert st[KERNEL_OP] == pytest.approx(12.0)


def test_breakdown_names_gaps_by_innermost_host_span():
    bd = rt.breakdown(_trace(devices=2))
    names = [n for n, _ in bd["device_ops"]]
    assert names[0] == KERNEL_OP
    assert bd["device_ops"][0][1] == pytest.approx(12.0)   # mean over chips
    # gaps [22,25] (3 s) and [10,12] (2 s): longest first
    assert bd["idle_gaps"] == [["bench.dispatch", 3.0],
                               ["bench.commit", 2.0]]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_per_step_readings_average_over_chips():
    tr = Trace([_decode_device(), _decode_device()], _trace().spans)
    rec = types.SimpleNamespace(trace=tr)
    assert readings.decode_step_seconds(rec) == pytest.approx(10.0)
    assert readings.kernel_seconds_per_step(rec) == pytest.approx(6.0)
    assert readings.collective_seconds_per_step(rec) == pytest.approx(1.0)


def test_untraced_run_reads_nothing():
    rec = types.SimpleNamespace(trace=None)
    assert readings.idle_share(rec) is None
    assert readings.decode_step_seconds(rec) is None
    empty = types.SimpleNamespace(trace=Trace([], [_ev("bench.step", 0, 1)]))
    assert readings.idle_share(empty) is None
    assert readings.decode_step_seconds(empty) is None
