"""The insert_ms_per_admit reader on hand-built traces: the self time of
the ops under the kv_insert scope over the engine.insert spans, and
nothing from a program without the scope."""
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run  # noqa: E402
from bench.program_trace import Program, Span  # noqa: E402
from bench.reduce_trace import Device, Event, Trace  # noqa: E402

HLO = {"jit_ins(7)": {"all-gather.1": "jit(ins)/kv_insert/all_gather",
                      "while.2": "jit(ins)/kv_insert/while",
                      "fusion.3": "jit(ins)/kv_insert/while/body/"
                                  "dynamic_update_slice",
                      "copy.4": ""},
       "jit_step(11)": {"fusion.1": "jit(step)/dot_general"}}


def _spans(n):
    return [Span("engine.insert", 1.0 + 10 * i, 2.0 + 10 * i, {"pages": 4})
            for i in range(n)]


def _device():
    """Two inserts, each a gather (1 unit), a loop (0.5 of its own) around
    two page copies (1 unit each) and an unscoped copy (2 units), beside
    a decode step whose ops carry no scope."""
    ops, mods = [], []
    for o in (2.0, 12.0):
        mods.append(Event("jit_ins(7)", o, o + 6))
        ops += [Event("all-gather.1", o, o + 1),
                Event("while.2", o + 1, o + 3.5),
                Event("fusion.3", o + 1.25, o + 2.25),
                Event("fusion.3", o + 2.25, o + 3.25),
                Event("copy.4", o + 3.5, o + 5.5)]
    mods.append(Event("jit_step(11)", 20, 25))
    ops.append(Event("fusion.1", 20, 25))
    return Device(sorted(ops, key=lambda e: e.start),
                  sorted(mods, key=lambda e: e.start))


def _rec(program, devices=1):
    tr = Trace([_device() for _ in range(devices)],
               [Event("bench.step", 0, 11), Event("bench.step", 11, 30)])
    return types.SimpleNamespace(trace=tr, program=program)


@pytest.mark.parametrize("devices", [1, 4])
def test_insert_time_is_the_scoped_self_time_per_insert_span(devices):
    rec = _rec(Program(_spans(2), hlo=dict(HLO)), devices)
    # per insert: gather 1 + loop 0.5 + copies 2 = 3.5 units; mean over
    # chips of the same
    assert run.reader("insert_ms_per_admit")(rec) == pytest.approx(3500.0)


def test_insert_time_divides_by_the_spans_in_the_window():
    # a third engine.insert span in the window whose program ran later
    rec = _rec(Program(_spans(2) + [Span("engine.insert", 26, 27, {})],
                       hlo=dict(HLO)))
    assert run.reader("insert_ms_per_admit")(rec) == pytest.approx(
        7000.0 / 3)


def test_insert_time_reads_nothing_without_the_scope_or_a_trace():
    read = run.reader("insert_ms_per_admit")
    assert read(types.SimpleNamespace(trace=None)) is None
    unscoped = {"jit_ins(7)": {k: "jit(ins)/scatter"
                               for k in HLO["jit_ins(7)"]},
                "jit_step(11)": HLO["jit_step(11)"]}
    assert read(_rec(Program(_spans(2), hlo=unscoped))) is None
    # a program with no engine spans at all (an older one)
    assert read(_rec(Program([], hlo={}))) is None
