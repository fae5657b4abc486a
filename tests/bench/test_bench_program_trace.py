"""The program's own trace instrumentation and its readers: the serving
engine's ``engine.*`` host spans in a real CPU trace of a tiny engine, the
HLO scopes the trace keeps beside it, and the three readers on hand-built
traces."""
import io
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import program_trace as pt, reduce_trace as rt  # noqa: E402
from bench import run  # noqa: E402
from bench.program_trace import Program, Span  # noqa: E402
from bench.reduce_trace import Device, Event, Trace  # noqa: E402

RIDS = [f"r{i}" for i in range(5)]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(trace dir, commits per tick) of a tiny engine (3 slots, async
    depth 1) serving five requests from submit to idle under the
    profiler, warmed up first so no tick compiles."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.launch.mesh import make_mesh
    from repro.serving import EngineConfig, Request, ServingEngine

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode="hnn")).replace(
        dtype=jnp.float32, codec="spike_fused")
    plan = SP.make_plan(cfg, ShapeCell("serve_decode", 32, 3, "decode"),
                        mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, EngineConfig(
        num_slots=3, max_seq=32, prefill_len=16, page_size=8,
        async_depth=1))
    eng.warmup([1, 2, 3])
    for i, rid in enumerate(RIDS):
        eng.submit(Request(rid=rid, prompt=list(range(1, 6 + i)),
                           max_new_tokens=2 + i))
    out = tmp_path_factory.mktemp("trace")
    commits = []
    jax.profiler.start_trace(str(out))
    while not eng.idle:
        n = eng.decode_steps
        eng.step()
        commits.append(eng.decode_steps - n)
    jax.profiler.stop_trace()
    return out, commits


def _inside(spans, outer, name):
    return [s for s in spans if s.name == name and s.start >= outer.start
            and s.end <= outer.end]


def test_each_tick_holds_one_dispatch_and_its_commits(traced):
    trace_dir, commits = traced
    spans = pt.load(trace_dir).spans
    steps = [s for s in spans if s.name == "engine.step"]
    assert [s.stats["tick"] for s in steps] == sorted(
        s.stats["tick"] for s in steps)
    assert len(steps) == len(commits) and sum(commits) > 0
    for s, n in zip(steps, commits):
        assert len(_inside(spans, s, "engine.dispatch")) == 1
        assert len(_inside(spans, s, "engine.commit")) == n
    commit_spans = [s for s in spans if s.name == "engine.commit"]
    waits = [s for s in spans if s.name == "engine.commit.wait"]
    assert len(waits) == len(commit_spans) == sum(commits)
    for c in commit_spans:
        assert len(_inside(spans, c, "engine.commit.wait")) == 1
        assert len(_inside(spans, c, "engine.commit.apply")) == 1
    launches = [s for s in spans if s.name == "engine.launch"]
    assert len(launches) == sum(commits)
    for ln in launches:
        assert any(d.start <= ln.start and ln.end <= d.end for d in spans
                   if d.name == "engine.dispatch")


def test_admit_and_retire_carry_the_request_id(traced):
    spans = pt.load(traced[0]).spans
    admits = [s for s in spans if s.name == "engine.admit"]
    assert sorted(s.stats["rid"] for s in admits) == RIDS
    assert {s.stats["prompt_len"] for s in admits} == {5, 6, 7, 8, 9}
    for a in admits:
        assert len(_inside(spans, a, "engine.prefill")) == 1
        assert len(_inside(spans, a, "engine.insert")) == 1
    retires = [s for s in spans if s.name == "engine.retire"]
    assert sorted(s.stats["rid"] for s in retires) == RIDS
    applies = [s for s in spans if s.name == "engine.commit.apply"]
    for r in retires:
        assert any(a.start <= r.start and r.end <= a.end for a in applies)


def test_engine_spans_stay_out_of_the_benchmark_s_own(traced):
    spans = rt.load(traced[0]).spans
    assert not any(s.name.startswith("engine.") for s in spans)


def test_the_trace_keeps_each_program_s_scopes(traced):
    prog = pt.load(traced[0])
    steps = [run for run in prog.hlo if run.startswith("jit_step(")]
    assert steps
    scopes = set()
    for name in steps:
        scopes |= {"encode" if "/spike_codec/encode/" in o else "decode"
                   for o in prog.op_names(name).values()
                   if pt.is_codec(o)}
    assert scopes == {"encode", "decode"}
    assert prog.op_names("jit_nothing(999999)") == {}


# -- the readers on hand-built traces -----------------------------------


def _span(name, start, end, **stats):
    return Span(name, float(start), float(end), stats)


def _program():
    """Two ticks: the first admits a request (prefill of 72 bytes) and
    launches a step, the second launches one and waits 3 units on the
    device."""
    return Program([
        _span("engine.step", 0, 10, tick=1),
        _span("engine.dispatch", 0, 8),
        _span("engine.admit", 1, 4, rid="a", prompt_len=5),
        _span("engine.prefill", 1, 2, bytes=72),
        _span("engine.stage", 5, 6, bytes=168),
        _span("engine.launch", 6, 8),
        _span("engine.commit", 8, 10),
        _span("engine.commit.wait", 8, 9),
        _span("engine.step", 12, 22, tick=2),
        _span("engine.stage", 12, 13, bytes=168),
        _span("engine.launch", 13, 15),
        _span("engine.commit", 15, 22),
        _span("engine.commit.wait", 16, 19),
        _span("engine.retire", 20, 21, rid="a"),
        _span("engine.step", 40, 41, tick=3),    # outside the window
    ], hlo={"jit_step(11)": {"fusion.1": "jit(step)/spike_codec/encode/mul",
                             "fusion.2": "jit(step)/x/spike_codec/decode",
                             "fusion.3": "jit(step)/dot_general",
                             "while.8": "jit(step)/while"}})


def _device():
    """Two decode steps (programs that run the kernel), each a loop over
    the kernel, two codec fusions and another one."""
    ops, mods = [], []
    for o in (0.0, 12.0):
        mods.append(Event("jit_step(11)", o, o + 10))
        ops += [Event("while.8", o, o + 10),
                Event("paged_flash_decode.11", o + 1, o + 4),
                Event("fusion.1", o + 4, o + 5.5),
                Event("fusion.3", o + 5.5, o + 7),
                Event("fusion.2", o + 7, o + 7.5)]
    return Device(sorted(ops, key=lambda e: e.start), mods)


def _rec(program, devices=1):
    bench_spans = [Event("bench.step", 0, 11), Event("bench.step", 11, 30)]
    tr = Trace([_device() for _ in range(devices)], bench_spans)
    return types.SimpleNamespace(trace=tr, program=program)


def test_host_time_per_step_leaves_out_the_wait_on_the_device():
    rec = _rec(_program())
    # ticks in [0, 30]: 10 - 1 and 10 - 3 -> mean 8 (units are seconds)
    assert run.reader("engine_host_ms_per_step")(rec) == pytest.approx(
        8000.0)


def test_staged_bytes_per_launched_step():
    rec = _rec(_program())
    # (72 + 168 + 168) bytes over 2 launches, in kB
    assert run.reader("staged_kb_per_step")(rec) == pytest.approx(0.204)


def test_codec_time_per_decode_step_is_the_scoped_ops_self_time():
    rec = _rec(_program(), devices=2)
    # fusion.1 and fusion.2: 1.5 + 0.5 units a step, mean over chips
    assert run.reader("codec_ms_per_step")(rec) == pytest.approx(2000.0)
    unscoped = Program(_program().spans,
                       hlo={"jit_step(11)": {"fusion.3": "jit(step)/dot"}})
    assert run.reader("codec_ms_per_step")(_rec(unscoped)) is None


@pytest.mark.parametrize("metric", ["engine_host_ms_per_step",
                                    "staged_kb_per_step",
                                    "codec_ms_per_step"])
def test_new_readers_read_nothing_without_their_trace(metric):
    read = run.reader(metric)
    assert read(types.SimpleNamespace(trace=None)) is None
    # a program with no engine spans and no codec scope (an older one)
    assert read(_rec(Program([], hlo={}))) is None


def test_idle_gaps_are_named_by_the_innermost_engine_span():
    rec = _rec(_program())
    # the device idles in [8.5, 9.5], inside engine.commit.wait [8, 9],
    # engine.commit [8, 10] and engine.step [0, 10]
    tr = Trace([Device([Event("fusion.1", 0, 8.5),
                        Event("fusion.1", 9.5, 30)],
                       [Event("jit_step(11)", 0, 30)])],
               rec.trace.spans)
    assert pt.engine_gaps(tr, rec.program) == [["engine.commit.wait",
                                                pytest.approx(1.0)]]
    out = io.StringIO()
    pt.print_engine_gaps(tr, rec.program, out)
    assert "in engine.commit.wait" in out.getvalue()
