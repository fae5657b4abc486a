"""The four-chip cell ``qwen4b-spike-tp4-decode``.

Its files hold Qwen1.5-4B's published widths, split evenly over tp=4.  At
tiny sizes on fake CPU devices, with a head size of 128 (tp 2) and of 64
(tp 4), the engine's served tokens and layer-0 keys and values agree with
the reference within the cell's own limits, and the float8 control fails
them.  Every collective of the compiled tp=4 decode step but the
sampler's two reductions runs under the exchange scope, with the codec's
scope inside it, and the wire streams are those of the step before the
scope.  Last, the exchange reader on hand-built traces."""
import json
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench.program_trace import Program  # noqa: E402
from bench.reduce_trace import Device, Event, Trace  # noqa: E402

CELL_NAME = "qwen4b-spike-tp4-decode"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = run.cell_of(MANIFEST, CELL_NAME)
CONFIG = run.load_json(ROOT / "bench" / "configs" / f"{CELL['config']}.json")
TRAFFIC = run.load_json(ROOT / "bench" / "traffic"
                        / f"{CELL['traffic']}.json")
LIMITS = TRAFFIC["check"]["limits"]
#: https://huggingface.co/Qwen/Qwen1.5-4B, config.json
PUBLISHED = {"hidden_size": 2560, "intermediate_size": 6912,
             "num_hidden_layers": 40, "num_attention_heads": 20,
             "num_key_value_heads": 20, "vocab_size": 151936,
             "hidden_act": "silu", "qkv_bias": True,
             "tie_word_embeddings": False, "torch_dtype": "bfloat16"}
SEED = 2 ** 31 + 1515
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _devices_env(n):
    return dict(ENV, XLA_FLAGS=f"--xla_force_host_platform_device_count={n}")


def test_the_cell_serves_qwen1_5_4b_at_published_widths_over_four_chips():
    assert CELL["chips"] == 4 == int(CONFIG["tp"])
    assert {k: CONFIG[k] for k in PUBLISHED} == PUBLISHED
    entry = [c for c in MANIFEST["configs"] if c["name"] == CELL["config"]]
    assert len(entry) == 1 and entry[0]["reduced"] == []
    assert entry[0]["source"] == CONFIG["source"] \
        == "https://huggingface.co/Qwen/Qwen1.5-4B"
    tp = CONFIG["tp"]
    heads, d_ff = CONFIG["num_attention_heads"], CONFIG["intermediate_size"]
    assert heads % tp == 0 and CONFIG["num_key_value_heads"] % tp == 0
    assert d_ff % tp == 0
    assert (heads // tp, d_ff // tp) == (5, 1728)
    assert CONFIG["hidden_size"] // heads == 128
    assert (CONFIG["hnn_mode"], CONFIG["codec"]) == ("hnn", "spike_fused")
    assert TRAFFIC["loop"] == "closed" and TRAFFIC["clients"] == 48
    assert TRAFFIC["engine"] == {"num_slots": 48, "prefill_len": 1536,
                                 "max_seq": 2048, "page_size": 16,
                                 "num_pages": 6144, "async_depth": 1}
    # every slot's longest context fits in its share of the pool
    eng = TRAFFIC["engine"]
    assert eng["num_slots"] * eng["max_seq"] // eng["page_size"] \
        <= eng["num_pages"]


_AGREE = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    import repro.launch.compile_cache as cc
    from bench import run
    run.require_accelerator = lambda chips: jax.devices()[:chips]
    cc.enable_compile_cache = lambda: "off"
    manifest = json.loads({manifest!r})
    config, traffic = json.loads({config!r}), json.loads({traffic!r})
    cell = {{"name": {name!r}, "chips": {tp}}}
    res, _ = run.run_cell(manifest, cell, config, traffic, {seed}, 1.5,
                          False, time.perf_counter(), modes=("fp8",))
    print(json.dumps({{"correct": res["correct"],
                       "readings": res["readings"]}}), flush=True)
""")


@pytest.mark.parametrize("heads,tp", [(2, 2), (4, 4)],
                         ids=["dh128-tp2", "dh64-tp4"])
def test_engine_agrees_with_the_reference_and_the_control_does_not(heads,
                                                                    tp):
    from bench import check
    config = dict(CONFIG, hidden_size=256, intermediate_size=512,
                  num_hidden_layers=2, num_attention_heads=heads,
                  num_key_value_heads=heads, vocab_size=512, tp=tp)
    traffic = dict(
        TRAFFIC, clients=4, requests=64,
        prompt_len={"median": 16, "sigma": 0.5, "min": 8, "max": 32},
        output_len={"median": 8, "sigma": 0.5, "min": 4, "max": 16},
        engine={"num_slots": 4, "prefill_len": 32, "max_seq": 64,
                "page_size": 8, "num_pages": 64, "async_depth": 1},
        check=dict(TRAFFIC["check"], requests=3, slots=3))
    code = _AGREE.format(root=str(ROOT), src=str(ROOT / "src"),
                         manifest=json.dumps(MANIFEST),
                         config=json.dumps(config),
                         traffic=json.dumps(traffic), name=CELL_NAME,
                         tp=tp, seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=_devices_env(tp), capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    readings = out["readings"]
    assert out["correct"] is True, readings
    assert check.passed(check.verdict(readings, LIMITS))
    assert not check.passed(check.verdict(readings, LIMITS, "fp8_")), \
        readings


_STEP = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import roofline as RL, specs as SP, train as TR
    from repro.launch.mesh import make_mesh
    from repro.serving import EngineConfig, ServingEngine
    mesh = make_mesh((1, 4), ("data", "model"))
    cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode="hnn")).replace(
        dtype=jnp.float32, codec="spike_fused")
    plan = SP.make_plan(cfg, ShapeCell("serve_decode", 32, 4, "decode"),
                        mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, EngineConfig(
        num_slots=4, max_seq=32, prefill_len=16, page_size=8))
    hlo = eng.compiled_decode_step().as_text()
    print(json.dumps({
        "profile": eng.wire_stream_profile(),
        "collectives": [op.op_name for op in RL.parse_collectives(hlo).ops],
        "op_names": sorted(set(__import__("re").findall(
            r'op_name="([^"]*)"', hlo)))}))
""")

#: ``wire_stream_profile()`` of that tp=4 engine before the exchange had
#: a named scope: the scope changes HLO metadata only (each collective of
#: the two-layer scan counted once per layer)
FROZEN_TP4 = {"decode": {"all_gather": 15360.0, "head_all_gather": 5760.0,
                         "partial_combine": 9216.0, "psum": 384.0}}


def test_every_exchange_of_the_tp4_decode_step_carries_the_scope():
    p = subprocess.run([sys.executable, "-c", _STEP],
                       env=dict(_devices_env(4),
                                PYTHONPATH=str(ROOT / "src")),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["profile"] == FROZEN_TP4
    scoped = [n for n in out["collectives"]
              if "spike_exchange" in n.split("/")]
    rest = [n.rsplit("/", 1)[-1] for n in out["collectives"]
            if n not in scoped]
    # the head gathers, the partials' combine and the coded psums; the
    # sampler's distributed argmax is no exchange of the boundary
    assert len(scoped) == 12, out["collectives"]
    assert sorted(rest) == ["pmax", "pmin"]
    for part in ("encode", "decode"):
        assert any(f"/spike_exchange/spike_codec/{part}/" in n
                   for n in out["op_names"]), part


# -- the exchange reader on hand-built traces -------------------------------


def _program(scoped=True):
    exchange = "jit(step)/shard_map/while/body/spike_exchange"
    names = {"all-gather-start.1": f"{exchange}/coded_head_all_gather/"
                                   "all_gather",
             "fusion.1": f"{exchange}/spike_codec/encode/mul",
             "fusion.2": f"{exchange}/add",
             "fusion.3": "jit(step)/shard_map/while/body/dot_general",
             "while.8": "jit(step)/shard_map/while"}
    if not scoped:      # the same step before the scope
        names = {k: v.replace("spike_exchange/", "")
                 for k, v in names.items()}
    return Program([], hlo={"jit_step(11)": names})


def _device():
    """Two decode steps, each a loop over the kernel, a gather, a codec
    fusion with a nested op of its own, a matmul and a local sum; a
    program run without the kernel (a prefill) holds a scoped op too."""
    ops, mods = [], []
    for o in (0.0, 12.0):
        mods.append(Event("jit_step(11)", o, o + 10))
        ops += [Event("while.8", o, o + 10),
                Event("paged_flash_decode.11", o + 1, o + 4),
                Event("all-gather-start.1", o + 4, o + 4.5),
                Event("fusion.1", o + 4.5, o + 6),
                Event("fusion.3", o + 5, o + 5.5),
                Event("fusion.3", o + 6, o + 7),
                Event("fusion.2", o + 7, o + 7.25)]
    mods.append(Event("jit_step(12)", 24, 28))
    ops.append(Event("fusion.1", 25, 27))
    return Device(sorted(ops, key=lambda e: e.start), mods)


def _rec(program, devices=2):
    spans = [Event("bench.step", 0, 11), Event("bench.step", 11, 30)]
    return types.SimpleNamespace(
        trace=Trace([_device() for _ in range(devices)], spans),
        program=program)


def test_exchange_time_is_the_scoped_ops_self_time_per_decode_step():
    read = run.reader("exchange_ms_per_step")
    # a step: the gather 0.5, the codec fusion 1.5 less its nested 0.5,
    # the sum 0.25 -> 1.75 units, the same on both chips; the prefill's
    # scoped op is no decode step's
    assert read(_rec(_program())) == pytest.approx(1750.0)
    # the gather is the one collective op of the step
    assert run.reader("collective_ms_per_step")(_rec(_program())) == \
        pytest.approx(500.0)


def test_exchange_reader_reads_nothing_where_there_is_nothing_to_read():
    read = run.reader("exchange_ms_per_step")
    assert read(types.SimpleNamespace(trace=None)) is None
    # the program before the scope, and one whose trace kept no HLO
    assert read(_rec(_program(scoped=False))) is None
    assert read(_rec(Program([], hlo={}))) is None
