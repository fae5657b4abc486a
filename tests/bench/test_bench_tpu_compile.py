"""Each cell's programs compiled at their real sizes for a described TPU
v5e (no chip attached): the engine's prefill, insert and decode step, on
one chip for the qwen1.5-0.5b cells and on a 1x4 mesh for qwen1.5-4b.
They must compile, hold the paged-decode kernel, fit a chip's 16 GB, and
on four chips carry int8 collectives.  Nothing touches the TPU library
while this module is imported: the topology is described, and skipped
where it cannot be, in a fixture."""
import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

HBM = 16e9
#: the benchmark's cells, and the cells whose files are kept for a later
#: change to add (their programs must keep compiling too)
CELLS = {w["name"]: w for w in [
    {"name": "qwen05b-spike-decode", "config": "qwen1.5-0.5b-hnn-spike",
     "traffic": "qwen05b-spike-decode", "chips": 1},
    {"name": "qwen05b-spike-chat", "config": "qwen1.5-0.5b-hnn-spike",
     "traffic": "qwen05b-spike-chat", "chips": 1},
    {"name": "qwen4b-spike-tp4-decode", "config": "qwen1.5-4b-hnn-spike-tp4",
     "traffic": "qwen4b-spike-tp4-decode", "chips": 4},
] + json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def compile_cell(topo, name):
    """{program: compiled} for one cell's prefill, insert and decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs.base import ShapeCell
    from repro.launch import specs as SP, train as TR
    from repro.serving.engine import (make_engine_decode_step,
                                      make_engine_prefill_step)
    from repro.serving.kv_cache import make_insert_fn
    from repro.serving.sampling import SamplingConfig
    from bench import serve

    cell = CELLS[name]
    config = json.loads((ROOT / "bench" / "configs"
                         / f"{cell['config']}.json").read_text())
    eng = json.loads((ROOT / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())["engine"]
    tp = int(config["tp"])
    mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp),
                ("data", "model"))
    cfg = serve.model_config(config)
    psz, pages = eng["page_size"], eng["num_pages"]
    plan = SP.make_plan(cfg, ShapeCell("serve_decode", eng["max_seq"],
                                       eng["num_slots"], "decode"), mesh)
    plan_pre = SP.make_plan(cfg, ShapeCell("serve_admit", eng["prefill_len"],
                                           1, "prefill"), mesh)

    def place(s, sp):
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=NamedSharding(mesh, sp))

    def placed(structs, specs):
        return jax.tree.map(place, structs, specs,
                            is_leaf=lambda x: isinstance(x, P))

    pstructs, pspecs = TR.abstract_sharded_params(cfg, plan)
    params = placed(pstructs, pspecs)
    ins, isp = SP.serve_decode_input_specs(plan, psz, pages)
    dec_args = [params] + [placed(ins[k], isp[k]) for k in (
        "cache", "token", "pos", "bt", "clp", "clo", "temp", "key")]
    scfg = SamplingConfig()
    out = {"decode": make_engine_decode_step(
        cfg, plan, mesh, scfg, psz, pages).lower(*dec_args).compile()}
    S = eng["prefill_len"]
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    pre_args = (params, place(jax.ShapeDtypeStruct((1, S), jnp.int32),
                              P(None, plan_pre.tp)),
                place(jax.ShapeDtypeStruct((1,), jnp.int32), P(None)),
                place(jax.ShapeDtypeStruct((1,), jnp.float32), P(None)),
                place(key, P()))
    out["prefill"] = make_engine_prefill_step(
        cfg, plan_pre, mesh, scfg).lower(*pre_args).compile()
    pre_structs, pre_specs = SP.cache_specs(plan_pre)
    pps = SP.pages_per_slot(eng["max_seq"], psz)
    ins_args = (dec_args[1], placed(pre_structs, pre_specs),
                place(jax.ShapeDtypeStruct((), jnp.int32), P()),
                place(jax.ShapeDtypeStruct((pps,), jnp.int32), P()))
    out["insert"] = make_insert_fn(plan, plan_pre, mesh, psz,
                                   pages).lower(*ins_args).compile()
    return out


def bytes_per_device(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_programs_compile_for_v5e(topo, name, monkeypatch):
    from repro.kernels import ops
    from repro.launch import roofline as RL
    # this process's backend is the CPU; the programs are compiled for the
    # described chip, so the kernel dispatch must take the TPU branch
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    progs = compile_cell(topo, name)
    dec = progs["decode"]
    assert 'custom_call_target="tpu_custom_call"' in dec.as_text()
    for prog, c in progs.items():
        assert bytes_per_device(c) < HBM, (prog, c.memory_analysis())
    # the pool is donated and updated in place: no pool-sized temp
    mem = dec.memory_analysis()
    assert mem.temp_size_in_bytes < mem.alias_size_in_bytes // 100
    if CELLS[name]["chips"] == 4:
        stats = RL.parse_collectives(dec.as_text())
        assert any(op.coded for op in stats.ops), stats.counts
