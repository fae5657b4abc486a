"""AOT compiles for a described TPU v5e: what interpret mode cannot show.

The paged-decode kernel is compiled by the TPU compiler at real widths —
qwen1.5-0.5b (MHA, dh 64) for decode (K1=1) and spec verify (K1=3), with
and without the int8 wire epilogue, qwen1.5-4b at its tp=4 per-chip
widths (dh 128: 5 heads, or 20 over a quarter of the pages), and
gemma2-2b (GQA, dh 256, sliding window, softcap) — over a page pool that
it reads in place from HBM, and so is the serving engine's whole decode
step at qwen1.5-0.5b widths: the kernel's custom call is
named by the kernel itself, whatever jitted function encloses it, and
the codec's ops carry their named scope.  The engine's admission insert
is compiled at each benchmark cell's sizes: it copies pages in place
and stages nothing the size of ``max_seq``.  No chip is needed: the
topology is described, not attached.  Nothing touches the TPU library
while this module is imported; the fixture describes the topology, and
skips, only once a test of this file runs.
"""
import json
import os
import re
import sys
from pathlib import Path

import pytest

PAGES, PAGE, SLOTS, UNITS = 4096, 16, 8, 24
ROOT = Path(__file__).resolve().parents[1]
#: benchmark cell -> (its configuration, its traffic) under bench/
BENCH_CELLS = {
    "qwen05b-spike-decode": ("qwen1.5-0.5b-hnn-spike",
                             "qwen05b-spike-decode"),
    "qwen4b-spike-tp4-decode": ("qwen1.5-4b-hnn-spike-tp4",
                                "qwen4b-spike-tp4-decode"),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(one_chip, K1, Hq, Hkv, dh, window=0, cap=0.0,
                    encode_wire=False, pages=PAGES):
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_decode import paged_decode_pallas

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ppc = 512 // PAGE
    pool = sds((UNITS, pages, PAGE, Hkv * dh), jnp.bfloat16)
    args = (sds((SLOTS, K1, Hq, dh), jnp.bfloat16), pool, pool,
            sds((SLOTS, ppc), jnp.int32), sds((SLOTS, ppc), jnp.int32),
            sds((SLOTS, K1), jnp.int32), sds((), jnp.int32))
    def kernel(*a):
        return paged_decode_pallas(*a, window=window, cap=cap,
                                   encode_wire=encode_wire)
    return (jax.jit(kernel).lower(*args).compile(), pool,
            jax.make_jaxpr(kernel)(*args))


def _kernel_calls(hlo: str) -> list:
    """Names of the Pallas custom calls in an HLO text."""
    return [re.match(r"\s*(?:ROOT )?%(\S+) = ", ln).group(1)
            for ln in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]


def _check_kernel(compiled, pool, jaxpr):
    from jax.experimental import pallas as pl
    hlo = compiled.as_text()
    # compiled inside an anonymous jit: the name is the kernel's own
    calls = _kernel_calls(hlo)
    assert calls and all(c.startswith("paged_flash_decode") for c in calls)
    # the kernel takes both pools whole in HBM (memory space ANY) and
    # copies its pages itself: its last two operands are the jit's pool
    # parameters as they came in, with nothing in between
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    pools = call.params["grid_mapping"].block_mappings[2:4]
    assert all(m.block_aval.memory_space == pl.ANY
               and m.block_aval.shape == pool.shape for m in pools)
    entry = hlo[hlo.index("\nENTRY "):]
    params = dict(re.findall(r"%(\S+) = \S+ parameter\(([12])\)", entry))
    (operands,) = re.findall(
        r"custom-call\((.*)\), custom_call_target=\"tpu_custom_call\"",
        entry)
    operands = [o.split("*/")[-1].lstrip("%") for o in operands.split(", ")]
    assert [params.get(o) for o in operands[-2:]] == ["1", "2"]
    # the pool is read in place: no relayout copy of it in front of the
    # kernel (a page-minor default layout would cost a full pool copy)
    pool_bytes = 2 * pool.size
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 100


@pytest.mark.parametrize("encode_wire", [False, True])
@pytest.mark.parametrize("K1", [1, 3])
def test_paged_decode_compiles_qwen_widths(one_chip, K1, encode_wire):
    _check_kernel(*_compile_kernel(one_chip, K1, Hq=16, Hkv=16, dh=64,
                                   encode_wire=encode_wire))


@pytest.mark.parametrize("heads,pages", [
    pytest.param(5, PAGES, id="head-sharded"),
    pytest.param(20, 6144 // 4, id="page-sharded")])
def test_paged_decode_compiles_qwen4b_tp4_chip_widths(one_chip, heads,
                                                      pages):
    """A chip's share of qwen1.5-4b at tp=4: 5 of the 20 heads, or, as
    the engine shards its pool, all 20 heads over a quarter of the cell's
    6144 pages (2560 lanes, so 12 pages a block within the ring's VMEM)."""
    _check_kernel(*_compile_kernel(one_chip, 1, Hq=heads, Hkv=heads,
                                   dh=128, encode_wire=True, pages=pages))


def test_paged_decode_compiles_gemma_gqa_window_softcap(one_chip):
    _check_kernel(*_compile_kernel(one_chip, 3, Hq=8, Hkv=4, dh=256,
                                   window=4096, cap=50.0, encode_wire=True))


@pytest.fixture(scope="module")
def decode_step(topo):
    """(compiled decode step, its input shapes): the engine's full decode
    step at qwen1.5-0.5b widths, spike_fused, on one described chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.kernels import ops
    from repro.launch import specs as SP, train as TR
    from repro.serving.engine import make_engine_decode_step
    from repro.serving.sampling import SamplingConfig

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    cfg = get_config("qwen1.5-0.5b", hnn_mode="hnn", codec="spike_fused")
    plan = SP.make_plan(cfg, ShapeCell("serve_decode", 512, SLOTS,
                                       "decode"), mesh)
    pstructs, pspecs = TR.abstract_sharded_params(cfg, plan)
    ins, isp = SP.serve_decode_input_specs(plan, PAGE, PAGES)

    def place(s, sp):
        return jax.ShapeDtypeStruct(s.shape, s.dtype,
                                    sharding=NamedSharding(mesh, sp))

    args = [jax.tree.map(place, pstructs, pspecs)] + [
        jax.tree.map(place, ins[k], isp[k])
        for k in ("cache", "token", "pos", "bt", "clp", "clo", "temp",
                  "key")]
    # this process's backend is the CPU; the step is compiled for the
    # described chip, so its kernel dispatch must take the TPU branch
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_on_tpu", lambda: True)
        step = make_engine_decode_step(cfg, plan, mesh, SamplingConfig(),
                                       PAGE, PAGES)
        return step.lower(*args).compile(), ins


def test_engine_decode_step_compiles_in_place(decode_step):
    """The kernel is in the decode step, and the 6.4 GB KV pool is donated
    and updated in place — no step-sized temp buffer (a
    scanned-in/scanned-out pool or a relayout would each cost a whole
    pool copy)."""
    import jax
    compiled, ins = decode_step
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(ins["cache"]))
    assert pool_bytes > 6e9
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100


def _computations(hlo: str) -> dict:
    """{computation name -> its body lines} of an HLO text."""
    out, cur = {}, None
    for ln in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) .*\{$", ln)
        if m:
            cur = out.setdefault(m.group(1), [])
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(ln)
    return out


def test_engine_decode_step_names_its_kernel_and_codec(decode_step):
    """The layer scan's body holds exactly one paged-decode custom call,
    named ``paged_flash_decode`` by the kernel (the trace reduction finds
    the kernel and the decode step by that name), and the spike codec's
    fusions carry the ``spike_codec`` scope in their op_name."""
    hlo = decode_step[0].as_text()
    comps = _computations(hlo)
    bodies = set(re.findall(r"\bwhile\(.*\bbody=%([\w.-]+)", hlo))
    with_kernel = {name: _kernel_calls("\n".join(lines))
                   for name, lines in comps.items()
                   if _kernel_calls("\n".join(lines))}
    assert with_kernel and set(with_kernel) <= bodies
    for calls in with_kernel.values():
        assert len(calls) == 1 and calls[0].startswith("paged_flash_decode")
    codec = [ln for ln in hlo.splitlines()
             if " fusion(" in ln and "/spike_codec/" in ln]
    assert codec


def _compile_insert(topo, cell):
    """(compiled insert, pages_per_slot, bytes of the gathered prefill
    rows) of the engine at ``cell``'s sizes, fed the prefill's hand-off
    as the engine's prefill program returns it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs.base import ShapeCell
    from repro.launch import specs as SP
    from repro.serving.kv_cache import make_insert_fn
    sys.path.insert(0, str(ROOT))
    from bench import serve

    config_name, traffic_name = BENCH_CELLS[cell]
    config = json.loads((ROOT / "bench" / "configs"
                         / f"{config_name}.json").read_text())
    eng = json.loads((ROOT / "bench" / "traffic"
                      / f"{traffic_name}.json").read_text())["engine"]
    tp = int(config["tp"])
    mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, tp),
                ("data", "model"))
    cfg = serve.model_config(config)
    psz, pages = eng["page_size"], eng["num_pages"]
    plan = SP.make_plan(cfg, ShapeCell("serve_decode", eng["max_seq"],
                                       eng["num_slots"], "decode"), mesh)
    plan_pre = SP.make_plan(cfg, ShapeCell(
        "serve_admit", eng["prefill_len"], 1, "prefill"), mesh)

    def placed(structs, specs):
        return jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            structs, specs, is_leaf=lambda x: isinstance(x, P))

    pps = SP.pages_per_slot(eng["max_seq"], psz)
    pre = SP.prefill_handoff_specs(plan_pre)
    args = (placed(*SP.paged_cache_specs(plan, psz, pages)), placed(*pre),
            placed(jax.ShapeDtypeStruct((), jnp.int32), P()),
            placed(jax.ShapeDtypeStruct((pps,), jnp.int32), P()))
    gathered = 0 if tp == 1 else sum(
        s.size * s.dtype.itemsize for s in jax.tree.leaves(pre[0]))
    compiled = make_insert_fn(plan, plan_pre, mesh, psz,
                              pages).lower(*args).compile()
    return compiled, pps, psz, gathered


@pytest.mark.parametrize("cell", sorted(BENCH_CELLS))
def test_engine_insert_copies_pages_in_place(topo, cell):
    """The insert updates the donated pool in place and its temp holds at
    most the prefill rows gathered over tp (none on one chip) and 16 MB
    besides; no buffer of it is ``max_seq`` rows long, as a source of
    ``pages_per_slot x page_size`` rows staged for a scatter would be."""
    compiled, pps, psz, gathered = _compile_insert(topo, cell)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 1e9
    assert mem.temp_size_in_bytes <= gathered + 16e6, mem
    hlo = compiled.as_text()
    for dims in re.findall(r"\b[a-z]+\d*\[([\d,]+)\]", hlo):
        d = [int(x) for x in dims.split(",")]
        assert pps * psz not in d, dims
        assert (pps, psz) not in zip(d, d[1:]), dims
    assert "/kv_insert/" in hlo
