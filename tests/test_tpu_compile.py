"""AOT compiles for a described TPU v5e: what interpret mode cannot show.

The paged-decode kernel is compiled by the TPU compiler at real widths —
qwen1.5-0.5b (MHA, dh 64) for decode (K1=1) and spec verify (K1=3), with
and without the int8 wire epilogue, qwen1.5-4b at its tp=4 per-chip
widths (dh 128: 5 heads, or 20 over a quarter of the pages), and
gemma2-2b (GQA, dh 256, sliding window, softcap) — over a page pool that
it reads in place from HBM, and so is the serving engine's whole decode
step at qwen1.5-0.5b widths: the kernel's custom call is
named by the kernel itself, whatever jitted function encloses it, and
the codec's ops carry their named scope.  No chip is needed: the
topology is described, not attached.  Nothing touches the TPU library
while this module is imported; the fixture describes the topology, and
skips, only once a test of this file runs.
"""
import os
import re

import pytest

PAGES, PAGE, SLOTS, UNITS = 4096, 16, 8, 24


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
