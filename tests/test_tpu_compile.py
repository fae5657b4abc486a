"""AOT compiles for a described TPU v5e: what interpret mode cannot show.

The paged-decode kernel is compiled by the TPU compiler at real widths —
qwen1.5-0.5b (MHA, dh 64) for decode (K1=1) and spec verify (K1=3), with
and without the int8 wire epilogue, and gemma2-2b (GQA, dh 256, sliding
window, softcap) — over a 4096-page pool, and so is the serving engine's
whole decode step at qwen1.5-0.5b widths.  No chip is needed: the
topology is described, not attached.  Nothing touches the TPU library
while this module is imported; the fixture describes the topology, and
skips, only once a test of this file runs.
"""
import os

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
                    encode_wire=False):
    import jax
    import jax.numpy as jnp
    from repro.kernels.paged_decode import paged_decode_pallas

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ppc = 512 // PAGE
    pool = sds((UNITS, PAGES, PAGE, Hkv * dh), jnp.bfloat16)
    args = (sds((SLOTS, K1, Hq, dh), jnp.bfloat16), pool, pool,
            sds((SLOTS, ppc), jnp.int32), sds((SLOTS, ppc), jnp.int32),
            sds((SLOTS, K1), jnp.int32), sds((), jnp.int32))
    fn = jax.jit(lambda *a: paged_decode_pallas(
        *a, window=window, cap=cap, encode_wire=encode_wire))
    return fn.lower(*args).compile(), pool


def _check_kernel(compiled, pool):
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    # the pool is read in place: no relayout copy of it in front of the
    # kernel (a page-minor default layout would cost a full pool copy)
    pool_bytes = 2 * pool.size
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 100


@pytest.mark.parametrize("encode_wire", [False, True])
@pytest.mark.parametrize("K1", [1, 3])
def test_paged_decode_compiles_qwen_widths(one_chip, K1, encode_wire):
    _check_kernel(*_compile_kernel(one_chip, K1, Hq=16, Hkv=16, dh=64,
                                   encode_wire=encode_wire))


def test_paged_decode_compiles_gemma_gqa_window_softcap(one_chip):
    _check_kernel(*_compile_kernel(one_chip, 3, Hq=8, Hkv=4, dh=256,
                                   window=4096, cap=50.0, encode_wire=True))


def test_engine_decode_step_compiles_in_place(topo, monkeypatch):
    """The engine's full decode step at qwen1.5-0.5b widths on one chip:
    the kernel is in it, and the 6.4 GB KV pool is donated and updated
    in place — no step-sized temp buffer (a scanned-in/scanned-out pool
    or a relayout would each cost a whole pool copy)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.kernels import ops
    from repro.launch import specs as SP, train as TR
    from repro.serving.engine import make_engine_decode_step
    from repro.serving.sampling import SamplingConfig

    # this process's backend is the CPU; the step is compiled for the
    # described chip, so its kernel dispatch must take the TPU branch
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
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
    step = make_engine_decode_step(cfg, plan, mesh, SamplingConfig(), PAGE,
                                   PAGES)
    compiled = step.lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    mem = compiled.memory_analysis()
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(ins["cache"]))
    assert pool_bytes > 6e9
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100
