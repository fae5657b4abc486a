"""What the serving engine counts and names for tracing: the host bytes it
stages per step against a hand count from the feed shapes, the codec's
named scope in the compiled decode step, and the per-collective wire
streams the scope must leave as they were."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, MAX_SEQ, PREFILL, PAGE = 3, 32, 16, 8

@pytest.fixture(scope="module")
def engine():
    """A tiny spike_fused engine, warmed up (built once: compile cost)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.launch.mesh import make_mesh
    from repro.serving import EngineConfig, ServingEngine

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode="hnn")).replace(
        dtype=jnp.float32, codec="spike_fused")
    plan = SP.make_plan(cfg, ShapeCell("serve_decode", MAX_SEQ, SLOTS,
                                       "decode"), mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, EngineConfig(
        num_slots=SLOTS, max_seq=MAX_SEQ, prefill_len=PREFILL,
        page_size=PAGE))
    eng.warmup([1, 2, 3])
    return eng


def test_staged_bytes_match_the_feed_shapes(engine):
    """One pool shard: a decode dispatch stages the block table and the
    two page lists ([slots, pages_per_slot] int32 each) and positions and
    temperatures ([slots] 4-byte each); an admission stages its padded
    prompt, last position and temperature.  reset_stats zeroes both
    counters."""
    from repro.serving import Request
    eng = engine
    eng.reset_stats()
    assert eng.staged_bytes == 0 and eng.queue_wait_s == 0.0
    pages_per_slot = MAX_SEQ // PAGE
    decode = 3 * SLOTS * pages_per_slot * 4 + 2 * SLOTS * 4
    prefill = PREFILL * 4 + 4 + 4
    eng.submit(Request(rid="a", prompt=[1, 2, 3, 4, 5], max_new_tokens=4))
    eng.step()                  # admits, then dispatches (and commits)
    assert eng.decode_steps == 1
    assert eng.staged_bytes == prefill + decode
    assert eng.queue_wait_s > 0.0
    eng.step()                  # no admission: the decode feeds alone
    assert eng.staged_bytes == prefill + 2 * decode
    eng.flush()
    while not eng.idle:
        eng.step()
    eng.reset_stats()
    assert eng.staged_bytes == 0 and eng.queue_wait_s == 0.0


@pytest.mark.parametrize("prompt_len,pages", [(3, 1), (8, 2)])
def test_kv_blocks_walked_counts_each_launch(engine, tmp_path, prompt_len,
                                             pages):
    """One pool shard, page 8, 32-token slots: 4-entry lists, one block
    each, so every launch computes one block per slot, live or free; the
    launch span carries the same count as its ``kv_blocks`` stat.  The
    kernel copies the mapped pages alone, the allocator's fill: a 3-token
    prompt decodes at positions 3 and 4, inside its first page, an
    8-token one at 8 and 9, on a second page mapped for the first step;
    the ``kv_pages`` stat of each launch says so, and
    ``kv_pages_fetched`` sums them."""
    import glob
    import jax
    from repro.serving import Request
    eng = engine
    assert eng.cache.kv_block_pages == MAX_SEQ // PAGE
    eng.reset_stats()
    eng.submit(Request(rid="b", prompt=list(range(1, prompt_len + 1)),
                       max_new_tokens=4))
    jax.profiler.start_trace(str(tmp_path))
    eng.step()
    assert eng.cache.kv_pages_fetched() == eng.cache.allocator.pages_in_use
    eng.step()
    jax.profiler.stop_trace()
    assert eng.decode_steps == 2
    assert eng.kv_blocks_walked == 2 * SLOTS
    assert eng.kv_pages_fetched == 2 * pages
    pb = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                       "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(pb[0])
    stats = [dict(e.stats)
             for plane in data.planes if plane.name.startswith("/host:")
             for ln in plane.lines for e in ln.events
             if e.name == "engine.launch"]
    assert [st.get("kv_blocks") for st in stats] == [SLOTS, SLOTS]
    assert [st.get("kv_pages") for st in stats] == [pages, pages]
    eng.flush()
    while not eng.idle:
        eng.step()
    eng.reset_stats()
    assert eng.kv_blocks_walked == 0 and eng.kv_pages_fetched == 0


def test_codec_ops_carry_the_named_scope(engine):
    """The compiled decode step's codec ops say ``spike_codec/encode`` and
    ``spike_codec/decode`` in their op_name, and no other op does."""
    import re
    hlo = engine.compiled_decode_step().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    codec = [n for n in names if "spike_codec" in n.split("/")]
    assert any("/spike_codec/encode/" in n for n in codec)
    assert any("/spike_codec/decode/" in n for n in codec)
    assert all(re.search(r"/spike_codec/(encode|decode)/", n)
               for n in codec)


_PROFILE = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.launch.mesh import make_mesh
    from repro.serving import EngineConfig, ServingEngine
    mesh = make_mesh((1, 2), ("data", "model"))
    cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode="hnn")).replace(
        dtype=jnp.float32, codec="spike_fused")
    plan = SP.make_plan(cfg, ShapeCell("serve_decode", 24, 4, "decode"),
                        mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, EngineConfig(
        num_slots=4, max_seq=24, prefill_len=8, page_size=8, spec_k=2))
    print(json.dumps(eng.wire_stream_profile()))
""")

#: ``wire_stream_profile()`` of that engine before the codec had a named
#: scope: the scope changes HLO metadata only, so the streams stay these
#: (each collective of the two-layer scan counted once per layer)
FROZEN = {"decode": {"all_gather": 2560.0, "head_all_gather": 1920.0,
                     "partial_combine": 1536.0, "psum": 128.0},
          "verify": {"all_gather": 7680.0, "head_all_gather": 5760.0,
                     "partial_combine": 4608.0, "psum": 384.0}}


def test_wire_stream_profile_is_unchanged_by_the_codec_scope():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", _PROFILE], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got == FROZEN


@pytest.mark.parametrize("mode", ["int8", "sparse_topk"])
def test_other_codecs_scope_their_local_ops(mode):
    """The roundtrip of the other coded modes runs under the scope too."""
    import jax
    import jax.numpy as jnp
    from repro.core import boundary, spike
    codec = boundary.BoundaryCodec(mode=mode, capacity=0.5)
    p = spike.init_spike_params(16)
    x = jnp.linspace(-1, 1, 32).reshape(2, 16)
    hlo = jax.jit(lambda a: boundary.wire_roundtrip(a, p, codec)).lower(
        x).as_text(debug_info=True)
    assert "spike_codec" in hlo


@pytest.mark.parametrize("fn", ["wire_roundtrip", "coded_psum",
                                "coded_all_gather", "coded_psum_scatter"])
def test_each_boundary_scopes_its_encode_and_decode(fn):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import boundary, spike
    from repro.launch.mesh import make_mesh
    p, codec = spike.init_spike_params(16), boundary.HNN_FUSED
    call = {
        "wire_roundtrip": lambda x: boundary.wire_roundtrip(x, p, codec),
        "coded_psum": lambda x: boundary.coded_psum(x, p, codec, "model"),
        "coded_all_gather": lambda x: boundary.coded_all_gather(
            x, p, codec, "model"),
        "coded_psum_scatter": lambda x: boundary.coded_psum_scatter(
            x, p, codec, "model"),
    }[fn]
    mesh = make_mesh((1, 1), ("data", "model"))
    f = jax.jit(jax.shard_map(call, mesh=mesh, in_specs=P(), out_specs=P(),
                              check_vma=False))
    txt = f.lower(jnp.linspace(-1, 1, 64).reshape(4, 16)).as_text(
        debug_info=True)
    assert "spike_codec/encode" in txt and "spike_codec/decode" in txt
