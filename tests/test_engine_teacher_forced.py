"""Serving engine vs the teacher-forced full-sequence forward.

Every served token must be the argmax of the teacher-forced forward over
prompt + served tokens, within a 1e-4 logit gap (``_teacher_forced``;
for coded codecs with the decode path's int8 rounding of the attention
output applied past the prompt), and the page pool must drain to zero.
Sweeps codec x spec_k x async_depth on reduced qwen1.5-0.5b (multi-head),
and a short sweep on the two grouped-KV configurations: reduced
qwen1.5-0.5b with 2 KV heads of 4, and reduced gemma2-2b with 2 KV heads
(local window 16, attention softcap 50), whose schedules run contexts
past the window.  At reduced width the attention scores stay far below
the softcap, so the gemma2 model's q/k projections are scaled up until
the cap binds: serving without it then flips tokens.
"""
import numpy as np
import pytest

from _hyp import given, settings, st
from _teacher_forced import assert_on_teacher_forced, teacher_forced_logits

PREFILL_LEN = 16
MAX_SEQ = 32
NUM_SLOTS = 3
VOCAB = 256
EOS = 7
TF_BATCH = 8

#: model name -> (registered config, overrides on its reduced variant,
#: factor on the q and k projection weights)
_CONFIGS = {
    "qwen": ("qwen1.5-0.5b", {}, 1.0),
    "qwen-gqa": ("qwen1.5-0.5b", {"n_kv_heads": 2}, 1.0),
    # x30 on q and k: scores x900, into the range where softcap 50 bends
    "gemma-gqa": ("gemma2-2b", {"n_kv_heads": 2}, 30.0),
}

_MODELS = {}
_ENGINES = {}
_ORACLES = {}


def _model(name, codec):
    key = (name, codec)
    if key not in _MODELS:
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import ShapeCell
        from repro.configs.reduced import reduced
        from repro.launch import specs as SP, train as TR
        from repro.launch.mesh import make_mesh

        base, over, qk = _CONFIGS[name]
        mesh = make_mesh((1, 1), ("data", "model"))
        hnn = "ann" if codec == "none" else "hnn"
        cfg = reduced(get_config(base, hnn_mode=hnn)).replace(
            dtype=jnp.float32, codec=codec, **over)
        cell = ShapeCell("serve_decode", MAX_SEQ, NUM_SLOTS, "decode")
        plan = SP.make_plan(cfg, cell, mesh)
        params = TR.init_sharded_params(cfg, plan, mesh,
                                        jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map_with_path(
            lambda p, x: x * qk if jax.tree_util.keystr(p).endswith(
                ("['wq']", "['wk']")) else x, params)
        _MODELS[key] = (cfg, mesh, params)
    return _MODELS[key]


def _engine(name, codec, spec_k, async_depth):
    key = (name, codec, spec_k, async_depth)
    if key not in _ENGINES:
        from repro.serving import EngineConfig, ServingEngine
        cfg, mesh, params = _model(name, codec)
        _ENGINES[key] = ServingEngine(
            cfg, mesh, params,
            EngineConfig(num_slots=NUM_SLOTS, max_seq=MAX_SEQ,
                         prefill_len=PREFILL_LEN, page_size=8, eos_id=EOS,
                         spec_k=spec_k, async_depth=async_depth))
    return _ENGINES[key]


def _oracle(name, codec):
    if (name, codec) not in _ORACLES:
        cfg, mesh, _ = _model(name, codec)
        _ORACLES[name, codec] = teacher_forced_logits(
            cfg, mesh, MAX_SEQ, TF_BATCH, round_decoded=codec != "none")
    return _ORACLES[name, codec]


def _serve_and_check(name, codec, spec_k, async_depth, schedule, seed=77):
    """Serve ``schedule`` ((prompt_len, max_new_tokens) pairs), hold each
    stream to the teacher-forced forward, and check the pool drained.
    Returns the longest context served."""
    from repro.serving import Request
    eng = _engine(name, codec, spec_k, async_depth)
    rng = np.random.RandomState(seed)
    reqs = [Request(rid=i, prompt=list(rng.randint(0, VOCAB, plen)),
                    max_new_tokens=mnt)
            for i, (plen, mnt) in enumerate(schedule)]
    res = eng.run(reqs)
    assert set(res) == {r.rid for r in reqs}
    for r in reqs:
        assert len(res[r.rid]) <= r.max_new_tokens
    _, _, params = _model(name, codec)
    assert_on_teacher_forced(
        _oracle(name, codec), params, [r.prompt for r in reqs],
        [res[r.rid] for r in reqs], what=(name, codec, spec_k, async_depth))
    alloc = eng.cache.allocator
    assert alloc.pages_in_use == 0 and alloc.pages_in_limbo == 0
    return max(len(r.prompt) + len(res[r.rid]) for r in reqs)


_SCHEDULE = [(16, 6), (3, 4), (9, 5), (1, 3), (12, 6)]
#: contexts up to 30 positions: past the reduced configs' window of 16
_LONG_SCHEDULE = [(16, 14), (5, 12), (12, 10), (2, 6)]


@pytest.mark.parametrize("codec", ["none", "spike_fused"])
@pytest.mark.parametrize("spec_k", [0, 2])
@pytest.mark.parametrize("async_depth", [0, 1])
def test_engine_matches_teacher_forced(codec, spec_k, async_depth):
    """Speculative and pipelined variants, both codecs: each served
    token is the teacher-forced argmax."""
    _serve_and_check("qwen", codec, spec_k, async_depth, _SCHEDULE)


@pytest.mark.slow
@settings(max_examples=5, deadline=None)
@given(schedule=st.lists(
    st.tuples(st.integers(1, PREFILL_LEN), st.integers(1, 8)),
    min_size=1, max_size=6))
def test_fuzz_engine_matches_teacher_forced(schedule):
    """Random schedules (queue pressure, mixed lengths, eos) through the
    sync vanilla engine — the cheapest combo, fuzzed hardest."""
    _serve_and_check("qwen", "none", 0, 0, schedule)


@pytest.mark.parametrize("name", ["qwen-gqa", "gemma-gqa"])
@pytest.mark.parametrize("codec,spec_k,async_depth", [
    ("none", 0, 0), ("spike_fused", 0, 1), ("spike_fused", 2, 1)])
def test_grouped_kv_engine_matches_teacher_forced(name, codec, spec_k,
                                                  async_depth):
    """Grouped KV (2 KV heads of 4), and for gemma2 the local window and
    the attention softcap (made to bind), through the engine's paged
    decode and verify."""
    cfg, _, _ = _model(name, codec)
    assert cfg.n_kv_heads == 2 < cfg.n_heads
    longest = _serve_and_check(name, codec, spec_k, async_depth,
                               _LONG_SCHEDULE)
    if name == "gemma-gqa":
        assert cfg.window == 16 and cfg.attn_softcap == 50.0
        assert longest > cfg.window
