"""Multi-device scenarios run in subprocesses (8 fake CPU devices).

Invoked by tests/test_distributed.py as:
    python tests/dist_scenarios.py <scenario>
Exit code 0 = pass.  XLA device-count env must be set before jax import,
which is why these run out-of-process (smoke tests elsewhere keep 1
device per the dry-run contract).
"""
import dataclasses
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


def mesh24():
    from repro.launch.mesh import make_mesh
    return make_mesh((2, 4), ("data", "model"))


def scenario_boundary_codecs():
    from repro.core import boundary, spike
    mesh = mesh24()
    D = 64
    bp = spike.init_spike_params(D)
    sm = lambda f, ins, outs: jax.shard_map(f, mesh=mesh, in_specs=ins,
                                            out_specs=outs, check_vma=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (32, D)) * 0.5
    for name, codec, tol in [
            ("none", boundary.ANN, 1e-6),
            ("int8", boundary.BoundaryCodec(mode="int8"), 0.02),
            ("spike", boundary.HNN_FAITHFUL, 0.2),
            ("spike_fused", boundary.HNN_FUSED, 0.2),
            ("spike_pack4", boundary.HNN_PACK4, 0.25),
            ("sparse_topk",
             boundary.BoundaryCodec(mode="sparse_topk", capacity=0.99), 0.3)]:
        def f(xx, t, l):
            return boundary.coded_all_gather(
                xx, {"theta": t, "log_scale": l}, codec, "model", axis=0)
        fm = sm(f, (P(("data", "model")), P(), P()), P("data"))
        y = fm(x, bp["theta"], bp["log_scale"])
        err = float(jnp.sqrt(jnp.mean((y - x) ** 2))
                    / jnp.sqrt(jnp.mean(x ** 2)))
        assert err <= tol, (name, err)
        g = jax.grad(lambda a, t, l: fm(a, t, l).sum())(
            x, bp["theta"], bp["log_scale"])
        assert np.isfinite(np.array(g)).all(), name
    # faithful == fused on the wire
    c1 = spike.encode(x, bp, spike.SpikeConfig(T=15, faithful=True))
    c2 = spike.encode(x, bp, spike.SpikeConfig(T=15, faithful=False))
    assert (np.array(c1) == np.array(c2)).all()
    print("boundary codecs OK")


def scenario_train_archs():
    from repro.configs import get_config, list_archs
    from repro.configs.base import smoke_shape
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    mesh = mesh24()
    cell = smoke_shape("train")
    names = sys.argv[2].split(",") if len(sys.argv) > 2 else list_archs()
    for name in names:
        cfg = reduced(get_config(name))
        plan = SP.make_plan(cfg, cell, mesh)
        step, *_ = TR.make_train_step(cfg, plan, mesh, with_optimizer=False)
        params = TR.init_sharded_params(cfg, plan, mesh,
                                        jax.random.PRNGKey(0))
        B, S = cell.global_batch, cell.seq_len
        tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                 cfg.vocab, jnp.int32)
        batch = {"tokens": tok, "labels": jnp.roll(tok, -1, axis=1)}
        if cfg.is_encdec:
            batch["enc_embeds"] = jax.random.normal(
                jax.random.PRNGKey(2), (B, S // 2, cfg.d_model),
                cfg.dtype) * 0.1
            batch["tokens"] = tok[:, :S // 2]
            batch["labels"] = batch["tokens"]
        if cfg.rope_kind == "mrope":
            batch["positions3"] = jnp.broadcast_to(
                jnp.arange(S)[None, None], (3, B, S)).astype(jnp.int32)
        loss, grads, metrics = step(params, batch)
        l = float(metrics["loss"])
        assert np.isfinite(l), (name, l)
        gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
        assert np.isfinite(gn) and gn > 0, name
        print(f"train OK {name} loss={l:.3f}")


def scenario_decode_chain():
    import jax.tree_util as jtu
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR, serve as SV
    mesh = mesh24()
    for name, B in (("gemma2-2b", 2), ("jamba-1.5-large-398b", 1),
                    ("xlstm-125m", 2)):
        cfg = reduced(get_config(name)).replace(hnn_mode="ann")
        S = 16
        cell = ShapeCell("d", S, B, "decode")
        plan = SP.make_plan(cfg, cell, mesh)
        params = TR.init_sharded_params(cfg, plan, mesh,
                                        jax.random.PRNGKey(0))
        pre, *_ = SV.make_prefill_step(cfg, plan, mesh)
        dec, _, _ = SV.make_decode_step(cfg, plan, mesh)
        structs, _ = SP.decode_input_specs(plan)
        tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                 cfg.vocab, jnp.int32)
        logits_pre, _ = pre(params, {"tokens": tok, "labels": tok})

        def init_leaf(path, s):
            if any(getattr(p, "key", None) == "pp" for p in path):
                return jnp.full(s.shape, -1e30, s.dtype)
            return jnp.zeros(s.shape, s.dtype)
        cache = jtu.tree_map_with_path(
            init_leaf, structs["cache"],
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        for t in range(S):
            logits_dec, cache = dec(params, cache, tok[:, t],
                                    jnp.asarray(t, jnp.int32))
        a = np.array(logits_pre, np.float32)
        b = np.array(logits_dec, np.float32)
        err = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
        assert err < 0.05, (name, err)
        print(f"decode chain OK {name} err={err:.4f}")


def scenario_mini_dryrun():
    """lower+compile train/decode on the 8-device mesh, parse collectives."""
    from repro.configs import get_config, SHAPES
    from repro.configs.base import ShapeCell
    from repro.launch import roofline as RL, specs as SP, train as TR
    from repro.optim import adamw
    mesh = mesh24()
    cfg = get_config("qwen1.5-0.5b")
    cell = ShapeCell("t", 512, 8, "train")
    plan = SP.make_plan(cfg, cell, mesh)
    step, *_ = TR.make_train_step(cfg, plan, mesh, with_optimizer=True)
    ap, _ = TR.abstract_sharded_params(cfg, plan)
    aopt = adamw.abstract_opt_state(ap)
    ab, _ = SP.train_input_specs(plan)
    compiled = step.lower(ap, aopt, ab).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):      # older jax: one dict per partition
        cost = cost[0]
    assert cost.get("flops", 0) > 0
    stats = RL.parse_collectives(compiled.as_text())
    assert stats.wire_bytes > 0 and len(stats.counts) >= 2, stats.counts
    # bug regression: group sizes come from the HLO (replica_groups /
    # num_partitions), so wire bytes must be invariant to the caller's
    # default_group — the old hardwired n=2 guess mis-scaled tp=4 rings
    for dg in (2, 4, 16):
        alt = RL.parse_collectives(compiled.as_text(), default_group=dg)
        assert alt.wire_bytes == stats.wire_bytes, (dg, alt.wire_bytes,
                                                    stats.wire_bytes)
    assert all(op.group > 1 for op in stats.ops), \
        sorted({op.group for op in stats.ops})
    assert sum(stats.by_stream.values()) == stats.wire_bytes or \
        abs(sum(stats.by_stream.values()) - stats.wire_bytes) < 1e-6
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes > 0
    print("mini dryrun OK:", dict(stats.counts), "streams:",
          sorted(stats.by_stream))


def scenario_serving_wire_streams():
    """Per-collective wire streams of a compiled serving engine on the
    (2, 4) mesh: ``wire_stream_profile()`` must classify the coded
    boundary's collectives into semantic streams (head_all_gather from
    the named scope at minimum, psum/all_gather from kind fallback),
    sum exactly to the scalar ``decode_wire_stats`` accounting, and —
    threaded through an ``SLOMonitor`` — reappear per tick in the step
    trace with the same totals the closed-form and cycle-level NoC
    bridges then price consistently (cycle-level >= closed form)."""
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.serving import (EngineConfig, Request, ServingEngine,
                               SLOMonitor)
    from repro.sim.noc import NocConfig, NocSim, emio_cost_from_trace
    mesh = mesh24()
    cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode="hnn")).replace(
        dtype=jnp.float32, codec="spike_fused")
    kw = dict(num_slots=4, max_seq=24, prefill_len=8, page_size=8)
    plan = SP.make_plan(cfg, ShapeCell("serve_decode", kw["max_seq"],
                                       kw["num_slots"], "decode"), mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, EngineConfig(**kw))
    profile = eng.wire_stream_profile()
    dec = profile["decode"]
    assert "head_all_gather" in dec, sorted(dec)
    assert len(dec) >= 2, sorted(dec)
    stats, per_tok = eng.decode_wire_stats()
    ndev = 8
    assert abs(sum(dec.values()) - stats.wire_bytes * ndev) < 1e-6, (
        sum(dec.values()), stats.wire_bytes * ndev)
    # thread through a monitor over a real run: per-tick stream splits
    # must sum to the scalar wire bytes, and the cycle-level NoC figure
    # must bound the closed-form EMIO figure
    mon = SLOMonitor(wire_streams_per_step=profile)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, cfg.vocab, 8)) for _ in range(4)]
    eng.observers.append(mon)
    eng.run([Request(rid=i, prompt=p, max_new_tokens=6)
             for i, p in enumerate(prompts)], on_step=mon.on_step)
    trace = mon.step_trace()
    assert any(s["wire_bytes"] > 0 for s in trace)
    for s in trace:
        assert abs(sum(s["wire_streams"].values()) - s["wire_bytes"]) \
            < 1e-6, s
    cosim = NocSim(NocConfig()).simulate_trace(trace)
    closed = emio_cost_from_trace(trace)
    assert cosim.total_cycles >= closed["emio_cycles"], (
        cosim.total_cycles, closed["emio_cycles"])
    print(f"serving wire streams OK: {sorted(dec)} "
          f"cyc={cosim.total_cycles:.0f}>=closed={closed['emio_cycles']:.0f}")


def scenario_elastic_checkpoint():
    """Save on (2,4) mesh, restore re-sharded onto (1,8)."""
    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config
    from repro.configs.base import smoke_shape
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.launch.mesh import make_mesh
    import tempfile
    mesh_a = mesh24()
    mesh_b = make_mesh((1, 8), ("data", "model"))
    cfg = reduced(get_config("qwen1.5-0.5b")).replace(
        d_model=64, n_heads=8, n_kv_heads=8)
    cell = smoke_shape("train")
    plan_a = SP.make_plan(cfg, cell, mesh_a)
    plan_b = SP.make_plan(cfg, cell, mesh_b)
    params = TR.init_sharded_params(cfg, plan_a, mesh_a,
                                    jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(3, params)
        _, pspecs_b, _ = TR.shard_params_specs(cfg, plan_b)
        restored, step = mgr.restore(params, mesh=mesh_b, specs=pspecs_b)
        assert step == 3
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("elastic checkpoint OK")


def scenario_compressed_psum():
    from repro.optim.compress import psum_compressed
    mesh = mesh24()
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 33)) * 2

    def f(g):
        out, err = psum_compressed(g, "model")
        return out, err
    fm = jax.shard_map(f, mesh=mesh, in_specs=P(("data", "model")),
                       out_specs=(P(("data", "model")),
                                  P(("data", "model"))), check_vma=False)
    out, err = fm(x)
    # reference: exact psum over model of replicated? x is sharded; each
    # model-group of 4 shards sums -> compare against exact groupwise sum
    xs = np.array(x).reshape(2, 4, 1, 33)
    exact = xs.sum(axis=1, keepdims=True).repeat(4, axis=1).reshape(8, 1, 33)[:, 0]
    rel = np.abs(np.array(out) - exact).max() / (np.abs(exact).max() + 1e-9)
    assert rel < 0.05, rel
    print("compressed psum OK rel", rel)




def scenario_analytic_crosscheck():
    """Analytic wire model vs HLO-parsed collectives (same mesh/plan).

    The parsed per-unit wire bytes must agree with the analytic per-unit
    boundary+FSDP bytes to within 2x (the analytic model intentionally
    ignores reshape paddings and sub-10%% glue collectives)."""
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.launch import analytic as AN, roofline as RL, specs as SP, \
        train as TR
    mesh = mesh24()
    cfg = get_config("qwen1.5-0.5b")
    cell = ShapeCell("t", 512, 8, "train")
    plan = SP.make_plan(cfg, cell, mesh)
    step, *_ = TR.make_train_step(cfg, plan, mesh, with_optimizer=False,
                                  microbatches=1)
    ap, _ = TR.abstract_sharded_params(cfg, plan)
    ab, _ = SP.train_input_specs(plan)
    compiled = step.lower(ap, ab).compile()
    stats = RL.parse_collectives(compiled.as_text())
    # structural expectation for the PARSED module (scan bodies counted
    # once per layer): each unit's boundary+FSDP wire in the fwd, remat
    # and grad-RS passes, plus the embedding/LM-head weight gathers
    # outside the scan
    w = AN.wire_bytes_per_elem(cfg.codec)
    tp, dp = 4, 2
    B_loc, S = 8 // dp, 512
    per_unit = AN.block_cost("attn", cfg, B_loc, S, tp, dp, w).wire
    D, Vp = cfg.d_model, cfg.vocab_padded(tp)
    emb_gather = (dp - 1) / dp * (Vp * D * 2.0 / tp)   # per fwd pass
    expected = (per_unit * 3 * cfg.n_layers
                + 2 * emb_gather * 4)                  # embed+head, ~4 passes
    ratio = stats.wire_bytes / max(expected, 1.0)
    assert 0.3 <= ratio <= 3.0, (stats.wire_bytes, expected, ratio)
    print(f"analytic crosscheck OK: parsed={stats.wire_bytes/1e6:.1f}MB "
          f"expected={expected/1e6:.1f}MB ratio={ratio:.2f}")


def scenario_decode_replicated_weights():
    """replicate_weights=True must be numerically identical to the
    FSDP-sharded decode path (same params, same logits)."""
    import jax.tree_util as jtu
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import serve as SV, specs as SP, train as TR
    mesh = mesh24()
    cfg = reduced(get_config("qwen1.5-0.5b")).replace(hnn_mode="ann")
    S, B = 16, 2
    cell = ShapeCell("d", S, B, "decode")
    plan = SP.make_plan(cfg, cell, mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    pre, *_ = SV.make_prefill_step(cfg, plan, mesh)
    dec_a, _, _ = SV.make_decode_step(cfg, plan, mesh,
                                      replicate_weights=False)
    dec_b, _, _ = SV.make_decode_step(cfg, plan, mesh,
                                      replicate_weights=True)
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab,
                             jnp.int32)
    _, cache = pre(params, {"tokens": tok, "labels": tok})
    la, _ = dec_a(params, cache, tok[:, -1], jnp.asarray(S - 1, jnp.int32))
    _, cache2 = pre(params, {"tokens": tok, "labels": tok})
    lb, _ = dec_b(params, cache2, tok[:, -1], jnp.asarray(S - 1, jnp.int32))
    err = float(jnp.max(jnp.abs(la - lb)))
    assert err < 1e-2, err
    print("replicated-weight decode OK, max err", err)


def scenario_serving_parity():
    """Batched continuous-batching engine vs (a) a single-request run and
    (b) teacher-forced full-sequence argmax, token-for-token, for the
    ``none`` and ``spike_fused`` codecs (f32 to avoid bf16 argmax ties)."""
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import serve as SV, specs as SP, train as TR
    from repro.serving import EngineConfig, Request, ServingEngine
    mesh = mesh24()
    P_len, N = 16, 8
    for codec in ("none", "spike_fused"):
        hnn = "ann" if codec == "none" else "hnn"
        cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode=hnn)).replace(
            dtype=jnp.float32, codec=codec)
        ecfg = EngineConfig(num_slots=4, max_seq=32, page_size=8)
        cell = ShapeCell("serve_decode", ecfg.max_seq, ecfg.num_slots,
                         "decode")
        plan = SP.make_plan(cfg, cell, mesh)
        params = TR.init_sharded_params(cfg, plan, mesh,
                                        jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, cfg.vocab, P_len)) for _ in range(6)]

        # 6 greedy requests through 4 slots: slot reuse + interleaved admits
        engine = ServingEngine(cfg, mesh, params, ecfg)
        res = engine.run([Request(rid=i, prompt=p, max_new_tokens=N)
                          for i, p in enumerate(prompts)])
        assert engine.idle and len(res) == 6
        assert all(len(v) == N for v in res.values())

        # (a) batched == single-request, bit-for-bit
        solo = ServingEngine(cfg, mesh, params, ecfg).run(
            [Request(rid=0, prompt=prompts[0], max_new_tokens=N)])
        assert solo[0] == res[0], (codec, solo[0], res[0])

        # (a') async pipeline (dispatch t+1 before syncing t, device-
        # chained token feed, deferred retirement) == sync, bit-for-bit,
        # and it drains page/limbo-clean on the real dp x tp mesh
        asn = ServingEngine(cfg, mesh, params,
                            dataclasses.replace(ecfg, async_depth=1))
        res_a = asn.run([Request(rid=i, prompt=p, max_new_tokens=N)
                         for i, p in enumerate(prompts)])
        for i in range(6):
            assert res_a[i] == res[i], (codec, i, res[i], res_a[i])
        alloc = asn.cache.allocator
        assert alloc.pages_in_use == 0 and alloc.pages_in_limbo == 0
        assert (alloc.block_table == -1).all()

        # (b) engine decode == teacher-forced argmax over prompt+generated
        S = P_len + N
        planT = SP.make_plan(cfg, ShapeCell("tf", S, 8, "train"), mesh)
        logits_fn = SV.make_logits_step(cfg, planT, mesh)
        toks = np.zeros((8, S), np.int32)
        for i in range(6):
            toks[i] = prompts[i] + res[i]
        lg = np.asarray(logits_fn(params, {"tokens": jnp.asarray(toks),
                                           "labels": jnp.asarray(toks)}),
                        np.float32)
        am = lg.argmax(-1)
        for i in range(6):
            got = list(am[i, P_len - 1:P_len - 1 + N])
            assert got == res[i], (codec, i, res[i], got)
        print(f"serving parity OK {codec}")


def scenario_serving_sampling():
    """Distributed sampling from tp-sharded logits: greedy argmax equals
    the host argmax, top-k/top-p never sample outside their support, and
    temperature sampling hits high-probability tokens."""
    from repro.launch.mesh import make_mesh
    from repro.serving.sampling import SamplingConfig, sample
    from jax.sharding import PartitionSpec as P  # noqa: F811
    mesh = make_mesh((1, 8), ("data", "model"))
    B, V = 16, 512
    logits = jax.random.normal(jax.random.PRNGKey(0), (B, V)) * 3.0
    key = jax.random.PRNGKey(7)

    def run(scfg, temps):
        f = jax.shard_map(
            lambda l, k, t: sample(l, k, t, tp="model", tp_size=8, cfg=scfg),
            mesh=mesh, in_specs=(P(None, "model"), P(), P()),
            out_specs=P(None), check_vma=False)
        return np.asarray(f(logits, key, temps))

    # greedy == host argmax
    tok = run(SamplingConfig(), jnp.zeros(B, jnp.float32))
    np.testing.assert_array_equal(tok, np.asarray(logits).argmax(-1))
    # top-k: every sample inside the global top-k set
    k = 8
    topk = np.argsort(np.asarray(logits), -1)[:, -k:]
    for s in range(3):
        tok = run(SamplingConfig(top_k=k),
                  jnp.full(B, 0.7 + 0.1 * s, jnp.float32))
        assert all(tok[b] in topk[b] for b in range(B)), s
    # top-p: sampled token always inside the minimal nucleus
    p = 0.6
    pr = jax.nn.softmax(jnp.asarray(logits, jnp.float32), -1)
    order = np.argsort(-np.asarray(pr), -1)
    csum = np.cumsum(np.take_along_axis(np.asarray(pr), order, -1), -1)
    tok = run(SamplingConfig(top_p=p), jnp.ones(B, jnp.float32))
    for b in range(B):
        nucleus = set(order[b, :int((csum[b] < p).sum()) + 1])
        assert tok[b] in nucleus, (b, tok[b])
    print("serving sampling OK")


def scenario_serving_spec_parity():
    """Speculative decoding invariant: with greedy sampling, spec_k>0 is
    token-identical to the vanilla engine for attention-family configs
    (``none`` and ``spike_fused`` codecs), the drafter accepts >1 token
    per verify step on a repetitive workload, and no pages leak through
    the accept/rollback path."""
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.serving import EngineConfig, Request, ServingEngine
    mesh = mesh24()
    P_len, N = 16, 24
    rng = np.random.RandomState(0)
    # repetitive prompts (greedy decode on random weights also falls into
    # cycles, which prompt-lookup then drafts correctly)
    base = [list(rng.randint(0, 256, 4)) for _ in range(3)]
    prompts = [base[i % 3] * 4 for i in range(6)]
    for codec in ("none", "spike_fused"):
        hnn = "ann" if codec == "none" else "hnn"
        cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode=hnn)).replace(
            dtype=jnp.float32, codec=codec)
        cell = ShapeCell("serve_decode", 48, 4, "decode")
        plan = SP.make_plan(cfg, cell, mesh)
        params = TR.init_sharded_params(cfg, plan, mesh,
                                        jax.random.PRNGKey(0))
        reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=N)
                        for i, p in enumerate(prompts)]
        vanilla = ServingEngine(cfg, mesh, params, EngineConfig(
            num_slots=4, max_seq=48, prefill_len=16, page_size=8))
        res_v = vanilla.run(reqs())
        spec = ServingEngine(cfg, mesh, params, EngineConfig(
            num_slots=4, max_seq=48, prefill_len=16, page_size=8,
            spec_k=3))
        res_s = spec.run(reqs())
        assert spec.spec_k == 3 and spec.spec_verifies > 0
        for i in range(6):
            assert res_s[i] == res_v[i], (codec, i, res_v[i], res_s[i])
        alloc = spec.cache.allocator
        assert alloc.pages_in_use == 0 and alloc.num_free == 4
        # async + speculative: drafting joins the pipeline (admits still
        # overlap the in-flight verify) — token streams stay identical
        spec_a = ServingEngine(cfg, mesh, params, EngineConfig(
            num_slots=4, max_seq=48, prefill_len=16, page_size=8,
            spec_k=3, async_depth=1))
        res_sa = spec_a.run(reqs())
        for i in range(6):
            assert res_sa[i] == res_v[i], (codec, i, res_v[i], res_sa[i])
        assert spec_a.cache.allocator.pages_in_limbo == 0
        assert spec_a.cache.allocator.pages_in_use == 0
        mal = spec.mean_accepted_len
        assert mal > 1.0, (codec, mal)
        assert spec.decode_steps < vanilla.decode_steps, (
            codec, spec.decode_steps, vanilla.decode_steps)
        _, per_tok = spec.verify_wire_stats(mal)
        assert per_tok > 0
        print(f"spec parity OK {codec} accepted={mal:.2f} "
              f"steps={spec.decode_steps}/{vanilla.decode_steps}")


def scenario_serving_paged_mixed():
    """Block-table paging payoff on the (2, 4) mesh: short prompts share
    the KV page pool with one long slot, the pool sized BELOW the dense
    per-slot reservation (16 vs 24 pages), and the token streams are
    identical to a dense-equivalent (full-pool) engine.  Pages shard
    over dp x tp while slots batch-shard over dp, so this also covers
    the group-partitioned allocator against real device placement."""
    from repro.configs import get_config
    from repro.configs.reduced import reduced
    from repro.launch import train as TR
    from repro.launch.specs import ShapeCell, make_plan
    from repro.serving import EngineConfig, Request, ServingEngine
    mesh = mesh24()
    cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode="ann")).replace(
        dtype=jnp.float32, codec="none")
    cell = ShapeCell("serve_decode", 48, 4, "decode")
    plan = make_plan(cfg, cell, mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    long_p = list(rng.randint(0, 256, 32))
    shorts = [list(rng.randint(0, 256, 8)) for _ in range(5)]

    def reqs():
        rs = [Request(rid=0, prompt=long_p, max_new_tokens=8)]
        rs += [Request(rid=i + 1, prompt=p, max_new_tokens=8)
               for i, p in enumerate(shorts)]
        return rs

    kw = dict(num_slots=4, max_seq=48, prefill_len=32, page_size=8)
    small = ServingEngine(cfg, mesh, params, EngineConfig(**kw,
                                                          num_pages=16))
    res_s = small.run(reqs())
    dense = ServingEngine(cfg, mesh, params, EngineConfig(**kw))
    res_d = dense.run(reqs())
    for rid in res_d:
        assert res_s[rid] == res_d[rid], (rid, res_d[rid], res_s[rid])
    ps = small.pool_stats()
    # the shrunk pool really is smaller than the dense reservation and
    # the workload peaked within it; everything drained back
    assert ps["num_pages"] == 16 < dense.num_pages
    assert ps["kv_bytes_pool"] < ps["kv_bytes_dense"]
    assert 0 < ps["peak_pages_in_use"] <= 16
    assert ps["pages_in_use"] == 0 and ps["kv_bytes_mapped"] == 0
    assert (small.cache.block_table == -1).all()
    print(f"paged mixed OK peak={ps['peak_pages_in_use']}/16 "
          f"poolMB={ps['kv_bytes_pool']/1e6:.2f} "
          f"denseMB={ps['kv_bytes_dense']/1e6:.2f}")


def scenario_serving_fused_parity():
    """Fused paged-decode kernel on the (2, 4) mesh: the compacted
    per-shard page lists really partition each slot's pages across the
    4 pool shards of its dp group, and the fused gather->flash->combine
    path is token-identical to the reference dense-gather path — for
    the plain and spike codecs, with the pool sized below the dense
    reservation so slots contend for pages, and (spike) through the
    speculative verify path (K1 > 1) as well."""
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.serving import EngineConfig, Request, ServingEngine
    mesh = mesh24()
    rng = np.random.RandomState(7)
    base = [list(rng.randint(0, 256, 4)) for _ in range(3)]
    prompts = ([base[i % 3] * 4 for i in range(4)]
               + [list(rng.randint(0, 256, 8)) for _ in range(3)])
    reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=10)
                    for i, p in enumerate(prompts)]
    kw = dict(num_slots=4, max_seq=48, prefill_len=16, page_size=8,
              num_pages=16)
    for codec in ("none", "spike_fused"):
        hnn = "ann" if codec == "none" else "hnn"
        cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode=hnn)).replace(
            dtype=jnp.float32, codec=codec)
        cell = ShapeCell("serve_decode", 48, 4, "decode")
        plan = SP.make_plan(cfg, cell, mesh)
        params = TR.init_sharded_params(cfg, plan, mesh,
                                        jax.random.PRNGKey(0))
        ref = ServingEngine(cfg, mesh, params, EngineConfig(
            **kw, attn_kernel="reference"))
        res_r = ref.run(reqs())
        fus = ServingEngine(cfg, mesh, params, EngineConfig(
            **kw, attn_kernel="fused"))
        res_f = fus.run(reqs())
        for i in range(len(prompts)):
            assert res_f[i] == res_r[i], (codec, i, res_r[i], res_f[i])
        alloc = fus.cache.allocator
        # the engine really built 4-way compacted lists for this mesh
        assert alloc.shards_per_group == 4
        assert alloc.pages_per_shard == -(-alloc.pages_per_slot // 4)
        assert alloc.pages_in_use == 0
        assert (alloc.page_list_loc == -1).all()
        if codec == "spike_fused":
            spec = ServingEngine(cfg, mesh, params, EngineConfig(
                **kw, attn_kernel="fused", spec_k=3))
            res_s = spec.run(reqs())
            assert spec.spec_verifies > 0 and spec.mean_accepted_len > 1.0
            for i in range(len(prompts)):
                assert res_s[i] == res_r[i], (i, res_r[i], res_s[i])
        print(f"fused parity OK {codec}")


def scenario_serving_disagg_parity():
    """Disaggregated prefill/decode on the (2, 4) mesh: dp group 0 owns
    prefill, dp group 1 owns decode, and every admission hands the
    finished prefill's paged KV across in ONE coded ppermute onto pages
    the decode group mapped for it.  Token streams must be bit-identical
    to the colocated engine for BOTH wire formats — fp and the
    pow2-absmax int8 coded wire (whose scales are exact powers of two,
    so encode/decode is idempotent on the pool) — with migrations
    landing mid-trace under queue pressure, the coded wire moving fewer
    bytes, and both groups draining page/limbo-clean.  A hybrid
    (attention + mamba) leg checks recurrent state rows ride the same
    migration."""
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.serving import EngineConfig, Request, ServingEngine
    mesh = mesh24()
    P_len, N = 16, 8
    kw = dict(num_slots=4, max_seq=32, prefill_len=16, page_size=8)
    for codec in ("none", "spike_fused"):
        hnn = "ann" if codec == "none" else "hnn"
        cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode=hnn)).replace(
            dtype=jnp.float32, codec=codec)
        cell = ShapeCell("serve_decode", kw["max_seq"], kw["num_slots"],
                         "decode")
        plan = SP.make_plan(cfg, cell, mesh)
        params = TR.init_sharded_params(cfg, plan, mesh,
                                        jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        prompts = [list(rng.randint(0, cfg.vocab, P_len)) for _ in range(6)]
        reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=N)
                        for i, p in enumerate(prompts)]
        ref = ServingEngine(cfg, mesh, params, EngineConfig(**kw)).run(
            reqs())
        wire = {}
        for kv_wire in ("fp", "coded"):
            eng = ServingEngine(cfg, mesh, params, EngineConfig(
                **kw, disagg=True, kv_wire=kv_wire))
            res = eng.run(reqs())
            for i in range(6):
                assert res[i] == ref[i], (codec, kv_wire, i, ref[i], res[i])
            # 6 admits through a 2-slot decode group: every one migrated,
            # the later ones mid-trace while earlier slots still decode
            assert eng.migrations == 6, (kv_wire, eng.migrations)
            assert eng.migrated_wire_bytes \
                == 6 * eng.cache.migrate_wire_bytes()
            wire[kv_wire] = eng.cache.migrate_wire_bytes()
            alloc = eng.cache.allocator
            assert alloc.pages_in_use == 0 and alloc.pages_in_limbo == 0
            assert (alloc.block_table == -1).all()
        assert wire["coded"] < wire["fp"], wire
        # the pipelined + speculative disagg engine rides the same coded
        # migration path and stays token-identical
        spec = ServingEngine(cfg, mesh, params, EngineConfig(
            **kw, disagg=True, kv_wire="coded", spec_k=2, async_depth=1))
        res_s = spec.run(reqs())
        for i in range(6):
            assert res_s[i] == ref[i], (codec, "spec", i, ref[i], res_s[i])
        assert spec.migrations == 6
        assert spec.cache.allocator.pages_in_limbo == 0
        print(f"serving disagg parity OK {codec} "
              f"wire={wire['coded']}/{wire['fp']}B")
    # hybrid family: slot-major mamba state rows migrate alongside the
    # paged attention KV (plain ppermute for state, coded for KV)
    cfg = reduced(get_config("jamba-1.5-large-398b", hnn_mode="ann")
                  ).replace(dtype=jnp.float32, codec="none")
    cell = ShapeCell("serve_decode", kw["max_seq"], kw["num_slots"],
                     "decode")
    plan = SP.make_plan(cfg, cell, mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(0, cfg.vocab, P_len)) for _ in range(4)]
    reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=6)
                    for i, p in enumerate(prompts)]
    ref = ServingEngine(cfg, mesh, params, EngineConfig(**kw)).run(reqs())
    eng = ServingEngine(cfg, mesh, params, EngineConfig(
        **kw, disagg=True, kv_wire="coded"))
    assert eng.cache.state_bytes_per_slot() > 0      # really hybrid
    res = eng.run(reqs())
    for i in range(4):
        assert res[i] == ref[i], ("jamba", i, ref[i], res[i])
    assert eng.migrations == 4
    print("serving disagg parity OK jamba")


def scenario_serving_disagg_fuzz():
    """One fuzz draw of disagg-vs-colocated identity, parameterized by
    argv: <spec_k> <async_depth> <codec> <kv_wire> <seed>.  The seed
    derives a random schedule (mixed prompt lengths, max_new, eos
    pressure); the disaggregated engine must be token-identical to the
    colocated one and drain clean.  Driven by the hypothesis property in
    tests/test_serving.py (and by fixed combos in the CI dist lane)."""
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.serving import EngineConfig, Request, ServingEngine
    spec_k, async_depth = int(sys.argv[2]), int(sys.argv[3])
    codec, kv_wire, seed = sys.argv[4], sys.argv[5], int(sys.argv[6])
    mesh = mesh24()
    hnn = "ann" if codec == "none" else "hnn"
    cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode=hnn)).replace(
        dtype=jnp.float32, codec=codec)
    kw = dict(num_slots=4, max_seq=32, prefill_len=16, page_size=8,
              eos_id=7)
    cell = ShapeCell("serve_decode", kw["max_seq"], kw["num_slots"],
                     "decode")
    plan = SP.make_plan(cfg, cell, mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    rng = np.random.RandomState(seed)
    reqs = lambda: [Request(rid=i,
                            prompt=list(rng.randint(0, 256, plen)),
                            max_new_tokens=int(mnt))
                    for i, (plen, mnt) in enumerate(
                        (int(rng.randint(1, 17)), rng.randint(1, 9))
                        for _ in range(int(rng.randint(1, 8))))]
    sched = reqs()
    clone = lambda: [Request(rid=r.rid, prompt=r.prompt,
                             max_new_tokens=r.max_new_tokens)
                     for r in sched]
    ref = ServingEngine(cfg, mesh, params, EngineConfig(**kw)).run(clone())
    eng = ServingEngine(cfg, mesh, params, EngineConfig(
        **kw, disagg=True, kv_wire=kv_wire, spec_k=spec_k,
        async_depth=async_depth))
    res = eng.run(clone())
    assert set(res) == set(ref)
    for i in ref:
        assert res[i] == ref[i], (i, ref[i], res[i])
    assert eng.migrations == len(sched)
    alloc = eng.cache.allocator
    assert alloc.pages_in_use == 0 and alloc.pages_in_limbo == 0
    assert (alloc.block_table == -1).all()
    print(f"disagg fuzz OK spec_k={spec_k} depth={async_depth} "
          f"{codec}/{kv_wire} seed={seed} n={len(sched)} "
          f"migrated={eng.migrated_wire_bytes}B")


def scenario_serving_spec_recurrent_fallback():
    """Recurrent-state families cannot roll back: the engine must force
    spec_k=0 and still serve correctly."""
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch import specs as SP, train as TR
    from repro.serving import EngineConfig, Request, ServingEngine
    mesh = mesh24()
    cfg = reduced(get_config("xlstm-125m", hnn_mode="ann")).replace(
        dtype=jnp.float32, codec="none")
    cell = ShapeCell("serve_decode", 32, 4, "decode")
    plan = SP.make_plan(cfg, cell, mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    ecfg = EngineConfig(num_slots=4, max_seq=32, prefill_len=16,
                        page_size=8, spec_k=3)
    eng = ServingEngine(cfg, mesh, params, ecfg)
    assert eng.spec_k == 0 and eng._verify is None
    rng = np.random.RandomState(0)
    res = eng.run([Request(rid=i, prompt=list(rng.randint(0, 256, 16)),
                           max_new_tokens=6) for i in range(4)])
    assert len(res) == 4 and all(len(v) == 6 for v in res.values())
    print("spec recurrent fallback OK")


def scenario_sampling_stats():
    """Statistical check of the fused distributed sampler at tp=8: the
    empirical distribution of >=2k draws matches a host-side reference
    softmax sampler (total-variation distance) for temperature-only,
    top-k, and top-p configurations."""
    from repro.launch.mesh import make_mesh
    from repro.serving.sampling import SamplingConfig, sample
    mesh = make_mesh((1, 8), ("data", "model"))
    from _ref_sampling import host_reference_probs
    V, DRAWS = 64, 4096
    rng = np.random.RandomState(5)
    row = rng.randn(V) * 2.0
    # one independent draw per batch row: per-slot independence turns a
    # [DRAWS, V] batch into DRAWS draws of the same distribution
    logits = jnp.asarray(np.broadcast_to(row, (DRAWS, V)), jnp.float32)
    temps = jnp.full(DRAWS, 0.7, jnp.float32)

    def host_ref(scfg):
        return host_reference_probs(row, 0.7, top_k=scfg.top_k,
                                    top_p=scfg.top_p)

    for name, scfg in [("temp", SamplingConfig()),
                       ("topk8", SamplingConfig(top_k=8)),
                       ("topp0.6", SamplingConfig(top_p=0.6))]:
        f = jax.shard_map(
            lambda l, k, t: sample(l, k, t, tp="model", tp_size=8, cfg=scfg),
            mesh=mesh, in_specs=(P(None, "model"), P(), P()),
            out_specs=P(None), check_vma=False)
        tok = np.asarray(f(logits, jax.random.PRNGKey(11), temps))
        emp = np.bincount(tok, minlength=V) / DRAWS
        ref = host_ref(scfg)
        tv = 0.5 * np.abs(emp - ref).sum()
        assert tv < 0.06, (name, tv)
        print(f"sampling stats OK {name} tv={tv:.4f}")


SCENARIOS = {k[len("scenario_"):]: v for k, v in list(globals().items())
             if k.startswith("scenario_")}

if __name__ == "__main__":
    SCENARIOS[sys.argv[1]]()
    print("PASS", sys.argv[1])
