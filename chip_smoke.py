"""Bring-up smoke test: serve qwen1.5-0.5b on the TPU through the engine.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the tensor-parallel path on four

One process, no children.  Builds qwen1.5-0.5b at its published widths
(24 layers, d_model 1024, 16 heads, vocab 151936, bf16; random weights
from ``--seed``) through the entry points a user calls:
``launch.specs.make_plan`` -> ``launch.train.init_sharded_params`` ->
``serving.ServingEngine`` -> ``warmup`` / ``run``, with the fused
paged-decode kernel walking a 4096-page KV pool.

One chip: the HNN model with the ``spike_fused`` codec serves 16
requests (prompts of 192-384 tokens, 32 greedy tokens each) through 8
slots.  Checks: every request returns its full token count, every token
id is in the vocabulary, and the compiled decode step contains the
kernel (``tpu_custom_call``).

``--chips 4`` runs only the four-chip phase: the same model on a 1x4
mesh (tp=4) with ``spike_fused`` and, in ANN mode, with codec ``none``.
Checks, besides the serving ones: the coded decode step carries int8
collectives, params and KV pages sit on all four devices with per-device
bytes in use within ``MEM_SPREAD`` of each other, and the last-position
logits of one prefill under the tp=4 plan agree with the same params on
one chip to a relative max-abs error of ``LOGIT_RTOL``.

Timing, memory and pool lines are informational.  The last line is one
JSON object naming the device.  Any failed check exits non-zero; without
a TPU the script fails and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ShapeCell  # noqa: E402
from repro.launch import roofline as RL  # noqa: E402
from repro.launch import specs as SP  # noqa: E402
from repro.launch import train as TR  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving import EngineConfig, Request, ServingEngine  # noqa: E402

ARCH = "qwen1.5-0.5b"
SLOTS, PAGE, PAGES = 8, 16, 4096
PREFILL, GEN, REQUESTS = 384, 32, 16
MAX_SEQ = PREFILL + GEN
#: tp=4 vs one-chip prefill logits, max |a - b| / max |b| (bf16 weights:
#: the row-sharded projections round each partial sum before the psum)
LOGIT_RTOL = 5e-2
#: per-device bytes in use on the 1x4 mesh: max <= (1 + MEM_SPREAD) * min
MEM_SPREAD = 0.10


def check(ok, msg: str):
    if not ok:
        sys.exit(f"chip_smoke: FAIL: {msg}")


def build(codec: str, hnn_mode: str, mesh, seed: int):
    cfg = get_config(ARCH, hnn_mode=hnn_mode, codec=codec)
    ecfg = EngineConfig(num_slots=SLOTS, max_seq=MAX_SEQ,
                        prefill_len=PREFILL, page_size=PAGE,
                        num_pages=PAGES, async_depth=1)
    plan = SP.make_plan(cfg, ShapeCell("serve_decode", MAX_SEQ, SLOTS,
                                       "decode"), mesh)
    params = TR.init_sharded_params(cfg, plan, mesh,
                                    jax.random.PRNGKey(seed))
    return cfg, params, ServingEngine(cfg, mesh, params, ecfg)


def make_requests(cfg, seed: int):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=list(rng.randint(0, cfg.vocab,
                                            rng.randint(PREFILL // 2,
                                                        PREFILL + 1))),
                    max_new_tokens=GEN)
            for i in range(REQUESTS)]


def serve(tag: str, cfg, engine, reqs) -> str:
    """Compile, warm up and serve ``reqs``; check the outputs and that
    the decode step runs the kernel.  Returns the decode step's HLO."""
    t0 = time.perf_counter()
    compiled = engine.compiled_decode_step()
    t_aot = time.perf_counter() - t0
    hlo = compiled.as_text()
    print(f"{tag}: decode step compiled in {t_aot:.1f} s; "
          f"memory_analysis: {compiled.memory_analysis()}")
    check("tpu_custom_call" in hlo,
          f"{tag}: no tpu_custom_call in the decode step: the fused "
          "paged-decode kernel is not what runs")
    t0 = time.perf_counter()
    engine.warmup(reqs[0].prompt)
    jax.block_until_ready(engine.cache.buffers)
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = engine.run(reqs)
    jax.block_until_ready(engine.cache.buffers)
    dt = time.perf_counter() - t0
    check(set(out) == {r.rid for r in reqs},
          f"{tag}: served {sorted(out)}, expected {len(reqs)} requests")
    for r in reqs:
        toks = out[r.rid]
        check(len(toks) == r.max_new_tokens,
              f"{tag}: request {r.rid} returned {len(toks)} tokens, "
              f"expected {r.max_new_tokens}")
        check(all(0 <= t < cfg.vocab for t in toks),
              f"{tag}: request {r.rid} has a token id outside "
              f"[0, {cfg.vocab}): {toks}")
    print(f"{tag}: warmup (compile prefill/insert/decode) {t_warm:.1f} s; "
          f"served {len(reqs)} requests, {engine.tokens_generated} tokens "
          f"in {dt:.3f} s = {engine.tokens_generated / dt:.1f} tok/s "
          f"(warm, host clock after block_until_ready), "
          f"{engine.decode_steps} decode steps")
    print(f"{tag}: pool_stats {engine.pool_stats()}")
    return hlo


def mem(dev, key: str):
    """One entry of the device's memory stats (None if not reported)."""
    return (dev.memory_stats() or {}).get(key)


def one_chip(seed: int):
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg, _, engine = build("spike_fused", "hnn", mesh, seed)
    serve("1x1 hnn/spike_fused", cfg, engine, make_requests(cfg, seed))
    peak = mem(jax.devices()[0], "peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}")


def prefill_logits(cfg, mesh, params, prompt) -> np.ndarray:
    """Last-position logits of one right-padded prefill under the plan
    of ``mesh`` (the engine's admit path, minus the sampler)."""
    plan = SP.make_plan(cfg, ShapeCell("serve_admit", PREFILL, 1,
                                       "prefill"), mesh)
    _, pspecs, _ = TR.shard_params_specs(cfg, plan)
    ctx = SP.make_context(plan, "prefill")
    toks = np.zeros((1, PREFILL), np.int32)
    toks[0, :len(prompt)] = prompt
    last = np.array([len(prompt) - 1], np.int32)

    def step(p, t, lp):
        return M.forward_prefill(p, {"tokens": t}, ctx, last_pos=lp)[0]

    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(pspecs, P(None, plan.tp), P(None)),
                       out_specs=P(None, plan.tp), check_vma=False)
    logits = jax.jit(fn)(params, toks, last)
    return np.asarray(logits, np.float32)[:, :cfg.vocab]


def four_chips(seed: int):
    check(len(jax.devices()) >= 4,
          f"--chips 4 needs four devices, found {len(jax.devices())}")
    mesh4 = make_mesh((1, 4), ("data", "model"))
    devs = set(mesh4.devices.flat)

    cfg, params, engine = build("spike_fused", "hnn", mesh4, seed)
    reqs = make_requests(cfg, seed)
    hlo = serve("1x4 hnn/spike_fused", cfg, engine, reqs)
    stats = RL.parse_collectives(hlo)
    coded = [op for op in stats.ops if op.coded]
    print(f"1x4 hnn/spike_fused: decode collectives {stats.counts}, "
          f"{len(coded)} int8, streams "
          f"{sorted({op.stream for op in coded})}")
    check(coded, "the coded decode step carries no int8 collective")
    on = set().union(*(x.sharding.device_set
                       for x in jax.tree.leaves(params)))
    check(on == devs, f"params sit on {len(on)} of the 4 devices")
    pools = [x for x in jax.tree.leaves(engine.cache.buffers)
             if x.shape[1:2] == (PAGES,)]
    check(pools, "no KV page pool among the cache buffers")
    for x in pools:
        shards = x.addressable_shards
        check({s.device for s in shards} == devs
              and all(s.data.shape[1] == PAGES // 4 for s in shards),
              f"KV pages not split over the 4 devices: "
              f"{[(s.device.id, s.data.shape) for s in shards]}")
    used = [mem(d, "bytes_in_use") for d in mesh4.devices.flat]
    print(f"1x4: bytes_in_use per device {used}; peak_bytes_in_use "
          f"{[mem(d, 'peak_bytes_in_use') for d in mesh4.devices.flat]}")
    check(None not in used, "the devices report no bytes_in_use")
    check(max(used) <= (1 + MEM_SPREAD) * min(used),
          f"per-device bytes in use spread past {MEM_SPREAD:.0%}: {used}")
    del cfg, params, engine
    gc.collect()

    cfg, params4, engine = build("none", "ann", mesh4, seed)
    serve("1x4 ann/none", cfg, engine, reqs)
    del engine
    gc.collect()
    prompt = reqs[0].prompt
    l4 = prefill_logits(cfg, mesh4, params4, prompt)
    del params4
    gc.collect()
    mesh1 = make_mesh((1, 1), ("data", "model"))
    plan1 = SP.make_plan(cfg, ShapeCell("serve_decode", MAX_SEQ, SLOTS,
                                        "decode"), mesh1)
    params1 = TR.init_sharded_params(cfg, plan1, mesh1,
                                     jax.random.PRNGKey(seed))
    l1 = prefill_logits(cfg, mesh1, params1, prompt)
    check(np.isfinite(l4).all() and np.isfinite(l1).all(),
          "non-finite prefill logits")
    err = float(np.max(np.abs(l4 - l1)) / np.max(np.abs(l1)))
    print(f"1x4 vs 1x1 ann/none prefill logits: relative max-abs error "
          f"{err:.3e} (limit {LOGIT_RTOL:.0e}), argmax "
          f"{int(l4.argmax())} vs {int(l1.argmax())}")
    check(err <= LOGIT_RTOL,
          f"tp=4 logits differ from one chip by {err:.3e} > {LOGIT_RTOL}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: jax found {dev.platform} devices; this smoke test "
          "never falls back to the CPU")
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    print(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
