"""Batched serving on the continuous-batching engine (repro.serving).

Admits a stream of variable-length requests into a fixed slot pool,
decodes all slots in lockstep with per-slot positions/temperatures and
fused on-device sampling, and keeps the spike wire on every decode-path
boundary collective.

    PYTHONPATH=src python examples/serve_hnn.py --arch qwen1.5-0.5b \
        --mesh 1x2 --slots 4 --requests 8 --prompt-len 16 --gen 16

Speculative decoding
--------------------
``--spec-k K`` turns on self-drafting speculative decoding: a
deterministic prompt-lookup (n-gram) drafter proposes K tokens per slot
from the slot's own committed history, and ONE batched verify step
scores all K+1 positions at once — the same coded collectives as a
decode step, carrying (K+1)x the D-space traffic, which is precisely
the boundary load the spike/int8 wire makes affordable.  The scheduler
keeps the longest draft prefix that matches the verify output plus the
model's correction token and rolls back the rejected tail's cache
occupancy.  Under greedy sampling (--temperature 0) the emitted token
streams are bit-identical to ``--spec-k 0``; only the step count drops.
Recurrent-state families (ssm/rnn/hybrid) silently fall back to
``spec_k=0`` — their state cannot roll back a rejected draft.

    PYTHONPATH=src python examples/serve_hnn.py --arch qwen1.5-0.5b \
        --mesh 1x2 --slots 4 --spec-k 3 --repetitive

``--repetitive`` makes the prompts cyclic so the drafter has something
to find; the report then shows ``accepted len > 1`` and the verify-step
wire bytes per committed token next to the vanilla decode wire.

Async decode streams
--------------------
``--async-depth 1`` runs the engine as a dispatch/commit pipeline: the
host launches decode step t+1 (feeding step t's sampled tokens straight
from the device array, no host round-trip) before it syncs step t, so
scheduling, admission prefill, and page bookkeeping overlap the device
step.  Greedy token streams are bit-identical to ``--async-depth 0``;
``bench/run.py`` measures the served path on the chip.

Pool pressure + graceful degradation
------------------------------------
Shrink ``--num-pages`` below the dense reservation and the pool — not
the slot count — becomes the binding limit.  When a mid-decode slot
cannot map its next page, the engine (by default) evicts + re-queues
the youngest slot of the starving group and restarts it on re-admit:
greedy streams stay bit-identical, only latency pays, and the report
prints the preemption count.  ``--no-preempt`` restores the raw typed
``PagePoolExhausted``.

    PYTHONPATH=src python examples/serve_hnn.py --mesh 1x2 --slots 4 \
        --page-size 8 --num-pages 10
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.configs.reduced import reduced
from repro.launch import specs as SP
from repro.launch import train as TR
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.serving import EngineConfig, Request, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--mesh", default="1x2")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="cache length (0: prompt-len + gen)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV pool page size (positions per page)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV page pool size (0: dense-equivalent "
                         "default — shrink it to make slots share)")
    ap.add_argument("--hnn-mode", default="hnn")
    ap.add_argument("--codec", default=None,
                    help="override cfg codec (none|int8|spike_fused|...)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0)
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft tokens per verify step "
                         "(0: vanilla decode)")
    ap.add_argument("--async-depth", type=int, default=0,
                    help="decode steps the host dispatches ahead of the "
                         "oldest un-synced step (1 overlaps host "
                         "scheduling with the device step; greedy "
                         "streams are token-identical to 0)")
    ap.add_argument("--repetitive", action="store_true",
                    help="cyclic prompts (speculative decoding's best "
                         "case: the n-gram drafter matches)")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable pool-pressure preemption: a starving "
                         "slot raises typed PagePoolExhausted instead "
                         "of evicting + re-queueing the youngest slot")
    args = ap.parse_args()
    enable_compile_cache()

    dp, tp = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((dp, tp), ("data", "model"))
    cfg = reduced(get_config(args.arch, hnn_mode=args.hnn_mode))
    if args.codec:
        cfg = cfg.replace(codec=args.codec)
    max_seq = args.max_seq or args.prompt_len + args.gen
    ecfg = EngineConfig(num_slots=args.slots, max_seq=max_seq,
                        prefill_len=args.prompt_len,
                        page_size=args.page_size,
                        num_pages=args.num_pages,
                        top_k=args.top_k, top_p=args.top_p,
                        spec_k=args.spec_k,
                        async_depth=args.async_depth,
                        preempt=not args.no_preempt)

    cell = ShapeCell("serve_decode", ecfg.max_seq, ecfg.num_slots, "decode")
    plan = SP.make_plan(cfg, cell, mesh)
    params = TR.init_sharded_params(cfg, plan, mesh, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, mesh, params, ecfg)

    rng = np.random.RandomState(1)

    def make_prompt():
        if args.repetitive:
            period = max(args.prompt_len // 4, 1)
            cycle = list(rng.randint(0, cfg.vocab, period))
            return (cycle * args.prompt_len)[:args.prompt_len]
        return list(rng.randint(0, cfg.vocab, args.prompt_len))

    reqs = [Request(rid=i, prompt=make_prompt(),
                    max_new_tokens=args.gen,
                    temperature=args.temperature)
            for i in range(args.requests)]

    engine.warmup(reqs[0].prompt)

    t0 = time.time()
    results = engine.run(reqs)
    dt = time.time() - t0
    toks = engine.tokens_generated
    stats, per_tok = engine.decode_wire_stats()
    ps = engine.pool_stats()
    peak_kb = ps["peak_pages_in_use"] * engine.cache.kv_page_bytes() / 1e3
    dev = jax.devices()[0]
    print(f"{cfg.name} ({cfg.hnn_mode}/{cfg.codec}) mesh={args.mesh} "
          f"slots={args.slots}: served {len(results)} requests, "
          f"{toks} tokens in {dt*1e3:.0f}ms "
          f"({toks/max(dt, 1e-9):.1f} tok/s on {dev.platform} "
          f"{dev.device_kind} x{len(jax.devices())})")
    print(f"decode steps={engine.decode_steps}  "
          f"async depth={engine.async_depth}  "
          f"wire {per_tok/1e3:.1f}KB/token "
          f"({dict(stats.counts)} collectives/step)")
    print(f"kv pool: peak {ps['peak_pages_in_use']}/{ps['num_pages']} "
          f"pages x {ps['page_size']} positions  "
          f"mapped {peak_kb:.1f}KB at peak vs "
          f"{ps['kv_bytes_dense']/1e3:.1f}KB dense per-slot reservation")
    if engine.preemptions:
        print(f"pool pressure: {engine.preemptions} preemption(s) — "
              "evicted + re-queued youngest slots; greedy outputs are "
              "unchanged, only latency paid")
    if engine.spec_k > 0:
        mal = engine.mean_accepted_len
        _, vper_tok = engine.verify_wire_stats(mal)
        print(f"speculative: k={engine.spec_k}  accepted len={mal:.2f}  "
              f"verify wire {vper_tok/1e3:.1f}KB/committed-token")
    print("sample:", results[0][:16])


if __name__ == "__main__":
    main()
