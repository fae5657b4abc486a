"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train_cli \
        --arch rwkv-paper --steps 300 --batch 8 --seq 128 \
        --mesh 1x1 --hnn-mode hnn --ckpt-dir /tmp/ckpt

Wires together: config -> mesh/plan -> sharded init -> AdamW train step
-> deterministic data pipeline -> fault-tolerant TrainLoop (checkpoint/
restart, straggler watch, NaN guard, preemption).

``--draft-heads K`` switches to the frozen-trunk draft-head mode
(``launch.train.make_draft_head_train_step``): K speculative draft
heads train against the next-k-token objective while the trunk stays
fixed, the optimizer covers only the heads, and checkpoints carry
trunk + heads as ONE params tree — exactly what the serving engine's
``drafter="heads"`` restores.  ``--init-from`` seeds the trunk from an
existing trunk-only checkpoint first (the usual flow: pretrain the
trunk, then bolt heads on).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv-paper")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1", help="DPxTP, e.g. 2x4")
    ap.add_argument("--hnn-mode", default="hnn",
                    choices=["ann", "hnn", "snn"])
    ap.add_argument("--codec", default="spike_fused")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (smoke) config")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--lam", type=float, default=None,
                    help="sparsity penalty weight override")
    ap.add_argument("--target-rate", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=30)
    ap.add_argument("--draft-heads", type=int, default=0,
                    help="train K frozen-trunk speculative draft heads "
                         "instead of the trunk (0: normal LM training)")
    ap.add_argument("--draft-hidden", type=int, default=0,
                    help="draft-head MLP hidden width (0: d_model // 2)")
    ap.add_argument("--init-from", default=None,
                    help="checkpoint dir to seed the TRUNK from before "
                         "heads-only training (trunk-only manifest)")
    args = ap.parse_args(argv)

    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    from ..configs import get_config
    from ..configs.base import ShapeCell
    from ..configs.reduced import reduced as reduce_cfg
    from ..core.spike import SpikeConfig
    from ..data.pipeline import DataConfig, SyntheticLM
    from ..optim import adamw
    from ..runtime.ft import FTConfig, TrainLoop
    from . import specs as SP
    from . import train as TR
    from .mesh import make_mesh

    cfg = get_config(args.arch, hnn_mode=args.hnn_mode, codec=args.codec)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    dp, tp = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((dp, tp), ("data", "model"))
    cell = ShapeCell("cli", args.seq, args.batch, "train")
    plan = SP.make_plan(cfg, cell, mesh)

    opt_cfg = adamw.AdamWConfig(lr=args.lr, warmup_steps=args.warmup,
                                total_steps=max(args.steps, 1))
    params = TR.init_sharded_params(cfg, plan, mesh,
                                    jax.random.PRNGKey(args.seed))
    if args.draft_heads > 0:
        if args.init_from:
            from ..checkpoint.manager import CheckpointManager
            tspecs = TR.shard_params_specs(cfg, plan)[1]
            params, ck_step = CheckpointManager(args.init_from).restore(
                (params, adamw.init_opt_state(params)),
                mesh=mesh, specs=(tspecs, adamw.opt_state_specs(tspecs)))
            params = params[0]
            print(f"[train] trunk seeded from {args.init_from} "
                  f"step {ck_step}")
        step, pspecs, ospecs, _ = TR.make_draft_head_train_step(
            cfg, plan, mesh, args.draft_heads, args.draft_hidden,
            opt_cfg=opt_cfg)
        params["draft_heads"] = TR.init_draft_head_params(
            cfg, plan, mesh, jax.random.PRNGKey(args.seed + 1),
            args.draft_heads, args.draft_hidden)
        opt = adamw.init_opt_state(params["draft_heads"])
    else:
        step, pspecs, ospecs, _ = TR.make_train_step(cfg, plan, mesh,
                                                     with_optimizer=True,
                                                     opt_cfg=opt_cfg)
        opt = adamw.init_opt_state(params)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    mode = (f"draft_heads={args.draft_heads}" if args.draft_heads > 0
            else "lm")
    print(f"[train] {cfg.name} mode={cfg.hnn_mode} codec={cfg.codec} "
          f"params={n_params/1e6:.2f}M mesh={mesh.shape} train={mode}")

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))

    hist = []

    def logged_step(p, o, batch):
        p, o, m = step(p, o, batch)
        hist.append(m)
        if len(hist) % args.log_every == 0:
            if "draft_acc" in m:
                print(f"  step {len(hist):5d} loss={float(m['loss']):.4f} "
                      f"draft_acc={float(m['draft_acc']):.3f}")
            else:
                print(f"  step {len(hist):5d} loss={float(m['loss']):.4f} "
                      f"occ={float(m['occupancy']):.3f} "
                      f"pen={float(m['penalty']):.5f}")
        return p, o, m

    loop = TrainLoop(logged_step, data,
                     FTConfig(ckpt_dir=args.ckpt_dir,
                              ckpt_every=args.ckpt_every))
    t0 = time.time()
    params, opt, metrics = loop.run(params, opt, args.steps,
                                    resume=not args.no_resume,
                                    mesh=mesh, pspecs=pspecs, ospecs=ospecs)
    dt = time.time() - t0
    out = {
        "arch": cfg.name, "mode": cfg.hnn_mode,
        "final_loss": metrics[-1]["loss"] if metrics else None,
        "final_occupancy": (metrics[-1].get("occupancy")
                            if metrics else None),
        "steps": len(metrics), "wall_s": round(dt, 1),
        "straggler_events": loop.straggler_events,
        "nan_skips": loop.nan_skips,
    }
    if args.draft_heads > 0 and metrics:
        out["draft_acc"] = metrics[-1].get("draft_acc")
    print("[train] done:", json.dumps(out))
    return out, metrics


if __name__ == "__main__":
    main()
