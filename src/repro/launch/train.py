"""Distributed train step: FSDP(pod,data) x TP(model) + spike boundaries.

``make_train_step`` builds the jit'd shard_map step for an (arch, shape,
mesh) plan.  Gradients of FSDP-sharded weights reduce via the AD
transpose of the forward all_gather (ZeRO-2-style reduce-scatter);
replicated params get an explicit psum over the axes missing from their
spec.  Optimizer states are sharded exactly like the params (ZeRO-1).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..models import draft_heads as DH
from ..models import model as M
from ..models import params as PR
from ..optim import adamw
from .specs import CellPlan, make_context, train_input_specs

F32 = jnp.float32


def shard_params_specs(cfg, plan: CellPlan):
    defs = M.model_defs(cfg, plan.tp_size)
    pspecs = PR.specs_tree(defs, plan.dp, plan.tp)
    psum_axes = PR.grad_psum_axes(defs, plan.dp, plan.tp)
    return defs, pspecs, psum_axes


def pick_microbatches(cfg, plan: CellPlan) -> int:
    """Gradient-accumulation factor: keep per-micro activation footprint
    (tokens x d_model) bounded so one block's fwd+bwd fits HBM."""
    B_loc = max(1, plan.cell.global_batch // plan.dp_size)
    if plan.cell.kind != "train":
        return 1
    # target <= ~8k tokens/device/micro at d_model >= 4k, scaled up for
    # smaller models
    tokens = B_loc * plan.cell.seq_len
    target = 8192 * max(1, 4096 // max(cfg.d_model, 1024)) ** 1
    mb = max(1, tokens // max(target, 1))
    while B_loc % mb != 0:
        mb -= 1
    return max(1, min(mb, B_loc))


def make_train_step(cfg, plan: CellPlan, mesh, with_optimizer=True,
                    microbatches: int | None = None,
                    opt_cfg: adamw.AdamWConfig | None = None):
    """Returns (step_fn, params_specs, opt_specs, batch_specs).

    step_fn(params, opt_state, batch) -> (params, opt_state, metrics)
    (or (loss, grads) when with_optimizer=False).

    Gradient accumulation: the local batch is split into ``microbatches``
    slices scanned with an fp32 grad accumulator — the standard way a
    398B train step fits 16 GB HBM.
    """
    defs, pspecs, psum_axes = shard_params_specs(cfg, plan)
    ctx = make_context(plan, "train")
    _, bspecs = train_input_specs(plan)
    n_micro = microbatches or pick_microbatches(cfg, plan)

    def loss_fn(params, batch):
        return M.forward_loss(params, batch, ctx)

    def micro_grads(params, batch):
        """Accumulate grads over microbatches (fp32)."""
        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, grads, metrics

        def split(x):
            b, rest = x.shape[0], x.shape[1:]
            return x.reshape(n_micro, b // n_micro, *rest)

        def split_batch(b):
            out = {}
            for k, v in b.items():
                if k == "positions3":
                    out[k] = jnp.moveaxis(
                        v.reshape(3, n_micro, v.shape[1] // n_micro,
                                  *v.shape[2:]), 1, 0)
                else:
                    out[k] = split(v)
            return out

        mb = split_batch(batch)

        def body(acc, mslice):
            gacc, lacc, macc = acc
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mslice)
            gacc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            macc = jax.tree.map(lambda a, m: a + m, macc, metrics)
            return (gacc, lacc + loss, macc), None

        gz = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        mz = {"loss": jnp.zeros((), F32), "penalty": jnp.zeros((), F32),
              "occupancy": jnp.zeros((), F32)}
        (gacc, loss, macc), _ = jax.lax.scan(
            body, (gz, jnp.zeros((), F32), mz), mb)
        inv = 1.0 / n_micro
        grads = jax.tree.map(lambda g: g * inv, gacc)
        metrics = jax.tree.map(lambda m: m * inv, macc)
        return loss * inv, grads, metrics

    def grads_psum(grads):
        def fix(g, axes):
            for a in axes:
                g = jax.lax.psum(g, a)
            return g
        return jax.tree.map(fix, grads, psum_axes)

    if not with_optimizer:
        def step(params, batch):
            loss, grads, metrics = micro_grads(params, batch)
            grads = grads_psum(grads)
            metrics = {k: jax.lax.pmean(v, plan.dp + (plan.tp,))
                       for k, v in metrics.items()}
            return loss, grads, metrics

        fn = jax.shard_map(step, mesh=mesh,
                           in_specs=(pspecs, bspecs),
                           out_specs=(P(), pspecs, {k: P() for k in
                                                    ("loss", "penalty",
                                                     "occupancy")}),
                           check_vma=False)
        return jax.jit(fn), pspecs, None, bspecs

    opt_specs = adamw.opt_state_specs(pspecs)
    all_axes = plan.dp + (plan.tp,)

    def global_grad_norm(grads):
        """Exact global norm: sharded leaves psum over their sharding
        axes (disjoint shards); replicated leaves contribute once."""
        buckets: dict[tuple, Any] = {}
        for g, rep_axes in zip(jax.tree.leaves(grads),
                               jax.tree.leaves(
                                   psum_axes,
                                   is_leaf=lambda x: isinstance(x, tuple))):
            shard_axes = tuple(a for a in all_axes if a not in rep_axes)
            s = jnp.sum(jnp.square(g.astype(F32)))
            buckets[shard_axes] = buckets.get(shard_axes, 0.0) + s
        total = 0.0
        for axes, s in buckets.items():
            total = total + (jax.lax.psum(s, axes) if axes else s)
        return jnp.sqrt(total)

    def step(params, opt_state, batch):
        loss, grads, metrics = micro_grads(params, batch)
        grads = grads_psum(grads)
        gnorm = global_grad_norm(grads)
        params, opt_state = adamw.apply_updates(
            params, grads, opt_state, gnorm=gnorm,
            cfg=opt_cfg or adamw.AdamWConfig())
        metrics = {k: jax.lax.pmean(v, plan.dp + (plan.tp,))
                   for k, v in metrics.items()}
        metrics["grad_norm"] = gnorm
        return params, opt_state, metrics

    mspec = {k: P() for k in ("loss", "penalty", "occupancy", "grad_norm")}
    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(pspecs, opt_specs, bspecs),
                       out_specs=(pspecs, opt_specs, mspec),
                       check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1)), pspecs, opt_specs, bspecs


def make_draft_head_train_step(cfg, plan: CellPlan, mesh, num_heads: int,
                               d_hidden: int = 0,
                               opt_cfg: adamw.AdamWConfig | None = None):
    """Frozen-trunk, heads-only train step (the draft-head mode).

    Returns (step_fn, params_specs, opt_specs, batch_specs) with
    step_fn(params, opt_state, batch) -> (params, opt_state, metrics).

    ``params`` is ONE tree: the trunk plus a ``"draft_heads"`` subtree
    (see ``models.draft_heads.draft_head_defs``).  Only the heads
    subtree differentiates — the trunk forward runs under stop_gradient
    inside ``draft_head_loss`` — and ``opt_state`` covers the heads
    alone, so the optimizer footprint is O(heads).  The full tree flows
    through unchanged otherwise, which is what lets the caller's
    checkpoint loop (runtime.ft.TrainLoop) save trunk + heads together
    as one path-keyed manifest.
    """
    defs, pspecs, _ = shard_params_specs(cfg, plan)
    hdefs = DH.draft_head_defs(cfg, num_heads, d_hidden)
    hspecs = PR.specs_tree(hdefs, plan.dp, plan.tp)
    hpsum = PR.grad_psum_axes(hdefs, plan.dp, plan.tp)
    pspecs_full = dict(pspecs)
    pspecs_full["draft_heads"] = hspecs
    ctx = make_context(plan, "train")
    _, bspecs = train_input_specs(plan)
    opt_specs = adamw.opt_state_specs(hspecs)

    def hloss(hp, params, batch):
        p = dict(params)
        p["draft_heads"] = hp
        return DH.draft_head_loss(p, batch, ctx)

    def step(params, opt_state, batch):
        hp = params["draft_heads"]
        (loss, metrics), grads = jax.value_and_grad(
            hloss, has_aux=True)(hp, params, batch)

        def fix(g, axes):
            for a in axes:
                g = jax.lax.psum(g, a)
            return g

        grads = jax.tree.map(fix, grads, hpsum)
        # heads are replicated and their grads are post-psum identical on
        # every rank: the global norm is a plain local sum of squares
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32)))
                             for g in jax.tree.leaves(grads)))
        hp, opt_state = adamw.apply_updates(
            hp, grads, opt_state, gnorm=gnorm,
            cfg=opt_cfg or adamw.AdamWConfig())
        params = dict(params)
        params["draft_heads"] = hp
        metrics = {k: jax.lax.pmean(v, plan.dp + (plan.tp,))
                   for k, v in metrics.items()}
        metrics["grad_norm"] = gnorm
        return params, opt_state, metrics

    mspec = {k: P() for k in ("loss", "draft_acc", "grad_norm")}
    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(pspecs_full, opt_specs, bspecs),
                       out_specs=(pspecs_full, opt_specs, mspec),
                       check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1)), pspecs_full, opt_specs, bspecs


def _init_on_mesh(defs, specs, mesh, key, dtype):
    """Materialize ``defs`` with every shard built on its own device (one
    jitted init with ``out_shardings``): no device ever holds the whole
    tree.  Values are those of ``PR.init_params(defs, key, dtype)``."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    return jax.jit(lambda k: PR.init_params(defs, k, dtype),
                   out_shardings=shardings)(key)


def init_draft_head_params(cfg, plan: CellPlan, mesh, key, num_heads: int,
                           d_hidden: int = 0, dtype=None):
    """Materialize a fresh (identity-init) draft-heads subtree, sharded
    (i.e. replicated — the defs carry no tp/fsdp dims) on the mesh."""
    hdefs = DH.draft_head_defs(cfg, num_heads, d_hidden)
    hspecs = PR.specs_tree(hdefs, plan.dp, plan.tp)
    return _init_on_mesh(hdefs, hspecs, mesh, key, dtype or cfg.dtype)


def init_sharded_params(cfg, plan: CellPlan, mesh, key, dtype=None):
    """Materialize params sharded on the mesh (for real runs, not dryrun)."""
    defs, pspecs, _ = shard_params_specs(cfg, plan)
    return _init_on_mesh(defs, pspecs, mesh, key, dtype or cfg.dtype)


def abstract_sharded_params(cfg, plan: CellPlan):
    defs, pspecs, _ = shard_params_specs(cfg, plan)
    return PR.abstract_params(defs, plan.cfg.dtype), pspecs
