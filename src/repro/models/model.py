"""Full model: embedding -> scanned layer units -> distributed LM loss.

Everything in this module runs *inside* shard_map (per-shard views).
Layers are scanned over homogeneous repeating units (the ``pattern`` in
the config) with per-unit FSDP weight gathers, MaxText-style; the scan
body is rematerialized (jax.checkpoint) so 72-layer x 398B configs
lower with per-layer activation memory only.

Distributed pieces:
  embedding  : vocab over tp; coded psum_scatter to the seq-sharded domain
  LM head    : seq gather (spike boundary) -> local-vocab logits ->
               cross-vocab softmax XE via pmax/psum over tp
  decode     : KV seq-sharded over ctx.cp (context parallel)
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from ..core import boundary
from . import blocks_attn, blocks_moe, blocks_rnn, blocks_ssm, common
from .context import Context, fsdp_gather
from .params import (abstract_params, init_params, pdef, spike_pdefs,
                     stack_defs)

F32 = jnp.float32

BLOCK_DEFS = {
    "attn": lambda cfg, tp: {**blocks_attn.attn_defs(cfg, tp),
                             **blocks_attn.mlp_defs(cfg, tp)},
    "global": lambda cfg, tp: {**blocks_attn.attn_defs(cfg, tp),
                               **blocks_attn.mlp_defs(cfg, tp)},
    "local": lambda cfg, tp: {**blocks_attn.attn_defs(cfg, tp),
                              **blocks_attn.mlp_defs(cfg, tp)},
    "attn_moe": lambda cfg, tp: {**blocks_attn.attn_defs(cfg, tp),
                                 **blocks_moe.moe_defs(cfg, tp)},
    "mamba": lambda cfg, tp: blocks_ssm.mamba_defs(cfg, tp),
    "mamba_mlp": lambda cfg, tp: {**blocks_ssm.mamba_defs(cfg, tp),
                                  **blocks_attn.mlp_defs(cfg, tp)},
    "mamba_moe": lambda cfg, tp: {**blocks_ssm.mamba_defs(cfg, tp),
                                  **blocks_moe.moe_defs(cfg, tp)},
    "mlstm": lambda cfg, tp: blocks_rnn.mlstm_defs(cfg, tp),
    "slstm": lambda cfg, tp: blocks_rnn.slstm_defs(cfg, tp),
    "rwkv": lambda cfg, tp: blocks_rnn.rwkv_defs(cfg, tp),
}


# ---------------------------------------------------------------------------
# parameter definitions for a whole model
# ---------------------------------------------------------------------------


def model_defs(cfg: ModelConfig, tp: int):
    D = cfg.d_model
    Vp = cfg.vocab_padded(tp)
    defs: dict[str, Any] = {
        "embed": pdef(Vp, D, tp=0, fsdp=1, init="embed"),
        "final_ln": pdef(D, init="zeros"),
        "sp_embed": spike_pdefs(D),
        "sp_head": spike_pdefs(D),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = pdef(D, Vp, tp=1, fsdp=0)

    unit = {}
    for i, kind in enumerate(cfg.pattern):
        unit[f"pos{i}"] = BLOCK_DEFS[kind](cfg, tp)
    defs["units"] = stack_defs(unit, cfg.n_units)

    if cfg.is_encdec:
        assert cfg.n_enc_layers > 0
        enc_unit = {"pos0": BLOCK_DEFS["attn"](cfg, tp)}
        defs["enc_units"] = stack_defs(enc_unit, cfg.n_enc_layers)
        # decoder cross-attention per decoder unit position
        cross_unit = {}
        for i, _ in enumerate(cfg.pattern):
            cross_unit[f"pos{i}"] = blocks_attn.attn_defs(cfg, tp, cross=True)
        defs["cross_units"] = stack_defs(cross_unit, cfg.n_units)
        defs["sp_enc_out"] = spike_pdefs(D)
    return defs


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(p, tokens_loc, ctx: Context):
    """tokens_loc [B_loc, S_loc] -> x [B_loc, S_loc, D] (seq-sharded).

    Vocab is tp-sharded; each rank embeds from its shard and the partials
    are summed+scattered back to the seq domain.  Exactly one rank
    contributes per token, so the wire is naturally sparse — a boundary.
    """
    cfg = ctx.cfg
    tp = ctx.tp_size
    Vp = cfg.vocab_padded(tp)
    V_loc = Vp // tp
    if tp == 1:
        emb = fsdp_gather(p["embed"], ctx, 1)
        return jnp.take(emb, tokens_loc, axis=0)
    ids = lax.all_gather(tokens_loc, ctx.tp, axis=1, tiled=True)  # [B,S]
    emb = fsdp_gather(p["embed"], ctx, 1)                         # [V_loc, D]
    r = lax.axis_index(ctx.tp)
    off = r * V_loc
    loc = jnp.clip(ids - off, 0, V_loc - 1)
    part = jnp.take(emb, loc, axis=0)                             # [B,S,D]
    valid = ((ids >= off) & (ids < off + V_loc))[..., None]
    part = jnp.where(valid, part, 0).astype(cfg.dtype)
    return boundary.coded_psum_scatter(part, p["sp_embed"], ctx.codec,
                                       ctx.tp, axis=1)


def lm_logits_local(p, x_loc, ctx: Context):
    """x_loc [B,S_loc,D] -> (logits [B,S,V_loc] for the full seq, pen)."""
    cfg = ctx.cfg
    h = common.norm(x_loc, p["final_ln"], cfg.norm)
    if ctx.tp_size == 1:
        head = _head_w(p, ctx)
        return (h @ head).astype(F32), jnp.zeros((), F32)
    pen, _ = blocks_attn._stats(h, p["sp_head"], ctx)
    xg = boundary.coded_all_gather(h, p["sp_head"], ctx.codec, ctx.tp,
                                   axis=1)
    head = _head_w(p, ctx)                                        # [D, V_loc]
    logits = (xg @ head).astype(F32)
    return logits, pen


def _head_w(p, ctx):
    cfg = ctx.cfg
    if cfg.tie_embeddings:
        emb = fsdp_gather(p["embed"], ctx, 1)                     # [V_loc, D]
        return emb.T.astype(cfg.dtype)
    return fsdp_gather(p["lm_head"], ctx, 0)


def lm_loss_chunked(p, x_loc, labels_loc, ctx: Context, mask=None,
                    chunk: int = 512):
    """Fused final-norm -> gather -> head matmul -> distributed XE,
    scanned over seq chunks so the [B, S, V_loc] logits tensor never
    materializes (the single largest activation at 150k-vocab scale).

    Returns (mean NLL, boundary penalty).
    """
    cfg = ctx.cfg
    tp = ctx.tp_size
    h = common.norm(x_loc, p["final_ln"], cfg.norm)
    if tp == 1:
        logits = (h @ _head_w(p, ctx)).astype(F32)
        if cfg.final_softcap:
            logits = common.softcap(logits, cfg.final_softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, labels_loc[..., None], axis=-1)[..., 0]
        nll = lse - gold
        if mask is not None:
            return (jnp.sum(nll * mask)
                    / jnp.maximum(jnp.sum(mask), 1)), jnp.zeros((), F32)
        return jnp.mean(nll), jnp.zeros((), F32)

    pen, _ = blocks_attn._stats(h, p["sp_head"], ctx)
    xg = boundary.coded_all_gather(h, p["sp_head"], ctx.codec, ctx.tp,
                                   axis=1)
    labels = lax.all_gather(labels_loc, ctx.tp, axis=1, tiled=True)
    mask_g = None
    if mask is not None:
        mask_g = lax.all_gather(mask, ctx.tp, axis=1, tiled=True)
    head = _head_w(p, ctx)                                    # [D, V_loc]
    V_loc = head.shape[1]
    r = lax.axis_index(ctx.tp)
    off = r * V_loc
    B, S, D = xg.shape
    qc = min(chunk, S)
    nc = S // qc

    def chunk_nll(xg_c, lab_c):
        logits = (xg_c @ head).astype(F32)                    # [B,qc,V_loc]
        if cfg.final_softcap:
            logits = common.softcap(logits, cfg.final_softcap)
        m_loc = jnp.max(logits, axis=-1)
        m = lax.stop_gradient(lax.pmax(lax.stop_gradient(m_loc), ctx.tp))
        se = lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), -1), ctx.tp)
        lse = m + jnp.log(se)
        loc = jnp.clip(lab_c - off, 0, V_loc - 1)
        gold_p = jnp.take_along_axis(logits, loc[..., None], -1)[..., 0]
        valid = (lab_c >= off) & (lab_c < off + V_loc)
        gold = lax.psum(jnp.where(valid, gold_p, 0.0), ctx.tp)
        return lse - gold                                     # [B,qc]

    if ctx.mode == "train":
        chunk_nll = jax.checkpoint(chunk_nll, prevent_cse=False)

    def body(acc, i):
        xg_c = lax.dynamic_slice_in_dim(xg, i * qc, qc, axis=1)
        lab_c = lax.dynamic_slice_in_dim(labels, i * qc, qc, axis=1)
        nll = chunk_nll(xg_c, lab_c)
        if mask_g is not None:
            mk = lax.dynamic_slice_in_dim(mask_g, i * qc, qc, axis=1)
            return (acc[0] + jnp.sum(nll * mk), acc[1] + jnp.sum(mk)), None
        return (acc[0] + jnp.sum(nll), acc[1] + nll.size), None

    (tot, cnt), _ = lax.scan(body, (jnp.zeros((), F32),
                                    jnp.zeros((), F32)), jnp.arange(nc))
    return tot / jnp.maximum(cnt, 1), pen


def xent_loss(logits_loc, labels_loc, ctx: Context, mask=None):
    """Cross-entropy over a tp-sharded vocab.

    logits_loc [B, S, V_loc] (full seq, local vocab shard);
    labels_loc [B, S_loc] (seq-sharded) -> scalar mean NLL over tokens.
    """
    cfg = ctx.cfg
    tp = ctx.tp_size
    if cfg.final_softcap:
        logits_loc = common.softcap(logits_loc, cfg.final_softcap)
    if tp == 1:
        lse = jax.nn.logsumexp(logits_loc, axis=-1)
        gold = jnp.take_along_axis(
            logits_loc, labels_loc[..., None], axis=-1)[..., 0]
        nll = lse - gold
        if mask is not None:
            return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
        return jnp.mean(nll)

    labels = lax.all_gather(labels_loc, ctx.tp, axis=1, tiled=True)  # [B,S]
    V_loc = logits_loc.shape[-1]
    r = lax.axis_index(ctx.tp)
    off = r * V_loc
    # distributed logsumexp over vocab shards (detached max: pmax has no
    # diff rule, and the max shift is gradient-free anyway)
    m_loc = jnp.max(logits_loc, axis=-1)
    m = lax.stop_gradient(lax.pmax(lax.stop_gradient(m_loc), ctx.tp))
    se = jnp.sum(jnp.exp(logits_loc - m[..., None]), axis=-1)
    se = lax.psum(se, ctx.tp)
    lse = m + jnp.log(se)
    # gold logit lives on exactly one shard
    loc = jnp.clip(labels - off, 0, V_loc - 1)
    gold_part = jnp.take_along_axis(logits_loc, loc[..., None], -1)[..., 0]
    valid = (labels >= off) & (labels < off + V_loc)
    gold = lax.psum(jnp.where(valid, gold_part, 0.0), ctx.tp)
    nll = lse - gold                                              # [B,S]
    if mask is not None:
        mask_g = lax.all_gather(mask, ctx.tp, axis=1, tiled=True)
        return jnp.sum(nll * mask_g) / jnp.maximum(jnp.sum(mask_g), 1)
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# block dispatch
# ---------------------------------------------------------------------------


def _ckpt(fn, ctx):
    """Per-block rematerialization: the unit backward then recomputes one
    block at a time instead of holding all blocks' residuals live."""
    if ctx.mode != "train":
        return fn
    return jax.checkpoint(fn, prevent_cse=False)


def _unit_fwd(unit_p, cross_p, x, ctx: Context, aux):
    """One scanned unit (train/prefill): run every pattern position."""
    cfg = ctx.cfg
    pen = jnp.zeros((), F32)
    occ = jnp.zeros((), F32)
    n = 0
    caches = {}
    for i, kind in enumerate(cfg.pattern):
        p = unit_p[f"pos{i}"]
        cache_i = {}
        if kind in ("attn", "global", "local", "attn_moe"):
            x, kv, pe, oc = _ckpt(
                lambda p_, x_, aux_: blocks_attn.attn_fwd(
                    p_, x_, ctx, aux_, kind=kind), ctx)(p, x, aux)
            if kv is not None:
                cache_i["kv"] = kv
            pen, occ, n = pen + pe, occ + oc, n + 1
            if cross_p is not None:
                xp = cross_p[f"pos{i}"]
                x, ckv, pe, oc = _ckpt(
                    lambda p_, x_, aux_: blocks_attn.attn_fwd(
                        p_, x_, ctx, aux_, kind="attn", prefix="x_"),
                    ctx)(xp, x, aux)
                if ckv is not None:
                    cache_i["cross_kv"] = ckv
                pen, occ, n = pen + pe, occ + oc, n + 1
            if kind == "attn_moe":
                x, pe, oc = _ckpt(
                    lambda p_, x_, aux_: blocks_moe.moe_fwd(
                        p_, x_, ctx, aux_), ctx)(p, x, aux)
            else:
                x, pe, oc = _ckpt(
                    lambda p_, x_, aux_: blocks_attn.mlp_fwd(
                        p_, x_, ctx, aux_), ctx)(p, x, aux)
            pen, occ, n = pen + pe, occ + oc, n + 1
        elif kind in ("mamba", "mamba_mlp", "mamba_moe"):
            x, st, pe, oc = _ckpt(
                lambda p_, x_, aux_: blocks_ssm.mamba_fwd(
                    p_, x_, ctx, aux_), ctx)(p, x, aux)
            if st is not None:
                cache_i["ssm_state"] = st
            pen, occ, n = pen + pe, occ + oc, n + 1
            if kind == "mamba_moe":
                x, pe, oc = _ckpt(
                    lambda p_, x_, aux_: blocks_moe.moe_fwd(
                        p_, x_, ctx, aux_), ctx)(p, x, aux)
                pen, occ, n = pen + pe, occ + oc, n + 1
            elif kind == "mamba_mlp":
                x, pe, oc = _ckpt(
                    lambda p_, x_, aux_: blocks_attn.mlp_fwd(
                        p_, x_, ctx, aux_), ctx)(p, x, aux)
                pen, occ, n = pen + pe, occ + oc, n + 1
        elif kind == "mlstm":
            x, st, pe, oc = _ckpt(
                lambda p_, x_, aux_: blocks_rnn.mlstm_fwd(
                    p_, x_, ctx, aux_), ctx)(p, x, aux)
            if st is not None:
                cache_i["rnn_state"] = st
            pen, occ, n = pen + pe, occ + oc, n + 1
        elif kind == "slstm":
            x, st, pe, oc = _ckpt(
                lambda p_, x_, aux_: blocks_rnn.slstm_fwd(
                    p_, x_, ctx, aux_), ctx)(p, x, aux)
            if st is not None:
                cache_i["rnn_state"] = st
            pen, occ, n = pen + pe, occ + oc, n + 1
        elif kind == "rwkv":
            x, st, pe, oc = _ckpt(
                lambda p_, x_, aux_: blocks_rnn.rwkv_fwd(
                    p_, x_, ctx, aux_), ctx)(p, x, aux)
            if st is not None:
                cache_i["rwkv_state"] = st
            pen, occ, n = pen + pe, occ + oc, n + 1
        else:
            raise ValueError(kind)
        caches[f"pos{i}"] = cache_i
    return x, caches, pen, occ / max(n, 1)


def _unit_decode(unit_p, cross_p, x, cache_u, pos, ctx: Context, aux):
    cfg = ctx.cfg
    new_cache = {}
    for i, kind in enumerate(cfg.pattern):
        p = unit_p[f"pos{i}"]
        c_i = cache_u[f"pos{i}"]
        nc_i = {}
        if kind in ("attn", "global", "local", "attn_moe"):
            x, kv = blocks_attn.attn_decode_fwd(
                p, x, c_i["kv"], pos, ctx, aux, kind=kind)
            nc_i["kv"] = kv
            if cross_p is not None:
                xp = cross_p[f"pos{i}"]
                x, ckv = blocks_attn.attn_decode_fwd(
                    xp, x, c_i["cross_kv"], pos, ctx, aux, prefix="x_")
                nc_i["cross_kv"] = ckv
            if kind == "attn_moe":
                x, _, _ = blocks_moe.moe_fwd(p, x, ctx, aux)
            else:
                x, _, _ = blocks_attn.mlp_fwd(p, x, ctx, aux)
        elif kind in ("mamba", "mamba_mlp", "mamba_moe"):
            x, st = blocks_ssm.mamba_decode_fwd(p, x, c_i["ssm_state"], pos,
                                                ctx, aux)
            nc_i["ssm_state"] = st
            if kind == "mamba_moe":
                x, _, _ = blocks_moe.moe_fwd(p, x, ctx, aux)
            elif kind == "mamba_mlp":
                x, _, _ = blocks_attn.mlp_fwd(p, x, ctx, aux)
        elif kind == "mlstm":
            x, st = blocks_rnn.mlstm_decode_fwd(p, x, c_i["rnn_state"], pos,
                                                ctx, aux)
            nc_i["rnn_state"] = st
        elif kind == "slstm":
            x, st = blocks_rnn.slstm_decode_fwd(p, x, c_i["rnn_state"], pos,
                                                ctx, aux)
            nc_i["rnn_state"] = st
        elif kind == "rwkv":
            x, st = blocks_rnn.rwkv_decode_fwd(p, x, c_i["rwkv_state"], pos,
                                               ctx, aux)
            nc_i["rwkv_state"] = st
        new_cache[f"pos{i}"] = nc_i
    return x, new_cache


# ---------------------------------------------------------------------------
# full forward passes (inside shard_map)
# ---------------------------------------------------------------------------


def _run_stack(params, x, ctx: Context, aux, collect_cache=False):
    cfg = ctx.cfg
    cross = params.get("cross_units")

    def body(carry, unit_slice):
        x, pen, occ = carry
        unit_p, cross_p = unit_slice
        x, caches, pe, oc = _unit_fwd(unit_p, cross_p, x, ctx, aux)
        out = caches if collect_cache else None
        return (x, pen + pe, occ + oc / cfg.n_units), out

    body = jax.checkpoint(body, prevent_cse=False)
    if cross is None:
        (x, pen, occ), caches = lax.scan(
            lambda c, u: body(c, (u, None)), (x, jnp.zeros((), F32),
                                              jnp.zeros((), F32)),
            params["units"])
    else:
        (x, pen, occ), caches = lax.scan(
            body, (x, jnp.zeros((), F32), jnp.zeros((), F32)),
            (params["units"], cross))
    return x, caches, pen, occ


def _run_encoder(params, enc_in, ctx: Context, aux):
    """Encoder stack (non-causal) over frame embeddings."""
    ctx_e = ctx.with_(is_encoder=True)
    B_loc, S_enc_loc, _ = enc_in.shape
    S_enc = S_enc_loc * ctx.tp_size
    aux = dict(aux)
    aux["positions"] = jnp.broadcast_to(jnp.arange(S_enc)[None],
                                        (B_loc, S_enc))

    def body(carry, unit_p):
        x, pen = carry
        x, _, pe, _ = _unit_fwd(unit_p, None, x, ctx_e, aux)
        return (x, pen + pe), None

    body = jax.checkpoint(body, prevent_cse=False)
    (x, pen), _ = lax.scan(body, (enc_in, jnp.zeros((), F32)),
                           params["enc_units"])
    return x, pen


def forward_loss(params, batch, ctx: Context):
    """Training forward.  batch: tokens/labels [B_loc, S_loc] (+ optional
    positions3, enc_embeds).  Returns (loss, metrics)."""
    cfg = ctx.cfg
    aux = _make_aux(batch, ctx)
    pen_total = jnp.zeros((), F32)

    if cfg.is_encdec:
        enc_x, pen_e = _run_encoder(params, batch["enc_embeds"], ctx, aux)
        pen_total += pen_e
        # boundary: encoder output crosses to the decoder partition
        enc_full = boundary.coded_all_gather(
            enc_x, params["sp_enc_out"], ctx.codec, ctx.tp, axis=1)
        aux = dict(aux)
        aux["cross_src"] = enc_full

    embed = _ckpt(lambda p_, t_: embed_tokens(p_, t_, ctx), ctx)
    x = embed(params, batch["tokens"])
    x, _, pen, occ = _run_stack(params, x, ctx, aux)
    loss_ce, pen_h = lm_loss_chunked(params, x, batch["labels"], ctx,
                                     mask=batch.get("mask"))
    pen_total = pen_total + pen + pen_h
    loss = loss_ce + pen_total
    # normalize for dp-psum of grads (see train.py)
    metrics = {"loss": loss_ce, "penalty": pen_total, "occupancy": occ}
    return loss / ctx.dp_size, metrics


def forward_prefill(params, batch, ctx: Context, last_pos=None,
                    return_hidden=False):
    """Prefill: fill caches, return last-token logits + caches.

    ``last_pos`` (optional, scalar or [B] int32): per-sequence index of
    the last *real* prompt token when prompts are right-padded into a
    fixed-length prefill (the serving engine's admit path).  Defaults to
    the final position.  When set, the selected hidden crosses the wire
    through the sp_head codec so its logits match the decode path.

    ``return_hidden``: also return the selected last hidden [B, D]
    (post-wire, tp-replicated) for the learned draft heads.
    """
    cfg = ctx.cfg
    ctx = ctx.with_(mode="prefill")
    aux = _make_aux(batch, ctx)
    if cfg.is_encdec:
        enc_x, _ = _run_encoder(params, batch["enc_embeds"], ctx, aux)
        enc_full = boundary.coded_all_gather(
            enc_x, params["sp_enc_out"], ctx.codec, ctx.tp, axis=1)
        aux = dict(aux)
        aux["cross_src"] = enc_full
    x = embed_tokens(params, batch["tokens"], ctx)
    x, caches, _, _ = _run_stack(params, x, ctx, aux, collect_cache=True)
    # only the last position's logits are needed: slice before the head
    # matmul so the [B, S, V] logits tensor never exists
    last = common.norm(x, params["final_ln"], cfg.norm)
    B, S_loc, _ = last.shape
    if last_pos is not None:
        lp = jnp.broadcast_to(jnp.asarray(last_pos, jnp.int32).reshape(-1),
                              (B,))
        lidx = lp % S_loc
        cand = jnp.take_along_axis(last, lidx[:, None, None], axis=1)[:, 0]
        if ctx.tp_size > 1:
            r = lax.axis_index(ctx.tp)
            own = (lp // S_loc == r)[:, None]
            part = jnp.where(own, cand, 0).astype(cfg.dtype)
            # only the owning rank contributes: the coded psum reduces to
            # the sp_head wire roundtrip the decode path applies
            xg_last = boundary.coded_psum(part, params["sp_head"],
                                          ctx.codec, ctx.tp)
        else:
            xg_last = cand
    elif ctx.tp_size > 1:
        # global last token lives on the last tp rank's local tail
        alll = lax.all_gather(last[:, -1], ctx.tp, axis=1)   # [B, tp, D]
        xg_last = alll[:, -1]
    else:
        xg_last = last[:, -1]
    logits = (xg_last @ _head_w(params, ctx)).astype(F32)
    if cfg.final_softcap:
        logits = common.softcap(logits, cfg.final_softcap)
    if return_hidden:
        return logits, caches, xg_last
    return logits, caches


def _split_pool(cache, paged: bool):
    """(KV page-pool leaves, every other leaf) of a decode cache tree.

    On the paged layout the layer scan CARRIES the pool whole and each
    unit reads and writes it at its own index (``aux["layer"]``): as a
    scanned input/output pair XLA would copy the entire pool between
    the two buffers on every step.  State leaves, and every leaf of the
    dense layout, stay scanned per unit."""
    if not paged:
        return {}, cache
    pool = {k: c["kv"] for k, c in cache.items() if "kv" in c}
    rest = {k: {n: v for n, v in c.items() if n != "kv"}
            for k, c in cache.items()}
    return pool, rest


def _join_pool(pool, rest):
    return {k: dict(c, kv=pool[k]) if k in pool else c
            for k, c in rest.items()}


def _scan_units(unit_fn, params, x, cache, aux):
    """Run ``unit_fn(unit_p, cross_p, x, cache_u, aux) -> (x, cache_u)``
    over the stacked units (``aux["layer"]`` = the unit index); returns
    (x, new cache)."""
    paged = "block_table" in aux
    pool, rest = _split_pool(cache, paged)

    def body(carry, slc):
        x, pool = carry
        unit_p, cross_p, rest_u, i = slc
        x, nc = unit_fn(unit_p, cross_p, x, _join_pool(pool, rest_u),
                        dict(aux, layer=i))
        pool, rest_u = _split_pool(nc, paged)
        return (x, pool), rest_u

    n = jax.tree.leaves(params["units"])[0].shape[0]
    (x, pool), rest = lax.scan(body, (x, pool),
                               (params["units"], params.get("cross_units"),
                                rest, jnp.arange(n, dtype=jnp.int32)))
    return x, _join_pool(pool, rest)


def forward_decode(params, cache, token, pos, ctx: Context, aux_extra=None):
    """One decode step.  token [B_loc] int32; pos scalar int32 or
    [B_loc] per-slot positions (batched serving).

    Two attention-cache layouts: dense slot-major (``cache[slot, pos]``,
    the single-request serve path) or — when ``aux_extra`` carries a
    ``"block_table"`` row per local slot — the serving engine's shared
    KV page pool, written ``cache[page, offset]`` through that table and
    read by the paged-decode kernel through the compacted
    ``"page_list"`` feed (see ``blocks_attn.attn_decode_fwd``).
    Recurrent-state leaves are slot-major in both.  Returns
    (logits_local [B_loc, V_loc], new_cache)."""
    cfg = ctx.cfg
    ctx = ctx.with_(mode="decode")
    aux = dict(aux_extra or {})
    B = token.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    # embed: vocab is tp-sharded; exactly one rank contributes per token,
    # summed over the coded wire (same boundary as the train-path
    # psum_scatter, minus the seq scatter)
    emb = fsdp_gather(params["embed"], ctx, 1)
    tp = ctx.tp_size
    if tp == 1:
        x = jnp.take(emb, token, axis=0)[:, None, :]
    else:
        V_loc = cfg.vocab_padded(tp) // tp
        r = lax.axis_index(ctx.tp)
        off = r * V_loc
        loc = jnp.clip(token - off, 0, V_loc - 1)
        part = jnp.take(emb, loc, axis=0)
        valid = ((token >= off) & (token < off + V_loc))[:, None]
        part = jnp.where(valid, part, 0).astype(cfg.dtype)
        x = boundary.coded_psum(part, params["sp_embed"], ctx.codec,
                                ctx.tp)[:, None, :]
    x = x.astype(cfg.dtype)

    x, new_cache = _scan_units(
        lambda p, xp, x, c, a: _unit_decode(p, xp, x, c, pos, ctx, a),
        params, x, cache, aux)

    h = common.norm(x, params["final_ln"], cfg.norm)
    if ctx.tp_size > 1:
        # hidden->head die crossing: train/prefill gather h through the
        # sp_head codec, so serving applies the same wire roundtrip
        h = boundary.wire_roundtrip(h, params["sp_head"], ctx.codec)
    head = _head_w(params, ctx)
    logits = (h[:, 0] @ head).astype(F32)
    if cfg.final_softcap:
        logits = common.softcap(logits, cfg.final_softcap)
    return logits, new_cache


def _unit_verify(unit_p, x, cache_u, pos, ctx: Context, aux):
    """One scanned unit of the batched k-token verify step.

    Attention families only: recurrent blocks (ssm/rnn/rwkv) fold every
    token into their state, which cannot roll back when a draft is
    rejected — the serving engine forces ``spec_k=0`` for those.
    """
    cfg = ctx.cfg
    new_cache = {}
    for i, kind in enumerate(cfg.pattern):
        p = unit_p[f"pos{i}"]
        c_i = cache_u[f"pos{i}"]
        nc_i = {}
        if kind in ("attn", "global", "local", "attn_moe"):
            x, kv = blocks_attn.attn_verify_fwd(p, x, c_i["kv"], pos, ctx,
                                                aux, kind=kind)
            nc_i["kv"] = kv
            if kind == "attn_moe":
                x, _, _ = blocks_moe.moe_fwd(p, x, ctx, aux)
            else:
                x, _, _ = blocks_attn.mlp_fwd(p, x, ctx, aux)
        else:
            raise NotImplementedError(
                f"verify step over recurrent block {kind!r}: state cannot "
                "roll back rejected drafts (engine falls back to spec_k=0)")
        new_cache[f"pos{i}"] = nc_i
    return x, new_cache


def forward_verify(params, cache, tokens, pos, ctx: Context, aux_extra=None,
                   return_hidden=False):
    """Batched speculative-verify step: score K1 = spec_k+1 positions of
    every slot in ONE forward (the decode-boundary traffic of K1 steps
    through one set of coded collectives — the workload the spike wire
    absorbs).

    tokens [B, K1] int32 — per slot, the last committed token followed by
    spec_k draft tokens; pos [B] int32 — the base cache position of each
    slot's first token.  KV for position pos+j is written for every j
    (through ``aux_extra["block_table"]`` when the cache is the engine's
    shared page pool — the scheduler must have mapped pages covering
    pos..pos+K1-1 first); acceptance (and page-exact rollback of
    rejected positions) is the scheduler's job.
    Returns (logits_local [B, K1, V_loc], new_cache);
    logits[:, j] condition on tokens[:, :j+1] — greedy-argmax of column j
    is the verify target for draft j+1.

    ``return_hidden``: also return the final hidden [B, K1, D] AFTER the
    sp_head wire roundtrip — replicated across tp ranks, so the learned
    draft heads (``draft_heads.head_hiddens``) can read it with no new
    collective.
    """
    cfg = ctx.cfg
    ctx = ctx.with_(mode="decode")
    aux = dict(aux_extra or {})
    B, K1 = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    # same vocab-sharded embed boundary as forward_decode, K1 tokens wide
    emb = fsdp_gather(params["embed"], ctx, 1)
    tp = ctx.tp_size
    if tp == 1:
        x = jnp.take(emb, tokens, axis=0)                    # [B,K1,D]
    else:
        V_loc = cfg.vocab_padded(tp) // tp
        r = lax.axis_index(ctx.tp)
        off = r * V_loc
        loc = jnp.clip(tokens - off, 0, V_loc - 1)
        part = jnp.take(emb, loc, axis=0)
        valid = ((tokens >= off) & (tokens < off + V_loc))[..., None]
        part = jnp.where(valid, part, 0).astype(cfg.dtype)
        x = boundary.coded_psum(part, params["sp_embed"], ctx.codec, ctx.tp)
    x = x.astype(cfg.dtype)

    if params.get("cross_units") is not None:
        raise NotImplementedError("verify step: encoder-decoder unsupported")

    x, new_cache = _scan_units(
        lambda p, _, x, c, a: _unit_verify(p, x, c, pos, ctx, a),
        params, x, cache, aux)

    h = common.norm(x, params["final_ln"], cfg.norm)
    if ctx.tp_size > 1:
        h = boundary.wire_roundtrip(h, params["sp_head"], ctx.codec)
    head = _head_w(params, ctx)
    logits = (h @ head).astype(F32)                          # [B,K1,V_loc]
    if cfg.final_softcap:
        logits = common.softcap(logits, cfg.final_softcap)
    if return_hidden:
        return logits, new_cache, h
    return logits, new_cache


def _make_aux(batch, ctx: Context):
    cfg = ctx.cfg
    tokens = batch["tokens"]
    B_loc, S_loc = tokens.shape
    S = S_loc * ctx.tp_size
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B_loc, S))
    aux = {"positions": positions}
    if cfg.rope_kind == "mrope":
        if "positions3" in batch:
            p3_loc = batch["positions3"]
            aux["positions3"] = lax.all_gather(p3_loc, ctx.tp, axis=2,
                                               tiled=True)
        else:
            aux["positions3"] = jnp.broadcast_to(positions[None],
                                                 (3, B_loc, S))
    return aux
