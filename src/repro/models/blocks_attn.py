"""Attention + dense-MLP blocks (per-shard, spike boundaries at collectives).

Sharding (Megatron-style TP with sequence parallelism):
  activations x [B_loc, S_loc, D] — batch over dp, seq over tp;
  attention: heads over tp; MLP: d_ff over tp.
  The 4 collectives per layer (gather-in / scatter-out for attn and MLP)
  are exactly the die-to-die boundaries; they carry the spike wire.

Decode (context-parallel): KV cache seq-sharded over ctx.cp; q heads are
gathered (tiny) and each shard computes an LSE partial over its cache
slice (distributed flash-decode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core import boundary
from ..kernels import ops as kops
from . import common
from .context import (Context, cp_linear_index, cp_size, fsdp_gather,
                      pool_linear_index, pool_local_pages)
from .params import pdef, spike_pdefs


# ---------------------------------------------------------------------------
# dims
# ---------------------------------------------------------------------------


def attn_dims(cfg, tp):
    dh = cfg.d_head
    Hkv = cfg.n_kv_heads
    if Hkv == cfg.n_heads:                      # MHA: pad both together
        Hq = cfg.padded(cfg.n_heads, tp)
        Hkv_p = Hq
        kv_rep = False
    else:
        Hq = cfg.padded(cfg.n_heads, tp)
        # need Hq % Hkv == 0 for grouped layout
        while Hq % Hkv != 0:
            Hq += tp
        Hkv_p = Hkv
        kv_rep = Hkv % tp != 0
    Hq_loc = Hq // tp
    Hkv_loc = Hkv_p if kv_rep else Hkv_p // tp
    return dict(dh=dh, Hq=Hq, Hq_loc=Hq_loc, Hkv=Hkv_p, Hkv_loc=Hkv_loc,
                kv_rep=kv_rep, group=Hq // Hkv_p)


# ---------------------------------------------------------------------------
# parameter defs
# ---------------------------------------------------------------------------


def attn_defs(cfg, tp, cross=False):
    d = attn_dims(cfg, tp)
    D, dh = cfg.d_model, d["dh"]
    kv_tp = None if d["kv_rep"] else 1
    defs = {
        "ln": pdef(D, init="zeros"),
        "wq": pdef(D, d["Hq"] * dh, tp=1, fsdp=0),
        "wk": pdef(D, d["Hkv"] * dh, tp=kv_tp, fsdp=0),
        "wv": pdef(D, d["Hkv"] * dh, tp=kv_tp, fsdp=0),
        "wo": pdef(d["Hq"] * dh, D, tp=0, fsdp=1),
        "sp_in": spike_pdefs(D),
        "sp_out": spike_pdefs(D),
    }
    if cfg.qkv_bias:
        defs["bq"] = pdef(d["Hq"] * dh, tp=0, init="zeros")
        defs["bk"] = pdef(d["Hkv"] * dh, tp=(None if d["kv_rep"] else 0),
                          init="zeros")
        defs["bv"] = pdef(d["Hkv"] * dh, tp=(None if d["kv_rep"] else 0),
                          init="zeros")
    if cfg.post_norm:
        defs["post_ln"] = pdef(D, init="zeros")
    if cfg.hnn_mode == "snn":
        defs["sp_snn"] = spike_pdefs(d["Hq_loc"] * dh if False else D)
    if cross:
        defs = {f"x_{k}": v for k, v in defs.items()}
    return defs


def mlp_defs(cfg, tp):
    D = cfg.d_model
    F = cfg.ff_padded(tp)
    defs = {
        "ln2": pdef(D, init="zeros"),
        "w1": pdef(D, F, tp=1, fsdp=0),
        "w3": pdef(D, F, tp=1, fsdp=0),
        "w2": pdef(F, D, tp=0, fsdp=1),
        "sp_in2": spike_pdefs(D),
        "sp_out2": spike_pdefs(D),
    }
    if cfg.post_norm:
        defs["post_ln2"] = pdef(D, init="zeros")
    if cfg.hnn_mode == "snn":
        defs["sp_snn2"] = spike_pdefs(D)
    return defs


def attn_cache_defs(cfg, tp, cp_total, B_loc, S, dtype):
    """KV cache (decode): seq-sharded over cp, full kv heads per shard."""
    d = attn_dims(cfg, tp)
    Ss = S // cp_total
    shape = (B_loc, Ss, d["Hkv"], d["dh"])
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _rope(cfg, x, aux):
    if cfg.rope_kind == "rope":
        return common.apply_rope(x, aux["positions"], cfg.rope_theta)
    if cfg.rope_kind == "mrope":
        half = x.shape[-1] // 2
        t = half - 2 * (half // 3)
        sec = (t, half // 3, half // 3)
        return common.apply_mrope(x, aux["positions3"], cfg.rope_theta, sec)
    return x


def _maybe_snn(h, p_snn, ctx):
    """SNN mode: intra-chip activations are spike-coded too."""
    if ctx.cfg.hnn_mode != "snn" or ctx.codec.mode == "none":
        return h
    return boundary._local_roundtrip(h, p_snn, ctx.codec)


def _stats(h, p, ctx):
    if ctx.mode == "train" and ctx.collect_stats:
        pen, occ = boundary.boundary_penalty(h, p, ctx.codec)
        return pen.astype(jnp.float32), occ.astype(jnp.float32)
    z = jnp.zeros((), jnp.float32)
    return z, z


# ---------------------------------------------------------------------------
# forward: train / prefill
# ---------------------------------------------------------------------------


def attn_fwd(p, x, ctx: Context, aux, kind="attn", prefix=""):
    """x [B_loc, S_loc, D] -> (x', cache_or_None, penalty, occupancy)."""
    cfg = ctx.cfg
    d = attn_dims(cfg, ctx.tp_size)
    dh = d["dh"]
    g = lambda k: p[prefix + k] if prefix else p[k]

    h = common.norm(x, g("ln"), cfg.norm)
    pen, occ = _stats(h, g("sp_in"), ctx)
    xg = boundary.coded_all_gather(h, g("sp_in"), ctx.codec, ctx.tp, axis=1)
    B, S, D = xg.shape

    wq = fsdp_gather(g("wq"), ctx, 0)
    wk = fsdp_gather(g("wk"), ctx, 0)
    wv = fsdp_gather(g("wv"), ctx, 0)

    kv_src = aux.get("cross_src") if prefix else None
    src = kv_src if kv_src is not None else xg

    q = xg @ wq
    k = src @ wk
    v = src @ wv
    if cfg.qkv_bias:
        q = q + g("bq")
        k = k + g("bk")
        v = v + g("bv")
    q = q.reshape(B, S, d["Hq_loc"], dh)
    Skv = src.shape[1]
    k = k.reshape(B, Skv, -1, dh)
    v = v.reshape(B, Skv, -1, dh)

    if kv_src is None and cfg.rope_kind != "none":
        q = _rope(cfg, q, aux)
        k = _rope(cfg, k, aux)

    if d["kv_rep"]:
        # local q heads pick their kv group from the replicated full set
        r = lax.axis_index(ctx.tp)
        gidx = (r * d["Hq_loc"] + jnp.arange(d["Hq_loc"])) // d["group"]
        k_use = jnp.take(k, gidx, axis=2)
        v_use = jnp.take(v, gidx, axis=2)
    else:
        k_use, v_use = k, v

    causal = (not ctx.is_encoder) and (kv_src is None)
    window = cfg.window if kind == "local" else 0
    out = common.flash_attention(
        q, k_use, v_use, causal=causal, window=window,
        cap=cfg.attn_softcap,
        q_chunk=min(512, S), kv_chunk=min(512, Skv))

    out = out.reshape(B, S, d["Hq_loc"] * dh)
    wo = fsdp_gather(g("wo"), ctx, 1)
    part = out @ wo
    y = boundary.coded_psum_scatter(part, g("sp_out"), ctx.codec, ctx.tp,
                                    axis=1)
    if cfg.hnn_mode == "snn":
        y = _maybe_snn(y, g("sp_snn"), ctx)
    if cfg.post_norm:
        y = common.norm(y, g("post_ln"), cfg.norm)

    cache = None
    if ctx.mode == "prefill":
        cache = _reshard_kv_for_decode(k, v, d, ctx)
    return x + y, cache, pen, occ


def _reshard_kv_for_decode(k, v, d, ctx: Context):
    """Train-layout kv (head-sharded or replicated, full seq) ->
    decode layout (seq-sharded over cp, full heads)."""
    n = cp_size(ctx)
    if d["kv_rep"]:
        # already full heads; slice local seq shard
        idx = cp_linear_index(ctx)
        Ss = k.shape[1] // n
        k_s = lax.dynamic_slice_in_dim(k, idx * Ss, Ss, axis=1)
        v_s = lax.dynamic_slice_in_dim(v, idx * Ss, Ss, axis=1)
        return {"k": k_s, "v": v_s}
    # heads sharded over tp: all_to_all seq<->heads over tp; if cp includes
    # dp axes (long-context), additionally slice seq locally.
    k2 = lax.all_to_all(k, ctx.tp, split_axis=1, concat_axis=2, tiled=True)
    v2 = lax.all_to_all(v, ctx.tp, split_axis=1, concat_axis=2, tiled=True)
    extra = n // ctx.tp_size if len(ctx.cp) > 1 else 1
    if extra > 1:
        idx = cp_linear_index(ctx) // ctx.tp_size
        Ss = k2.shape[1] // extra
        k2 = lax.dynamic_slice_in_dim(k2, idx * Ss, Ss, axis=1)
        v2 = lax.dynamic_slice_in_dim(v2, idx * Ss, Ss, axis=1)
    return {"k": k2, "v": v2}


def mlp_fwd(p, x, ctx: Context, aux):
    cfg = ctx.cfg
    h = common.norm(x, p["ln2"], cfg.norm)
    pen, occ = _stats(h, p["sp_in2"], ctx)
    if ctx.mode == "decode":
        # tokens replicated over tp; classic TP with the coded wire on
        # both hops (roundtrip in, spike-accumulated psum out)
        h = boundary.wire_roundtrip(h, p["sp_in2"], ctx.codec)
        w1 = fsdp_gather(p["w1"], ctx, 0)
        w3 = fsdp_gather(p["w3"], ctx, 0)
        w2 = fsdp_gather(p["w2"], ctx, 1)
        hh = common.act_fn(h @ w1, cfg.act) * (h @ w3)
        y = boundary.coded_psum(hh @ w2, p["sp_out2"], ctx.codec, ctx.tp)
    else:
        xg = boundary.coded_all_gather(h, p["sp_in2"], ctx.codec, ctx.tp,
                                       axis=1)
        w1 = fsdp_gather(p["w1"], ctx, 0)
        w3 = fsdp_gather(p["w3"], ctx, 0)
        w2 = fsdp_gather(p["w2"], ctx, 1)
        hh = common.act_fn(xg @ w1, cfg.act) * (xg @ w3)
        part = hh @ w2
        y = boundary.coded_psum_scatter(part, p["sp_out2"], ctx.codec,
                                        ctx.tp, axis=1)
    if cfg.hnn_mode == "snn":
        y = _maybe_snn(y, p.get("sp_snn2"), ctx)
    if cfg.post_norm:
        y = common.norm(y, p["post_ln2"], cfg.norm)
    return x + y, pen, occ


# ---------------------------------------------------------------------------
# paged KV: block-table indexed writes/gathers on the shared page pool
# ---------------------------------------------------------------------------


def _paged_kv_write(cache, bt, qpos, k_new, v_new, ctx: Context, layer):
    """Scatter new KV rows through the block table into the local pool
    shard: ``pool[layer, page, offset]`` with ``page = bt[slot,
    pos//psz]`` — in place on the whole stacked pool, so no per-layer
    slice of it is ever copied.

    cache {k,v} [U, P_loc, psz, Hkv*dh] (this shard's pages of the
    lane-flat pool, every unit); bt [B, PPS] int32 global page ids (-1
    unmapped); qpos [B, K1] absolute write positions; k_new/v_new
    [B, K1, Hkv, dh].

    Writes whose page is unmapped, resident on another shard, or whose
    position falls past the block table (>= PPS * psz) are DROPPED via
    an out-of-bounds scatter index — never clipped into a live page.
    An evicted slot (bt row all -1) therefore cannot corrupt a page
    that was recycled to another slot, which the old slot-major layout
    got for free from slot-private rows.  Valid (page, offset) targets
    are unique across (slot, query): a slot's qpos are distinct and
    live slots' page sets are disjoint (allocator invariant), so the
    scatter needs no duplicate-resolution order.
    """
    ck, cv = cache["k"], cache["v"]
    P_loc, psz = ck.shape[1], ck.shape[2]
    PPS = bt.shape[1]
    pj = qpos // psz                                        # [B, K1]
    oj = qpos % psz
    g = jnp.take_along_axis(bt, jnp.clip(pj, 0, PPS - 1), axis=1)
    loc, _ = pool_local_pages(g, pool_linear_index(ctx), P_loc)
    # a position past the block table (>= PPS * psz) must also drop
    loc = jnp.where(pj < PPS, loc, P_loc)    # OOB index -> mode="drop"
    flat = lambda x, c: x.reshape(*x.shape[:2], -1).astype(c.dtype)
    ck = ck.at[layer, loc, oj].set(flat(k_new, ck), mode="drop")
    cv = cv.at[layer, loc, oj].set(flat(v_new, cv), mode="drop")
    return {"k": ck, "v": cv}


def _combine_partials(o, lse, ctx: Context):
    """Cross-shard combine of a flash partial; coded wire when the codec
    is.  Mode "none" is the plain fp LSE combine; every coded mode
    quantizes the locally-normalized partial to the per-token int8 wire
    (``boundary.quantize_partial`` — bit-identical to the fused kernel's
    epilogue) and combines through ``coded_combine_partials``, so the
    decode step's last fp collective becomes int8 + fp LSE scalars."""
    if ctx.codec.mode == "none":
        return common.combine_decode_partials(o, lse, ctx.cp)
    wire, scale = boundary.quantize_partial(o)
    return boundary.coded_combine_partials(wire, scale, lse, ctx.cp,
                                           jnp.float32)


def _paged_attn_combined(q, cache, page_list, qpos, ctx: Context, window,
                         cap, layer):
    """Paged attention partial + cross-shard combine.

    q [B, K1, Hq, dh] (full heads, post-gather); qpos [B, K1] absolute
    query positions; ``layer`` the unit index into the stacked pool;
    ``page_list`` the engine's compacted per-shard feed (local
    [B, 1, ppc] after sharding).  The fused Pallas kernel runs gather ->
    flash -> LSE partial in one pass over this shard's resident pages,
    with the int8 wire encode fused at the kernel epilogue when the
    codec is coded.  Returns the combined [B, K1, Hq, dh] f32 attention
    output.
    """
    clp, clo = page_list
    clp, clo = clp[:, 0], clo[:, 0]                # [B_loc, ppc]
    if ctx.codec.mode != "none":
        wire, scale, lse = kops.paged_flash_decode(
            q, cache["k"], cache["v"], clp, clo, qpos, layer,
            window=window, cap=cap, encode_wire=True)
        return boundary.coded_combine_partials(wire, scale, lse,
                                               ctx.cp, jnp.float32)
    o, lse = kops.paged_flash_decode(q, cache["k"], cache["v"],
                                     clp, clo, qpos, layer,
                                     window=window, cap=cap)
    return common.combine_decode_partials(o, lse, ctx.cp)


# ---------------------------------------------------------------------------
# forward: decode (one token, context-parallel KV)
# ---------------------------------------------------------------------------


def attn_decode_fwd(p, x, cache, pos, ctx: Context, aux, kind="attn",
                    prefix=""):
    """x [B_loc, 1, D] replicated over tp; pos scalar or [B_loc] per-slot
    positions.  Two cache layouts, selected by ``aux["block_table"]``:

      dense (single-request serve path): cache {k,v} [B_loc, Ss, Hkv, dh]
        seq-sharded over ctx.cp, indexed ``cache[slot, pos]``;
      paged (serving engine): cache {k,v} [U, P_loc, psz, Hkv*dh] —
        this shard's pages of the shared pool, every unit.  The new
        token's K/V is written at ``cache[aux["layer"], page, offset]``
        through the per-slot block table rows in ``aux["block_table"]``;
        attention reads the pages through the paged-decode kernel fed by
        ``aux["page_list"]`` (the engine's compacted per-shard page list,
        required on this layout).

    Returns (x', cache')."""
    cfg = ctx.cfg
    d = attn_dims(cfg, ctx.tp_size)
    dh = d["dh"]
    g = lambda k: p[prefix + k] if prefix else p[k]
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    h = common.norm(x, g("ln"), cfg.norm)
    # block input crosses the die boundary (train/prefill gather it); the
    # decode activation is replicated so the hop is a local roundtrip
    h = boundary.wire_roundtrip(h, g("sp_in"), ctx.codec)
    wq = fsdp_gather(g("wq"), ctx, 0)
    q = h @ wq                                      # [B,1,Hq_loc*dh]
    if cfg.qkv_bias:
        q = q + g("bq")
    q = q.reshape(B, 1, d["Hq_loc"], dh)

    is_cross = prefix != ""
    if not is_cross:
        wk = fsdp_gather(g("wk"), ctx, 0)
        wv = fsdp_gather(g("wv"), ctx, 0)
        k_new = h @ wk
        v_new = h @ wv
        if cfg.qkv_bias:
            k_new = k_new + g("bk")
            v_new = v_new + g("bv")
        k_new = k_new.reshape(B, 1, d["Hkv_loc"], dh)
        v_new = v_new.reshape(B, 1, d["Hkv_loc"], dh)
        if cfg.rope_kind != "none":
            aux_d = dict(aux)
            aux_d["positions"] = pos[:, None]                     # [B,1]
            if cfg.rope_kind == "mrope":
                aux_d["positions3"] = jnp.broadcast_to(
                    pos[None, :, None], (3, B, 1))
            q = _rope(cfg, q, aux_d)
            k_new = _rope(cfg, k_new, aux_d)
        # full q heads / kv heads on every rank — a head-space die
        # boundary, so the gather wire is coded like every other decode
        # collective (int8 per-token absmax; fp only for mode "none")
        if ctx.tp_size > 1:
            q = boundary.coded_head_all_gather(q, ctx.codec, ctx.tp,
                                               axis=2)
        if not d["kv_rep"] and ctx.tp_size > 1:
            k_new = boundary.coded_head_all_gather(k_new, ctx.codec,
                                                   ctx.tp, axis=2)
            v_new = boundary.coded_head_all_gather(v_new, ctx.codec,
                                                   ctx.tp, axis=2)
        bt = aux.get("block_table")
        if bt is not None:
            # paged: route the write through the slot's block-table row
            cache = _paged_kv_write(cache, bt, pos[:, None], k_new, v_new,
                                    ctx, aux["layer"])
        else:
            # dense per-slot cache write: each slot lands at its own
            # position, only on the cp shard that owns it
            Ss = cache["k"].shape[1]
            off = cp_linear_index(ctx) * Ss
            in_range = (pos >= off) & (pos < off + Ss)           # [B]
            loc = jnp.clip(pos - off, 0, Ss - 1)                 # [B]
            bidx = jnp.arange(B)
            k_cur = cache["k"][bidx, loc]                        # [B,Hkv,dh]
            v_cur = cache["v"][bidx, loc]
            sel = in_range[:, None, None]
            k_w = jnp.where(sel, k_new[:, 0].astype(cache["k"].dtype), k_cur)
            v_w = jnp.where(sel, v_new[:, 0].astype(cache["v"].dtype), v_cur)
            cache = {"k": cache["k"].at[bidx, loc].set(k_w),
                     "v": cache["v"].at[bidx, loc].set(v_w)}
    else:
        if ctx.tp_size > 1:
            q = boundary.coded_head_all_gather(q, ctx.codec, ctx.tp,
                                               axis=2)

    window = cfg.window if kind == "local" else 0
    bt = None if is_cross else aux.get("block_table")
    if bt is not None:
        # paged: the fused kernel walks the compacted page lists
        o = _paged_attn_combined(q, cache, aux["page_list"], pos[:, None],
                                 ctx, window, cfg.attn_softcap,
                                 aux["layer"])[:, 0]
    else:
        k_s, v_s, kv_valid = cache["k"], cache["v"], None
        off = cp_linear_index(ctx) * cache["k"].shape[1]
        eff_pos = pos if not is_cross else jnp.full((B,), 10 ** 9,
                                                    jnp.int32)
        o, lse = common.decode_attention_partial(
            q[:, 0], k_s, v_s, pos=eff_pos, shard_offset=off,
            window=window, cap=cfg.attn_softcap, kv_valid=kv_valid)
        o = _combine_partials(o, lse, ctx)

    # output projection: local head slice, psum over tp
    r = lax.axis_index(ctx.tp)
    o_loc = lax.dynamic_slice_in_dim(o, r * d["Hq_loc"], d["Hq_loc"], axis=1)
    wo = fsdp_gather(g("wo"), ctx, 1)
    part = o_loc.reshape(B, 1, d["Hq_loc"] * dh).astype(x.dtype) @ wo
    y = boundary.coded_psum(part, g("sp_out"), ctx.codec, ctx.tp)
    if cfg.post_norm:
        y = common.norm(y, g("post_ln"), cfg.norm)
    return x + y, cache


# ---------------------------------------------------------------------------
# forward: speculative verify (K1 = spec_k+1 tokens, context-parallel KV)
# ---------------------------------------------------------------------------


def attn_verify_fwd(p, x, cache, pos, ctx: Context, aux, kind="attn"):
    """Batched k-token verify: x [B, K1, D] replicated over tp — the last
    committed token followed by spec_k draft tokens per slot; pos [B]
    per-slot *base* positions (query j sits at pos+j).  Cache layout is
    dense ([B, Ss, Hkv, dh] seq-sharded over ctx.cp) or the shared page
    pool ([U, P_loc, psz, Hkv*dh] + ``aux["block_table"]`` for the
    writes, ``aux["page_list"]`` for the kernel's reads, and
    ``aux["layer"]``), exactly as in ``attn_decode_fwd``.

    Every per-token op is shared with ``attn_decode_fwd`` (same norms,
    same ``wire_roundtrip`` spike boundary, same projections), so under
    greedy decoding the verify logits at position j with an all-correct
    draft prefix are bit-identical to j vanilla decode steps.  KV for
    all K1 positions lands in the cache before attention; rejected-draft
    entries are dead by masking (never attended: the committed position
    stays behind them) and are overwritten by the next verify window.
    Returns (x', cache')."""
    cfg = ctx.cfg
    d = attn_dims(cfg, ctx.tp_size)
    dh = d["dh"]
    B, K1, _ = x.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    qpos = pos[:, None] + jnp.arange(K1, dtype=jnp.int32)[None, :]  # [B,K1]

    h = common.norm(x, p["ln"], cfg.norm)
    h = boundary.wire_roundtrip(h, p["sp_in"], ctx.codec)
    wq = fsdp_gather(p["wq"], ctx, 0)
    q = h @ wq                                      # [B,K1,Hq_loc*dh]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(B, K1, d["Hq_loc"], dh)

    wk = fsdp_gather(p["wk"], ctx, 0)
    wv = fsdp_gather(p["wv"], ctx, 0)
    k_new = h @ wk
    v_new = h @ wv
    if cfg.qkv_bias:
        k_new = k_new + p["bk"]
        v_new = v_new + p["bv"]
    k_new = k_new.reshape(B, K1, d["Hkv_loc"], dh)
    v_new = v_new.reshape(B, K1, d["Hkv_loc"], dh)
    if cfg.rope_kind != "none":
        aux_d = dict(aux)
        aux_d["positions"] = qpos
        if cfg.rope_kind == "mrope":
            aux_d["positions3"] = jnp.broadcast_to(qpos[None], (3, B, K1))
        q = _rope(cfg, q, aux_d)
        k_new = _rope(cfg, k_new, aux_d)
    if ctx.tp_size > 1:
        q = boundary.coded_head_all_gather(q, ctx.codec, ctx.tp, axis=2)
    if not d["kv_rep"] and ctx.tp_size > 1:
        k_new = boundary.coded_head_all_gather(k_new, ctx.codec, ctx.tp,
                                               axis=2)
        v_new = boundary.coded_head_all_gather(v_new, ctx.codec, ctx.tp,
                                               axis=2)

    bt = aux.get("block_table")
    window = cfg.window if kind == "local" else 0
    if bt is not None:
        # paged: one duplicate-free scatter for all K1 positions (their
        # (page, offset) targets are distinct by construction), then
        # attend over the slot's resident pages through the fused kernel
        cache = _paged_kv_write(cache, bt, qpos, k_new, v_new, ctx,
                                aux["layer"])
        o = _paged_attn_combined(q, cache, aux["page_list"], qpos, ctx,
                                 window, cfg.attn_softcap, aux["layer"])
    else:
        # dense: scatter the K1 new KV rows one position at a time (K1
        # is static and small) — sequential writes keep the update
        # duplicate-free when out-of-range clips collide with in-range
        # positions
        Ss = cache["k"].shape[1]
        off = cp_linear_index(ctx) * Ss
        bidx = jnp.arange(B)
        ck, cv = cache["k"], cache["v"]
        for j in range(K1):
            pj = qpos[:, j]
            in_range = (pj >= off) & (pj < off + Ss)
            loc = jnp.clip(pj - off, 0, Ss - 1)
            sel = in_range[:, None, None]
            k_w = jnp.where(sel, k_new[:, j].astype(ck.dtype), ck[bidx, loc])
            v_w = jnp.where(sel, v_new[:, j].astype(cv.dtype), cv[bidx, loc])
            ck = ck.at[bidx, loc].set(k_w)
            cv = cv.at[bidx, loc].set(v_w)
        cache = {"k": ck, "v": cv}
        o, lse = common.verify_attention_partial(
            q, cache["k"], cache["v"], pos=qpos, shard_offset=off,
            window=window, cap=cfg.attn_softcap, kv_valid=None)
        o = _combine_partials(o, lse, ctx)

    r = lax.axis_index(ctx.tp)
    o_loc = lax.dynamic_slice_in_dim(o, r * d["Hq_loc"], d["Hq_loc"], axis=2)
    wo = fsdp_gather(p["wo"], ctx, 1)
    part = o_loc.reshape(B, K1, d["Hq_loc"] * dh).astype(x.dtype) @ wo
    y = boundary.coded_psum(part, p["sp_out"], ctx.codec, ctx.tp)
    if cfg.post_norm:
        y = common.norm(y, p["post_ln"], cfg.norm)
    return x + y, cache
