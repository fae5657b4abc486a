"""Input/cache ShapeDtypeStructs + PartitionSpecs per (arch x shape cell).

This is the dry-run contract: everything jit'd in train.py/serve.py is
lowered against these stand-ins (weak-type-correct, shardable, no device
allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, ShapeCell
from ..models import blocks_attn, blocks_rnn, blocks_ssm
from ..models.context import Context, codec_from_name

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class CellPlan:
    """Static plan for one (arch, shape, mesh) cell."""

    cfg: ModelConfig
    cell: ShapeCell
    dp: tuple                    # data axes
    tp: str
    dp_size: int
    tp_size: int
    batch_sharded: bool          # batch over dp? (False -> replicated)
    cp: tuple                    # context-parallel axes for decode


def make_plan(cfg: ModelConfig, cell: ShapeCell, mesh) -> CellPlan:
    names = mesh.axis_names
    dp = tuple(n for n in names if n != "model")
    tp = "model"
    dp_size = 1
    for n in dp:
        dp_size *= mesh.shape[n]
    tp_size = mesh.shape[tp]
    batch_sharded = cell.global_batch % dp_size == 0
    if cell.kind == "decode":
        cp = (tp,) if batch_sharded else dp + (tp,)
    else:
        cp = (tp,)
    return CellPlan(cfg, cell, dp, tp, dp_size, tp_size, batch_sharded, cp)


def make_context(plan: CellPlan, mode: str) -> Context:
    cfg = plan.cfg
    codec = codec_from_name(cfg.codec, cfg.hnn_mode)
    return Context(cfg=cfg, dp=plan.dp, tp=plan.tp, dp_size=plan.dp_size,
                   tp_size=plan.tp_size, codec=codec, mode=mode, cp=plan.cp)


def _bspec(plan: CellPlan):
    """PartitionSpec entry for the global batch dim."""
    if not plan.batch_sharded:
        return None
    return plan.dp if len(plan.dp) > 1 else plan.dp[0]


# ---------------------------------------------------------------------------
# train / prefill inputs
# ---------------------------------------------------------------------------


def train_input_specs(plan: CellPlan):
    """(ShapeDtypeStruct tree, PartitionSpec tree) for a train batch."""
    cfg, cell = plan.cfg, plan.cell
    B, S = cell.global_batch, cell.seq_len
    bs = _bspec(plan)
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    specs = {"tokens": P(bs, plan.tp), "labels": P(bs, plan.tp)}
    batch = {"tokens": tok, "labels": tok}
    if cfg.is_encdec:
        # half the token budget to the encoder (frame embeddings), half
        # to the decoder (text): S_enc = S_dec = S/2
        S2 = S // 2
        batch = {"tokens": jax.ShapeDtypeStruct((B, S2), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((B, S2), jnp.int32),
                 "enc_embeds": jax.ShapeDtypeStruct((B, S2, cfg.d_model),
                                                    cfg.dtype)}
        specs = {"tokens": P(bs, plan.tp), "labels": P(bs, plan.tp),
                 "enc_embeds": P(bs, plan.tp, None)}
    if cfg.rope_kind == "mrope":
        batch["positions3"] = jax.ShapeDtypeStruct((3, B, S), jnp.int32)
        specs["positions3"] = P(None, bs, plan.tp)
    return batch, specs


# ---------------------------------------------------------------------------
# decode inputs (KV/state caches)
# ---------------------------------------------------------------------------


def cache_specs(plan: CellPlan):
    """(ShapeDtypeStruct tree, PartitionSpec tree) for the decode cache."""
    cfg, cell = plan.cfg, plan.cell
    tp = plan.tp_size
    U = cfg.n_units
    B, S = cell.global_batch, cell.seq_len
    bs = _bspec(plan)
    cps = plan.cp if len(plan.cp) > 1 else plan.cp[0]
    dt = cfg.dtype

    structs: dict[str, Any] = {}
    specs: dict[str, Any] = {}
    d_at = blocks_attn.attn_dims(cfg, tp)

    for i, kind in enumerate(cfg.pattern):
        st: dict[str, Any] = {}
        sp: dict[str, Any] = {}
        if kind in ("attn", "global", "local", "attn_moe"):
            shape = (U, B, S, d_at["Hkv"], d_at["dh"])
            st["kv"] = {"k": jax.ShapeDtypeStruct(shape, dt),
                        "v": jax.ShapeDtypeStruct(shape, dt)}
            sp["kv"] = {"k": P(None, bs, cps, None, None),
                        "v": P(None, bs, cps, None, None)}
            if cfg.is_encdec:
                S_enc = max(cell.seq_len // 8, 32)
                xshape = (U, B, S_enc, d_at["Hkv"], d_at["dh"])
                st["cross_kv"] = {"k": jax.ShapeDtypeStruct(xshape, dt),
                                  "v": jax.ShapeDtypeStruct(xshape, dt)}
                sp["cross_kv"] = sp["kv"]
        elif kind in ("mamba", "mamba_mlp", "mamba_moe"):
            d = blocks_ssm.ssm_dims(cfg, tp)
            st["ssm_state"] = {
                "conv": jax.ShapeDtypeStruct((U, B, d["K"] - 1, d["Di"]), dt),
                "ssm": jax.ShapeDtypeStruct((U, B, d["Di"], d["N"]), F32)}
            sp["ssm_state"] = {"conv": P(None, bs, None, plan.tp),
                               "ssm": P(None, bs, plan.tp, None)}
        elif kind == "mlstm":
            d = blocks_rnn.mlstm_dims(cfg, tp)
            st["rnn_state"] = {
                "C": jax.ShapeDtypeStruct((U, B, d["H"], d["dh"], d["dh"]),
                                          F32),
                "n": jax.ShapeDtypeStruct((U, B, d["H"], d["dh"]), F32),
                "m": jax.ShapeDtypeStruct((U, B, d["H"]), F32)}
            sp["rnn_state"] = {"C": P(None, bs, plan.tp, None, None),
                               "n": P(None, bs, plan.tp, None),
                               "m": P(None, bs, plan.tp)}
        elif kind == "slstm":
            d = blocks_rnn.mlstm_dims(cfg, tp)
            shape = (U, B, d["H"], d["dh"])
            st["rnn_state"] = {k: jax.ShapeDtypeStruct(shape, F32)
                               for k in ("c", "n", "h", "m")}
            sp["rnn_state"] = {k: P(None, bs, plan.tp, None)
                               for k in ("c", "n", "h", "m")}
        elif kind == "rwkv":
            d = blocks_rnn.rwkv_dims(cfg, tp)
            D = cfg.d_model
            st["rwkv_state"] = {
                "x_tm": jax.ShapeDtypeStruct((U, B, D), dt),
                "x_cm": jax.ShapeDtypeStruct((U, B, D), dt),
                "aa": jax.ShapeDtypeStruct((U, B, d["C"]), F32),
                "bb": jax.ShapeDtypeStruct((U, B, d["C"]), F32),
                "pp": jax.ShapeDtypeStruct((U, B, d["C"]), F32)}
            sp["rwkv_state"] = {
                "x_tm": P(None, bs, None), "x_cm": P(None, bs, None),
                "aa": P(None, bs, plan.tp), "bb": P(None, bs, plan.tp),
                "pp": P(None, bs, plan.tp)}
        structs[f"pos{i}"] = st
        specs[f"pos{i}"] = sp
    return structs, specs


def pages_per_slot(max_seq: int, page_size: int) -> int:
    """Block-table width: pages a slot at full ``max_seq`` occupancy maps."""
    return -(-max_seq // page_size)


def default_num_pages(plan: CellPlan, page_size: int) -> int:
    """Pool size that reproduces the old dense reservation exactly.

    Per dp group: every local slot can map ``pages_per_slot`` pages
    (rounded up to a tp multiple so the pool dim shards evenly over the
    dp x tp devices).  With this default the pool can never exhaust
    before the slot count does — byte-for-byte the old guarantee — and
    shrinking ``num_pages`` below it is the knob paging buys.
    """
    pps = pages_per_slot(plan.cell.seq_len, page_size)
    slots_loc = plan.cell.global_batch // plan.dp_size
    per_group = -(-slots_loc * pps // plan.tp_size) * plan.tp_size
    return per_group * plan.dp_size


def _pool_axes(plan: CellPlan):
    """Mesh axes the page-pool dim shards over: ALL of them (dp x tp).

    Slots are batch-sharded over dp and each slot's pages are drawn from
    its own dp group's contiguous page range (allocator invariant), so
    sharding pages over dp+tp keeps every slot's pages on its own dp
    group's tp shards — the flash-decode LSE combine stays over
    ``plan.cp`` exactly as in the dense layout.
    """
    return tuple(plan.dp) + (plan.tp,)


def paged_cache_specs(plan: CellPlan, page_size: int, num_pages: int):
    """(ShapeDtypeStruct tree, PartitionSpec tree) for the POOLED cache.

    Attention KV leaves become a shared device page pool
    ``[U, num_pages, page_size, Hkv*dh]`` with the page dim sharded
    over dp x tp (see ``_pool_axes``).  The heads are folded into the
    minor (lane) dim so the pool's default TPU layout is row-major, the
    layout the fused decode kernel reads; with a minor dim of ``dh``
    (64 for many models) XLA lays the pool out page-minor and copies the
    whole pool around every kernel call.  Recurrent/SSM state leaves stay
    slot-major — only attention KV pages (state cannot be paged: it is
    O(1) per slot and every block reads all of it every step).
    """
    cfg = plan.cfg
    if cfg.is_encdec:
        raise NotImplementedError(
            "paged KV for encoder-decoder (cross_kv) serving: follow-on")
    structs, specs = cache_specs(plan)
    d_at = blocks_attn.attn_dims(cfg, plan.tp_size)
    shape = (cfg.n_units, num_pages, page_size, d_at["Hkv"] * d_at["dh"])
    sp = P(None, _pool_axes(plan), None, None)
    for i, kind in enumerate(cfg.pattern):
        if kind in ("attn", "global", "local", "attn_moe"):
            structs[f"pos{i}"]["kv"] = {
                "k": jax.ShapeDtypeStruct(shape, cfg.dtype),
                "v": jax.ShapeDtypeStruct(shape, cfg.dtype)}
            specs[f"pos{i}"]["kv"] = {"k": sp, "v": sp}
    return structs, specs


def prefill_handoff_specs(plan: CellPlan):
    """(ShapeDtypeStruct tree, PartitionSpec tree) of the cache the engine
    prefill program hands to the pool insert.

    ``cache_specs`` of the prefill plan, with each attention KV leaf
    lane-flat ``[U, B, S, Hkv*dh]``: the page pool's row layout, so the
    insert copies whole pages without relaying out a row.  State leaves
    are as in ``cache_specs``.
    """
    structs, specs = cache_specs(plan)
    for i, kind in enumerate(plan.cfg.pattern):
        if kind in ("attn", "global", "local", "attn_moe"):
            st, sp = structs[f"pos{i}"]["kv"], specs[f"pos{i}"]["kv"]
            for kk in ("k", "v"):
                U, B, S, Hkv, dh = st[kk].shape
                st[kk] = jax.ShapeDtypeStruct((U, B, S, Hkv * dh),
                                              st[kk].dtype)
                sp[kk] = P(*sp[kk][:3], None)
    return structs, specs


def block_table_specs(plan: CellPlan, page_size: int):
    """(ShapeDtypeStruct, PartitionSpec) of the per-slot block table.

    ``[slots, pages_per_slot]`` int32 global page ids (-1 = unmapped),
    slot dim batch-sharded like the tokens so each dp rank sees exactly
    its local slots' rows; replicated over tp (every tp shard needs the
    full row to find its resident pages).
    """
    B, S = plan.cell.global_batch, plan.cell.seq_len
    pps = pages_per_slot(S, page_size)
    return (jax.ShapeDtypeStruct((B, pps), jnp.int32),
            P(_bspec(plan), None))


def page_list_specs(plan: CellPlan, page_size: int):
    """(ShapeDtypeStructs, PartitionSpecs) of the compacted page lists.

    Two ``[slots, pool_shards, pages_per_shard]`` int32 arrays (local
    page row / absolute start position, -1 = no page) built by the
    allocator alongside the block table and staged per dispatch the same
    way.  The slot dim is batch-sharded like the tokens; the shard dim
    is sharded over tp so each device receives exactly ITS OWN
    ``[B_loc, 1, pages_per_shard]`` list — the fused paged-decode kernel
    walks only these entries instead of the full ``pages_per_slot``-wide
    table.  ``pages_per_shard = ceil(pages_per_slot / pool_shards_per_
    group)``: the 1/cp page-count reduction the dense layout had.
    """
    B, S = plan.cell.global_batch, plan.cell.seq_len
    groups = plan.dp_size if plan.batch_sharded else 1
    shards = (plan.dp_size * plan.tp_size) // groups
    pps = -(-pages_per_slot(S, page_size) // shards)
    struct = jax.ShapeDtypeStruct((B, shards, pps), jnp.int32)
    # shard dim over tp only when slots are dp-sharded (shards == tp);
    # in the replicated-batch case the shard dim spans dp x tp
    saxes = plan.tp if plan.batch_sharded else _pool_axes(plan)
    sp = P(_bspec(plan), saxes, None)
    return (struct, struct), (sp, sp)


def decode_input_specs(plan: CellPlan):
    """(inputs, specs) for one decode step: cache + token + pos."""
    cfg, cell = plan.cfg, plan.cell
    B = cell.global_batch
    bs = _bspec(plan)
    cache, cache_sp = cache_specs(plan)
    inputs = {"cache": cache,
              "token": jax.ShapeDtypeStruct((B,), jnp.int32),
              "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    specs = {"cache": cache_sp, "token": P(bs), "pos": P()}
    return inputs, specs


def serve_decode_input_specs(plan: CellPlan, page_size: int,
                             num_pages: int):
    """(inputs, specs) for one batched engine decode step.

    Differs from ``decode_input_specs`` in the scheduler-facing inputs
    (per-slot positions and sampling temperatures, batch-sharded like
    the tokens, plus a replicated PRNG key) and in the cache layout:
    the engine cache is the shared KV page pool + per-slot block table
    (``paged_cache_specs`` / ``block_table_specs``).
    """
    cfg, cell = plan.cfg, plan.cell
    B = cell.global_batch
    bs = _bspec(plan)
    cache, cache_sp = paged_cache_specs(plan, page_size, num_pages)
    bt, bt_sp = block_table_specs(plan, page_size)
    (clp, clo), (clp_sp, clo_sp) = page_list_specs(plan, page_size)
    inputs = {"cache": cache,
              "token": jax.ShapeDtypeStruct((B,), jnp.int32),
              "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
              "bt": bt, "clp": clp, "clo": clo,
              "temp": jax.ShapeDtypeStruct((B,), jnp.float32),
              "key": jax.ShapeDtypeStruct((2,), jnp.uint32)}
    specs = {"cache": cache_sp, "token": P(bs), "pos": P(bs),
             "bt": bt_sp, "clp": clp_sp, "clo": clo_sp,
             "temp": P(bs), "key": P()}
    return inputs, specs


def serve_feed_specs(plan: CellPlan, page_size: int, spec_k: int = 0):
    """PartitionSpecs for the engine's per-dispatch feed staging.

    The async engine (``EngineConfig.async_depth > 0``) double-buffers
    its scheduler-facing inputs: each dispatch stages a FRESH device
    copy of the host token/pos/temp arrays and block table (via
    ``jax.device_put`` with these specs), while the in-flight step keeps
    sole ownership of the previous copies — host-side scheduling can
    then mutate its arrays for step t+1 without racing step t's
    transfer.  Staging with the step's own input sharding also means no
    reshard sits between the feed and the compiled shard_map program.
    ``vtoken`` (present when ``spec_k > 0``) is the [B, spec_k+1]
    speculative token block of a verify step.
    """
    bs = _bspec(plan)
    _, bt_sp = block_table_specs(plan, page_size)
    _, (clp_sp, clo_sp) = page_list_specs(plan, page_size)
    specs = {"token": P(bs), "pos": P(bs), "temp": P(bs), "bt": bt_sp,
             "clp": clp_sp, "clo": clo_sp}
    if spec_k > 0:
        specs["vtoken"] = P(bs, None)
    return specs


def verify_shape_cell(max_seq: int, num_slots: int, spec_k: int) -> ShapeCell:
    """Shape cell for the speculative k-token verify program.

    Same (seq_len, batch, kind) footprint as the decode cell — the verify
    step reads/writes the same slot-major cache — but named per ``spec_k``
    so dry-run/roofline tables key the two compiled programs apart.
    """
    return ShapeCell(f"serve_verify_k{spec_k}", max_seq, num_slots, "decode")


def serve_verify_input_specs(plan: CellPlan, spec_k: int, page_size: int,
                             num_pages: int):
    """(inputs, specs) for one batched speculative-verify step.

    Like ``serve_decode_input_specs`` but with K1 = spec_k+1 token
    columns per slot (last committed token + spec_k draft tokens) and a
    per-slot *base* position; the sampled-output token block is [B, K1].
    The cache is the same page pool + block table as the decode step —
    the two programs alternate over one donated buffer set.
    """
    cfg, cell = plan.cfg, plan.cell
    B = cell.global_batch
    bs = _bspec(plan)
    cache, cache_sp = paged_cache_specs(plan, page_size, num_pages)
    bt, bt_sp = block_table_specs(plan, page_size)
    (clp, clo), (clp_sp, clo_sp) = page_list_specs(plan, page_size)
    K1 = spec_k + 1
    inputs = {"cache": cache,
              "token": jax.ShapeDtypeStruct((B, K1), jnp.int32),
              "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
              "bt": bt, "clp": clp, "clo": clo,
              "temp": jax.ShapeDtypeStruct((B,), jnp.float32),
              "key": jax.ShapeDtypeStruct((2,), jnp.uint32)}
    specs = {"cache": cache_sp, "token": P(bs, None), "pos": P(bs),
             "bt": bt_sp, "clp": clp_sp, "clo": clo_sp,
             "temp": P(bs), "key": P()}
    return inputs, specs


def serve_heads_feed_specs(plan: CellPlan, page_size: int, spec_k: int):
    """PartitionSpecs for the HEADS-drafter verify feed chain.

    With ``EngineConfig.drafter = "heads"`` the verify step itself emits
    the next dispatch's inputs — ``vtoken`` [B, spec_k+1] (corrected
    token + head-argmax drafts) and ``vpos`` [B] (base position advanced
    by the accepted length) — which the engine chains device-to-device
    exactly like the async decode token feed (PR 5): no host join sits
    between verify dispatches.  ``vpos`` shares the ``pos`` layout; it
    gets its own key because the heads chain stages BOTH arrays fresh
    only on re-seed (admission / post-suspend), not per dispatch.
    """
    specs = serve_feed_specs(plan, page_size, spec_k)
    specs["vpos"] = specs["pos"]
    return specs
