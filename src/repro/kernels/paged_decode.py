"""Pallas TPU kernel: fused paged-decode attention over compacted lists.

The kernel walks each batch slot's compacted per-shard page list
(host-built by ``serving.kv_cache.SlotAllocator`` next to the block
table) and fuses the three stages the reference path runs separately:

    page gather -> online-softmax flash decode (K1 >= 1 query tokens,
    covering both the decode K1=1 case and spec verify) -> locally
    normalized partial + LSE for the cross-shard combine,

optionally with the int8 wire encode of the attention output fused at
the epilogue (the ``pack4.py`` / ``lif_encode.py`` idiom): the partial
leaves the kernel already quantized for the coded die-to-die combine,
so no ``[B, pages_per_slot*psz, Hkv, dh]`` gathered KV block ever
materializes in HBM.  Work per slot is ``pages_per_shard =
ceil(pages_per_slot / pool_shards)`` pages — the 1/cp page-count
reduction the dense layout had — instead of the full block table the
reference gather scores and masks.

Numerics: f32 throughout, same -1e30 masking sentinel and 1e-30
normalizer floors as ``models.common.verify_attention_partial``.  The
online per-page max/rescale reduction is mathematically identical to
the reference's single-max softmax but associates differently, so
results agree to fp epsilon, not bit-for-bit; greedy token-identity of
the served stream is what the engine fuzz enforces.  An invalid (-1)
list entry is scored against pool row 0 and masked to -1e30, so next to
any valid page its weight is exactly 0.  A fully masked shard (no
resident page at <= qpos) yields lse ~= -1e30 exactly like the
reference, so its weight underflows to exactly 0 in the combine.

Layout.  The pool is stacked over units and lane-flat, ``[U, P_loc,
psz, Hkv*dh]``, and the unit to read is a scalar operand: the decode
step's layer scan carries the whole pool and writes it in place, so no
per-layer slice of it is ever copied.  With a minor dim of ``Hkv*dh``
(not ``dh``, often 64) the TPU's default HBM layout of the pool is
row-major, which is what the kernel reads, so no relayout copy of the
pool sits in front of the call either.  Heads are handled without
splitting lanes: each query row ``(j, h)`` is expanded to the full
``Hkv*dh`` width with zeros outside its kv head's ``dh`` block
(``q_exp``), so one ``[K1*Hq, Hkv*dh] x [Hkv*dh, psz]`` product scores
every head (GQA included) and ``p @ V`` gives every head's output in its
own block; the epilogue masks the other blocks to zero and the wrapper
picks each row's block.

Grid (B, ppc), the page axis innermost and sequential.  The unit index,
page lists and query positions are scalar-prefetch operands (SMEM); the
pools stay in HBM and each grid step's ``index_map`` reads the page row
from the prefetched list, so the pipeline DMAs one ``[psz, Hkv*dh]`` page of K
and of V into VMEM per step (an invalid tail maps to row 0 repeatedly,
which the pipeline does not re-fetch).  The online-softmax state (m, l,
acc) lives in VMEM scratch across the page axis; the outputs are
per-slot blocks written once, after the slot's last page.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _paged_decode_kernel(layer_ref, clp_ref, clo_ref, qpos_ref, q_ref,
                         bm_ref, k_ref, v_ref, *refs, K1: int, scale: float,
                         window: int, cap: float, encode_wire: bool):
    *out_refs, m_ref, l_ref, acc_ref = refs
    b, c = pl.program_id(0), pl.program_id(1)
    ppc = pl.num_programs(1)
    R = q_ref.shape[1]                              # K1 * Hq query rows
    Hq = R // K1

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    q = q_ref[0].astype(F32)                        # [R, Hkv*dh]
    k = k_ref[0, 0].astype(F32)                     # [psz, Hkv*dh]
    v = v_ref[0, 0].astype(F32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # [R, psz]
    if cap:
        s = cap * jnp.tanh(s / cap)
    k_pos = clo_ref[b * ppc + c] + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    q_pos = jnp.full(s.shape, qpos_ref[b * K1], jnp.int32)
    for j in range(1, K1):
        q_pos = jnp.where(row >= j * Hq, qpos_ref[b * K1 + j], q_pos)
    mask = (clp_ref[b * ppc + c] >= 0) & (k_pos <= q_pos)
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, -1e30)
    m = m_ref[...]                                  # [R, 1]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=F32)
    m_ref[...] = m_new

    @pl.when(c == ppc - 1)
    def _finish():
        l = l_ref[...]
        o = acc_ref[...] * bm_ref[...] / jnp.maximum(l, 1e-30)
        lse = m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))
        if encode_wire:
            wire_ref, scale_ref, lse_ref = out_refs
            s_q = jnp.maximum(jnp.max(jnp.abs(o), axis=1, keepdims=True),
                              1e-6) / 127.0
            wire_ref[0] = jnp.round(o / s_q).astype(jnp.int8)
            scale_ref[0] = s_q
        else:
            o_ref, lse_ref = out_refs
            o_ref[0] = o
        lse_ref[0] = lse


def paged_decode_pallas(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        cl_page: jax.Array, cl_pos: jax.Array,
                        qpos: jax.Array, layer, *, window: int = 0,
                        cap: float = 0.0, encode_wire: bool = False,
                        interpret: bool = False):
    """Fused gather->flash->partial over one pool shard.

    q [B, K1, Hq, dh]; k_pool/v_pool [U, P_loc, psz, Hkv*dh] (this
    shard's lane-flat pool slice, every unit); cl_page [B, ppc] int32
    shard-LOCAL page rows (-1 = no page); cl_pos [B, ppc] int32 absolute
    position of each page's first token; qpos [B, K1] int32 absolute
    per-query positions; layer: int32 scalar, the unit to read.

    Returns ``(o [B,K1,Hq,dh] f32, lse [B,K1,Hq] f32)``, or with
    ``encode_wire`` the epilogue-quantized partial ``(wire int8
    [B,K1,Hq,dh], scale f32 [B,K1,Hq,1], lse)`` ready for the coded
    cross-shard combine (``core.boundary.coded_combine_partials``).
    """
    B, K1, Hq, dh = q.shape
    _, _, psz, L = k_pool.shape
    Hkv = L // dh
    g = Hq // Hkv
    R = K1 * Hq
    ppc = cl_page.shape[1]
    # row (j, h) of q_exp / bm lives in kv head h // g's dh-lane block
    own = jnp.arange(Hq)[:, None] // g == jnp.arange(Hkv)[None, :]
    q_exp = jnp.where(own[None, None, :, :, None], q[:, :, :, None, :],
                      0).astype(q.dtype).reshape(B, R, L)
    bm = jnp.broadcast_to(own[None, :, :, None],
                          (K1, Hq, Hkv, dh)).reshape(R, L).astype(F32)

    def page_map(b, c, lay, clp, clo, qp):
        return lay[0], jnp.maximum(clp[b * ppc + c], 0), 0, 0

    def slot_map(b, c, *_):
        return b, 0, 0

    rows_spec = pl.BlockSpec((1, R, L), slot_map)
    col_spec = pl.BlockSpec((1, R, 1), slot_map)
    lse_shape = jax.ShapeDtypeStruct((B, R, 1), F32)
    if encode_wire:
        out_shape = (jax.ShapeDtypeStruct((B, R, L), jnp.int8),
                     jax.ShapeDtypeStruct((B, R, 1), F32), lse_shape)
        out_specs = (rows_spec, col_spec, col_spec)
    else:
        out_shape = (jax.ShapeDtypeStruct((B, R, L), F32), lse_shape)
        out_specs = (rows_spec, col_spec)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, ppc),
        in_specs=[rows_spec,
                  pl.BlockSpec((R, L), lambda b, c, *_: (0, 0)),
                  pl.BlockSpec((1, 1, psz, L), page_map),
                  pl.BlockSpec((1, 1, psz, L), page_map)],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((R, 1), F32), pltpu.VMEM((R, 1), F32),
                        pltpu.VMEM((R, L), F32)])
    outs = pl.pallas_call(
        functools.partial(_paged_decode_kernel, K1=K1,
                          scale=1.0 / math.sqrt(dh), window=window, cap=cap,
                          encode_wire=encode_wire),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        # the custom call's name in HLO and in the device trace, whatever
        # jitted function encloses the call
        name="paged_flash_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      cl_page.reshape(-1).astype(jnp.int32),
      cl_pos.reshape(-1).astype(jnp.int32),
      qpos.reshape(-1).astype(jnp.int32), q_exp, bm, k_pool, v_pool)
    # each row's own kv-head block (the others are exactly zero)
    kv_of = (jnp.arange(Hq) // g)[None, None, :, None, None]
    own_block = lambda x: jnp.take_along_axis(
        x.reshape(B, K1, Hq, Hkv, dh), kv_of, axis=3)[:, :, :, 0]
    lse = outs[-1].reshape(B, K1, Hq)
    if encode_wire:
        wire, s_q, _ = outs
        return own_block(wire), s_q.reshape(B, K1, Hq, 1), lse
    return own_block(outs[0]), lse
