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
list entry inside a walked block is scored against a resident pool row
and masked to -1e30, so next to any valid page its weight is exactly 0.
A fully masked shard (no resident page at <= qpos) yields lse ~= -1e30
exactly like the reference, so its weight underflows to exactly 0 in
the combine.

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

Grid (B, ceil(ppc / N)), the block axis innermost and sequential.  A
grid step walks a block of ``N`` list entries: the pools are passed
``N`` times over, input ``i`` reading entry ``c * N + i`` through its
``index_map``, so the pipeline DMAs ``N`` ``[psz, Hkv*dh]`` pages of K
and of V into VMEM per step, and they are scored against the query rows
as one ``[K1*Hq, N*psz]`` tile under one online-softmax update.  ``N``
comes from the shapes the kernel sees (``pages_per_block``): about
``BLOCK_TOKENS`` tokens a block, within ``BLOCK_VMEM_BYTES`` for the
double-buffered K and V blocks, never more than the list is long.  The
wrapper pads the lists to whole blocks with -1 and computes, on the
device, each slot's walk length ``n_blk``: the blocks up to its last
mapped entry, at least one.  Steps past it clamp their index maps to
the last walked block, which is already resident, so the pipeline
fetches nothing, and skip the body; the unmapped entries of a walked
block fetch the slot's last mapped row.  The unit index, fetch rows,
page positions, query positions and walk lengths are scalar-prefetch
operands (SMEM).  The online-softmax state (m, l, acc) lives in VMEM
scratch across the block axis; the outputs are per-slot blocks written
once, at the last grid index.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

#: tokens of K (and of V) one grid step aims to walk
BLOCK_TOKENS = 256
#: VMEM for one grid step's K and V page blocks, double-buffered
BLOCK_VMEM_BYTES = 4 << 20


def pages_per_block(ppc: int, psz: int, lanes: int, itemsize: int) -> int:
    """List entries ``N`` one grid step walks, from the kernel's shapes:
    ``BLOCK_TOKENS`` tokens of pages, no more than fit
    ``BLOCK_VMEM_BYTES`` double-buffered for K and V, no more than the
    list's ``ppc`` entries, at least one."""
    page_bytes = psz * lanes * itemsize
    return max(1, min(BLOCK_TOKENS // psz,
                      BLOCK_VMEM_BYTES // (4 * page_bytes), ppc))


def blocks_walked(fill, n: int) -> int:
    """Blocks the kernel computes, summed over lists holding ``fill``
    mapped entries each (compacted, so the last mapped entry is the
    ``fill``-th), at ``n`` entries a block: ``max(1, ceil(fill / n))``
    each."""
    return int(np.maximum(1, -(-np.asarray(fill) // n)).sum())


def _paged_decode_kernel(layer_ref, rows_ref, clo_ref, qpos_ref, nblk_ref,
                         q_ref, bm_ref, *refs, N: int, K1: int, psz: int,
                         scale: float, window: int, cap: float,
                         encode_wire: bool):
    k_refs, v_refs = refs[:N], refs[N:2 * N]
    *out_refs, m_ref, l_ref, acc_ref = refs[2 * N:]
    b, c = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    R = q_ref.shape[1]                              # K1 * Hq query rows
    Hq = R // K1

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    @pl.when(c < nblk_ref[b])
    def _block():
        base = (b * nb + c) * N
        q = q_ref[0].astype(F32)                    # [R, Hkv*dh]
        k = jnp.concatenate([r[0, 0].astype(F32) for r in k_refs], axis=0)
        v = jnp.concatenate([r[0, 0].astype(F32) for r in v_refs], axis=0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        if cap:                                     # s [R, N*psz]
            s = cap * jnp.tanh(s / cap)
        # column t holds token t - i*psz of entry i = t // psz: its
        # position is clo[entry] - i*psz + t, its row < 0 if unmapped
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        k_pos = jnp.full(s.shape, clo_ref[base], jnp.int32)
        entry_row = jnp.full(s.shape, rows_ref[base], jnp.int32)
        for i in range(1, N):
            later = col >= i * psz
            k_pos = jnp.where(later, clo_ref[base + i] - i * psz, k_pos)
            entry_row = jnp.where(later, rows_ref[base + i], entry_row)
        k_pos = k_pos + col
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = jnp.full(s.shape, qpos_ref[b * K1], jnp.int32)
        for j in range(1, K1):
            q_pos = jnp.where(row >= j * Hq, qpos_ref[b * K1 + j], q_pos)
        mask = (entry_row >= 0) & (k_pos <= q_pos)
        if window:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, -1e30)
        m = m_ref[...]                              # [R, 1]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=F32)
        m_ref[...] = m_new

    @pl.when(c == nb - 1)
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
    N = pages_per_block(ppc, psz, L, k_pool.dtype.itemsize)
    nb = -(-ppc // N)
    # row (j, h) of q_exp / bm lives in kv head h // g's dh-lane block
    own = jnp.arange(Hq)[:, None] // g == jnp.arange(Hkv)[None, :]
    q_exp = jnp.where(own[None, None, :, :, None], q[:, :, :, None, :],
                      0).astype(q.dtype).reshape(B, R, L)
    bm = jnp.broadcast_to(own[None, :, :, None],
                          (K1, Hq, Hkv, dh)).reshape(R, L).astype(F32)
    # lists padded to whole blocks; each slot's walk ends at the block of
    # its last mapped entry (block 0 at least); an unmapped entry r < 0
    # fetches row -1 - r, the slot's last mapped row (0 if it has none)
    pad = ((0, 0), (0, nb * N - ppc))
    clp = jnp.pad(cl_page.astype(jnp.int32), pad, constant_values=-1)
    clo = jnp.pad(cl_pos.astype(jnp.int32), pad, constant_values=-1)
    mapped = clp >= 0
    n_mapped = jnp.max(jnp.where(mapped, jnp.arange(1, nb * N + 1), 0),
                       axis=1)
    n_blk = jnp.maximum(1, -(-n_mapped // N))
    last_row = jnp.take_along_axis(
        clp, jnp.maximum(n_mapped - 1, 0)[:, None], axis=1)
    rows = jnp.where(mapped, clp, -1 - jnp.maximum(last_row, 0))

    def page_map(i):
        def index(b, c, lay, rows, clo, qp, nblk):
            r = rows[(b * nb + jnp.minimum(c, nblk[b] - 1)) * N + i]
            return lay[0], jnp.where(r >= 0, r, -1 - r), 0, 0
        return index

    def slot_map(b, c, *_):
        return b, 0, 0

    rows_spec = pl.BlockSpec((1, R, L), slot_map)
    col_spec = pl.BlockSpec((1, R, 1), slot_map)
    page_specs = [pl.BlockSpec((1, 1, psz, L), page_map(i))
                  for i in range(N)]
    lse_shape = jax.ShapeDtypeStruct((B, R, 1), F32)
    if encode_wire:
        out_shape = (jax.ShapeDtypeStruct((B, R, L), jnp.int8),
                     jax.ShapeDtypeStruct((B, R, 1), F32), lse_shape)
        out_specs = (rows_spec, col_spec, col_spec)
    else:
        out_shape = (jax.ShapeDtypeStruct((B, R, L), F32), lse_shape)
        out_specs = (rows_spec, col_spec)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, nb),
        in_specs=[rows_spec,
                  pl.BlockSpec((R, L), lambda b, c, *_: (0, 0)),
                  *page_specs, *page_specs],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((R, 1), F32), pltpu.VMEM((R, 1), F32),
                        pltpu.VMEM((R, L), F32)])
    outs = pl.pallas_call(
        functools.partial(_paged_decode_kernel, N=N, K1=K1, psz=psz,
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
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.reshape(-1),
      clo.reshape(-1), qpos.reshape(-1).astype(jnp.int32), n_blk,
      q_exp, bm, *[k_pool] * N, *[v_pool] * N)
    # each row's own kv-head block (the others are exactly zero)
    kv_of = (jnp.arange(Hq) // g)[None, None, :, None, None]
    own_block = lambda x: jnp.take_along_axis(
        x.reshape(B, K1, Hq, Hkv, dh), kv_of, axis=3)[:, :, :, 0]
    lse = outs[-1].reshape(B, K1, Hq)
    if encode_wire:
        wire, s_q, _ = outs
        return own_block(wire), s_q.reshape(B, K1, Hq, 1), lse
    return own_block(outs[0]), lse
