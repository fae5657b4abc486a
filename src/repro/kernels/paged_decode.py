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
list entry inside a walked block is not fetched: its scores are masked
to -1e30 and its V rows are zero, so it adds nothing to the output.
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

Walk.  The grid has one step per slot, taken in order; a step loops over
the slot's walked blocks: the blocks up to its last mapped entry, at
least one, each ``N`` list entries scored against the query rows as one
``[K1*Hq, N*psz]`` tile under one online-softmax update.  ``N`` comes
from the shapes the kernel sees (``pages_per_block``): about
``BLOCK_TOKENS`` tokens a block, within ``BLOCK_VMEM_BYTES`` for the
K and V rings, never more than the list is long.  The kernel copies its
own pages: the pools stay in HBM (``memory_space=pl.ANY``) and each
mapped entry's ``[psz, Hkv*dh]`` K and V pages are copied into a ring of
``RING_DEPTH`` block buffers in VMEM, one DMA semaphore per buffer.
Blocks are numbered in walk order across slots (the wrapper's ``walk``
table holds each one's list offset), and while block ``g`` is scored the
copies of the next ``RING_DEPTH - 1`` blocks are in flight, the next
slot's first block included.  Only mapped entries are fetched; the rest
of a block's buffer holds stale or uninitialised rows, whose scores are
masked to -1e30 and whose V rows are zeroed before ``p @ V``, so no 0 x
NaN reaches the output.  A slot with no mapped entry walks one block of
zeros: its o is 0.  The unit index, list rows, page positions, query
positions, each slot's first block and the walk table are
scalar-prefetch operands (SMEM).  The online-softmax state (m, l, acc)
lives in VMEM scratch; the outputs are per-slot blocks written once, at
the end of the slot's step.
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

#: tokens of K (and of V) one block aims to walk
BLOCK_TOKENS = 256
#: VMEM for the kernel's K and V block rings
BLOCK_VMEM_BYTES = 4 << 20
#: block buffers in each ring: the block being scored and the blocks whose
#: page copies are in flight behind it
RING_DEPTH = 2


def pages_per_block(ppc: int, psz: int, lanes: int, itemsize: int) -> int:
    """List entries ``N`` one block walks, from the kernel's shapes:
    ``BLOCK_TOKENS`` tokens of pages, no more than fit
    ``BLOCK_VMEM_BYTES`` in ``RING_DEPTH`` buffers each of K and V, no
    more than the list's ``ppc`` entries, at least one."""
    page_bytes = psz * lanes * itemsize
    return max(1, min(BLOCK_TOKENS // psz,
                      BLOCK_VMEM_BYTES // (2 * RING_DEPTH * page_bytes),
                      ppc))


def blocks_walked(fill, n: int) -> int:
    """Blocks the kernel computes, summed over lists holding ``fill``
    mapped entries each (compacted, so the last mapped entry is the
    ``fill``-th), at ``n`` entries a block: ``max(1, ceil(fill / n))``
    each."""
    return int(np.maximum(1, -(-np.asarray(fill) // n)).sum())


def pages_fetched(fill) -> int:
    """Pages of K (and as many of V) the kernel copies, summed over lists
    holding ``fill`` mapped entries each: the mapped entries alone."""
    return int(np.asarray(fill).sum())


def _paged_decode_kernel(layer_ref, rows_ref, clo_ref, qpos_ref, blk0_ref,
                         walk_ref, q_ref, bm_ref, k_hbm, v_hbm, *refs,
                         N: int, K1: int, psz: int, scale: float,
                         window: int, cap: float, encode_wire: bool):
    *out_refs, k_buf, v_buf, sems, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    depth = k_buf.shape[0]
    total = blk0_ref[pl.num_programs(0)]            # blocks in the walk
    R = q_ref.shape[1]                              # K1 * Hq query rows
    Hq = R // K1

    def page_copies(g):
        """Walked block ``g``'s entries: (pool row, K copy, V copy) each,
        into buffer ``g % depth`` of the rings."""
        base, buf = walk_ref[g], g % depth
        for i in range(N):
            r = rows_ref[base + i]
            src = (layer_ref[0], jnp.maximum(r, 0))
            dst = (buf, pl.ds(i * psz, psz))
            yield (r, pltpu.make_async_copy(k_hbm.at[src], k_buf.at[dst],
                                            sems.at[0, buf]),
                   pltpu.make_async_copy(v_hbm.at[src], v_buf.at[dst],
                                         sems.at[1, buf]))

    def fetch(g):
        for r, k_copy, v_copy in page_copies(g):
            @pl.when(r >= 0)
            def _start():
                k_copy.start()
                v_copy.start()

    def arrive(g):
        for r, k_copy, v_copy in page_copies(g):
            @pl.when(r >= 0)
            def _wait():
                k_copy.wait()
                v_copy.wait()

    @pl.when(b == 0)
    def _prologue():
        for g in range(depth - 1):
            @pl.when(g < total)
            def _first():
                fetch(g)

    m_ref[...] = jnp.full(m_ref.shape, -1e30, F32)
    l_ref[...] = jnp.zeros(l_ref.shape, F32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    def block(g, carry):
        @pl.when(g + depth - 1 < total)
        def _ahead():
            fetch(g + depth - 1)

        arrive(g)
        base, buf = walk_ref[g], g % depth
        for i in range(N):                          # unfetched V rows: 0
            @pl.when(rows_ref[base + i] < 0)
            def _zero():
                v_buf[buf, pl.ds(i * psz, psz)] = jnp.zeros(
                    (psz, v_buf.shape[2]), v_buf.dtype)

        q = q_ref[0].astype(F32)                    # [R, Hkv*dh]
        k = k_buf[buf].astype(F32)                  # [N*psz, Hkv*dh]
        v = v_buf[buf].astype(F32)
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
        return carry

    jax.lax.fori_loop(blk0_ref[b], blk0_ref[b + 1], block, 0)

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
                        interpret=False):
    """Fused gather->flash->partial over one pool shard.

    q [B, K1, Hq, dh]; k_pool/v_pool [U, P_loc, psz, Hkv*dh] (this
    shard's lane-flat pool slice, every unit); cl_page [B, ppc] int32
    shard-LOCAL page rows (-1 = no page); cl_pos [B, ppc] int32 absolute
    position of each page's first token; qpos [B, K1] int32 absolute
    per-query positions; layer: int32 scalar, the unit to read;
    ``interpret``: ``pallas_call``'s, a bool or ``pltpu.InterpretParams``.

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
    # lists padded to whole blocks; each slot walks the blocks up to its
    # last mapped entry (block 0 at least), numbered in walk order across
    # slots: slot b's are blk0[b] .. blk0[b+1] - 1, and walk[g] is block
    # g's offset into the flat lists
    pad = ((0, 0), (0, nb * N - ppc))
    rows = jnp.pad(cl_page.astype(jnp.int32), pad, constant_values=-1)
    clo = jnp.pad(cl_pos.astype(jnp.int32), pad, constant_values=-1)
    n_mapped = jnp.max(jnp.where(rows >= 0, jnp.arange(1, nb * N + 1), 0),
                       axis=1)
    n_blk = jnp.maximum(1, -(-n_mapped // N))
    blk0 = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(n_blk).astype(jnp.int32)])
    c = jnp.arange(nb)[None, :]
    walk = jnp.zeros(B * nb, jnp.int32).at[
        jnp.where(c < n_blk[:, None], blk0[:-1, None] + c, B * nb)].set(
            (jnp.arange(B)[:, None] * nb + c) * N, mode="drop")

    def slot_map(b, *_):
        return b, 0, 0

    rows_spec = pl.BlockSpec((1, R, L), slot_map)
    col_spec = pl.BlockSpec((1, R, 1), slot_map)
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    lse_shape = jax.ShapeDtypeStruct((B, R, 1), F32)
    if encode_wire:
        out_shape = (jax.ShapeDtypeStruct((B, R, L), jnp.int8),
                     jax.ShapeDtypeStruct((B, R, 1), F32), lse_shape)
        out_specs = (rows_spec, col_spec, col_spec)
    else:
        out_shape = (jax.ShapeDtypeStruct((B, R, L), F32), lse_shape)
        out_specs = (rows_spec, col_spec)
    ring = pltpu.VMEM((RING_DEPTH, N * psz, L), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B,),
        in_specs=[rows_spec, pl.BlockSpec((R, L), lambda b, *_: (0, 0)),
                  pool_spec, pool_spec],
        out_specs=out_specs,
        scratch_shapes=[ring, ring,
                        pltpu.SemaphoreType.DMA((2, RING_DEPTH)),
                        pltpu.VMEM((R, 1), F32), pltpu.VMEM((R, 1), F32),
                        pltpu.VMEM((R, L), F32)])
    outs = pl.pallas_call(
        functools.partial(_paged_decode_kernel, N=N, K1=K1, psz=psz,
                          scale=1.0 / math.sqrt(dh), window=window, cap=cap,
                          encode_wire=encode_wire),
        grid_spec=grid_spec,
        out_shape=out_shape,
        # the walk's copies run ahead across slots: steps go in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the custom call's name in HLO and in the device trace, whatever
        # jitted function encloses the call
        name="paged_flash_decode",
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.reshape(-1),
      clo.reshape(-1), qpos.reshape(-1).astype(jnp.int32), blk0, walk,
      q_exp, bm, k_pool, v_pool)
    # each row's own kv-head block (the others are exactly zero)
    kv_of = (jnp.arange(Hq) // g)[None, None, :, None, None]
    own_block = lambda x: jnp.take_along_axis(
        x.reshape(B, K1, Hq, Hkv, dh), kv_of, axis=3)[:, :, :, 0]
    lse = outs[-1].reshape(B, K1, Hq)
    if encode_wire:
        wire, s_q, _ = outs
        return own_block(wire), s_q.reshape(B, K1, Hq, 1), lse
    return own_block(outs[0]), lse
