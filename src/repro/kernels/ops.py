"""Jit'd public wrappers for the Pallas kernels.

Which path runs is decided by the backend alone.  On a TPU backend every
wrapper compiles its Pallas kernel: there is no interpret mode and no
oracle fallback there.  Off-TPU (the CPU test suite), the kernels run in
interpret mode, which executes the kernel body through the jax
interpreter and is what the tests validate against the ``ref.py``
oracles; ``paged_flash_decode`` runs its ``ref.py`` oracle instead
unless asked for the interpreted kernel (see its docstring).

Ragged shapes are padded up to block multiples here so the kernels can
assume aligned tiles.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .count_matmul import count_matmul_pallas
from .lif_encode import lif_encode_pallas
from .pack4 import pack4_pallas, unpack4_pallas
from .paged_decode import paged_decode_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, mult0: int, mult1: int):
    m, c = x.shape
    pm = (-m) % mult0
    pc = (-c) % mult1
    if pm or pc:
        x = jnp.pad(x, ((0, pm), (0, pc)))
    return x, (m, c)


@partial(jax.jit, static_argnames=("T",))
def lif_encode(x: jax.Array, theta: jax.Array, scale: jax.Array, *,
               T: int = 15) -> jax.Array:
    """Fused T-tick IF rate encoder. x [M,C] -> int8 counts [M,C]."""
    (M, C) = x.shape
    bm = 8 if M < 256 else 256
    bc = 128 if C < 512 else 512
    xp, (m0, c0) = _pad_to(x, bm, bc)
    tp = jnp.pad(theta, (0, xp.shape[1] - C), constant_values=1e9)
    sp = jnp.pad(scale, (0, xp.shape[1] - C), constant_values=1.0)
    out = lif_encode_pallas(xp, tp, sp, T=T, block_m=bm,
                            block_c=bc, interpret=not _on_tpu())
    return out[:m0, :c0]


@partial(jax.jit, static_argnames=("T", "out_dtype"))
def count_matmul(counts: jax.Array, w: jax.Array, scale: jax.Array, *,
                 T: int = 15, out_dtype=jnp.bfloat16) -> jax.Array:
    """int8 counts [M,K] x w [K,N] with fused rate decode."""
    M, K = counts.shape
    _, N = w.shape
    bm = 8 if M < 256 else 256
    bn = 128 if N < 256 else 256
    bk = 128 if K < 512 else 512
    cp, (m0, _) = _pad_to(counts, bm, bk)
    wp, _ = _pad_to(w, bk, bn)
    sp = jnp.pad(scale, (0, cp.shape[1] - K))
    out = count_matmul_pallas(cp, wp, sp, T=T, block_m=bm, block_n=bn,
                              block_k=bk, out_dtype=out_dtype,
                              interpret=not _on_tpu())
    return out[:m0, :N]


@jax.jit
def pack4(wire: jax.Array) -> jax.Array:
    M, C = wire.shape
    bm = 8 if M < 256 else 256
    bc = 256 if C < 1024 else 1024
    xp, (m0, _) = _pad_to(wire, bm, bc)
    out = pack4_pallas(xp, block_m=bm, block_c=bc, interpret=not _on_tpu())
    return out[:m0, : C // 2]


@jax.jit
def unpack4(packed: jax.Array) -> jax.Array:
    M, C2 = packed.shape
    bm = 8 if M < 256 else 256
    bc = 128 if C2 < 512 else 512
    xp, (m0, _) = _pad_to(packed, bm, bc)
    out = unpack4_pallas(xp, block_m=bm, block_c=bc, interpret=not _on_tpu())
    return out[:m0, : C2 * 2]


@partial(jax.jit,
         static_argnames=("window", "cap", "encode_wire", "interpret"))
def paged_flash_decode(q, k_pool, v_pool, cl_page, cl_pos, qpos, layer, *,
                       window: int = 0, cap: float = 0.0,
                       encode_wire: bool = False, interpret: bool = False):
    """Fused page-gather -> flash decode -> LSE partial over one shard.

    q [B,K1,Hq,dh] x unit ``layer`` of this shard's stacked lane-flat
    pool slice [U,P_loc,psz,Hkv*dh], walking the slot's compacted page
    list (cl_page local rows / cl_pos absolute start positions, [B,ppc],
    -1 = none).  Returns ``(o, lse)`` or, with ``encode_wire``, the
    epilogue-quantized ``(wire, scale, lse)`` for the coded cross-shard
    combine.

    Dispatch is by backend.  On TPU the Pallas kernel always runs
    compiled, whatever ``interpret`` says.  Off-TPU the same compacted
    algorithm runs through XLA via the ``ref.py`` oracle (interpret-mode
    Pallas would bury the engine tests in per-program overhead), unless
    ``interpret=True`` asks for the interpreted kernel body: the knob
    the kernel-vs-oracle tests use to validate the Pallas code path.
    """
    if _on_tpu() or interpret:
        return paged_decode_pallas(q, k_pool, v_pool, cl_page, cl_pos,
                                   qpos, layer, window=window, cap=cap,
                                   encode_wire=encode_wire,
                                   interpret=not _on_tpu())
    o, lse = ref.paged_decode_ref(q, k_pool, v_pool, cl_page, cl_pos, qpos,
                                  layer, window=window, cap=cap)
    if not encode_wire:
        return o, lse
    # same per-token absmax int8 contract as the kernel epilogue and
    # core.boundary.quantize_partial
    s = jnp.maximum(jnp.max(jnp.abs(o), axis=-1, keepdims=True),
                    1e-6) / 127.0
    return jnp.round(o / s).astype(jnp.int8), s, lse


__all__ = ["lif_encode", "count_matmul", "pack4", "unpack4",
           "paged_flash_decode", "ref"]
