"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are asserted against
(tests/test_kernels.py sweeps shapes/dtypes with assert_allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def lif_encode_ref(x: jax.Array, theta: jax.Array, scale: jax.Array,
                   *, T: int = 15) -> jax.Array:
    """Reference T-tick on/off IF rate encoder -> int8 signed counts."""
    x = x.astype(jnp.float32)
    theta = theta.astype(jnp.float32)
    scale = scale.astype(jnp.float32)
    gate = (jnp.abs(x) >= theta).astype(jnp.float32)
    drive_p = jnp.clip(x / scale, 0.0, 1.0)
    drive_n = jnp.clip(-x / scale, 0.0, 1.0)

    def tick(carry, _):
        up, un, cp, cn = carry
        up = up + drive_p
        un = un + drive_n
        sp = (up >= 1.0).astype(jnp.float32)
        sn = (un >= 1.0).astype(jnp.float32)
        return (up - sp, un - sn, cp + sp, cn + sn), None

    h = jnp.full_like(x, 0.5)
    z = jnp.zeros_like(x)
    (_, _, cp, cn), _ = jax.lax.scan(tick, (h, h, z, z), None, length=T)
    return ((cp - cn) * gate).astype(jnp.int8)


def count_matmul_ref(counts: jax.Array, w: jax.Array, scale: jax.Array,
                     *, T: int = 15, out_dtype=jnp.bfloat16) -> jax.Array:
    """Decode-then-matmul reference: (counts * scale/T) @ w."""
    a = counts.astype(jnp.float32) * (scale.astype(jnp.float32) / T)[None, :]
    y = a @ w.astype(jnp.float32)
    return y.astype(out_dtype)


def paged_decode_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     cl_page: jax.Array, cl_pos: jax.Array, qpos: jax.Array,
                     layer, *, window: int = 0, cap: float = 0.0):
    """Dense single-softmax oracle for the fused paged-decode kernel.

    Same inputs/outputs as ``paged_decode.paged_decode_pallas`` (without
    the wire epilogue; pools stacked and lane-flat ``[U, P_loc, psz,
    Hkv*dh]``, read at unit ``layer``);
    gathers every compacted-list page densely and runs the exact
    masking/softmax math of ``models.common.verify_attention_partial`` —
    one global max, not the kernel's online per-page reduction, so
    agreement is fp-epsilon.
    """
    import math
    B, K1, Hq, dh = q.shape
    _, P_loc, psz, L = k_pool.shape
    Hkv = L // dh
    ppc = cl_page.shape[1]
    valid = cl_page >= 0                                     # [B, ppc]
    safe = jnp.where(valid, cl_page, 0)
    k_s = k_pool[layer, safe].astype(jnp.float32)  # [B,ppc,psz,Hkv*dh]
    v_s = v_pool[layer, safe].astype(jnp.float32)
    k_s = k_s.reshape(B, ppc * psz, Hkv, dh)
    v_s = v_s.reshape(B, ppc * psz, Hkv, dh)
    if Hkv != Hq:
        g = Hq // Hkv
        k_s = jnp.repeat(k_s, g, axis=2)
        v_s = jnp.repeat(v_s, g, axis=2)
    k_pos = (cl_pos[:, :, None] + jnp.arange(psz)).reshape(B, ppc * psz)
    ent_ok = jnp.repeat(valid, psz, axis=1)                  # [B, ppc*psz]
    s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32), k_s)
    s = s / math.sqrt(dh)
    if cap:
        s = cap * jnp.tanh(s / cap)
    posb = qpos[:, :, None, None]                            # [B,K1,1,1]
    mask = k_pos[:, None, None, :] <= posb
    if window:
        mask &= (posb - k_pos[:, None, None, :]) < window
    mask &= ent_ok[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bqhk,bkhd->bqhd", p, v_s)
    o = o / jnp.maximum(l[..., None], 1e-30)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return o, lse


def pack4_ref(wire: jax.Array) -> jax.Array:
    lo = wire[..., 0::2]
    hi = wire[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack4_ref(packed: jax.Array) -> jax.Array:
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)
